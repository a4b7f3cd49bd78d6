"""Vectorized population evaluation — the paper's core technique, in JAX.

The paper transposes the dataset so each feature is a vector (its Eq. 1→2)
and evaluates each tree's expression as a TensorFlow graph over those
vectors. Here the *whole population* is evaluated by one level-synchronous
sweep over the heap encoding:

    for level d = max_depth .. 0:
        node_val[d] = select(opcode, f(child_vals[d+1]), terminal_vals)

Every step is a fused elementwise select over a [pop, 2**d, data] block —
one static XLA program for any population content. This module is the pure
jnp reference path; kernels/gp_eval.py is the Pallas TPU version of the
same contraction (fused with the fitness reduction), and kernels/ref.py
re-exports these functions as the kernel oracle.

Predictions are computed for EVERY data column, padded or not — dataset
padding (data/loader.pad_rows) is masked one layer up, where the
`weight: f32[D]` vector zeroes padded points out of the fitness
reduction (core/fitness partial_fitness, kernels/ref, kernels/ops, and
the Pallas kernel's w_ref all share that convention), so a padded
dataset scores exactly like the unpadded one.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from typing import NamedTuple

from repro.core import primitives as prim
from repro.core import trees as trees_mod
from repro.core.trees import TreeSpec


@partial(jax.jit, static_argnames=("spec",))
def evaluate_population(op, arg, X, const_table, spec: TreeSpec):
    """Evaluate every tree against every data point.

    op, arg:     int32[P, N]        population in the spec's genome form
    X:           float[F, D]        feature-major data (the paper's Eq. 2 layout)
    const_table: float[C]
    returns      float32[P, D]      predictions

    Dispatches on spec.genome: heap populations run the level sweep
    below; postfix populations run the stack machine
    (`evaluate_population_postfix`). Both apply the same f32 primitives
    to the same operand values in the same order per node, so the two
    forms of one tree produce bitwise-identical predictions.
    """
    if spec.genome == "postfix":
        return evaluate_population_postfix(op, arg, X, const_table, spec)
    P, N = op.shape
    D = X.shape[1]
    max_depth = (N + 1).bit_length() - 2
    X = X.astype(jnp.float32)
    const_table = const_table.astype(jnp.float32)

    vals = None  # child-level buffer [P, 2**(d+1), D]
    for d in range(max_depth, -1, -1):
        lo, w = 2**d - 1, 2**d
        opd = op[:, lo:lo + w, None]  # [P, w, 1]
        argd = arg[:, lo:lo + w]
        feat = X[jnp.clip(argd, 0, X.shape[0] - 1)]  # [P, w, D] gather
        cons = const_table[jnp.clip(argd, 0, const_table.shape[0] - 1)][..., None]
        node = jnp.where(opd == prim.FEATURE, feat, jnp.broadcast_to(cons, (P, w, D)))
        if vals is not None:
            lhs, rhs = vals[:, 0::2], vals[:, 1::2]
            fn = prim.apply_function(opd, lhs, rhs, spec.fn_set)
            node = jnp.where(opd >= 3, fn, node)
        node = jnp.where(opd == prim.EMPTY, 0.0, node)
        vals = node
    return vals[:, 0]  # [P, D]


@partial(jax.jit, static_argnames=("spec",))
def evaluate_population_postfix(op, arg, X, const_table, spec: TreeSpec):
    """Stack-machine evaluation of postfix populations — the jnp
    reference for the Pallas stack kernel (kernels/gp_eval.py).

    One `lax.scan` over all NODES instruction slots carries an operand
    stack f32[P, stack_size, D] (slot 0 = top): terminals shift-push
    their value, unary functions replace the top, binary functions fold
    the top two and shift up; EMPTY slots hold the stack unchanged, so
    rows of different active lengths share the fixed-trip scan. Applies
    the identical f32 primitives (`prim.apply_function`) to the same
    operand values as the heap level sweep — bitwise-equal predictions
    for the two forms of one tree.
    """
    P, N = op.shape
    D = X.shape[1]
    S = spec.stack_size
    X = X.astype(jnp.float32)
    const_table = const_table.astype(jnp.float32)
    ARITY = jnp.asarray(prim.ARITY)

    def step(stack, xs):
        opt, argt = xs  # int32[P]
        feat = X[jnp.clip(argt, 0, X.shape[0] - 1)]  # [P, D]
        cons = const_table[jnp.clip(argt, 0, const_table.shape[0] - 1)][:, None]
        tval = jnp.where((opt == prim.FEATURE)[:, None], feat,
                         jnp.broadcast_to(cons, (P, D)))
        top = stack[:, 0]
        ar = ARITY[opt]
        lhs = jnp.where((ar == 2)[:, None], stack[:, 1], top)
        fnv = prim.apply_function(opt[:, None], lhs, top, spec.fn_set)
        push = jnp.concatenate([tval[:, None], stack[:, :S - 1]], axis=1)
        una = stack.at[:, 0].set(fnv)
        binr = jnp.concatenate(
            [fnv[:, None], stack[:, 2:], jnp.zeros((P, 1, D), jnp.float32)],
            axis=1)
        a = ar[:, None, None]
        new = jnp.where(a == 0, push, jnp.where(a == 1, una, binr))
        new = jnp.where((opt == prim.EMPTY)[:, None, None], stack, new)
        return new, None

    stack0 = jnp.zeros((P, S, D), jnp.float32)
    stack, _ = jax.lax.scan(step, stack0, (op.T, arg.T))
    return stack[:, 0]  # [P, D]; all-EMPTY rows stay 0.0 like the heap path


def evaluate_tree(op_row, arg_row, X, const_table, spec: TreeSpec):
    """Single-tree convenience wrapper (used by tests/examples)."""
    preds = evaluate_population(op_row[None], arg_row[None], X, const_table, spec)
    return preds[0]


# --- population-wide subexpression dedup (tier 1, exact) ---------------------
#
# Crossover copies subtrees verbatim across the population, so the same
# subexpression is re-evaluated over the full data axis many times per
# generation. This layer enumerates every postfix subtree span
# (trees.subtree_spans), canonicalizes each to a packed int32 signature
# (trees.subtree_signatures), dedups across the whole [P, N] population
# with one on-device sort, evaluates ONE representative per distinct
# subexpression with a level loop (operands always have strictly shorter
# spans, so length IS a topological level), and gathers each tree's root
# value back. Every unique node applies the identical
# `prim.apply_function` select chain to the identical operand bits as
# the stack interpreter, so predictions — and fitness — are BITWISE
# identical to dedup-off. Everything is fixed-shape: `cap` bounds the
# unique table, slot `cap - 1` is reserved for the all-EMPTY row root,
# and `n_unique > cap - 1` flips a single `lax.cond` onto the plain
# interpreter (still bitwise; only the plan build is wasted).


class DedupPlan(NamedTuple):
    """Fixed-shape per-generation dedup schedule (all on device).

    uop/uarg/ulen: int32[cap]  opcode / terminal arg / span length of the
                               representative node per unique slot (EMPTY/0
                               beyond ``n_unique`` and in the reserved
                               last slot)
    ulhs/urhs:     int32[cap]  unique-slot ids of the operands (binary:
                               left/right; unary: both the operand;
                               terminals: 0, never read)
    root:          int32[P]    unique-slot id of each tree's root value
                               (reserved slot ``cap - 1`` for all-EMPTY
                               rows, which stays 0.0 like the interpreter)
    n_unique:      int32[]     distinct active subexpressions found
    total:         int32[]     active subtree instances in the population
    overflow:      bool[]      n_unique exceeds the usable ``cap - 1``
    """

    uop: jnp.ndarray
    uarg: jnp.ndarray
    ulhs: jnp.ndarray
    urhs: jnp.ndarray
    ulen: jnp.ndarray
    root: jnp.ndarray
    n_unique: jnp.ndarray
    total: jnp.ndarray
    overflow: jnp.ndarray


def resolve_dedup_cap(dedup_cap: int, pop: int, num_nodes: int) -> int:
    """Static unique-table capacity. Explicit ``dedup_cap > 0`` wins;
    otherwise ``max(64, pop)`` — dedup then engages exactly when the
    population holds fewer distinct subexpressions than trees, i.e. when
    it beats evaluating every tree. Clamped to the ``P*N + 1`` slots any
    population can occupy (+1 for the reserved all-EMPTY slot)."""
    cap = dedup_cap if dedup_cap > 0 else max(64, pop)
    return int(min(cap, pop * num_nodes + 1))


def _sorted_signatures(sigf):
    """(order int32[T], is_new bool[T]) for the rows of sigf int32[T, W]:
    `order` lists the rows lexicographically with ties in position order,
    and `is_new[k]` marks the first of each run of equal rows in that
    order. The order is built least-significant word first, one stable
    single-key sort per word inside a loop — the permutation of a W-key
    `lax.sort` with position as the last key, but a loop of one small
    sort compiles in seconds for the TPU, where the W-key sort at depth
    5 (W = 21) takes over ten minutes to compile."""
    T, W = sigf.shape

    def word(i, order):
        key = sigf[order, W - 1 - i]
        return jax.lax.sort((key, order), num_keys=1, is_stable=True)[1]

    order = jax.lax.fori_loop(0, W, word, jnp.arange(T, dtype=jnp.int32))
    srt = sigf[order]
    is_new = jnp.concatenate([jnp.ones((1,), bool),
                              (srt[1:] != srt[:-1]).any(axis=1)])
    return order, is_new


@partial(jax.jit, static_argnames=("spec", "cap"))
@jax.named_scope("gp.dedup_plan")
def build_dedup_plan(op, arg, spec: TreeSpec, cap: int) -> DedupPlan:
    """Canonicalize + sort + unique the population's subtree spans into a
    fixed-shape evaluation schedule. A lexicographic sort of the
    signature words (position as final tiebreak) puts equal
    subexpressions adjacent; segment heads become unique slots."""
    P, N = op.shape
    T = P * N
    sig = trees_mod.subtree_signatures(op, arg, spec)  # [P, N, W]
    W = sig.shape[-1]
    sigf = sig.reshape(T, W)
    active = (op != prim.EMPTY).reshape(T)
    start = trees_mod.subtree_spans(op)
    length = jnp.arange(N, dtype=jnp.int32)[None, :] - start + 1
    lhs_i = trees_mod.postfix_lhs_index(op)

    s_pos, is_new = _sorted_signatures(sigf)
    new_u = is_new & active[s_pos]  # all-zero (inactive) sigs sort first
    uid_s = jnp.cumsum(new_u.astype(jnp.int32)) - 1
    n_unique = jnp.sum(new_u.astype(jnp.int32))
    total = jnp.sum(active.astype(jnp.int32))
    # flat position -> unique id (-1 on inactive positions, never read)
    inv = jnp.zeros((T,), jnp.int32).at[s_pos].set(uid_s)
    # unique id -> representative flat position (first occurrence)
    rep = jnp.zeros((cap,), jnp.int32).at[
        jnp.where(new_u, uid_s, cap)].set(s_pos, mode="drop")

    slot = jnp.arange(cap, dtype=jnp.int32)
    valid = slot < n_unique
    rp, ri = rep // N, rep % N
    ARITY = jnp.asarray(prim.ARITY)
    uop = jnp.where(valid, op[rp, ri], prim.EMPTY).astype(jnp.int32)
    uar = ARITY[uop]
    uarg = jnp.where(valid & (uar == 0), arg[rp, ri], 0).astype(jnp.int32)
    ulen = jnp.where(valid, length[rp, ri], 0).astype(jnp.int32)

    def inv_at(flat_pos):
        return inv[jnp.clip(flat_pos, 0, T - 1)]

    # operands: right operand of any function ends at i-1; the left
    # operand of a binary ends where the right one starts, minus one
    urhs = jnp.where(uar >= 1, inv_at(rp * N + ri - 1), 0)
    ulhs = jnp.where(uar == 2, inv_at(rp * N + lhs_i[rp, ri]), urhs)

    row_len = jnp.sum((op != prim.EMPTY).astype(jnp.int32), axis=1)
    root_pos = jnp.arange(P, dtype=jnp.int32) * N + jnp.maximum(row_len - 1, 0)
    root = jnp.where(row_len > 0, inv[root_pos], cap - 1).astype(jnp.int32)
    overflow = n_unique > cap - 1
    return DedupPlan(uop, uarg, ulhs, urhs, ulen, root,
                     n_unique, total, overflow)


@partial(jax.jit, static_argnames=("spec",))
def evaluate_unique_subtrees(plan: DedupPlan, X, const_table, spec: TreeSpec):
    """f32[cap, D] value of every unique subexpression (0.0 on unused
    slots). Level loop over span length: operands of a length-l node
    have length < l, so each sweep's inputs are already final. Terminal
    lookups and the `prim.apply_function` select chain are the exact
    operations of `evaluate_population_postfix` — bitwise-equal values.
    """
    X = X.astype(jnp.float32)
    const_table = const_table.astype(jnp.float32)
    feat = X[jnp.clip(plan.uarg, 0, X.shape[0] - 1)]  # [cap, D]
    cons = const_table[jnp.clip(plan.uarg, 0, const_table.shape[0] - 1)][:, None]
    tval = jnp.where((plan.uop == prim.FEATURE)[:, None], feat,
                     jnp.broadcast_to(cons, feat.shape))
    vals = jnp.where((plan.ulen == 1)[:, None], tval, 0.0)

    def level(lvl, vals):
        lhs = vals[plan.ulhs]
        rhs = vals[plan.urhs]
        fnv = prim.apply_function(plan.uop[:, None], lhs, rhs, spec.fn_set)
        return jnp.where((plan.ulen == lvl)[:, None], fnv, vals)

    return jax.lax.fori_loop(2, jnp.max(plan.ulen) + 1, level, vals)


@partial(jax.jit, static_argnames=("spec", "cap"))
def evaluate_population_dedup(op, arg, X, const_table, spec: TreeSpec,
                              cap: int):
    """Drop-in for `evaluate_population_postfix` with cross-population
    subexpression dedup: evaluate each distinct subtree once, gather
    roots. Bitwise-identical predictions; overflow (> cap - 1 distinct
    subexpressions) falls back to the plain interpreter via `lax.cond`.
    """
    plan = build_dedup_plan(op, arg, spec, cap)
    return jax.lax.cond(
        plan.overflow,
        lambda: evaluate_population_postfix(op, arg, X, const_table, spec),
        lambda: evaluate_unique_subtrees(plan, X, const_table, spec)[plan.root])


def make_postfix_evaluator(op, arg, const_table, spec: TreeSpec,
                           dedup: str = "off", dedup_cap: int = 0):
    """Closure ``X -> f32[P, D]`` with the dedup plan built ONCE, so
    tiled/streamed fitness paths (kernels/ref.py) reuse one plan across
    every data tile. Any ``dedup != "off"`` engages the exact tier here;
    the semantic tier (engine) adds cross-generation cache keys on top.
    Non-postfix genomes always use the plain evaluator (dedup is a
    postfix-only optimization; heap trees share the front door)."""
    if dedup == "off" or spec.genome != "postfix":
        return lambda X: evaluate_population(op, arg, X, const_table, spec)
    cap = resolve_dedup_cap(dedup_cap, *op.shape)
    plan = build_dedup_plan(op, arg, spec, cap)

    def ev(X):
        return jax.lax.cond(
            plan.overflow,
            lambda: evaluate_population_postfix(op, arg, X, const_table, spec),
            lambda: evaluate_unique_subtrees(plan, X, const_table, spec)[
                plan.root])

    return ev


@partial(jax.jit, static_argnames=("spec", "cap"))
def dedup_stats(op, arg, spec: TreeSpec, cap: int):
    """(unique_subtrees, subtree_evals_saved) int32 scalars for the
    telemetry counter stream — the signature sort without the schedule
    gathers. ``saved`` is 0 when the unique table would overflow (the
    eval path then ran the plain interpreter)."""
    P, N = op.shape
    T = P * N
    sig = trees_mod.subtree_signatures(op, arg, spec).reshape(T, -1)
    active = (op != prim.EMPTY).reshape(T)
    order, is_new = _sorted_signatures(sig)
    n_unique = jnp.sum((is_new & active[order]).astype(jnp.int32))
    total = jnp.sum(active.astype(jnp.int32))
    saved = jnp.where(n_unique > cap - 1, 0, total - n_unique)
    return n_unique, saved
