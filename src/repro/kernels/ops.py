"""Jitted public wrappers for the GP eval+fitness kernel.

Handles padding (population to pop_tile, data to data_tile with a zero
weight mask) and sizes the data tile to a VMEM budget. Two surfaces:

    fitness(...)  f32[P] finalized fitness — phase 1 moments accumulated
                  across the Pallas data grid, phase 2 reduce on the
                  result (a [P, M] @ tiny elementwise epilogue)
    moments(...)  f32[P, M] phase-1 moments only — what a mesh step
                  `psum`s across the data axis before finalizing

`impl="jnp"` falls through to the oracle so callers (engine, benchmarks)
can flip implementations with one flag.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.core import eval as _eval
from repro.core.fitness import FitnessSpec
from repro.core.trees import TreeSpec
from repro.kernels import ref as _ref
from repro.kernels.gp_eval import (TABLE_PARTS, bank_slabs,
                                   eval_fitness_pallas_from_preds,
                                   eval_fitness_pallas_from_subtrees,
                                   eval_fitness_pallas_postfix,
                                   eval_fitness_pallas_tree)

_VMEM_BUDGET = 12 * 2**20  # bytes; leave headroom under ~16 MB/core


def _moment_tile(n_terms: int, n_nodes: int, pop_tile: int,
                 data_tile: int) -> int:
    """Rows each partial-moment merge of the tree fitness covers. This
    is the tree fitness's rounding contract: the moments of a tree are
    merged over consecutive blocks of this many rows, in data order, so
    a fitness keeps its bits from release to release. The rule is
    `data_tile` halved, down to 128 rows, while 4·(4·K·Db +
    4·Pb·(N+1)·Db + Pb·N·K) bytes exceed 12 MiB (K = n_terms = features
    + constants, N = n_nodes): 1,024 rows at KAT-7's 9 features, 256 at
    LIGO's 1,373."""
    Db = data_tile
    while Db > 128 and 4 * (TABLE_PARTS * n_terms * Db
                            + 4 * pop_tile * (n_nodes + 1) * Db
                            + pop_tile * n_nodes * n_terms) > _VMEM_BUDGET:
        Db //= 2
    return Db


def _tree_vmem(n_features: int, n_consts: int, max_depth: int,
               pop_tile: int, data_tile: int) -> int:
    """VMEM bytes of the tree kernel, in f32 slabs of data_tile values:
    the bank (`gp_eval.bank_slabs`), Pb root slabs, y and w
    double-buffered. The population adds nothing: each block carries
    only its own pop tile's moments."""
    slabs = bank_slabs(n_features, n_consts, max_depth) + pop_tile + 4
    return 4 * slabs * data_tile


def pick_tiles(n_features: int, n_consts: int, max_depth: int, data: int,
               pop_tile: int = 8, data_tile: int = 1024):
    """(pop_tile, data_tile, moment_tile) of the tree kernel.

    moment_tile is `_moment_tile`'s, from the callers' `data_tile` down.
    The kernel's data_tile is the fewest data tiles the VMEM budget
    allows, balanced over `data` rows padded to whole moment tiles, each
    a multiple of 1,024 rows and of the moment tile: 2 tiles of 45,056
    rows for KAT-7's 90,000 × 9. It is chosen from the shapes alone.

    Raises ValueError when not even one such tile fits: the bank holds
    every feature, so that is past 3,042 features at depth 5 with 8
    constants, whatever the population.
    """
    n_nodes = 2 ** (max_depth + 1) - 1
    moment_tile = _moment_tile(n_features + n_consts, n_nodes, pop_tile,
                               data_tile)
    unit = math.lcm(1024, moment_tile)
    vmem = partial(_tree_vmem, n_features, n_consts, max_depth, pop_tile)
    most = (_VMEM_BUDGET - vmem(0)) // (vmem(1) - vmem(0)) // unit * unit
    if most < unit:
        raise ValueError(
            f"{n_features} features do not fit the tree kernel's VMEM "
            f"budget at {unit:,} rows; evaluate with the jnp backend "
            f"(backend='jnp')")
    rows = -(-data // moment_tile) * moment_tile
    n = -(-rows // most)
    return pop_tile, -(-rows // (n * unit)) * unit, moment_tile


def pick_tiles_postfix(n_terms: int, stack_size: int, pop: int, data: int,
                       pop_tile: int = 8, data_tile: int = 1024,
                       dedup_rows: int = 0):
    """Tile pick for the postfix stack kernel. The carried state is S
    [Pb, Db] stack slots (S = max_depth + 1), ~S/N of a level sweep's
    node-resident buffers, so the data tile can grow under the same VMEM
    budget — fewer, larger grid blocks amortize the per-instruction loop.

    `dedup_rows` (the dedup unique-table cap) charges the budget for the
    f32[U, Db] unique-subtree block the in-VMEM dedup gather kernel
    keeps resident. `_moments_padded` never lets this change the picked
    tile — the dedup-off pick (``dedup_rows=0``) anchors the merge order
    for the bitwise contract; the charged pick is the VMEM re-check that
    decides whether the in-VMEM gather kernel is safe or the gather must
    spill to HBM (`eval_fitness_pallas_from_preds`)."""
    Db = data_tile

    def vmem(Db):
        return _postfix_vmem(n_terms, stack_size, pop_tile, Db, dedup_rows)

    while Db * 2 <= data and vmem(Db * 2) <= _VMEM_BUDGET and Db < 2048:
        Db *= 2
    while Db > 128 and vmem(Db) > _VMEM_BUDGET:
        Db //= 2
    return pop_tile, Db


def _postfix_vmem(n_terms: int, stack_size: int, pop_tile: int, Db: int,
                  dedup_rows: int = 0) -> int:
    """VMEM bytes per block of the postfix stack kernel: the double-
    buffered split terminal table (16·K·Db) + the stack + the handful of
    [Pb, Db] per-instruction temps + the double-buffered dedup
    unique-subtree block when that kernel is live. Mosaic reports
    21.8 MiB for F=1,373 at Db=1024 (modelled 22.0 MiB)."""
    return 4 * (TABLE_PARTS * n_terms * Db + pop_tile * (stack_size + 8) * Db
                + 2 * dedup_rows * Db)


def _moments_padded(op, arg, X, y, const_table, tree_spec: TreeSpec,
                    fit_spec: FitnessSpec, weight, data_tile: int, pop_tile: int,
                    interpret: bool | None, dedup: str = "off", dedup_cap: int = 0):
    """Pad to tile multiples and run the fused kernel: f32[P, M] moments.
    Padded data points carry weight 0.0, so every moment they touch is an
    exact 0.0 and the grid accumulation stays padding-invariant.

    Any ``dedup != "off"`` engages the exact-tier subexpression dedup
    for postfix genomes: build the population's unique-subtree schedule
    (core/eval.build_dedup_plan), evaluate each distinct subtree once,
    and run a gather+moments kernel over the f32[cap, D] unique table.
    The tile geometry is ALWAYS the plain (dedup_rows=0) pick — the
    (pop, data) grid and merge order the dedup-off kernel uses — so
    moments stay bitwise identical to dedup-off. When the uniq scratch
    fits VMEM at that pick (re-checked by charging `dedup_rows=cap` to
    the same budget model) the in-VMEM gather kernel runs; otherwise the
    gather happens at the XLA level (HBM `uniq[root]`) and the spill
    kernel streams plain-geometry blocks. Unique-table overflow
    `lax.cond`s back onto the plain kernel."""
    P, N = op.shape
    F, D = X.shape
    K = F + const_table.shape[0]  # rows of the kernels' terminal table
    if tree_spec.genome == "postfix":
        cap = (_eval.resolve_dedup_cap(dedup_cap, P, N)
               if dedup != "off" else 0)
        pop_tile, data_tile = pick_tiles_postfix(
            K, tree_spec.stack_size, P, D, pop_tile, data_tile)
        # Would the f32[cap, Db] unique table still fit VMEM at this
        # exact pick?  If not, spill the gather to HBM instead of
        # shrinking the tile (which would change the merge order).
        dedup_fits = (cap == 0 or _postfix_vmem(
            K, tree_spec.stack_size, pop_tile, data_tile,
            dedup_rows=cap) <= _VMEM_BUDGET)
    else:
        pop_tile, data_tile, moment_tile = pick_tiles(
            F, const_table.shape[0], tree_spec.max_depth, D, pop_tile,
            data_tile)

    pad_p = (-P) % pop_tile
    pad_d = (-D) % data_tile
    weight = (jnp.ones((D,), jnp.float32) if weight is None
              else weight.astype(jnp.float32))
    if pad_p:
        op = jnp.pad(op, ((0, pad_p), (0, 0)))
        arg = jnp.pad(arg, ((0, pad_p), (0, 0)))
    if pad_d:
        X = jnp.pad(X, ((0, 0), (0, pad_d)))
        y = jnp.pad(y, (0, pad_d))
        weight = jnp.pad(weight, (0, pad_d))

    fn_codes = tuple(int(c) for c in tree_spec.fn_set.opcodes)
    if tree_spec.genome == "postfix":
        # Sort rows by active length so each pop tile's fori trip count is
        # its own max length (short-program tiles finish early) — this
        # sorting is where most of the postfix speedup lives. Moments are
        # per-row, so sort → eval → unsort is exact; padded rows (len 0)
        # sort to the front and are sliced off after the unsort.
        lens = (op != 0).sum(-1).astype(jnp.int32)
        order = jnp.argsort(lens)
        op_s, arg_s = op[order], arg[order]

        def _plain():
            out = eval_fitness_pallas_postfix(
                op_s, arg_s, lens[order], X, y, weight, const_table,
                stack_size=tree_spec.stack_size, kernel=fit_spec.kernel,
                n_classes=fit_spec.n_classes, precision=fit_spec.precision,
                pop_tile=pop_tile, data_tile=data_tile,
                interpret=interpret, fn_codes=fn_codes)
            return out[jnp.argsort(order)]

        if dedup != "off":
            plan = _eval.build_dedup_plan(op, arg, tree_spec, cap)

            def _dedup():
                uniq = _eval.evaluate_unique_subtrees(plan, X, const_table,
                                                      tree_spec)
                if dedup_fits:
                    return eval_fitness_pallas_from_subtrees(
                        plan.root, uniq, y, weight, kernel=fit_spec.kernel,
                        n_classes=fit_spec.n_classes,
                        precision=fit_spec.precision, pop_tile=pop_tile,
                        data_tile=data_tile, interpret=interpret)
                preds = jnp.take(uniq, jnp.clip(plan.root, 0, cap - 1),
                                 axis=0)
                return eval_fitness_pallas_from_preds(
                    preds, y, weight, kernel=fit_spec.kernel,
                    n_classes=fit_spec.n_classes,
                    precision=fit_spec.precision, pop_tile=pop_tile,
                    data_tile=data_tile, interpret=interpret)

            return jax.lax.cond(plan.overflow, _plain, _dedup)[:P]
        return _plain()[:P]
    out = eval_fitness_pallas_tree(
        op, arg, X, y, weight, const_table, kernel=fit_spec.kernel,
        n_classes=fit_spec.n_classes, precision=fit_spec.precision,
        pop_tile=pop_tile, data_tile=data_tile, moment_tile=moment_tile,
        n_chunks=-(-D // moment_tile), interpret=interpret,
        fn_codes=fn_codes)
    return out[:P]


@partial(jax.jit, static_argnames=("tree_spec", "fit_spec", "data_tile", "pop_tile",
                                   "interpret", "dedup", "dedup_cap"))
def moments(op, arg, X, y, const_table, tree_spec: TreeSpec, fit_spec: FitnessSpec,
            *, weight=None, data_tile: int = 1024, pop_tile: int = 8,
            interpret: bool | None = None,
            dedup: str = "off", dedup_cap: int = 0):
    """f32[P, M] phase-1 moments of every tree against (X:[F,D], y:[D]),
    fused with evaluation on the Pallas path. Sum with the other shards'
    moments (e.g. `lax.psum` on the mesh data axis), then finalize with
    `get_kernel(fit_spec.kernel).reduce_moments`."""
    from repro.core.fitness import get_kernel

    if get_kernel(fit_spec.kernel).moments is None:
        raise ValueError(f"fitness kernel {fit_spec.kernel!r} defines no moment "
                         f"pass; it cannot accumulate across data tiles/shards")
    return _moments_padded(op, arg, X, y, const_table, tree_spec, fit_spec,
                           weight, data_tile, pop_tile, interpret,
                           dedup=dedup, dedup_cap=dedup_cap)


@partial(jax.jit, static_argnames=("tree_spec", "fit_spec", "data_tile", "pop_tile",
                                   "impl", "interpret", "dedup",
                                   "dedup_cap"))
def stream_moments(acc, op, arg, X, y, const_table, tree_spec: TreeSpec,
                   fit_spec: FitnessSpec, *, weight=None, data_tile: int = 1024,
                   pop_tile: int = 8,
                   impl: str = "pallas", interpret: bool | None = None,
                   dedup: str = "off", dedup_cap: int = 0):
    """One streaming fold step, ONE dispatch: phase-1 moments of this
    data chunk merged into the running f32[P, M] accumulator `acc` via
    the kernel's merge (elementwise sum, or `combine_moments`). Seed the
    fold with zeros — the merge identity by contract — and finalize the
    final accumulator once with `reduce_moments`. Every chunk of a
    `data/loader.ChunkedDataset` has the same fixed shape, so the whole
    stream re-enters this one compiled program."""
    from repro.core.fitness import get_kernel

    kern = get_kernel(fit_spec.kernel)
    if kern.moments is None:
        raise ValueError(f"fitness kernel {fit_spec.kernel!r} defines no moment "
                         f"pass; it cannot accumulate across data chunks")
    if impl == "jnp":
        m = _ref.moments_ref_tiled(op, arg, X, y, const_table, tree_spec,
                                   fit_spec, weight=weight, dedup=dedup,
                                   dedup_cap=dedup_cap)
    else:
        m = _moments_padded(op, arg, X, y, const_table, tree_spec, fit_spec,
                            weight, data_tile, pop_tile, interpret,
                            dedup=dedup, dedup_cap=dedup_cap)
    return kern.merge_moments(acc, m, fit_spec)


@partial(jax.jit, static_argnames=("tree_spec", "fit_spec", "data_tile", "pop_tile",
                                   "impl", "interpret", "dedup",
                                   "dedup_cap"))
def fitness(op, arg, X, y, const_table, tree_spec: TreeSpec, fit_spec: FitnessSpec,
            *, weight=None, data_tile: int = 1024, pop_tile: int = 8,
            impl: str = "pallas",
            interpret: bool | None = None,
            dedup: str = "off", dedup_cap: int = 0):
    """f32[P] fitness (minimize) of every tree against (X:[F,D], y:[D]).

    `weight` is an optional f32[D] mask (0.0 on dataset-padding points,
    e.g. from data/loader.pad_rows); it composes with the kernel's own
    data-tile padding mask so padded datasets score exactly. Every
    registered kernel with a moment pass — decomposable one-moment
    objectives and two-pass statistics (pearson, r2) alike — runs the
    fused Pallas grid; only legacy kernels registered without moments
    fall back to the un-tiled reference path."""
    from repro.core.fitness import get_kernel

    kern = get_kernel(fit_spec.kernel)
    if impl == "jnp" or kern.moments is None:
        return _ref.fitness_ref(op, arg, X, y, const_table, tree_spec, fit_spec,
                                weight=weight, dedup=dedup, dedup_cap=dedup_cap)
    m = _moments_padded(op, arg, X, y, const_table, tree_spec, fit_spec,
                        weight, data_tile, pop_tile, interpret,
                        dedup=dedup, dedup_cap=dedup_cap)
    return kern.reduce_moments(m, fit_spec)
