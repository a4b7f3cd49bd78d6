"""Population-wide subexpression dedup (docs/genomes.md).

Tier 1 (exact): span-math edge cases, signature injectivity, plan
reconstruction pinned BITWISE against the plain stack interpreter —
across eval impl × fitness kernel × island layout, through full evolve
trajectories, the tenant batch and the overflow fallback. Tier 2
(semantic): the probe-fingerprint elite-cache gate, tolerance-pinned.
The 8-device mesh trajectory pin lives in the tier2 subprocess test.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core import FitnessSpec, GPConfig, evolve_step, init_state
from repro.core import engine as eng
from repro.core import eval as ce
from repro.core import primitives as prim
from repro.core import trees
from repro.core.islands import IslandConfig
from repro.core.trees import TreeSpec, generate_population, heap_to_postfix
from repro.kernels import ops as kops


def _pops(seed, pop=33, depth=5, nf=4):
    spec_t = TreeSpec(max_depth=depth, n_features=nf, n_consts=8)
    spec_p = dataclasses.replace(spec_t, genome="postfix")
    op_t, arg_t = generate_population(jax.random.PRNGKey(seed), pop, spec_t)
    op_p, arg_p = heap_to_postfix(op_t, arg_t)
    return spec_t, spec_p, (op_t, arg_t), (op_p, arg_p)


def _data(seed, nf, D):
    r = np.random.RandomState(seed)
    X = jnp.asarray(r.randn(nf, D).astype(np.float32))
    y = jnp.asarray((r.rand(D) * 3).astype(np.float32))
    return X, y


def _dup_heavy(seed, pop, depth, nf=4):
    """A duplicate-heavy postfix population: few distinct genomes, many
    copies — the regime the dedup tier exists for."""
    spec_t, spec_p, _, (op, arg) = _pops(seed, pop=max(4, pop // 8),
                                         depth=depth, nf=nf)
    reps = -(-pop // op.shape[0])
    op = jnp.tile(op, (reps, 1))[:pop]
    arg = jnp.tile(arg, (reps, 1))[:pop]
    return spec_p, op, arg


# --- span math edge cases (trees.subtree_spans & friends) --------------------


def test_spans_single_terminal_row():
    """One active CONST: its span starts at 0 and the running stack depth
    is 1 after it; EMPTY padding contributes +1 per slot by contract."""
    N = 7
    op = np.zeros((1, N), np.int32)
    op[0, 0] = prim.CONST
    S = np.asarray(trees.postfix_stack_depths(op))
    np.testing.assert_array_equal(S[0], np.arange(1, N + 1))
    start = np.asarray(trees.subtree_spans(op))
    assert start[0, 0] == 0


def test_spans_full_length_row():
    """A caterpillar chain filling every slot of N=7: t t + t + t + .
    Binary spans telescope back to 0; each lhs index is the previous
    chain result; the row finishes with stack depth exactly 1."""
    add = prim.opcode_of("add")
    op = np.asarray([[prim.CONST, prim.CONST, add, prim.FEATURE, add,
                      prim.FEATURE, add]], np.int32)
    S = np.asarray(trees.postfix_stack_depths(op))
    np.testing.assert_array_equal(S[0], [1, 2, 1, 2, 1, 2, 1])
    start = np.asarray(trees.subtree_spans(op))
    np.testing.assert_array_equal(start[0], [0, 1, 0, 3, 0, 5, 0])
    lhs = np.asarray(trees.postfix_lhs_index(op))
    assert lhs[0, 2] == 0 and lhs[0, 4] == 2 and lhs[0, 6] == 4


def test_spans_all_padding_row():
    """All-EMPTY rows must stay well-defined (they exist in real
    populations: the tenant batch's empty slots): every EMPTY bumps the
    depth, so each position's 'span' is just itself."""
    N = 15
    op = np.zeros((3, N), np.int32)
    S = np.asarray(trees.postfix_stack_depths(op))
    np.testing.assert_array_equal(S, np.tile(np.arange(1, N + 1), (3, 1)))
    start = np.asarray(trees.subtree_spans(op))
    np.testing.assert_array_equal(start, np.tile(np.arange(N), (3, 1)))
    lhs = np.asarray(trees.postfix_lhs_index(op))
    assert (lhs >= -1).all()


# --- signature canonicalization ----------------------------------------------


def _brute_tokens(op, arg, K):
    """Reference canonical form: the token tuple of the subexpression
    ending at each active position (what the packed signature encodes)."""
    op, arg = np.asarray(op), np.asarray(arg)
    start = np.asarray(trees.subtree_spans(op))
    out = {}
    for p in range(op.shape[0]):
        for i in range(op.shape[1]):
            if op[p, i] == prim.EMPTY:
                continue
            toks = []
            for t in range(start[p, i], i + 1):
                o = int(op[p, t])
                a = int(np.clip(arg[p, t], 0, K - 1)) if prim.ARITY[o] == 0 else 0
                toks.append(1 + o * K + a)
            out[(p, i)] = tuple(toks)
    return out


def test_signatures_injective_on_population():
    """Equal packed signature ⟺ equal canonical token stream, checked
    against a brute-force per-span extraction on a real population."""
    _, spec_p, _, (op, arg) = _pops(23, pop=24, depth=4)
    sig = np.asarray(trees.subtree_signatures(op, arg, spec_p))
    K = max(spec_p.n_features, len(spec_p.const_table()), 1)
    toks = _brute_tokens(op, arg, K)
    by_sig, by_tok = {}, {}
    for (p, i), t in toks.items():
        by_sig.setdefault(tuple(sig[p, i]), set()).add(t)
        by_tok.setdefault(t, set()).add(tuple(sig[p, i]))
    assert all(len(v) == 1 for v in by_sig.values()), "signature collision"
    assert all(len(v) == 1 for v in by_tok.values()), "signature instability"


def test_signatures_inactive_positions_are_zero():
    _, spec_p, _, (op, arg) = _pops(29, pop=8, depth=3)
    sig = np.asarray(trees.subtree_signatures(op, arg, spec_p))
    inactive = np.asarray(op) == prim.EMPTY
    assert (sig[inactive] == 0).all()
    # ...and no ACTIVE subexpression packs to all-zero (word 0 carries a
    # token code >= 1), so padding can never alias a real subtree
    assert (sig[~inactive] != 0).any(axis=-1).all()


def test_signature_geometry_rejects_overwide_codes():
    with pytest.raises(ValueError):
        trees.signature_geometry(
            TreeSpec(max_depth=3, n_features=1 << 28, genome="postfix"), 15)


# --- plan + unique-subtree evaluation: bitwise reconstruction ----------------


def test_dedup_reconstruction_bitwise():
    spec_p, op, arg = _dup_heavy(3, pop=48, depth=5)
    X, _ = _data(3, 4, 200)
    ct = spec_p.const_table()
    base = np.asarray(ce.evaluate_population_postfix(op, arg, X, ct, spec_p))
    cap = op.shape[0] * op.shape[1] + 1  # roomy: the dedup path, not fallback
    out = np.asarray(ce.evaluate_population_dedup(op, arg, X, ct, spec_p, cap))
    np.testing.assert_array_equal(base, out)
    plan = ce.build_dedup_plan(op, arg, spec_p, cap)
    assert not bool(plan.overflow)
    assert int(plan.n_unique) < int(plan.total)  # duplicates actually deduped


def test_dedup_overflow_falls_back_bitwise():
    _, spec_p, _, (op, arg) = _pops(31, pop=40, depth=5)
    X, _ = _data(31, 4, 128)
    ct = spec_p.const_table()
    plan = ce.build_dedup_plan(op, arg, spec_p, 8)
    assert bool(plan.overflow)
    base = np.asarray(ce.evaluate_population_postfix(op, arg, X, ct, spec_p))
    out = np.asarray(ce.evaluate_population_dedup(op, arg, X, ct, spec_p, 8))
    np.testing.assert_array_equal(base, out)


def test_dedup_all_empty_rows_evaluate_to_zero():
    spec_p = TreeSpec(max_depth=4, n_features=3, n_consts=8, genome="postfix")
    N = spec_p.num_nodes
    op = jnp.zeros((5, N), jnp.int32)
    arg = jnp.zeros((5, N), jnp.int32)
    X, _ = _data(1, 3, 64)
    out = np.asarray(ce.evaluate_population_dedup(
        op, arg, X, spec_p.const_table(), spec_p, 64))
    np.testing.assert_array_equal(out, np.zeros((5, 64), np.float32))


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), depth=st.integers(1, 5),
       pop=st.sampled_from([1, 9, 40]), cap=st.sampled_from([0, 8, 4096]))
def test_dedup_scatter_reconstruction_property(seed, depth, pop, cap):
    """For ANY population/cap: scatter-back of the unique-subtree table
    (or the overflow fallback) == the plain stack interpreter, bitwise."""
    _, spec_p, _, (op, arg) = _pops(seed % 10_000, pop=pop, depth=depth)
    X, _ = _data(seed % 97, 4, 96)
    ct = spec_p.const_table()
    cap = ce.resolve_dedup_cap(cap, pop, spec_p.num_nodes)
    base = np.asarray(ce.evaluate_population_postfix(op, arg, X, ct, spec_p))
    out = np.asarray(ce.evaluate_population_dedup(op, arg, X, ct, spec_p, cap))
    np.testing.assert_array_equal(base, out)


def test_sorted_signatures_is_lexicographic_with_position_ties():
    """The looped least-significant-word sort must give exactly the order
    of a lexicographic sort with position as the final key, and flag the
    head of every run of equal rows."""
    r = np.random.RandomState(0)
    sig = r.randint(0, 3, size=(257, 5)).astype(np.int32)  # many duplicates
    order, is_new = ce._sorted_signatures(jnp.asarray(sig))
    want = np.lexsort(tuple(sig[:, k] for k in range(4, -1, -1)))
    np.testing.assert_array_equal(np.asarray(order), want)
    srt = sig[want]
    heads = np.concatenate([[True], (srt[1:] != srt[:-1]).any(axis=1)])
    np.testing.assert_array_equal(np.asarray(is_new), heads)


def test_resolve_dedup_cap():
    assert ce.resolve_dedup_cap(512, 1024, 63) == 512
    assert ce.resolve_dedup_cap(0, 1024, 63) == 1024
    assert ce.resolve_dedup_cap(0, 16, 63) == 64
    # never exceeds the total span count + the reserved empty-row slot
    assert ce.resolve_dedup_cap(10**9, 4, 7) == 4 * 7 + 1


def test_dedup_stats_matches_brute_force():
    spec_p, op, arg = _dup_heavy(17, pop=32, depth=4)
    K = max(spec_p.n_features, len(spec_p.const_table()), 1)
    toks = _brute_tokens(op, arg, K)
    uniq_ref = len(set(toks.values()))
    total_ref = len(toks)
    n_unique, saved = ce.dedup_stats(op, arg, spec_p, 100_000)
    assert int(n_unique) == uniq_ref
    assert int(saved) == total_ref - uniq_ref
    # overflowing cap zeroes `saved` (the eval path fell back) but still
    # reports the true distinct count — that's the telemetry contract
    n2, s2 = ce.dedup_stats(op, arg, spec_p, 4)
    assert int(n2) == uniq_ref and int(s2) == 0


# --- kernel-path parity: backend × kernel × impl, bitwise --------------------


@pytest.mark.parametrize("kernel", ["r", "mse", "pearson", "r2"])
@pytest.mark.parametrize("impl", ["jnp", "pallas"])
@pytest.mark.parametrize("cap", [0, 100_000])
def test_fitness_dedup_parity_bitwise(kernel, impl, cap):
    """dedup="exact" must not change a single bit of kops.fitness —
    P=100/D=777 exercises pop-, data-tile and unique-table padding.
    cap=0 (auto) overflows on this random population and takes the
    fallback branch of the jitted cond; the roomy cap takes the
    unique-subtree gather kernel. Both must be bitwise."""
    _, spec_p, _, (op, arg) = _pops(7, pop=100, depth=5)
    X, y = _data(7, 4, 777)
    fs = FitnessSpec(kernel)
    ct = spec_p.const_table()
    kw = dict(impl=impl, data_tile=512, pop_tile=8)
    f0 = np.asarray(kops.fitness(op, arg, X, y, ct, spec_p, fs, **kw))
    f1 = np.asarray(kops.fitness(op, arg, X, y, ct, spec_p, fs,
                                 dedup="exact", dedup_cap=cap, **kw))
    np.testing.assert_array_equal(f0, f1)


@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_backend_fitness_dedup_parity_bitwise(backend):
    from repro.gp import get_backend

    _, spec_p, _, (op, arg) = _pops(5, pop=24, depth=4)
    X, y = _data(5, 4, 150)
    ct = spec_p.const_table()
    fs = FitnessSpec("r")
    b = get_backend(backend)
    f0 = np.asarray(b.fitness(op, arg, X, y, ct, spec_p, fs))
    f1 = np.asarray(b.fitness(op, arg, X, y, ct, spec_p, fs, dedup="exact"))
    np.testing.assert_array_equal(f0, f1)


def test_stream_moments_dedup_parity_bitwise():
    """The streaming fold builds ONE plan per call and shares it across
    chunks — merged moments must stay bitwise equal to dedup-off."""
    _, spec_p, _, (op, arg) = _pops(9, pop=32, depth=4)
    X, y = _data(9, 4, 600)
    ct = spec_p.const_table()
    from repro.core.fitness import get_kernel

    fs = FitnessSpec("pearson")
    acc = jnp.zeros((32, get_kernel("pearson").n_moments), jnp.float32)
    kw = dict(impl="jnp", data_tile=256)
    m0 = kops.stream_moments(acc, op, arg, X, y, ct, spec_p, fs, **kw)
    m1 = kops.stream_moments(acc, op, arg, X, y, ct, spec_p, fs,
                             dedup="exact", **kw)
    np.testing.assert_array_equal(np.asarray(m0), np.asarray(m1))


def test_pick_tiles_postfix_accounts_dedup_scratch():
    """Satellite: with the f32[U, Db] unique-subtree scratch live, the
    VMEM re-check must shrink the data tile before it overflows; with
    dedup_rows=0 the pick is unchanged from the seed behavior."""
    base = kops.pick_tiles_postfix(4, 6, 1024, 1 << 20, pop_tile=8,
                                   data_tile=65536)
    again = kops.pick_tiles_postfix(4, 6, 1024, 1 << 20, pop_tile=8,
                                    data_tile=65536, dedup_rows=0)
    assert base == again
    pt, dt = kops.pick_tiles_postfix(4, 6, 1024, 1 << 20, pop_tile=8,
                                     data_tile=65536, dedup_rows=100_000)
    assert dt < base[1]  # the scratch is charged against the budget
    vmem = 4 * (4 * 4 * dt + pt * (6 + 8) * dt + 2 * 100_000 * dt)
    assert vmem <= kops._VMEM_BUDGET or dt == 128  # floor tile is the stop


# --- full-trajectory pins: evolve, islands, tenant batch ---------------------


@pytest.mark.parametrize("islands", [1, 3])
@pytest.mark.parametrize("cap", [0, 100_000])
def test_evolve_trajectory_dedup_bitwise(islands, cap):
    """dedup="exact" must not change a single bit of the evolution
    trajectory vs dedup="off" — auto cap (overflow fallback in play for
    random populations) and a roomy explicit cap (dedup path in play),
    classic and island layouts."""
    spec = TreeSpec(max_depth=4, n_features=3, n_consts=8, genome="postfix")
    X, y = _data(13, 3, 160)
    base = dict(pop_size=24, tree_spec=spec, fitness=FitnessSpec("r"),
                elitism=2, eval_impl="jnp", dedup_cap=cap,
                island=IslandConfig(islands=islands, migrate_every=2,
                                    migrate_k=2))
    c_off = GPConfig(dedup="off", **base)
    c_on = GPConfig(dedup="exact", **base)
    s_off = init_state(c_off, jax.random.PRNGKey(1))
    s_on = init_state(c_on, jax.random.PRNGKey(1))
    for g in range(6):
        s_off = evolve_step(c_off, s_off, X, y)
        s_on = evolve_step(c_on, s_on, X, y)
        for f in ("op", "arg", "fitness", "best_fitness", "best_op"):
            np.testing.assert_array_equal(
                np.asarray(getattr(s_off, f)), np.asarray(getattr(s_on, f)),
                err_msg=f"islands={islands} cap={cap} gen={g} field={f}")


def test_tenant_block_dedup_bitwise():
    """The multi-tenant batch: a dedup="exact" block must replay the
    dedup="off" block bit for bit (per-slot plans, frozen slots, the
    elite cache and the 8-column counter stream all in play)."""
    spec = TreeSpec(max_depth=4, n_features=3, n_consts=8, genome="postfix")
    I, P, Dc = 3, 16, 64
    state = eng.empty_tenant_state(I, P, spec, elitism=1)
    for i in range(I):
        sub = eng.init_tenant_slot(jax.random.PRNGKey(i), P, spec, elitism=1)
        state = jax.tree.map(lambda b, s, i=i: b.at[i].set(s), state, sub)
    r = np.random.RandomState(3)
    X = jnp.asarray(r.randn(I, 3, Dc).astype(np.float32))
    y = jnp.asarray(r.randn(I, Dc).astype(np.float32))
    w = jnp.ones((I, Dc), jnp.float32)
    params = eng.TenantParams(
        probs=jnp.tile(jnp.asarray([[0.1, 0.1, 0.1, 0.7]], jnp.float32),
                       (I, 1)),
        tourn=jnp.full((I,), 4, jnp.int32),
        point_rate=jnp.full((I,), 0.1, jnp.float32),
        kernel_id=jnp.zeros((I,), jnp.int32),
        n_classes=jnp.full((I,), 3.0, jnp.float32),
        precision=jnp.full((I,), 1e-4, jnp.float32),
        stop=jnp.full((I,), -jnp.inf, jnp.float32),
        budget=jnp.full((I,), 6, jnp.int32))
    blk_off = jax.jit(eng.build_tenant_block(spec, ("r",), 6, 1, 4))
    blk_on = jax.jit(eng.build_tenant_block(spec, ("r",), 6, 1, 4,
                                            dedup="exact", dedup_cap=100_000))
    st_off, h_off, c_off = blk_off(state, X, y, w, params)
    st_on, h_on, c_on = blk_on(state, X, y, w, params)
    for name, a, b in zip(st_off._fields, jax.tree.leaves(st_off),
                          jax.tree.leaves(st_on)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
    np.testing.assert_array_equal(np.asarray(h_off), np.asarray(h_on))
    assert np.asarray(c_on).shape == np.asarray(c_off).shape
    assert np.asarray(c_on).shape[1] == 8


def test_counter_stream_reports_dedup_columns():
    """Duplicate-heavy population + roomy cap: the telemetry stream's
    SUBTREE_EVALS_SAVED / UNIQUE_SUBTREES columns go positive, and both
    stay zero with dedup="off"."""
    from repro.obs import counters as tc

    spec = TreeSpec(max_depth=4, n_features=3, n_consts=8, genome="postfix")
    X, y = _data(5, 3, 128)
    base = dict(pop_size=32, tree_spec=spec, fitness=FitnessSpec("r"),
                elitism=2, eval_impl="jnp")
    cfg = GPConfig(dedup="exact", dedup_cap=100_000, **base)
    _, _, ctr = eng.evolve_block(cfg, init_state(cfg, jax.random.PRNGKey(0)),
                                 X, y, None, n_steps=4)
    ctr = np.asarray(ctr)
    assert ctr.shape == (4, tc.N_COUNTERS) == (4, 8)
    assert (ctr[:, tc.UNIQUE_SUBTREES] > 0).all()
    # 32 trees over 3 features + 8 consts: pigeonhole guarantees shared
    # terminal subtrees every generation
    assert (ctr[:, tc.SUBTREE_EVALS_SAVED] > 0).all()
    cfg_off = GPConfig(dedup="off", **base)
    _, _, c0 = eng.evolve_block(cfg_off,
                                init_state(cfg_off, jax.random.PRNGKey(0)),
                                X, y, None, n_steps=4)
    assert (np.asarray(c0)[:, [tc.SUBTREE_EVALS_SAVED,
                               tc.UNIQUE_SUBTREES]] == 0).all()


# --- tier 2: semantic probe-fingerprint cache --------------------------------


def _commute_adds(op, arg):
    """Swap the operands of every top-level add whose operands are both
    terminals: semantically identical (IEEE f32 addition is commutative),
    syntactically different — the recurring-but-rewritten elite."""
    add = prim.opcode_of("add")
    op, arg = np.asarray(op).copy(), np.asarray(arg).copy()
    for p in range(op.shape[0]):
        for i in range(2, op.shape[1]):
            if (op[p, i] == add and prim.ARITY[op[p, i - 1]] == 0
                    and prim.ARITY[op[p, i - 2]] == 0):
                op[p, i - 2], op[p, i - 1] = op[p, i - 1], op[p, i - 2]
                arg[p, i - 2], arg[p, i - 1] = arg[p, i - 1], arg[p, i - 2]
                break
    return jnp.asarray(op), jnp.asarray(arg)


def test_semantic_hit_serves_rewritten_elites():
    """A head row that is a commuted rewrite of the cached elite misses
    the exact gate but hits the semantic one; the served fitness is the
    cached value, which equals re-evaluation to f32 tolerance (here
    exactly, since commuted addition is bitwise)."""
    spec = TreeSpec(max_depth=4, n_features=3, n_consts=8, genome="postfix")
    cfg = GPConfig(pop_size=16, tree_spec=spec, fitness=FitnessSpec("r"),
                   elitism=2, eval_impl="jnp", dedup="semantic")
    X, y = _data(21, 3, 120)
    ct = spec.const_table()
    op_t, arg_t = generate_population(jax.random.PRNGKey(2), 16,
                                      dataclasses.replace(spec, genome="tree"))
    op, arg = heap_to_postfix(op_t, arg_t)
    op2, arg2 = _commute_adds(op[:2], arg[:2])
    changed = not (np.array_equal(np.asarray(op2), np.asarray(op[:2]))
                   and np.array_equal(np.asarray(arg2), np.asarray(arg[:2])))

    def eval_rows(o, a):
        return kops.fitness(o, a, X, y, ct, spec, FitnessSpec("r"), impl="jnp")

    full = np.asarray(eval_rows(op, arg))
    probe = eng._probe_fn(cfg, X, ct)
    assert probe is not None
    state = eng.GPState(
        key=jax.random.PRNGKey(0), op=op, arg=arg,
        fitness=jnp.full((16,), jnp.inf), best_op=op[0], best_arg=arg[0],
        best_fitness=jnp.asarray(jnp.inf), generation=jnp.asarray(0),
        cache_op=op2, cache_arg=arg2, cache_fit=jnp.asarray(full[:2]))
    served = np.asarray(eng._cached_fitness(state, eval_rows, probe=probe))
    np.testing.assert_allclose(served, full, rtol=1e-6, atol=1e-6)
    if changed:  # the hit really came through the semantic gate
        hit_exact = bool(jnp.all(state.op[:2] == state.cache_op)
                         & jnp.all(state.arg[:2] == state.cache_arg))
        assert not hit_exact


def test_semantic_zero_cache_never_hits():
    """The zero-initialized cache's all-EMPTY rows probe to 0.0 —
    exactly what a legitimate x-x elite produces. The all-finite guard
    on cache_fit keeps the +inf sentinel from being served to such a
    head even though the probe outputs match bitwise."""
    spec = TreeSpec(max_depth=4, n_features=3, n_consts=8, genome="postfix")
    cfg = GPConfig(pop_size=8, tree_spec=spec, fitness=FitnessSpec("r"),
                   elitism=2, eval_impl="jnp", dedup="semantic")
    X, y = _data(8, 3, 80)
    ct = spec.const_table()
    N = spec.num_nodes
    # population head: x0 - x0 rows — probe to 0.0 like the zero cache,
    # but differ from it in bytes, so only the semantic gate is in play
    sub = prim.opcode_of("sub")
    row_op = np.zeros((N,), np.int32)
    row_arg = np.zeros((N,), np.int32)
    row_op[:3] = [prim.FEATURE, prim.FEATURE, sub]
    op = jnp.asarray(np.tile(row_op, (8, 1)))
    arg = jnp.asarray(np.tile(row_arg, (8, 1)))
    state = init_state(cfg, jax.random.PRNGKey(0))._replace(op=op, arg=arg)
    assert np.isinf(np.asarray(state.cache_fit)).all()  # fresh sentinel
    probe = eng._probe_fn(cfg, X, ct)
    np.testing.assert_array_equal(  # the probe outputs DO match...
        np.asarray(probe(op[:2], arg[:2])),
        np.asarray(probe(state.cache_op, state.cache_arg)))

    def eval_rows(o, a):
        return kops.fitness(o, a, X, y, ct, spec, FitnessSpec("r"), impl="jnp")

    served = np.asarray(eng._cached_fitness(state, eval_rows, probe=probe))
    assert np.isfinite(served).all()  # ...but never the +inf sentinel


def test_semantic_trajectory_matches_off_within_tolerance():
    """dedup="semantic" trajectories stay within f32 tolerance of
    dedup="off" (the documented probe-collision contract — in practice
    random runs have no collisions and match bitwise)."""
    spec = TreeSpec(max_depth=4, n_features=3, n_consts=8, genome="postfix")
    X, y = _data(13, 3, 160)
    base = dict(pop_size=24, tree_spec=spec, fitness=FitnessSpec("r"),
                elitism=2, eval_impl="jnp")
    c_off = GPConfig(dedup="off", **base)
    c_sem = GPConfig(dedup="semantic", **base)
    s_off = init_state(c_off, jax.random.PRNGKey(1))
    s_sem = init_state(c_sem, jax.random.PRNGKey(1))
    for _ in range(6):
        s_off = evolve_step(c_off, s_off, X, y)
        s_sem = evolve_step(c_sem, s_sem, X, y)
        np.testing.assert_allclose(np.asarray(s_sem.fitness),
                                   np.asarray(s_off.fitness),
                                   rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(s_sem.best_fitness),
                               float(s_off.best_fitness),
                               rtol=1e-5, atol=1e-5)


def test_config_rejects_unknown_dedup():
    with pytest.raises(ValueError, match="dedup"):
        GPConfig(pop_size=8, tree_spec=TreeSpec(max_depth=3, n_features=2),
                 fitness=FitnessSpec("r"), dedup="fuzzy")


# --- 8-device mesh trajectory (tier2 subprocess) -----------------------------

_SUBPROCESS_MESH_DEDUP = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import (GPConfig, TreeSpec, FitnessSpec, init_state,
                            sharded_evolve_block)
    from repro.core.islands import IslandConfig
    from repro.launch.mesh import make_host_mesh

    spec = TreeSpec(max_depth=4, n_features=2, n_consts=8, genome="postfix")
    rng = np.random.RandomState(1)
    X = jnp.asarray(np.abs(rng.randn(2, 128)).astype(np.float32) + 0.5)
    y = jnp.asarray((np.asarray(X)[0]**2 / np.asarray(X)[1]).astype(np.float32))
    w = jnp.ones((128,), jnp.float32)
    mesh = make_host_mesh(data=2, model=2, pod=2)

    for island in (None, IslandConfig(islands=2, migrate_every=2,
                                      migrate_k=2)):
        base = dict(pop_size=32, tree_spec=spec, fitness=FitnessSpec("r"))
        if island is not None:
            base["island"] = island
        outs = {}
        for mode in ("off", "exact"):
            cfg = GPConfig(dedup=mode, dedup_cap=100_000, **base)
            block, _ = sharded_evolve_block(cfg, mesh, n_steps=5,
                                            pod_axis="pod")
            with jax.set_mesh(mesh):
                s, hist, ctr = jax.jit(block)(
                    init_state(cfg, jax.random.PRNGKey(0)), X, y, w,
                    jnp.asarray(5, jnp.int32))
            outs[mode] = (s, np.asarray(hist))
        s0, h0 = outs["off"]; s1, h1 = outs["exact"]
        for name, a, b in zip(s0._fields, jax.tree.leaves(s0),
                              jax.tree.leaves(s1)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg="GPState." + name)
        np.testing.assert_array_equal(h0, h1)
    print("MESH_DEDUP_OK")
""")


@pytest.mark.tier2
def test_mesh_dedup_trajectory_subprocess():
    """dedup="exact" == dedup="off", bitwise, on an 8-device host mesh
    (per-shard plans over each shard's population slice), classic and
    island layouts."""
    env = dict(os.environ, PYTHONPATH=os.path.join(
        os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SUBPROCESS_MESH_DEDUP], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "MESH_DEDUP_OK" in r.stdout
