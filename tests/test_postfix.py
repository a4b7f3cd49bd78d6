"""Postfix linear genomes: heap↔postfix round-trip, tree-vs-postfix fitness
parity pinned BITWISE within each eval impl, the cross-generation elite
fitness cache (hits must equal re-evaluation bit for bit), and splice-
operator invariants P1–P5 on linear genomes."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core import FitnessSpec, GPConfig, evolve_step, init_state
from repro.core import engine as eng
from repro.core import evolve as ev
from repro.core.islands import IslandConfig
from repro.core.trees import (TreeSpec, check_invariants, generate_population,
                              heap_to_postfix, postfix_to_heap, to_string)
from repro.kernels import ops as kops
from repro.kernels.ref import fitness_ref


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


# --- representation ----------------------------------------------------------


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), depth=st.integers(1, 6),
       pop=st.sampled_from([1, 9, 40]))
def test_heap_postfix_roundtrip(seed, depth, pop):
    spec_t, spec_p, (op_t, arg_t), (op_p, arg_p) = _pops(seed, pop, depth)
    check_invariants(np.asarray(op_p), spec_p)
    op_h, arg_h = postfix_to_heap(op_p, arg_p, spec_t)
    np.testing.assert_array_equal(np.asarray(op_h), np.asarray(op_t))
    np.testing.assert_array_equal(np.asarray(arg_h), np.asarray(arg_t))


def test_mixed_form_raises_value_error():
    """A heap population checked under a postfix spec (and vice versa) is
    the stale-checkpoint signature — must raise the descriptive ValueError,
    not a bare AssertionError."""
    spec_t, spec_p, (op_t, _), (op_p, _) = _pops(0, pop=16, depth=4)
    with pytest.raises(ValueError, match="genome"):
        check_invariants(np.asarray(op_t), spec_p)
    with pytest.raises(ValueError, match="genome"):
        check_invariants(np.asarray(op_p), spec_t)


def test_to_string_agrees_across_forms():
    spec_t, spec_p, (op_t, arg_t), (op_p, arg_p) = _pops(2, pop=8, depth=4)
    ct = np.asarray(spec_t.const_table())
    for i in range(8):
        s_t = to_string(np.asarray(op_t[i]), np.asarray(arg_t[i]), const_table=ct)
        s_p = to_string(np.asarray(op_p[i]), np.asarray(arg_p[i]), const_table=ct,
                        genome="postfix")
        assert s_t == s_p


# --- fitness parity: tree vs postfix, pinned bitwise -------------------------


@pytest.mark.parametrize("kernel", ["r", "mse", "pearson", "r2"])
@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_fitness_parity_tree_vs_postfix_bitwise(kernel, impl):
    """The two encodings of the same population must score bitwise-equal
    within each impl (P=100/D=777 exercises pop- and data-tile padding).
    Tiles are pinned identical for both forms — the per-genome tile
    pickers intentionally diverge by default."""
    spec_t, spec_p, (op_t, arg_t), (op_p, arg_p) = _pops(7, pop=100, depth=5)
    X, y = _data(7, 4, 777)
    fs = FitnessSpec(kernel)
    ct = spec_t.const_table()
    kw = dict(impl=impl, data_tile=512, pop_tile=8)
    f_t = np.asarray(kops.fitness(op_t, arg_t, X, y, ct, spec_t, fs, **kw))
    f_p = np.asarray(kops.fitness(op_p, arg_p, X, y, ct, spec_p, fs, **kw))
    np.testing.assert_array_equal(f_t, f_p)
    # generation-1 champion parity follows, pinned explicitly
    assert int(f_t.argmin()) == int(f_p.argmin())
    assert f_t.min() == f_p.min()


def test_fitness_parity_on_reference_path():
    spec_t, spec_p, (op_t, arg_t), (op_p, arg_p) = _pops(11, pop=64, depth=5)
    X, y = _data(11, 4, 300)
    fs = FitnessSpec("r")
    ct = spec_t.const_table()
    f_t = np.asarray(fitness_ref(op_t, arg_t, X, y, ct, spec_t, fs))
    f_p = np.asarray(fitness_ref(op_p, arg_p, X, y, ct, spec_p, fs))
    np.testing.assert_array_equal(f_t, f_p)


def test_postfix_backend_agreement():
    """scalar / jnp / pallas must agree on a postfix population just as
    they do on heap trees (the existing test_gp_api parity sweep)."""
    from repro.gp import get_backend

    _, spec_p, _, (op_p, arg_p) = _pops(5, pop=24, depth=4)
    X, y = _data(5, 4, 150)
    ct = np.asarray(spec_p.const_table())
    fs = FitnessSpec("r")
    outs = {name: np.asarray(get_backend(name).fitness(
        op_p, arg_p, np.asarray(X), np.asarray(y), ct, spec_p, fs))
        for name in ("scalar", "jnp", "pallas")}
    np.testing.assert_allclose(outs["jnp"], outs["scalar"], rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(outs["jnp"], outs["pallas"], rtol=1e-5, atol=1e-4)


# --- semantic elite cache ----------------------------------------------------


def test_cached_fitness_hit_is_bitwise_reevaluation():
    """A cache hit must return exactly what re-evaluating the rows would —
    the cached value IS last generation's evaluation of identical rows."""
    spec_t, _, (op, arg), _ = _pops(3, pop=20, depth=4)
    X, y = _data(3, 4, 200)
    fs = FitnessSpec("r")
    ct = spec_t.const_table()

    def eval_rows(o, a):
        return kops.fitness(o, a, X, y, ct, spec_t, fs, impl="jnp")

    full = np.asarray(eval_rows(op, arg))
    E = 3
    state = eng.GPState(
        key=jax.random.PRNGKey(0), op=op, arg=arg,
        fitness=jnp.full((20,), jnp.inf), best_op=op[0], best_arg=arg[0],
        best_fitness=jnp.asarray(jnp.inf), generation=jnp.asarray(0),
        cache_op=op[:E], cache_arg=arg[:E], cache_fit=jnp.asarray(full[:E]))
    served = np.asarray(eng._cached_fitness(state, eval_rows))
    np.testing.assert_array_equal(served, full)
    # one perturbed cached genome -> miss -> full evaluation, same result
    miss = state._replace(cache_arg=state.cache_arg.at[0, 0].add(1))
    np.testing.assert_array_equal(np.asarray(eng._cached_fitness(miss, eval_rows)),
                                  full)


@pytest.mark.parametrize("islands", [1, 3])
@pytest.mark.parametrize("genome", ["tree", "postfix"])
def test_elite_cache_trajectory_bitwise(islands, genome):
    """elite_cache=True must not change a single bit of the evolution
    trajectory vs elite_cache=False — cache hits replace re-evaluations
    exactly, across classic and island layouts and both genome forms
    (migration rewrites last-k slots, so [:E] elites stay cache hits)."""
    spec = TreeSpec(max_depth=4, n_features=3, n_consts=8, genome=genome)
    X, y = _data(13, 3, 160)
    base = dict(pop_size=24, tree_spec=spec, fitness=FitnessSpec("r"),
                elitism=2, eval_impl="jnp",
                island=IslandConfig(islands=islands, migrate_every=2,
                                    migrate_k=2))
    s_on = init_state(GPConfig(elite_cache=True, **base), jax.random.PRNGKey(1))
    s_off = init_state(GPConfig(elite_cache=False, **base), jax.random.PRNGKey(1))
    for _ in range(6):
        s_on = evolve_step(GPConfig(elite_cache=True, **base), s_on, X, y)
        s_off = evolve_step(GPConfig(elite_cache=False, **base), s_off, X, y)
        for f in ("op", "arg", "fitness", "best_fitness", "best_op"):
            np.testing.assert_array_equal(np.asarray(getattr(s_on, f)),
                                          np.asarray(getattr(s_off, f)), err_msg=f)


def test_session_ingest_invalidates_cache():
    from repro.gp import GPSession

    X, y = _data(17, 3, 120)
    sess = GPSession(GPConfig(pop_size=16, elitism=2,
                              tree_spec=TreeSpec(max_depth=4, n_features=3,
                                                 n_consts=8),
                              fitness=FitnessSpec("r"), generations=3),
                     backend="jnp")
    sess.fit(np.asarray(X).T, np.asarray(y))
    assert np.isfinite(np.asarray(sess.state.cache_fit)).all()
    sess.ingest(np.asarray(X).T, np.asarray(y) + 1.0)  # new data: stale cache
    assert np.isinf(np.asarray(sess.state.cache_fit)).all()
    assert not np.asarray(sess.state.cache_op).any()


# --- linear-genome operators -------------------------------------------------


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_postfix_operators_preserve_invariants(seed):
    spec_t, spec_p, _, (op_p, arg_p) = _pops(seed % 1000, pop=16, depth=5)
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(seed), 4)
    op_b2, arg_b2 = generate_population(k1, 16, spec_p)
    op_x, arg_x = ev.crossover_postfix(k2, op_p, arg_p, op_b2, arg_b2, spec_p)
    check_invariants(np.asarray(op_x), spec_p)
    op_m, arg_m = ev.mutate_branch_postfix(k3, op_p, arg_p, spec_p)
    check_invariants(np.asarray(op_m), spec_p)
    op_pt, arg_pt = ev.mutate_point(k4, op_p, arg_p, spec_p, p=0.5)
    check_invariants(np.asarray(op_pt), spec_p)
    # point mutation is structure-preserving: opcodes keep their arity
    from repro.core import primitives as prim
    np.testing.assert_array_equal(prim.ARITY[np.asarray(op_pt)],
                                  prim.ARITY[np.asarray(op_p)])


def test_postfix_evolution_invariants_over_generations():
    """Full breeding dispatch (next_generation_arrays under evolve_step)
    must keep every postfix generation P1–P5-valid."""
    spec = TreeSpec(max_depth=5, n_features=3, n_consts=8, genome="postfix")
    cfg = GPConfig(pop_size=32, tree_spec=spec, fitness=FitnessSpec("r"),
                   elitism=1, eval_impl="jnp")
    X, y = _data(19, 3, 128)
    state = init_state(cfg, jax.random.PRNGKey(4))
    for _ in range(5):
        state = evolve_step(cfg, state, X, y)
        check_invariants(np.asarray(state.op), spec)
    assert float(state.best_fitness) < float("inf")


# --- checkpoint format guard -------------------------------------------------


def test_checkpoint_leaf_count_mismatch_is_descriptive(tmp_path):
    """Restoring a pre-elite-cache checkpoint into the new GPState layout
    must fail with the migration hint, not an opaque unflatten error."""
    from repro.ckpt import checkpoint as ck

    old = {"op": np.zeros((4, 15), np.int32), "fit": np.zeros((4,), np.float32)}
    ck.save(old, str(tmp_path), 0)
    new_layout = {"op": old["op"], "fit": old["fit"],
                  "cache_fit": np.zeros((2,), np.float32)}
    with pytest.raises(ValueError, match="state\n?\\s*format changed|format changed"):
        ck.restore(str(tmp_path), 0, like=new_layout)
    leaves, manifest = ck.restore(str(tmp_path), 0, like=None)
    assert len(leaves) == 2
