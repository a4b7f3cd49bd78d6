"""Device-resident evolution blocks: scan-block vs step-by-step equivalence
(single-device and mesh), padding-exact weighted evaluation on every
backend × kernel, on-device early stop, and the block-driving session's
host-sync budget (one synchronization per block)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    FitnessSpec, GPConfig, TreeSpec, evolve_block, evolve_step, init_state,
)
from repro.core import fitness as fit
from repro.core.trees import generate_population
from repro.data.datasets import kepler
from repro.data.loader import feature_major, pad_feature_major
from repro.gp import GPSession, get_backend


def _kepler_setup(pop=24, depth=4):
    X_rows, y, _ = kepler()
    spec = TreeSpec(max_depth=depth, n_features=1, n_consts=8)
    cfg = GPConfig(pop_size=pop, tree_spec=spec, fitness=FitnessSpec("r"))
    return cfg, jnp.asarray(feature_major(X_rows)), jnp.asarray(y)


# --- scan-block vs step-by-step ----------------------------------------------


def test_block_bitwise_identical_to_stepwise():
    """K scanned generations == K dispatched generations, bit for bit:
    same PRNG stream, same state pytree. The scan shares the step's body,
    so the device-resident loop cannot drift from the reference loop."""
    cfg, X, y = _kepler_setup()
    K = 7
    s_step = init_state(cfg, jax.random.PRNGKey(0))
    for _ in range(K):
        s_step = evolve_step(cfg, s_step, X, y)
    s_blk, hist, counters = evolve_block(
        cfg, init_state(cfg, jax.random.PRNGKey(0)), X, y, None, n_steps=K)
    for name, a, b in zip(s_step._fields, jax.tree.leaves(s_step),
                          jax.tree.leaves(s_blk)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"GPState.{name} diverged")
    assert hist.shape == (K,)
    assert float(hist[-1]) == float(s_step.best_fitness)
    assert counters.shape == (K, 8)  # telemetry stream rides the same scan


def test_block_early_stop_freezes_on_device():
    """Once best_fitness <= stop_fitness, the remaining scan steps are
    no-ops: generation stops advancing and the state (PRNG key included)
    is carried unchanged — the host can detect the stop from the
    generation counter alone, at the block boundary."""
    import dataclasses

    cfg, X, y = _kepler_setup()
    cfg = dataclasses.replace(cfg, stop_fitness=1e9)  # stops after gen 1
    state, hist, counters = evolve_block(
        cfg, init_state(cfg, jax.random.PRNGKey(0)), X, y, None, n_steps=10)
    assert int(state.generation) == 1
    assert np.all(np.asarray(hist) == np.asarray(hist)[0])
    # frozen steps self-report in the counter stream (column 2)
    assert int(np.asarray(counters)[:, 2].sum()) == 9


def test_session_one_sync_per_block():
    """The step()/evolve() contract drift fixed: a multi-generation
    evolve() on a jitted backend issues at most one host synchronization
    per evolution block — ⌈G/K⌉ total, and exactly ONE for the default
    whole-run block."""
    X_rows, y, _ = kepler()
    s = GPSession(pop_size=24, generations=50, kernel="r", backend="jnp",
                  block_size=10)
    s.fit(X_rows, y, key=jax.random.PRNGKey(0))
    assert s.generation == 50 and len(s.history) == 50
    assert s.stats["host_syncs"] <= -(-50 // 10), s.stats

    s2 = GPSession(pop_size=24, generations=50, kernel="r", backend="jnp")
    s2.fit(X_rows, y, key=jax.random.PRNGKey(0))
    assert s2.stats["host_syncs"] == 1, s2.stats
    # identical trajectory regardless of block partitioning
    np.testing.assert_array_equal(np.asarray(s.history), np.asarray(s2.history))


def test_session_callback_and_checkpoint_set_block_span():
    """Block size respects the callback/checkpoint periods, so host-side
    side effects still fire exactly as configured."""
    X_rows, y, _ = kepler()
    seen = []
    s = GPSession(pop_size=16, generations=12, kernel="r", backend="jnp",
                  callback=lambda g, st: seen.append(g), callback_every=4)
    s.fit(X_rows, y, key=jax.random.PRNGKey(0))
    assert seen == [3, 7, 11]
    assert s.stats["blocks"] == 3 and len(s.history) == 12


def test_checkpoint_period_phase_aligns_with_blocks(tmp_path):
    """Periodic checkpoints fire on their configured multiples even when
    another period forces misaligned block boundaries: checkpoint_every=4
    with callback_every=3 → boundaries 3,4,6,8,9,12 and saves at 4,8,12."""
    X_rows, y, _ = kepler()
    s = GPSession(pop_size=16, generations=12, kernel="r", backend="jnp",
                  checkpoint_dir=str(tmp_path), checkpoint_every=4,
                  callback=lambda g, st: None, callback_every=3)
    s.fit(X_rows, y, key=jax.random.PRNGKey(0))
    s._manager.wait()
    assert sorted(s._manager.saved_steps) == [4, 8, 12], s._manager.saved_steps


def test_callback_every_honored_on_host_backend():
    """The scalar host loop fires the callback on the callback_every
    cadence (plus the final generation), not every generation."""
    X_rows, y, _ = kepler()
    seen = []
    s = GPSession(pop_size=12, generations=5, kernel="r", backend="scalar",
                  callback=lambda g, st: seen.append(g), callback_every=2)
    s.fit(X_rows, y)
    assert seen == [1, 3, 4], seen


def test_raw_evolve_block_then_evolve_stays_coherent():
    """Mixing the raw evolve_block() surface with evolve() keeps the
    host's generation mirror coherent — including under stop_fitness,
    where frozen steps mean the device counter can lag the dispatch
    count (evolve() resyncs once instead of crashing/desyncing)."""
    X_rows, y, _ = kepler()
    s = GPSession(pop_size=16, generations=30, kernel="r", backend="jnp",
                  stop_fitness=-1.0)  # unreachable: no freeze, but traced
    s.ingest(X_rows, y)
    s.init(key=jax.random.PRNGKey(0))
    s.evolve_block(5)
    s.evolve(10)
    assert s.generation == 15 and len(s.history) == 10

    s2 = GPSession(pop_size=16, generations=30, kernel="r", backend="jnp",
                   stop_fitness=1e9)  # stops after generation 1
    s2.ingest(X_rows, y)
    s2.init(key=jax.random.PRNGKey(0))
    s2.evolve_block(5)  # device froze at gen 1; host mirror marked stale
    s2.evolve(10)
    assert s2.generation == 1  # resynced, not 5 + garbage


def test_unreached_stop_fitness_runs_all_generations():
    """An armed-but-never-reached stop_fitness must not shorten the run:
    the block span is capped at the compiled quantum (_STOP_CHECK_SPAN),
    so `ran < K` only ever signals a real on-device freeze — previously
    K could exceed the dispatched block length and a full 32-step block
    was misread as an early stop, silently truncating generations."""
    X_rows, y, _ = kepler()
    s = GPSession(pop_size=16, generations=100, kernel="r", backend="jnp",
                  stop_fitness=-1.0)  # unreachable
    s.fit(X_rows, y, key=jax.random.PRNGKey(0))
    assert s.generation == 100, s.generation
    assert len(s.history) == 100
    assert s.stats["blocks"] == -(-100 // GPSession._STOP_CHECK_SPAN)


def test_stop_fitness_bounds_block_span():
    """Frozen steps still execute on-device, so with stop_fitness armed
    and no other period the session caps blocks at _STOP_CHECK_SPAN: a
    run converging early overshoots at most one capped block."""
    X_rows, y, _ = kepler()
    s = GPSession(pop_size=16, generations=500, kernel="r", backend="jnp",
                  stop_fitness=1e9)  # stops after generation 1
    s.fit(X_rows, y, key=jax.random.PRNGKey(0))
    assert s.generation == 1
    assert s.stats["blocks"] == 1  # one capped block, not a 500-step scan


def test_ragged_blocks_reuse_one_compiled_program():
    """Phase-aligned boundaries produce ragged block lengths; the session
    must serve them all from ONE fixed-length compiled scan (dynamic
    limit), not one compile per distinct length."""
    from repro.core import engine

    X_rows, y, _ = kepler()
    s = GPSession(pop_size=16, generations=17, kernel="r", backend="jnp",
                  callback=lambda g, st: None, callback_every=7)
    s.ingest(X_rows, y)
    s.init(key=jax.random.PRNGKey(0))
    n0 = engine.evolve_block._cache_size()
    s.evolve()  # boundaries at 7, 14, 17 → lengths 7, 7, 3
    assert s.generation == 17 and s.stats["blocks"] == 3
    assert engine.evolve_block._cache_size() == n0 + 1


# --- padding-exact weighted evaluation ---------------------------------------


@pytest.mark.parametrize("backend", ["jnp", "pallas", "scalar"])
@pytest.mark.parametrize("kernel", ["r", "c", "m", "mse", "pearson", "r2"])
def test_padded_fitness_matches_unpadded(backend, kernel):
    """fitness on zero-weighted padded [D+r] data == fitness on the
    unpadded [D] data, for every registered kernel on every backend —
    the guarantee that lets any dataset shard on any data axis."""
    spec = TreeSpec(max_depth=4, n_features=4, n_consts=8)
    op, arg = generate_population(jax.random.PRNGKey(3), 16, spec)
    rng = np.random.RandomState(0)
    X = rng.randn(4, 101).astype(np.float32)  # odd D: pads to 112 (tile 8)
    y = rng.randint(0, 3, 101).astype(np.float32)
    Xp, yp, w = pad_feature_major(X, y, 8)
    assert Xp.shape[1] != X.shape[1]  # padding actually happened
    fs = FitnessSpec(kernel, n_classes=3, precision=0.5)
    consts = np.asarray(spec.const_table())
    be = get_backend(backend)
    base = np.asarray(be.fitness(op, arg, X, y, consts, spec, fs))
    padded = np.asarray(be.fitness(op, arg, Xp, yp, consts, spec, fs,
                                   weight=jnp.asarray(w)))
    np.testing.assert_allclose(padded, base, rtol=1e-5, atol=1e-5)


def test_weighted_partials_all_kernels_direct():
    """FitnessKernel.partial_fitness itself ignores zero-weight points —
    including the two-pass pearson/r2 kernels' global moments."""
    rng = np.random.RandomState(1)
    preds = jnp.asarray(rng.randn(5, 64).astype(np.float32))
    y = jnp.asarray(rng.randn(64).astype(np.float32))
    pad = jnp.asarray(rng.randn(5, 16).astype(np.float32))
    preds_p = jnp.concatenate([preds, pad], axis=1)
    y_p = jnp.concatenate([y, jnp.zeros(16)])
    w = jnp.concatenate([jnp.ones(64), jnp.zeros(16)])
    for kernel in fit.available_kernels():
        spec = FitnessSpec(kernel, n_classes=3, precision=0.5)
        base = np.asarray(fit.fitness_from_preds(preds, y, spec))
        padded = np.asarray(fit.fitness_from_preds(preds_p, y_p, spec, weight=w))
        np.testing.assert_allclose(padded, base, rtol=1e-5, atol=1e-5,
                                   err_msg=f"kernel {kernel!r}")


# --- mesh: scan-inside-shard_map + padded sharding (subprocess) --------------

_SUBPROCESS_MESH_BLOCKS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import (GPConfig, TreeSpec, FitnessSpec, init_state,
                            sharded_evolve_step, sharded_evolve_block)
    from repro.core.engine import evolve_step
    from repro.launch.mesh import make_host_mesh
    from repro.gp import GPSession, MeshTopology

    spec = TreeSpec(max_depth=4, n_features=2, n_consts=8)
    cfg = GPConfig(pop_size=32, tree_spec=spec, fitness=FitnessSpec("r"))
    rng = np.random.RandomState(1)
    Xk = np.abs(rng.randn(2, 128)).astype(np.float32) + 0.5
    yk = (Xk[0]**2 / Xk[1]).astype(np.float32)
    X, y = jnp.asarray(Xk), jnp.asarray(yk)
    w = jnp.ones((128,), jnp.float32)

    # scan-inside-shard_map block == K dispatched sharded steps, bitwise
    mesh = make_host_mesh(data=2, model=2, pod=2)
    step, _ = sharded_evolve_step(cfg, mesh, pod_axis="pod")
    block, _ = sharded_evolve_block(cfg, mesh, n_steps=6, pod_axis="pod")
    s_step = init_state(cfg, jax.random.PRNGKey(0))
    with jax.set_mesh(mesh):
        js = jax.jit(step)
        for _ in range(6):
            s_step = js(s_step, X, y, w)
        s_blk, hist, counters = jax.jit(block)(
            init_state(cfg, jax.random.PRNGKey(0)), X, y, w,
            jnp.asarray(6, jnp.int32))
    for name, a, b in zip(s_step._fields, jax.tree.leaves(s_step), jax.tree.leaves(s_blk)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg="GPState." + name)
    assert hist.shape == (6,)
    assert float(np.asarray(hist)[-1]) == float(s_step.best_fitness)
    # the cache, dedup and node_evals columns are 0 on a mesh
    assert (np.asarray(counters)[:, [0, 1, 5, 6, 7]] == 0).all(), counters

    # acceptance: odd rows shard on data=2 — padded, masked, and the
    # evaluated fitness matches the unpadded single-device computation
    X_rows = np.ascontiguousarray(Xk.T)[:101]   # 101 % 2 == 1
    y101 = yk[:101]
    sm = GPSession(pop_size=32, generations=1, kernel="r",
                   topology=MeshTopology(data=2))
    sm.ingest(X_rows, y101)
    sm.init(key=jax.random.PRNGKey(2))
    sm.step()
    ss = GPSession(pop_size=32, generations=1, kernel="r", backend="jnp")
    ss.ingest(X_rows, y101)
    ss.init(key=jax.random.PRNGKey(2))
    ss.step()
    np.testing.assert_allclose(np.asarray(sm.state.fitness),
                               np.asarray(ss.state.fitness), rtol=1e-5, atol=1e-5)
    assert float(sm.state.best_fitness) == float(ss.state.best_fitness) or (
        abs(float(sm.state.best_fitness) - float(ss.state.best_fitness)) < 1e-5)

    # and a full padded mesh fit() drives blocks end to end
    sm2 = GPSession(pop_size=32, generations=10, kernel="r",
                    topology=MeshTopology(data=2, model=2))
    sm2.fit(X_rows, y101)
    assert sm2.generation == 10 and np.isfinite(sm2.best_fitness)
    assert sm2.stats["host_syncs"] == 1, sm2.stats

    # two-pass kernels (pearson, r2) on the mesh data axis: the merged
    # (hoisted + Chan-combined) moments must match the single-device
    # fitness, on unpadded (128) and padded ragged (101 -> 104 on data=4)
    # datasets alike. Centered moments killed the old raw-moment rounding
    # amplification, so BOTH kernels now hold 1e-4 (pearson was 5e-3).
    tol = {"pearson": 1e-4, "r2": 1e-4}
    for kern in ("pearson", "r2"):
        for rows in (128, 101):
            Xr, yr = np.ascontiguousarray(Xk.T)[:rows], yk[:rows]
            sm = GPSession(pop_size=32, generations=1, kernel=kern,
                           topology=MeshTopology(data=4, model=2))
            sm.ingest(Xr, yr)
            sm.init(key=jax.random.PRNGKey(3))
            sm.step()
            ss = GPSession(pop_size=32, generations=1, kernel=kern, backend="jnp")
            ss.ingest(Xr, yr)
            ss.init(key=jax.random.PRNGKey(3))
            ss.step()
            np.testing.assert_allclose(
                np.asarray(sm.state.fitness), np.asarray(ss.state.fitness),
                rtol=tol[kern], atol=tol[kern],
                err_msg="mesh-vs-single %s rows=%d" % (kern, rows))
    print("MESH_BLOCKS_OK")
""")


@pytest.mark.tier2
def test_mesh_blocks_subprocess():
    env = dict(os.environ, PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SUBPROCESS_MESH_BLOCKS], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "MESH_BLOCKS_OK" in r.stdout


# --- scalar host loop: cached selection program ------------------------------


def test_host_next_generation_cached_across_sessions():
    """The scalar backend's host loop re-enters ONE jitted selection
    program per (spec, mix, tourn_size, elitism) — no per-call-site
    retrace (ROADMAP open item)."""
    from repro.gp import backends as B

    X_rows, y, _ = kepler()
    B.host_next_generation.cache_clear()
    s1 = GPSession(pop_size=12, generations=2, kernel="r", backend="scalar")
    s1.fit(X_rows, y)
    s2 = GPSession(pop_size=12, generations=2, kernel="r", backend="scalar")
    s2.fit(X_rows, y)
    info = B.host_next_generation.cache_info()
    assert info.misses == 1 and info.hits >= 3, info
    fn = B.host_next_generation(s1.config.tree_spec, s1.config.mix,
                                s1.config.tourn_size, s1.config.elitism)
    assert fn._cache_size() == 1  # one compiled program across 4 generations
