"""Engine behaviour: convergence on paper problems + sharded-step subprocess
tests (multi-device CPU meshes must live in their own process so the main
pytest process keeps a single device)."""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import GPConfig, TreeSpec, FitnessSpec, init_state, evolve_step, run
from repro.data.datasets import iris, kepler
from repro.data.loader import feature_major


def test_kepler_convergence():
    """The engine must rediscover Kepler's 3rd law (p = sqrt(r^3)) — the
    paper's flagship regression (fitness → ~0)."""
    X_rows, y, meta = kepler()
    from repro.core import primitives as prim
    spec = TreeSpec(max_depth=5, n_features=1, n_consts=8,
                    fn_set=prim.KITCHEN_SINK)
    cfg = GPConfig(pop_size=200, tree_spec=spec, fitness=FitnessSpec("r"),
                   generations=30)
    state = run(cfg, feature_major(X_rows), y, key=jax.random.PRNGKey(0))
    assert float(state.best_fitness) < 1.0  # sum|err| over 9 planets


def test_iris_classification_signal():
    X_rows, y, meta = iris()
    cfg = GPConfig(pop_size=100, tree_spec=TreeSpec(max_depth=5, n_features=4,
                                                    n_consts=8),
                   fitness=FitnessSpec("c", n_classes=3), generations=12)
    state = run(cfg, feature_major(X_rows), y, key=jax.random.PRNGKey(0))
    acc = -float(state.best_fitness) / 150.0
    assert acc > 0.60  # must beat chance (1/3) decisively


def test_pallas_impl_agrees_with_jnp():
    X_rows, y, meta = iris()
    X = feature_major(X_rows)
    spec = TreeSpec(max_depth=4, n_features=4, n_consts=8)
    base = dict(pop_size=40, tree_spec=spec,
                fitness=FitnessSpec("c", n_classes=3), generations=4)
    s1 = run(GPConfig(eval_impl="jnp", **base), X, y, key=jax.random.PRNGKey(5))
    s2 = run(GPConfig(eval_impl="pallas", **base), X, y, key=jax.random.PRNGKey(5))
    assert float(s1.best_fitness) == pytest.approx(float(s2.best_fitness), abs=1e-3)


_SUBPROCESS_SHARDED = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import GPConfig, TreeSpec, FitnessSpec, init_state, sharded_evolve_step, evolve_step
    from repro.launch.mesh import make_host_mesh

    spec = TreeSpec(max_depth=5, n_features=2, n_consts=8)
    cfg = GPConfig(pop_size=64, tree_spec=spec, fitness=FitnessSpec("r"),
                   migrate_every=3)
    Xk = np.abs(np.random.RandomState(1).randn(2, 128)).astype(np.float32) + 0.5
    yk = (Xk[0]**2 / Xk[1]).astype(np.float32)

    wk = jnp.ones((128,), jnp.float32)

    # 3D mesh with island model
    mesh = make_host_mesh(data=2, model=2, pod=2)
    step, specs = sharded_evolve_step(cfg, mesh, pod_axis="pod")
    s = init_state(cfg, jax.random.PRNGKey(0))
    with jax.set_mesh(mesh):
        js = jax.jit(step)
        for _ in range(12):
            s = js(s, jnp.asarray(Xk), jnp.asarray(yk), wk)
    assert np.isfinite(float(s.best_fitness)), s.best_fitness
    assert float(s.best_fitness) < 50.0
    assert int(s.generation) == 12

    # 2D mesh, same engine — and the single-device reference still improves
    mesh2 = make_host_mesh(data=4, model=2)
    step2, _ = sharded_evolve_step(cfg, mesh2)
    s2 = init_state(cfg, jax.random.PRNGKey(0))
    with jax.set_mesh(mesh2):
        js2 = jax.jit(step2)
        for _ in range(12):
            s2 = js2(s2, jnp.asarray(Xk), jnp.asarray(yk), wk)
    assert np.isfinite(float(s2.best_fitness))
    print("SHARDED_OK")
""")


@pytest.mark.tier2
def test_sharded_engine_subprocess():
    env = dict(os.environ, PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SUBPROCESS_SHARDED], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "SHARDED_OK" in r.stdout


def test_state_is_checkpointable():
    from repro.ckpt.checkpoint import save, restore
    import tempfile
    cfg = GPConfig(pop_size=16, tree_spec=TreeSpec(max_depth=3, n_features=2),
                   fitness=FitnessSpec("r"))
    state = init_state(cfg, jax.random.PRNGKey(0))
    with tempfile.TemporaryDirectory() as d:
        save(state, d, 7)
        back = restore(d, 7, like=state)
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(back)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
