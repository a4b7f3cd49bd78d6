"""Checkpointing: roundtrip, corruption detection, retention, async,
elastic resharding across different meshes (subprocess)."""
import os
import subprocess
import sys
import tempfile
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt.checkpoint import CheckpointManager, latest_step, restore, save


def _tree(seed=0):
    r = np.random.RandomState(seed)
    return {"a": jnp.asarray(r.randn(4, 8).astype(np.float32)),
            "nested": {"b": jnp.asarray(r.randint(0, 9, (3,)).astype(np.int32)),
                       "c": jnp.asarray(r.randn(2).astype(np.float32))}}


def test_roundtrip():
    t = _tree()
    with tempfile.TemporaryDirectory() as d:
        save(t, d, 3)
        back = restore(d, 3, like=t)
        for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(back)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert latest_step(d) == 3


def test_corruption_detected():
    t = _tree()
    with tempfile.TemporaryDirectory() as d:
        p = save(t, d, 1)
        victim = os.path.join(p, "000000.npy")
        arr = np.load(victim)
        arr.flat[0] += 1.0
        np.save(victim, arr)
        with pytest.raises(IOError, match="corruption"):
            restore(d, 1, like=t)


def test_torn_write_not_visible():
    t = _tree()
    with tempfile.TemporaryDirectory() as d:
        save(t, d, 5)
        os.makedirs(os.path.join(d, "step_00000009.tmp"))  # crashed save
        assert latest_step(d) == 5


def test_manager_async_retention():
    t = _tree()
    with tempfile.TemporaryDirectory() as d:
        m = CheckpointManager(d, keep=2, every=1)
        for s in range(1, 6):
            m.maybe_save(t, s)
        m.wait()
        steps = sorted(int(x.split("_")[1]) for x in os.listdir(d))
        assert steps == [4, 5]
        (restored, s0) = m.restore_latest(like=t)
        assert s0 == 5 and restored is not None


_ELASTIC = textwrap.dedent("""
    import os, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_reduced
    from repro.launch.mesh import make_host_mesh, batch_axes
    from repro.launch import sharding as SH
    from repro.models import model as Md
    from repro.models.transformer import ShardingPolicy
    from repro.optim.adamw import for_config
    from repro.ckpt.checkpoint import save, restore
    from repro.ckpt.elastic import reshard_state

    cfg = get_reduced("gemma-2b")
    mesh_a = make_host_mesh(data=2, model=4)
    cfg_a = cfg.with_policy(ShardingPolicy(batch=batch_axes(mesh_a), tp_size=4))
    opt = for_config(cfg_a)
    params = Md.init_params(cfg_a, jax.random.PRNGKey(0))
    state = {"params": params, "opt": opt.init(params), "step": jnp.zeros((), jnp.int32)}
    with tempfile.TemporaryDirectory() as d:
        save(state, d, 1)
        host = restore(d, 1, like=state)
        # restart on a DIFFERENT mesh shape (elastic scaling)
        mesh_b = make_host_mesh(data=4, model=2)
        cfg_b = cfg.with_policy(ShardingPolicy(batch=batch_axes(mesh_b), tp_size=2))
        state_b = reshard_state(host, cfg_b, mesh_b)
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(state_b)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # and it can actually take a train step on the new mesh
        shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state_b)
        specs = SH.train_state_specs(cfg_b, shapes, mesh_b)
        step = jax.jit(Md.make_train_step(cfg_b, opt, param_specs=specs["params"]))
        toks = jnp.zeros((4, 16), jnp.int32)
        batch = {"tokens": toks, "labels": toks, "mask": jnp.ones((4,16), jnp.float32)}
        with jax.set_mesh(mesh_b):
            state_b2, m = step(state_b, batch)
        assert np.isfinite(float(m["loss"]))
    print("ELASTIC_OK")
""")


@pytest.mark.tier2
def test_elastic_reshard_subprocess():
    env = dict(os.environ, PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _ELASTIC], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "ELASTIC_OK" in r.stdout


_ELASTIC_GP = textwrap.dedent("""
    import os, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import engine
    from repro.ckpt.checkpoint import save, restore
    from repro.ckpt.elastic import reshard_gp_state
    from repro.gp import GPSession, MeshTopology
    from repro.launch.mesh import make_host_mesh

    rng = np.random.RandomState(3)
    X_rows = np.abs(rng.randn(128, 2)).astype(np.float32) + 0.5
    y = (X_rows[:, 0] ** 2 / X_rows[:, 1]).astype(np.float32)

    # islands=4 run on a (data=2, model=2, pod=2) mesh, a few generations in
    s = GPSession(pop_size=16, generations=4, kernel="r", islands=4,
                  migrate_every=100,  # no mid-run migration: pure evolution
                  topology=MeshTopology(data=2, model=2, pod=2))
    s.fit(X_rows, y)
    cfg = s._cfg
    host = jax.tree.map(np.asarray, jax.device_get(s.state))

    with tempfile.TemporaryDirectory() as d:
        save(host, d, 1)
        back = restore(d, 1, like=host)
        # restart on a DIFFERENT pod/model split (elastic GP scaling):
        # 4 islands over pod=4, each population unsharded (model=1)
        mesh_b = make_host_mesh(data=2, model=1, pod=4)
        state_b = reshard_gp_state(back, cfg, mesh_b, pod_axis="pod")
        for a, b in zip(jax.tree.leaves(host), jax.tree.leaves(state_b)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # the champion survived re-placement bit-for-bit
        assert float(jnp.min(state_b.best_fitness)) == float(np.min(host.best_fitness))
        # and the resharded state can actually take a step on the new mesh
        step, specs = engine.sharded_evolve_step(cfg, mesh_b, pod_axis="pod")
        from repro.data.loader import pad_feature_major
        X_fm, yy, w = pad_feature_major(X_rows.T.copy(), y, 2)
        Xd = jax.device_put(jnp.asarray(X_fm), NamedSharding(mesh_b, P(None, "data")))
        yd = jax.device_put(jnp.asarray(yy), NamedSharding(mesh_b, P("data")))
        wd = jax.device_put(jnp.asarray(w), NamedSharding(mesh_b, P("data")))
        with jax.set_mesh(mesh_b):
            state_b2 = jax.jit(step)(state_b, Xd, yd, wd)
        assert int(jnp.max(state_b2.generation)) == int(np.max(host.generation)) + 1
        assert float(jnp.min(state_b2.best_fitness)) <= float(np.min(host.best_fitness))
    print("ELASTIC_GP_OK")
""")


@pytest.mark.tier2
def test_elastic_gp_reshard_subprocess():
    """A GPState from an islands=4 run saved on a (2,2,2) mesh restores
    and resharded onto a (2,1,4) mesh bit-identically — champion
    included — and the new mesh can evolve it further."""
    env = dict(os.environ, PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _ELASTIC_GP], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "ELASTIC_GP_OK" in r.stdout
