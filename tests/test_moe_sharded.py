"""moe_apply_sharded (explicit EP via shard_map) vs the reference path —
subprocess tests (need 8 fake devices)."""
import os
import subprocess
import sys
import textwrap
import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.models import moe as M
    from repro.models.transformer import ShardingPolicy
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(data=2, model=4)
    pol = ShardingPolicy(batch=("data",), model="model", tp_size=4, dp_size=2)
    rng = np.random.RandomState(0)
    d, ff, E, B, S = 16, 32, 8, 4, 8
    x = jnp.asarray(rng.randn(B, S, d).astype(np.float32) * 0.5)

    # divisible experts
    p = M.moe_init(jax.random.PRNGKey(0), d, ff, E)
    y_ref, _ = M.moe_apply(p, x, top_k=2, capacity_factor=8.0)
    with jax.set_mesh(mesh):
        y_sh, _ = jax.jit(lambda p, x: M.moe_apply_sharded(
            p, x, top_k=2, capacity_factor=8.0, policy=pol))(p, x)
    np.testing.assert_allclose(np.asarray(y_sh), np.asarray(y_ref),
                               rtol=2e-5, atol=2e-5)

    # non-divisible experts (granite case): 5 -> padded to 8
    p5 = M.moe_init(jax.random.PRNGKey(1), d, ff, 5)
    y5_ref, _ = M.moe_apply(p5, x, top_k=2, capacity_factor=8.0)
    with jax.set_mesh(mesh):
        y5_sh, _ = jax.jit(lambda p, x: M.moe_apply_sharded(
            p, x, top_k=2, capacity_factor=8.0, policy=pol))(p5, x)
    np.testing.assert_allclose(np.asarray(y5_sh), np.asarray(y5_ref),
                               rtol=2e-5, atol=2e-5)

    # gradients through shard_map + all_to_all + remat
    with jax.set_mesh(mesh):
        g = jax.jit(jax.grad(lambda p, x: M.moe_apply_sharded(
            p, x, top_k=2, policy=pol)[0].astype(jnp.float32).sum()))(p, x)
    assert all(np.isfinite(np.asarray(l)).all() for l in jax.tree.leaves(g))
    print("MOE_SHARDED_OK")
""")


@pytest.mark.tier2
def test_moe_sharded_subprocess():
    env = dict(os.environ, PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "MOE_SHARDED_OK" in r.stdout
