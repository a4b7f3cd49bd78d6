"""Context-parallel (seq-sharded-cache) decode attention == single-device
attn_decode, including the cache write landing on the owning shard."""
import os
import subprocess
import sys
import textwrap
import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_host_mesh
    from repro.launch.serving import cp_decode_attention
    from repro.models.layers import AttnDims, attn_decode, attn_init

    mesh = make_host_mesh(data=8, model=1)
    dims = AttnDims(d_model=32, n_heads=4, n_kv=2, d_head=8)
    p = attn_init(jax.random.PRNGKey(0), dims)
    rng = np.random.RandomState(0)
    B, S = 2, 64
    ck = jnp.asarray(rng.randn(B, S, 2, 8).astype(np.float32) * 0.3)
    cv = jnp.asarray(rng.randn(B, S, 2, 8).astype(np.float32) * 0.3)

    for cur_len in (0, 7, 13, 40, 63):
        x = jnp.asarray(rng.randn(B, 1, 32).astype(np.float32) * 0.3)
        want_o, want_k, want_v = attn_decode(p, x, ck, cv,
                                             jnp.asarray(cur_len), dims)
        with jax.set_mesh(mesh):
            got_o, got_k, got_v = jax.jit(
                lambda p, x, ck, cv, L: cp_decode_attention(
                    p, x, ck, cv, L, dims, mesh, seq_axis="data"))(
                p, x, ck, cv, jnp.asarray(cur_len, jnp.int32))
        np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(got_k), np.asarray(want_k),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(got_v), np.asarray(want_v),
                                   rtol=1e-6, atol=1e-6)
        ck, cv = got_k, got_v  # roll the cache forward
    print("CP_DECODE_OK")
""")


@pytest.mark.tier2
def test_cp_decode_matches_reference():
    env = dict(os.environ, PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "CP_DECODE_OK" in r.stdout
