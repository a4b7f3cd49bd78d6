"""Optimizers + gradient compression properties."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.optim.adamw import adamw, adafactor, cosine_schedule
from repro.optim.compress import dequantize, quantize


@pytest.mark.parametrize("make", [adamw, adafactor])
def test_optimizer_descends_quadratic(make):
    opt = make(lr=0.1)
    params = {"w": jnp.asarray(np.random.RandomState(0).randn(8, 4).astype(np.float32)),
              "b": jnp.asarray(np.random.RandomState(1).randn(4).astype(np.float32))}
    state = opt.init(params)

    def loss(p):
        return jnp.sum(p["w"] ** 2) + jnp.sum(p["b"] ** 2)

    l0 = float(loss(params))
    for step in range(50):
        g = jax.grad(loss)(params)
        params, state = opt.update(g, state, params, step)
    assert float(loss(params)) < 0.05 * l0


def test_adafactor_state_is_factored():
    opt = adafactor()
    params = {"w": jnp.zeros((64, 32)), "v": jnp.zeros((16,))}
    st_ = opt.init(params)
    assert st_["stats"]["w"]["r"].shape == (64,)
    assert st_["stats"]["w"]["c"].shape == (32,)
    assert st_["stats"]["v"]["v"].shape == (16,)


def test_cosine_schedule_shape():
    lr = cosine_schedule(1e-3, warmup=10, total=100)
    assert float(lr(0)) == 0.0
    assert float(lr(10)) == pytest.approx(1e-3, rel=1e-5)
    assert float(lr(100)) == pytest.approx(0.0, abs=1e-9)
    assert float(lr(55)) < 1e-3


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), scale=st.floats(1e-6, 1e4))
def test_quantize_roundtrip_error_bound(seed, scale):
    x = np.random.RandomState(seed).randn(64).astype(np.float32) * scale
    q, s = quantize(jnp.asarray(x))
    back = np.asarray(dequantize(q, s))
    assert np.abs(back - x).max() <= float(s) * 0.5 + 1e-12


_COMPRESS_SUBPROCESS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.optim.compress import compressed_psum
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(data=4, model=1)
    rng = np.random.RandomState(0)
    gs = rng.randn(4, 128).astype(np.float32)

    def body(g, r):
        mean, new_r = compressed_psum({"g": g}, "data", {"g": r})
        return mean["g"], new_r["g"]

    f = jax.jit(jax.shard_map(body, mesh=mesh, check_vma=False, in_specs=(P("data"), P("data")),
                                  out_specs=(P("data"), P("data"))))
    with jax.set_mesh(mesh):
        resid = jnp.zeros((4*128 // 4 * 4,), jnp.float32).reshape(512)[:512]*0
        resid = jnp.zeros((512,), jnp.float32)
        g = jnp.asarray(gs.reshape(512))
        mean, resid = f(g, resid)
    true_mean = gs.reshape(4, 128).mean(0)
    got = np.asarray(mean).reshape(4, 128)[0]
    # shared-scale quantization: error of the mean bounded by scale/2
    err = np.abs(got - true_mean).max()
    assert err < np.abs(gs).max() / 127 * 0.75 + 1e-6, err
    # error feedback: residual holds what was lost
    assert np.isfinite(np.asarray(resid)).all()
    print("COMPRESS_OK")
""")


@pytest.mark.tier2
def test_compressed_psum_subprocess():
    env = dict(os.environ, PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _COMPRESS_SUBPROCESS], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "COMPRESS_OK" in r.stdout


def test_error_feedback_converges():
    """EF-compressed SGD must track uncompressed SGD on a quadratic."""
    w = jnp.ones((32,)) * 5.0
    w_ref = jnp.ones((32,)) * 5.0
    resid = jnp.zeros((32,))
    for _ in range(200):
        g = 2 * w
        g_fb = g + resid
        q, s = quantize(g_fb)
        g_hat = dequantize(q, s)
        resid = g_fb - g_hat
        w = w - 0.01 * g_hat
        w_ref = w_ref - 0.01 * (2 * w_ref)
    np.testing.assert_allclose(np.asarray(w), np.asarray(w_ref), atol=0.05)
