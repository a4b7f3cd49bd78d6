"""Pallas kernel sweep: shapes × dtypes × fitness kernels × gather modes,
asserted allclose against the pure-jnp oracle (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fitness import FitnessSpec
from repro.core.trees import TreeSpec, generate_population
from repro.kernels import ops as kops
from repro.kernels.ref import fitness_ref


def _case(depth, F, D, pop, seed, genome="tree"):
    spec = TreeSpec(max_depth=depth, n_features=F, n_consts=8, genome=genome)
    op, arg = generate_population(jax.random.PRNGKey(seed), pop, spec)
    X = jnp.asarray(np.random.RandomState(seed).randn(F, D).astype(np.float32))
    y = jnp.asarray((np.random.RandomState(seed + 1).rand(D) * 3).astype(np.float32))
    return spec, op, arg, X, y


@pytest.mark.parametrize("depth", [2, 3, 5])
@pytest.mark.parametrize("F,D", [(1, 9), (2, 37), (9, 500), (16, 1030)])
@pytest.mark.parametrize("genome", ["tree", "postfix"])
def test_kernel_matches_oracle(depth, F, D, genome):
    spec, op, arg, X, y = _case(depth, F, D, pop=21, seed=depth * 100 + F,
                                genome=genome)
    fs = FitnessSpec("r")
    got = kops.fitness(op, arg, X, y, spec.const_table(), spec, fs)
    want = fitness_ref(op, arg, X, y, spec.const_table(), spec, fs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("kern,kw", [("c", dict(n_classes=3)),
                                     ("m", dict(precision=0.5))])
def test_kernel_classify_match(kern, kw):
    spec, op, arg, X, y = _case(4, 4, 150, pop=16, seed=7)
    fs = FitnessSpec(kern, **kw)
    got = kops.fitness(op, arg, X, y, spec.const_table(), spec, fs)
    want = fitness_ref(op, arg, X, y, spec.const_table(), spec, fs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


def test_kernel_large_feature_count():
    """LIGO-shaped: F=1373 makes a 1381-row terminal table, which the
    tile picker answers with small data tiles."""
    spec, op, arg, X, y = _case(5, 1373, 256, pop=8, seed=11)
    fs = FitnessSpec("c", n_classes=2)
    got = kops.fitness(op, arg, X, y, spec.const_table(), spec, fs)
    want = fitness_ref(op, arg, X, y, spec.const_table(), spec, fs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


def test_kernel_dtype_bf16_data():
    spec, op, arg, X, y = _case(3, 4, 128, pop=8, seed=3)
    fs = FitnessSpec("r")
    got = kops.fitness(op, arg, X.astype(jnp.bfloat16), y, spec.const_table(), spec, fs)
    want = fitness_ref(op, arg, X.astype(jnp.bfloat16).astype(jnp.float32), y,
                       spec.const_table(), spec, fs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-2, atol=2e-2)


def test_tile_picker_respects_budget():
    from repro.kernels.ops import pick_tiles, _VMEM_BUDGET
    for F in (2, 64, 1373):
        K = F + 8  # terminal-table rows: features + constants
        pb, db = pick_tiles(K, 63, 100, 1 << 20)
        assert db >= 128
        base = 4 * (4 * K * db + 4 * pb * 64 * db)
        assert base <= _VMEM_BUDGET * 1.05


def test_interpret_mode_is_decided_in_one_place(monkeypatch):
    """Explicit values win; otherwise compiled kernels on a TPU backend and
    the interpreter everywhere else."""
    from repro.kernels.gp_eval import interpret_mode

    assert interpret_mode(True) is True and interpret_mode(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert interpret_mode() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert interpret_mode() is False and interpret_mode(None) is False


def test_terminal_table_parts_sum_exactly():
    """The three bf16 value parts of every finite terminal-table entry
    sum back to the f32 entry bit for bit, and the code part marks each
    non-finite entry, so the kernels' one-hot matmul lookups are exact
    on any MXU precision."""
    from repro.kernels.gp_eval import terminal_table

    r = np.random.RandomState(0)
    X = np.concatenate([r.randn(3, 512) * 10.0 ** r.randint(-20, 20, (3, 1)),
                        np.array([[0.0, -0.0, 1.0, -3.5, 1e-30, 3.4e38,
                                   -1.1754944e-38, 16777215.0] * 64]),
                        np.array([[np.nan, np.inf, -np.inf, 2.0] * 128])]
                       ).astype(np.float32)
    consts = np.array([1, 2, -1, -2.5], np.float32)
    parts = np.asarray(terminal_table(jnp.asarray(X), jnp.asarray(consts)))
    assert parts.dtype == jnp.bfloat16 and parts.shape == (4, 9, 512)
    f = parts.astype(np.float32)
    assert np.isfinite(f).all()
    want = np.concatenate([X, np.broadcast_to(consts[:, None], (4, 512))])
    fin = np.isfinite(want)
    np.testing.assert_array_equal(((f[0] + f[1]) + f[2])[fin], want[fin])
    code = np.select([fin, np.isnan(want), want > 0], [0, 1, 2], 3)
    np.testing.assert_array_equal(f[3], code)


def test_builtin_kernels_never_take_the_reference_fallback():
    """ops.fitness hands kernels without a moment pass to the un-tiled
    reference; every built-in kernel has one, so on a TPU the Pallas
    path is the only one they reach."""
    from repro.core.fitness import get_kernel

    for name in ("r", "c", "m", "mse", "pearson", "r2"):
        assert get_kernel(name).moments is not None, name
