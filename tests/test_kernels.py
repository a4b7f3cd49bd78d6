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
    """The tree kernel's tile fits the VMEM budget at every width it
    accepts, and the moment tile is the merge tile the tree fitness has
    always had."""
    from repro.kernels.ops import _VMEM_BUDGET, _tree_vmem, pick_tiles
    for F, moment in ((2, 1024), (64, 1024), (1373, 256)):
        pb, db, mt = pick_tiles(F, 8, 5, 1 << 20)
        assert mt == moment and db % 1024 == 0 and db % mt == 0
        assert _tree_vmem(F, 8, 5, pb, db) <= _VMEM_BUDGET


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


# --- the tree kernel (gp_tree_eval), called alone ----------------------------


def _tree_moments(op, arg, X, y, spec, fs, data_tile, moment_tile):
    """gp_tree_eval alone, padded as ops pads: P to the pop tile, D to
    the data tile with zero weight, only the real moment tiles merged."""
    from repro.kernels.gp_eval import eval_fitness_pallas_tree

    P, D = op.shape[0], X.shape[1]
    pp, pd = (-P) % 8, (-D) % data_tile
    out = eval_fitness_pallas_tree(
        jnp.pad(op, ((0, pp), (0, 0))), jnp.pad(arg, ((0, pp), (0, 0))),
        jnp.pad(X, ((0, 0), (0, pd))), jnp.pad(y, (0, pd)),
        jnp.pad(jnp.ones((D,), jnp.float32), (0, pd)), spec.const_table(),
        kernel=fs.kernel, n_classes=fs.n_classes, precision=fs.precision,
        data_tile=data_tile, moment_tile=moment_tile,
        n_chunks=-(-D // moment_tile),
        fn_codes=tuple(int(c) for c in spec.fn_set.opcodes))
    return out[:P]


def _tree_fitness(op, arg, X, y, spec, fs, data_tile=1024, moment_tile=1024):
    from repro.core.fitness import get_kernel

    return get_kernel(fs.kernel).reduce_moments(
        _tree_moments(op, arg, X, y, spec, fs, data_tile, moment_tile), fs)


@pytest.mark.parametrize("depth", [2, 3, 4, 5, 6])
@pytest.mark.parametrize("F,D", [(9, 1500), (1373, 300)])
def test_tree_kernel_matches_oracle_bf16(depth, F, D):
    """Through ops.fitness (the picker's tiles), bf16 data, across the
    depths a run uses, at KAT-7's and LIGO's widths."""
    spec, op, arg, X, y = _case(depth, F, D, pop=12, seed=depth * 10 + F)
    Xb = X.astype(jnp.bfloat16)
    fs = FitnessSpec("r")
    got = kops.fitness(op, arg, Xb, y, spec.const_table(), spec, fs)
    want = fitness_ref(op, arg, Xb.astype(jnp.float32), y,
                       spec.const_table(), spec, fs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kern,kw", [("r", {}), ("c", dict(n_classes=3)),
                                     ("m", dict(precision=0.5)),
                                     ("mse", {}), ("pearson", {}),
                                     ("r2", {})])
def test_tree_kernel_fitness_kernels(kern, kw):
    spec, op, arg, X, y = _case(5, 9, 2100, pop=20, seed=5)
    fs = FitnessSpec(kern, **kw)
    got = _tree_fitness(op, arg, X, y, spec, fs, data_tile=2048)
    want = fitness_ref(op, arg, X, y, spec.const_table(), spec, fs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kern", ["r", "c", "m", "mse", "pearson", "r2"])
def test_tree_kernel_moments_bitwise_equal_postfix_kernel(kern):
    """The postfix kernel at data_tile = moment_tile gives the tree
    kernel's moments for the same trees bit for bit: D = 2,100 spans
    three data tiles of 1,024 rows (two of 2,048), and its last merged
    moment tile of 512 rows is part padding, with one more tile of
    padding after it that is never merged."""
    from repro.core.trees import heap_to_postfix
    from repro.kernels.gp_eval import eval_fitness_pallas_postfix

    D, mt = 2100, 512
    spec, op, arg, X, y = _case(5, 9, D, pop=16, seed=13)
    fs = FitnessSpec(kern, n_classes=3, precision=0.5)
    op_p, arg_p = heap_to_postfix(op, arg)
    pd = (-D) % mt
    post = eval_fitness_pallas_postfix(
        op_p, arg_p, (op_p != 0).sum(-1).astype(jnp.int32),
        jnp.pad(X, ((0, 0), (0, pd))), jnp.pad(y, (0, pd)),
        jnp.pad(jnp.ones((D,), jnp.float32), (0, pd)), spec.const_table(),
        stack_size=spec.stack_size, kernel=kern, n_classes=3, precision=0.5,
        data_tile=mt, fn_codes=tuple(int(c) for c in spec.fn_set.opcodes))
    for tile in (1024, 2048):
        got = _tree_moments(op, arg, X, y, spec, fs, tile, mt)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(post))


@pytest.mark.parametrize("kern", ["r", "c", "pearson"])
def test_tree_kernel_reads_nonfinite_features_as_they_are(kern):
    """NaN and ±inf feature values reach the operators unchanged: the
    tree kernel reads a feature's own slab, so the fitness (inf where the
    kernel declares a tree invalid) is the oracle's."""
    spec, op, arg, X, y = _case(4, 3, 1024, pop=16, seed=9)
    X = X.at[1, ::7].set(jnp.nan).at[2, ::5].set(jnp.inf)
    X = X.at[2, 3::11].set(-jnp.inf)
    fs = FitnessSpec(kern, n_classes=3)
    got = np.asarray(_tree_fitness(op, arg, X, y, spec, fs))
    want = np.asarray(fitness_ref(op, arg, X, y, spec.const_table(), spec, fs))
    assert np.isinf(want).any()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_tree_kernel_empty_children_read_zero():
    """A unary function's EMPTY right child hands it nothing stale, and a
    (malformed) binary function over an EMPTY child reads 0.0, as the
    oracle does — with earlier trees of the tile leaving other values in
    the level buffers."""
    from repro.core import primitives as prim

    spec = TreeSpec(max_depth=3, n_features=2, n_consts=8,
                    fn_set=prim.KITCHEN_SINK)
    N = spec.num_nodes
    op = np.zeros((4, N), np.int32)
    arg = np.zeros((4, N), np.int32)
    mul, add, sqrt, neg = (prim.opcode_of(n) for n in ("mul", "add", "sqrt",
                                                       "neg"))
    # tree 0 fills buffers: (x0 * x1) + (x1 * 3.0)
    op[0, :7] = [add, mul, mul, prim.FEATURE, prim.FEATURE, prim.FEATURE,
                 prim.CONST]
    arg[0, :7] = [0, 0, 0, 0, 1, 1, 2]
    # tree 1: neg(sqrt(x1)) — unary chain, right children EMPTY
    op[1, [0, 1, 3]] = [neg, sqrt, prim.FEATURE]
    arg[1, 3] = 1
    # tree 2: add(x0, EMPTY) — malformed, the EMPTY child reads 0.0
    op[2, [0, 1]] = [add, prim.FEATURE]
    # tree 3: a lone constant root
    op[3, 0], arg[3, 0] = prim.CONST, 5
    op, arg = jnp.asarray(op), jnp.asarray(arg)
    X = jnp.asarray(np.random.RandomState(0).randn(2, 1024).astype(np.float32))
    y = jnp.zeros((1024,), jnp.float32)
    fs = FitnessSpec("r")
    got = np.asarray(_tree_fitness(op, arg, X, y, spec, fs))
    want = np.asarray(fitness_ref(op, arg, X, y, spec.const_table(), spec, fs))
    np.testing.assert_allclose(got, want, rtol=1e-6)


@pytest.mark.parametrize("F", [9, 1373])
@pytest.mark.parametrize("D", [9, 150, 90_000])
def test_tree_genome_has_one_kernel(F, D):
    """Every tree-genome call, whatever its rows and features, lowers to
    gp_tree_eval and to no other kernel."""
    spec = TreeSpec(max_depth=5, n_features=F, n_consts=8)
    ints = jax.ShapeDtypeStruct((100, spec.num_nodes), jnp.int32)
    text = str(jax.make_jaxpr(lambda o, a, x, y: kops.fitness(
        o, a, x, y, spec.const_table(), spec, FitnessSpec("c", n_classes=2)))(
        ints, ints, jax.ShapeDtypeStruct((F, D), jnp.float32),
        jax.ShapeDtypeStruct((D,), jnp.float32)))
    assert text.count("pallas_call[") == 1
    assert "name=gp_tree_eval" in text


@pytest.mark.parametrize("pop", [100, 1024, 100_000])
def test_tree_kernel_feature_limit(pop):
    """The bank holds every feature slab of a tile: the picker takes up
    to 3,042 features at 1,024 rows (above the 2,800 the old sublane
    kernel's budget allowed) and raises past its limit, naming the jnp
    backend; ops.fitness traces at the limit whatever the population,
    and refuses a wider call when it is traced."""
    from repro.kernels.ops import _VMEM_BUDGET, _tree_vmem, pick_tiles

    pb, db, _ = pick_tiles(3_042, 8, 5, 90_000)
    assert db >= 1024 and _tree_vmem(3_042, 8, 5, pb, db) <= _VMEM_BUDGET
    with pytest.raises(ValueError, match="jnp backend"):
        pick_tiles(3_043, 8, 5, 90_000)
    wide = TreeSpec(max_depth=5, n_features=3_042, n_consts=8)
    sds = jax.ShapeDtypeStruct
    out = jax.eval_shape(
        lambda op, X, y: kops.fitness(op, op, X, y, wide.const_table(), wide,
                                      FitnessSpec("r")),
        sds((pop, wide.num_nodes), jnp.int32),
        sds((3_042, 90_000), jnp.float32), sds((90_000,), jnp.float32))
    assert out.shape == (pop,)
    spec = TreeSpec(max_depth=3, n_features=4000, n_consts=8)
    op = jnp.zeros((8, spec.num_nodes), jnp.int32)
    with pytest.raises(ValueError, match="features do not fit"):
        kops.fitness(op, op, jnp.zeros((4000, 16), jnp.float32),
                     jnp.zeros((16,), jnp.float32), spec.const_table(), spec,
                     FitnessSpec("r"))


@pytest.mark.parametrize("F", [1, 9, 64, 1373])
@pytest.mark.parametrize("D", [150, 4096, 90_000])
@pytest.mark.parametrize("moment_tile", [256, 1024])
def test_tree_tile_picker_fewest_tiles(F, D, moment_tile):
    """The data tile fits the VMEM budget, is a multiple of 1,024 rows
    and of the moment tile, covers D, and no fewer tiles would fit."""
    from repro.kernels.ops import _VMEM_BUDGET, _tree_vmem, pick_tiles

    pb, db, mt = pick_tiles(F, 8, 5, D, data_tile=moment_tile)
    assert mt <= moment_tile and db % 1024 == 0 and db % mt == 0
    assert _tree_vmem(F, 8, 5, pb, db) <= _VMEM_BUDGET
    n = -(-D // db)
    rows = -(-D // mt) * mt
    assert n * db >= rows
    if n > 1:  # one tile fewer would not fit
        unit = np.lcm(1024, mt)
        fewer = -(-rows // ((n - 1) * unit)) * unit
        assert _tree_vmem(F, 8, 5, pb, fewer) > _VMEM_BUDGET


def test_kat7_cell_gets_two_data_tiles():
    """KAT-7's 90,000 × 9 at population 100: 2 tiles of 45,056 rows,
    merged in moment tiles of 1,024."""
    assert kops.pick_tiles(9, 8, 5, 90_000) == (8, 45_056, 1024)
