"""The main path's Pallas kernels, compiled for a described TPU v5e.

Interpret mode (every other kernel test) runs whatever jnp a kernel body
holds; only Mosaic, the TPU kernel compiler, refuses a lane-unaligned
slice, a lane-dynamic index, an unsupported gather or a block past the
VMEM limit. These tests compile each kernel that `kernels/ops.py` can
reach — the tree kernel, the postfix kernel and both dedup kernels — at
the eval cell's shapes (P=1024, N=63, D=32,768, KAT-7's F=9, plus
LIGO's F=1,373, and the tree kernel at the KAT-7 benchmark cell's
P=100, D=90,000), at the tiles the pickers choose, for a chip that is
described and not attached. Nothing runs; a compile that passes is not a
chip run.

The topology is described inside module-scoped fixtures (only the
worker that runs this file loads the TPU compiler), and the persistent
compilation cache is off around the compiles: their entries could not
be read back without a chip.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import primitives as prim
from repro.core.fitness import FitnessSpec
from repro.core.trees import TreeSpec
from repro.kernels import gp_eval
from repro.kernels import ops as kops

P, DEPTH, D, C = 1024, 5, 32_768, 8
KAT7_F, LIGO_F = 9, 1_373


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_fitness(sharding, genome, kernel, F, **kw):
    """Compiled text of ops.fitness (the Pallas path, interpret off) for
    the described chip."""
    spec = TreeSpec(max_depth=DEPTH, n_features=F, n_consts=C, genome=genome,
                    fn_set=prim.CLASSIFY_SET)
    N = spec.num_nodes
    return kops.fitness.lower(
        _sds(sharding, (P, N), jnp.int32), _sds(sharding, (P, N), jnp.int32),
        _sds(sharding, (F, D), jnp.float32), _sds(sharding, (D,), jnp.float32),
        _sds(sharding, (C,), jnp.float32), spec,
        FitnessSpec(kernel, n_classes=2), interpret=False,
        **kw).compile().as_text()


def _kernels(text):
    return text.count('custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("kernel", ["r", "c", "pearson"])
def test_tree_kernel_compiles(one_chip, kernel):
    assert _kernels(_compile_fitness(one_chip, "tree", kernel, KAT7_F)) == 1


@pytest.mark.parametrize("kernel", ["r", "c", "pearson"])
def test_postfix_kernel_compiles(one_chip, kernel):
    assert _kernels(_compile_fitness(one_chip, "postfix", kernel, KAT7_F)) == 1


@pytest.mark.parametrize("genome", ["tree", "postfix"])
def test_ligo_width_compiles(one_chip, genome):
    """F=1,373: the 1,381-row terminal table (postfix) or feature bank
    (tree) makes the pickers shrink the data tile; the compiler must
    accept the block they choose."""
    assert _kernels(_compile_fitness(one_chip, genome, "c", LIGO_F)) == 1


def test_dedup_path_compiles(one_chip):
    """The whole exact-dedup path through ops: plan build, unique-subtree
    evaluation, the gather kernel and the overflow fallback onto the
    plain postfix kernel. Auto cap (P rows) spills the gather to HBM at
    this size, so the program holds the postfix and spill kernels."""
    text = _compile_fitness(one_chip, "postfix", "c", KAT7_F, dedup="exact")
    assert _kernels(text) == 2


def test_dedup_vmem_gather_kernel_compiles(one_chip):
    """In-VMEM gather of scalar-prefetched rows from a unique table that
    fits beside the postfix tile pick."""
    U = 64
    _, db = kops.pick_tiles_postfix(KAT7_F + C, DEPTH + 1, P, D)
    assert kops._postfix_vmem(KAT7_F + C, DEPTH + 1, 8, db,
                              dedup_rows=U) <= kops._VMEM_BUDGET
    fn = jax.jit(lambda r, u, y, w: gp_eval.eval_fitness_pallas_from_subtrees(
        r, u, y, w, kernel="c", n_classes=2, data_tile=db, interpret=False))
    text = fn.lower(_sds(one_chip, (P,), jnp.int32),
                    _sds(one_chip, (U, D), jnp.float32),
                    _sds(one_chip, (D,), jnp.float32),
                    _sds(one_chip, (D,), jnp.float32)).compile().as_text()
    assert _kernels(text) == 1


def test_dedup_spill_kernel_compiles(one_chip):
    fn = jax.jit(lambda p, y, w: gp_eval.eval_fitness_pallas_from_preds(
        p, y, w, kernel="pearson", data_tile=2048, interpret=False))
    text = fn.lower(_sds(one_chip, (P, D), jnp.float32),
                    _sds(one_chip, (D,), jnp.float32),
                    _sds(one_chip, (D,), jnp.float32)).compile().as_text()
    assert _kernels(text) == 1


def test_tree_fit_block_carries_scopes_and_kernel_name(one_chip, monkeypatch):
    """The KAT-7 tree-fit evolution block (Table 2's population 100 over
    90,000 rows, kernel c), lowered for the described chip: its ops carry
    the engine's layer scopes and the tree kernel its own name, which is
    what a profile of the block shows."""
    from repro.core import engine
    from repro.core.fitness import FitnessSpec as Fit

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    spec = TreeSpec(max_depth=DEPTH, n_features=KAT7_F, n_consts=C,
                    fn_set=prim.CLASSIFY_SET)
    cfg = engine.GPConfig(tree_spec=spec, pop_size=100, tourn_size=10,
                          generations=30, fitness=Fit("c", n_classes=2),
                          eval_impl="pallas")
    state = jax.tree.map(
        lambda a: _sds(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda k: engine.init_state(cfg, k),
                       jax.random.PRNGKey(0)))
    rows = 90_000
    text = engine.evolve_block.lower(
        cfg, state, _sds(one_chip, (KAT7_F, rows), jnp.float32),
        _sds(one_chip, (rows,), jnp.float32), None,
        _sds(one_chip, (), jnp.int32), n_steps=30).as_text(debug_info=True)
    for scope in ("gp.eval", "gp.select_best", "gp.breed", "gp.telemetry"):
        # a name-stack component of some op's location
        assert re.search(rf'["/]{re.escape(scope)}[/"]', text), scope
    assert 'kernel_name = "gp_tree_eval"' in text


@pytest.mark.parametrize("F,pop,rows,tile", [
    (KAT7_F, 100, 90_000, 45_056),   # the kat7-90k.tree-fit cell
    (KAT7_F, P, D, 32_768),
    (LIGO_F, P, D, 2_048),
    (KAT7_F, 100_000, 90_000, 45_056)])
def test_tree_kernel_compiles_at_picked_tile(one_chip, F, pop, rows, tile):
    """gp_tree_eval through ops.fitness at the data tile its picker
    chooses: the KAT-7 cell's two tiles of 45,056 rows, one tile at
    P=1,024, LIGO's width, where 1,373 feature slabs in the bank leave
    a 2,048-row tile, and 100,000 trees, whose moments VMEM never holds
    all at once."""
    assert kops.pick_tiles(F, C, DEPTH, rows)[1] == tile
    spec = TreeSpec(max_depth=DEPTH, n_features=F, n_consts=C,
                    fn_set=prim.CLASSIFY_SET)
    N = spec.num_nodes
    lowered = kops.fitness.lower(
        _sds(one_chip, (pop, N), jnp.int32), _sds(one_chip, (pop, N), jnp.int32),
        _sds(one_chip, (F, rows), jnp.float32),
        _sds(one_chip, (rows,), jnp.float32), _sds(one_chip, (C,), jnp.float32),
        spec, FitnessSpec("c", n_classes=2), interpret=False)
    assert 'kernel_name = "gp_tree_eval"' in lowered.as_text()
    assert _kernels(lowered.compile().as_text()) == 1
