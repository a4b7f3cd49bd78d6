"""CPU tests of the benchmark harness (no chip): `pytest bench/tests`."""
import os
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")


@pytest.fixture
def small_cell():
    """A cell of BENCHMARK.json cut to a size a CPU test can run: the
    same driver, reference and checks, on fewer rows and trees, with the
    program's jnp backend in place of the Pallas kernels."""
    from harness.cells import load_cell

    def make(workload: str):
        cell = load_cell(workload)
        cell.config["dataset"]["rows"] = 2048
        cell.config["gp"].update(pop_size=16, generations=4, backend="jnp",
                                 require_backend="jnp")
        return cell

    return make


@pytest.fixture
def fresh_jax(tmp_path, monkeypatch):
    """Programs traced anew (a planted fault must not hit a compiled
    program from an earlier test) and a compile cache of the test's own."""
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    jax.clear_caches()
    yield
    jax.clear_caches()
