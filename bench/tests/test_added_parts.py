"""A new configuration with a new dataset generator, and a new traffic
mix with a new driver, run through `run.py`'s lookup when they are only
added: new files beside a copy of the benchmark, and new entries in its
BENCHMARK.json. No file of the copy is edited."""
import json
import shutil

import pytest

import run
from harness.cells import BENCH, ROOT, load_cell

GENERATOR = '''
import numpy as np


def make(seed, rows, features, classes, scale):
    rng = np.random.RandomState(seed)
    X = rng.uniform(-scale, scale, (rows, features)).astype(np.float32)
    y = (X[:, 0] * X[:, 1] > X[:, 2]).astype(np.float32)
    return X, y
'''

DRIVER = '''
from harness import cells, datagen


def run(cell, seed, seconds, window, control=False):
    """Passes over the data, each relabelling every row."""
    (data_seed,) = datagen.seeds(seed, 1)
    X, y = cells.make_dataset(cell.config["dataset"], data_seed, cell.root)
    window.setup_done()
    passes = wrong = 0
    with window.measure() as clock:
        while clock() < seconds:
            wrong += int(((X[:, 0] * X[:, 1] > X[:, 2]) != y).sum())
            passes += 1
            window.tick(1)
    window.read_memory()
    return {"attempted": passes, "failed": 0,
            "e2e": {"passes_per_s": passes / window.elapsed},
            "checks": [("wrong_labels", float(wrong), 0.0)],
            "work": {"passes": passes, "rows": X.shape[0],
                     "features": X.shape[1]},
            "counters": {}, "spans": {}, "worst": None}
'''

CONFIG = {
    "name": "plane3-2k", "precision": "float32",
    "dataset": {"generator": "plane3", "rows": 2048, "features": 3,
                "classes": 2, "scale": 2.0},
    "gp": {"pop_size": 16, "tourn_size": 4, "generations": 4,
           "max_depth": 4, "n_consts": 8, "kernel": "c", "n_classes": 2,
           "fn_set": ["add", "sub", "mul", "div"], "backend": "jnp",
           "require_backend": "jnp"},
    "limits": {"fitness_gap": 0.00025}, "reduced": [], "assumed": {},
}


@pytest.fixture
def added_root(tmp_path):
    """A copy of the benchmark with a configuration, a generator, a mix
    and a driver added, and their entries appended to BENCHMARK.json."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = tmp_path / "bench"
    for path in ("datasets/plane3.py", "harness/passes.py",
                 "configs/plane3-2k.json", "traffic/passes.json"):
        assert not (bench / path).exists()
    (bench / "datasets" / "plane3.py").write_text(GENERATOR)
    (bench / "harness" / "passes.py").write_text(DRIVER)
    (bench / "configs" / "plane3-2k.json").write_text(json.dumps(CONFIG))
    (bench / "traffic" / "passes.json").write_text(
        json.dumps({"driver": "passes", "about": "relabel every row"}))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "plane3-2k", "source": "a test",
                            "file": "bench/configs/plane3-2k.json",
                            "reduced": [], "why": "a test"})
    spec["workloads"] += [
        {"name": "plane3-2k.tree-fit", "config": "plane3-2k",
         "traffic": "tree-fit", "chips": 1, "why": "a test"},
        {"name": "plane3-2k.passes", "config": "plane3-2k",
         "traffic": "passes", "chips": 1, "why": "a test"}]
    spec["end_to_end"].append(
        {"name": "passes_per_s", "unit": "1/s", "better": "higher",
         "bound": 0.25, "source": "host_clock",
         "workloads": ["plane3-2k.passes"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp_path


@pytest.mark.parametrize("workload, metric", [
    ("plane3-2k.tree-fit", None),  # the fit driver, on the new generator
    ("plane3-2k.passes", "passes_per_s"),  # the new driver
])
def test_added_cell_runs_through_the_lookup(workload, metric, added_root,
                                            fresh_jax):
    cell = load_cell(workload, root=added_root)
    assert cell.root == added_root
    result, rec = run.run_cell(workload, 2**31 + 5, 0.5, False,
                               require_tpu=False, cell=cell)
    assert result["correct"], result["checks"]
    assert rec["work"]["rows"] == 2048
    assert set(result["metrics"]) == {"setup_s"} | ({metric} - {None})
    if metric is None:  # the fit driver published trees over 3 features
        assert rec["worst"]["expression"]


def test_unknown_driver_names_its_path():
    cell = load_cell("kat7-90k.tree-fit")
    cell.traffic["driver"] = "no_such_loop"
    with pytest.raises(FileNotFoundError) as e:
        run.run_cell(cell.name, 1, 1.0, False, require_tpu=False, cell=cell)
    assert str(BENCH / "harness" / "no_such_loop.py") in str(e.value)
