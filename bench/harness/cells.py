"""Find a cell's parts by name: its entry in BENCHMARK.json, its
configuration (`bench/configs/<config>.json`), its traffic mix
(`bench/traffic/<traffic>.json`), the driver the mix names
(`bench/harness/<driver>.py`), the dataset generator the configuration
names (`bench/datasets/<generator>.py`) and the readers of its
per-layer metrics (`bench/metrics/<metric>.py`). Adding a cell, a
configuration, a mix, a driver, a generator or a metric is adding files
and entries; nothing here names one."""
from __future__ import annotations

import dataclasses
import importlib.util
import inspect
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list  # metric entries this cell reports with --trace 0
    per_layer: list  # metric entries this cell reports with --trace 1
    root: Path = ROOT  # the checkout whose bench/ holds the cell's parts


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, workload: str, reported: set) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    return metric.get("moves") in reported if "moves" in metric else True


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = load_json(root / cfg_entry["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{entry['traffic']}.json")
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload, set())]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, workload, reported)]
    return Cell(workload, int(entry["chips"]), config, traffic, e2e, per_layer,
                root)


def _part(folder: str, name: str, function: str, root: Path):
    """The function `function` of `bench/<folder>/<name>.py`; a missing
    file or function is an error that names the file."""
    path = root / "bench" / folder / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"bench: no {folder} part {name!r}: "
                                f"{path} does not exist")
    spec = importlib.util.spec_from_file_location(
        f"bench_{folder}_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    if not callable(getattr(module, function, None)):
        raise AttributeError(f"bench: {path} has no function {function}()")
    return getattr(module, function)


def metric_reader(name: str, root: Path = ROOT):
    """The `read(ctx)` function of `bench/metrics/<name>.py`."""
    return _part("metrics", name, "read", root)


def driver(name: str, root: Path = ROOT):
    """The `run(cell, seed, seconds, window, control=False)` function of
    `bench/harness/<name>.py`, which sets up, measures and checks."""
    return _part("harness", name, "run", root)


def make_dataset(dataset: dict, seed: int, root: Path = ROOT):
    """`(X_rows, y)` from the `make(seed, **shape)` function of
    `bench/datasets/<generator>.py`, given every other key of the
    configuration's `dataset` entry (`rows`, `features`, `classes`, ...).
    A key the generator does not take, or one it needs and is not given,
    is an error, as is data of another shape than the entry states."""
    shape = {k: v for k, v in dataset.items() if k != "generator"}
    make = _part("datasets", dataset["generator"], "make", root)
    try:
        inspect.signature(make).bind(seed, **shape)
    except TypeError as e:
        raise TypeError(f"bench: dataset generator {dataset['generator']!r} "
                        f"and the keys {sorted(shape)} do not match: {e}"
                        ) from None
    X_rows, y = make(seed, **shape)
    want = (shape["rows"], shape["features"])
    if X_rows.shape != want or y.shape != want[:1]:
        raise ValueError(f"bench: dataset generator {dataset['generator']!r} "
                         f"made X {X_rows.shape} and y {y.shape}, the "
                         f"configuration states {want}")
    return X_rows, y


def peaks_for(device_kind: str, root: Path = ROOT) -> dict:
    """The published peaks of `device_kind`; an unknown kind is an error."""
    table = load_json(root / "bench" / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in bench/peaks.json "
                       f"({sorted(table['devices'])})")
    return table["devices"][device_kind]
