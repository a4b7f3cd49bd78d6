"""Every cell's parts are found by name, and BENCHMARK.json keeps to the
shape its check reads."""
import json
import re

import pytest

from harness.cells import (BENCH, ROOT, driver, load_cell, metric_reader,
                           peaks_for)

BENCHMARK = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
WORKLOADS = [w["name"] for w in BENCHMARK["workloads"]]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_parts_found_by_name(workload):
    cell = load_cell(workload)
    assert (BENCH / "harness" / f"{cell.traffic['driver']}.py").is_file()
    assert callable(driver(cell.traffic["driver"]))
    generator = cell.config["dataset"]["generator"]
    assert (BENCH / "datasets" / f"{generator}.py").is_file()
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    reported = {m["name"] for m in cell.end_to_end}
    for m in cell.per_layer:
        assert m["moves"] in reported, (workload, m["name"])
        assert callable(metric_reader(m["name"]))
    assert "limits" in cell.config and all(
        isinstance(v, float) for v in cell.config["limits"].values())


def test_every_config_and_metric_has_its_file():
    for c in BENCHMARK["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
    for m in BENCHMARK["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
    for w in BENCHMARK["workloads"]:
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()


def test_names_units_and_sources():
    names = [m["name"] for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]]
    names += WORKLOADS + [c["name"] for c in BENCHMARK["configs"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BENCHMARK["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    layers = {m["layer"] for m in BENCHMARK["per_layer"]}
    assert all(1 <= len(x) <= 200 and "\n" not in x for x in layers)


def test_unknown_device_kind_is_an_error():
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("cpu")
