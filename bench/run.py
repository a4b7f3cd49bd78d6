#!/usr/bin/env python3
"""Chip benchmark of the GP system: one cell per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is one entry of `workloads` in BENCHMARK.json: a configuration
(`bench/configs/<config>.json`, whose `dataset` names its generator in
`bench/datasets/`) under a traffic mix (`bench/traffic/<traffic>.json`,
whose `driver` names the loop `bench/harness/<driver>.py` that drives
it). The run sets up (data from the seed, compile or cache load, one
warm pass of the cell's shapes), measures for `--seconds`, then checks
what the window produced against the plain reference in
`bench/reference/`.

With `--trace 0` the result carries the cell's end-to-end metrics; with
`--trace 1` the window runs under the profiler and the result carries
the per-layer metrics, each read by `bench/metrics/<metric>.py` from the
reduced trace, the program's counters and the driver's spans.

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics`, `device` (and `breakdown` with
`--trace 1`), then `checks`: each compared number beside its limit. The
same numbers are the last lines on standard error. The run exits 1, and
prints no result, when JAX finds no TPU or fewer chips than the cell
asks for, or when the repository's `src/` is missing.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# the persistent compile cache lives at a fixed path inside the checkout
CACHE_DIR = ROOT / ".jax_cache"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def _per_layer(cell, rec, window, peaks) -> dict:
    from harness.cells import metric_reader

    ctx = {"trace": window.reduced, "work": rec["work"],
           "counters": rec["counters"], "spans": rec["spans"],
           "peaks": peaks, "chips": cell.chips}
    out = {}
    for m in cell.per_layer:
        value = metric_reader(m["name"], cell.root)(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, cell=None, control: bool = False):
    """One run of one cell; returns (result dict, run record). With
    `control` the bfloat16 reference is put in the program's place in the
    checks (see `bench/probe.py`); the benchmark's own runs never set it."""
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit(f"bench: no repository source at {ROOT / 'src'}")
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    from harness.cells import driver, load_cell, peaks_for
    from harness.window import Window

    cell = cell if cell is not None else load_cell(workload)
    drive = driver(cell.traffic["driver"], cell.root)
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise SystemExit(f"bench: JAX found no TPU (device 0 is "
                         f"{devices[0].platform!r}); this benchmark runs on "
                         f"the chip only")
    if len(devices) < cell.chips:
        raise SystemExit(f"bench: {workload} needs {cell.chips} chips, JAX "
                         f"found {len(devices)}")
    used = devices[:cell.chips]
    peaks = peaks_for(devices[0].device_kind) if require_tpu else {}
    from repro.runtime.compile_cache import CacheProbe, enable_compile_cache

    enable_compile_cache()
    probe = CacheProbe()
    window = Window(T_START, trace, used,
                    float(cell.traffic.get("trace_seconds", 5.0)))
    rec = drive(cell, seed, seconds, window, control=control)
    checks = rec["checks"]
    correct = all(value <= limit for _, value, limit in checks)
    result = {"correct": correct, "attempted": rec["attempted"],
              "failed": rec["failed"]}
    if trace:
        red = window.reduce_trace()
        result["metrics"] = _per_layer(cell, rec, window, peaks)
    else:
        metrics = {m["name"]: {"value": rec["e2e"][m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in rec["e2e"]}
        metrics["setup_s"] = {"value": window.setup_s, "unit": "s"}
        result["metrics"] = metrics
    dev = devices[0]
    result["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(used),
                        "memory_peak_bytes": window.memory_peak_bytes}
    if trace:
        result["device"]["busy_s"] = red.mean(red.busy_s)
        result["device"]["window_s"] = red.window_s
        result["breakdown"] = {"device_ops": red.top_ops,
                               "idle_gaps": red.idle_gaps}
    log(f"bench: {workload} seed {seed}: setup {window.setup_s:.3f} s, window "
        f"{window.elapsed:.3f} s, {window.compiles} compiles in the window, "
        f"compile cache {probe.hits} hits / {probe.misses} misses")
    if trace:
        log(f"bench: trace of {red.window_s:.3f} s stopped in "
            f"{window.paused:.3f} s (outside the window's clock), read in "
            f"{window.read_s:.3f} s")
    log(f"bench: work {json.dumps(rec['work'])}")
    log(f"bench: widest gap at {json.dumps(rec['worst'])}")
    result["checks"] = {name: {"value": _num(value), "limit": limit}
                        for name, value, limit in checks}
    for name, value, limit in checks:
        log(f"check {name}: {value!r} (limit {limit!r}) "
            f"{'ok' if value <= limit else 'FAILED'}")
    return result, rec


def _num(v: float):
    """JSON has no infinity: an infinite reading prints as a string."""
    return v if math.isfinite(v) else str(v)


def _own_cache():
    """The compile cache at the checkout's fixed path, whatever the
    machine set: without a size limit, so JAX keeps no access-time files
    (with one set, entries were written but every lookup missed)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    CACHE_DIR.mkdir(exist_ok=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    _own_cache()
    try:
        result, _ = run_cell(args.workload, args.seed, args.seconds,
                             bool(args.trace))
    except SystemExit as e:
        log(str(e))
        return 1
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
