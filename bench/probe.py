#!/usr/bin/env python3
"""Readings for setting the benchmark's limits, many runs of
one cell in one process (the benchmark's own runs never call this):

    # the compared numbers of the program on a dozen seeds
    python3 bench/probe.py --workload kat7-90k.tree-fit --seconds 10 \\
        --seeds 1,2,3,4,5,6,7,8,9,10,11,12

    # the same with the control (the reference in bfloat16 put in the
    # program's place), whose runs must come out not correct
    python3 bench/probe.py --workload kat7-90k.tree-fit --seconds 4 \\
        --seeds 1,2,3 --control

Each run prints one JSON line: the seed, whether it was the
control, `correct`, the end-to-end numbers and every compared number
with its limit.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    import run

    run._own_cache()
    from harness.cells import load_cell

    for seed in [int(s) for s in args.seeds.split(",")]:
        cell = load_cell(args.workload)
        result, rec = run.run_cell(args.workload, seed, args.seconds,
                                   False, cell=cell, control=args.control)
        print(json.dumps({
            "seed": seed, "control": args.control,
            "correct": result["correct"],
            "attempted": result["attempted"], "failed": result["failed"],
            "e2e": rec["e2e"], "work": rec["work"],
            "checks": result["checks"], "worst": rec["worst"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
