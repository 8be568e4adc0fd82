#!/usr/bin/env python3
"""Readings of the control, which set the upper ends of a cell's limits.

    python3 benchmark/control.py --workload dwell-z21.fleet-day-5m \\
        --seeds 11 12 13

For each seed, the rows of the window's first job (job 1), as a run
makes them, go through the numpy reference twice: in float64, and in
float32 put in the program's place (``control``), or in float64 with
float32 sums (``sums_f32``). Each prints its compared numbers and the
verdict that a run's limits give them (``correct``, ``checks``) as one
JSON line. The benchmark's own runs never run this; it needs no chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    from benchmark import compare, harness, traffic

    cell = harness.load_cell(ROOT, args.workload)
    job = cell.config["job"]
    for seed in args.seeds:
        columns = traffic.make_job(cell.traffic, seed, 1)
        for name, dtype, sum_dtype in (("control", np.float32, None),
                                       ("sums_f32", np.float64, np.float32)):
            t = time.perf_counter()
            readings = compare.control_readings(columns, job, dtype=dtype,
                                                sum_dtype=sum_dtype)
            correct, checks = compare.verdict(readings, cell.config["limits"])
            print(json.dumps({"workload": cell.name, "seed": seed,
                              "variant": name, "correct": correct,
                              "checks": checks,
                              "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
