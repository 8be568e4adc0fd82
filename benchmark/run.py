#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 benchmark/run.py --workload refjob-z21.fleet-day-5m \\
        --seed 12345 --seconds 30 --trace 0

Run from the root of a checkout. The cell, its configuration and its
traffic mix are found by name through ``BENCHMARK.json``. The last line
of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device`` and, last, ``checks``
(each number compared, with its limit). The compared numbers are also
the last lines of standard error. Without a TPU, or with fewer chips
than the cell asks for, the run exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# Import the benchmark as a package from the checkout's root, not its
# modules from this file's directory.
sys.path[0] = ROOT


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark import harness

    cell = harness.load_cell(ROOT, args.workload)
    return harness.main(cell, seed=args.seed, seconds=args.seconds,
                        trace=bool(args.trace), t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
