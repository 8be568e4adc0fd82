#!/usr/bin/env python
"""Streaming (BASELINE config 4) on-chip benchmark: the decayed
micro-batch update step at the headline window.

Measures the compiled HeatmapStream update loop — per-batch exponential
time decay + window binning on a donated device raster — and prints one
JSON line per (backend, batch) cell:

    {"check": "stream", "backend": ..., "batch": ..., "window": "z11",
     "pts_per_s": ..., "steps_per_s": ..., "device": ...}

Backends route the shard-local binning (ops.histogram): "xla" and
"partitioned" everywhere, "pallas" where Mosaic compiles. The routing
decision for StreamConfig's default backend follows the same rule as
the batch path: flip only on measured on-chip wins.

    PYTHONPATH=. python tools/bench_stream.py [--state STATE.jsonl] [--cpu]

``--state`` appends one JSONL row per completed cell and skips cells
already present, so a rerun measures only the cells still missing.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np


def _done_cells(state_path: str) -> set:
    done = set()
    if not state_path or not os.path.exists(state_path):
        return done
    with open(state_path) as f:
        for line in f:
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if rec.get("check") == "stream":
                done.add((rec.get("backend"), rec.get("batch"),
                          rec.get("device")))
    return done


def _append(state_path: str, rec: dict) -> None:
    if not state_path:
        return
    with open(state_path, "a") as f:
        f.write(json.dumps(rec) + "\n")
        f.flush()
        os.fsync(f.fileno())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--zoom", type=int, default=11)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batches", default=None,
                    help="comma list of batch sizes (default 262144)")
    ap.add_argument("--backends", default="auto,xla,partitioned,pallas",
                    help="'auto' measures the routed default so the "
                    "decision rule can compare it against each pinned "
                    "backend")
    ap.add_argument("--state", default=None)
    ap.add_argument("--cpu", action="store_true",
                    help="run on the CPU backend")
    args = ap.parse_args()

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    from heatmap_tpu.ops import window_from_bounds
    from heatmap_tpu.streaming import HeatmapStream, StreamConfig

    device = jax.devices()[0].platform
    batches = ([int(b) for b in args.batches.split(",")]
               if args.batches else [1 << 18])
    window = window_from_bounds((35.0, 55.0), (-5.0, 20.0),
                                zoom=args.zoom, align_levels=4)
    print(json.dumps({"stage": "setup", "device": device,
                      "window": list(window.shape)}), flush=True)
    done = _done_cells(args.state)

    rng = np.random.default_rng(7)
    for backend in args.backends.split(","):
        for batch in batches:
            key = (backend, batch, device)
            if key in done:
                print(json.dumps({"skip": "done",
                                  "backend": backend, "batch": batch}),
                      flush=True)
                continue
            cfg = StreamConfig(window=window, half_life_s=600.0,
                               pad_to=batch, backend=backend)
            stream = HeatmapStream(cfg)
            lat = rng.uniform(35.0, 55.0, (args.steps, batch))
            lon = rng.uniform(-5.0, 20.0, (args.steps, batch))
            try:
                # Warm step compiles; excluded from the timed loop.
                stream.update(lat[0], lon[0], t=0.0)
                stream.snapshot()
                t0 = time.perf_counter()
                for i in range(1, args.steps):
                    stream.update(lat[i % args.steps],
                                  lon[i % args.steps], t=float(i))
                np.asarray(stream.snapshot())
                dt = time.perf_counter() - t0
            except Exception as e:  # noqa: BLE001 — report, keep sweeping
                print(json.dumps({"check": "stream", "backend": backend,
                                  "batch": batch, "device": device,
                                  "error": f"{type(e).__name__}: {e}"[:300]}),
                      flush=True)
                continue
            steps = args.steps - 1
            rec = {
                "check": "stream", "backend": backend, "batch": batch,
                "window": f"z{args.zoom}", "device": device,
                "steps_per_s": round(steps / dt, 2),
                "pts_per_s": round(steps * batch / dt, 1),
            }
            print(json.dumps(rec), flush=True)
            _append(args.state, rec)


if __name__ == "__main__":
    main()
