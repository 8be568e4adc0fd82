#!/usr/bin/env python
"""Peak-RSS probe for the bounded path's cross-chunk merge.

Reproduces the adversarial shape (normal sigma ~0.5 deg ->
near-unique z21 keys, output ~= input) and measures peak RSS of
``run_job_fast(..., max_points_in_flight=...)`` with the in-RAM merge
vs the disk-spill merge (``merge_spill_dir``), each in a fresh
subprocess so high-water marks don't pollute each other. Sinks to
arrays: egress (the at-scale path). Prints one JSON line per mode:

    {"mode": "ram"|"spill", "peak_rss_gb": ..., "seconds": ...,
     "rows": ..., "n": ..., "chunks": ...}

Usage:
    PYTHONPATH=. python tools/mem_probe.py \
        [--n 20000000] [--chunk 2000000] [--modes ram,spill]

The probe is CPU-only (forces jax_platforms=cpu): merge behavior is
host-side.

``--fleet-rss SPEC`` switches to the serve-fleet memory probe
(heatmap_tpu.tilefs): spawn N real backend processes over the spec,
sweep the tile universe against every backend so store pages actually
fault in, and report the fleet's total Pss from
``/proc/<pid>/smaps_rollup`` — Pss, not Rss, because the mmap'd tilefs
store's whole point is that N backends *share* the page-cache copy of
the level arrays, and Pss divides shared pages by their mapper count
while Rss would charge every backend the full store. Pass
``--fleet-rss-heap SPEC`` too and the probe prints both legs plus the
mapped/heap ratio (sub-linear fleet memory is the tilefs acceptance
claim; tools/load_gen.py --cold-vs-warm embeds the same measurement in
BENCH_serve.json as ``serve:fleet_rss_ratio``).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

_CHILD = """
import json, os, resource, sys, time
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

from heatmap_tpu.io.hmpb import HMPBSource
from heatmap_tpu.io.sinks import LevelArraysSink
from heatmap_tpu.pipeline import BatchJobConfig, run_job_fast
from heatmap_tpu.pipeline import batch as batch_mod

hmpb, out_dir, spill_dir, chunk = sys.argv[1:5]
chunk = int(chunk)
if spill_dir == "-":
    # "ram" mode must measure the pure in-RAM fold: disable the
    # AUTO_SPILL_ROWS conversion that is now the production default.
    batch_mod.AUTO_SPILL_ROWS = 1 << 62
cfg = BatchJobConfig()
t0 = time.perf_counter()
stats = run_job_fast(
    HMPBSource(hmpb), LevelArraysSink(out_dir), cfg,
    max_points_in_flight=chunk,
    merge_spill_dir=spill_dir if spill_dir != "-" else None,
)
dt = time.perf_counter() - t0
peak_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
print(json.dumps({
    "peak_rss_gb": round(peak_kb / (1 << 20), 2),
    "seconds": round(dt, 1),
    "rows": stats.get("rows"),
}), flush=True)
"""


def build_points(path: str, n: int, seed: int = 3) -> None:
    from heatmap_tpu.io.hmpb import write_hmpb

    rng = np.random.default_rng(seed)
    lat = rng.normal(47.6, 0.5, n)
    lon = rng.normal(-122.3, 0.5, n)
    routed = rng.integers(0, 8, n).astype(np.int32)
    write_hmpb(path, lat, lon, routed, [f"u{i}" for i in range(8)])


def run_mode(hmpb: str, mode: str, chunk: int, work: str) -> dict:
    out_dir = os.path.join(work, f"out-{mode}")
    spill = os.path.join(work, "spill") if mode == "spill" else "-"
    env = dict(os.environ)
    env["PYTHONPATH"] = "." + os.pathsep + env.get("PYTHONPATH", "")
    t0 = time.perf_counter()
    r = subprocess.run(
        [sys.executable, "-c", _CHILD, hmpb, out_dir, spill, str(chunk)],
        capture_output=True, text=True, env=env,
    )
    if r.returncode != 0:
        raise SystemExit(f"{mode} child failed:\n{r.stderr[-2000:]}")
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    rec.update(mode=mode, wall_s=round(time.perf_counter() - t0, 1))
    return rec


def pss_kb(pid: int) -> tuple:
    """``(kilobytes, source)`` for one process: Pss from smaps_rollup
    (shared file pages split across their mappers — the honest number
    for an mmap'd fleet), falling back to VmRSS where the kernel lacks
    the rollup file, ``(None, "unavailable")`` off-Linux."""
    try:
        with open(f"/proc/{pid}/smaps_rollup") as f:
            for line in f:
                if line.startswith("Pss:"):
                    return int(line.split()[1]), "pss"
    except OSError:
        pass
    try:
        with open(f"/proc/{pid}/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]), "rss"
    except OSError:
        pass
    return None, "unavailable"


def measure_fleet_pss(spec: str, n: int, paths, *,
                      cache_bytes: int = 8 << 20) -> dict:
    """Total proportional RSS of ``n`` real backend processes serving
    ``spec`` after each has answered every path in ``paths``.

    Sweeps every backend *directly* (not through the router) so all of
    them fault the same store pages in — least-load routing would leave
    the measurement at the mercy of which backend won each request. The
    heap tile cache is kept small on purpose: the probe measures the
    store's memory, not cached render bytes.
    """
    from heatmap_tpu.serve.fleet import FleetSupervisor

    import http.client as http_client

    rows = []
    with FleetSupervisor(spec, n, cache_bytes=cache_bytes,
                         probe_interval_s=0.25) as sup:
        sup.start()
        for bid in sorted(sup.router.backends):
            client = sup.router.backends[bid]
            host, port = client.address.rsplit(":", 1)
            conn = http_client.HTTPConnection(host, int(port), timeout=30)
            for p in paths:
                conn.request("GET", p)
                conn.getresponse().read()
            conn.close()
        for bid in sorted(sup._handles):
            proc = getattr(sup._handles[bid], "proc", None)
            if proc is None:  # thread-mode fleet: nothing to attribute
                continue
            kb, source = pss_kb(proc.pid)
            rows.append({"backend": bid, "pid": proc.pid,
                         "kb": kb, "source": source})
    measured = [r for r in rows if r["kb"] is not None]
    total_kb = sum(r["kb"] for r in measured)
    return {
        "spec": spec, "n": n, "paths": len(paths),
        "total_mb": round(total_kb / 1024, 1) if measured else None,
        "per_backend_mb": [round(r["kb"] / 1024, 1) for r in measured],
        "source": measured[0]["source"] if measured else "unavailable",
    }


def fleet_rss_mode(args) -> int:
    """``--fleet-rss``: mapped (and optionally heap) fleet Pss legs."""
    import sys as _sys

    _sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from load_gen import tile_universe

    from heatmap_tpu.serve import TileStore

    universe = tile_universe(TileStore(args.fleet_rss), args.fleet_tiles)
    paths = [f"/tiles/{layer}/{z}/{x}/{y}.{fmt}"
             for layer, z, x, y, fmt in universe]
    mapped = measure_fleet_pss(args.fleet_rss, args.fleet_n, paths)
    print(json.dumps({"leg": "mapped", **mapped}), flush=True)
    if args.fleet_rss_heap:
        heap = measure_fleet_pss(args.fleet_rss_heap, args.fleet_n, paths)
        print(json.dumps({"leg": "heap", **heap}), flush=True)
        ratio = (round(mapped["total_mb"] / heap["total_mb"], 4)
                 if mapped["total_mb"] and heap["total_mb"] else None)
        print(json.dumps({"pss_ratio": ratio, "n": args.fleet_n}),
              flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=20_000_000)
    ap.add_argument("--chunk", type=int, default=2_000_000)
    ap.add_argument("--modes", default="ram,spill")
    ap.add_argument("--workdir", default=None,
                    help="default: a fresh temp dir (removed on exit)")
    ap.add_argument("--fleet-rss", default=None, metavar="SPEC",
                    help="serve-fleet Pss probe over this store spec "
                    "(e.g. tilefs:levels/) instead of the merge probe")
    ap.add_argument("--fleet-rss-heap", default=None, metavar="SPEC",
                    help="heap comparison leg (e.g. arrays:levels/); "
                    "with --fleet-rss, also prints the Pss ratio")
    ap.add_argument("--fleet-n", type=int, default=3,
                    help="backends per fleet leg")
    ap.add_argument("--fleet-tiles", type=int, default=128,
                    help="tile universe size swept per backend")
    args = ap.parse_args()

    if args.fleet_rss:
        return fleet_rss_mode(args)

    import shutil

    work = args.workdir or tempfile.mkdtemp(prefix="mem-probe-")
    try:
        hmpb = os.path.join(work, "pts.hmpb")
        build_points(hmpb, args.n)
        for mode in args.modes.split(","):
            rec = run_mode(hmpb, mode.strip(), args.chunk, work)
            rec.update(n=args.n, chunks=-(-args.n // args.chunk))
            print(json.dumps(rec), flush=True)
    finally:
        if args.workdir is None:
            shutil.rmtree(work, ignore_errors=True)
    return 0


if __name__ == "__main__":
    main()
