#!/usr/bin/env python
"""End-to-end batch-job benchmark: HMPB ingest -> cascade -> egress.

Generates an HMPB file of synthetic GPS points (hot cluster + fringe,
multiple users incl. rt-/x- routing), runs run_job_fast end to end, and
prints the tracer's stage balance plus a points/sec headline. Unlike
bench.py (the isolated projection+binning kernel), this measures the
full production job: mmap ingest, group routing, the z21 composite-key
cascade, decode/finalize, and egress.

Every measurement runs in this one process (a chip belongs to one
process at a time).

    PYTHONPATH=. python tools/bench_job.py [--n 16777216]
        [--egress arrays|json|none] [--runs 1] [--cascade-backend both]

``--cascade-backend both`` measures each backend on the same input
file.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

def synth_hmpb(path: str, n: int, seed: int = 0) -> str:
    from heatmap_tpu.io.hmpb import write_hmpb

    rng = np.random.default_rng(seed)
    n_hot = n // 4
    lat = np.concatenate([47.6 + rng.normal(0, 0.5, n - n_hot),
                          47.6 + rng.normal(0, 0.02, n_hot)])
    lon = np.concatenate([-122.3 + rng.normal(0, 0.7, n - n_hot),
                          -122.3 + rng.normal(0, 0.03, n_hot)])
    # Routed ids against a names table shaped like production: a few
    # hundred users, one pooled "route" group, x-excluded rows (-1).
    names = ["all"] + [f"user{i}" for i in range(200)] + ["route"]
    routed = rng.integers(1, len(names), n, dtype=np.int32)
    routed[rng.random(n) < 0.05] = -1  # x- excluded
    ts = rng.integers(1_500_000_000_000, 1_700_000_000_000, n, dtype=np.int64)
    background = (rng.random(n) < 0.02).astype(np.uint8)
    return write_hmpb(path, lat, lon, routed, names,
                      timestamp=ts, background=background)


def measure(args, hmpb: str, run: int, backend: str | None) -> None:
    """One measurement: ingest the prepared HMPB, run the job once,
    print the result JSON line."""
    import jax

    from heatmap_tpu import obs
    from heatmap_tpu.io.hmpb import HMPBSource
    from heatmap_tpu.io.sinks import LevelArraysSink, MemorySink
    from heatmap_tpu.obs import tracing
    from heatmap_tpu.pipeline import BatchJobConfig, run_job_fast
    from heatmap_tpu.utils.trace import get_tracer

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import trace_analyze

    # Metrics ride along on every measurement (counters/gauges only —
    # no event log, so no per-span I/O in the timed region); the folded
    # run report lands in the bench record below.
    obs.enable_metrics(True)
    config = (BatchJobConfig() if backend is None
              else BatchJobConfig(cascade_backend=backend))
    tracer = get_tracer()
    tracer.reset()
    # Span-tree capture rides along (hooks only; no I/O in the timed
    # region) so the record carries critical-path attribution.
    collector = tracing.enable_tracing()
    if args.egress == "arrays":
        sink = LevelArraysSink(os.path.join(
            os.path.dirname(hmpb), f"levels{run}-{backend}"))
    elif args.egress == "json":
        sink = MemorySink()
    else:
        sink = None
    t0 = time.perf_counter()
    out = run_job_fast(HMPBSource(hmpb), sink=sink, config=config)
    dt = time.perf_counter() - t0
    stages = {
        name: round(r["total_s"], 3)
        for name, r in sorted(tracer.report().items())
    }
    obs.sample_device_memory()
    print(json.dumps({
        "run": run,
        "device": jax.devices()[0].platform,
        "n_points": args.n,
        "cascade_backend": backend or "default",
        "egress": args.egress,
        "total_s": round(dt, 2),
        "pts_per_s": round(args.n / dt),
        "stages": stages,
        "out": (len(out) if hasattr(out, "__len__") else str(out)[:80]),
        # Full per-stage attribution + io/cascade counters for the
        # decision evaluator: BENCH rows carry the same artifact
        # `cli run --report` writes (obs.report schema).
        "run_report": obs.build_run_report(tracer=tracer,
                                           registry=obs.get_registry()),
        # Span-tree digest: top self-time spans + the slowest trace's
        # critical path (tools/trace_analyze.py).
        "trace": trace_analyze.summarize(collector.to_chrome()),
    }, default=str), flush=True)


def partition_sweep(args) -> int:
    """Uniform-DP vs Morton-range cascade A/B (ISSUE 13 satellite).

    Two point sets — uniform and a Zipf-clustered mixture whose
    clusters are wide enough to hold distinct detail codes (a single
    heavy code is irreducible mass no planner can split) — each run
    through the mesh cascade with ``partition_splits`` off and on (the
    uniform and range programs of parallel/gspmd.py). The record
    carries measured wall seconds, the plan's skew ratio, and the
    MODELED per-pyramid merge volume: uniform DP merges every shard's
    full per-level partial buffers, the Morton path gathers only the
    boundary candidates (``min(lcap, 2*n_slots)`` keys per shard per
    coarse level, level 0 exchanging nothing — gspmd.patch_boundary).
    Bytes are 16 per key slot (int64 key + 8-byte accumulator). The
    byte gate rides along: both programs must produce identical level
    arrays or the row is marked failed.
    """
    if args.cpu:
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8")
    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp

    from heatmap_tpu.parallel import make_mesh
    from heatmap_tpu.parallel.partition import plan_partition, route_counts
    from heatmap_tpu.pipeline.batch import project_detail_codes
    from heatmap_tpu.pipeline.cascade import CascadeConfig, run_cascade

    n = args.sweep_n
    dz, mz = 16, 10
    cfg = CascadeConfig(detail_zoom=dz, min_detail_zoom=mz, result_delta=2)
    levels = cfg.n_levels
    mesh = make_mesh()
    ndev = int(np.prod(list(mesh.shape.values())))
    rng = np.random.default_rng(17)

    def zipf_points(m):
        # 80% of the mass over Zipf-ranked cluster centers, sigma wide
        # enough that a cluster spans thousands of z16 tiles.
        n_c = 32
        ranks = np.arange(1, n_c + 1, dtype=np.float64)
        p = (1.0 / ranks) / np.sum(1.0 / ranks)
        centers_lat = rng.uniform(-55.0, 55.0, n_c)
        centers_lon = rng.uniform(-170.0, 170.0, n_c)
        k = int(m * 0.8)
        c = rng.choice(n_c, size=k, p=p)
        lat = np.concatenate([centers_lat[c] + rng.normal(0, 0.3, k),
                              rng.uniform(-55.0, 55.0, m - k)])
        lon = np.concatenate([centers_lon[c] + rng.normal(0, 0.3, k),
                              rng.uniform(-170.0, 170.0, m - k)])
        return lat, lon

    datasets = {
        "uniform": (rng.uniform(-55.0, 55.0, n),
                    rng.uniform(-170.0, 170.0, n)),
        "zipf": zipf_points(n),
    }

    def levels_equal(a, b):
        for (au, asl, an), (bu, bsl, bn) in zip(a, b):
            m = int(an)
            if m != int(bn):
                return False
            if not (np.array_equal(np.asarray(au)[:m], np.asarray(bu)[:m])
                    and np.array_equal(np.asarray(asl)[:m],
                                       np.asarray(bsl)[:m])):
                return False
        return True

    def timed(fn, reps):
        fn()  # warmup: compile outside the timed region
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(fn())
        return (time.perf_counter() - t0) / reps

    rows = []
    for name, (lat, lon) in datasets.items():
        codes, valid = project_detail_codes(lat, lon, dz,
                                            prefer_device=False)
        codes, valid = np.asarray(codes), np.asarray(valid)
        plan = plan_partition(codes, ndev, detail_zoom=dz, valid=valid,
                              n_levels=levels)
        slots = np.zeros(n, np.int32)
        route = route_counts(plan, codes, valid)
        splits = jnp.asarray(plan.splits, jnp.int64)

        # Host inputs: the mesh cascade donates device-resident inputs,
        # and each timed call needs its own.
        def run_off():
            return run_cascade(codes, slots, cfg, 1, valid=valid,
                               capacity=n, mesh=mesh)

        def run_morton():
            return run_cascade(codes, slots, cfg, 1, valid=valid,
                               capacity=n, mesh=mesh,
                               partition_splits=splits,
                               route_capacity=(route.pair_capacity,
                                               route.recv_capacity))

        identical = levels_equal(run_off(), run_morton())
        wall_off = timed(run_off, args.sweep_reps)
        wall_morton = timed(run_morton, args.sweep_reps)

        # Buffer sizing: every shard's per-level partial buffer vs only
        # each range shard's boundary candidates (n_slots=1 here).
        lcap = max(1, n // ndev)
        bcap = max(1, min(route.recv_capacity, 2 * 1))
        uniform_bytes = (levels + 1) * ndev * lcap * 16
        morton_bytes = levels * ndev * bcap * 16
        rows.append({
            "dataset": name,
            "n_points": n,
            "skew_ratio": round(plan.skew_ratio, 4),
            "resplits": plan.resplits,
            "degenerate": plan.degenerate,
            "boundary_tiles": plan.boundary_tiles_total(levels),
            "wall_s": {"off": round(wall_off, 4),
                       "morton": round(wall_morton, 4)},
            "modeled_merge_bytes": {"uniform": uniform_bytes,
                                    "morton": morton_bytes},
            "merge_ratio": round(uniform_bytes / max(morton_bytes, 1), 2),
            "byte_identical": bool(identical),
        })
        print(json.dumps(rows[-1]), flush=True)

    doc = {
        "bench": "partition",
        "device": jax.devices()[0].platform,
        "ndev": ndev,
        "detail_zoom": dz,
        "levels": levels,
        "reps": args.sweep_reps,
        "results": rows,
    }
    with open(args.partition_sweep, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    print(json.dumps({"wrote": args.partition_sweep}), flush=True)
    return 0 if all(r["byte_identical"] for r in rows) else 1


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1 << 24)
    ap.add_argument("--egress", choices=("arrays", "json", "none"),
                    default="arrays")
    ap.add_argument("--runs", type=int, default=1)
    ap.add_argument("--keep", action="store_true",
                    help="keep the generated HMPB file")
    ap.add_argument("--cpu", action="store_true",
                    help="run on the CPU backend")
    ap.add_argument("--cascade-backend", default=None,
                    choices=("scatter", "partitioned", "both"),
                    help="cascade reduction backend; 'both' measures each "
                    "backend on the same input file")
    ap.add_argument("--partition-sweep", nargs="?",
                    const="BENCH_partition.json", default=None,
                    metavar="OUT.json",
                    help="uniform-DP vs Morton-range cascade A/B on "
                    "uniform + Zipf-clustered point sets: wall time, "
                    "plan skew, modeled merge bytes, byte gate "
                    "(bench_gate reads the artifact as partition:* "
                    "series)")
    ap.add_argument("--sweep-n", type=int, default=1 << 20,
                    help="points per partition-sweep dataset")
    ap.add_argument("--sweep-reps", type=int, default=3,
                    help="timed repetitions per partition-sweep leg")
    args = ap.parse_args()

    if args.partition_sweep:
        return partition_sweep(args)

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)  # int64 keys + exact z21
    backends = (("scatter", "partitioned")
                if args.cascade_backend == "both"
                else (args.cascade_backend,))
    tmpdir = tempfile.mkdtemp(prefix="benchjob-")
    try:
        hmpb = os.path.join(tmpdir, f"points-{args.n}.hmpb")
        t0 = time.perf_counter()
        synth_hmpb(hmpb, args.n)
        print(json.dumps({
            "stage": "synth+write_hmpb",
            "s": round(time.perf_counter() - t0, 2),
            "path": hmpb, "n": args.n,
            "bytes": os.path.getsize(hmpb)}), flush=True)
        for run in range(args.runs):
            for be in backends:
                measure(args, hmpb, run, be)
    finally:
        if args.keep:
            print(json.dumps({"kept": tmpdir}), flush=True)
        else:
            import shutil

            shutil.rmtree(tmpdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
