#!/usr/bin/env python
"""Scale soak checks: equivalences the unit suite can't afford.

The unit tests (tests/) run small shapes; overflow/capacity bugs can
hide above them (the int32 chunk-merge wrap only fired at ~300 users x
z21 x multiple chunks). This tool runs minutes-long cross-path
equivalence checks at configurable scale on the current backend:

  fast-vs-bounded   run_job_fast vs chunked run_job: byte-identical
                    level arrays (the strongest whole-chain check)
  mesh              sharded reduce-by-key over the device mesh vs the
                    single-device kernel, on skewed keys
  dp-job            run_job_fast data-parallel over the mesh vs
                    single-device: byte-equal level arrays at scale
  resume            crash (fault injection) + resume == uninterrupted
  streaming         sharded decayed raster: deterministic replay
  weighted          weighted job linearity (3x values == 3x counts,
                    exact) + weighted partitioned-vs-scatter kernels

    PYTHONPATH=.:$PYTHONPATH XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tools/soak.py [--n 2000000] [--checks fast-vs-bounded,...]

Every check runs and reports one JSON line; the exit code is non-zero
if any failed. CPU by default (--tpu to let the default backend
through); the mesh checks need the 8-device XLA_FLAGS above.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

CHECKS = ("fast-vs-bounded", "mesh", "dp-job", "resume", "streaming", "weighted")


def _synth_hmpb(path, n, n_users=300, seed=1, dated=False,
                weighted=False):
    from heatmap_tpu.io.hmpb import write_hmpb

    rng = np.random.default_rng(seed)
    names = ["all"] + [f"user{i}" for i in range(n_users)] + ["route"]
    return write_hmpb(
        path,
        47.6 + rng.normal(0, 0.5, n),
        -122.3 + rng.normal(0, 0.7, n),
        rng.integers(1, len(names), n, dtype=np.int32),
        names,
        timestamp=rng.integers(1_500_000_000_000, 1_600_000_000_000, n)
        if dated else None,
        background=(rng.random(n) < 0.02).astype(np.uint8),
        # Integer-valued f64 weights: exact sums under any split, so
        # weighted cross-path checks can assert byte equality.
        value=rng.integers(1, 12, n).astype(np.float64)
        if weighted else None,
    )


def _assert_levels_equal(a_dir, b_dir):
    """Full-column byte equality of two LevelArraysSink dirs;
    -> (levels, rows)."""
    from heatmap_tpu.io.sinks import LevelArraysSink

    la, lb = LevelArraysSink.load(a_dir), LevelArraysSink.load(b_dir)
    assert la.keys() == lb.keys(), (sorted(la), sorted(lb))
    rows = 0
    for z in la:
        for k in ("row", "col", "value", "user", "timespan",
                  "coarse_row", "coarse_col"):
            np.testing.assert_array_equal(la[z][k], lb[z][k])
        rows += len(la[z]["value"])
    return len(la), rows


def check_fast_vs_bounded(n, tmp):
    from heatmap_tpu.io.hmpb import HMPBSource
    from heatmap_tpu.io.sinks import LevelArraysSink
    from heatmap_tpu.pipeline import BatchJobConfig, run_job, run_job_fast

    hmpb = _synth_hmpb(os.path.join(tmp, "p.hmpb"), n)
    # data_parallel=False: this check is about fast-vs-bounded
    # equality, and auto-DP at soak sizes trips XLA's CPU collective
    # rendezvous timeout on low-core hosts (8 virtual devices
    # SERIALIZE on the cores available; a participant arriving >60s
    # after the first aborts the process — a CPU-emulation artifact,
    # not a program property). DP equality has its own check below
    # with a deliberately bounded per-shard size.
    cfg = BatchJobConfig(data_parallel=False)
    a = os.path.join(tmp, "a")
    b = os.path.join(tmp, "b")
    run_job_fast(HMPBSource(hmpb), LevelArraysSink(a), config=cfg)
    run_job(HMPBSource(hmpb), LevelArraysSink(b), config=cfg,
            max_points_in_flight=max(n // 4, 1000))
    levels, rows = _assert_levels_equal(a, b)
    return {"levels": levels, "rows": rows}


def check_mesh(n, tmp):
    """Device-mesh sharded aggregation vs single-device, at scale.

    (run_job_multihost falls through to run_job in a single process,
    so comparing those two here would be vacuous — the mesh coverage
    must drive the sharded kernels directly.)
    """
    import jax
    import jax.numpy as jnp

    from heatmap_tpu.ops.sparse import aggregate_keys
    from heatmap_tpu.parallel import make_mesh
    from heatmap_tpu.parallel.sharded import aggregate_keys_sharded

    if len(jax.devices()) < 2:
        return {"skipped": "needs a multi-device mesh (set XLA_FLAGS)"}
    mesh = make_mesh()
    ndev = len(jax.devices())
    n = max(n - n % ndev, ndev)  # shardable length
    rng = np.random.default_rng(4)
    # Skewed keys: hot head + long tail, the shape that trips local
    # capacity/overflow logic.
    keys = jnp.asarray(np.concatenate([
        rng.integers(0, 500, n // 2),
        rng.integers(0, n, n - n // 2),
    ]).astype(np.int64))
    want_k, want_s, want_n = aggregate_keys(keys, capacity=n)
    got_k, got_s, got_n = aggregate_keys_sharded(
        keys, capacity=n, mesh=mesh
    )
    wn, gn = int(want_n), int(got_n)
    assert wn == gn, f"unique counts diverged: {wn} vs {gn}"
    np.testing.assert_array_equal(np.asarray(want_k)[:wn],
                                  np.asarray(got_k)[:gn])
    np.testing.assert_array_equal(np.asarray(want_s)[:wn],
                                  np.asarray(got_s)[:gn])
    return {"uniques": wn, "devices": len(jax.devices()),
            "mesh": dict(mesh.shape)}


def check_dp_job(n, tmp):
    """Flagship job data-parallel over the virtual mesh vs
    single-device, at scale: byte-equal level arrays. The unit suite
    pins small shapes; this drives the padding + zoom-clamped
    capacities through the gspmd cascade at soak size."""
    import jax

    from heatmap_tpu.io.hmpb import HMPBSource
    from heatmap_tpu.io.sinks import LevelArraysSink
    from heatmap_tpu.pipeline import BatchJobConfig, run_job_fast

    if len(jax.devices()) < 2:
        return {"skipped": "needs a multi-device mesh (set XLA_FLAGS)"}
    # Bound the DP size: on a low-core host the virtual devices'
    # collective participants serialize, and XLA's CPU rendezvous
    # aborts the process if one arrives >60s late — 500k points keeps
    # per-shard work far under that while still 10x the unit suite.
    n = min(n, 500_000)
    hmpb = _synth_hmpb(os.path.join(tmp, "dp.hmpb"), n)
    a, b = os.path.join(tmp, "dp-a"), os.path.join(tmp, "dp-b")
    run_job_fast(HMPBSource(hmpb), LevelArraysSink(a),
                 config=BatchJobConfig(data_parallel=True))
    run_job_fast(HMPBSource(hmpb), LevelArraysSink(b),
                 config=BatchJobConfig(data_parallel=False))
    levels, rows = _assert_levels_equal(a, b)
    # Weighted variant: integer-valued f64 weights stay bit-exact
    # through the sharded cascade's merge at scale.
    whmpb = _synth_hmpb(os.path.join(tmp, "dpw.hmpb"), n, weighted=True)
    wa, wb = os.path.join(tmp, "dpw-a"), os.path.join(tmp, "dpw-b")
    wcfg = dict(weighted=True)
    run_job_fast(HMPBSource(whmpb), LevelArraysSink(wa),
                 config=BatchJobConfig(data_parallel=True, **wcfg))
    run_job_fast(HMPBSource(whmpb), LevelArraysSink(wb),
                 config=BatchJobConfig(data_parallel=False, **wcfg))
    wlevels, wrows = _assert_levels_equal(wa, wb)
    return {"levels": levels, "rows": rows, "weighted_rows": wrows,
            "devices": len(jax.devices())}


def check_resume(n, tmp):
    from heatmap_tpu.io.hmpb import HMPBSource
    from heatmap_tpu.pipeline import BatchJobConfig, run_job_fast
    from heatmap_tpu.utils.recovery import FaultInjector

    hmpb = _synth_hmpb(os.path.join(tmp, "r.hmpb"), n, dated=True)
    # data_parallel=False: see check_fast_vs_bounded's rendezvous note.
    cfg = BatchJobConfig(timespans=("alltime", "day"),
                         data_parallel=False)
    bs = max(n // 8, 1)  # always >= 8 batches, so the mid fault fires
    n_batches = -(-n // bs)
    fail_at = n_batches // 2
    want = run_job_fast(HMPBSource(hmpb), config=cfg, batch_size=bs)
    ck = os.path.join(tmp, "ck")
    try:
        run_job_fast(HMPBSource(hmpb), config=cfg, batch_size=bs,
                     checkpoint_dir=ck, checkpoint_every=2,
                     fault_injector=FaultInjector({fail_at: 1}))
        raise AssertionError("expected the injected fault to fire")
    except RuntimeError:
        pass
    got = run_job_fast(HMPBSource(hmpb), config=cfg, batch_size=bs,
                       checkpoint_dir=ck, checkpoint_every=2)
    assert want == got, (
        f"resume diverged: {len(want)} vs {len(got)} blobs"
    )
    return {"blobs": len(want)}


def check_streaming(n, tmp):
    import jax

    from heatmap_tpu.ops import window_from_bounds
    from heatmap_tpu.parallel import make_mesh
    from heatmap_tpu.streaming import HeatmapStream, StreamConfig

    win = window_from_bounds((44.0, 51.0), (-127.0, -117.0), zoom=12,
                             align_levels=10, pad_multiple=256)
    mesh = make_mesh() if len(jax.devices()) > 1 else None
    batch = max(n // 50, 1000)

    def run():
        s = HeatmapStream(
            StreamConfig(window=win, half_life_s=30.0, pad_to=batch),
            mesh=mesh,
        )
        rng = np.random.default_rng(0)
        t = 0.0
        for _ in range(50):
            t += 5.0
            s.update(47.6 + rng.normal(0, 0.5, batch),
                     -122.3 + rng.normal(0, 0.7, batch), t)
        return np.asarray(s.raster)

    r1, r2 = run(), run()
    np.testing.assert_array_equal(r1, r2)
    return {"batches": 50, "batch": batch, "mass": float(r1.sum()),
            "sharded": mesh is not None}


def check_weighted(n, tmp):
    """Weighted-path equivalences at scale.

    (a) Job linearity: run --weighted semantics with every value == 3
    must equal exactly 3x the counted blobs (integer-valued weights
    keep the f64 sums exact at any fan-in). (b) Kernel cross-path:
    weighted sort-partitioned binning vs the weighted XLA scatter,
    bit-equal for integer weights at a million-point z15 window.
    """
    import jax.numpy as jnp

    from heatmap_tpu.ops import window_from_bounds
    from heatmap_tpu.ops.histogram import bin_rowcol_window
    from heatmap_tpu.ops.partitioned import bin_rowcol_window_partitioned
    from heatmap_tpu.pipeline import BatchJobConfig, run_job
    from heatmap_tpu.tilemath import mercator

    rng = np.random.default_rng(9)
    n_job = min(n, 200_000)  # the string job path is host-bound
    users = (["all_is_reserved"] + [f"u{i}" for i in range(50)]
             + ["x-hidden", "rt-bus"])
    lat = 47.6 + rng.normal(0, 0.5, n_job)
    lon = -122.3 + rng.normal(0, 0.7, n_job)
    uid = rng.integers(0, len(users), n_job)

    class _Src:
        def __init__(self, with_values):
            self.with_values = with_values

        def batches(self, batch_size):
            for i in range(0, n_job, batch_size):
                sl = slice(i, i + batch_size)
                out = {
                    "latitude": lat[sl], "longitude": lon[sl],
                    "user_id": [users[j] for j in uid[sl]],
                    "source": [], "timestamp": [],
                }
                if self.with_values:
                    out["value"] = np.full(len(lat[sl]), 3.0)
                yield out

    # data_parallel=False: see check_fast_vs_bounded's rendezvous note.
    cfg = BatchJobConfig(detail_zoom=14, min_detail_zoom=6,
                         data_parallel=False)
    counted = run_job(_Src(False), config=cfg, batch_size=1 << 16)
    weighted = run_job(_Src(True),
                       config=dataclasses.replace(cfg, weighted=True),
                       batch_size=1 << 16)
    assert counted.keys() == weighted.keys()
    checked = 0
    for key, blob in counted.items():
        c = json.loads(blob)
        w = json.loads(weighted[key])
        assert c.keys() == w.keys(), key
        for tile, cnt in c.items():
            assert w[tile] == 3.0 * cnt, (key, tile, w[tile], cnt)
            checked += 1

    win = window_from_bounds((44.0, 51.0), (-127.0, -117.0), zoom=15,
                             align_levels=12, pad_multiple=256)
    m = min(n, 1 << 20)
    kl = jnp.asarray((47.6 + rng.normal(0, 0.5, m)).astype(np.float32))
    ko = jnp.asarray((-122.3 + rng.normal(0, 0.7, m)).astype(np.float32))
    kw = jnp.asarray(rng.integers(0, 16, m).astype(np.float32))
    r, c, v = mercator.project_points(kl, ko, win.zoom, dtype=jnp.float32)
    a = np.asarray(bin_rowcol_window(r, c, win, weights=kw, valid=v))
    b = np.asarray(bin_rowcol_window_partitioned(r, c, win, weights=kw,
                                                 valid=v))
    np.testing.assert_array_equal(a, b)
    return {"blob_values_checked": checked, "kernel_points": m,
            "kernel_mass": float(a.sum())}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=2_000_000)
    ap.add_argument("--checks", default=",".join(CHECKS))
    ap.add_argument("--tpu", action="store_true",
                    help="run on the default backend instead of forcing CPU")
    args = ap.parse_args()

    import jax

    if not args.tpu:
        jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)

    fns = {"fast-vs-bounded": check_fast_vs_bounded,
           "mesh": check_mesh,
           "dp-job": check_dp_job,
           "resume": check_resume,
           "streaming": check_streaming,
           "weighted": check_weighted}
    failed = 0
    for name in args.checks.split(","):
        name = name.strip()
        if name not in fns:
            raise SystemExit(f"unknown check {name!r}; valid: {CHECKS}")
        tmp = tempfile.mkdtemp(prefix=f"soak-{name}-")
        t0 = time.perf_counter()
        try:
            extra = fns[name](args.n, tmp)
            print(json.dumps({"check": name, "ok": True,
                              "s": round(time.perf_counter() - t0, 1),
                              **extra}), flush=True)
        except Exception as e:  # noqa: BLE001 — run all, report each
            failed += 1
            print(json.dumps({"check": name, "ok": False,
                              "error": f"{type(e).__name__}: {e}"[:300]}),
                  flush=True)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
