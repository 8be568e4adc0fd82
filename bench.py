#!/usr/bin/env python
"""Headline benchmark: points/sec binned into a z0-z15 tile pyramid.

Runs the fused projection -> window-raster scatter-add -> full pyramid
step (the BASELINE.md primary metric) on the TPU (CPU with --cpu, for
rehearsal) and prints ONE JSON line:

    {"metric": ..., "value": ..., "unit": ..., "vs_baseline": ...}

``vs_baseline`` is the speedup over a vectorized numpy CPU
implementation of the same workload measured in-process (the reference
publishes no numbers — BASELINE.md — so the baseline proxy is the
strongest single-core CPU formulation of the reference's hot path:
vectorized projection + np.add.at scatter + reshape-sum pyramid, far
faster than the reference's per-record Python mappers).

Without --cpu a host where JAX finds no TPU is an error, and so is a
kernel that fails: nothing falls back to another device or kernel.
"""

from __future__ import annotations

import argparse
import json
import math
import time

import numpy as np


def _make_points(n, seed=0):
    """Clustered synthetic GPS points (hot-spot mixture over a metro area),
    the access pattern heatmaps actually see."""
    rng = np.random.default_rng(seed)
    n_hot = n // 4
    base_lat, base_lon = 47.6, -122.3
    lat = np.concatenate(
        [
            base_lat + rng.normal(0, 0.5, n - n_hot),
            base_lat + rng.normal(0, 0.02, n_hot),
        ]
    )
    lon = np.concatenate(
        [
            base_lon + rng.normal(0, 0.7, n - n_hot),
            base_lon + rng.normal(0, 0.03, n_hot),
        ]
    )
    return lat.astype(np.float32), lon.astype(np.float32)


def _numpy_baseline(lat64, lon64, window, levels):
    """Single-core vectorized numpy version of the same step."""
    n = 1 << window.zoom
    phi = lat64 * math.pi / 180
    y = (1 - np.log(np.tan(phi) + 1 / np.cos(phi)) / math.pi) / 2
    row = np.floor(y * n).astype(np.int64) - window.row0
    col = np.floor((lon64 + 180.0) / 360.0 * n).astype(np.int64) - window.col0
    ok = (row >= 0) & (row < window.height) & (col >= 0) & (col < window.width)
    raster = np.zeros((window.height, window.width), np.int32)
    np.add.at(raster, (row[ok], col[ok]), 1)
    out = raster
    for _ in range(levels):
        h, w = out.shape
        if h < 2 or w < 2:
            break
        out = out.reshape(h // 2, 2, w // 2, 2).sum(axis=(1, 3))
    return raster.sum()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1 << 25, help="points per step")
    ap.add_argument("--zoom", type=int, default=15)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--baseline-n", type=int, default=1 << 20)
    ap.add_argument("--cpu", action="store_true",
                    help="run on CPU instead of TPU (rehearsal only)")
    ap.add_argument("--bin-backend", default="auto",
                    choices=("auto", "xla", "pallas", "partitioned"),
                    help="binning path: auto (measured per-window routing), "
                    "xla scatter, pallas MXU kernel, or the sort-partitioned "
                    "MXU kernel (ops/partitioned.py)")
    args = ap.parse_args()

    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    device = jax.devices()[0].platform
    if not args.cpu and device != "tpu":
        raise SystemExit(f"bench.py: JAX found no TPU (default platform "
                         f"{device!r}); pass --cpu to rehearse on the host")
    import jax.numpy as jnp

    from heatmap_tpu.ops import bin_points_window, window_from_bounds
    from heatmap_tpu.ops.histogram import _pick_backend

    levels = args.zoom  # roll all the way to z0 (window shrinks to 1x1 early)
    window = window_from_bounds(
        (44.0, 51.0), (-127.0, -117.0), zoom=args.zoom,
        align_levels=min(12, args.zoom), pad_multiple=256,
    )

    lat, lon = _make_points(args.n)
    d_lat = jax.device_put(jnp.asarray(lat))
    d_lon = jax.device_put(jnp.asarray(lon))

    def make_step(backend):
        @jax.jit
        def step(la, lo):
            raster = bin_points_window(
                la, lo, window, proj_dtype=jnp.float32, backend=backend,
            )
            pyr = pyramid_from_raster_capped(raster)
            # Return the top so the whole pyramid materializes.
            return pyr[-1].sum(), raster

        return step

    def pyramid_from_raster_capped(raster):
        out = [raster]
        r = raster
        for _ in range(levels):
            if r.shape[0] < 2 or r.shape[1] < 2:
                break
            h, w = r.shape
            r = r.reshape(h // 2, 2, w // 2, 2).sum(axis=(1, 3))
            out.append(r)
        return out

    # Warmup/compile. Timing forces a scalar device->host transfer per
    # step, so every step ends with the device work done.
    resolved = _pick_backend(args.bin_backend, window)
    step = make_step(args.bin_backend)
    total, _ = step(d_lat, d_lon)
    int(total)

    # Median over per-step times: one stalled step must not halve the
    # recorded number.
    times = []
    for _ in range(args.steps):
        t0 = time.perf_counter()
        total, raster = step(d_lat, d_lon)
        int(total)
        times.append(time.perf_counter() - t0)
    times.sort()
    dt = times[len(times) // 2]
    pts_per_sec = args.n / dt

    # CPU baseline on a smaller sample, scaled linearly.
    bl_lat, bl_lon = _make_points(args.baseline_n, seed=1)
    t0 = time.perf_counter()
    _numpy_baseline(bl_lat.astype(np.float64), bl_lon.astype(np.float64), window, levels)
    bl_dt = time.perf_counter() - t0
    bl_pts_per_sec = args.baseline_n / bl_dt

    out = {
        "metric": f"points/sec binned into z0-z{args.zoom} tile pyramid",
        "value": round(pts_per_sec),
        "unit": "points/sec",
        "vs_baseline": round(pts_per_sec / bl_pts_per_sec, 2),
        "device": device,
        "bin_backend": args.bin_backend,
        # "auto" resolves per window/platform — record what actually ran
        # so artifacts from different rounds stay comparable.
        "bin_backend_resolved": resolved,
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
