#!/usr/bin/env python
"""Smoke run of the production path on a TPU: z21 backfill and tile serving.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # data-parallel run_job across four chips

One chip runs, in this one process and through the entry points a user
calls:

1. device check: the default JAX device must be a TPU;
2. native library: ``heatmap_tpu.native.build()`` (make on the tracked
   sources);
3. backfill: ``heatmap-tpu run`` (``cli.main``) over 2^24 synthetic
   points at the defaults (z21 detail, 16 stored levels, x64) into
   ``arrays:`` level files; the event log must show the cascade
   resolved to the partitioned kernel, compiled (not interpreted);
4. the same source with ``--cascade-backend scatter``, byte-identical
   to the backfill's level arrays;
5. oracle slice: the first 2^16 points through ``run_job``'s chunked
   path (four chunks, with the device feeder) on the scatter cascade,
   blob for blob equal to ``tests/oracle.py``; with step 4 this ties
   the partitioned kernel to the reference;
6. serving: ``TileStore`` + ``ServeApp.handle`` answer a few tile
   requests with 200, and the z1 tiles of ``all|alltime`` sum to the
   job's point count.

The whole run must finish well inside 20 minutes with nothing compiled
beforehand. Two measured costs on a v5e shaped it: a full-size chunked
run (``--max-points-in-flight 4194304``) took 645 s (its cross-chunk
host merge and its own compile), so the chunked path is checked on the
oracle slice instead; and the partitioned kernel then padded every call
to its 2^24-element slab, so a four-chunk oracle slice on it took 475 s,
which is why the slice runs on the scatter cascade.

``--chips 4`` runs only the four-chip phase: ``run_job`` on 2^20
points of the same source, on the scatter cascade, with
``data_parallel=True`` and ``spatial_partition`` "morton" then "off"
(the gspmd range and uniform programs), each byte-compared with
``data_parallel=False`` on device 0. It also checks that the
cascade's inputs arrived spread over the chips, and reports whether the
cascade consumed (donated) its input buffers; buffer donation engages
only on this path (a jitted gspmd cascade over a mesh), never on one
chip.

Every phase prints one JSON line (wall seconds, compile seconds, peak
device bytes, ...). The last line of standard output is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The script exits non-zero, printing no result, when JAX finds no TPU or
any phase fails.
"""

from __future__ import annotations

import argparse
import collections
import concurrent.futures
import contextlib
import glob
import json
import os
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

N_POINTS = 1 << 24  # -> 2^25 cascade emissions (the point + its 'all' slot)
#: The four-chip phase runs 2^20 points on the scatter cascade: its time
#: is compilation, which barely depends on size, while the host work at
#: 2^20 points is seconds. The Morton program at full size is the
#: benchmark's planet cell (BENCHMARK.json), on the partitioned kernel;
#: the kernel's agreement with scatter is checked on one chip.
FOUR_CHIP_POINTS = 1 << 20
FOUR_CHIP_BACKEND = "scatter"
SEED = 7
ORACLE_POINTS = 1 << 16
ORACLE_CHUNKS = 4
REPO = os.path.dirname(os.path.abspath(__file__))

#: jax.monitoring events whose durations count as compilation.
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class SmokeFailure(AssertionError):
    """A phase produced a wrong or missing result."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def report(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def peak_bytes(device) -> int | None:
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


class PhaseClock:
    """Wall and compilation seconds of one phase. Compilation is counted
    per thread (JAX reports it in the thread that compiles), so phases
    that run side by side each count their own."""

    def __init__(self):
        import jax

        self.compile_s = collections.defaultdict(float)
        self.compiles = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            me = threading.get_ident()
            self.compile_s[me] += duration
            self.compiles[me] += event == COMPILE_EVENTS[-1]

    @contextlib.contextmanager
    def phase(self, name: str, **fields):
        """Time the body and report it with the fields the body adds to
        the yielded dict."""
        import jax

        me = threading.get_ident()
        out = dict(fields)
        c0, n0 = self.compile_s[me], self.compiles[me]
        t0 = time.perf_counter()
        yield out
        out["wall_s"] = time.perf_counter() - t0
        out["compile_s"] = self.compile_s[me] - c0
        out["compiles"] = self.compiles[me] - n0
        out["peak_bytes_in_use"] = peak_bytes(jax.devices()[0])
        report(name, **out)


def device_record() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# -- one-chip phases ---------------------------------------------------------


def build_native() -> str:
    from heatmap_tpu import native

    path = native.build()
    check(path is not None, "make -C native failed")
    check(native.available(), "native library did not load")
    return path


def read_events(path: str) -> list[dict]:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def run_cli(source: str, levels_dir: str, events: str, *extra) -> dict:
    """``heatmap-tpu run`` in this process; returns what the event log
    says the cascade ran."""
    from heatmap_tpu import cli

    rc = cli.main(["run", "--input", source, "--output",
                   f"arrays:{levels_dir}", "--events", events, *extra])
    check(rc == 0, f"run exited {rc}")
    evs = read_events(events)
    resolved = {e["resolved"] for e in evs
                if e["event"] == "backend_resolved"}
    dispatches = [e for e in evs if e["event"] == "cascade_dispatch"]
    check(dispatches, "no cascade_dispatch event in the event log")
    end = [e for e in evs if e["event"] == "run_end"]
    check(end and end[-1]["status"] == "ok", f"run_end: {end}")
    return {"resolved": sorted(resolved),
            "backends": sorted({e["backend"] for e in dispatches}),
            # The Pallas mode each segment-kernel trace was lowered in.
            "interpret": sorted({e["interpret"] for e in evs
                                 if e["event"] == "pallas_kernel"}),
            "mesh": sorted({e.get("mesh") for e in dispatches}, key=str),
            "dispatches": len(dispatches),
            "rows": end[-1].get("rows")}


def level_files(levels_dir: str) -> list[str]:
    names = sorted(os.path.basename(p) for p in
                   glob.glob(os.path.join(levels_dir, "level_z*.npz")))
    check(names, f"no level files in {levels_dir}")
    return names


def levels_identical(dir_a: str, dir_b: str) -> bool:
    """Byte equality of two ``arrays:`` egresses, one level at a time:
    the same files holding the same arrays (name, dtype, shape, bytes)."""
    names = level_files(dir_a)
    if names != level_files(dir_b):
        return False
    for name in names:
        with np.load(os.path.join(dir_a, name)) as x, \
                np.load(os.path.join(dir_b, name)) as y:
            if sorted(x.files) != sorted(y.files):
                return False
            for k in x.files:
                u, v = x[k], y[k]
                if (u.dtype != v.dtype or u.shape != v.shape
                        or u.tobytes() != v.tobytes()):
                    return False
    return True


def blob_count(levels_dir: str) -> int:
    """Distinct (user, timespan, coarse tile) blobs across all levels."""
    total = 0
    for name in level_files(levels_dir):
        with np.load(os.path.join(levels_dir, name)) as z:
            # Coarse zooms stay <= 16 (21 bits per axis is ample) and
            # the user/timespan tables are small: one int64 key per row.
            n_ts = int(z["timespan_idx"].max(initial=0)) + 1
            key = ((z["user_idx"].astype(np.int64) * n_ts
                    + z["timespan_idx"]) << 42
                   | z["coarse_row"].astype(np.int64) << 21
                   | z["coarse_col"].astype(np.int64))
        total += len(np.unique(key))
    return total


def source_points(n: int, seed: int) -> int:
    """Points the job counts: non-background rows inside the Mercator
    latitude range."""
    from heatmap_tpu.io.sources import SyntheticSource

    kept = 0
    for batch in SyntheticSource(n=n, seed=seed).batches():
        lat = np.asarray(batch["latitude"])
        fg = np.asarray(batch["source"]) != "background"
        kept += int((fg & (np.abs(lat) < 85.0511)).sum())
    return kept


def oracle_slice(n: int, seed: int, detail_zoom: int = 21,
                 min_detail_zoom: int = 5) -> int:
    """Blobs of ``run_job`` on the first ``n`` points, run as
    ``ORACLE_CHUNKS`` chunks on the scatter cascade, equal the
    pure-Python reference oracle's; returns the blob count."""
    sys.path.insert(0, os.path.join(REPO, "tests"))
    import oracle

    from heatmap_tpu.io.sources import SyntheticSource
    from heatmap_tpu.pipeline import BatchJobConfig, run_job

    src = SyntheticSource(n=n, seed=seed)
    got = run_job(src, config=BatchJobConfig(
        detail_zoom=detail_zoom, min_detail_zoom=min_detail_zoom,
        cascade_backend="scatter"),
        max_points_in_flight=-(-n // ORACLE_CHUNKS))
    rows = []
    for b in src.batches():
        rows += [{"latitude": la, "longitude": lo, "user_id": u,
                  "source": s}
                 for la, lo, u, s in zip(b["latitude"].tolist(),
                                         b["longitude"].tolist(),
                                         b["user_id"], b["source"])]
    want = oracle.run_job(rows, detail_zoom=detail_zoom,
                          min_detail_zoom=min_detail_zoom, amplify_all=False)
    check(got.keys() == want.keys(),
          f"oracle blob ids differ: {len(got)} vs {len(want)}")
    bad = [k for k in want if json.loads(got[k]) != want[k]]
    check(not bad, f"{len(bad)} blobs differ from the oracle, e.g. {bad[:3]}")
    return len(want)


def serve_tiles(levels_dir: str, expected_points: int) -> dict:
    """Tile requests through ``ServeApp.handle``: every one answers 200,
    and the z1 JSON tiles of the default layer sum to the point count."""
    from heatmap_tpu.serve import ServeApp, TileStore
    from heatmap_tpu.tilemath.morton import morton_decode_np

    app = ServeApp(TileStore(f"arrays:{levels_dir}"))
    layer = app.store.layer("default")
    delta = layer.result_delta

    def tiles_at(z):
        codes = np.unique(layer.levels[z + delta].codes >> (2 * delta))
        rows, cols = morton_decode_np(codes)
        return list(zip(cols.tolist(), rows.tolist()))

    statuses = []
    z1_total = 0.0
    for x, y in tiles_at(1):
        status, _, body, *_ = app.handle(
            "GET", f"/tiles/default/1/{x}/{y}.json")
        statuses.append(status)
        if status == 200:
            z1_total += sum(json.loads(body).values())
    zooms = sorted(z - delta for z in layer.levels)
    for z in sorted(set(zooms[::max(1, (len(zooms) - 1) // 3)]
                        + zooms[-1:])):
        tiles = tiles_at(z)
        x, y = tiles[len(tiles) // 2]
        for fmt in ("png",) if z == 1 else ("json", "png"):
            status, _, body, *_ = app.handle(
                "GET", f"/tiles/default/{z}/{x}/{y}.{fmt}")
            statuses.append(status)
            check(status != 200 or len(body) > 0, f"empty 200 body z{z}")
    check(all(s == 200 for s in statuses), f"tile statuses {statuses}")
    check(z1_total == expected_points,
          f"z1 tiles sum to {z1_total}, job counted {expected_points}")
    return {"requests": len(statuses), "z1_total": z1_total}


def run_one_chip(work: str, clock: PhaseClock, n: int = N_POINTS,
                 oracle_n: int = ORACLE_POINTS, zooms: tuple = (21, 5),
                 extra: tuple = (), expect_backend: str = "partitioned",
                 expect_interpret: bool = False) -> None:
    """The one-chip phases. ``zooms`` (detail, min detail) and the CLI
    flags in ``extra`` exist so the phases can run small on a CPU."""
    import jax

    # A fresh trace of every program, so the segment kernel logs the
    # mode it is lowered in during the backfill.
    jax.clear_caches()
    source = f"synthetic:{n}:{SEED}"
    extra = ("--detail-zoom", str(zooms[0]),
             "--min-detail-zoom", str(zooms[1]), *extra)
    with clock.phase("native") as out:
        out["lib"] = os.path.relpath(build_native(), REPO)

    backfill = os.path.join(work, "backfill")
    with clock.phase("backfill", points=n) as out:
        out.update(run_cli(source, backfill,
                           os.path.join(work, "backfill.events.jsonl"),
                           *extra))
        out["blobs"] = blob_count(backfill)
        check(out["resolved"] == [expect_backend],
              f"cascade resolved to {out['resolved']}")
        check(out["backends"] == [expect_backend],
              f"cascade ran {out['backends']}")
        check(out["interpret"] == [expect_interpret],
              f"kernel interpret modes {out['interpret']}")

    with clock.phase("scatter", points=n) as out:
        scatter = os.path.join(work, "scatter")
        out.update(run_cli(source, scatter,
                           os.path.join(work, "scatter.events.jsonl"),
                           *extra, "--cascade-backend", "scatter"))
        out["identical"] = levels_identical(scatter, backfill)
        check(out["identical"], "scatter level arrays differ from the "
              "backfill's")
        shutil.rmtree(scatter)

    with clock.phase("oracle", points=oracle_n,
                     chunks=ORACLE_CHUNKS) as out:
        out["blobs"] = oracle_slice(oracle_n, SEED, *zooms)

    with clock.phase("serve") as out:
        out["points"] = source_points(n, SEED)
        out.update(serve_tiles(os.path.join(work, "backfill"), out["points"]))


# -- four-chip phase ---------------------------------------------------------


@contextlib.contextmanager
def cascade_placements():
    """Record, per cascade dispatch and per calling thread, how many
    devices hold each jax array going in and coming out, and whether the
    dispatch consumed (donated) its input code buffer. Yields a function
    that returns the calling thread's records."""
    import jax

    from heatmap_tpu.pipeline import cascade as cascade_mod

    seen = collections.defaultdict(list)
    real = cascade_mod.run_cascade

    def spans(tree):
        return sorted({len(a.sharding.device_set)
                       for a in jax.tree_util.tree_leaves(tree)
                       if isinstance(a, jax.Array)})

    def recording(codes, slots, *args, **kwargs):
        rec = {"mesh": kwargs.get("mesh") is not None,
               "in": spans((codes, slots))}
        out = real(codes, slots, *args, **kwargs)
        rec["out"] = spans(out)
        rec["donated"] = isinstance(codes, jax.Array) and codes.is_deleted()
        seen[threading.get_ident()].append(rec)
        return out

    cascade_mod.run_cascade = recording
    try:
        yield lambda: seen[threading.get_ident()]
    finally:
        cascade_mod.run_cascade = real


def run_four_chips(work: str, clock: PhaseClock, n: int = FOUR_CHIP_POINTS,
                   n_devices: int = 4, detail_zoom: int = 21,
                   min_detail_zoom: int = 5) -> None:
    import jax

    from heatmap_tpu.io import open_sink
    from heatmap_tpu.io.sources import SyntheticSource
    from heatmap_tpu.pipeline import BatchJobConfig, run_job
    from heatmap_tpu.pipeline.batch import _dp_mesh

    devs = jax.devices()
    check(len(devs) == n_devices, f"expected {n_devices} devices, got {devs}")

    def job(name, placements, **cfg):
        config = BatchJobConfig(detail_zoom=detail_zoom,
                                min_detail_zoom=min_detail_zoom,
                                cascade_backend=FOUR_CHIP_BACKEND, **cfg)
        levels_dir = os.path.join(work, name)
        with clock.phase(name, points=n, **cfg) as out:
            first = len(placements())
            with open_sink(f"arrays:{levels_dir}") as sink:
                run_job(SyntheticSource(n=n, seed=SEED), sink, config)
            seen = out["placements"] = placements()[first:]
            out["device_peak_bytes"] = [peak_bytes(d) for d in devs]
            if cfg.get("data_parallel"):
                mesh = _dp_mesh(config)
                out["mesh_devices"] = int(mesh.devices.size)
                check(set(mesh.devices.flat) == set(devs),
                      f"mesh {mesh} does not span all {n_devices} devices")
                check(seen and all(p["mesh"] for p in seen),
                      "a data-parallel cascade ran without its mesh")
                check(all(p["out"] == [n_devices] for p in seen),
                      f"cascade outputs not spread over the mesh: {seen}")
                # run_job places the gspmd cascade's inputs in per-chip
                # blocks before the program: no chip receives the batch.
                out["inputs_spread"] = all(p["in"] == [n_devices]
                                           for p in seen)
                check(out["inputs_spread"],
                      f"cascade inputs not spread over the mesh: {seen}")
        return levels_dir

    def compare(partition, got, single):
        same = levels_identical(got, single)
        report(f"dp_{partition}_compare", identical=same)
        check(same, f"data-parallel ({partition}) levels differ from "
              "the single-device run")
        shutil.rmtree(got)

    def data_parallel(partition, placements):
        return job(f"dp_{partition}", placements, data_parallel=True,
                   spatial_partition=partition)

    with cascade_placements() as placements, \
            concurrent.futures.ThreadPoolExecutor(1) as pool:
        # The single-device reference runs beside the Morton job so the
        # two programs compile at the same time. It has no collectives,
        # so neither job can wait on the other. The two data-parallel
        # jobs never overlap: their collectives could queue in different
        # orders on different devices.
        reference = pool.submit(job, "single_device", placements,
                                data_parallel=False)
        morton = data_parallel("morton", placements)
        single = reference.result()
        compare("morton", morton, single)
        compare("off", data_parallel("off", placements), single)


# -- entry --------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip phases; 4: only the "
                    "four-chip data-parallel phase")
    args = ap.parse_args(argv)

    import jax

    dev = device_record()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU (default device platform is "
              f"{dev['platform']!r})", file=sys.stderr)
        return 1
    if dev["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPUs, "
              f"found {dev['count']}", file=sys.stderr)
        return 1

    from heatmap_tpu.utils.compile_cache import enable_compile_cache

    report("device", **dev,
           tmp_free_bytes=shutil.disk_usage(tempfile.gettempdir()).free)
    report("compile_cache", dir=enable_compile_cache())
    jax.config.update("jax_enable_x64", True)
    clock = PhaseClock()
    with tempfile.TemporaryDirectory(prefix="chip_smoke-") as work:
        try:
            if args.chips == 4:
                run_four_chips(work, clock)
            else:
                run_one_chip(work, clock)
        except SmokeFailure as e:
            print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
            return 1
    print(json.dumps({"ok": True, "device": device_record()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
