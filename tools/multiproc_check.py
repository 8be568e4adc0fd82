#!/usr/bin/env python
"""REAL multi-process validation of the multihost layer (CPU, gloo).

Round 2 pinned the pod paths with algebra tests and injected
transports ("true multi-process DCN runs need a pod"); that was wrong
— JAX's distributed runtime + gloo CPU collectives run fine as k
local processes. This tool spawns k children that jointly execute the
ACTUAL code paths end to end:

- ``jax.distributed.initialize`` (the multihost.initialize ordering
  contract) with 1 CPU device per process;
- process-sharded ingest (``shard_source_rows`` batch slices);
- ``egress="gather"``: gather_blobs' framed u8 allgather over the
  real runtime — every process's merged dict must equal a
  single-process ``run_job`` oracle;
- ``egress="sharded"``: scatter_blobs' ``lax.all_to_all`` byte
  exchange over a 1-device-per-process mesh — each process's owned
  shard must carry exactly its blob_owner keys, per-host JSONL sink
  shards must reassemble to the oracle;
- columnar sharded egress: scatter_levels + per-host LevelArraysSink
  dirs reassembling to the oracle's level arrays.

Usage:
    PYTHONPATH=.:$PYTHONPATH python tools/multiproc_check.py \
        [--k 2] [--n 3000] [--timeout 600]

Prints one JSON line per process plus a final parent verdict line:
    {"check": "multiproc", "ok": true, "k": 2, "n": 3000, ...}
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import tempfile
import time

_CHILD = r"""
import json, os, sys
import numpy as np
import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_cpu_collectives_implementation", "gloo")
jax.config.update("jax_enable_x64", True)

coord, pid, k, n, work = (
    sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]),
    sys.argv[5],
)
jax.distributed.initialize(coord, num_processes=k, process_id=pid)

from heatmap_tpu.io.sinks import (
    JSONLBlobSink, LevelArraysSink, open_sink, per_process_sink_spec,
)
from heatmap_tpu.io.sources import SyntheticSource
from heatmap_tpu.parallel.multihost import blob_owner, run_job_multihost
from heatmap_tpu.pipeline import BatchJobConfig, run_job

from jax.experimental import multihost_utils


def barrier(tag):
    multihost_utils.process_allgather(np.asarray([pid]))


def blobs_equal(got, want):
    # Decoded equality: re-encoded collision merges may reorder the
    # inner dicts, so string equality is too strict.
    return set(got) == set(want) and all(
        json.loads(got[key]) == json.loads(want[key]) for key in want
    )


cfg = BatchJobConfig(detail_zoom=11, min_detail_zoom=8)
src = SyntheticSource(n=n, seed=13)
batch = 256
checks = {}

# Oracle: plain single-process job over the whole source. Local
# compute only (1 local CPU device) — safe under the distributed init.
want = run_job(SyntheticSource(n=n, seed=13), config=cfg,
               batch_size=batch, max_points_in_flight=0)

# 1) gather egress over the real framed allgather. Colliding blobs
# (straddling host shards) re-encode after the merge, so inner-dict
# key order may differ from the oracle's — compare decoded.
got = run_job_multihost(src, config=cfg, batch_size=batch,
                        egress="gather")
checks["gather_equals_oracle"] = blobs_equal(got, want)

# 1b) weighted job over the same transport: f64 per-point sums must
# merge across hosts exactly like counts (linearity).


class _WSrc:
    n = n

    def batches(self, batch_size):
        off = 0
        for b in SyntheticSource(n=n, seed=13).batches(batch_size):
            m = len(b["latitude"])
            b = dict(b)
            # Deterministic integer-valued weights from the GLOBAL row
            # position (batches() yields the full stream in order even
            # when shard_source_rows later filters) -> exact f64 sums.
            b["value"] = ((np.arange(off, off + m) % 7) + 1).astype(
                np.float64
            )
            off += m
            yield b


wcfg = BatchJobConfig(detail_zoom=11, min_detail_zoom=8, weighted=True)
want_w = run_job(_WSrc(), config=wcfg, batch_size=batch,
                 max_points_in_flight=0)
got_w = run_job_multihost(_WSrc(), config=wcfg, batch_size=batch,
                          egress="gather")
checks["weighted_gather_equals_oracle"] = blobs_equal(got_w, want_w)

# 1c) bounded slice ingest over the same transport: each process
# streams its slice through the CHUNKED cascade + host merge
# (max_points_in_flight composes with multi-process runs) — ~700-point
# chunks force several chunks per slice,
# and blobs must still equal the unbounded oracle exactly.
got_b = run_job_multihost(src, config=cfg, batch_size=batch,
                          egress="gather", max_points_in_flight=700)
checks["bounded_gather_equals_oracle"] = blobs_equal(got_b, want)

# 1d) bounded + WEIGHTED (integer-valued f64 weights sum exactly under
# any chunk/host split) and bounded + SHARDED egress (each process's
# owned shard carries only its keys, values equal the oracle's).
got_wb = run_job_multihost(_WSrc(), config=wcfg, batch_size=batch,
                           egress="gather", max_points_in_flight=700)
checks["bounded_weighted_equals_oracle"] = blobs_equal(got_wb, want_w)
owned_b = run_job_multihost(src, config=cfg, batch_size=batch,
                            egress="sharded", max_points_in_flight=700)
# Completeness, not just consistency: this process's shard must hold
# EXACTLY the oracle keys it owns (every process holds the full
# oracle, so the expected set is computable locally) — a bounded-path
# regression that drops chunks or invents keys fails the set equality
# instead of passing vacuously / dying on a KeyError.
expected_owned = {key for key in want if blob_owner(key, k) == pid}
checks["bounded_sharded_owned_ok"] = (
    set(owned_b) == expected_owned
    and all(json.loads(owned_b[key]) == json.loads(want[key])
            for key in owned_b)
)

# 1e) per-host DP INSIDE the multi-process run: each process shards
# its slice's cascade over its own local devices (8 virtual CPU
# devices per child under the suite's XLA_FLAGS, 1 otherwise — both
# legal), then the cross-process gather merges as usual. The v5e-pod
# layout: DP over local chips x process-sharded ingest.
dp_cfg = BatchJobConfig(detail_zoom=11, min_detail_zoom=8,
                        data_parallel=True)
got_dp = run_job_multihost(src, config=dp_cfg, batch_size=batch,
                           egress="gather")
checks["dp_gather_equals_oracle"] = blobs_equal(got_dp, want)

# 2) sharded blob egress over the real all_to_all; per-host JSONL.
# open_sink(per_process_sink_spec(...)) is exactly the CLI's path —
# the tool must exercise the production spec parser, not re-parse.
with open_sink(per_process_sink_spec(f"jsonl:{work}/blobs.jsonl",
                                     pid)) as sink:
    owned = run_job_multihost(src, sink, cfg, batch_size=batch,
                              egress="sharded")
checks["owned_keys_are_mine"] = all(
    blob_owner(key, k) == pid for key in owned
)
barrier("blobs-written")
if pid == 0:
    merged = {}
    for i in range(k):
        merged.update(JSONLBlobSink.load(f"{work}/blobs.jsonl.p{i:03d}"))
    import json as _json
    checks["sharded_union_equals_oracle"] = (
        set(merged) == set(want)
        and all(merged[key] == _json.loads(want[key]) for key in want)
    )

# 3) columnar sharded egress: per-host level-array dirs.
stats = run_job_multihost(
    src, open_sink(per_process_sink_spec(f"arrays:{work}/cols", pid)),
    cfg, batch_size=batch, egress="sharded",
)
checks["columnar_stats"] = stats.get("egress") == "levels-sharded"
barrier("cols-written")
if pid == 0:
    ref_dir = os.path.join(work, "oracle-cols")
    run_job(SyntheticSource(n=n, seed=13), LevelArraysSink(ref_dir),
            config=cfg, batch_size=batch, max_points_in_flight=0)
    want_cols = LevelArraysSink.load(ref_dir)
    per_host = [LevelArraysSink.load(f"{work}/cols/host{i:03d}")
                for i in range(k)]
    # Zoom SETS must agree too: a spurious extra level (or one missing
    # everywhere) is a real divergence, not something to skip over.
    got_zooms = set().union(*(set(h) for h in per_host))
    ok = got_zooms == set(want_cols)
    for zoom, wlvl in want_cols.items():
        if not ok:
            break
        rows = {c: [] for c in ("row", "col", "value", "user", "timespan")}
        for got_cols in per_host:
            if zoom in got_cols:
                for c in rows:
                    rows[c].append(got_cols[zoom][c])
        if not rows["value"]:
            ok = False
            break
        cat = {c: np.concatenate(rows[c]) for c in rows}
        if len(cat["value"]) != len(wlvl["value"]):
            ok = False
            break
        order_g = np.lexsort((cat["col"], cat["row"], cat["user"],
                              cat["timespan"]))
        order_w = np.lexsort((wlvl["col"], wlvl["row"], wlvl["user"],
                              wlvl["timespan"]))
        for c in rows:
            if not np.array_equal(np.asarray(cat[c])[order_g],
                                  np.asarray(wlvl[c])[order_w]):
                ok = False
        if not ok:
            break
    checks["columnar_union_equals_oracle"] = ok

# 4) cross-process COMPUTE collectives: the binning/aggregation
# kernels on a mesh spanning both processes (1 device each) — psum /
# psum_scatter / all_gather ride the inter-process transport, exactly
# the pod layout (DCN instead of gloo, same program).
from heatmap_tpu.ops import (
    aggregate_keys, bin_points_window, window_from_bounds,
)
from heatmap_tpu.parallel import (
    aggregate_keys_sharded, bin_points_replicated, bin_points_rowsharded,
)
from heatmap_tpu.parallel.multihost import make_hybrid_mesh

# The hybrid mesh spans EVERY device of every process (8 local CPU
# devices per child under the test suite's XLA_FLAGS, 1 otherwise) —
# the realistic pod shape: intra-process "ICI" + inter-process gloo.
mesh = make_hybrid_mesh()
ndev = jax.device_count()  # k * local_device_count
rng = np.random.default_rng(17)
n_pts = ndev * k * (4096 // (ndev * k))  # divides shards for ANY k/ndev
lats = rng.uniform(35.0, 55.0, n_pts)
lons = rng.uniform(-5.0, 20.0, n_pts)
win = window_from_bounds((35.0, 55.0), (-5.0, 20.0), zoom=9,
                         align_levels=0, pad_multiple=ndev)
from jax.sharding import NamedSharding, PartitionSpec as P

sharding = NamedSharding(mesh, P("data"))
lo, hi = pid * (n_pts // k), (pid + 1) * (n_pts // k)
glat = jax.make_array_from_process_local_data(sharding, lats[lo:hi])
glon = jax.make_array_from_process_local_data(sharding, lons[lo:hi])
local_raster = np.asarray(bin_points_window(lats, lons, win))

raster = bin_points_replicated(glat, glon, win, mesh)
got_raster = np.asarray(list(raster.addressable_shards)[0].data)
checks["crossproc_psum_binning"] = bool(
    (got_raster == local_raster).all()
)

# psum_scatter path: the merged raster stays row-sharded — EVERY
# local band (8 per process under the suite's virtual-device flags)
# must equal the oracle's corresponding rows.
rowsharded = bin_points_rowsharded(glat, glon, win, mesh)
checks["crossproc_psum_scatter_binning"] = all(
    bool((np.asarray(s.data) == local_raster[s.index]).all())
    for s in rowsharded.addressable_shards
)

keys = rng.integers(0, 500, n_pts).astype(np.int32)
gkeys = jax.make_array_from_process_local_data(
    NamedSharding(mesh, P("data")), keys[lo:hi]
)
gu, gs, gn = aggregate_keys_sharded(gkeys, mesh, capacity=512)
lu, ls, ln = aggregate_keys(keys, capacity=512)
n_unique = int(np.asarray(list(gn.addressable_shards)[0].data))
lu_n = int(ln)
checks["crossproc_aggregate_keys"] = (
    n_unique == lu_n
    and bool(
        (np.asarray(list(gu.addressable_shards)[0].data)[:n_unique]
         == np.asarray(lu)[:lu_n]).all()
    )
    and bool(  # the reduce-by-key SUMS must survive the merge too
        (np.asarray(list(gs.addressable_shards)[0].data)[:n_unique]
         == np.asarray(ls)[:lu_n]).all()
    )
)

barrier("done")
print(json.dumps({"pid": pid, "ok": all(checks.values()),
                  "checks": checks}), flush=True)
sys.exit(0 if all(checks.values()) else 1)
"""


_SKEW_CHILD = r"""
import json, sys
import numpy as np
import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_cpu_collectives_implementation", "gloo")
jax.config.update("jax_enable_x64", True)

coord, pid, k = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
jax.distributed.initialize(coord, num_processes=k, process_id=pid)

from heatmap_tpu.parallel.multihost import _alltoall_bytes


def payload(src, dst):
    # ONE hot pair (1 -> 0) 100x the rest: the skew shape that made
    # the old dense (k, global-max) frame pad every row.
    n = 200_000 if (src, dst) == (1, 0) else 2_000
    rng = np.random.default_rng(1000 * src + dst)
    return rng.integers(0, 256, n).astype(np.uint8).tobytes()


dest = [payload(pid, d) for d in range(k)]
# max_bytes=300k: the dense frame (k x 200_008 = 800k at k=4) would
# have refused; the shift-decomposed exchange fits because no process
# RECEIVES more than ~206k, and chunk_bytes=64k keeps every collective
# buffer small regardless of the hot payload's size.
got = _alltoall_bytes(dest, max_bytes=300_000, chunk_bytes=64_000)
ok = all(got[s] == payload(s, pid) for s in range(k))
print(json.dumps({"pid": pid, "ok": bool(ok),
                  "checks": {"skew_alltoall": bool(ok)}}), flush=True)
sys.exit(0 if ok else 1)
"""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--k", type=int, default=2)
    ap.add_argument("--n", type=int, default=3000)
    ap.add_argument("--timeout", type=float, default=600.0)
    ap.add_argument("--skew-only", action="store_true",
                    help="run only the skewed byte-exchange check "
                         "(fast; use --k 4 to exercise several shift "
                         "rounds)")
    args = ap.parse_args()
    child_src = _SKEW_CHILD if args.skew_only else _CHILD

    import shutil

    work = tempfile.mkdtemp(prefix="multiproc-check-")
    env = dict(os.environ)
    env["PYTHONPATH"] = "." + os.pathsep + env.get("PYTHONPATH", "")
    coord = f"127.0.0.1:{free_port()}"
    t0 = time.perf_counter()
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", child_src, coord, str(i), str(args.k),
             str(args.n), work],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env,
        )
        for i in range(args.k)
    ]
    ok = True
    reports = []
    # --timeout is a TOTAL budget shared across the children: one hung
    # child must not push the parent past its caller's deadline, and a
    # killed coordinator leaves peers stuck in collectives, so every
    # child is reaped before exit — no orphaned JAX grandchildren.
    deadline = time.monotonic() + args.timeout
    try:
        for p in procs:
            try:
                out, err = p.communicate(
                    timeout=max(1.0, deadline - time.monotonic())
                )
            except subprocess.TimeoutExpired:
                ok = False
                for q in procs:
                    if q.poll() is None:
                        q.kill()
                out, err = p.communicate()
            for line in out.splitlines():
                if line.startswith("{"):
                    print(line, flush=True)
                    reports.append(json.loads(line))
            if p.returncode != 0:
                ok = False
                tail = err.strip().splitlines()[-8:]
                print(f"[child rc={p.returncode}] " + " | ".join(tail),
                      file=sys.stderr, flush=True)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
        shutil.rmtree(work, ignore_errors=True)
    ok = ok and len(reports) == args.k and all(r["ok"] for r in reports)
    print(json.dumps({
        "check": "multiproc", "ok": ok, "k": args.k, "n": args.n,
        "s": round(time.perf_counter() - t0, 1),
    }), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
