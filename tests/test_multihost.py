"""Multi-host layer (parallel.multihost).

Two layers of evidence: the unit tests here pin the pieces
(deterministic process-shard math, the shard+merge algebra — per-host
cascade then blob merge must equal the global cascade, everything
linear in counts — and the single-process degradation contract), and
``test_multiproc_end_to_end`` executes the REAL runtime — k local
processes under ``jax.distributed`` with gloo CPU collectives running
the actual gather allgather and sharded ``all_to_all`` egress
(tools/multiproc_check.py). Only true DCN/ICI transport needs a pod.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from heatmap_tpu.parallel.multihost import (
    _merge_blob_values,
    gather_blobs,
    make_hybrid_mesh,
    process_shard_bounds,
    run_job_multihost,
    shard_source_rows,
)


def test_process_shard_bounds_partition():
    for n in (0, 1, 7, 64, 1001):
        for k in (1, 2, 3, 8):
            slices = [process_shard_bounds(n, k, i) for i in range(k)]
            # Contiguous, disjoint, covering, balanced within 1.
            assert slices[0][0] == 0 and slices[-1][1] == n
            for (a0, a1), (b0, b1) in zip(slices, slices[1:]):
                assert a1 == b0
            sizes = [b - a for a, b in slices]
            assert max(sizes) - min(sizes) <= 1


def test_process_shard_bounds_validates():
    with pytest.raises(ValueError):
        process_shard_bounds(10, 4, 4)


def test_shard_source_range_shardable():
    """Range-shardable sources (Cassandra/Cosmos) get this process's
    interleaved assignment instead of row slicing."""
    from heatmap_tpu.io.sources import CassandraSource
    from heatmap_tpu.parallel.multihost import shard_source

    src = CassandraSource()
    mine = shard_source(src, process_count=4, process_index=2)
    assert (mine.shard_index, mine.shard_count) == (2, 4)
    assert (src.shard_index, src.shard_count) == (0, 1)  # untouched
    owned = [i for i, _ in mine.my_ranges()]
    assert owned == list(range(2, src.config.n_ranges, 4))
    # Pre-sharded sources are a configuration error, not silent data loss.
    with pytest.raises(ValueError, match="already carries"):
        shard_source(mine, process_count=4, process_index=0)


def test_run_job_multihost_gather_rejects_columnar_sinks(tmp_path):
    """Explicit gather egress is blob-based; columnar sinks must be
    refused at submit time (sharded egress is the columnar path)."""
    from heatmap_tpu.io.sinks import LevelArraysSink
    from heatmap_tpu.io.sources import SyntheticSource
    from heatmap_tpu.parallel.multihost import run_job_multihost

    with pytest.raises(ValueError, match="sharded"):
        run_job_multihost(SyntheticSource(n=10),
                          LevelArraysSink(str(tmp_path / "c")),
                          egress="gather")
    with pytest.raises(ValueError, match="egress"):
        run_job_multihost(SyntheticSource(n=10), egress="bogus")


def test_run_job_multihost_columnar_single_process(tmp_path):
    """Columnar sinks now work through run_job_multihost (the round-2
    refusal is lifted): single-process degrades to run_job, writing the
    same level files a plain columnar job writes."""
    from heatmap_tpu.io.sinks import LevelArraysSink
    from heatmap_tpu.io.sources import SyntheticSource
    from heatmap_tpu.pipeline import BatchJobConfig, run_job

    cfg = BatchJobConfig(detail_zoom=10, min_detail_zoom=8)
    src = SyntheticSource(n=500, seed=3)
    run_job_multihost(src, LevelArraysSink(str(tmp_path / "mh")), config=cfg)
    run_job(src, LevelArraysSink(str(tmp_path / "ref")), config=cfg)
    got = LevelArraysSink.load(str(tmp_path / "mh"))
    want = LevelArraysSink.load(str(tmp_path / "ref"))
    assert set(got) == set(want)
    for zoom in want:
        for col in ("row", "col", "value", "user", "timespan"):
            np.testing.assert_array_equal(got[zoom][col], want[zoom][col])


def test_shard_source_returns_none_for_plain_sources():
    from heatmap_tpu.io.sources import SyntheticSource
    from heatmap_tpu.parallel.multihost import shard_source

    assert shard_source(SyntheticSource(n=10), 2, 0) is None


def test_shard_source_rows_covers_exactly():
    batches = [np.full(10, i) for i in range(7)]
    seen = []
    for i in range(3):
        seen += [int(b[0]) for b in shard_source_rows(
            iter(batches), n_total=70, batch_size=10,
            process_count=3, process_index=i,
        )]
    assert seen == list(range(7))


def test_make_hybrid_mesh_single_process_matches_make_mesh(devices):
    from heatmap_tpu.parallel import make_mesh

    mesh = make_hybrid_mesh(devices=devices)
    ref = make_mesh(devices=devices)
    assert mesh.shape == ref.shape
    assert list(mesh.devices.flat) == list(ref.devices.flat)


def test_gather_blobs_single_process_identity():
    blobs = {"all|alltime|3_1_2": json.dumps({"8_40_65": 2.0})}
    assert gather_blobs(blobs) is blobs


def test_merge_blob_values_sums_json_dicts():
    a = json.dumps({"t1": 1.0, "t2": 2.0})
    b = json.dumps({"t2": 3.0, "t3": 4.0})
    assert json.loads(_merge_blob_values(a, b)) == {
        "t1": 1.0, "t2": 5.0, "t3": 4.0
    }
    # Raw-dict form too (non-JSON sinks).
    assert _merge_blob_values({"t": 1}, {"t": 2}) == {"t": 3}


def test_merge_blob_values_rejects_non_numeric_collisions():
    """Anything but summable {tile: number} dicts at a merge point is
    corruption — loud, never last-process-wins (round-2 weak #6)."""
    with pytest.raises(ValueError, match="non-numeric"):
        _merge_blob_values({"t": "x"}, {"t": 1.0})
    with pytest.raises(ValueError, match="not mergeable"):
        _merge_blob_values(json.dumps([1, 2]), json.dumps({"t": 1.0}))
    # Disjoint keys never collide, so shape of the VALUE only matters
    # on actual collisions — including non-numeric new keys.
    assert _merge_blob_values({"a": 1}, {"b": 2}) == {"a": 1, "b": 2}
    assert _merge_blob_values({"a": 1}, {"b": "meta"}) == {
        "a": 1, "b": "meta"
    }


@pytest.mark.slow
def test_sharded_cascade_merge_equals_global():
    """Per-host run + blob merge == single global run (linearity)."""
    from heatmap_tpu.io.sources import SyntheticSource
    from heatmap_tpu.pipeline import BatchJobConfig, run_job
    from heatmap_tpu.pipeline.batch import _run_loaded, load_columns

    cfg = BatchJobConfig(detail_zoom=11, min_detail_zoom=8)
    src = SyntheticSource(n=3000, seed=4)
    batch_size = 256
    global_blobs = run_job(src, config=cfg, batch_size=batch_size)

    k = 3
    merged: dict = {}
    for pi in range(k):
        lats, lons, users, stamps = [], [], [], []
        for batch in shard_source_rows(src.batches(batch_size),
                                       n_total=3000, batch_size=batch_size,
                                       process_count=k, process_index=pi):
            cols = load_columns(batch)
            lats.append(cols["latitude"])
            lons.append(cols["longitude"])
            users.extend(cols["user_id"])
            stamps.extend(cols["timestamp"])
        if not lats or sum(len(a) for a in lats) == 0:
            continue
        local = _run_loaded(
            {
                "latitude": np.concatenate(lats),
                "longitude": np.concatenate(lons),
                "user_id": users,
                "timestamp": stamps,
            },
            cfg,
            as_json=True,
        )
        for key, val in local.items():
            merged[key] = (
                _merge_blob_values(merged[key], val) if key in merged else val
            )
    assert set(merged) == set(global_blobs)
    for key in global_blobs:
        assert json.loads(merged[key]) == pytest.approx(
            json.loads(global_blobs[key])
        )


@pytest.mark.slow
def test_sharded_weighted_merge_equals_global():
    """The multihost ingest path with config.weighted: per-host
    weighted runs merged via _merge_blob_values equal one global
    weighted run exactly (integer weights -> exact f64 sums; collisions
    sum across hosts just like counts)."""
    from heatmap_tpu.pipeline import BatchJobConfig, run_job
    from heatmap_tpu.pipeline.batch import _run_loaded, load_columns

    rng = np.random.default_rng(6)
    n = 2400
    lat = 47.6 + rng.normal(0, 0.3, n)
    lon = -122.3 + rng.normal(0, 0.4, n)
    users = [f"u{int(i)}" for i in rng.integers(0, 12, n)]
    value = rng.integers(0, 9, n).astype(np.float64)

    class _WSrc:
        def batches(self, batch_size):
            for lo in range(0, n, batch_size):
                hi = min(lo + batch_size, n)
                yield {
                    "latitude": lat[lo:hi], "longitude": lon[lo:hi],
                    "user_id": users[lo:hi], "source": [],
                    "timestamp": [], "value": value[lo:hi],
                }

    cfg = BatchJobConfig(detail_zoom=11, min_detail_zoom=8, weighted=True)
    batch_size = 256
    global_blobs = run_job(_WSrc(), config=cfg, batch_size=batch_size)

    k = 3
    merged: dict = {}
    for pi in range(k):
        lats, lons, us, stamps, vals = [], [], [], [], []
        for batch in shard_source_rows(_WSrc().batches(batch_size),
                                       n_total=n, batch_size=batch_size,
                                       process_count=k, process_index=pi):
            cols = load_columns(batch)
            lats.append(cols["latitude"])
            lons.append(cols["longitude"])
            us.extend(cols["user_id"])
            stamps.extend(cols["timestamp"])
            vals.append(cols["value"])
        if not lats or sum(len(a) for a in lats) == 0:
            continue
        local = _run_loaded(
            {
                "latitude": np.concatenate(lats),
                "longitude": np.concatenate(lons),
                "user_id": us,
                "timestamp": stamps,
                "value": np.concatenate(vals),
            },
            cfg,
            as_json=True,
        )
        for key, val in local.items():
            merged[key] = (
                _merge_blob_values(merged[key], val) if key in merged else val
            )
    assert set(merged) == set(global_blobs)
    for key in global_blobs:
        assert json.loads(merged[key]) == json.loads(global_blobs[key])


def test_blob_owner_deterministic_in_range():
    from heatmap_tpu.parallel.multihost import blob_owner

    keys = [f"u{i}|alltime|3_{i % 7}_{i % 5}" for i in range(1000)]
    owners = [blob_owner(k, 4) for k in keys]
    assert owners == [blob_owner(k, 4) for k in keys]  # stable
    assert set(owners) == {0, 1, 2, 3}  # every shard used
    assert all(0 <= o < 4 for o in owners)


def test_scatter_blobs_partition_merge_equals_gather():
    """Sharded egress algebra: per-host partition + owner-side merge
    yields disjoint shards whose union equals the full gather merge."""
    from heatmap_tpu.parallel.multihost import (
        blob_owner, merge_blob_parts, partition_blobs,
    )

    rng = np.random.default_rng(5)
    k = 3
    # Overlapping keys across hosts (straddling blobs) with numeric
    # JSON payloads that must SUM on collision.
    locals_ = []
    for host in range(k):
        blobs = {}
        for i in rng.integers(0, 40, 25):
            key = f"u{i % 6}|alltime|4_{i % 4}_{i % 3}"
            blobs[key] = json.dumps({f"9_{i}_{i}": float(host + 1)})
        locals_.append(blobs)

    want = merge_blob_parts(locals_)

    owned = []
    for owner in range(k):
        parts = [partition_blobs(loc, k)[owner] for loc in locals_]
        owned.append(merge_blob_parts(parts))
    # Disjoint at blob granularity, each key on its blob_owner shard...
    seen = {}
    for host, shard in enumerate(owned):
        for key in shard:
            assert key not in seen
            assert blob_owner(key, k) == host
            seen[key] = shard[key]
    # ...and the union IS the gather result.
    assert set(seen) == set(want)
    for key in want:
        assert json.loads(seen[key]) == json.loads(want[key])


def test_scatter_blobs_fake_transport_wiring():
    """scatter_blobs end to end with an injected transport simulating
    3 processes: every host receives exactly its owner shard."""
    from heatmap_tpu.parallel.multihost import (
        blob_owner, partition_blobs, scatter_blobs,
    )

    k = 3
    locals_ = [
        {f"u{j}|alltime|2_{j}_1": json.dumps({"7_1_1": 1.0 * (i + 1)})
         for j in range(6)}
        for i in range(k)
    ]
    # Phase 1: what every host would SEND (payloads[d] JSON of its
    # owner-d sub-dict) — precomputed so the fake transport can hand
    # host i row i of every sender.
    sent = [
        [json.dumps(p).encode() for p in partition_blobs(loc, k)]
        for loc in locals_
    ]
    results = []
    for i in range(k):
        transport = lambda payloads, i=i: [sent[s][i] for s in range(k)]
        results.append(
            scatter_blobs(locals_[i], process_count=k, transport=transport)
        )
    all_keys = set().union(*locals_)
    for i, owned in enumerate(results):
        assert set(owned) == {key for key in all_keys
                              if blob_owner(key, k) == i}
        for key, val in owned.items():
            # 3 hosts each contributed 1.0*(host+1) under the same
            # inner tile key -> summed to 6.0.
            assert json.loads(val) == {"7_1_1": 6.0}


@pytest.mark.slow
def test_scatter_levels_equals_global_columnar_run(tmp_path):
    """The multihost 'done' bar: per-host cascade + level scatter +
    per-host columnar writes reassemble to exactly the global columnar
    run, with no host ever holding the full result."""
    from heatmap_tpu.io.sinks import LevelArraysSink
    from heatmap_tpu.io.sources import SyntheticSource
    from heatmap_tpu.pipeline import BatchJobConfig, run_job
    from heatmap_tpu.pipeline.batch import _run_loaded, load_columns
    from heatmap_tpu.parallel.multihost import (
        _CaptureLevels, _levels_from_bytes, _levels_to_bytes,
        merge_level_parts, partition_levels,
    )

    cfg = BatchJobConfig(detail_zoom=11, min_detail_zoom=8)
    src = SyntheticSource(n=3000, seed=9)
    batch_size = 256
    run_job(src, LevelArraysSink(str(tmp_path / "global")), config=cfg,
            batch_size=batch_size)
    want = LevelArraysSink.load(str(tmp_path / "global"))

    k = 3
    # Phase 1: per-host local cascades -> per-destination payloads
    # (through the real serialization, as the jax transport would).
    sent: list[list[bytes]] = []
    for pi in range(k):
        lats, lons, users, stamps = [], [], [], []
        for batch in shard_source_rows(src.batches(batch_size),
                                       n_total=3000, batch_size=batch_size,
                                       process_count=k, process_index=pi):
            cols = load_columns(batch)
            lats.append(cols["latitude"])
            lons.append(cols["longitude"])
            users.extend(cols["user_id"])
            stamps.extend(cols["timestamp"])
        cap = _CaptureLevels()
        if lats and sum(len(a) for a in lats):
            _run_loaded(
                {
                    "latitude": np.concatenate(lats),
                    "longitude": np.concatenate(lons),
                    "user_id": users,
                    "timestamp": stamps,
                },
                cfg, as_json=False, sink=cap,
            )
        sent.append([_levels_to_bytes(p)
                     for p in partition_levels(cap.levels, k)])

    # Phase 2: deliver + merge + per-host columnar write.
    for pi in range(k):
        owned = merge_level_parts(
            _levels_from_bytes(sent[s][pi]) for s in range(k)
        )
        LevelArraysSink(str(tmp_path / f"host{pi}")).write_levels(owned)

    # Reassemble the per-host shards and compare to the global run.
    for zoom, wlvl in want.items():
        rows = {c: [] for c in ("row", "col", "value", "user", "timespan")}
        for pi in range(k):
            got = LevelArraysSink.load(str(tmp_path / f"host{pi}"))
            if zoom in got:
                for c in rows:
                    rows[c].append(got[zoom][c])
        got_cols = {c: np.concatenate(rows[c]) for c in rows}
        assert len(got_cols["value"]) == len(wlvl["value"])
        # Order-insensitive compare: sort both sides the same way.
        def _order(c):
            return np.lexsort((c["col"], c["row"], c["user"], c["timespan"]))
        go, wo = _order(got_cols), _order(wlvl)
        for c in rows:
            np.testing.assert_array_equal(
                got_cols[c][go], np.asarray(wlvl[c])[wo]
            )


def test_run_job_multihost_weighted_single_process():
    """config.weighted flows through run_job_multihost's single-process
    fall-through (and the multi-process branch shares the same
    _run_loaded call, exercised shard-by-shard above)."""
    from heatmap_tpu.pipeline import BatchJobConfig, run_job

    rng = np.random.default_rng(8)
    n = 500
    lat = 47.6 + rng.normal(0, 0.2, n)
    lon = -122.3 + rng.normal(0, 0.2, n)

    class _WSrc:
        def batches(self, batch_size):
            yield {
                "latitude": lat, "longitude": lon,
                "user_id": ["u"] * n, "source": [], "timestamp": [],
                "value": np.full(n, 2.0),
            }

    cfg = BatchJobConfig(detail_zoom=10, min_detail_zoom=8, weighted=True)
    a = run_job_multihost(_WSrc(), config=cfg)
    b = run_job(_WSrc(), config=cfg)
    assert a == b and len(a) > 0


def test_run_job_multihost_single_process_falls_through():
    from heatmap_tpu.io.sources import SyntheticSource
    from heatmap_tpu.pipeline import BatchJobConfig, run_job

    cfg = BatchJobConfig(detail_zoom=11, min_detail_zoom=9)
    src = SyntheticSource(n=1000, seed=1)
    assert run_job_multihost(src, config=cfg) == run_job(src, config=cfg)


@pytest.mark.slow
def test_multiproc_end_to_end():
    """REAL 2-process execution of the multihost layer: distributed
    init, process-sharded ingest, gather_blobs' framed allgather and
    scatter_blobs/scatter_levels' all_to_all over gloo CPU collectives,
    per-host sink shards reassembling to the single-process oracle
    (tools/multiproc_check.py — subprocesses, so the suite's own jax
    stays single-process)."""
    # The tool's --timeout is its TOTAL child budget; the outer
    # timeout only needs a teardown margin on top.
    r = subprocess.run(
        [sys.executable, "tools/multiproc_check.py", "--k", "2",
         "--n", "2000", "--timeout", "390"],
        capture_output=True, text=True, cwd=_REPO_ROOT, timeout=450,
        env=_multiproc_env(),
    )
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("{")]
    assert lines, f"no output; stderr: {r.stderr[-1500:]}"
    verdict = json.loads(lines[-1])
    assert r.returncode == 0 and verdict["ok"], (
        f"multiproc check failed: {lines}\nstderr: {r.stderr[-1500:]}"
    )


def _multiproc_env():
    # The children force jax_platforms=cpu themselves; they only need
    # the repo (and the site dir that may hold the accelerator plugin)
    # importable.
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    return env


def test_slice_source_recuts_oversized_batches():
    """_SliceSource pins the shard assignment at the construction batch
    size and re-cuts locally when the bounded path re-reads at a
    smaller granularity — rows, order, and column alignment preserved."""
    from heatmap_tpu.parallel.multihost import _SliceSource

    class _Src:
        def batches(self, bs):
            for i in range(0, 250, bs):
                m = min(bs, 250 - i)
                yield {
                    "latitude": np.arange(i, i + m, dtype=np.float64),
                    "longitude": np.arange(i, i + m, dtype=np.float64),
                    "user_id": [f"u{j}" for j in range(i, i + m)],
                    "timestamp": [None] * m,
                }

    src = _SliceSource(_Src(), n_total=250, batch_size=100)
    out = list(src.batches(40))
    assert all(len(b["latitude"]) <= 40 for b in out)
    lats = np.concatenate([b["latitude"] for b in out])
    np.testing.assert_array_equal(lats, np.arange(250, dtype=np.float64))
    users = [u for b in out for u in b["user_id"]]
    assert users == [f"u{j}" for j in range(250)]
    # At or above the pinned size: batches pass through untouched.
    passthrough = list(src.batches(100))
    assert [len(b["latitude"]) for b in passthrough] == [100, 100, 50]


@pytest.mark.slow
def test_run_job_multihost_bounded_single_process_matches():
    """max_points_in_flight routes the single-process fallthrough
    through run_job's bounded path — blobs equal the unbounded run."""
    from heatmap_tpu.io.sources import SyntheticSource
    from heatmap_tpu.parallel.multihost import run_job_multihost
    from heatmap_tpu.pipeline import BatchJobConfig

    cfg = BatchJobConfig(detail_zoom=10, min_detail_zoom=7)
    want = run_job_multihost(SyntheticSource(n=2000, seed=3), config=cfg,
                             batch_size=256, max_points_in_flight=0)
    got = run_job_multihost(SyntheticSource(n=2000, seed=3), config=cfg,
                            batch_size=256, max_points_in_flight=300)
    assert got == want and len(got) > 0


@pytest.mark.slow
def test_multiproc_skew_exchange():
    """REAL 4-process gloo run of the skew-proof byte exchange: one
    payload 100x the rest passes under a max_bytes the old dense
    (k, global-max) frame would have violated, with chunked ppermute
    rounds bounding every collective buffer."""
    r = subprocess.run(
        [sys.executable, "tools/multiproc_check.py", "--skew-only",
         "--k", "4", "--timeout", "300"],
        capture_output=True, text=True, cwd=_REPO_ROOT, timeout=360,
        env=_multiproc_env(),
    )
    lines = [ln for ln in r.stdout.splitlines() if ln.startswith("{")]
    assert lines, f"no output; stderr: {r.stderr[-1500:]}"
    verdict = json.loads(lines[-1])
    assert r.returncode == 0 and verdict["ok"], (
        f"skew exchange failed: {lines}\nstderr: {r.stderr[-1500:]}"
    )


def test_never_beating_host_caught_at_first_boundary():
    """The detection gap pinned by the satellite: a host whose gauge
    sample went STALE is caught by the age map alone, but a host that
    NEVER heartbeat has no sample to go stale — without ``expected`` it
    is invisible, and with ``expected`` it is flagged (age=inf) at the
    first phase boundary instead of hanging the job."""
    import time as _time

    from heatmap_tpu import obs
    from heatmap_tpu.parallel.multihost import (StragglerTimeout,
                                                check_heartbeats)

    obs.enable_metrics(True)
    try:
        now = _time.time()
        obs.heartbeat("join", process=0)
        obs.heartbeat("join", process=1)
        # Hosts 0 and 1 beat; host 2 never does.

        # Observed-hosts-only semantics: everything fresh, no straggler
        # — the never-beating host is invisible.
        ages = check_heartbeats(5.0, now=now)
        assert set(ages) == {"0", "1"}

        # expected= closes the gap at the first boundary, with age=inf.
        with pytest.raises(StragglerTimeout) as ei:
            check_heartbeats(5.0, now=now, expected=[0, 1, 2])
        assert ei.value.stale == {"2": float("inf")}

        # Contrast: a host that DID beat and then went silent is the
        # ordinary stale case, caught without expected=.
        with pytest.raises(StragglerTimeout) as ei:
            check_heartbeats(5.0, now=now + 10.0)
        assert set(ei.value.stale) == {"0", "1"}
    finally:
        obs.enable_metrics(False)


def test_check_heartbeats_expected_matches_beaten_hosts():
    """expected= is a no-op when every expected label has beaten."""
    from heatmap_tpu import obs
    from heatmap_tpu.parallel.multihost import check_heartbeats

    obs.enable_metrics(True)
    try:
        for p in range(3):
            obs.heartbeat("join", process=p)
        ages = check_heartbeats(5.0, expected=[0, 1, 2])
        assert set(ages) == {"0", "1", "2"}
    finally:
        obs.enable_metrics(False)
