"""Device-resident GSPMD cascade (parallel/gspmd.py): the one
formulation of the data-parallel cascade.

Four layers under test:

- the global-view NamedSharding programs themselves (uniform DP and
  Morton-range), gated byte-identical against the single-device
  cascade at the kernel level (level arrays AND counts), and their
  lowering pinned to the same collectives;
- the end-to-end mesh route through run_job — every tested shape
  (weighted, retraction sign=-1, pow2-bucketed, Morton-partitioned,
  morton + adaptive_capacity, multihost-elastic) must serve blobs
  byte-identical to ``data_parallel=False``;
- donation safety: re-using a donated buffer is a typed
  :class:`DonatedBufferError` on every platform, ``donate_argnums`` is
  dropped automatically on CPU, and results are byte-identical either
  way;
- the host->device feeder (pipeline/feeder.py): order preservation,
  overlap stats, the ``feeder.put`` fault site, and byte-identical
  ingest with the feeder on/off.

Plus the jax<0.5 compat-shim regression: importing the gspmd module
under ``mesh.force_cpu_devices`` must yield a working multi-device CPU
mesh (satellite of the same PR).
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from heatmap_tpu import faults, obs
from heatmap_tpu.parallel import gspmd
from heatmap_tpu.parallel.mesh import make_mesh, named_sharding
from heatmap_tpu.pipeline import BatchJobConfig, feeder, run_job
from heatmap_tpu.pipeline.batch import run_batch

DZ = 12
SPACE = 1 << (2 * DZ)


def _rows(n=500, seed=0,
          users=("alice", "bob", "rt-bus7", "xscout", "carol")):
    rng = np.random.default_rng(seed)
    return [{
        "latitude": float(rng.uniform(40.0, 55.0)),
        "longitude": float(rng.uniform(-5.0, 15.0)),
        "user_id": users[int(rng.integers(0, len(users)))],
        "timestamp": 1_500_000_000_000 + int(rng.integers(0, 10**9)),
    } for _ in range(n)]


class _ColSource:
    def __init__(self, rows):
        self.rows = rows

    def batches(self, batch_size):
        for i in range(0, len(self.rows), batch_size):
            chunk = self.rows[i:i + batch_size]
            out = {
                "latitude": [r["latitude"] for r in chunk],
                "longitude": [r["longitude"] for r in chunk],
                "user_id": [r["user_id"] for r in chunk],
                "timestamp": [r.get("timestamp") for r in chunk],
            }
            if any("value" in r for r in chunk):
                out["value"] = [float(r.get("value", 1.0)) for r in chunk]
            yield out


def _cfg(**kw):
    base = dict(detail_zoom=DZ, min_detail_zoom=6, data_parallel=True)
    base.update(kw)
    return BatchJobConfig(**base)


def _levels_equal(a, b):
    """Level-tuple equality up to each level's REAL row count (the
    padded tails may differ only past n; they don't here, but the
    contract is the prefix)."""
    assert len(a) == len(b)
    for (au, as_, an), (bu, bs, bn) in zip(a, b):
        n = int(an)
        assert n == int(bn)
        assert np.array_equal(np.asarray(au), np.asarray(bu))
        assert np.array_equal(np.asarray(as_), np.asarray(bs))


def _keys(n, seed, n_slots=20):
    rng = np.random.default_rng(seed)
    code = rng.integers(0, SPACE, n)
    slot = rng.integers(0, n_slots, n)
    return jnp.asarray((slot << np.int64(2 * DZ)) | code, jnp.int64)


# -- kernel-level byte identity --------------------------------------------


def test_gspmd_uniform_matches_single_device():
    from heatmap_tpu.ops import pyramid

    mesh = make_mesh()
    ck = _keys(4096, 3)
    w = jnp.asarray(np.random.default_rng(4).integers(1, 9, 4096),
                    jnp.float64)
    valid = jnp.asarray(np.random.default_rng(5).random(4096) > 0.1)
    for weights in (None, w):
        acc = jnp.float64 if weights is not None else None
        got = gspmd.pyramid_gspmd_uniform(
            ck, mesh, weights=weights, valid=valid, levels=6,
            capacity=4096, acc_dtype=acc)
        want = pyramid.pyramid_sparse_morton(
            ck, weights=weights, valid=valid, levels=6, capacity=4096,
            acc_dtype=acc)
        _levels_equal(got, want)


def test_gspmd_uniform_eager_equals_jit():
    mesh = make_mesh()
    ck = _keys(2048, 7)
    eager = gspmd.pyramid_gspmd_uniform(ck, mesh, levels=5, capacity=2048)
    jitted = jax.jit(
        lambda k: gspmd.pyramid_gspmd_uniform(k, mesh, levels=5,
                                              capacity=2048))(ck)
    _levels_equal(eager, jitted)


def _plan(splits, ndev):
    from heatmap_tpu.parallel.partition import PartitionPlan

    return PartitionPlan(
        detail_zoom=DZ, n_shards=ndev, splits=tuple(int(x) for x in splits),
        sampled_points=0, balance_factor=1.25, shard_mass=(), resplits=0,
        fingerprint="")


def _shard_route(mesh, ck, valid, splits, counts, weights=None):
    """route_on_device on every shard of ``mesh``: (ndev, recv) keys,
    weights (or None) and the per-shard overflow flags."""
    from jax.sharding import PartitionSpec as P

    from heatmap_tpu.parallel.mesh import shard_map

    ndev = mesh.devices.size

    def body(k, v, spl, *w):
        rk, rw, over = gspmd.route_on_device(
            k, v, spl, w[0] if w else None, code_bits=2 * DZ,
            axes=("data",), n_shards=ndev,
            pair_capacity=counts.pair_capacity,
            recv_capacity=counts.recv_capacity)
        return rk[None], (rk if rw is None else rw)[None], over[None]

    extra = () if weights is None else (weights,)
    fn = jax.jit(shard_map(body, mesh,
                           in_specs=(P("data"), P("data"), P())
                           + (P("data"),) * len(extra),
                           out_specs=(P("data"),) * 3, check_vma=False))
    rk, rw, over = fn(ck, valid, jnp.asarray(splits), *extra)
    return (np.asarray(rk), None if weights is None else np.asarray(rw),
            np.asarray(over))


def test_route_on_device_matches_host_router():
    """Every valid lane reaches the shard the host convention names
    (shard = #{splits <= code}, side='right'), sentinel-padded to the
    receive width; with weights, in global lane order, each weight
    beside its key. The widths from the exact host counts hold every
    lane."""
    from heatmap_tpu.parallel import partition

    mesh = make_mesh()
    rng = np.random.default_rng(11)
    n = 2048
    code = rng.integers(0, SPACE, n)
    ck = jnp.asarray((rng.integers(0, 8, n) << np.int64(2 * DZ)) | code)
    valid = rng.random(n) > 0.2
    splits = np.sort(rng.integers(1, SPACE, 7))
    counts = partition.route_counts(_plan(splits, 8), code, valid)
    weights = np.arange(n, dtype=np.float64)
    got, _, over = _shard_route(mesh, ck, jnp.asarray(valid), splits, counts)
    got_w, w_of, over_w = _shard_route(mesh, ck, jnp.asarray(valid), splits,
                                       counts, jnp.asarray(weights))
    owner = np.searchsorted(splits, code, side="right")
    sentinel = np.iinfo(np.int64).max
    assert got.shape == (8, counts.recv_capacity)
    assert not over.any() and not over_w.any()
    for d in range(8):
        mine = valid & (owner == d)
        want = np.asarray(ck)[mine]
        assert np.array_equal(np.sort(got[d][:want.size]), np.sort(want))
        assert np.array_equal(got_w[d][:want.size], want)
        assert np.array_equal(w_of[d][:want.size], weights[mine])
        assert (got[d][want.size:] == sentinel).all()
        assert (w_of[d][want.size:] == 0).all()
        assert counts.owned[d] == want.size
    src = np.arange(n) // (n // 8)
    assert counts.remote_lanes == int((valid & (owner != src)).sum())
    assert counts.overflow_lanes == 0


def _walk_codes(n, seed):
    """Codes of seeded random walks: runs of neighbouring tiles."""
    from heatmap_tpu.tilemath.morton import morton_encode_np

    rng = np.random.default_rng(seed)
    walks, steps = 16, n // 16
    start = rng.integers(0, 1 << DZ, (walks, 2))
    path = start[:, None, :] + np.cumsum(
        rng.integers(-1, 2, (walks, steps, 2)), axis=1)
    path = np.clip(path, 0, (1 << DZ) - 1).reshape(-1, 2)
    return morton_encode_np(path[:, 0], path[:, 1]).astype(np.int64)


def _range_case(case, ndev, n=4096):
    """(codes, valid, splits) of one routing case."""
    from heatmap_tpu.parallel import partition

    rng = np.random.default_rng(ndev)
    code = _walk_codes(n, 5)
    valid = rng.random(n) > 0.05
    splits = np.asarray(partition.plan_partition(
        code, ndev, detail_zoom=DZ, valid=valid).splits)
    if case == "one_owner":
        splits = np.full(ndev - 1, SPACE - 1)
        code = code % (SPACE - 1)
    elif case == "one_source":
        valid[n // ndev:] = False
    elif case == "empty_shards":
        splits = np.sort(np.concatenate(
            [splits[:2], np.full(ndev - 3, splits[1])]))
    elif case == "hotspot":
        # 95% of the lanes in one 64x64-tile block inside range 1 of
        # even splits: one shard owns nearly the whole stream.
        splits = np.arange(1, ndev) * (SPACE // ndev)
        hot = rng.random(n) < 0.95
        code = np.where(hot, splits[0] + (7 << 12)
                        + rng.integers(0, 1 << 12, n), code)
    elif case == "unique_heavy":
        # Every point its own detail tile.
        code = rng.choice(SPACE, n, replace=False)
        splits = np.asarray(partition.plan_partition(
            code, ndev, detail_zoom=DZ, valid=valid).splits)
    elif case == "all_invalid":
        valid[:] = False
    return code, valid, splits


RANGE_CASES = ["traces", "one_owner", "one_source", "empty_shards",
               "exactly_full"]
#: Cases on four devices only: the partitioned backend's detail stage
#: (interpret mode here), a one-range hotspot, a stream of all-distinct
#: tiles, a (data, tile) mesh, and a stream with no valid lane.
MESH4_CASES = ["partitioned", "hotspot", "unique_heavy", "mesh_2d",
               "all_invalid"]


@pytest.mark.parametrize(
    "case,ndev", [(c, d) for d in (4, 8) for c in RANGE_CASES]
    + [(c, 4) for c in MESH4_CASES])
def test_gspmd_range_matches_single_device(case, ndev):
    """The owner-routed range program is byte-identical to the
    single-device cascade on every routing case, counts and integer
    weights alike, so no row is dropped or duplicated; the widths come
    from the exact counts, bucketed as run_job buckets them
    ("exactly_full": unbucketed, so the widest owner fills its receive
    buffer exactly)."""
    from functools import partial

    from heatmap_tpu.ops import pyramid
    from heatmap_tpu.parallel import partition
    from heatmap_tpu.pipeline import bucketing

    if case == "mesh_2d":
        mesh = make_mesh(data=2, tile=2, devices=jax.devices()[:ndev])
    else:
        mesh = make_mesh(devices=jax.devices()[:ndev])
    backend = "partitioned" if case == "partitioned" else "scatter"
    code, valid, splits = _range_case(case, ndev)
    n = len(code)
    slot = np.random.default_rng(3).integers(0, 20, n)
    ck = jnp.asarray((slot << np.int64(2 * DZ)) | code)
    bucket = (None if case == "exactly_full"
              else partial(bucketing.bucket_size, mode="geometric",
                           min_bucket=64))
    counts = partition.route_counts(_plan(splits, ndev), code, valid,
                                    bucket=bucket)
    if case == "exactly_full":
        assert max(counts.owned) == counts.recv_capacity
    w = jnp.asarray(np.random.default_rng(4).integers(1, 9, n), jnp.float64)
    for weights in (None, w):
        acc = None if weights is None else jnp.float64
        got = jax.jit(lambda k, v, s, wt: gspmd.pyramid_gspmd_range(
            k, mesh, s, code_bits=2 * DZ, slot_bound=20,
            pair_capacity=counts.pair_capacity,
            recv_capacity=counts.recv_capacity, weights=wt, valid=v,
            levels=6, capacity=n, acc_dtype=acc, backend=backend,
            weight_bound=None if wt is None else 8))(
                ck, jnp.asarray(valid), jnp.asarray(splits), weights)
        want = pyramid.pyramid_sparse_morton(
            ck, weights=weights, valid=jnp.asarray(valid), levels=6,
            capacity=n, acc_dtype=acc)
        _levels_equal(got, want)
        assert int(got[0][2]) == len(np.unique(np.asarray(ck)[valid]))


#: Collective kinds in the lowering of the owner-routed count cascade:
#: the routing and egress all-to-alls, the boundary and per-slot
#: all-gathers, and the overflow flag's all-reduce. Another kind, or
#: one fewer, means the mesh program changed.
ROUTED_COLLECTIVES = ["all-gather", "all-reduce", "all-to-all"]


def _collective_kinds(lowered):
    import re

    return sorted(set(re.findall(
        r"\b(all-reduce|reduce-scatter|all-to-all|all-gather"
        r"|collective-permute|collective-broadcast)\b",
        lowered.as_text(dialect="hlo"))))


def test_cascade_lowering_pins_collectives():
    """_build_cascade_jit on a 2^12-point count job: on one device it
    lowers with no collective; on four, with a Morton plan, it lowers
    to the owner-routed program (its all-to-all present) with the
    collective kinds of ROUTED_COLLECTIVES."""
    from heatmap_tpu.parallel import partition
    from heatmap_tpu.parallel.mesh import DATA_AXIS
    from heatmap_tpu.pipeline import cascade as cascade_mod

    n = 1 << 12
    ccfg = cascade_mod.CascadeConfig(detail_zoom=DZ, min_detail_zoom=6,
                                     result_delta=5)
    rng = np.random.default_rng(0)
    codes = rng.integers(0, SPACE, n)
    slots = rng.integers(0, 8, n)
    valid = rng.random(n) > 0.1
    jit = cascade_mod._build_cascade_jit
    one = jit.lower(jnp.asarray(codes), jnp.asarray(slots), config=ccfg,
                    n_slots=8, valid=jnp.asarray(valid), capacity=n)
    assert _collective_kinds(one) == []
    mesh = make_mesh(devices=jax.devices()[:4])
    plan = partition.plan_partition(codes, 4, detail_zoom=DZ, valid=valid)
    route = partition.route_counts(plan, codes, valid)
    rows = named_sharding(mesh, DATA_AXIS)

    def put(a):
        return jax.device_put(jnp.asarray(a), rows)

    four = jit.lower(
        put(codes), put(slots), config=ccfg, n_slots=8, valid=put(valid),
        capacity=n, mesh=mesh,
        partition_splits=jnp.asarray(plan.splits, jnp.int64),
        route_capacity=(route.pair_capacity, route.recv_capacity))
    assert _collective_kinds(four) == ROUTED_COLLECTIVES


def test_gspmd_range_one_shard_matches():
    """A one-device mesh: one range, no split, no boundary tile."""
    from heatmap_tpu.ops import pyramid

    mesh = make_mesh(devices=jax.devices()[:1])
    code, valid, _ = _range_case("traces", 4, n=2048)
    ck = jnp.asarray(code)
    got = jax.jit(lambda k, v: gspmd.pyramid_gspmd_range(
        k, mesh, jnp.zeros((0,), jnp.int64), code_bits=2 * DZ,
        slot_bound=1, pair_capacity=2048, recv_capacity=2048, valid=v,
        levels=4, capacity=2048))(ck, jnp.asarray(valid))
    want = pyramid.pyramid_sparse_morton(ck, valid=jnp.asarray(valid),
                                         levels=4, capacity=2048)
    _levels_equal(got, want)


def test_gspmd_range_partitioned_backend_matches():
    """The per-shard detail stage on the partitioned segment kernel
    (interpret mode here) gives the scatter program's bytes."""
    from heatmap_tpu.ops import pyramid
    from heatmap_tpu.parallel import partition

    mesh = make_mesh(devices=jax.devices()[:4])
    code, valid, splits = _range_case("traces", 4, n=2048)
    ck = jnp.asarray((np.int64(3) << np.int64(2 * DZ)) | code)
    counts = partition.route_counts(_plan(splits, 4), code, valid)
    got = jax.jit(lambda k, v, spl: gspmd.pyramid_gspmd_range(
        k, mesh, spl, code_bits=2 * DZ, slot_bound=4,
        pair_capacity=counts.pair_capacity,
        recv_capacity=counts.recv_capacity, valid=v, levels=4,
        capacity=2048, backend="partitioned"))(
            ck, jnp.asarray(valid), jnp.asarray(splits))
    want = pyramid.pyramid_sparse_morton(ck, valid=jnp.asarray(valid),
                                         levels=4, capacity=2048)
    _levels_equal(got, want)


def test_gspmd_range_overflow_is_loud():
    """Widths below the exact counts drop lanes, and the program says
    so: every level's count is forced past its capacity, and the
    counts' overflow_lanes reports the lanes past the widths."""
    from heatmap_tpu.parallel import partition

    mesh = make_mesh(devices=jax.devices()[:4])
    code, valid, splits = _range_case("traces", 4, n=2048)
    exact = partition.route_counts(_plan(splits, 4), code, valid)
    for pair, recv in ((exact.pair_capacity - 1, exact.recv_capacity),
                       (exact.pair_capacity, exact.recv_capacity - 1)):
        forced = partition.RouteCounts(pairs=exact.pairs, pair_capacity=pair,
                                       recv_capacity=recv)
        assert forced.overflow_lanes > 0
        out = jax.jit(lambda k, v, spl: gspmd.pyramid_gspmd_range(
            k, mesh, spl, code_bits=2 * DZ, slot_bound=1,
            pair_capacity=pair, recv_capacity=recv, valid=v, levels=3,
            capacity=2048))(jnp.asarray(code), jnp.asarray(valid),
                            jnp.asarray(splits))
        assert all(int(n) > 2048 for _, _, n in out)


# -- end-to-end byte identity ----------------------------------------------


def _ab(rows, **kw):
    a = run_job(_ColSource(rows), config=_cfg(**kw))
    kw.pop("spatial_partition")  # the single-device run has no plan
    b = run_job(_ColSource(rows), config=_cfg(data_parallel=False, **kw))
    assert a == b and len(a) > 0
    return a


def test_run_job_gspmd_uniform_byte_identical():
    _ab(_rows(n=800, seed=42), spatial_partition="off")


def test_run_job_gspmd_morton_byte_identical():
    _ab(_rows(n=800, seed=42), spatial_partition="morton")


@pytest.mark.slow
def test_run_job_gspmd_weighted_byte_identical():
    rng = np.random.default_rng(15)
    rows = _rows(n=1200, seed=15)
    for r in rows:
        r["value"] = float(rng.integers(1, 12))
    _ab(rows, weighted=True, spatial_partition="morton")


@pytest.mark.slow
def test_run_job_gspmd_pad_bucketing_byte_identical():
    _ab(_rows(n=1500, seed=5), pad_bucketing="pow2",
        spatial_partition="morton")


def _walk_rows(n=800, seed=31, users=("alice", "bob", "rt-bus7", "xscout",
                                      "carol")):
    """Rows along seeded GPS-like walks: neighbouring fixes share tiles."""
    rng = np.random.default_rng(seed)
    walks = 8
    start = np.column_stack([rng.uniform(40.0, 55.0, walks),
                             rng.uniform(-5.0, 15.0, walks)])
    steps = rng.normal(0.0, 2e-3, (walks, n // walks, 2))
    path = (start[:, None, :] + np.cumsum(steps, axis=1)).reshape(-1, 2)
    who = np.repeat(rng.integers(0, len(users), walks), n // walks)
    return [{"latitude": float(lat), "longitude": float(lon),
             "user_id": users[int(u)],
             "timestamp": 1_500_000_000_000 + i}
            for i, ((lat, lon), u) in enumerate(zip(path, who))]


@pytest.mark.parametrize("ndev", [4, 8])
def test_run_job_routed_matches_single_device_and_oracle(ndev, monkeypatch,
                                                         tmp_path):
    """run_job on a count job with data_parallel=True runs the owner-
    routed program with its inputs spread over the mesh, and writes the
    single-device cascade's blobs, which are the oracle's; the exchange
    records its exact counts and no lane past its widths."""
    import oracle
    from heatmap_tpu.pipeline import cascade as cascade_mod

    devs = jax.devices()[:ndev]
    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: devs)
    real, seen = cascade_mod.run_cascade, []

    def recording(codes, slots, *args, **kwargs):
        seen.append((len(codes.sharding.device_set),
                     kwargs.get("route_capacity")))
        return real(codes, slots, *args, **kwargs)

    monkeypatch.setattr(cascade_mod, "run_cascade", recording)
    rows = _walk_rows()
    path = str(tmp_path / "events.jsonl")
    log = obs.EventLog(path)
    obs.set_event_log(log)
    try:
        got = run_job(_ColSource(rows), config=_cfg(
            spatial_partition="morton"))
    finally:
        obs.set_event_log(None)
        log.close()
    single = run_job(_ColSource(rows), config=_cfg(data_parallel=False))
    want = oracle.run_job(rows, detail_zoom=DZ, min_detail_zoom=6,
                          amplify_all=False)
    assert got == single and len(got) > 0
    assert got.keys() == want.keys()
    assert all(json.loads(got[k]) == want[k] for k in want)
    assert seen[0][0] == ndev and seen[0][1] is not None
    route = [e for e in obs.read_events(path) if e["event"] == "mesh_route"]
    assert len(route) == 1 and len(route[0]["lanes"]) == ndev
    excluded = sum(r["user_id"].startswith("x") for r in rows)
    assert sum(route[0]["lanes"]) == 2 * len(rows) - excluded
    assert route[0]["overflow_lanes"] == 0
    assert route[0]["recv_capacity"] >= max(route[0]["lanes"])


def test_run_job_gspmd_morton_adaptive_composes():
    """morton + adaptive_capacity runs on the mesh, and its blobs equal
    BOTH the single-device cascade and the non-adaptive range run
    (adaptive is result-neutral)."""
    rows = _rows(n=800, seed=9)
    adaptive = run_job(_ColSource(rows), config=_cfg(
        spatial_partition="morton", adaptive_capacity=True))
    plain = run_job(_ColSource(rows), config=_cfg(
        spatial_partition="morton"))
    single = run_job(_ColSource(rows), config=_cfg(data_parallel=False))
    assert adaptive == plain == single and len(adaptive) > 0


@pytest.mark.slow
def test_retraction_delta_gspmd_byte_identical(tmp_path):
    """sign=-1 negates finalized levels AFTER the cascade; the gspmd
    route must produce the single-device run's artifact files."""
    from heatmap_tpu.delta.compute import compute_delta

    rows = _rows(n=1000, seed=21)
    dirs = {}
    for name, dp in (("gspmd", True), ("single", False)):
        out = str(tmp_path / name)
        cfg = (_cfg(spatial_partition="morton") if dp
               else _cfg(data_parallel=False))
        compute_delta(_ColSource(rows), out, cfg, sign=-1)
        dirs[name] = out

    def blob(d):
        return {f: open(os.path.join(d, f), "rb").read()
                for f in sorted(os.listdir(d))
                if os.path.isfile(os.path.join(d, f))}

    assert blob(dirs["gspmd"]) == blob(dirs["single"])


@pytest.mark.slow
def test_run_job_elastic_gspmd_byte_identical(tmp_path):
    from heatmap_tpu.io.sinks import LevelArraysSink
    from heatmap_tpu.io.sources import SyntheticSource
    from heatmap_tpu.parallel import run_job_elastic

    out = {}
    for name, dp in (("gspmd", True), ("single", False)):
        cfg = BatchJobConfig(detail_zoom=10, min_detail_zoom=8,
                             result_delta=2, data_parallel=dp)
        d = str(tmp_path / name)
        run_job_elastic(SyntheticSource(n=900, seed=7),
                        LevelArraysSink(d), cfg, batch_size=150,
                        lineage_dir=str(tmp_path / f"lin-{name}"),
                        n_hosts=3, partition="morton")
        out[name] = {f: open(os.path.join(d, f), "rb").read()
                     for f in sorted(os.listdir(d))
                     if os.path.isfile(os.path.join(d, f))}
    assert out["gspmd"] == out["single"]


# -- config surface --------------------------------------------------------


def test_backend_resolved_event_carries_dispatch(tmp_path):
    path = str(tmp_path / "events.jsonl")
    obs.set_event_log(obs.EventLog(path))
    try:
        run_job(_ColSource(_rows(n=200, seed=1)),
                config=_cfg(spatial_partition="off"))
    finally:
        log = obs.get_event_log()
        obs.set_event_log(None)
        log.close()
    recs = [r for r in obs.read_events(path)
            if r["event"] == "backend_resolved"]
    assert recs and recs[0]["dispatch"] == "gspmd"
    dis = [r for r in obs.read_events(path)
           if r["event"] == "cascade_dispatch"]
    assert dis and dis[0]["dispatch"] == "gspmd"


def test_dispatch_overhead_metrics(tmp_path):
    """DispatchTimer splits stage attribution into host vs device and
    feeds the dispatch_overhead_seconds histogram."""
    obs.enable_metrics(True)
    try:
        run_job(_ColSource(_rows(n=200, seed=2)),
                config=_cfg(spatial_partition="off"))
        over = obs.DISPATCH_OVERHEAD.samples()
        assert ("gspmd",) in over and over[("gspmd",)][2] >= 1
        stages = obs.STAGE_SECONDS.samples()
        assert ("cascade.dispatch.host",) in stages
        assert ("cascade.dispatch.device",) in stages
    finally:
        obs.enable_metrics(False)
        obs.get_registry().reset()


# -- donation safety -------------------------------------------------------


def test_donation_dropped_on_cpu():
    assert not gspmd.donation_supported("cpu")
    assert gspmd.donation_supported("tpu")
    assert gspmd.donation_supported("gpu")
    fn = gspmd.donating_jit(lambda x: x + 1, donate_argnums=(0,),
                            ledger=gspmd.DonationLedger())
    assert fn.donation_active is False  # CPU test session


def test_donated_buffer_reuse_is_typed_error():
    led = gspmd.DonationLedger()
    fn = gspmd.donating_jit(lambda x: x * 2, donate_argnums=(0,),
                            ledger=led)
    x = jnp.arange(16, dtype=jnp.int64)
    y = fn(x)
    assert np.array_equal(np.asarray(y), np.arange(16) * 2)
    with pytest.raises(gspmd.DonatedBufferError,
                       match="donated to a previous cascade dispatch"):
        fn(x)
    # A FRESH buffer with identical contents is fine (identity, not
    # value, is what donation consumes).
    z = fn(jnp.arange(16, dtype=jnp.int64))
    assert np.array_equal(np.asarray(y), np.asarray(z))


def test_donation_argnames_guard_kwargs():
    led = gspmd.DonationLedger()
    fn = gspmd.donating_jit(lambda x, w=None: x if w is None else x + w,
                            donate_argnames=("w",), ledger=led)
    w = jnp.ones(8, jnp.float64)
    fn(jnp.zeros(8, jnp.float64), w=w)
    with pytest.raises(gspmd.DonatedBufferError):
        fn(jnp.zeros(8, jnp.float64), w=w)


def test_donating_cascade_byte_identity():
    """The donating jit entry produces the same bytes as the plain
    entry — donation changes buffer lifetime, never values."""
    mesh = make_mesh()
    ck = _keys(2048, 13)

    def prog(k):
        return gspmd.pyramid_gspmd_uniform(k, mesh, levels=5,
                                           capacity=2048)

    plain = jax.jit(prog)(ck)
    donating = gspmd.donating_jit(prog, donate_argnums=(0,),
                                  ledger=gspmd.DonationLedger())
    donated = donating(jnp.array(ck))  # fresh copy — ck stays usable
    _levels_equal(plain, donated)


def test_run_cascade_gspmd_marks_device_inputs():
    """run_cascade's gspmd jit path routes device-resident emissions
    through the donating entry: re-passing the SAME consumed buffers is
    the typed error, on CPU too."""
    from heatmap_tpu.pipeline import cascade as cascade_mod

    cfg = _cfg(spatial_partition="off")
    ccfg = cfg.cascade_config()
    mesh = make_mesh()
    n = 4096
    rng = np.random.default_rng(3)
    codes = jax.device_put(rng.integers(0, SPACE, n))
    slots = jax.device_put(rng.integers(0, 4, n))

    def run():
        return cascade_mod.run_cascade(
            codes, slots, ccfg, n_slots=4, capacity=n, mesh=mesh)

    cascade_mod.decode_levels(run(), ccfg)
    try:
        with pytest.raises(gspmd.DonatedBufferError):
            run()
    finally:
        gspmd.ledger.clear()


# -- virtual CPU mesh ------------------------------------------------------


def test_force_cpu_devices_gives_gspmd_a_mesh():
    """force_cpu_devices in a fresh interpreter (no XLA_FLAGS) gives the
    gspmd entry points a 4-device CPU mesh."""
    code = (
        "import os\n"
        "os.environ.pop('XLA_FLAGS', None)\n"
        "from heatmap_tpu.parallel import mesh\n"
        "mesh.force_cpu_devices(4)\n"
        "import jax\n"
        "assert jax.device_count() == 4, jax.devices()\n"
        "jax.config.update('jax_enable_x64', True)\n"
        "import jax.numpy as jnp\n"
        "from heatmap_tpu.parallel import gspmd\n"
        "m = mesh.make_mesh()\n"
        "assert m.devices.size == 4\n"
        "lv = gspmd.pyramid_gspmd_uniform(\n"
        "    jnp.arange(64, dtype=jnp.int64), m, levels=2, capacity=64)\n"
        "assert int(lv[0][2]) == 64\n"
        "s = mesh.named_sharding(m, mesh.DATA_AXIS)\n"
        "assert s.is_fully_addressable\n"
        "print('MESH-OK')\n"
    )
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=180)
    assert out.returncode == 0, out.stderr
    assert "MESH-OK" in out.stdout


# -- feeder ----------------------------------------------------------------


def test_feeder_preserves_order_and_counts():
    stats = feeder.FeederStats()
    got = list(feeder.feed(iter(range(20)), lambda x: x * 10, depth=2,
                           stats=stats))
    assert got == [x * 10 for x in range(20)]
    assert stats.batches == 20
    assert 0.0 <= stats.overlap_pct <= 100.0
    assert stats.depth_hwm <= 2


def test_feeder_depth_validation():
    with pytest.raises(ValueError, match="depth"):
        list(feeder.feed(iter([1]), lambda x: x, depth=0))


def test_feeder_device_put_columns_moves_numeric_only():
    cols = {"latitude": np.arange(4, dtype=np.float64),
            "longitude": np.arange(4, dtype=np.float64),
            "value": np.ones(4),
            "timestamp": np.arange(4, dtype=np.int64),
            "user_id": ["a", "b", "c", "d"]}
    fed = feeder.device_put_columns(cols)
    assert isinstance(fed["latitude"], jax.Array)
    assert isinstance(fed["value"], jax.Array)
    # timestamp feeds the host-side labeler; user_id is strings.
    assert isinstance(fed["timestamp"], np.ndarray)
    assert fed["user_id"] is cols["user_id"]
    assert np.array_equal(np.asarray(fed["latitude"]), cols["latitude"])


def test_feeder_fault_site_retries_then_propagates():
    # One injected fault at feeder.put: absorbed by the retry policy,
    # every item still arrives exactly once in order.
    faults.install(faults.FaultPlane(seed=1, backoff_scale=0.0)
                   .add_rule("feeder.put", count=1))
    try:
        got = list(feeder.feed(iter(range(8)), lambda x: x, depth=1))
        assert got == list(range(8))
        assert faults.get_plane().injected == 1
    finally:
        faults.install(None)
    # A storm past the retry budget propagates to the consumer.
    faults.install(faults.FaultPlane(seed=1, backoff_scale=0.0)
                   .add_rule("feeder.put", count=50))
    try:
        with pytest.raises(faults.InjectedFault):
            list(feeder.feed(iter(range(8)), lambda x: x, depth=1))
    finally:
        faults.install(None)


def test_ingest_feeder_byte_identical_store(tmp_path):
    """Draining the same source with the feeder on vs off produces
    byte-identical delta stores: same journal content hashes (the
    feeder moves buffers, never values) and identical artifact files.
    Journal entry FILES carry a wall-clock ``ts`` so they compare by
    content hash, not bytes."""
    from heatmap_tpu import ingest as ingest_mod
    from heatmap_tpu.delta.compact import journal_dir
    from heatmap_tpu.delta.journal import DeltaJournal
    from heatmap_tpu.io import open_source

    digests, hashes = {}, {}
    for depth in (0, 2):
        root = str(tmp_path / f"d{depth}")
        st = ingest_mod.run_ingest(
            root, open_source("synthetic:2000:13"),
            config=BatchJobConfig(detail_zoom=10, min_detail_zoom=8,
                                  result_delta=2, pad_bucketing="pow2"),
            ingest=ingest_mod.IngestConfig(micro_batch=512,
                                           feed_depth=depth))
        assert st.ticks == 4 and st.points == 2000
        if depth:
            assert st.feeder_depth_hwm >= 1
        hashes[depth] = [
            (e["epoch"], e["content_hash"], e["points"], e["sign"])
            for e in DeltaJournal(journal_dir(root)).entries()]
        files = {}
        for dirpath, _, names in os.walk(root):
            if "journal" in os.path.relpath(dirpath, root).split(os.sep):
                continue
            for f in names:
                p = os.path.join(dirpath, f)
                files[os.path.relpath(p, root)] = open(p, "rb").read()
        digests[depth] = files
    assert hashes[0] == hashes[2] and len(hashes[0]) == 4
    assert sorted(digests[0]) == sorted(digests[2])
    diff = [k for k in digests[0] if digests[0][k] != digests[2][k]]
    assert not diff, diff
