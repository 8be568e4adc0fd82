"""Locality-aware Morton-range sharding (parallel/partition.py).

Two layers under test: the planner (deterministic quantile splits,
skew-resistant re-splitting, boundary-tile enumeration) and the
end-to-end gate — a spatially partitioned job's blobs are byte-identical
to the uniform program's on every tested shape, including weighted,
retraction, and elastic-failover jobs. The range program's own
routing cases are in tests/test_gspmd.py.
"""

import os

import numpy as np
import pytest

from heatmap_tpu import obs
from heatmap_tpu.parallel import (
    PartitionPlan,
    plan_partition,
    plan_shards,
    run_job_elastic,
)
from heatmap_tpu.pipeline import BatchJobConfig, run_job
from heatmap_tpu.tilemath import split_boundary_codes_np

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
        "source": "gps" if rng.uniform() > 0.1 else "background",
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
                "source": [r.get("source", "gps") for r in chunk],
            }
            if any("value" in r for r in chunk):
                out["value"] = [float(r.get("value", 1.0)) for r in chunk]
            yield out


def _cfg(**kw):
    # data_parallel=True + spatial_partition="morton" exercises the
    # range-sharded mesh route at test sizes the auto thresholds
    # deliberately route single-device.
    base = dict(detail_zoom=DZ, min_detail_zoom=6, data_parallel=True,
                spatial_partition="morton")
    base.update(kw)
    return BatchJobConfig(**base)


# -- planner ---------------------------------------------------------------


def test_plan_determinism_and_monotonicity():
    rng = np.random.default_rng(3)
    codes = rng.integers(0, SPACE, 50_000)
    a = plan_partition(codes, 8, detail_zoom=DZ, seed=5)
    b = plan_partition(codes, 8, detail_zoom=DZ, seed=5)
    assert a.splits == b.splits and a.fingerprint == b.fingerprint
    assert len(a.splits) == 7 and a.n_shards == 8
    assert list(a.splits) == sorted(a.splits)
    # A different seed samples differently but stays a valid plan.
    c = plan_partition(codes, 8, detail_zoom=DZ, seed=6)
    assert list(c.splits) == sorted(c.splits)
    # Ownership convention: a split opens the range to its right.
    s0 = a.splits[0]
    assert a.shard_of_codes(np.asarray([s0 - 1, s0, s0 + 1])).tolist() \
        == [0, 1, 1]


def test_plan_quantiles_balance_distinct_codes():
    """Quantile splits over distinct codes are balanced without any
    re-splitting — skew comes only from duplicate-code mass."""
    rng = np.random.default_rng(11)
    codes = rng.choice(SPACE, size=40_000, replace=False)
    plan = plan_partition(codes, 8, detail_zoom=DZ)
    assert plan.resplits == 0
    assert plan.skew_ratio <= 1.25
    assert not plan.degenerate


def test_resplit_bounds_pathological_hotspot_skew():
    """20% of the mass on ONE code collapses the naive quantile splits
    (duplicates denote empty ranges); the re-split loop peels the
    uniform tail back out and lands under the ISSUE's skew gate."""
    rng = np.random.default_rng(7)
    hot = np.full(10_000, 123_456, np.int64)
    cold = rng.choice(SPACE, size=40_000, replace=False)
    codes = np.concatenate([hot, cold])
    plan = plan_partition(codes, 8, detail_zoom=DZ, seed=1)
    assert plan.resplits >= 1
    assert plan.skew_ratio <= 2.0, plan.shard_mass
    assert not plan.degenerate


def test_degenerate_plans():
    # No samples, single shard, or one range owning ~all mass.
    assert plan_partition(np.asarray([], np.int64), 4,
                          detail_zoom=DZ).degenerate
    assert plan_partition(np.arange(100), 1, detail_zoom=DZ).degenerate
    one_code = np.full(5_000, 42, np.int64)
    assert plan_partition(one_code, 4, detail_zoom=DZ).degenerate
    # valid mask removes all mass -> degenerate, not a crash.
    assert plan_partition(np.arange(100), 4, detail_zoom=DZ,
                          valid=np.zeros(100, bool)).degenerate


def test_boundary_codes_match_brute_force():
    """A tile at level L straddles a split iff its first and last
    detail children land on different shards — checked independently
    through shard_of_codes for every level and random split set."""
    rng = np.random.default_rng(19)
    for trial in range(5):
        splits = np.sort(rng.integers(1, SPACE, 7))
        plan = PartitionPlan(detail_zoom=DZ, n_shards=8,
                             splits=tuple(int(s) for s in splits),
                             sampled_points=1, balance_factor=1.25,
                             shard_mass=(1.0,) * 8, resplits=0,
                             fingerprint="t")
        assert split_boundary_codes_np(splits, 0).size == 0
        for lvl in range(1, 7):
            got = set(plan.boundary_codes(lvl).tolist())
            cand = np.unique(splits >> np.int64(2 * lvl))
            lo = cand << np.int64(2 * lvl)
            hi = lo + (np.int64(1) << np.int64(2 * lvl)) - 1
            first = plan.shard_of_codes(lo)
            last = plan.shard_of_codes(hi)
            want = set(cand[first != last].tolist())
            assert got == want, (trial, lvl)
        total = plan.boundary_tiles_total(6)
        assert total == sum(len(plan.boundary_codes(v))
                            for v in range(1, 7))
        # Per level there are at most n_shards - 1 straddling tiles.
        assert all(len(plan.boundary_codes(v)) <= 7 for v in range(1, 7))


# -- observability ---------------------------------------------------------


def test_partition_planned_event_and_metrics(tmp_path):
    path = str(tmp_path / "events.jsonl")
    rng = np.random.default_rng(31)
    codes = rng.choice(SPACE, size=20_000, replace=False)
    obs.enable_metrics(True)
    obs.set_event_log(obs.EventLog(path))
    try:
        plan = plan_partition(codes, 8, detail_zoom=DZ, n_levels=6)
        assert obs.PARTITION_SKEW.value() == pytest.approx(
            plan.skew_ratio)
        assert obs.BOUNDARY_TILES.value() == plan.boundary_tiles_total(6)
    finally:
        log = obs.get_event_log()
        obs.set_event_log(None)
        log.close()
        obs.enable_metrics(False)
    [rec] = obs.read_events(path)
    assert rec["event"] == "partition_planned"
    assert rec["n_shards"] == 8 and len(rec["splits"]) == 7
    assert rec["fingerprint"] == plan.fingerprint
    assert rec["boundary_tiles"] == plan.boundary_tiles_total(6)
    assert not rec["degenerate"]


def test_dp_mesh_for_degenerate_plan_falls_back(tmp_path):
    """Satellite fix: a degenerate plan must NOT serialize the cascade
    on one shard — dispatch keeps the mesh, drops the plan, and leaves
    a backend_resolved audit record."""
    from heatmap_tpu.pipeline.batch import _dp_mesh, _dp_mesh_for

    cfg = _cfg()
    mesh = _dp_mesh(cfg)
    assert mesh is not None
    plan = plan_partition(np.full(5_000, 42, np.int64), 8, detail_zoom=DZ)
    assert plan.degenerate
    path = str(tmp_path / "events.jsonl")
    obs.set_event_log(obs.EventLog(path))
    try:
        assert _dp_mesh_for(mesh, cfg, 5_000, plan=plan) is mesh
    finally:
        log = obs.get_event_log()
        obs.set_event_log(None)
        log.close()
    recs = [r for r in obs.read_events(path)
            if r["event"] == "backend_resolved"]
    assert recs and recs[0]["resolved"] == "uniform-dp"
    assert recs[0]["spatial_partition"] == "morton"


# -- config surface --------------------------------------------------------


def test_spatial_partition_config_rejections():
    with pytest.raises(ValueError, match="spatial_partition"):
        BatchJobConfig(spatial_partition="hilbert")
    with pytest.raises(ValueError, match="data_parallel"):
        BatchJobConfig(spatial_partition="morton", data_parallel=False)
    # The composing modes construct fine, morton + adaptive_capacity
    # included (the range program routes on-device against traced
    # splits).
    BatchJobConfig(spatial_partition="off")
    BatchJobConfig(spatial_partition="morton", data_parallel=True,
                   pad_bucketing="pow2")
    BatchJobConfig(spatial_partition="morton", data_parallel=True,
                   adaptive_capacity=True)


# -- elastic Morton shards -------------------------------------------------


def test_plan_shards_morton_ranges():
    ranges = [(0, 100), (100, 100), (100, SPACE)]
    plan = plan_shards(5, 3, "jfp", code_ranges=ranges)
    assert [s.index for s in plan] == [0, 1, 2]
    # Every Morton shard spans the FULL batch range; the code range is
    # the ownership filter.
    assert all(s.lo == 0 and s.hi == 5 for s in plan)
    assert [(s.code_lo, s.code_hi) for s in plan] == ranges
    assert len({s.fingerprint for s in plan}) == 3
    # Batch-mode fingerprints must not collide with Morton ones.
    batch = plan_shards(5, 3, "jfp")
    assert {s.fingerprint for s in plan}.isdisjoint(
        {s.fingerprint for s in batch})


def test_elastic_empty_range_shard_publishes_empty_partial(tmp_path):
    """An empty code range yields an empty partial (not a crash, not a
    missing manifest entry) and the merge of the remaining shards still
    reproduces the full job."""
    import threading

    from heatmap_tpu.parallel.elastic import ShardLineage, _make_executor

    rows = _rows(n=120, seed=13)
    cfg = BatchJobConfig(detail_zoom=10, min_detail_zoom=8, result_delta=2)
    ranges = [(0, 1 << 20), (1 << 20, 1 << 20), (1 << 20, 1 << 20)]
    plan = plan_shards(1, 3, "jfp", code_ranges=ranges)
    execute = _make_executor(_ColSource(rows), cfg, 128, threading.Lock())
    lineage = ShardLineage(str(tmp_path / "lin"))
    for s in plan:
        levels, meta = execute(s)
        won, _ = lineage.publish(s, "h0", levels, meta)
        assert won
    # Shards 1 and 2 own empty ranges -> empty partials.
    merged = lineage.merge(plan)
    assert isinstance(merged, list)


def test_run_job_elastic_rejects_unknown_partition(tmp_path):
    with pytest.raises(ValueError, match="partition"):
        run_job_elastic(_ColSource(_rows(8)), None,
                        BatchJobConfig(detail_zoom=10, min_detail_zoom=8),
                        n_total=8, lineage_dir=str(tmp_path),
                        partition="hilbert")


# -- end-to-end byte equality ----------------------------------------------


def test_run_job_morton_byte_identical_small():
    """The acceptance gate at tier-1 size: a Morton-partitioned job's
    blobs equal the uniform dispatch byte-for-byte."""
    rows = _rows(n=800, seed=42)
    morton = run_job(_ColSource(rows), config=_cfg())
    off = run_job(_ColSource(rows), config=_cfg(spatial_partition="off"))
    assert morton == off and len(morton) > 0


@pytest.mark.slow
def test_run_job_morton_partitioned_backend_byte_identical():
    rows = _rows(n=2000, seed=9)
    morton = run_job(_ColSource(rows),
                     config=_cfg(cascade_backend="partitioned"))
    off = run_job(_ColSource(rows),
                  config=_cfg(cascade_backend="partitioned",
                              spatial_partition="off"))
    assert morton == off and len(morton) > 0


@pytest.mark.slow
def test_run_job_morton_weighted_integer_byte_identical():
    rng = np.random.default_rng(15)
    rows = _rows(n=1500, seed=15)
    for r in rows:
        r["value"] = float(rng.integers(1, 12))
    morton = run_job(_ColSource(rows), config=_cfg(weighted=True))
    off = run_job(_ColSource(rows),
                  config=_cfg(weighted=True, spatial_partition="off"))
    assert morton == off and len(morton) > 0


@pytest.mark.slow
def test_run_job_morton_pad_bucketing_byte_identical():
    """Routed per-shard segments hit the bucketed compile cache; the
    padding changes shapes only, never bytes."""
    rows = _rows(n=2000, seed=5)
    morton = run_job(_ColSource(rows), config=_cfg(pad_bucketing="pow2"))
    off = run_job(_ColSource(rows),
                  config=_cfg(pad_bucketing="pow2",
                              spatial_partition="off"))
    assert morton == off and len(morton) > 0


@pytest.mark.slow
def test_run_job_morton_clustered_hotspot_byte_identical():
    """An 80%-clustered set (the shape the planner exists for) still
    meets the byte gate."""
    rng = np.random.default_rng(77)
    rows = _rows(n=2000, seed=77)
    k = int(len(rows) * 0.8)
    for r in rows[:k]:
        r["latitude"] = float(47.6 + rng.normal(0, 0.05))
        r["longitude"] = float(-122.3 + rng.normal(0, 0.05))
    morton = run_job(_ColSource(rows), config=_cfg())
    off = run_job(_ColSource(rows), config=_cfg(spatial_partition="off"))
    assert morton == off and len(morton) > 0


@pytest.mark.slow
def test_retraction_delta_morton_byte_identical(tmp_path):
    """Retractions (delta/compute.py sign=-1) negate finalized levels
    AFTER the cascade, so the partitioned route must produce identical
    artifact files."""
    from heatmap_tpu.delta.compute import compute_delta

    rows = _rows(n=1200, seed=21)
    dirs = {}
    for name, sp in (("morton", "morton"), ("off", "off")):
        out = str(tmp_path / name)
        compute_delta(_ColSource(rows), out, _cfg(spatial_partition=sp),
                      sign=-1)
        dirs[name] = out

    def blob(d):
        return {f: open(os.path.join(d, f), "rb").read()
                for f in sorted(os.listdir(d))
                if os.path.isfile(os.path.join(d, f))}

    a, b = blob(dirs["morton"]), blob(dirs["off"])
    assert sorted(a) == sorted(b)
    assert all(a[k] == b[k] for k in a)


@pytest.mark.slow
def test_run_job_elastic_morton_byte_identical(tmp_path):
    from heatmap_tpu.io.sinks import LevelArraysSink
    from heatmap_tpu.io.sources import SyntheticSource

    cfg = BatchJobConfig(detail_zoom=10, min_detail_zoom=8, result_delta=2)
    out = {}
    for mode in ("batch", "morton"):
        d = str(tmp_path / mode)
        run_job_elastic(SyntheticSource(n=900, seed=7),
                        LevelArraysSink(d), cfg, batch_size=150,
                        lineage_dir=str(tmp_path / f"lin-{mode}"),
                        n_hosts=3, partition=mode)
        out[mode] = {f: open(os.path.join(d, f), "rb").read()
                     for f in sorted(os.listdir(d))
                     if os.path.isfile(os.path.join(d, f))}
    assert out["batch"] == out["morton"]
