"""The planet trace driver (``benchmark/traces.py``): its generator, its
per-chip reduction of a device trace, and whole runs of its cell on four
virtual CPU devices, sound and with the mesh program broken underneath.
"""

import json
import os
import sys
import time

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import exchange, harness, traces  # noqa: E402

CELL = "planet-z21-dp4.planet-traces-16m"
SMALL = {"points_per_job": 20000, "cities": 16, "uploaders": 60,
         "uploaders_per_job": 12, "trace_points": [100, 300]}


def mix(**kw):
    return {**harness.load_cell(REPO, CELL).traffic, **SMALL, **kw}


def test_generator_is_deterministic_per_seed_and_job():
    m = mix()
    a, b = traces.make_job(m, 2**40 + 3, 1), traces.make_job(m, 2**40 + 3, 1)
    for k in a:
        assert np.array_equal(a[k], b[k]), k
    other = traces.make_job(m, 2**40 + 3, 2)
    assert not np.array_equal(a["latitude"], other["latitude"])
    assert not np.array_equal(
        a["latitude"], traces.make_job(m, 2**40 + 4, 1)["latitude"])


def test_generator_makes_the_mix():
    m = mix(points_per_job=60000)
    cols = traces.make_job(m, 11, 1)
    assert len(cols["latitude"]) == m["points_per_job"]
    ids = set(cols["user_id"])
    assert len(ids) == m["uploaders_per_job"]
    assert not any(u.startswith(("x", "rt-")) for u in ids)
    assert set(cols["source"]) == {"gps"}
    assert (np.abs(cols["latitude"]) < 85).all()
    assert ((cols["longitude"] >= -180) & (cols["longitude"] < 180)).all()
    # Whole traces: one uploader each, fixes one interval apart, each
    # step one of the mix's speeds.
    gap = np.diff(cols["timestamp"])
    new = gap != m["fix_interval_s"] * 1000
    starts = np.concatenate([[0], np.flatnonzero(new) + 1])
    lengths = np.diff(np.concatenate([starts, [len(gap) + 1]]))
    lo, hi = m["trace_points"]
    assert lengths.min() >= lo * 0.9 and lengths.max() <= hi * 1.1
    same = ~new
    assert (cols["user_id"][1:][same] == cols["user_id"][:-1][same]).all()
    lat = np.radians(cols["latitude"])
    dy = np.diff(cols["latitude"]) * traces.EARTH_M_PER_DEG
    dx = (np.diff(cols["longitude"]) * traces.EARTH_M_PER_DEG
          * np.cos(lat[:-1]))
    step = np.hypot(dx, dy)[same]
    speeds = np.asarray(m["speeds_mps"])
    near = np.min(np.abs(step[:, None] - speeds[None, :]), axis=1)
    assert np.median(near / step) < 0.01
    # Zipf over cities: the heaviest centre starts the most traces.
    clat, clon, cw = traces.cities(m)
    first_lat = cols["latitude"][starts]
    nearest = np.argmin(np.abs(first_lat[:, None] - clat[None, :]), axis=1)
    assert np.bincount(nearest, minlength=len(cw)).argmax() == cw.argmax()


def test_per_chip_reduction_on_two_devices():
    """Two chips: the cascade's device time and a scope's time are the
    larger chip's, the idle share the mean of the two chips' own."""
    prog = "jit_build_cascade(7)"
    route = "jit(build_cascade)/mesh.route/all-to-all"
    detail = "jit(build_cascade)/mesh.detail/cascade.level0/sort"

    def op(device, start, end, op_name, module=prog):
        return {"device": device, "op": "x", "module": module,
                "start": start, "end": end, "op_name": op_name}

    ops = [op("/device:TPU:0", 0, 100, route),
           op("/device:TPU:0", 100, 400, detail),
           op("/device:TPU:1", 0, 300, route),
           op("/device:TPU:1", 300, 500, detail),
           op("/device:TPU:1", 600, 700, None, module="jit_other(1)")]
    prof = traces.per_chip(ops, 0, 1000, chips=2)
    assert prof["cascade_device_s"] == pytest.approx(500e-9)
    assert prof["cascade_device_s_per_chip"] == pytest.approx([400e-9,
                                                               500e-9])
    assert prof["scope_s"]["mesh.route"] == pytest.approx(300e-9)
    assert prof["scope_s"]["mesh.detail"] == pytest.approx(300e-9)
    assert prof["idle_pct"] == pytest.approx((60.0 + 40.0) / 2)
    # A chip that ran nothing is idle throughout.
    assert traces.per_chip(ops, 0, 1000, chips=4)["idle_pct"] == \
        pytest.approx((60.0 + 40.0 + 100.0 + 100.0) / 4)


def test_exchange_roofline_counts_the_floor():
    assert exchange.least_bytes(1000) == 9000
    assert exchange.roofline_pct(9000, 4, 1e-6, 1e10) == pytest.approx(22.5)
    assert exchange.roofline_pct(9000, 4, 0.0, 1e10) is None
    assert exchange.ici_bytes_per_s("TPU v5 lite") == 200e9
    with pytest.raises(KeyError):
        exchange.ici_bytes_per_s("no such chip")


def test_config_resolves_to_gspmd_morton_partitioned(monkeypatch):
    """Everything but data_parallel at its default resolves, under a
    four-chip mesh on a TPU, to the gspmd dispatch, Morton ranges (the
    job's emissions pass the auto threshold) and the partitioned
    kernel."""
    from benchmark.backfill import job_config
    from heatmap_tpu.pipeline import batch

    cell = harness.load_cell(REPO, CELL)
    config = job_config(cell)
    assert config.data_parallel is True
    assert config.resolved_dispatch == "gspmd"
    assert config.spatial_partition == "auto"
    assert config.dp_min_emissions is None
    assert 2 * cell.traffic["points_per_job"] >= batch.AUTO_DP_MIN_EMISSIONS

    class Chip:
        platform = "tpu"

    monkeypatch.setattr(jax, "devices", lambda *a, **k: [Chip()] * 4)
    assert config.resolved_cascade_backend == "partitioned"


@pytest.fixture
def small_cell(monkeypatch, tmp_path):
    """The cell at a small size on four virtual devices, its job forced
    onto Morton ranges (below the auto threshold at this size)."""
    monkeypatch.setattr(harness, "enable_caches", lambda: "off")
    devs = jax.devices()[:4]
    monkeypatch.setattr(jax, "local_devices", lambda *a, **k: devs)
    peaks = tmp_path / "ici.json"
    peaks.write_text(json.dumps({"cpu": {"ici_bytes_per_s": 1e9}}))
    monkeypatch.setattr(exchange, "PEAKS", str(peaks))
    cell = harness.load_cell(REPO, CELL)
    cell.traffic = {**cell.traffic, **SMALL}
    cell.config = {**cell.config, "job": {**cell.config["job"],
                                          "spatial_partition": "morton"}}
    return cell


def drive(cell, capsys, seed, trace=False):
    rc = harness.main(cell, seed=seed, seconds=0, trace=trace,
                      t_start=time.perf_counter(), require_chip=False)
    out, err = capsys.readouterr()
    assert rc == 0
    assert err.strip().splitlines()[-1].startswith("check ")
    return json.loads(out.strip().splitlines()[-1])


def one_shard_dropped(monkeypatch):
    """Shard 1 receives nothing from the exchange."""
    from jax import lax

    from heatmap_tpu.parallel import gspmd

    real = gspmd.route_on_device

    def route(keys, *a, axes, **k):
        rk, rw, over = real(keys, *a, axes=axes, **k)
        lost = lax.axis_index(axes[0]) == 1
        return jax.numpy.where(lost, jax.numpy.iinfo(rk.dtype).max, rk), \
            rw, over

    monkeypatch.setattr(gspmd, "route_on_device", route)


def altered_answer(monkeypatch):
    """One sum changed where the cascade's levels are decoded."""
    from heatmap_tpu.pipeline import cascade

    real = cascade.decode_levels

    def decode(level_data, config):
        out = real(level_data, config)
        out[0]["value"][0] += 1.0
        return out

    monkeypatch.setattr(cascade, "decode_levels", decode)


FAULTS = {"one_shard_dropped": one_shard_dropped,
          "altered_answer": altered_answer}


def test_sound_run_is_correct(small_cell, capsys):
    result = drive(small_cell, capsys, seed=2**35 + 1)
    assert result["correct"] is True
    assert result["attempted"] == 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"backfill_points_per_s", "setup_s"}
    assert result["checks"]["rows_wrong"]["value"] == 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_mesh_path_is_not_correct(small_cell, capsys, monkeypatch,
                                         fault):
    # The cascade is jitted: trace it again with the fault in, and keep
    # the broken program from later tests.
    jax.clear_caches()
    try:
        FAULTS[fault](monkeypatch)
        result = drive(small_cell, capsys, seed=2**35 + 2)
    finally:
        jax.clear_caches()
    assert result["correct"] is False


def test_traced_run_reports_every_per_layer_metric(small_cell, capsys):
    result = drive(small_cell, capsys, seed=7, trace=True)
    assert result["correct"] is True
    assert set(result["metrics"]) == {m["name"] for m in small_cell.per_layer}
    assert len(small_cell.per_layer) == 5
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["metrics"]["mesh_imbalance.planet"]["value"] >= 1.0
    assert result["breakdown"]["device_ops"]
