"""Golden end-to-end tests: pipeline vs the pure-Python reference oracle."""

import json

import numpy as np
import pytest

from heatmap_tpu.pipeline import (
    BatchJobConfig,
    UserVocab,
    route_user,
    run_batch,
    timespan_label,
)
from heatmap_tpu.pipeline.groups import ALL_GROUP, EXCLUDED
import oracle


def _rows(n=500, seed=0, users=("alice", "bob", "rt-bus7", "rt-tram2", "xscout", "carol")):
    rng = np.random.default_rng(seed)
    rows = []
    for i in range(n):
        rows.append(
            {
                "latitude": float(rng.uniform(40.0, 55.0)),
                "longitude": float(rng.uniform(-5.0, 15.0)),
                "user_id": users[int(rng.integers(0, len(users)))],
                "timestamp": 1_500_000_000_000 + int(rng.integers(0, 10**9)),
                "source": "gps" if rng.uniform() > 0.1 else "background",
            }
        )
    return rows


# -- unit semantics --------------------------------------------------------


def test_route_user_rules():
    # Reference heatmap.py:64-70 semantics.
    assert route_user("alice") == "alice"
    assert route_user("rt-bus7") == "route"
    assert route_user("rt-") == "route"
    assert route_user("xscout") is None
    assert route_user("x") is None
    # 'rt' without dash is a normal user; 'Xupper' is NOT excluded.
    assert route_user("rtbus") == "rtbus"
    assert route_user("Xupper") == "Xupper"
    for uid in ("alice", "rt-bus7", "xscout", "x", "rtbus"):
        expected = oracle.user_groups(uid)
        got = ["all"] + ([route_user(uid)] if route_user(uid) else [])
        assert got == expected


def test_user_vocab():
    v = UserVocab()
    ids = v.group_ids(["alice", "rt-a", "rt-b", "xs", "alice"])
    assert ids[0] == ids[4] != ALL_GROUP
    assert ids[1] == ids[2]  # pooled under route
    assert ids[3] == EXCLUDED
    assert v.name_for(ALL_GROUP) == "all"


def test_timespan_labels():
    import datetime

    d = datetime.date(2017, 3, 7)
    assert timespan_label("alltime", d) == "alltime"
    assert timespan_label("year", d) == "2017"
    assert timespan_label("month", d) == "2017-03"
    assert timespan_label("day", d) == "2017-03-07"
    with pytest.raises(ValueError):
        timespan_label("week", d)


def test_label_ids_datetime64_column():
    import numpy as np

    from heatmap_tpu.pipeline.timespan import TimespanVocab

    vocab = TimespanVocab()
    col = np.asarray(
        ["2017-03-07T12:30", "2017-03-08T01:00", "2017-03-07T23:59"],
        dtype="datetime64[m]",
    )
    ids = vocab.label_ids("day", col)
    assert [vocab.label_for(i) for i in ids] == [
        "2017-03-07", "2017-03-08", "2017-03-07",
    ]
    # Matches the per-object path on equivalent epoch-ms ints.
    ms = col.astype("datetime64[ms]").astype(np.int64)
    vocab2 = TimespanVocab()
    ids2 = vocab2.label_ids("day", [int(m) for m in ms])
    assert [vocab2.label_for(i) for i in ids2] == [
        vocab.label_for(i) for i in ids
    ]
    # NaT == TS_MISSING: missing values raise like timestamp=None.
    nat = np.asarray(["2017-03-07", "NaT"], dtype="datetime64[s]")
    with pytest.raises(ValueError, match="timestamp"):
        TimespanVocab().label_ids("day", nat)


def test_json_blobs_match_dict_path_exactly():
    """The vectorized direct-to-JSON egress must produce byte-identical
    strings to json.dumps over the dict path, including float
    formatting and key order."""
    import json as _json

    import numpy as np

    from heatmap_tpu.pipeline import cascade as cascade_mod
    from heatmap_tpu.pipeline.batch import (
        BatchJobConfig, _cascade_codes, _slot_names, build_emissions,
    )
    from heatmap_tpu.pipeline.groups import UserVocab

    rng = np.random.default_rng(5)
    n = 30000
    lat = np.clip(rng.normal(47, 3, n), -85, 85)
    lon = np.clip(rng.normal(-122, 4, n), -179, 179)
    users = [f"user-{i}" for i in rng.integers(0, 9, n)]
    vocab = UserVocab()
    gids = vocab.group_ids(users)
    cfg = BatchJobConfig(detail_zoom=12, min_detail_zoom=7)
    codes, valid = _cascade_codes(lat, lon, cfg.detail_zoom)
    e_codes, e_slots, e_valid, ts_vocab, n_groups, _ = build_emissions(
        codes, valid, gids, [None] * n, cfg
    )
    ccfg = cfg.cascade_config()
    lvl = cascade_mod.build_cascade(
        e_codes, e_slots, ccfg, n_slots=len(ts_vocab) * n_groups,
        valid=e_valid, capacity=len(e_codes),
    )
    fin = cascade_mod.finalize_level_arrays(
        cascade_mod.decode_levels(lvl, ccfg), ccfg,
        _slot_names(vocab, ts_vocab, n_groups),
    )
    want = {
        k: _json.dumps(v)
        for k, v in cascade_mod.blobs_from_level_arrays(fin).items()
    }
    got = cascade_mod.json_blobs_from_level_arrays(fin)
    assert got == want


def test_project_detail_codes_device_matches_host():
    """The on-device f64 projection+interleave must agree bit-for-bit
    with the host numpy path (same IEEE-double op order) at z21,
    including validity at poles/antimeridian edges."""
    import numpy as np

    from heatmap_tpu.pipeline.batch import project_detail_codes

    rng = np.random.default_rng(11)
    lat = np.concatenate([
        np.clip(rng.normal(40, 30, 20000), -89.9, 89.9),
        [90.0, -90.0, 85.06, -85.06, 0.0],
    ])
    lon = np.concatenate([
        rng.uniform(-180.0, 180.0, 20000), [180.0, -180.0, 0.0, 1e-9, -1e-9],
    ])
    dev_codes, dev_valid = project_detail_codes(lat, lon, 21)
    host_codes, host_valid = project_detail_codes(
        lat, lon, 21, prefer_device=False
    )
    np.testing.assert_array_equal(dev_valid, host_valid)
    np.testing.assert_array_equal(dev_codes[dev_valid],
                                  host_codes[host_valid])


# -- golden end-to-end -----------------------------------------------------


@pytest.mark.parametrize("detail_zoom,min_zoom", [(12, 5), (21, 16)])
def test_batch_matches_oracle_correct_mode(detail_zoom, min_zoom):
    rows = _rows(n=300, seed=detail_zoom)
    cfg = BatchJobConfig(detail_zoom=detail_zoom, min_detail_zoom=min_zoom)
    got = run_batch(rows, cfg)
    want = oracle.run_job(
        rows, detail_zoom=detail_zoom, min_detail_zoom=min_zoom, amplify_all=False
    )
    assert got.keys() == want.keys()
    for key in want:
        assert got[key] == want[key], key


@pytest.mark.parametrize("detail_zoom,min_zoom", [(12, 5), (21, 16)])
def test_batch_matches_oracle_amplified_compat(detail_zoom, min_zoom):
    # Reference-compat mode must reproduce the 'all'-amplification bug
    # (SURVEY.md §8.1) exactly as the faithful oracle simulates it.
    rows = _rows(n=300, seed=100 + detail_zoom)
    cfg = BatchJobConfig(
        detail_zoom=detail_zoom, min_detail_zoom=min_zoom, amplify_all=True
    )
    got = run_batch(rows, cfg)
    want = oracle.run_job(
        rows, detail_zoom=detail_zoom, min_detail_zoom=min_zoom, amplify_all=True
    )
    assert got.keys() == want.keys()
    for key in want:
        assert got[key] == pytest.approx(want[key]), key


def test_amplified_all_growth_pattern():
    # The survey's 4-point example: totals 4 -> 11 -> 25 over three levels
    # (SURVEY.md §8.1) when all points share one tile deep in the pyramid.
    rows = [
        {"latitude": 50.0001, "longitude": 8.0001, "user_id": u, "source": "gps"}
        for u in ("a", "b", "c", "xd")
    ]
    cfg = BatchJobConfig(detail_zoom=10, min_detail_zoom=7, amplify_all=True)
    blobs = run_batch(rows, cfg)
    all_totals = {}
    for key, hm in blobs.items():
        user, ts, coarse = key.split("|")
        if user == "all":
            zoom = int(coarse.split("_")[0]) + 5
            all_totals[zoom] = sum(hm.values())
    assert all_totals[10] == 4.0
    assert all_totals[9] == 2 * 4 + 3
    assert all_totals[8] == 2 * 11 + 3


def test_background_rows_dropped():
    rows = [
        {"latitude": 50.0, "longitude": 8.0, "user_id": "a", "source": "background"},
        {"latitude": 50.0, "longitude": 8.0, "user_id": "a", "source": "gps"},
    ]
    blobs = run_batch(rows, BatchJobConfig(detail_zoom=8, min_detail_zoom=6))
    total = sum(v for hm in blobs.items() if hm[0].startswith("all|") for v in hm[1].values())
    assert total == 2.0  # one point at two levels (z8, z7)


def test_empty_input():
    assert run_batch([]) == {}
    assert run_batch([{"latitude": 1, "longitude": 1, "user_id": "a",
                       "source": "background"}]) == {}


def test_as_json_output_shape():
    rows = _rows(n=50, seed=9)
    blobs = run_batch(rows, BatchJobConfig(detail_zoom=10, min_detail_zoom=8),
                      as_json=True)
    for key, payload in blobs.items():
        user, ts, coarse = key.split("|")
        assert ts == "alltime"
        decoded = json.loads(payload)
        assert all(isinstance(v, float) for v in decoded.values())
        # detail ids sit exactly result_delta zooms below the coarse id.
        cz = int(coarse.split("_")[0])
        for det in decoded:
            assert int(det.split("_")[0]) == cz + 5


def test_multi_timespan_emission():
    import datetime

    rows = [
        {
            "latitude": 50.0,
            "longitude": 8.0,
            "user_id": "a",
            "timestamp": datetime.datetime(2017, 3, 7, 12, 0),
            "source": "gps",
        },
        {
            "latitude": 50.0,
            "longitude": 8.0,
            "user_id": "a",
            "timestamp": datetime.datetime(2018, 4, 1, 12, 0),
            "source": "gps",
        },
    ]
    cfg = BatchJobConfig(
        detail_zoom=8, min_detail_zoom=6, timespans=("alltime", "year", "month")
    )
    blobs = run_batch(rows, cfg)
    labels = {k.split("|")[1] for k in blobs}
    assert labels == {"alltime", "2017", "2018", "2017-03", "2018-04"}
    # Quirk-compat mode: only the first timespan emits (SURVEY.md §8.2).
    cfg_q = BatchJobConfig(
        detail_zoom=8, min_detail_zoom=6,
        timespans=("alltime", "year"), first_timespan_only=True,
    )
    labels_q = {k.split("|")[1] for k in run_batch(rows, cfg_q)}
    assert labels_q == {"alltime"}


# -- bounded-memory chunked cascade ---------------------------------------


class _ColSource:
    """Columnar batches over row dicts, for run_job tests."""

    def __init__(self, rows):
        self.rows = rows

    def batches(self, batch_size):
        for i in range(0, len(self.rows), batch_size):
            chunk = self.rows[i : i + batch_size]
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


@pytest.mark.slow
@pytest.mark.parametrize("amplify", [False, True])
def test_run_job_bounded_matches_unbounded(amplify):
    """max_points_in_flight chunks the cascade; linearity of the
    per-level (key, sum) reduction makes the result exactly equal."""
    from heatmap_tpu.pipeline import run_job

    rows = _rows(n=2000, seed=7)
    cfg = BatchJobConfig(
        detail_zoom=12, min_detail_zoom=6,
        timespans=("alltime", "month"), amplify_all=amplify,
    )
    plain = run_job(_ColSource(rows), config=cfg, batch_size=128)
    bounded = run_job(
        _ColSource(rows), config=cfg, batch_size=128,
        max_points_in_flight=150,
    )
    assert plain == bounded
    # The sequential (no-prefetch-thread) path is byte-identical too.
    sequential = run_job(
        _ColSource(rows), config=cfg, batch_size=128,
        max_points_in_flight=150, overlap_ingest=False,
    )
    assert plain == sequential


@pytest.mark.slow
@pytest.mark.parametrize("amplify", [False, True])
def test_bounded_spill_merge_matches_in_ram(tmp_path, amplify):
    """merge_spill_dir replaces the in-RAM cross-chunk table with disk
    runs + per-level egress merges — byte-identical blobs, spill files
    cleaned up afterwards (both amplify modes: streaming egress for
    False, materialized for True)."""
    from heatmap_tpu.pipeline import run_job

    rows = _rows(n=2000, seed=7)
    cfg = BatchJobConfig(
        detail_zoom=12, min_detail_zoom=6,
        timespans=("alltime", "month"), amplify_all=amplify,
    )
    plain = run_job(_ColSource(rows), config=cfg, batch_size=128,
                    max_points_in_flight=150)
    spill_root = tmp_path / "spill"
    spilled = run_job(
        _ColSource(rows), config=cfg, batch_size=128,
        max_points_in_flight=150, merge_spill_dir=str(spill_root),
    )
    assert spilled == plain
    # The temp run directory is removed; only the (empty) root remains.
    assert list(spill_root.iterdir()) == []


@pytest.mark.slow
def test_bounded_auto_spill_activates_and_matches(monkeypatch):
    """With AUTO_SPILL_ROWS lowered, a plain bounded run converts its
    in-RAM table to the spill merge mid-job — same blobs, spill
    tempdir cleaned up."""
    import glob

    from heatmap_tpu.pipeline import batch as batch_mod
    from heatmap_tpu.pipeline import run_job

    rows = _rows(n=2000, seed=7)
    cfg = BatchJobConfig(detail_zoom=12, min_detail_zoom=6)
    plain = run_job(_ColSource(rows), config=cfg, batch_size=128,
                    max_points_in_flight=150)

    created = []
    real_spill = batch_mod._SpillMerge

    class _Spy(real_spill):
        def __init__(self, root, n_levels):
            super().__init__(root, n_levels)
            created.append(self.dir)

    monkeypatch.setattr(batch_mod, "_SpillMerge", _Spy)
    monkeypatch.setattr(batch_mod, "AUTO_SPILL_ROWS", 500)
    # Pin the auto-spill target to a real (disk-backed) dir so the
    # test is independent of whether the host's /tmp is tmpfs.
    monkeypatch.setattr(batch_mod, "_auto_spill_target",
                        lambda: batch_mod.AUTO_SPILL_DIR)
    monkeypatch.setattr(batch_mod, "AUTO_SPILL_DIR", "/tmp/auto-spill-test")
    auto = run_job(_ColSource(rows), config=cfg, batch_size=128,
                   max_points_in_flight=150)
    assert auto == plain
    assert len(created) == 1  # activation happened exactly once
    assert not glob.glob(created[0] + "*")  # tempdir removed


def test_auto_spill_target_refuses_tmpfs(tmp_path, monkeypatch):
    """A RAM-backed temp dir must disable auto-spill (tmpfs pages
    count against the same memory the spill exists to save)."""
    from heatmap_tpu.pipeline import batch as batch_mod

    mounts = tmp_path / "mounts"
    mounts.write_text(
        "/dev/root / ext4 rw 0 0\n"
        "tmpfs /ramtmp tmpfs rw 0 0\n"
        "/dev/sdb /ramtmp/disk ext4 rw 0 0\n"
    )
    real_fstype = batch_mod._mount_fstype
    fstype = lambda p: real_fstype(p, str(mounts))
    assert fstype("/ramtmp/x") == "tmpfs"
    assert fstype("/ramtmp/disk/x") == "ext4"  # longest prefix wins
    assert fstype("/var/spool") == "ext4"

    monkeypatch.setattr(batch_mod, "AUTO_SPILL_DIR", "/ramtmp/x")
    monkeypatch.setattr(
        batch_mod, "_mount_fstype", lambda p: fstype(p)
    )
    assert batch_mod._auto_spill_target() is None
    monkeypatch.setattr(batch_mod, "AUTO_SPILL_DIR", "/var/spool")
    assert batch_mod._auto_spill_target() == "/var/spool"


@pytest.mark.slow
def test_bounded_spill_cleans_up_on_ingest_failure(tmp_path):
    """A source that dies mid-run must not leave spill run files
    behind (they are tens of GB at the shapes spill targets)."""
    from heatmap_tpu.pipeline import run_job

    good = _rows(n=600, seed=3)

    class _Boom:
        def batches(self, batch_size):
            yield from _ColSource(good).batches(batch_size)
            raise RuntimeError("source died")

    root = tmp_path / "spill"
    with pytest.raises(RuntimeError, match="source died"):
        run_job(_Boom(), config=BatchJobConfig(detail_zoom=10,
                                               min_detail_zoom=8),
                batch_size=100, max_points_in_flight=200,
                merge_spill_dir=str(root))
    assert list(root.iterdir()) == []


def test_spill_requires_bounded_path():
    """merge_spill_dir on a single-shot route must refuse loudly, not
    silently run the in-RAM merge it exists to avoid."""
    from heatmap_tpu.io.sources import SyntheticSource
    from heatmap_tpu.pipeline import run_job
    from heatmap_tpu.pipeline.batch import run_job_fast

    with pytest.raises(ValueError, match="bounded path"):
        run_job(SyntheticSource(n=50), config=BatchJobConfig(),
                max_points_in_flight=0, merge_spill_dir="/tmp/nope")
    with pytest.raises(ValueError, match="bounded path"):
        run_job_fast(SyntheticSource(n=50), config=BatchJobConfig(),
                     max_points_in_flight=0, merge_spill_dir="/tmp/nope")


@pytest.mark.slow
def test_bounded_spill_weighted_and_columnar(tmp_path):
    """Weighted spill sums match the in-RAM merge exactly (chunk-order
    summation), and the streaming per-level egress composes with a
    columnar sink (per-level write_levels calls, summed stats)."""
    from heatmap_tpu.io.sinks import LevelArraysSink
    from heatmap_tpu.pipeline import run_job

    rows = _rows(n=1500, seed=21)
    for i, r in enumerate(rows):
        r["value"] = float((i % 7) + 1)  # integer-valued -> exact sums
    cfg = BatchJobConfig(detail_zoom=11, min_detail_zoom=7, weighted=True)
    plain = run_job(_ColSource(rows), config=cfg, batch_size=100,
                    max_points_in_flight=200)
    spilled = run_job(
        _ColSource(rows), config=cfg, batch_size=100,
        max_points_in_flight=200, merge_spill_dir=str(tmp_path / "s"),
    )
    assert spilled == plain

    stats_ram = run_job(_ColSource(rows),
                        LevelArraysSink(str(tmp_path / "ram")),
                        config=cfg, batch_size=100,
                        max_points_in_flight=200)
    stats_spill = run_job(
        _ColSource(rows), LevelArraysSink(str(tmp_path / "spl")),
        config=cfg, batch_size=100, max_points_in_flight=200,
        merge_spill_dir=str(tmp_path / "s2"),
    )
    assert stats_spill == stats_ram
    got = LevelArraysSink.load(str(tmp_path / "spl"))
    want = LevelArraysSink.load(str(tmp_path / "ram"))
    assert set(got) == set(want)
    for zoom in want:
        for col in ("row", "col", "value", "user", "timespan"):
            np.testing.assert_array_equal(got[zoom][col], want[zoom][col])


def test_auto_points_in_flight_decision():
    """Oversized sources auto-route to the bounded path; sources that
    fit (or can't be sized) keep the single-shot path."""
    from heatmap_tpu.io.sources import SyntheticSource
    from heatmap_tpu.pipeline.batch import (
        _HOST_BYTES_PER_POINT, _auto_points_in_flight,
        _estimate_source_points,
    )

    small = SyntheticSource(n=1000)
    big = SyntheticSource(n=50_000_000)
    assert _estimate_source_points(small) == 1000
    # Fits the budget comfortably: unchanged single-shot.
    assert _auto_points_in_flight(small, ram_budget=1 << 30) is None
    # 50M points vs a 1 GiB budget (~6.7M points): bounded, chunk a
    # quarter of what fits.
    got = _auto_points_in_flight(big, ram_budget=1 << 30)
    fits = (1 << 30) // _HOST_BYTES_PER_POINT
    assert got == max(1 << 16, fits // 4)
    # Tiny-RAM host: the floor must stay under the budget's order of
    # magnitude, not balloon past it (75 MB budget -> ~490k fit; the
    # chunk must be <= what fits, not a fixed 1M).
    tiny = _auto_points_in_flight(big, ram_budget=75 << 20)
    assert tiny <= (75 << 20) // _HOST_BYTES_PER_POINT
    assert tiny >= 1 << 16
    # Unsizeable sources (no n, no path) can't auto-route.
    assert _auto_points_in_flight(object()) is None


def test_estimate_source_points_from_file_size(tmp_path):
    from heatmap_tpu.pipeline.batch import (
        _MIN_TEXT_ROW_BYTES, _estimate_source_points,
    )

    p = tmp_path / "pts.csv"
    p.write_text("lat,lon,user\n" * 1000)
    est = _estimate_source_points(str(p))
    assert est == p.stat().st_size // _MIN_TEXT_ROW_BYTES
    # Path-holding source objects estimate the same way.
    class _S:
        path = str(p)
    assert _estimate_source_points(_S()) == est


def test_run_job_auto_bounds_oversized_source(monkeypatch):
    """With host RAM faked tiny, the default run_job call takes the
    bounded path on its own — and stays exactly equal to single-shot
    (linearity), with 0 forcing single-shot back."""
    from heatmap_tpu.io.sources import SyntheticSource
    from heatmap_tpu.pipeline import batch as batch_mod
    from heatmap_tpu.pipeline import run_job

    cfg = BatchJobConfig(detail_zoom=11, min_detail_zoom=7)
    src = SyntheticSource(n=3000, seed=11)
    plain = run_job(src, config=cfg, max_points_in_flight=0)

    taken = {}
    real_bounded = batch_mod._run_job_bounded

    def spy(source, sink, config, batch_size, max_points, **kw):
        taken["max_points"] = max_points
        return real_bounded(source, sink, config, batch_size, max_points,
                            **kw)

    monkeypatch.setattr(batch_mod, "_run_job_bounded", spy)
    # ~48 KiB budget -> fits ~300 points, so n=3000 must auto-bound
    # (the 64k floor kicks in; correctness is chunk-size independent).
    monkeypatch.setattr(
        batch_mod, "_available_ram_bytes", lambda: 96 * 1024
    )
    auto = run_job(src, config=cfg)
    assert taken["max_points"] == 1 << 16  # floor kicked in
    assert auto == plain


def test_weighted_job_is_linear_in_weights():
    """config.weighted with every value == 2.5 must yield exactly
    2.5x the count job's blob values (the cascade is a linear (key,
    sum) reduction; counts are oracle-verified elsewhere), across every
    level, slot, and timespan."""
    from heatmap_tpu.pipeline import run_job

    rows = _rows(n=800, seed=3)
    wrows = [dict(r, value=2.5) for r in rows]
    cfg = BatchJobConfig(detail_zoom=12, min_detail_zoom=6,
                         timespans=("alltime", "month"))
    import dataclasses

    counted = run_job(_ColSource(rows), config=cfg, batch_size=128)
    weighted = run_job(
        _ColSource(wrows),
        config=dataclasses.replace(cfg, weighted=True),
        batch_size=128,
    )
    assert counted.keys() == weighted.keys()
    for key, blob in counted.items():
        c = json.loads(blob)
        w = json.loads(weighted[key])
        assert c.keys() == w.keys(), key
        for tile, cnt in c.items():
            assert w[tile] == pytest.approx(2.5 * cnt), (key, tile)


def test_weighted_job_hand_computed_sums():
    """Distinct per-row values on known tiles: blob values must be the
    exact per-(user, tile) sums, 'all' the total, background dropped,
    x-users only in 'all'."""
    from heatmap_tpu.pipeline import run_job

    base = {"latitude": 47.6, "longitude": -122.3, "timestamp": None}
    rows = [
        dict(base, user_id="alice", value=1.25),
        dict(base, user_id="alice", value=2.0),
        dict(base, user_id="bob", value=10.0),
        dict(base, user_id="x-spy", value=100.0),   # 'all' only
        dict(base, user_id="carol", value=5.0, source="background"),
    ]
    cfg = BatchJobConfig(detail_zoom=10, min_detail_zoom=4, weighted=True)
    blobs = run_job(_ColSource(rows), config=cfg, batch_size=10)
    from heatmap_tpu.tilemath.tile import Tile

    detail = Tile.tile_id_from_lat_long(47.6, -122.3, 10)
    per_user = {}
    for key, blob in blobs.items():
        user = key.split("|")[0]
        doc = json.loads(blob)
        if detail in doc:
            per_user[user] = doc[detail]
    assert per_user["alice"] == pytest.approx(3.25)
    assert per_user["bob"] == pytest.approx(10.0)
    assert "x-spy" not in per_user
    assert "carol" not in per_user
    assert per_user["all"] == pytest.approx(113.25)


def test_weighted_job_missing_value_column_raises():
    from heatmap_tpu.pipeline import run_job

    rows = _rows(n=20, seed=1)
    cfg = BatchJobConfig(detail_zoom=10, min_detail_zoom=4, weighted=True)
    with pytest.raises(ValueError, match="value"):
        run_job(_ColSource(rows), config=cfg)


@pytest.mark.slow
def test_weighted_fast_hmpb_matches_string_path(tmp_path):
    """run_job_fast on an HMPB file with a value section must produce
    the same blobs as the string path over the same weighted rows —
    plain AND bounded (integer weights keep every f64 sum exact)."""
    from heatmap_tpu.io.hmpb import HMPBSource, write_hmpb
    from heatmap_tpu.pipeline import run_job, run_job_fast
    from heatmap_tpu.pipeline.groups import route_user

    rng = np.random.default_rng(23)
    rows = [dict(r, value=float(v))
            for r, v in zip(_rows(n=600, seed=19),
                            rng.integers(0, 12, 600))]
    cfg = BatchJobConfig(detail_zoom=12, min_detail_zoom=6, weighted=True)
    want = run_job(_ColSource(rows), config=cfg, batch_size=128)

    # Same rows in the fast layout (route host-side like convert does).
    names, intern = [], {}
    rid = np.empty(len(rows), np.int32)
    for i, r in enumerate(rows):
        name = route_user(r["user_id"])
        if name is None:
            rid[i] = -1
            continue
        if name not in intern:
            intern[name] = len(names)
            names.append(name)
        rid[i] = intern[name]
    path = write_hmpb(
        str(tmp_path / "w.hmpb"),
        np.asarray([r["latitude"] for r in rows]),
        np.asarray([r["longitude"] for r in rows]),
        rid, names,
        timestamp=np.asarray([r["timestamp"] for r in rows], np.int64),
        background=np.asarray(
            [r.get("source") == "background" for r in rows], np.uint8),
        value=np.asarray([r["value"] for r in rows]),
    )
    src = HMPBSource(path)
    assert src.has_value
    got = run_job_fast(src, config=cfg, batch_size=128)
    assert want == got
    bounded = run_job_fast(HMPBSource(path), config=cfg, batch_size=128,
                           max_points_in_flight=150)
    assert want == bounded


def test_weighted_fast_without_value_column_raises(tmp_path):
    from heatmap_tpu.io.hmpb import HMPBSource, write_hmpb
    from heatmap_tpu.pipeline import run_job_fast

    path = write_hmpb(str(tmp_path / "nv.hmpb"),
                      np.asarray([47.6]), np.asarray([-122.3]),
                      np.asarray([0], np.int32), ["u1"])
    cfg = BatchJobConfig(detail_zoom=10, min_detail_zoom=4, weighted=True)
    with pytest.raises(ValueError, match="value"):
        run_job_fast(HMPBSource(path), config=cfg)
    with pytest.raises(ValueError, match="value"):
        run_job_fast(HMPBSource(path), config=cfg, max_points_in_flight=10)


@pytest.mark.slow
@pytest.mark.parametrize("overlap", [False, True])
def test_weighted_bounded_matches_plain(overlap):
    """Weighted jobs under max_points_in_flight: integer-valued weights
    keep every f64 sum exact, so the chunked merge must reproduce the
    plain path byte-for-byte."""
    import dataclasses

    from heatmap_tpu.pipeline import run_job

    rng = np.random.default_rng(17)
    rows = [dict(r, value=float(v))
            for r, v in zip(_rows(n=1500, seed=11),
                            rng.integers(0, 20, 1500))]
    cfg = BatchJobConfig(detail_zoom=12, min_detail_zoom=6,
                         timespans=("alltime", "month"), weighted=True)
    plain = run_job(_ColSource(rows), config=cfg, batch_size=128)
    bounded = run_job(_ColSource(rows), config=cfg, batch_size=128,
                      max_points_in_flight=200, overlap_ingest=overlap)
    assert plain == bounded


def test_weighted_bounded_missing_value_column_raises():
    from heatmap_tpu.pipeline import run_job

    rows = _rows(n=50, seed=1)  # no value column
    cfg = BatchJobConfig(detail_zoom=10, min_detail_zoom=4, weighted=True)
    with pytest.raises(ValueError, match="value"):
        run_job(_ColSource(rows), config=cfg, max_points_in_flight=20)


@pytest.mark.slow
def test_cascade_backend_partitioned_identical_blobs():
    """BatchJobConfig(cascade_backend='partitioned'): the MXU cascade
    reduction produces the same blobs as the scatter backend for count
    jobs; weighted jobs refuse it loudly."""
    import dataclasses

    from heatmap_tpu.pipeline import run_job

    rows = _rows(n=900, seed=31)
    cfg = BatchJobConfig(detail_zoom=13, min_detail_zoom=6,
                         cascade_backend="partitioned")
    a = run_job(_ColSource(rows), config=cfg, batch_size=256)
    b = run_job(_ColSource(rows),
                config=dataclasses.replace(cfg, cascade_backend="scatter"),
                batch_size=256)
    assert a == b and len(a) > 0
    wrows = [dict(r, value=2.0) for r in rows]
    with pytest.raises(ValueError, match="bounded-integer"):
        run_job(_ColSource(wrows),
                config=dataclasses.replace(cfg, weighted=True),
                batch_size=256)
    # Bounded path honors the backend too (identical blobs).
    bounded = run_job(_ColSource(rows), config=cfg, batch_size=256,
                      max_points_in_flight=300)
    assert bounded == a
    # Bounded-integer weighted contract: weight_bound unlocks the
    # partitioned backend for integer-weighted jobs, byte-identical
    # to the scatter backend.
    rng = np.random.default_rng(32)
    for r in wrows:
        r["value"] = float(rng.integers(0, 100))
    wp = run_job(_ColSource(wrows),
                 config=dataclasses.replace(cfg, weighted=True,
                                            weight_bound=100),
                 batch_size=256)
    ws = run_job(_ColSource(wrows),
                 config=BatchJobConfig(detail_zoom=13, min_detail_zoom=6,
                                       weighted=True),
                 batch_size=256)
    assert wp == ws and len(wp) > 0
    # A weight outside the declared bound surfaces as overflow, not a
    # silently rounded sum.
    bad = [dict(r, value=250.75) for r in wrows[:4]] + wrows
    with pytest.raises(ValueError, match="overflowed capacity"):
        run_job(_ColSource(bad),
                config=dataclasses.replace(cfg, weighted=True,
                                           weight_bound=100),
                batch_size=256)
    # The contract knob is rejected where it would silently no-op.
    with pytest.raises(ValueError, match="weighted=True"):
        BatchJobConfig(weight_bound=10)
    # A bound past the kernel's exactness limit fails at config time,
    # not mid-job (no slab can keep f32 sums exact there).
    with pytest.raises(ValueError, match="exactness limit"):
        BatchJobConfig(weighted=True, weight_bound=20_000,
                       cascade_backend="partitioned")
    # Scatter has no such limit — big integer weights are fine there.
    BatchJobConfig(weighted=True, weight_bound=20_000)
    # Typos die at config construction, not after a full ingest.
    with pytest.raises(ValueError, match="unknown cascade backend"):
        BatchJobConfig(cascade_backend="partioned")
    # 60-bit key-budget guard: zoom 21 with huge slot counts cannot
    # reconstruct through three 20-bit channels.
    from heatmap_tpu.pipeline.cascade import CascadeConfig, build_cascade

    with pytest.raises(ValueError, match="60-bit"):
        build_cascade(np.zeros(4, np.int64), np.zeros(4, np.int64),
                      CascadeConfig(detail_zoom=21), n_slots=1 << 19,
                      backend="partitioned")


@pytest.mark.slow
def test_adaptive_capacity_identical_results():
    """adaptive_capacity shrinks deep cascade levels to the real
    unique counts; blobs must be identical to the fixed-shape path
    (counted AND weighted), including under amplify_all."""
    import dataclasses

    from heatmap_tpu.pipeline import run_job

    rows = [dict(r, value=float(v))
            for r, v in zip(_rows(n=1200, seed=29),
                            np.random.default_rng(29).integers(0, 9, 1200))]
    for weighted in (False, True):
        for amplify in (False, True):
            cfg = BatchJobConfig(detail_zoom=14, min_detail_zoom=5,
                                 weighted=weighted, amplify_all=amplify,
                                 adaptive_capacity=True)
            a = run_job(_ColSource(rows), config=cfg, batch_size=256)
            b = run_job(_ColSource(rows),
                        config=dataclasses.replace(
                            cfg, adaptive_capacity=False),
                        batch_size=256)
            assert a == b and len(a) > 0, (weighted, amplify)


@pytest.mark.slow
def test_run_job_bounded_propagates_ingest_errors():
    """A source failure in the prefetch thread must surface as the
    job's exception, not a hang or a silent partial result."""
    from heatmap_tpu.pipeline import run_job

    class ExplodingSource:
        def batches(self, batch_size):
            rows = _rows(n=400, seed=3)
            yield {
                "latitude": np.asarray([r["latitude"] for r in rows]),
                "longitude": np.asarray([r["longitude"] for r in rows]),
                "user_id": [r["user_id"] for r in rows],
                "timestamp": [r.get("timestamp") for r in rows],
                "source": [r.get("source", "gps") for r in rows],
            }
            raise OSError("disk vanished mid-scan")

    with pytest.raises(OSError, match="disk vanished"):
        run_job(ExplodingSource(), config=BatchJobConfig(detail_zoom=10,
                                                         min_detail_zoom=7),
                batch_size=100, max_points_in_flight=120)


@pytest.mark.slow
def test_run_job_bounded_device_arrays_stay_small(monkeypatch):
    """A source 10x larger than the bound never materializes more than
    ~one chunk's emissions on device (the config-5 memory shape)."""
    from heatmap_tpu.pipeline import batch as batch_mod
    from heatmap_tpu.pipeline import cascade as cascade_mod
    from heatmap_tpu.pipeline import run_job

    sizes = []
    real = cascade_mod.run_cascade

    def spy(e_codes, *a, **kw):
        sizes.append(len(e_codes))
        return real(e_codes, *a, **kw)

    monkeypatch.setattr(batch_mod.cascade_mod, "run_cascade", spy)
    rows = _rows(n=3000, seed=9)
    cfg = BatchJobConfig(detail_zoom=11, min_detail_zoom=7)
    bound = 300
    bounded = run_job(_ColSource(rows), config=cfg, batch_size=100,
                      max_points_in_flight=bound)
    assert len(sizes) >= 8  # actually chunked, not one big pass
    # <= 2 emissions per point (all + per-user); chunks never overshoot
    # the bound (flush happens before an overfilling append).
    assert max(sizes) <= 2 * bound
    sizes.clear()
    plain = run_job(_ColSource(rows), config=cfg, batch_size=100)
    assert sizes and sizes[0] > 2 * bound  # unbounded = one big cascade
    assert plain == bounded


@pytest.mark.slow
def test_run_job_bounded_default_zoom_regression():
    """z21 regression: the chunk merge packs (ts, g, code) with
    code_bits = 42, which silently wrapped when the slot columns
    arrived int32 off the native key decoder (int32 << 42). Must match
    the unbounded job exactly at the DEFAULT detail zoom."""
    from heatmap_tpu.pipeline import run_job

    rows = _rows(n=1500, seed=21)
    cfg = BatchJobConfig()  # detail_zoom=21: the reference's real shape
    plain = run_job(_ColSource(rows), config=cfg, batch_size=128)
    bounded = run_job(_ColSource(rows), config=cfg, batch_size=128,
                      max_points_in_flight=200)
    assert plain == bounded


def test_merge_sorted_level_int32_slots_wide_codes():
    """Direct pin of the int32-shift wrap: _merge_sorted_level must pack
    int32 ts/g columns with 42-bit codes without wrapping, regardless
    of whether the native decoder (the int32 provenance) is built."""
    from heatmap_tpu.pipeline.batch import _merge_sorted_level

    empty = {"ts": np.empty(0, np.int64), "g": np.empty(0, np.int64),
             "code": np.empty(0, np.int64),
             "value": np.empty(0, np.float64)}
    code = np.array([1, (1 << 42) - 5], np.int64)
    a = _merge_sorted_level(
        empty, np.zeros(2, np.int32), np.array([3, 200], np.int32),
        code, np.array([1.0, 2.0]),
    )
    m = _merge_sorted_level(
        a, np.zeros(2, np.int32), np.array([3, 299], np.int32),
        code, np.array([5.0, 7.0]),
    )
    # (g=3, code=1)+=5, new (g=200, big) and (g=299, big) stay distinct.
    assert m["g"].tolist() == [3, 200, 299]
    assert m["code"].tolist() == [1, (1 << 42) - 5, (1 << 42) - 5]
    assert m["value"].tolist() == [6.0, 2.0, 7.0]


@pytest.mark.slow
def test_zoom_clamped_capacities_match_unclamped():
    """build_cascade's static per-level capacity clamp (n_slots * 4^zoom
    bounds the key space) must not change any aggregate — only array
    padding. Uses a LOW detail zoom so the clamp actually bites."""
    import jax.numpy as jnp

    from heatmap_tpu.pipeline import cascade as cascade_mod

    rng = np.random.default_rng(21)
    n, n_slots = 20_000, 7
    cfg = cascade_mod.CascadeConfig(detail_zoom=6, min_detail_zoom=2,
                                    result_delta=2)
    codes = jnp.asarray(rng.integers(0, 1 << 12, n), jnp.int64)
    slots = jnp.asarray(rng.integers(0, n_slots, n), jnp.int32)

    clamped = cascade_mod.build_cascade(codes, slots, cfg, n_slots)
    explicit = cascade_mod.build_cascade(
        codes, slots, cfg, n_slots,
        capacity=[n] * (cfg.n_levels + 1))
    for lvl, ((cu, cs, cn), (eu, es, en)) in enumerate(zip(clamped, explicit)):
        zoom = cfg.detail_zoom - lvl
        assert cu.shape[0] <= n_slots << (2 * zoom)
        m = int(en)
        assert int(cn) == m, lvl
        np.testing.assert_array_equal(np.asarray(cu)[:m], np.asarray(eu)[:m])
        np.testing.assert_array_equal(np.asarray(cs)[:m], np.asarray(es)[:m])


# -- data-parallel cascade (local multi-device DP) -------------------------


def _dp_cfg(**kw):
    # data_parallel=True: the equivalence tests below must exercise the
    # mesh route at test-sized inputs, which the auto threshold
    # (AUTO_DP_MIN_EMISSIONS) deliberately routes single-device.
    base = dict(detail_zoom=12, min_detail_zoom=6,
                timespans=("alltime", "month"), data_parallel=True)
    base.update(kw)
    return BatchJobConfig(**base)


def test_dp_mesh_auto_routing():
    """Auto (None) is capable on this 8-device env but engages only at
    AUTO_DP_MIN_EMISSIONS; True always engages; False pins it off; the
    non-composing configs route single-device instead of raising."""
    from heatmap_tpu.pipeline.batch import (
        AUTO_DP_MIN_EMISSIONS, _dp_mesh, _dp_mesh_for,
    )

    auto = _dp_cfg(data_parallel=None)
    mesh = _dp_mesh(auto)
    assert mesh is not None
    assert _dp_mesh(_dp_cfg()) is not None  # True
    assert _dp_mesh(_dp_cfg(data_parallel=False)) is None
    # The partitioned cascade composes with DP (each shard's detail
    # reduction swaps kernels), so it does not force the
    # single-device route.
    assert _dp_mesh(
        _dp_cfg(data_parallel=None, cascade_backend="partitioned")
    ) is not None
    # adaptive_capacity composes with the mesh, auto and explicit.
    assert _dp_mesh(
        _dp_cfg(data_parallel=None, adaptive_capacity=True)
    ) is not None
    assert _dp_mesh(_dp_cfg(adaptive_capacity=True)) is not None
    # The size gate: auto stays single-device below the threshold
    # (tiny shards lose to the dispatch), engages at it; explicit True
    # engages at any size.
    assert _dp_mesh_for(mesh, auto, AUTO_DP_MIN_EMISSIONS - 1) is None
    assert _dp_mesh_for(mesh, auto, AUTO_DP_MIN_EMISSIONS) is mesh
    assert _dp_mesh_for(mesh, _dp_cfg(), 8) is mesh


def test_dp_config_rejections():
    """data_parallel=True composes with the partitioned cascade and
    with adaptive capacities; the mesh has one formulation, gspmd, and
    no option selects another."""
    cfg = _dp_cfg(data_parallel=True, cascade_backend="partitioned")
    assert cfg.resolved_cascade_backend == "partitioned"
    assert _dp_cfg(data_parallel=True,
                   adaptive_capacity=True).resolved_dispatch == "gspmd"
    for knob in ("dispatch", "dp_merge"):
        with pytest.raises(TypeError, match=knob):
            _dp_cfg(**{knob: "gspmd"})


def test_cascade_backend_auto_resolution(monkeypatch):
    """"auto" routes count jobs to the partitioned MXU kernel ON TPU
    only (off TPU the pallas kernel would run in interpret mode,
    orders slower than native scatter — same gate as
    ops/histogram._pick_backend); weighted jobs stay on scatter;
    explicit choices are honored on any platform."""
    import types

    import jax

    assert BatchJobConfig().resolved_cascade_backend == "scatter"  # CPU
    assert (BatchJobConfig(cascade_backend="partitioned")
            .resolved_cascade_backend == "partitioned")
    monkeypatch.setattr(jax, "devices",
                        lambda: [types.SimpleNamespace(platform="tpu")])
    assert BatchJobConfig().resolved_cascade_backend == "partitioned"
    assert (BatchJobConfig(weighted=True).resolved_cascade_backend
            == "scatter")
    assert (BatchJobConfig(cascade_backend="scatter")
            .resolved_cascade_backend == "scatter")


def test_dp_min_emissions_override():
    """The calibration knob moves the auto threshold; combining it with
    an explicit on/off (where it would silently do nothing) is rejected
    at config time."""
    from heatmap_tpu.pipeline.batch import _dp_mesh, _dp_mesh_for

    tuned = _dp_cfg(data_parallel=None, dp_min_emissions=1000)
    mesh = _dp_mesh(tuned)
    assert mesh is not None
    assert _dp_mesh_for(mesh, tuned, 999) is None
    assert _dp_mesh_for(mesh, tuned, 1000) is mesh
    # 0 engages auto at any size (the "my hardware always wins" pin).
    always = _dp_cfg(data_parallel=None, dp_min_emissions=0)
    assert _dp_mesh_for(mesh, always, 1) is mesh
    with pytest.raises(ValueError, match="AUTO"):
        _dp_cfg(data_parallel=True, dp_min_emissions=1000)
    with pytest.raises(ValueError, match="AUTO"):
        _dp_cfg(data_parallel=False, dp_min_emissions=1000)
    with pytest.raises(ValueError, match=">= 0"):
        _dp_cfg(data_parallel=None, dp_min_emissions=-1)


@pytest.mark.slow
@pytest.mark.parametrize("amplify", [False, True])
def test_run_job_data_parallel_byte_identical(amplify):
    """The flagship job over the 8-device mesh:
    blobs byte-identical to the single-device cascade at every level,
    in both compat modes."""
    from heatmap_tpu.pipeline import run_job

    rows = _rows(n=2500, seed=42)
    dp = run_job(_ColSource(rows), config=_dp_cfg(amplify_all=amplify))
    single = run_job(
        _ColSource(rows),
        config=_dp_cfg(amplify_all=amplify, data_parallel=False),
    )
    assert dp == single and len(dp) > 0


@pytest.mark.slow
def test_run_job_dp_partitioned_cascade_byte_identical():
    """DP x partitioned composition at the blob level: the MXU segment
    reduction of each shard's detail stage must emit blobs
    byte-identical to BOTH the DP scatter cascade and the single-device
    partitioned cascade. Counts are exact integers in any summation
    order, so the bar is equality — the same bar the scatter DP route
    passes."""
    from heatmap_tpu.pipeline import run_job

    rows = _rows(n=2500, seed=42)
    dp_part = run_job(_ColSource(rows),
                      config=_dp_cfg(cascade_backend="partitioned"))
    dp_scat = run_job(_ColSource(rows),
                      config=_dp_cfg(cascade_backend="scatter"))
    single = run_job(_ColSource(rows),
                     config=_dp_cfg(cascade_backend="partitioned",
                                    data_parallel=False))
    assert dp_part == dp_scat == single and len(dp_part) > 0


def test_run_job_data_parallel_matches_oracle():
    """DP blobs equal the pure-Python reference oracle exactly — the
    sharded route is held to the same golden bar as the single-device
    path, not just to path-vs-path equality."""
    rows = _rows(n=300, seed=77)
    cfg = BatchJobConfig(detail_zoom=12, min_detail_zoom=5,
                         data_parallel=True)
    got = run_batch(rows, cfg)
    want = oracle.run_job(rows, detail_zoom=12, min_detail_zoom=5,
                          amplify_all=False)
    assert got.keys() == want.keys()
    for key in want:
        assert got[key] == want[key], key


@pytest.mark.slow
def test_run_job_data_parallel_bounded_byte_identical():
    """DP composes with the bounded chunked path (per-chunk sharded
    cascade, host merge unchanged)."""
    from heatmap_tpu.pipeline import run_job

    rows = _rows(n=2000, seed=9)
    dp = run_job(_ColSource(rows), config=_dp_cfg(),
                 batch_size=128, max_points_in_flight=300)
    single = run_job(_ColSource(rows), config=_dp_cfg(data_parallel=False),
                     batch_size=128, max_points_in_flight=300)
    assert dp == single and len(dp) > 0


@pytest.mark.slow
def test_run_job_data_parallel_weighted_integer_bit_identical():
    """Integer-valued weighted sums are exact in f64 under any
    summation order, so the DP route must match bit-for-bit."""
    from heatmap_tpu.pipeline import run_job

    rng = np.random.default_rng(5)
    rows = _rows(n=1500, seed=5)
    for r in rows:
        r["value"] = float(rng.integers(1, 12))
    dp = run_job(_ColSource(rows), config=_dp_cfg(weighted=True))
    single = run_job(_ColSource(rows),
                     config=_dp_cfg(weighted=True, data_parallel=False))
    assert dp == single and len(dp) > 0


@pytest.mark.slow
def test_run_job_data_parallel_fractional_weights_allclose():
    """Fractional weighted sums agree up to f64 summation-order
    rounding (the documented contract, same as the bounded merge)."""
    from heatmap_tpu.pipeline import run_job

    rng = np.random.default_rng(6)
    rows = _rows(n=1500, seed=6)
    for r in rows:
        r["value"] = float(rng.random())
    dp = run_job(_ColSource(rows), config=_dp_cfg(weighted=True))
    single = run_job(_ColSource(rows),
                     config=_dp_cfg(weighted=True, data_parallel=False))
    assert dp.keys() == single.keys()
    for key in single:
        a, b = json.loads(dp[key]), json.loads(single[key])
        assert list(a) == list(b), key
        for field in a:
            assert a[field] == pytest.approx(b[field], rel=1e-12), key


@pytest.mark.slow
def test_dp_cascade_overflow_detected():
    """An undersized capacity must still raise through the sharded
    route — the per-device overflow flag propagates into every level's
    n_unique (the ops/sparse.py contract)."""
    from heatmap_tpu.parallel.mesh import make_mesh
    from heatmap_tpu.pipeline import cascade as cascade_mod
    import jax

    rng = np.random.default_rng(13)
    cfg = cascade_mod.CascadeConfig(detail_zoom=8, min_detail_zoom=4,
                                    result_delta=4)
    codes = rng.integers(0, 1 << 16, 4096)
    slots = np.zeros(4096, np.int64)
    mesh = make_mesh(devices=jax.devices())
    levels = cascade_mod.build_cascade(
        codes, slots, cfg, n_slots=1, capacity=8, mesh=mesh
    )
    with pytest.raises(ValueError, match="overflowed"):
        cascade_mod.decode_levels(levels, cfg)


def test_build_cascade_mesh_rejects_noncomposing():
    """mesh + adaptive on the partitioned backend still raises at the
    cascade layer (covers callers that bypass BatchJobConfig); mesh +
    partitioned composes — each shard's detail reduction runs the
    segment kernel — and so does mesh + adaptive on scatter: both must
    match the mesh scatter cascade exactly."""
    from heatmap_tpu.parallel.mesh import make_mesh
    from heatmap_tpu.pipeline import cascade as cascade_mod
    import jax

    cfg = cascade_mod.CascadeConfig(detail_zoom=8, min_detail_zoom=4,
                                    result_delta=4)
    codes = np.arange(64, dtype=np.int64)
    slots = np.zeros(64, np.int64)
    mesh = make_mesh(devices=jax.devices())
    with pytest.raises(ValueError, match="adaptive"):
        cascade_mod.build_cascade(codes, slots, cfg, n_slots=1,
                                  adaptive=True, backend="partitioned",
                                  mesh=mesh)
    part = cascade_mod.build_cascade(codes, slots, cfg, n_slots=1,
                                     backend="partitioned", mesh=mesh)
    adaptive = cascade_mod.build_cascade(codes, slots, cfg, n_slots=1,
                                         adaptive=True, mesh=mesh)
    scat = cascade_mod.build_cascade(codes, slots, cfg, n_slots=1,
                                     backend="scatter", mesh=mesh)
    for got in (part, adaptive):
        assert len(got) == len(scat)
        for (pu, ps, pn), (su, ss, sn) in zip(got, scat):
            n = int(sn)
            assert int(pn) == n
            np.testing.assert_array_equal(np.asarray(pu)[:n],
                                          np.asarray(su)[:n])
            np.testing.assert_array_equal(np.asarray(ps)[:n],
                                          np.asarray(ss)[:n])


# -- auto-spill safety rails -----------------------------------------------


def _auto_spill_env(monkeypatch, batch_mod, tmp_path):
    """Force auto-spill eligibility: tiny threshold, per-test dir (a
    shared hardcoded dir would let parallel runs see each other's live
    spill tempdirs)."""
    monkeypatch.setattr(batch_mod, "AUTO_SPILL_ROWS", 500)
    monkeypatch.setattr(batch_mod, "_auto_spill_target",
                        lambda: batch_mod.AUTO_SPILL_DIR)
    monkeypatch.setattr(batch_mod, "AUTO_SPILL_DIR",
                        str(tmp_path / "auto-spill"))


def test_auto_spill_refused_when_projection_exceeds_free_space(
        monkeypatch, tmp_path):
    """A too-small target filesystem must keep the in-RAM fold (with a
    warning), never convert and then ENOSPC a job that RAM finishes."""
    import glob

    from heatmap_tpu.pipeline import batch as batch_mod
    from heatmap_tpu.pipeline import run_job

    rows = _rows(n=2000, seed=7)
    cfg = BatchJobConfig(detail_zoom=12, min_detail_zoom=6)
    plain = run_job(_ColSource(rows), config=cfg, batch_size=128,
                    max_points_in_flight=150)

    _auto_spill_env(monkeypatch, batch_mod, tmp_path)
    monkeypatch.setattr(batch_mod, "_free_disk_bytes", lambda p: 1024)
    created = []
    real_spill = batch_mod._SpillMerge

    class _Spy(real_spill):
        def __init__(self, root, n_levels):
            super().__init__(root, n_levels)
            created.append(self.dir)

    monkeypatch.setattr(batch_mod, "_SpillMerge", _Spy)
    with pytest.warns(RuntimeWarning, match="auto-spill skipped"):
        got = run_job(_ColSource(rows), config=cfg, batch_size=128,
                      max_points_in_flight=150)
    assert got == plain
    assert created == []  # never converted
    assert not glob.glob(str(tmp_path / "auto-spill" / "merge-spill-*"))


def test_auto_spill_write_failure_falls_back_to_ram(monkeypatch, tmp_path):
    """An OSError mid-spill on the AUTO path folds the spilled runs
    back into RAM and finishes diskless — byte-identical blobs, spill
    tempdir cleaned up, warning raised (auto-spill must not fail a job
    that fits in RAM)."""
    import glob

    from heatmap_tpu.pipeline import batch as batch_mod
    from heatmap_tpu.pipeline import run_job

    rows = _rows(n=2000, seed=7)
    cfg = BatchJobConfig(detail_zoom=12, min_detail_zoom=6)
    plain = run_job(_ColSource(rows), config=cfg, batch_size=128,
                    max_points_in_flight=150)

    _auto_spill_env(monkeypatch, batch_mod, tmp_path)
    real_spill = batch_mod._SpillMerge
    state = {"adds": 0, "dirs": []}

    class _Failing(real_spill):
        def __init__(self, root, n_levels):
            super().__init__(root, n_levels)
            state["dirs"].append(self.dir)

        def add_level(self, run, level, ts, g, code, value):
            # Let the conversion (run 0) through, then die partway
            # through a later run — some levels written, some not, and
            # the failing level's last file TRUNCATED-but-present (the
            # real ENOSPC shape): recovery must drop it by name, not
            # trust file existence.
            if run >= 1 and level >= 3:
                base = self._base(run, level)
                np.save(base + "_ts.npy", np.asarray(ts, np.int32))
                np.save(base + "_g.npy", np.asarray(g, np.int32))
                np.save(base + "_code.npy", np.asarray(code, np.int64))
                with open(base + "_value.npy", "wb") as f:
                    f.write(b"\x93NUMPY")  # truncated mid-write
                raise OSError(28, "No space left on device")
            return super().add_level(run, level, ts, g, code, value)

    monkeypatch.setattr(batch_mod, "_SpillMerge", _Failing)
    with pytest.warns(RuntimeWarning, match="auto-spill write failed"):
        got = run_job(_ColSource(rows), config=cfg, batch_size=128,
                      max_points_in_flight=150)
    assert got == plain
    assert len(state["dirs"]) == 1
    assert not glob.glob(state["dirs"][0] + "*")  # cleaned up


def test_explicit_spill_write_failure_still_raises(monkeypatch, tmp_path):
    """merge_spill_dir is the operator's explicit choice: a disk error
    there must fail the job loudly, not silently fall back to the
    in-RAM merge whose footprint the operator asked to avoid."""
    from heatmap_tpu.pipeline import batch as batch_mod
    from heatmap_tpu.pipeline import run_job

    real_spill = batch_mod._SpillMerge

    class _Failing(real_spill):
        def add_level(self, run, level, ts, g, code, value):
            if run >= 1:
                raise OSError(28, "No space left on device")
            return super().add_level(run, level, ts, g, code, value)

    monkeypatch.setattr(batch_mod, "_SpillMerge", _Failing)
    rows = _rows(n=2000, seed=7)
    cfg = BatchJobConfig(detail_zoom=12, min_detail_zoom=6)
    with pytest.raises(OSError):
        run_job(_ColSource(rows), config=cfg, batch_size=128,
                max_points_in_flight=150,
                merge_spill_dir=str(tmp_path / "spill"))
    # Cleanup still ran (the ingest-failure cleanup path).
    spill_root = tmp_path / "spill"
    assert not spill_root.exists() or list(spill_root.iterdir()) == []


def test_auto_spill_projection_math():
    from heatmap_tpu.pipeline import batch as batch_mod

    fits = batch_mod._auto_spill_projection_fits
    # Known totals: 1000 table rows + 2 remaining * 500-row chunks
    # -> 24 * 2000 * 1.25 = 60000 bytes projected.
    import unittest.mock as mock
    with mock.patch.object(batch_mod, "_free_disk_bytes",
                           lambda p: 60_000):
        assert fits("/x", 1000, 3, 5, 500)
    with mock.patch.object(batch_mod, "_free_disk_bytes",
                           lambda p: 59_999):
        assert not fits("/x", 1000, 3, 5, 500)
    # Unknown chunk total: assume as many chunks remain as have run.
    with mock.patch.object(batch_mod, "_free_disk_bytes",
                           lambda p: 10**12):
        assert fits("/x", 1000, 3, None, 500)
    # No free-space signal: keep the measured default (spill).
    with mock.patch.object(batch_mod, "_free_disk_bytes",
                           lambda p: None):
        assert fits("/x", 10**12, 1, None, 10**12)


def test_fast_auto_routing_respects_source_bytes_per_point():
    """HMPB mmap ingest (~30 B/point resident) must not be demoted to
    the chunked path by the 160 B string-ingest constant:
    the fast auto call consults fast_host_bytes_per_point, the string
    call ignores it."""
    from heatmap_tpu.pipeline.batch import _auto_points_in_flight

    class _FakeHMPB:
        n = 1_000_000
        fast_host_bytes_per_point = 30

    # Effective fast rate: 30 declared + 64/timespan of emission/sort
    # arrays = 94 B/pt at one timespan — fits a 100 B/pt budget where
    # the 160 B string constant would demote.
    budget = 1_000_000 * 100
    assert _auto_points_in_flight(_FakeHMPB(), ram_budget=budget,
                                  fast=True) is None
    assert _auto_points_in_flight(_FakeHMPB(),
                                  ram_budget=budget) is not None
    # More timespans mean more emission arrays per point: the same
    # source stops fitting (30 + 4*64 = 286 B/pt).
    assert _auto_points_in_flight(_FakeHMPB(), ram_budget=budget,
                                  fast=True, n_timespans=4) is not None
    # Weighted adds the f64 value column + expanded e_weights
    # (30 + 64 + 8 + 32 = 134 B/pt > the 100 B/pt budget).
    assert _auto_points_in_flight(_FakeHMPB(), ram_budget=budget,
                                  fast=True, weighted=True) is not None

    class _Plain:
        n = 1_000_000

    # No attribute: fast ingest keeps the conservative constant.
    assert _auto_points_in_flight(_Plain(), ram_budget=budget,
                                  fast=True) is not None


def test_dp_edge_shapes_byte_identical():
    """DP padding edges: fewer points than devices, one point, one
    unique location, all-excluded users — every shape must byte-equal
    the single-device cascade (pad lanes are valid=False and the
    per-device capacity floors at 1)."""
    from heatmap_tpu.pipeline import run_job

    cases = [
        [dict(r, source="gps") for r in _rows(n=3, seed=1)],  # n < ndev
        [dict(r, source="gps") for r in _rows(n=1, seed=2)],  # 1 point
        [dict(r, latitude=50.0, longitude=8.0, source="gps")  # 1 unique
         for r in _rows(n=40, seed=3)],                       # location
        [dict(r, user_id="xonly", source="gps")  # all users excluded:
         for r in _rows(n=24, seed=4)],          # only 'all' slots emit
    ]
    for i, rows in enumerate(cases):
        dp = run_job(_ColSource(rows), config=_dp_cfg())
        single = run_job(_ColSource(rows),
                         config=_dp_cfg(data_parallel=False))
        assert dp == single, f"case {i}"
        assert len(dp) > 0, f"case {i}"


def test_dp_all_background_returns_empty():
    from heatmap_tpu.pipeline import run_job

    rows = [dict(r, source="background") for r in _rows(n=30, seed=5)]
    assert run_job(_ColSource(rows), config=_dp_cfg()) == {}


def test_decode_levels_device_slices_match_host_path(monkeypatch):
    """The accelerator branch of decode_levels (device slices, one
    batched device_get) decodes exactly what the host branch does."""
    from heatmap_tpu.pipeline import cascade as cascade_mod
    from heatmap_tpu.pipeline.batch import _cascade_codes

    rng = np.random.default_rng(12)
    n = 5000
    codes, valid = _cascade_codes(rng.normal(47.6, 0.3, n),
                                  rng.normal(-122.3, 0.4, n), 12)
    ccfg = cascade_mod.CascadeConfig(detail_zoom=12, min_detail_zoom=6)
    slots = np.asarray(rng.integers(0, 3, n), np.int64)
    levels = cascade_mod.build_cascade(codes, slots, ccfg, n_slots=3,
                                       valid=valid, capacity=n)
    want = cascade_mod.decode_levels(levels, ccfg)
    monkeypatch.setattr(cascade_mod, "_on_accelerator", lambda x: True)
    got = cascade_mod.decode_levels(levels, ccfg)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.keys() == w.keys()
        for k in w:
            np.testing.assert_array_equal(g[k], w[k])
