"""IO layer tests: sources, sinks, PNG encoding, source->sink job.

Covers the reference's storage boundary semantics (SURVEY.md C11/C12):
column contract, background filtering downstream, upsert-by-id egress.
"""

import json

import numpy as np
import pytest

from heatmap_tpu.io import (
    CSVSource,
    DirectoryBlobSink,
    JSONLBlobSink,
    JSONLSource,
    MemorySink,
    ParquetSource,
    PNGTileSink,
    SyntheticSource,
    colorize,
    open_sink,
    open_source,
    png_bytes,
)
from heatmap_tpu.io.sources import CassandraSource
from heatmap_tpu.ops import Window
from heatmap_tpu.pipeline import BatchJobConfig, run_batch, run_job


def _write_csv(path, rows):
    cols = ["latitude", "longitude", "user_id", "source", "timestamp"]
    with open(path, "w") as f:
        f.write(",".join(cols) + "\n")
        for r in rows:
            f.write(",".join(str(r[c]) for c in cols) + "\n")


ROWS = [
    {"latitude": 47.6, "longitude": -122.3, "user_id": "alice", "source": "gps", "timestamp": 1},
    {"latitude": 47.61, "longitude": -122.31, "user_id": "bob", "source": "gps", "timestamp": 2},
    {"latitude": 47.62, "longitude": -122.32, "user_id": "x-9", "source": "gps", "timestamp": 3},
    {"latitude": 47.63, "longitude": -122.33, "user_id": "rt-1", "source": "background", "timestamp": 4},
]

#: Fixed fake Murmur3 tokens spread over the ring so rows land in
#: different token ranges (CassandraSource shard/recovery tests).
_FAKE_TOKENS = {
    "alice": -(1 << 62),
    "bob": -12345,
    "x-9": 1 << 61,
    "rt-1": (1 << 63) - 7,
}


class _FakeTokenSession:
    """Fake driver session honoring the token-range predicate contract:
    execute(cql) filters ROWS by each row's fake partition token."""

    import re as _re

    _PAT = _re.compile(r"token\(.*\) >= (-?\d+) AND token\(.*\) <= (-?\d+)")

    def execute(self, q):
        assert "rhom.locations" in q  # reference heatmap.py:137
        m = self._PAT.search(q)
        assert m, f"query missing token-range predicate: {q}"
        lo, hi = int(m.group(1)), int(m.group(2))
        return iter(
            [r for r in ROWS if lo <= _FAKE_TOKENS[r["user_id"]] <= hi]
        )


class TestSources:
    def test_synthetic_deterministic_and_batched(self):
        src = SyntheticSource(n=1000, seed=7)
        b1 = list(src.batches(300))
        b2 = list(SyntheticSource(n=1000, seed=7).batches(300))
        assert [len(b["latitude"]) for b in b1] == [300, 300, 300, 100]
        np.testing.assert_array_equal(b1[0]["latitude"], b2[0]["latitude"])
        assert any(u.startswith("x-") for b in b1 for u in b["user_id"])
        assert any(u.startswith("rt-") for b in b1 for u in b["user_id"])
        assert any(s == "background" for b in b1 for s in b["source"])

    def test_csv_roundtrip(self, tmp_path):
        p = tmp_path / "pts.csv"
        _write_csv(p, ROWS)
        batches = list(CSVSource(str(p), use_native=False).batches(3))
        assert sum(len(b["latitude"]) for b in batches) == 4
        assert batches[0]["user_id"][0] == "alice"
        np.testing.assert_allclose(batches[0]["latitude"][0], 47.6)

    def test_jsonl_roundtrip(self, tmp_path):
        p = tmp_path / "pts.jsonl"
        with open(p, "w") as f:
            for r in ROWS:
                f.write(json.dumps(r) + "\n")
        (b,) = list(JSONLSource(str(p)).batches())
        assert b["user_id"] == ["alice", "bob", "x-9", "rt-1"]

    def test_parquet_roundtrip(self, tmp_path):
        pa = pytest.importorskip("pyarrow")
        import pyarrow.parquet as pq

        p = tmp_path / "pts.parquet"
        tbl = pa.table({k: [r[k] for r in ROWS] for k in ROWS[0]})
        pq.write_table(tbl, p)
        (b,) = list(ParquetSource(str(p)).batches())
        assert b["user_id"] == ["alice", "bob", "x-9", "rt-1"]
        assert b["latitude"].dtype == np.float64

    def test_value_column_passthrough(self, tmp_path):
        """Weighted inputs (BASELINE config 3): a 'value' column rides
        through CSV/JSONL/Parquet batches and load_columns' background
        filter; sources without one omit the key entirely."""
        from heatmap_tpu.pipeline import load_columns

        vrows = [dict(r, value=v) for r, v in zip(ROWS, (2.5, 0.5, 3.0, 7.0))]
        # CSV (the value column routes past the native decoder).
        p = tmp_path / "w.csv"
        cols = ["latitude", "longitude", "user_id", "source", "timestamp",
                "value"]
        with open(p, "w") as f:
            f.write(",".join(cols) + "\n")
            for r in vrows:
                f.write(",".join(str(r[c]) for c in cols) + "\n")
        (b,) = list(CSVSource(str(p)).batches())
        np.testing.assert_allclose(b["value"], [2.5, 0.5, 3.0, 7.0])
        # JSONL.
        pj = tmp_path / "w.jsonl"
        with open(pj, "w") as f:
            for r in vrows:
                f.write(json.dumps(r) + "\n")
        (bj,) = list(JSONLSource(str(pj)).batches())
        np.testing.assert_allclose(bj["value"], [2.5, 0.5, 3.0, 7.0])
        # Parquet.
        pa = pytest.importorskip("pyarrow")
        import pyarrow.parquet as pq

        pp = tmp_path / "w.parquet"
        pq.write_table(
            pa.table({k: [r[k] for r in vrows] for k in vrows[0]}), pp)
        (bp,) = list(ParquetSource(str(pp)).batches())
        np.testing.assert_allclose(bp["value"], [2.5, 0.5, 3.0, 7.0])
        # load_columns drops the background row's value with the row.
        lc = load_columns(bj)
        np.testing.assert_allclose(lc["value"], [2.5, 0.5, 3.0])
        # No value column -> key absent end to end.
        _write_csv(tmp_path / "nw.csv", ROWS)
        (nb,) = list(CSVSource(str(tmp_path / "nw.csv")).batches())
        assert "value" not in nb
        assert "value" not in load_columns(nb)

    def test_value_column_missing_entries_default_to_one(self, tmp_path):
        pj = tmp_path / "m.jsonl"
        with open(pj, "w") as f:
            f.write(json.dumps(dict(ROWS[0], value=4.0)) + "\n")
            f.write(json.dumps(ROWS[1]) + "\n")  # no value -> 1.0
        (b,) = list(JSONLSource(str(pj)).batches())
        np.testing.assert_allclose(b["value"], [4.0, 1.0])

    def test_jsonl_late_value_raises_read_value_false_ignores(self, tmp_path):
        """The first JSONL row decides weightedness for the whole file;
        a 'value' appearing later is an error (silent dropping would
        corrupt sums, per-batch flapping would abort consumers
        mid-stream). read_value=False ignores values entirely."""
        pj = tmp_path / "late.jsonl"
        with open(pj, "w") as f:
            f.write(json.dumps(ROWS[0]) + "\n")  # no value
            f.write(json.dumps(dict(ROWS[1], value=9.0)) + "\n")
        with pytest.raises(ValueError, match="value"):
            list(JSONLSource(str(pj)).batches())
        (b,) = list(JSONLSource(str(pj), read_value=False).batches())
        assert "value" not in b
        # read_value=True forces weighted reading: row 1's missing
        # value defaults to 1.0, the late value is kept, no error.
        (bt,) = list(JSONLSource(str(pj), read_value=True).batches())
        np.testing.assert_allclose(bt["value"], [1.0, 9.0])

    def test_read_value_false_keeps_csv_native_path(self, tmp_path):
        """A value-bearing CSV with read_value=False must omit the
        column (and so stays eligible for the native fast parser)."""
        p = tmp_path / "w.csv"
        with open(p, "w") as f:
            f.write("latitude,longitude,user_id,source,timestamp,value\n")
            f.write("47.6,-122.3,u,gps,1,2.5\n")
        (b,) = list(CSVSource(str(p), read_value=False).batches())
        assert "value" not in b
        (bw,) = list(CSVSource(str(p)).batches())
        np.testing.assert_allclose(bw["value"], [2.5])

    def test_rows_view_matches_batches(self, tmp_path):
        p = tmp_path / "pts.csv"
        _write_csv(p, ROWS)
        rows = list(CSVSource(str(p), use_native=False).rows())
        assert [r["user_id"] for r in rows] == ["alice", "bob", "x-9", "rt-1"]

    def test_open_source_specs(self, tmp_path):
        assert isinstance(open_source("synthetic:100"), SyntheticSource)
        assert open_source("synthetic:100:3").seed == 3
        assert isinstance(open_source("csv:/x.csv"), CSVSource)
        assert isinstance(open_source(str(tmp_path / "a.jsonl")), JSONLSource)
        cs = open_source("cassandra:10.0.0.5")
        assert isinstance(cs, CassandraSource)
        assert cs.config.endpoint == "10.0.0.5"
        with pytest.raises(ValueError):
            open_source("nope")

    def test_cassandra_without_driver_raises_helpfully(self):
        src = CassandraSource()
        with pytest.raises(RuntimeError, match="cassandra-driver"):
            next(src.batches())

    def test_cassandra_with_injected_session(self):
        src = CassandraSource(session_factory=_FakeTokenSession)
        (b,) = list(src.batches())
        # Row order follows token-range order, not table order; the
        # multiset of rows must be exactly the table.
        assert sorted(b["user_id"]) == ["alice", "bob", "rt-1", "x-9"]

    def test_cassandra_token_ranges_cover_ring_exactly(self):
        from heatmap_tpu.io.sources import TOKEN_MAX, TOKEN_MIN, token_ranges

        for n in (1, 3, 64):
            rs = token_ranges(n)
            assert rs[0][0] == TOKEN_MIN and rs[-1][1] == TOKEN_MAX
            for (lo, hi), (lo2, _) in zip(rs, rs[1:]):
                assert lo <= hi and lo2 == hi + 1

    def test_cassandra_shards_partition_rows(self):
        # Interleaved shards together read every row exactly once.
        parts = [
            CassandraSource(
                session_factory=_FakeTokenSession,
                shard_index=i, shard_count=3,
            )
            for i in range(3)
        ]
        seen = []
        for src in parts:
            for b in src.batches():
                seen.extend(b["user_id"])
        assert sorted(seen) == ["alice", "bob", "rt-1", "x-9"]
        # Shard 0 with the same config sees a strict subset.
        assert len(seen) == len(ROWS)

    def test_cassandra_range_reread_is_deterministic(self):
        # Recovery: re-reading one failed range yields exactly the rows
        # whose tokens fall in that range, every time.
        src = CassandraSource(session_factory=_FakeTokenSession)
        from heatmap_tpu.io.sources import token_ranges

        per_range = {}
        for i, (lo, hi) in enumerate(token_ranges(src.config.n_ranges)):
            got = [u for b in src.range_batches(i) for u in b["user_id"]]
            again = [u for b in src.range_batches(i) for u in b["user_id"]]
            assert got == again
            if got:
                per_range[i] = got
            for u in got:
                tok = _FAKE_TOKENS[u]
                assert lo <= tok <= hi
        assert sorted(u for us in per_range.values() for u in us) == [
            "alice", "bob", "rt-1", "x-9",
        ]

    def test_cassandra_invalid_shard_assignment_raises(self):
        with pytest.raises(ValueError, match="shard"):
            CassandraSource(session_factory=_FakeTokenSession,
                            shard_index=3, shard_count=3)
        with pytest.raises(ValueError, match="shard"):
            CassandraSource(session_factory=_FakeTokenSession,
                            shard_index=-1)

    def test_cassandra_query_names_partition_key(self):
        from heatmap_tpu.io.sources import CassandraConfig

        src = CassandraSource(
            config=CassandraConfig(partition_keys=("device_id", "day")),
            session_factory=_FakeTokenSession,
        )
        q = src._range_query(-5, 5)
        assert "token(device_id, day) >= -5" in q
        assert "token(device_id, day) <= 5" in q


class _FakeCosmosClient:
    """Fake ContainerProxy adapter: two partition key ranges splitting
    ROWS by row parity."""

    calls: list = []

    def partition_key_range_ids(self):
        return ["0", "1"]

    def query_items(self, sql, partition_key_range_id=None):
        assert sql.startswith("SELECT c.latitude")
        type(self).calls.append(partition_key_range_id)
        return iter([
            dict(r) for i, r in enumerate(ROWS)
            if str(i % 2) == partition_key_range_id
        ])


class TestCosmosDBSource:
    def test_reads_all_ranges(self):
        from heatmap_tpu.io.sources import CosmosDBSource

        src = CosmosDBSource(client_factory=_FakeCosmosClient)
        (b,) = list(src.batches())
        assert sorted(b["user_id"]) == ["alice", "bob", "rt-1", "x-9"]

    def test_shards_partition_ranges(self):
        from heatmap_tpu.io.sources import CosmosDBSource

        seen = []
        for i in range(2):
            src = CosmosDBSource(client_factory=_FakeCosmosClient,
                                 shard_index=i, shard_count=2)
            for b in src.batches():
                seen.extend(b["user_id"])
        assert sorted(seen) == ["alice", "bob", "rt-1", "x-9"]

    def test_range_reread_is_deterministic(self):
        from heatmap_tpu.io.sources import CosmosDBSource

        src = CosmosDBSource(client_factory=_FakeCosmosClient)
        got = [u for b in src.range_batches("1") for u in b["user_id"]]
        assert got == [u for b in src.range_batches("1")
                       for u in b["user_id"]]
        assert got == [ROWS[1]["user_id"], ROWS[3]["user_id"]]

    def test_missing_env_raises_helpfully(self, monkeypatch):
        from heatmap_tpu.io.sources import CosmosDBSource

        monkeypatch.delenv("LOCATIONS_COSMOSDB_HOST", raising=False)
        with pytest.raises(RuntimeError, match="LOCATIONS_COSMOSDB_HOST"):
            next(CosmosDBSource().batches())

    def test_open_source_specs_route_to_cosmos(self):
        from heatmap_tpu.io.sources import CosmosDBSource

        # Falsy cassandra endpoint selects CosmosDB, like the
        # reference's truthiness test (reference heatmap.py:132).
        assert isinstance(open_source("cassandra:"), CosmosDBSource)
        assert isinstance(open_source("cosmosdb:"), CosmosDBSource)

    def test_invalid_shard_assignment_raises(self):
        from heatmap_tpu.io.sources import CosmosDBSource

        with pytest.raises(ValueError, match="shard"):
            CosmosDBSource(shard_index=2, shard_count=2)


class TestLevelArraysSink:
    def test_columnar_egress_matches_blob_path(self, tmp_path):
        """arrays: sink receives the same information as the blob
        format — reconstruct blobs from the columns and diff exactly."""
        from heatmap_tpu.io.sinks import LevelArraysSink

        src = SyntheticSource(n=3000, seed=4)
        cfg = BatchJobConfig(detail_zoom=11, min_detail_zoom=8)
        want = run_job(src, config=cfg)  # reference-format blobs (json)

        sink = LevelArraysSink(str(tmp_path / "cols"))
        stats = run_job(src, sink, config=cfg)
        assert stats["egress"] == "levels"
        assert stats["rows"] > 0

        got: dict = {}
        for zoom, cols in LevelArraysSink.load(str(tmp_path / "cols")).items():
            cz = int(cols["coarse_zoom"])
            for i in range(len(cols["value"])):
                bid = (f"{cols['user'][i]}|{cols['timespan'][i]}|"
                       f"{cz}_{cols['coarse_row'][i]}_{cols['coarse_col'][i]}")
                did = f"{zoom}_{cols['row'][i]}_{cols['col'][i]}"
                got.setdefault(bid, {})[did] = float(cols["value"][i])
        assert got == {k: json.loads(v) for k, v in want.items()}

    def test_parquet_format_roundtrips_identically(self, tmp_path):
        from heatmap_tpu.io.sinks import LevelArraysSink

        src = SyntheticSource(n=1500, seed=6)
        cfg = BatchJobConfig(detail_zoom=10, min_detail_zoom=8)
        run_job(src, LevelArraysSink(str(tmp_path / "npz")), config=cfg)
        run_job(src, LevelArraysSink(str(tmp_path / "pq"), format="parquet"),
                config=cfg)
        a = LevelArraysSink.load(str(tmp_path / "npz"))
        b = LevelArraysSink.load(str(tmp_path / "pq"))
        assert a.keys() == b.keys()
        for z in a:
            for k in a[z]:
                np.testing.assert_array_equal(a[z][k], b[z][k])

    def test_open_sink_parquet_spec_and_bad_format(self, tmp_path):
        from heatmap_tpu.io.sinks import LevelArraysSink

        s = open_sink(f"arrays-parquet:{tmp_path / 'c'}")
        assert isinstance(s, LevelArraysSink) and s.format == "parquet"
        with pytest.raises(ValueError, match="format"):
            LevelArraysSink(str(tmp_path / "x"), format="csv")

    def test_columnar_sink_rejects_blob_records(self, tmp_path):
        from heatmap_tpu.io.sinks import LevelArraysSink

        with pytest.raises(TypeError, match="columnar"):
            LevelArraysSink(str(tmp_path / "c")).write([("id", "{}")])

    def test_open_sink_arrays_spec(self, tmp_path):
        from heatmap_tpu.io.sinks import LevelArraysSink

        s = open_sink(f"arrays:{tmp_path / 'c'}")
        assert isinstance(s, LevelArraysSink)

    def test_bounded_job_routes_columnar(self, tmp_path):
        from heatmap_tpu.io.sinks import LevelArraysSink

        src = SyntheticSource(n=2000, seed=9)
        cfg = BatchJobConfig(detail_zoom=10, min_detail_zoom=7)
        want = run_job(src, config=cfg)
        sink = LevelArraysSink(str(tmp_path / "cols"))
        stats = run_job(src, sink, config=cfg, batch_size=256,
                        max_points_in_flight=512)
        assert stats["egress"] == "levels"
        total = sum(len(json.loads(v)) for v in want.values())
        assert stats["rows"] == total


class TestSinks:
    def test_jsonl_sink_upsert_semantics(self, tmp_path):
        p = tmp_path / "out.jsonl"
        with JSONLBlobSink(str(p)) as sink:
            sink.write([("a|alltime|5_1_2", {"6_2_4": 1.0})])
            sink.write([("a|alltime|5_1_2", {"6_2_4": 3.0})])
        loaded = JSONLBlobSink.load(str(p))
        assert loaded == {"a|alltime|5_1_2": {"6_2_4": 3.0}}

    def test_directory_sink(self, tmp_path):
        sink = DirectoryBlobSink(str(tmp_path / "blobs"))
        sink.write([("u|alltime|3_1_1", {"8_32_32": 2.0})])
        files = list((tmp_path / "blobs").iterdir())
        assert len(files) == 1
        assert json.loads(files[0].read_text()) == {"8_32_32": 2.0}

    def test_open_sink_specs(self, tmp_path):
        assert isinstance(open_sink("memory:"), MemorySink)
        assert isinstance(open_sink(f"jsonl:{tmp_path}/o.jsonl"), JSONLBlobSink)
        assert isinstance(open_sink(str(tmp_path / "o.jsonl")), JSONLBlobSink)
        assert isinstance(open_sink(f"dir:{tmp_path}/d"), DirectoryBlobSink)

    def test_per_process_sink_spec(self):
        """Sharded multihost egress derives distinct per-host paths for
        path-backed sinks and passes through process-local / upsert
        sinks unchanged."""
        from heatmap_tpu.io.sinks import per_process_sink_spec as pps

        assert pps("jsonl:/out/h.jsonl", 2) == "jsonl:/out/h.jsonl.p002"
        assert pps("/out/h.jsonl", 7) == "jsonl:/out/h.jsonl.p007"
        assert pps("arrays:/out/cols", 0) == "arrays:/out/cols/host000"
        assert pps("arrays-parquet:/o", 1) == "arrays-parquet:/o/host001"
        assert pps("dir:/out/blobs", 11) == "dir:/out/blobs/host011"
        assert pps("memory:", 3) == "memory:"
        assert pps("cassandra:", 5) == "cassandra:"
        with pytest.raises(ValueError):
            pps("bogus:/x", 0)
        # Derived specs all open.
        for spec in ("jsonl:/tmp/x.jsonl.p002", "dir:/tmp/d/host000"):
            open_sink(spec)

    def test_cassandra_sink_batches_async_inserts(self):
        """C12 egress (reference heatmap.py:149-150,157): statements
        carry (id, json) params against rhom.heatmaps, async futures
        drain every `concurrency` writes and at close."""
        from heatmap_tpu.io.sinks import CassandraBlobSink

        class FakeFuture:
            def __init__(self, log):
                self.log = log
                self.resolved = False

            def result(self):
                self.resolved = True
                self.log.append("drain")

        class FakeSession:
            def __init__(self):
                self.calls = []
                self.log = []

            def execute_async(self, cql, params):
                self.calls.append((cql, params))
                self.log.append("insert")
                return FakeFuture(self.log)

        session = FakeSession()
        with CassandraBlobSink(session=session, concurrency=2) as sink:
            sink.write([
                ("u1|alltime|3_1_1", {"8_32_32": 2.0}),
                ("u2|alltime|3_1_2", {"8_33_32": 1.0}),
                ("u3|alltime|3_1_3", {"8_34_32": 4.0}),
            ])
        assert len(session.calls) == 3
        cql, params = session.calls[0]
        assert "INSERT INTO rhom.heatmaps" in cql
        assert params[0] == "u1|alltime|3_1_1"
        assert json.loads(params[1]) == {"8_32_32": 2.0}
        # Futures 1-2 drained at the concurrency threshold (after the
        # 2nd insert), the 3rd at close — nothing left pending.
        assert session.log == ["insert", "insert", "drain", "drain",
                               "insert", "drain"]
        assert sink._pending == []

    def test_cassandra_sink_without_session_raises(self):
        from heatmap_tpu.io.sinks import CassandraBlobSink

        with pytest.raises(RuntimeError, match="session"):
            CassandraBlobSink().write_one("id", {"t": 1.0})

    def test_cassandra_sink_custom_table_and_keyspace(self):
        from heatmap_tpu.io.sinks import CassandraBlobSink

        class FakeSession:
            def __init__(self):
                self.calls = []

            def execute_async(self, cql, params):
                self.calls.append(cql)

                class _F:
                    def result(self):
                        pass

                return _F()

        session = FakeSession()
        sink = CassandraBlobSink(session=session, keyspace="ks", table="hm")
        sink.write_one("a|b|1_0_0", {"2_0_0": 1.0})
        sink.close()
        assert "INSERT INTO ks.hm " in session.calls[0]


class TestPNG:
    def test_png_decodes_via_pil(self):
        PIL = pytest.importorskip("PIL.Image")
        import io as _io

        raster = np.zeros((16, 16), np.int32)
        raster[3, 4] = 10
        raster[8, 8] = 100
        data = png_bytes(colorize(raster))
        img = PIL.open(_io.BytesIO(data))
        arr = np.asarray(img)
        assert arr.shape == (16, 16, 4)
        assert arr[3, 4, 3] == 255  # occupied -> opaque
        assert arr[0, 0, 3] == 0  # empty -> transparent
        # hotter cell is brighter
        assert int(arr[8, 8, :3].sum()) > int(arr[3, 4, :3].sum())

    def test_png_grayscale_and_rgb_shapes(self):
        assert png_bytes(np.zeros((4, 4), np.uint8))[:4] == b"\x89PNG"
        assert png_bytes(np.zeros((4, 4, 3), np.uint8))[:4] == b"\x89PNG"
        with pytest.raises(ValueError):
            png_bytes(np.zeros((4, 4), np.float32))

    def test_tile_sink_writes_zxy_tree(self, tmp_path):
        window = Window(zoom=10, row0=256, col0=512, height=8, width=8)
        raster = np.zeros((8, 8), np.int32)
        raster[1, 2] = 5
        sink = PNGTileSink(str(tmp_path / "tiles"), pixel_delta=2)  # 4px tiles
        n = sink.write_window(raster, window)
        assert n == 1
        # tile zoom 8; x = col0/4 = 128, y = row0/4 + 0 = 64
        assert (tmp_path / "tiles" / "8" / "128" / "64.png").exists()


class TestRunJob:
    def test_run_job_matches_run_batch(self, tmp_path):
        src = SyntheticSource(n=500, seed=3)
        sink = MemorySink()
        cfg = BatchJobConfig(detail_zoom=12, min_detail_zoom=5)
        blobs = run_job(src, sink, cfg, batch_size=128)
        rows = list(SyntheticSource(n=500, seed=3).rows())
        expected = run_batch(rows, cfg, as_json=True)
        assert blobs == expected
        assert sink.blobs == expected
        assert len(blobs) > 0

    def test_run_job_filters_background(self):
        src = SyntheticSource(n=300, seed=1, background_frac=1.0)
        assert run_job(src, None, BatchJobConfig(detail_zoom=10)) == {}


class TestLevelArraysSinkCompat:
    def test_load_reads_pre_dictionary_npz(self, tmp_path):
        """Files written before dictionary encoding (plain user/timespan
        string columns, no *_names tables) must still load."""
        from heatmap_tpu.io.sinks import LevelArraysSink

        d = tmp_path / "old"
        d.mkdir()
        cols = {
            "row": np.array([1, 2], np.int64),
            "col": np.array([3, 4], np.int64),
            "value": np.array([1.0, 2.0]),
            "user": np.array(["alice", "all"]),
            "timespan": np.array(["alltime", "alltime"]),
            "coarse_row": np.array([0, 0], np.int64),
            "coarse_col": np.array([0, 0], np.int64),
            "zoom": np.asarray(9),
            "coarse_zoom": np.asarray(4),
        }
        with open(d / "level_z09.npz", "wb") as f:
            np.savez(f, **cols)
        out = LevelArraysSink.load(str(d))
        assert list(out) == [9]
        np.testing.assert_array_equal(out[9]["user"], cols["user"])
        np.testing.assert_array_equal(out[9]["timespan"], cols["timespan"])

    def test_load_reads_pre_dictionary_parquet(self, tmp_path):
        import pyarrow as pa
        import pyarrow.parquet as pq

        from heatmap_tpu.io.sinks import LevelArraysSink

        d = tmp_path / "oldpq"
        d.mkdir()
        t = pa.table({
            "row": np.array([1], np.int64),
            "col": np.array([2], np.int64),
            "value": np.array([3.0]),
            "user": ["alice"],          # plain string, not dictionary
            "timespan": ["alltime"],
            "coarse_row": np.array([0], np.int64),
            "coarse_col": np.array([0], np.int64),
            "zoom": np.array([7], np.int64),
            "coarse_zoom": np.array([2], np.int64),
        })
        pq.write_table(t, str(d / "level_z07.parquet"))
        out = LevelArraysSink.load(str(d))
        assert out[7]["user"][0] == "alice"
        assert out[7]["timespan"][0] == "alltime"
        assert int(out[7]["coarse_zoom"]) == 2

    def test_npz_compressed_format_roundtrips(self, tmp_path):
        from heatmap_tpu.io.sinks import LevelArraysSink

        src = SyntheticSource(n=800, seed=3)
        cfg = BatchJobConfig(detail_zoom=9, min_detail_zoom=7)
        run_job(src, LevelArraysSink(str(tmp_path / "a")), config=cfg)
        run_job(src, LevelArraysSink(str(tmp_path / "b"),
                                     format="npz-compressed"), config=cfg)
        a = LevelArraysSink.load(str(tmp_path / "a"))
        b = LevelArraysSink.load(str(tmp_path / "b"))
        assert a.keys() == b.keys()
        for z in a:
            assert a[z].keys() == b[z].keys()
            for k in a[z]:
                np.testing.assert_array_equal(a[z][k], b[z][k])


# -- shard merging (heatmap_tpu.io.merge + CLI merge) ----------------------


class TestMergeShards:
    def _job_blobs(self, tmp_path, n=1500, seed=4):
        from heatmap_tpu.io.sources import SyntheticSource
        from heatmap_tpu.pipeline import BatchJobConfig, run_job

        cfg = BatchJobConfig(detail_zoom=10, min_detail_zoom=7)
        return run_job(SyntheticSource(n=n, seed=seed), config=cfg)

    def test_blob_merge_equals_unsharded(self, tmp_path):
        """Splitting a job's blobs across two jsonl shards and merging
        reproduces the full dict exactly."""
        import json as _json

        from heatmap_tpu.io.merge import merge_blob_files
        from heatmap_tpu.io.sinks import JSONLBlobSink

        blobs = self._job_blobs(tmp_path)
        items = sorted(blobs.items())
        a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
        with JSONLBlobSink(str(a)) as s:
            s.write(items[::2])
        with JSONLBlobSink(str(b)) as s:
            s.write(items[1::2])
        merged = merge_blob_files([str(a), str(b)])
        assert merged.keys() == blobs.keys()
        for key in blobs:
            assert merged[key] == _json.loads(blobs[key]), key

    def test_blob_merge_sums_collisions(self, tmp_path):
        """The same shard merged twice doubles every value — upsert-sum
        semantics, matching the cross-host merge."""
        import json as _json

        from heatmap_tpu.io.merge import merge_blob_files
        from heatmap_tpu.io.sinks import JSONLBlobSink

        blobs = self._job_blobs(tmp_path, n=400)
        p = tmp_path / "x.jsonl"
        with JSONLBlobSink(str(p)) as s:
            s.write(sorted(blobs.items()))
        merged = merge_blob_files([str(p), str(p)])
        for key in blobs:
            want = {k: 2 * v for k, v in _json.loads(blobs[key]).items()}
            assert merged[key] == want, key

    def test_blob_merge_rejects_non_summable(self, tmp_path):
        import json as _json

        p1, p2 = tmp_path / "1.jsonl", tmp_path / "2.jsonl"
        p1.write_text(_json.dumps(
            {"id": "a|alltime|3_1_2", "heatmap": '{"8_1_2": "oops"}'}
        ) + "\n")
        p2.write_text(_json.dumps(
            {"id": "a|alltime|3_1_2", "heatmap": '{"8_1_2": 2.0}'}
        ) + "\n")
        from heatmap_tpu.io.merge import merge_blob_files

        with pytest.raises((TypeError, ValueError)):
            merge_blob_files([str(p1), str(p2)])

    def test_level_dirs_merge_equals_unsharded(self, tmp_path):
        """Two per-host columnar shards (from a real sharded-egress
        partition) merge back to the unsharded job's level arrays."""
        from heatmap_tpu.io.merge import merge_level_dirs
        from heatmap_tpu.io.sinks import LevelArraysSink
        from heatmap_tpu.io.sources import SyntheticSource
        from heatmap_tpu.parallel.multihost import partition_levels
        from heatmap_tpu.pipeline import BatchJobConfig, run_job

        cfg = BatchJobConfig(detail_zoom=10, min_detail_zoom=7)
        ref_dir = tmp_path / "ref"
        run_job(SyntheticSource(n=1500, seed=4),
                LevelArraysSink(str(ref_dir)), config=cfg)
        want = LevelArraysSink.load(str(ref_dir))

        # Partition the finalized levels like sharded egress does and
        # write each part through its own per-host sink dir.
        ref_levels = []

        class _Cap:
            def write_levels(self, levels):
                ref_levels.extend(levels)
                return 0

        run_job(SyntheticSource(n=1500, seed=4), _Cap(), config=cfg)
        parts = partition_levels(ref_levels, 2)
        shard_dirs = []
        for i, part in enumerate(parts):
            d = tmp_path / f"host{i}"
            LevelArraysSink(str(d)).write_levels(part)
            shard_dirs.append(str(d))

        merged_dir = tmp_path / "merged"
        LevelArraysSink(str(merged_dir)).write_levels(
            merge_level_dirs(shard_dirs)
        )
        got = LevelArraysSink.load(str(merged_dir))
        assert got.keys() == want.keys()
        for z, wlvl in want.items():
            glvl = got[z]
            ow = np.lexsort((wlvl["col"], wlvl["row"], wlvl["user"],
                             wlvl["timespan"]))
            og = np.lexsort((glvl["col"], glvl["row"], glvl["user"],
                             glvl["timespan"]))
            for k in ("row", "col", "value", "user", "timespan",
                      "coarse_row", "coarse_col"):
                np.testing.assert_array_equal(
                    np.asarray(glvl[k])[og], np.asarray(wlvl[k])[ow],
                    err_msg=f"z{z} {k}",
                )

    @pytest.mark.slow
    def test_cli_merge_blobs(self, tmp_path):
        import json as _json
        import os
        import subprocess
        import sys

        blobs = self._job_blobs(tmp_path, n=400)
        items = sorted(blobs.items())
        from heatmap_tpu.io.sinks import JSONLBlobSink

        a, b = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
        with JSONLBlobSink(str(a)) as s:
            s.write(items[::2])
        with JSONLBlobSink(str(b)) as s:
            s.write(items[1::2])
        out = tmp_path / "merged.jsonl"
        r = subprocess.run(
            [sys.executable, "-m", "heatmap_tpu", "merge",
             "--inputs", str(a), str(b), "--output", f"jsonl:{out}"],
            capture_output=True, text=True,
            cwd=os.path.dirname(os.path.dirname(__file__)),
        )
        assert r.returncode == 0, r.stderr[-800:]
        stats = _json.loads(r.stdout.strip().splitlines()[-1])
        assert stats["mode"] == "blobs" and stats["blobs"] == len(blobs)
        loaded = JSONLBlobSink.load(str(out))
        assert loaded.keys() == blobs.keys()
        for key in blobs:
            assert loaded[key] == _json.loads(blobs[key]), key

    def test_cli_merge_rejects_mixed_inputs(self, tmp_path):
        import os
        import subprocess
        import sys

        f = tmp_path / "a.jsonl"
        f.write_text("")
        d = tmp_path / "dir"
        d.mkdir()
        r = subprocess.run(
            [sys.executable, "-m", "heatmap_tpu", "merge",
             "--inputs", str(f), str(d), "--output", "memory:"],
            capture_output=True, text=True,
            cwd=os.path.dirname(os.path.dirname(__file__)),
        )
        assert r.returncode != 0
        assert "all one kind" in r.stderr or "not a mix" in r.stderr


    def test_cli_merge_rejects_mismatched_output_kind(self, tmp_path):
        import os
        import subprocess
        import sys

        d1, d2 = tmp_path / "h0", tmp_path / "h1"
        d1.mkdir(); d2.mkdir()
        repo = os.path.dirname(os.path.dirname(__file__))
        r = subprocess.run(
            [sys.executable, "-m", "heatmap_tpu", "merge",
             "--inputs", str(d1), str(d2), "--output", "jsonl:x.jsonl"],
            capture_output=True, text=True, cwd=repo,
        )
        assert r.returncode != 0 and "arrays:DIR" in r.stderr
        f1, f2 = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
        f1.write_text(""); f2.write_text("")
        r = subprocess.run(
            [sys.executable, "-m", "heatmap_tpu", "merge",
             "--inputs", str(f1), str(f2), "--output", "arrays:out"],
            capture_output=True, text=True, cwd=repo,
        )
        assert r.returncode != 0 and "columnar-only" in r.stderr

    def test_merge_module_initializes_no_backend(self):
        """Merging must never initialize a jax backend — the
        offline-merge contract in io/merge.py's docstring."""
        import subprocess
        import sys

        code = (
            "import heatmap_tpu.io.merge as m\n"
            "print(sorted(m.merge_blob_parts([{'a': {'t': 1}},"
            " {'a': {'t': 2}}])['a'].items()))\n"
            "from jax._src import xla_bridge\n"
            "print('backends_initialized', bool(xla_bridge._backends))\n"
        )
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True)
        assert r.returncode == 0, r.stderr[-800:]
        assert "('t', 3)" in r.stdout
        # Private-API probe: if the attribute moves, the line above
        # fails the subprocess and this assert reports it loudly.
        assert "backends_initialized False" in r.stdout, r.stdout

    @pytest.mark.slow
    def test_cli_merge_level_dirs(self, tmp_path):
        import json as _json
        import os
        import subprocess
        import sys

        from heatmap_tpu.io.sinks import LevelArraysSink
        from heatmap_tpu.io.sources import SyntheticSource
        from heatmap_tpu.pipeline import BatchJobConfig, run_job

        cfg = BatchJobConfig(detail_zoom=9, min_detail_zoom=7)
        ref = tmp_path / "ref"
        run_job(SyntheticSource(n=600, seed=8), LevelArraysSink(str(ref)),
                config=cfg)
        want = LevelArraysSink.load(str(ref))
        # Two "shards": the same dir twice — the merge must double
        # every value (upsert-sum semantics, easy to assert exactly).
        out = tmp_path / "merged"
        r = subprocess.run(
            [sys.executable, "-m", "heatmap_tpu", "merge",
             "--inputs", str(ref), str(ref),
             "--output", f"arrays:{out}"],
            capture_output=True, text=True,
            cwd=os.path.dirname(os.path.dirname(__file__)),
        )
        assert r.returncode == 0, r.stderr[-800:]
        stats = _json.loads(r.stdout.strip().splitlines()[-1])
        assert stats["mode"] == "levels" and stats["levels"] == len(want)
        got = LevelArraysSink.load(str(out))
        assert got.keys() == want.keys()
        for z in want:
            assert np.asarray(got[z]["value"]).sum() == \
                2 * np.asarray(want[z]["value"]).sum(), z

    def test_level_dirs_merge_rejects_mismatched_coarse_zoom(self, tmp_path):
        """Shards that disagree on a level's coarse_zoom are not shards
        of one job — the merge must refuse, not silently mix result
        granularities."""
        from heatmap_tpu.io.merge import merge_level_dirs
        from heatmap_tpu.io.sinks import LevelArraysSink

        def lvl(coarse_zoom):
            return {
                "zoom": 8, "coarse_zoom": coarse_zoom,
                "row": np.asarray([1]), "col": np.asarray([2]),
                "value": np.asarray([1.0]),
                "user_idx": np.asarray([0], np.int32),
                "timespan_idx": np.asarray([0], np.int32),
                "user_names": np.asarray(["all"]),
                "timespan_names": np.asarray(["alltime"]),
                "coarse_row": np.asarray([0]),
                "coarse_col": np.asarray([0]),
            }

        a, b = tmp_path / "a", tmp_path / "b"
        LevelArraysSink(str(a)).write_levels([lvl(3)])
        LevelArraysSink(str(b)).write_levels([lvl(4)])
        with pytest.raises(ValueError, match="coarse_zoom"):
            merge_level_dirs([str(a), str(b)])

    @staticmethod
    def _lvl(rows, cols, values, zoom=8, coarse_zoom=3, user="all"):
        n = len(rows)
        return {
            "zoom": zoom, "coarse_zoom": coarse_zoom,
            "row": np.asarray(rows), "col": np.asarray(cols),
            "value": np.asarray(values, np.float64),
            "user_idx": np.zeros(n, np.int32),
            "timespan_idx": np.zeros(n, np.int32),
            "user_names": np.asarray([user]),
            "timespan_names": np.asarray(["alltime"]),
            "coarse_row": np.zeros(n, np.int64),
            "coarse_col": np.zeros(n, np.int64),
        }

    def test_level_parts_empty_part_is_identity(self):
        """An empty part (a host that ingested nothing) contributes
        nothing — the merge equals merging the non-empty part alone."""
        from heatmap_tpu.io.merge import merge_level_parts

        part = [self._lvl([1, 2], [3, 4], [1.0, 2.0])]
        alone = merge_level_parts([part])
        with_empty = merge_level_parts([part, []])
        assert len(with_empty) == len(alone) == 1
        for key in ("row", "col", "value", "user_idx", "timespan_idx"):
            np.testing.assert_array_equal(with_empty[0][key], alone[0][key])

    def test_level_parts_disjoint_keys_union_unsummed(self):
        """Parts with disjoint (timespan, user, row, col) keys union:
        every row survives with its original value — re-aggregation
        only sums genuine collisions."""
        from heatmap_tpu.io.merge import merge_level_parts

        a = [self._lvl([1], [1], [5.0])]
        b = [self._lvl([2], [2], [7.0])]
        (merged,) = merge_level_parts([a, b])
        np.testing.assert_array_equal(merged["row"], [1, 2])
        np.testing.assert_array_equal(merged["col"], [1, 2])
        np.testing.assert_array_equal(merged["value"], [5.0, 7.0])

    def test_level_dirs_missing_shard_dir_raises(self, tmp_path):
        """A listed-but-absent shard dir is a hard error (a silently
        skipped host would under-count every tile it owned)."""
        from heatmap_tpu.io.merge import merge_level_dirs
        from heatmap_tpu.io.sinks import LevelArraysSink

        a = tmp_path / "host000"
        LevelArraysSink(str(a)).write_levels([self._lvl([1], [1], [1.0])])
        with pytest.raises(FileNotFoundError):
            merge_level_dirs([str(a), str(tmp_path / "host001")])

    def test_level_dirs_empty_shard_dir_contributes_nothing(self, tmp_path):
        """An existing-but-empty shard dir (host wrote no levels) is a
        valid empty contribution, not an error."""
        from heatmap_tpu.io.merge import merge_level_dirs
        from heatmap_tpu.io.sinks import LevelArraysSink

        a, b = tmp_path / "host000", tmp_path / "host001"
        LevelArraysSink(str(a)).write_levels([self._lvl([1], [2], [3.0])])
        b.mkdir()
        merged = merge_level_dirs([str(a), str(b)])
        (alone,) = merge_level_dirs([str(a)])
        assert len(merged) == 1
        np.testing.assert_array_equal(merged[0]["value"], alone["value"])
        np.testing.assert_array_equal(merged[0]["row"], alone["row"])
