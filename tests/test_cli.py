"""CLI tests: flag parsing in-process, end-to-end runs via subprocess.

The subprocess runs use ``--backend cpu`` (the CLI's own platform
switch — the flag system under test) rather than the conftest's
config, since they are fresh interpreters.
"""

import json
import os
import subprocess
import sys

import pytest

from heatmap_tpu.cli import build_parser

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_cli(*argv, timeout=240):
    env = dict(os.environ, PYTHONPATH=REPO)
    return subprocess.run(
        [sys.executable, "-m", "heatmap_tpu", *argv],
        capture_output=True,
        text=True,
        timeout=timeout,
        cwd=REPO,
        env=env,
    )


class TestParser:
    def test_run_defaults_match_reference_constants(self):
        args = build_parser().parse_args(["run", "--input", "synthetic:10"])
        # reference heatmap.py:16-17: DETAIL_ZOOM_DELTA=5, MAX_ZOOM_LEVEL=16
        assert args.detail_zoom == 21
        assert args.min_detail_zoom == 5
        assert args.result_delta == 5
        assert args.timespans == "alltime"
        assert args.backend == "tpu"

    def test_backend_choices(self):
        with pytest.raises(SystemExit):
            build_parser().parse_args(["run", "--input", "x", "--backend", "gpu"])

    def test_subcommand_required(self):
        with pytest.raises(SystemExit):
            build_parser().parse_args([])

    def test_bad_timespan_rejected_before_ingest(self):
        from heatmap_tpu.cli import cmd_run

        args = build_parser().parse_args(
            ["run", "--input", "synthetic:10", "--timespans", "dayly"]
        )
        with pytest.raises(SystemExit, match="dayly"):
            cmd_run(args)

    def test_tiles_zoom_below_pixel_delta_rejected(self):
        from heatmap_tpu.cli import cmd_tiles

        args = build_parser().parse_args(
            ["tiles", "--input", "synthetic:10", "--zoom", "6"]
        )
        with pytest.raises(SystemExit, match="pixel-delta"):
            cmd_tiles(args)


class TestBackendTpu:
    """``--backend tpu`` (the default) means a TPU; the suite's CPU-only
    host must be refused, never quietly used."""

    @pytest.fixture(autouse=True)
    def _restore_cache_dir(self):
        import jax

        prev = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", prev)

    @pytest.mark.parametrize("argv", [
        ["run", "--input", "synthetic:10", "--output", "memory:"],
        ["tiles", "--input", "synthetic:10"],
        ["info"],
    ])
    def test_refused_in_process(self, argv):
        from heatmap_tpu.cli import main

        with pytest.raises(SystemExit, match="found no TPU"):
            main(argv)

    def test_run_exits_nonzero(self):
        r = _run_cli("run", "--backend", "tpu", "--input", "synthetic:10",
                     "--output", "memory:")
        assert r.returncode != 0
        assert "found no TPU" in r.stderr


class TestCompileCache:
    @pytest.fixture(autouse=True)
    def _restore_cache_dir(self):
        import jax

        prev = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", prev)

    def test_env_var_wins_and_nothing_else_is_set(self, monkeypatch,
                                                  tmp_path):
        import jax

        from heatmap_tpu.utils.compile_cache import enable_compile_cache

        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None

    def test_fixed_checkout_path_when_unset(self, monkeypatch):
        import jax

        from heatmap_tpu.utils.compile_cache import enable_compile_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(REPO, ".jax_cache")
        assert enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want

    def test_cli_backend_init_places_the_cache(self, monkeypatch):
        import jax

        from heatmap_tpu.cli import _init_backend

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        jax.config.update("jax_compilation_cache_dir", None)
        _init_backend(build_parser().parse_args(
            ["info", "--backend", "cpu"]))
        assert (jax.config.jax_compilation_cache_dir
                == os.path.join(REPO, ".jax_cache"))


class TestEndToEnd:
    def test_run_synthetic_to_jsonl(self, tmp_path):
        out = tmp_path / "blobs.jsonl"
        r = _run_cli(
            "run",
            "--backend", "cpu",
            "--input", "synthetic:2000:3",
            "--output", f"jsonl:{out}",
            "--detail-zoom", "12",
        )
        assert r.returncode == 0, r.stderr
        stats = json.loads(r.stdout.strip().splitlines()[-1])
        assert stats["blobs"] > 0
        from heatmap_tpu.io import JSONLBlobSink

        loaded = JSONLBlobSink.load(str(out))
        assert len(loaded) == stats["blobs"]
        assert any(k.startswith("all|alltime|") for k in loaded)

    def test_run_weighted_jsonl_source(self, tmp_path):
        """run --weighted sums the source's value column into blob
        values; composing with --fast fails cleanly."""
        src = tmp_path / "pts.jsonl"
        with open(src, "w") as f:
            for v in (1.25, 2.0):
                f.write(json.dumps({
                    "latitude": 47.6, "longitude": -122.3,
                    "user_id": "alice", "value": v,
                }) + "\n")
        out = tmp_path / "blobs.jsonl"
        r = _run_cli(
            "run", "--backend", "cpu",
            "--input", f"jsonl:{src}", "--output", f"jsonl:{out}",
            "--detail-zoom", "10", "--min-detail-zoom", "4", "--weighted",
        )
        assert r.returncode == 0, r.stderr
        from heatmap_tpu.io import JSONLBlobSink
        from heatmap_tpu.tilemath.tile import Tile

        loaded = JSONLBlobSink.load(str(out))
        detail = Tile.tile_id_from_lat_long(47.6, -122.3, 10)
        alice = next(b if isinstance(b, dict) else json.loads(b)
                     for k, b in loaded.items() if k.startswith("alice|"))
        assert alice[detail] == 3.25
        # --weighted composes with --checkpoint-dir too (values ride
        # the checkpoint); same blobs as the plain weighted run.
        out2 = tmp_path / "blobs_ck.jsonl"
        r2 = _run_cli(
            "run", "--backend", "cpu",
            "--input", f"jsonl:{src}", "--output", f"jsonl:{out2}",
            "--detail-zoom", "10", "--min-detail-zoom", "4", "--weighted",
            "--checkpoint-dir", str(tmp_path / "ck"),
        )
        assert r2.returncode == 0, r2.stderr
        assert out2.read_bytes() == out.read_bytes()

    @pytest.mark.slow
    def test_run_fast_csv_matches_plain(self, tmp_path):
        import csv
        import numpy as np

        from heatmap_tpu import native

        if not native.available():
            pytest.skip("native library not built")
        pts = tmp_path / "pts.csv"
        rng = np.random.default_rng(9)
        with open(pts, "w", newline="") as f:
            w = csv.writer(f)
            w.writerow(["latitude", "longitude", "user_id", "source",
                        "timestamp"])
            for _ in range(1500):
                w.writerow([
                    rng.uniform(40, 50), rng.uniform(-130, -110),
                    ["alice", "x-2", "rt-4"][rng.integers(0, 3)],
                    "background" if rng.random() < 0.1 else "gps", 1,
                ])
        outs = {}
        summaries = {}
        # "plain" needs --no-fast now: eligible CSV sources auto-route
        # to the fast path, and this test exists to pin the two paths'
        # blob equality. "auto" (no flag) must take fast by itself.
        for name, extra in (("plain", ["--no-fast"]), ("fast", ["--fast"]),
                            ("auto", [])):
            out = tmp_path / f"{name}.jsonl"
            r = _run_cli(
                "run", "--backend", "cpu",
                "--input", f"csv:{pts}",
                "--output", f"jsonl:{out}",
                "--detail-zoom", "12", "--min-detail-zoom", "9",
                *extra,
            )
            assert r.returncode == 0, r.stderr
            from heatmap_tpu.io import JSONLBlobSink

            outs[name] = JSONLBlobSink.load(str(out))
            summaries[name] = json.loads(r.stdout.strip().splitlines()[-1])
        assert outs["plain"] == outs["fast"] == outs["auto"]
        assert summaries["plain"]["ingest"] == "standard"
        assert summaries["fast"]["ingest"] == "fast"
        assert summaries["auto"]["ingest"] == "fast"

    def test_run_with_checkpoint_dir_resumes(self, tmp_path):
        out = tmp_path / "blobs.jsonl"
        ck = tmp_path / "ck"
        common = [
            "run", "--backend", "cpu",
            "--input", "synthetic:3000:5",
            "--output", f"jsonl:{out}",
            "--detail-zoom", "12", "--min-detail-zoom", "9",
            "--batch-size", "512",
            "--checkpoint-dir", str(ck), "--checkpoint-every", "2",
        ]
        r = _run_cli(*common)
        assert r.returncode == 0, r.stderr
        assert any(f.startswith("ckpt-") for f in os.listdir(ck))
        # Rerun resumes from checkpoints and reproduces the same blobs.
        from heatmap_tpu.io import JSONLBlobSink

        first = JSONLBlobSink.load(str(out))
        r2 = _run_cli(*common)
        assert r2.returncode == 0, r2.stderr
        assert JSONLBlobSink.load(str(out)) == first

    def test_run_arrays_output_spec(self, tmp_path):
        import json as _json

        out = tmp_path / "cols"
        r = _run_cli(
            "run", "--backend", "cpu",
            "--input", "synthetic:500:2",
            "--output", f"arrays:{out}",
            "--detail-zoom", "10", "--min-detail-zoom", "8",
        )
        assert r.returncode == 0, r.stderr
        summary = _json.loads(r.stdout.strip().splitlines()[-1])
        # detail z10 down to min_detail_zoom+1 = z9: two levels.
        assert summary["rows"] > 0 and summary["levels"] == 2
        assert any(f.name.endswith(".npz") for f in out.iterdir())

    def test_multihost_single_process_falls_through(self, tmp_path):
        import json as _json

        out = tmp_path / "mh.jsonl"
        r = _run_cli(
            "run", "--backend", "cpu", "--multihost",
            "--input", "synthetic:500:2",
            "--output", f"jsonl:{out}",
            "--detail-zoom", "10", "--min-detail-zoom", "8",
        )
        assert r.returncode == 0, r.stderr
        assert _json.loads(r.stdout.strip().splitlines()[-1])["blobs"] > 0
        r = _run_cli("run", "--backend", "cpu", "--multihost", "--fast",
                     "--input", "csv:x.csv")
        assert r.returncode != 0 and "standard job path" in r.stderr

    def test_multihost_bounded_flag_accepted(self, tmp_path):
        """--multihost composes with --max-points-in-flight (and the
        spill knob) since the bounded slice ingest landed; the old
        rejection must stay gone."""
        import json as _json

        out = tmp_path / "mhb.jsonl"
        r = _run_cli(
            "run", "--backend", "cpu", "--multihost",
            "--input", "synthetic:900:2",
            "--output", f"jsonl:{out}",
            "--detail-zoom", "10", "--min-detail-zoom", "8",
            "--max-points-in-flight", "200",
            "--merge-spill-dir", str(tmp_path / "spill"),
        )
        assert r.returncode == 0, r.stderr
        assert _json.loads(r.stdout.strip().splitlines()[-1])["blobs"] > 0

    def test_fast_rejects_non_csv_source(self):
        r = _run_cli("run", "--backend", "cpu", "--fast",
                     "--input", "synthetic:10")
        assert r.returncode != 0
        assert "csv" in r.stderr

    def test_fast_with_checkpoint_dir_matches_fast_alone(self, tmp_path):
        from heatmap_tpu.io import JSONLBlobSink
        from heatmap_tpu.io.hmpb import convert_to_hmpb

        hp = tmp_path / "pts.hmpb"
        convert_to_hmpb("synthetic:2000:3", str(hp))
        outs = {}
        for name, extra in (
            ("plain", []),
            ("ckpt", ["--checkpoint-dir", str(tmp_path / "ck"),
                      "--checkpoint-every", "2"]),
        ):
            out = tmp_path / f"{name}.jsonl"
            r = _run_cli(
                "run", "--backend", "cpu", "--fast",
                "--input", f"hmpb:{hp}",
                "--output", f"jsonl:{out}",
                "--detail-zoom", "11", "--min-detail-zoom", "9",
                "--batch-size", "512",
                *extra,
            )
            assert r.returncode == 0, r.stderr
            outs[name] = JSONLBlobSink.load(str(out))
        assert outs["plain"] == outs["ckpt"]
        # The checkpoint run actually wrote checkpoints.
        assert any((tmp_path / "ck").iterdir())

    @pytest.mark.slow
    def test_hmpb_auto_routes_fast(self, tmp_path):
        """An hmpb input with no flag must take the fast path and match
        the --no-fast standard path blob-for-blob (mirror of the CSV
        auto-routing test; checkpoint runs must stay standard)."""
        from heatmap_tpu.io import JSONLBlobSink
        from heatmap_tpu.io.hmpb import convert_to_hmpb

        hp = tmp_path / "pts.hmpb"
        convert_to_hmpb("synthetic:2000:5", str(hp))
        outs = {}
        ingests = {}
        for name, extra in (
            ("auto", []),
            ("plain", ["--no-fast"]),
            ("ckpt", ["--checkpoint-dir", str(tmp_path / "ck")]),
        ):
            out = tmp_path / f"{name}.jsonl"
            r = _run_cli(
                "run", "--backend", "cpu",
                "--input", f"hmpb:{hp}",
                "--output", f"jsonl:{out}",
                "--detail-zoom", "11", "--min-detail-zoom", "9",
                *extra,
            )
            assert r.returncode == 0, r.stderr
            outs[name] = JSONLBlobSink.load(str(out))
            ingests[name] = json.loads(
                r.stdout.strip().splitlines()[-1])["ingest"]
        assert outs["auto"] == outs["plain"] == outs["ckpt"]
        assert ingests["auto"] == "fast"
        assert ingests["plain"] == "standard"
        # --checkpoint-dir keeps the resumable standard path (format
        # stability for existing checkpoints).
        assert ingests["ckpt"] == "standard"

    def test_stream_synthetic_decay_and_resume(self, tmp_path):
        out = tmp_path / "live"
        ck = tmp_path / "ck"
        common = [
            "stream", "--backend", "cpu",
            "--input", "synthetic:20000:4",
            "--output", str(out),
            "--batch-points", "2048",
            "--interval", "600", "--half-life", "1200",
            "--zoom", "10", "--pixel-delta", "6",
            "--lat-min", "46", "--lat-max", "49",
            "--lon-min", "-124", "--lon-max", "-120",
            "--checkpoint-dir", str(ck), "--checkpoint-every", "3",
        ]
        r = _run_cli(*common)
        assert r.returncode == 0, r.stderr
        stats = json.loads(r.stdout.strip().splitlines()[-1])
        assert stats["batches"] >= 9
        assert stats["tiles"] > 0
        # Decay: live mass is well under the raw point count.
        assert 0 < stats["live_mass"] < 20000
        assert any(f.startswith("ckpt-") for f in os.listdir(ck))
        # Rerun: resumes from the final checkpoint, consumes nothing new,
        # and reproduces the same live mass.
        r2 = _run_cli(*common)
        assert r2.returncode == 0, r2.stderr
        stats2 = json.loads(r2.stdout.strip().splitlines()[-1])
        assert stats2["batches"] == stats["batches"]
        assert stats2["live_mass"] == pytest.approx(stats["live_mass"])

    @pytest.mark.slow
    def test_stream_bin_backend_flag(self, tmp_path):
        """--bin-backend pins the update step's binning kernel; xla and
        the auto route must produce identical live mass (same points,
        bit-exact count kernels either way)."""
        masses = {}
        for be in ("auto", "xla"):
            r = _run_cli(
                "stream", "--backend", "cpu",
                "--input", "synthetic:8000:4",
                "--output", "",
                "--batch-points", "2048",
                "--interval", "600", "--half-life", "1200",
                "--zoom", "10", "--pixel-delta", "6",
                "--lat-min", "46", "--lat-max", "49",
                "--lon-min", "-124", "--lon-max", "-120",
                "--bin-backend", be,
            )
            assert r.returncode == 0, r.stderr
            masses[be] = json.loads(
                r.stdout.strip().splitlines()[-1]
            )["live_mass"]
        assert masses["auto"] == pytest.approx(masses["xla"])

    def test_tiles_synthetic_to_png_tree(self, tmp_path):
        out = tmp_path / "tiles"
        r = _run_cli(
            "tiles",
            "--backend", "cpu",
            "--input", "synthetic:5000:1",
            "--output", str(out),
            "--zoom", "12",
            "--pixel-delta", "6",
        )
        assert r.returncode == 0, r.stderr
        stats = json.loads(r.stdout.strip().splitlines()[-1])
        assert stats["tiles"] >= 1
        assert stats["tile_zoom"] == 6
        pngs = [f for _, _, fs in os.walk(out) for f in fs]
        assert len(pngs) == stats["tiles"]

    @pytest.mark.slow
    def test_tiles_weighted_csv(self, tmp_path):
        """--weighted sums the input's 'value' column (BASELINE config
        3): non-uniform weights change the rendered pixels, uniform
        weights of 1.0 reproduce counting byte-for-byte, and a missing
        value column fails cleanly."""

        def render(csv_path, subdir, *extra):
            out = tmp_path / subdir
            r = _run_cli(
                "tiles", "--backend", "cpu",
                "--input", f"csv:{csv_path}", "--output", str(out),
                "--zoom", "12", "--pixel-delta", "6",
                "--lat-min", "47.0", "--lat-max", "48.5",
                "--lon-min", "-123.0", "--lon-max", "-121.5", *extra,
            )
            assert r.returncode == 0, r.stderr
            assert json.loads(r.stdout.strip().splitlines()[-1])["tiles"] >= 1
            return {
                os.path.relpath(os.path.join(d, f), out):
                    open(os.path.join(d, f), "rb").read()
                for d, _, fs in os.walk(out) for f in fs
            }

        def write_csv(path, value_expr):
            with open(path, "w") as f:
                f.write("latitude,longitude,user_id,source,timestamp,value\n")
                for i in range(50):
                    f.write(f"47.{600 + i},-122.{300 + i},u,gps,1,"
                            f"{value_expr(i)}\n")

        p = tmp_path / "w.csv"
        write_csv(p, lambda i: 1.0 + 10.0 * (i % 7))  # non-uniform
        weighted = render(p, "tw", "--weighted")
        counted = render(p, "tc")
        assert weighted.keys() == counted.keys()
        # Non-uniform weights must actually change at least one pixel.
        assert weighted != counted
        # Uniform weights of 1.0 == counting, byte-for-byte.
        p1 = tmp_path / "w1.csv"
        write_csv(p1, lambda i: 1.0)
        assert render(p1, "t1w", "--weighted") == render(p1, "t1c")
        # No value column -> clean error, not a stack trace.
        p2 = tmp_path / "nw.csv"
        with open(p2, "w") as f:
            f.write("latitude,longitude,user_id,source,timestamp\n")
            f.write("47.6,-122.3,u,gps,1\n")
        r2 = _run_cli(
            "tiles", "--backend", "cpu",
            "--input", f"csv:{p2}", "--output", str(tmp_path / "t2"),
            "--zoom", "12", "--pixel-delta", "6", "--weighted",
        )
        assert r2.returncode != 0
        assert "value" in r2.stderr

    @pytest.mark.slow
    def test_run_cascade_backend_flag(self, tmp_path):
        """--cascade-backend partitioned produces byte-identical blobs
        to the default scatter backend, and the unbounded-weighted rejection
        proves the flag actually reaches the config (byte-equality
        alone would pass even if the plumbing silently dropped it)."""
        outs = {}
        for be in ("scatter", "partitioned"):
            out = tmp_path / f"{be}.jsonl"
            r = _run_cli(
                "run", "--backend", "cpu",
                "--input", "synthetic:4000:6",
                "--output", f"jsonl:{out}",
                "--detail-zoom", "11", "--min-detail-zoom", "5",
                "--cascade-backend", be,
            )
            assert r.returncode == 0, r.stderr
            outs[be] = out.read_bytes()
        assert outs["scatter"] == outs["partitioned"]
        # The flag must reach BatchJobConfig: weighted+partitioned is
        # rejected at config time, before any ingest, cleanly.
        r2 = _run_cli(
            "run", "--backend", "cpu",
            "--input", "synthetic:10", "--output", "memory:",
            "--cascade-backend", "partitioned", "--weighted",
        )
        assert r2.returncode != 0
        assert "bounded-integer" in r2.stderr
        assert "Traceback" not in r2.stderr

    @pytest.mark.slow
    def test_run_data_parallel_flag(self, tmp_path):
        """--data-parallel on/off produce byte-identical blobs, and the
        rejection of --dp-min-emissions with an explicit mode proves
        both flags reach BatchJobConfig (byte-equality alone would pass
        if the plumbing silently dropped them)."""
        outs = {}
        for dp in ("on", "off", "auto"):
            out = tmp_path / f"dp_{dp}.jsonl"
            r = _run_cli(
                "run", "--backend", "cpu",
                "--input", "synthetic:4000:6",
                "--output", f"jsonl:{out}",
                "--detail-zoom", "11", "--min-detail-zoom", "5",
                "--data-parallel", dp,
            )
            assert r.returncode == 0, r.stderr
            outs[dp] = out.read_bytes()
        assert outs["on"] == outs["off"] == outs["auto"]
        r2 = _run_cli(
            "run", "--backend", "cpu",
            "--input", "synthetic:10", "--output", "memory:",
            "--data-parallel", "on", "--dp-min-emissions", "1000",
        )
        assert r2.returncode != 0
        assert "AUTO" in r2.stderr
        assert "Traceback" not in r2.stderr

    def test_info_reports_platform(self):
        r = _run_cli("info", "--backend", "cpu")
        assert r.returncode == 0, r.stderr
        info = json.loads(r.stdout.strip())
        assert info["platform"] == "cpu"
        assert info["x64"] is True


class TestRender:
    @pytest.mark.slow
    def test_render_from_arrays_and_jsonl(self, tmp_path):
        """Stored heatmaps -> PNG tiles from both storage kinds; the
        arrays and jsonl inputs must paint the same tile set for the
        same job."""
        import glob
        import json as _json

        lv = tmp_path / "lv"
        bl = tmp_path / "b.jsonl"
        for out in (f"arrays:{lv}", f"jsonl:{bl}"):
            r = _run_cli(
                "run", "--backend", "cpu",
                "--input", "synthetic:3000:5",
                "--output", out,
                "--detail-zoom", "12", "--min-detail-zoom", "8",
            )
            assert r.returncode == 0, r.stderr
        outs = {}
        for name, spec in (("arrays", f"arrays:{lv}"), ("jsonl", f"jsonl:{bl}")):
            td = tmp_path / f"tiles-{name}"
            r = _run_cli(
                "render", "--input", spec, "--zoom", "10",
                "--pixel-delta", "6", "--output", str(td),
            )
            assert r.returncode == 0, r.stderr
            stats = _json.loads(r.stdout.strip().splitlines()[-1])
            assert stats["tiles"] >= 1 and stats["zoom"] == 10
            outs[name] = sorted(
                p.relative_to(td).as_posix()
                for p in td.rglob("*.png")
            )
        assert outs["arrays"] == outs["jsonl"]

    def test_render_missing_zoom_fails_loudly(self, tmp_path):
        lv = tmp_path / "lv"
        r = _run_cli(
            "run", "--backend", "cpu", "--input", "synthetic:500:1",
            "--output", f"arrays:{lv}",
            "--detail-zoom", "10", "--min-detail-zoom", "8",
        )
        assert r.returncode == 0, r.stderr
        r = _run_cli("render", "--input", f"arrays:{lv}", "--zoom", "3",
                     "--output", str(tmp_path / "t"))
        assert r.returncode != 0
        assert "available" in r.stderr

    def test_render_jsonl_missing_zoom_fails_loudly(self, tmp_path):
        bl = tmp_path / "b.jsonl"
        r = _run_cli(
            "run", "--backend", "cpu", "--input", "synthetic:500:1",
            "--output", f"jsonl:{bl}",
            "--detail-zoom", "10", "--min-detail-zoom", "8",
        )
        assert r.returncode == 0, r.stderr
        r = _run_cli("render", "--input", f"jsonl:{bl}", "--zoom", "3",
                     "--output", str(tmp_path / "t"))
        assert r.returncode != 0
        assert "available" in r.stderr


class TestAutoBounds:
    def test_tiles_auto_bounds_finds_distant_data(self, tmp_path):
        """Data outside the default PNW window: the fixed flags miss it
        entirely; --auto-bounds derives the window from the data."""
        import json as _json

        p = tmp_path / "tokyo.csv"
        rows = ["latitude,longitude,user_id,source,timestamp"]
        rows += [f"{35.68 + i * 1e-4},{139.69 + i * 1e-4},u,gps,{i}"
                 for i in range(200)]
        p.write_text("\n".join(rows) + "\n")
        r0 = _run_cli("tiles", "--backend", "cpu", "--input", str(p),
                      "--zoom", "12", "--pixel-delta", "6",
                      "--output", str(tmp_path / "t0"))
        assert r0.returncode == 0, r0.stderr
        assert _json.loads(r0.stdout.strip().splitlines()[-1])["tiles"] == 0
        r1 = _run_cli("tiles", "--backend", "cpu", "--input", str(p),
                      "--zoom", "12", "--pixel-delta", "6", "--auto-bounds",
                      "--output", str(tmp_path / "t1"))
        assert r1.returncode == 0, r1.stderr
        stats = _json.loads(r1.stdout.strip().splitlines()[-1])
        assert stats["tiles"] >= 1
        lat_min, lat_max, lon_min, lon_max = stats["bounds"]
        assert lat_min < 35.68 < lat_max and lon_min < 139.69 < lon_max

    def test_stream_weighted_csv(self, tmp_path):
        """stream --weighted decays weighted mass: uniform value 5.0
        yields exactly 5x the counted live mass on the same input."""
        p = tmp_path / "w.csv"
        with open(p, "w") as f:
            f.write("latitude,longitude,user_id,source,timestamp,value\n")
            for i in range(4000):
                f.write(f"47.{600 + i % 300},-122.{300 + i % 300},u,gps,1,5\n")
        common = [
            "stream", "--backend", "cpu",
            "--input", f"csv:{p}",
            "--batch-points", "1000",
            "--interval", "600", "--half-life", "1200",
            "--zoom", "10", "--pixel-delta", "6",
            "--lat-min", "46", "--lat-max", "49",
            "--lon-min", "-124", "--lon-max", "-120",
        ]
        rw = _run_cli(*common, "--weighted")
        rc = _run_cli(*common)
        assert rw.returncode == 0, rw.stderr
        assert rc.returncode == 0, rc.stderr
        mw = json.loads(rw.stdout.strip().splitlines()[-1])["live_mass"]
        mc = json.loads(rc.stdout.strip().splitlines()[-1])["live_mass"]
        assert mw == pytest.approx(5.0 * mc, rel=1e-6)
        assert mc > 0

    def test_stream_auto_bounds(self, tmp_path):
        import json as _json

        p = tmp_path / "sydney.csv"
        rows = ["latitude,longitude,user_id,source,timestamp"]
        rows += [f"{-33.86 + i * 1e-4},{151.20 + i * 1e-4},u,gps,{i}"
                 for i in range(300)]
        p.write_text("\n".join(rows) + "\n")
        r = _run_cli("stream", "--backend", "cpu", "--input", str(p),
                     "--zoom", "10", "--pixel-delta", "6", "--auto-bounds",
                     "--batch-points", "128",
                     "--output", str(tmp_path / "t"))
        assert r.returncode == 0, r.stderr
        stats = _json.loads(r.stdout.strip().splitlines()[-1])
        assert stats["tiles"] >= 1 and stats["live_mass"] > 0

    def test_render_from_parquet_arrays(self, tmp_path):
        import json as _json

        lv = tmp_path / "lvpq"
        r = _run_cli(
            "run", "--backend", "cpu", "--input", "synthetic:1200:2",
            "--output", f"arrays-parquet:{lv}",
            "--detail-zoom", "11", "--min-detail-zoom", "8",
        )
        assert r.returncode == 0, r.stderr
        r = _run_cli("render", "--input", f"arrays-parquet:{lv}",
                     "--zoom", "9", "--pixel-delta", "6",
                     "--output", str(tmp_path / "t"))
        assert r.returncode == 0, r.stderr
        assert _json.loads(r.stdout.strip().splitlines()[-1])["tiles"] >= 1
