"""Telemetry subsystem tests: metrics registry, event log, run report,
CLI wiring, and the no-raw-instrumentation guard.

The smoke tests drive ``cli.cmd_run`` in-process (conftest already
pins the cpu backend + x64); blob byte-equality with telemetry on vs
off is the acceptance bar — telemetry must be purely observational.
"""

import json
import os
import re
import threading

import pytest

from heatmap_tpu import obs
from heatmap_tpu.obs.metrics import MetricsRegistry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Minimal valid payload per event type (keep in sync with EVENT_SCHEMA —
# the round-trip test emits each one).
_PAYLOADS = {
    "run_start": {"config": {"detail_zoom": 12}, "backend": "cpu",
                  "devices": {"platform": "cpu", "n_devices": 8}},
    "stage_end": {"stage": "cascade.device", "wall_s": 0.5,
                  "items": 100, "backend": "scatter"},
    "backend_resolved": {"requested": "auto", "resolved": "scatter",
                         "reason": "non-tpu platform -> xla scatter"},
    "cascade_dispatch": {"backend": "scatter", "jit": True,
                         "n_emissions": 10},
    "pallas_kernel": {"kernel": "segment", "interpret": False, "n": 2048},
    "partition_planned": {"n_shards": 4, "splits": [12, 90, 400],
                          "sampled_points": 4096, "balance_factor": 1.25,
                          "max_shard_mass": 0.27, "mean_shard_mass": 0.25,
                          "skew_ratio": 1.08, "resplits": 0,
                          "degenerate": False, "fingerprint": "sha256:00",
                          "boundary_tiles": 6},
    "mesh_route": {"lanes": [40, 38, 41, 39], "remote_lanes": 117,
                   "pair_capacity": 4096, "recv_capacity": 4096,
                   "overflow_lanes": 0},
    "device_memory": {"samples": []},
    "retry": {"shard": 3, "attempt": 1, "error": "RuntimeError('x')"},
    "recovery": {"shard": 3, "attempts": 2},
    "heartbeat": {"process_index": 0, "process_count": 1,
                  "phase": "ingest_done", "uptime_s": 1.5},
    "profiler_unavailable": {"error": "RuntimeError('no profiler')"},
    "http_request": {"route": "tiles", "status": 200,
                     "path": "/tiles/default/7/20/44.json", "ms": 1.2,
                     "bytes": 512, "cache": "hit"},
    "store_reload": {"old_generation": 0, "generation": 1, "levels": 5,
                     "seconds": 0.1, "spec": "delta:store/", "layers": 3,
                     "initial": False},
    "delta_applied": {"epoch": 2, "points": 300, "sign": 1,
                      "seconds": 0.8, "content_hash": "sha256:00",
                      "artifact": "delta-000002", "rows": 120,
                      "duplicate": False, "watermark": 1.7e12,
                      "keys_invalidated": 42},
    "ingest_tick": {"tick": 7, "points": 300, "seconds": 0.12,
                    "epoch": 8, "duplicate": False, "watermark": 1.5e9,
                    "lag_s": 0.34, "queue_depth": 2,
                    "keys_invalidated": 17, "compacted": False},
    "compaction_start": {"root": "store/", "deltas": 3,
                         "base": "base-000001"},
    "compaction_end": {"root": "store/", "seconds": 0.4, "status": "ok",
                       "base": "base-000004", "levels": 5, "rows": 2048,
                       "pruned_entries": 2, "buckets": 4},
    "retraction_applied": {"root": "store/", "rows": 40, "batches": 2,
                           "scanned": 80, "where": {"user_id": "alice"},
                           "epochs": [3, 4], "seconds": 1.2},
    "temporal_served": {"layer": "default", "zoom": 2, "mode": "as_of",
                        "as_of": "1250", "cache": "hit", "ms": 0.8},
    "bucket_roll": {"root": "store/", "prev_ref": 1600.0, "ref": 1700.0,
                    "retired": 1, "keys_invalidated": 12,
                    "windows": ["150"]},
    "fault_injected": {"site": "source.read", "fault_seq": 0, "key": "jsonl",
                       "rule": "source.read=3x5"},
    "degraded_enter": {"cause": "render", "detail": "serving stale tiles"},
    "degraded_exit": {"cause": "render"},
    "degrade_step": {"rung": 1, "from_rung": 0, "direction": "up",
                     "cause": "tiles-fast", "burn": 1.5},
    "quarantine": {"root": "store/", "path": "journal/ckpt-3.npz",
                   "reason": "digest_mismatch", "kind": "journal_entry",
                   "detail": "recorded sha256:aa..., actual sha256:bb..."},
    "anomaly_detected": {"series": "ingest_lag_seconds", "z": 7.2,
                         "threshold": 6.0, "watch": "ingest_lag_seconds",
                         "value": 42.5},
    "shard_orphaned": {"shard": "5", "host": "2", "reason": "heartbeat"},
    "shard_reassigned": {"shard": "5", "from_host": "2", "to_host": "0"},
    "speculative_launch": {"shard": "3", "host": "1", "runtime_s": 4.2,
                           "threshold_s": 1.9},
    "speculative_win": {"shard": "3", "winner": "1", "loser": "0",
                        "quarantined": "quarantine/shard-00003-ab-loser"},
    "fleet_backend_down": {"backend": "b2", "reason": "probe_failures",
                           "detail": "3 consecutive probe failures"},
    "fleet_backend_up": {"backend": "b2", "detail": "half-open probe ok"},
    "synopsis_built": {"zoom": 6, "pairs": 4, "bytes": 2048,
                       "max_err": 12.5, "coefficients": 256,
                       "path": "store/base-000001/synopsis-z06.npz"},
    "synopsis_served": {"layer": "all-alltime", "zoom": 6,
                        "max_err": 12.5, "source_zoom": 6,
                        "stale": False},
    "integral_built": {"zoom": 6, "pairs": 4, "bytes": 2048,
                       "path": "store/base-000001/integral-z06.npz"},
    "query_served": {"op": "sum", "zoom": 8, "path": "integral",
                     "layer": "all-alltime", "bbox_area": 100,
                     "cells": 5, "k": 10, "q": 0.5, "max_err": 12.5,
                     "ms": 0.2},
    "slo_breach": {"slo": "tiles-fast", "burn_rate": 2.5,
                   "kind": "latency", "compliance": 0.9975,
                   "target": 0.999, "window_s": 300.0,
                   "detail": "threshold_ms=50"},
    "incident_flush": {"trigger": "shed", "path": "incidents/ab12-0",
                       "seq": 0, "detail": "in-flight bound 2",
                       "bytes": 4096},
    "prewarm_done": {"keys": 12, "seconds": 0.8, "bytes": 65536,
                     "errors": 0, "planned": 16,
                     "budget_exhausted": False, "source": "startup"},
    "writeplane_append": {"points": 1500, "ranges": 3, "sign": 1,
                          "duplicate": False, "seconds": 0.4,
                          "content_hash": "sha256:00"},
    "writeplane_publish": {"epoch": 4, "ranges": 3, "seconds": 0.02,
                           "live_deltas": 5},
    "writeplane_rebalance": {"range": "r000", "new_range": "r004",
                             "split": 123456, "reason": "hot_range",
                             "seconds": 0.3},
    "run_end": {"status": "ok", "blobs": 42, "checksum": "crc32:00000000",
                "seconds": 1.0},
}


class TestEventSchema:
    def test_catalog_round_trip(self, tmp_path):
        """Every cataloged event type emits, survives the JSONL round
        trip, and re-validates — with one monotonic seq per log."""
        path = str(tmp_path / "events.jsonl")
        with obs.EventLog(path, run_id="testrun") as log:
            for event, payload in _PAYLOADS.items():
                log.emit(event, **payload)
        records = obs.read_events(path)
        assert [r["event"] for r in records] == list(_PAYLOADS)
        for rec in records:
            obs.validate_event(rec)  # must not raise
            assert rec["run_id"] == "testrun"
        assert [r["seq"] for r in records] == list(range(len(_PAYLOADS)))

    def test_payloads_cover_schema(self):
        assert set(_PAYLOADS) == set(obs.EVENT_SCHEMA)

    def test_unknown_field_rejected(self, tmp_path):
        with obs.EventLog(str(tmp_path / "e.jsonl")) as log:
            with pytest.raises(ValueError, match="unknown field"):
                log.emit("run_end", status="ok", bogus_field=1)

    def test_missing_required_rejected(self, tmp_path):
        with obs.EventLog(str(tmp_path / "e.jsonl")) as log:
            with pytest.raises(ValueError, match="missing required"):
                log.emit("stage_end", wall_s=0.1)  # no stage

    def test_unknown_event_type_rejected(self, tmp_path):
        with obs.EventLog(str(tmp_path / "e.jsonl")) as log:
            with pytest.raises(ValueError, match="unknown event type"):
                log.emit("made_up_event", foo=1)

    def test_module_emit_noop_without_log(self):
        assert obs.get_event_log() is None
        assert obs.emit("run_end", status="ok") is None

    def test_concurrent_emit_keeps_seq_dense(self, tmp_path):
        path = str(tmp_path / "e.jsonl")
        with obs.EventLog(path) as log:
            threads = [
                threading.Thread(
                    target=lambda: [log.emit("heartbeat", process_index=0,
                                             process_count=1, phase="p")
                                    for _ in range(200)])
                for _ in range(8)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        seqs = sorted(r["seq"] for r in obs.read_events(path))
        assert seqs == list(range(1600))


class TestMetricsRegistry:
    def test_disabled_is_noop(self):
        reg = MetricsRegistry()
        c = reg.counter("c_total")
        c.inc(5)
        assert c.value() == 0

    def test_counter_concurrency(self):
        reg = MetricsRegistry()
        reg.enabled = True
        c = reg.counter("hits_total", labelnames=("k",))
        n_threads, per_thread = 8, 5000

        def worker():
            for _ in range(per_thread):
                c.inc(k="a")

        threads = [threading.Thread(target=worker)
                   for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value(k="a") == n_threads * per_thread

    def test_label_mismatch_raises(self):
        reg = MetricsRegistry()
        reg.enabled = True
        c = reg.counter("c_total", labelnames=("backend",))
        with pytest.raises(ValueError, match="takes labels"):
            c.inc(1, wrong="x")

    def test_same_name_same_object_type_conflict_raises(self):
        reg = MetricsRegistry()
        assert reg.counter("x_total") is reg.counter("x_total")
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("x_total")

    def test_counter_rejects_negative(self):
        reg = MetricsRegistry()
        reg.enabled = True
        with pytest.raises(ValueError, match="only go up"):
            reg.counter("n_total").inc(-1)

    def test_histogram_and_prometheus_rendering(self):
        reg = MetricsRegistry()
        reg.enabled = True
        h = reg.histogram("lat_seconds", "spans", labelnames=("stage",),
                          buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 5.0):
            h.observe(v, stage="s")
        reg.gauge("g", "a gauge").set(2.5)
        text = reg.render_prometheus()
        assert '# TYPE lat_seconds histogram' in text
        assert 'lat_seconds_bucket{stage="s",le="0.1"} 1' in text
        assert 'lat_seconds_bucket{stage="s",le="1"} 2' in text
        assert 'lat_seconds_bucket{stage="s",le="+Inf"} 3' in text
        assert 'lat_seconds_count{stage="s"} 3' in text
        assert "g 2.5" in text

    def test_reset_keeps_handles_valid(self):
        reg = MetricsRegistry()
        reg.enabled = True
        c = reg.counter("y_total")
        c.inc(3)
        reg.reset()
        assert c.value() == 0
        c.inc(2)
        assert reg.counter("y_total").value() == 2

    def test_snapshot_is_json_serializable(self):
        reg = MetricsRegistry()
        reg.enabled = True
        reg.histogram("h_seconds", buckets=(1.0,)).observe(0.5)
        reg.counter("c_total", labelnames=("a",)).inc(1, a="v")
        json.dumps(reg.snapshot())


class TestTracerFeedsRegistry:
    def test_span_records_histogram_items_and_event(self, tmp_path):
        from heatmap_tpu.utils.trace import span

        obs.enable_metrics(True)
        path = str(tmp_path / "e.jsonl")
        obs.set_event_log(obs.EventLog(path))
        with span("unit.stage", items=64, backend="scatter"):
            pass
        obs.get_event_log().close()
        obs.set_event_log(None)
        snap = obs.get_registry().snapshot()
        [sample] = [s for s in snap["stage_duration_seconds"]["samples"]
                    if s["labels"] == {"stage": "unit.stage"}]
        assert sample["count"] == 1
        [items] = [s for s in snap["stage_items_total"]["samples"]
                   if s["labels"] == {"stage": "unit.stage"}]
        assert items["value"] == 64
        [rec] = obs.read_events(path)
        assert rec["event"] == "stage_end"
        assert rec["stage"] == "unit.stage"
        assert rec["items"] == 64
        assert rec["backend"] == "scatter"

    def test_span_free_when_telemetry_off(self):
        from heatmap_tpu.utils.trace import get_tracer, span

        with span("quiet.stage", items=1):
            pass
        assert "quiet.stage" in get_tracer().report()
        snap = obs.get_registry().snapshot()
        assert not any(s["labels"].get("stage") == "quiet.stage"
                       for s in snap["stage_duration_seconds"]["samples"])


class TestProfilerUnavailable:
    def test_warning_attribute_and_event(self, tmp_path, monkeypatch):
        """The jax_profile docstring promises a tracer warning on
        profiler failure — the satellite fix records it and emits the
        profiler_unavailable event."""
        import jax

        from heatmap_tpu.utils.trace import get_tracer, jax_profile

        def boom(logdir):
            raise RuntimeError("profiler not supported here")

        monkeypatch.setattr(jax.profiler, "start_trace", boom)
        path = str(tmp_path / "e.jsonl")
        obs.set_event_log(obs.EventLog(path))
        with jax_profile(str(tmp_path / "trace")):
            pass
        obs.get_event_log().close()
        obs.set_event_log(None)
        tracer = get_tracer()
        assert tracer.profiler_warning is not None
        assert "profiler not supported here" in tracer.profiler_warning
        [rec] = obs.read_events(path)
        assert rec["event"] == "profiler_unavailable"
        assert "profiler not supported here" in rec["error"]
        report = obs.build_run_report(tracer=tracer)
        assert any("profiler" in w for w in report["warnings"])

    def test_no_warning_when_profiler_starts(self, tmp_path):
        from heatmap_tpu.utils.trace import get_tracer, jax_profile

        with jax_profile(str(tmp_path / "trace")):
            pass
        assert get_tracer().profiler_warning is None


def _run_args(extra):
    from heatmap_tpu.cli import build_parser

    return build_parser().parse_args(
        ["run", "--backend", "cpu", "--input", "synthetic:2000:3",
         "--detail-zoom", "12", *extra])


class TestRunTelemetry:
    def test_events_report_and_blob_equality(self, tmp_path, capsys):
        """One batch job, telemetry off then on: the on-run yields a
        parseable event log (run_start first, run_end last, stage_end +
        backend_resolved + device_memory between), a run_report.json
        with stages/metrics/manifest, a Prometheus dump with io
        counters — and byte-identical blobs to the off-run."""
        from heatmap_tpu.cli import cmd_run

        out_off = tmp_path / "off.jsonl"
        assert cmd_run(_run_args(["--output", f"jsonl:{out_off}"])) == 0

        out_on = tmp_path / "on.jsonl"
        events = tmp_path / "events.jsonl"
        report_path = tmp_path / "run_report.json"
        mdir = tmp_path / "metrics"
        assert cmd_run(_run_args(
            ["--output", f"jsonl:{out_on}",
             "--events", str(events),
             "--report", str(report_path),
             "--metrics-dir", str(mdir)])) == 0
        capsys.readouterr()

        # -- acceptance: blobs byte-identical with telemetry on vs off
        assert out_on.read_bytes() == out_off.read_bytes()

        # -- and with span tracing + an SLO engine on top (the span
        # tree must be purely observational too)
        out_traced = tmp_path / "traced.jsonl"
        trace_out = tmp_path / "trace.json"
        assert cmd_run(_run_args(
            ["--output", f"jsonl:{out_traced}",
             "--trace-out", str(trace_out),
             "--slo", "stage-budget:error_rate:target=0.9"])) == 0
        capsys.readouterr()
        assert out_traced.read_bytes() == out_off.read_bytes()
        traced = json.loads(trace_out.read_text())
        assert any(e.get("name") == "run"
                   for e in traced["traceEvents"])

        # -- and with a brownout controller armed at rung 0: an idle
        # ladder (no burn) must be purely observational too.
        from heatmap_tpu.serve import degrade

        controller = degrade.BrownoutController(poll_interval_s=0.0)
        out_ctl = tmp_path / "ctl.jsonl"
        controller.poll()
        assert cmd_run(_run_args(
            ["--output", f"jsonl:{out_ctl}",
             "--slo", "stage-budget:error_rate:target=0.9"])) == 0
        capsys.readouterr()
        controller.poll()
        assert controller.rung == 0
        assert out_ctl.read_bytes() == out_off.read_bytes()

        # -- event log: ordering + coverage
        records = obs.read_events(str(events))
        for rec in records:
            obs.validate_event(rec)
        kinds = [r["event"] for r in records]
        assert kinds[0] == "run_start"
        assert kinds[-1] == "run_end"
        assert "stage_end" in kinds
        assert "backend_resolved" in kinds
        assert "cascade_dispatch" in kinds
        assert "device_memory" in kinds
        assert len({r["run_id"] for r in records}) == 1
        assert [r["seq"] for r in records] == list(range(len(records)))
        start = records[0]
        assert start["config"]["detail_zoom"] == 12
        assert start["devices"]["platform"] == "cpu"
        end = records[-1]
        assert end["status"] == "ok"
        assert end["blobs"] > 0
        assert end["checksum"].startswith("crc32:")
        [resolved] = [r for r in records if r["event"] == "backend_resolved"]
        assert resolved["requested"] == "auto"
        assert resolved["resolved"] == "scatter"

        # -- run report: parseable, stages with attribution, manifest
        report = json.loads(report_path.read_text())
        assert report["schema"].startswith("heatmap-tpu.run_report")
        assert "cascade.device" in report["stages"]
        assert report["run"]["status"] == "ok"
        assert report["run"]["checksum"] == end["checksum"]
        assert report["backends"][0]["resolved"] == "scatter"
        # io counters made it into the metrics snapshot
        rows = report["metrics"]["source_rows_read_total"]["samples"]
        assert sum(s["value"] for s in rows) == 2000
        blobs_written = report["metrics"]["sink_blobs_written_total"]
        assert sum(s["value"]
                   for s in blobs_written["samples"]) == end["blobs"]
        binned = report["metrics"]["points_binned_total"]["samples"]
        assert binned[0]["labels"] == {"backend": "scatter"}

        # -- Prometheus exposition
        prom = (mdir / "metrics.prom").read_text()
        assert "# TYPE stage_duration_seconds histogram" in prom
        assert 'source_rows_read_total{source="synthetic"} 2000' in prom

    def test_blobs_identical_with_compile_spans(self, tmp_path, capsys):
        """A traced run that compiles every program (caches cleared)
        records the compiles as ``jax.compile`` spans and writes the
        same bytes as an untraced run."""
        import jax

        from heatmap_tpu.cli import cmd_run

        out_off = tmp_path / "off.jsonl"
        assert cmd_run(_run_args(["--output", f"jsonl:{out_off}"])) == 0
        jax.clear_caches()
        out_traced = tmp_path / "traced.jsonl"
        trace_out = tmp_path / "trace.json"
        assert cmd_run(_run_args(["--output", f"jsonl:{out_traced}",
                                  "--trace-out", str(trace_out)])) == 0
        capsys.readouterr()
        assert out_traced.read_bytes() == out_off.read_bytes()
        compiles = [e for e in json.loads(trace_out.read_text())["traceEvents"]
                    if e.get("name") == "jax.compile"]
        assert {e["args"]["phase"] for e in compiles} == {
            "trace", "lower", "backend"}
        assert any(e["args"]["fun_name"] == "jit(build_cascade)"
                   for e in compiles)

    def test_report_flag_prints_table_without_profile(self, tmp_path,
                                                      capsys):
        """Satellite: the span/throughput report under --report alone
        (previously reachable only with --profile)."""
        from heatmap_tpu.cli import cmd_run

        report_path = tmp_path / "r.json"
        assert cmd_run(_run_args(
            ["--output", f"jsonl:{tmp_path / 'b.jsonl'}",
             "--report", str(report_path)])) == 0
        err = capsys.readouterr().err
        assert "run report" in err
        assert "cascade.device" in err
        assert report_path.exists()

    def test_run_end_records_job_error(self, tmp_path):
        """A failing job still closes the event log with
        run_end{status=error} before the error propagates."""
        from heatmap_tpu.cli import cmd_run

        events = tmp_path / "events.jsonl"
        args = _run_args(
            ["--output", f"jsonl:{tmp_path / 'b.jsonl'}",
             "--events", str(events),
             "--timespans", "alltime,year"])
        # Dated timespans need timestamps; synthetic provides them —
        # inject the failure further down instead: weighted without a
        # value column.
        args.weighted = True
        with pytest.raises(ValueError, match="value"):
            cmd_run(args)
        records = obs.read_events(str(events))
        assert records[-1]["event"] == "run_end"
        assert records[-1]["status"] == "error"
        assert "value" in records[-1]["error"]
        assert obs.get_event_log() is None  # log detached + closed


class TestRecoveryEvents:
    def test_retry_and_recovery_emitted(self, tmp_path):
        from heatmap_tpu.utils.recovery import FaultInjector, run_shards

        obs.enable_metrics(True)
        path = str(tmp_path / "e.jsonl")
        obs.set_event_log(obs.EventLog(path))
        inj = FaultInjector({1: 2})
        result = run_shards([10, 20, 30], lambda s: s * 2, retries=3,
                            fault_injector=inj)
        obs.get_event_log().close()
        obs.set_event_log(None)
        assert result == [20, 40, 60]
        records = obs.read_events(path)
        retries = [r for r in records if r["event"] == "retry"]
        assert [r["attempt"] for r in retries] == [1, 2]
        assert all(r["shard"] == 1 for r in retries)
        [rec] = [r for r in records if r["event"] == "recovery"]
        assert rec == {**rec, "shard": 1, "attempts": 2}
        assert obs.SHARD_RETRIES.value() == 2


class TestStreamingTelemetry:
    def test_update_and_default_hook_gauges(self):
        import numpy as np

        from heatmap_tpu.ops import Window
        from heatmap_tpu.streaming import (HeatmapStream, StreamConfig,
                                           run_stream)

        obs.enable_metrics(True)
        window = Window(zoom=8, row0=80, col0=40, height=8, width=8)
        stream = HeatmapStream(StreamConfig(window=window, half_life_s=60.0))
        batches = [
            (float(t), {"latitude": np.full(5, 47.6),
                        "longitude": np.full(5, -122.3),
                        "user_id": ["u"] * 5, "source": ["gps"] * 5,
                        "timestamp": [0] * 5})
            for t in (0, 30, 60)
        ]
        run_stream(stream, batches)
        assert obs.STREAM_POINTS.value() == 15
        assert obs.STREAM_BATCHES.value() == 3
        assert obs.STREAM_TICKS.value() == 3
        assert obs.STREAM_TIME.value() == 60.0


class TestNoRawInstrumentation:
    # Modules allowed to talk to stdout / own a clock: the telemetry
    # subsystem itself, the tracer, and the CLI boundary.
    ALLOWED = ("heatmap_tpu/obs/", "heatmap_tpu/utils/trace.py",
               "heatmap_tpu/cli.py", "heatmap_tpu/__main__.py")
    PATTERN = re.compile(r"(?:(?<![\w.])print\(|time\.perf_counter\()")

    def test_no_raw_print_or_timer_outside_obs(self):
        """All future instrumentation goes through heatmap_tpu.obs /
        utils.trace — raw print()/perf_counter() in library modules
        would bypass the zero-cost-when-off discipline."""
        offenders = []
        pkg = os.path.join(REPO, "heatmap_tpu")
        for dirpath, _, files in os.walk(pkg):
            for fname in files:
                if not fname.endswith(".py"):
                    continue
                full = os.path.join(dirpath, fname)
                rel = os.path.relpath(full, REPO).replace(os.sep, "/")
                if any(rel.startswith(a) for a in self.ALLOWED):
                    continue
                with open(full) as f:
                    for lineno, line in enumerate(f, 1):
                        code = line.split("#", 1)[0]
                        if self.PATTERN.search(code):
                            offenders.append(f"{rel}:{lineno}")
        assert not offenders, (
            "raw print()/time.perf_counter() outside obs//trace.py — "
            "route instrumentation through heatmap_tpu.obs: "
            + ", ".join(offenders))

    def test_serve_tree_is_guarded(self):
        """The serve/ package is the layer MOST tempted to print (HTTP
        request logging) and to time ad hoc (render latency): pin that
        it exists, is scanned by the walk above, and is not allowed."""
        serve = os.path.join(REPO, "heatmap_tpu", "serve")
        assert os.path.isdir(serve)
        scanned = [f for f in os.listdir(serve) if f.endswith(".py")]
        assert "http.py" in scanned and "cache.py" in scanned
        assert not any(a.startswith("heatmap_tpu/serve")
                       for a in self.ALLOWED)
        # And the guard pattern does bite on what serve must not do.
        assert self.PATTERN.search("print('GET /tiles 200')")
        assert self.PATTERN.search("t0 = time.perf_counter()")

    SLEEP_ALLOWED = ("heatmap_tpu/faults/",)
    SLEEP_PATTERN = re.compile(r"(?<![\w.])time\.sleep\(")

    def test_no_hand_rolled_retry_sleeps(self):
        """Every backoff sleep goes through faults.sleep_backoff — the
        only sanctioned ``time.sleep`` in the library. A hand-rolled
        ``time.sleep`` retry loop would dodge the unified policy table,
        the chaos plane's ``backoff_scale`` (which is how the soak and
        the chaos tests keep injected-fault retries instant), and the
        ``io_retries_total`` accounting (docs/robustness.md)."""
        offenders = []
        pkg = os.path.join(REPO, "heatmap_tpu")
        for dirpath, _, files in os.walk(pkg):
            for fname in files:
                if not fname.endswith(".py"):
                    continue
                full = os.path.join(dirpath, fname)
                rel = os.path.relpath(full, REPO).replace(os.sep, "/")
                if any(rel.startswith(a) for a in self.SLEEP_ALLOWED):
                    continue
                with open(full) as f:
                    for lineno, line in enumerate(f, 1):
                        code = line.split("#", 1)[0]
                        if self.SLEEP_PATTERN.search(code):
                            offenders.append(f"{rel}:{lineno}")
        assert not offenders, (
            "time.sleep() outside heatmap_tpu/faults/ — use "
            "faults.sleep_backoff / faults.retry_call for retry waits: "
            + ", ".join(offenders))
        # The pattern does bite on what the guard forbids.
        assert self.SLEEP_PATTERN.search("time.sleep(backoff_s * attempt)")

    TRACING_MODULES = ("heatmap_tpu/obs/tracing.py",
                       "heatmap_tpu/obs/slo.py",
                       "heatmap_tpu/obs/recorder.py",
                       "heatmap_tpu/obs/incident.py")
    TRACING_PATTERN = re.compile(
        r"(?:(?<![\w.])print\(|time\.perf_counter\(|(?<![\w.])time\.sleep\()")

    def test_tracing_and_slo_have_no_unsanctioned_clocks(self):
        """obs/tracing.py, obs/slo.py, obs/recorder.py and
        obs/incident.py sit inside the blanket ``heatmap_tpu/obs/``
        allowance above, so they get their own tighter guard: no raw
        print()/perf_counter()/time.sleep() except on lines explicitly
        marked ``# sanctioned:`` (tracing's single ``_now_s`` clock
        site). The SLO engine and the flight recorder run entirely on
        event/span timestamps — they never own a clock or sleep; the
        incident manager's wall clock is time.time (injectable), never
        perf_counter."""
        offenders, sanctioned = [], []
        for rel in self.TRACING_MODULES:
            full = os.path.join(REPO, rel)
            assert os.path.isfile(full), f"{rel} missing"
            with open(full) as f:
                for lineno, line in enumerate(f, 1):
                    if not self.TRACING_PATTERN.search(line):
                        continue
                    if "# sanctioned:" in line:
                        sanctioned.append(f"{rel}:{lineno}")
                    else:
                        offenders.append(f"{rel}:{lineno}")
        assert not offenders, (
            "unsanctioned print()/perf_counter()/sleep() in the "
            "tracing/SLO modules — all timing goes through _now_s "
            "(mark deliberate sites '# sanctioned: <why>'): "
            + ", ".join(offenders))
        # Exactly one sanctioned clock: tracing._now_s. Growing this
        # list is a deliberate act that must touch this test.
        assert sanctioned == ["heatmap_tpu/obs/tracing.py:59"] or (
            len(sanctioned) == 1
            and sanctioned[0].startswith("heatmap_tpu/obs/tracing.py:"))

    def test_tilefs_tree_is_guarded(self):
        """The tilefs/ package sits on the serve path twice over (mmap
        store reads, disk-cache fills) and replays requests at startup
        (prewarm) — ad-hoc warm-progress prints or hand-rolled fill
        timing would bypass the obs discipline: pin that the tree
        exists, is scanned by the walks above, and is not allowed."""
        tfs = os.path.join(REPO, "heatmap_tpu", "tilefs")
        assert os.path.isdir(tfs)
        scanned = [f for f in os.listdir(tfs) if f.endswith(".py")]
        assert "format.py" in scanned and "diskcache.py" in scanned
        assert "prewarm.py" in scanned
        assert not any(a.startswith("heatmap_tpu/tilefs")
                       for a in self.ALLOWED)
        assert not any(a.startswith("heatmap_tpu/tilefs")
                       for a in self.SLEEP_ALLOWED)
        assert self.PATTERN.search("print('prewarmed 64 keys')")

    def test_synopsis_tree_is_guarded(self):
        """The synopsis/ package sits on the serve decode path — ad-hoc
        decode timing or build-progress prints would bypass the obs
        discipline exactly like serve/ would: pin that the tree exists,
        is scanned by the walk above, and is not allowed."""
        syn = os.path.join(REPO, "heatmap_tpu", "synopsis")
        assert os.path.isdir(syn)
        scanned = [f for f in os.listdir(syn) if f.endswith(".py")]
        assert "transform.py" in scanned and "build.py" in scanned
        assert not any(a.startswith("heatmap_tpu/synopsis")
                       for a in self.ALLOWED)
        assert self.PATTERN.search("t0 = time.perf_counter()  # decode")

    # Modules the serve tier's tile DECODE path imports: synopsis
    # decoding must work on a box with no jax install at all
    # (docs/synopsis.md), so module-level jax imports are forbidden.
    # serve/live.py is deliberately absent — it renders via
    # tilemath.mercator and legitimately pulls jax.
    JAX_FREE = ("heatmap_tpu/serve/store.py", "heatmap_tpu/serve/render.py",
                "heatmap_tpu/serve/http.py", "heatmap_tpu/serve/cache.py",
                "heatmap_tpu/serve/router.py",
                "heatmap_tpu/serve/dashboard.py",
                "heatmap_tpu/serve/degrade.py", "heatmap_tpu/synopsis/",
                "heatmap_tpu/analytics/", "heatmap_tpu/tilefs/")
    JAX_IMPORT = re.compile(r"^(?:import jax\b|from jax\b)")

    def test_decode_path_has_no_module_level_jax(self):
        """The serving decode path (TileStore -> render -> http/router
        + the whole synopsis package) must not import jax at module
        level — lazy imports inside ``*_jax`` functions are the
        sanctioned idiom (synopsis/transform.py docstring)."""
        offenders = []
        for target in self.JAX_FREE:
            full = os.path.join(REPO, target)
            if target.endswith("/"):
                files = [os.path.join(full, f) for f in os.listdir(full)
                         if f.endswith(".py")]
            else:
                files = [full]
            assert files, f"{target} matched no files"
            for fpath in files:
                rel = os.path.relpath(fpath, REPO).replace(os.sep, "/")
                with open(fpath) as f:
                    for lineno, line in enumerate(f, 1):
                        if self.JAX_IMPORT.search(line):
                            offenders.append(f"{rel}:{lineno}")
        assert not offenders, (
            "module-level jax import on the serve decode path — import "
            "jax lazily inside *_jax functions instead: "
            + ", ".join(offenders))
        # The pattern bites on both import spellings but not the lazy
        # (indented) idiom.
        assert self.JAX_IMPORT.search("import jax.numpy as jnp")
        assert self.JAX_IMPORT.search("from jax import lax")
        assert not self.JAX_IMPORT.search("    import jax")

    def test_analytics_tree_is_guarded(self):
        """The analytics/ package sits on the /query serve path — query
        latency belongs to the query_seconds histogram and the
        query_served event, never an ad-hoc perf_counter: pin that the
        tree exists, is scanned by the walk above, and is not allowed.
        (Its jax discipline is pinned by JAX_FREE: integral2d_jax
        imports jax lazily, so /query decoding works without jax.)"""
        ana = os.path.join(REPO, "heatmap_tpu", "analytics")
        assert os.path.isdir(ana)
        scanned = [f for f in os.listdir(ana) if f.endswith(".py")]
        assert "integral.py" in scanned and "query.py" in scanned
        assert not any(a.startswith("heatmap_tpu/analytics")
                       for a in self.ALLOWED)
        assert self.PATTERN.search("t0 = time.perf_counter()  # query")

    def test_delta_tree_is_guarded(self):
        """The delta/ package times applies and compactions — that must
        flow through the obs metrics/events, never ad-hoc timers or
        progress prints: pin that the tree exists, is scanned, and is
        not allowed."""
        delta = os.path.join(REPO, "heatmap_tpu", "delta")
        assert os.path.isdir(delta)
        scanned = [f for f in os.listdir(delta) if f.endswith(".py")]
        assert "journal.py" in scanned and "compact.py" in scanned
        assert not any(a.startswith("heatmap_tpu/delta")
                       for a in self.ALLOWED)
        assert self.PATTERN.search("print('compacted 3 deltas')")
