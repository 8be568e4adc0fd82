"""Hierarchical tracing tests: span trees, cross-thread propagation,
traceparent continuation, Chrome export, and critical-path analysis.

The acceptance bar (ISSUE 6): a sampled serve request and a delta
apply each produce ONE connected span tree — a single root, every
span's parent present, the trace id stamped onto the corresponding
``http_request``/``stage_end`` events — exported as Chrome trace-event
JSON that ``tools/trace_analyze.py`` loads, with self-times summing to
the root's wall clock within 5%.
"""

from __future__ import annotations

import concurrent.futures
import json
import os
import sys
import threading
import urllib.request

import numpy as np
import pytest

from heatmap_tpu import obs
from heatmap_tpu.obs import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))
import trace_analyze  # noqa: E402  (tools/ is import-shared, not a pkg)


class TestSpanTree:
    def test_off_by_default_and_hooks_uninstalled(self):
        from heatmap_tpu.obs import events
        from heatmap_tpu.utils import trace as utrace

        assert not tracing.tracing_enabled()
        assert tracing.begin_span("x") is None
        assert tracing.current_span() is None
        assert tracing.current_traceparent() is None
        # zero-cost stance: with tracing off nothing is hooked
        assert utrace._tree_begin is None
        assert utrace._tree_end is None
        assert events._trace_ids is None
        # and context_bound is the identity
        fn = lambda: None  # noqa: E731
        assert tracing.context_bound(fn) is fn

    def test_root_on_demand_nesting_and_new_trace_after_unwind(self):
        collector = tracing.enable_tracing()
        with tracing.span("root") as root:
            assert root.parent_id is None
            with tracing.span("child") as child:
                assert child.trace_id == root.trace_id
                assert child.parent_id == root.span_id
                with tracing.span("grandchild") as g:
                    assert g.parent_id == child.span_id
        assert {s["name"] for s in collector.spans()} == {
            "root", "child", "grandchild"}
        with tracing.span("root2") as root2:
            assert root2.parent_id is None
            assert root2.trace_id != root.trace_id

    def test_unsampled_root_suppresses_descendants(self):
        collector = tracing.enable_tracing(sample=0.0)
        sentinel = tracing.begin_span("root")
        assert not isinstance(sentinel, tracing.Span)
        # descendants no-op instead of opening fresh roots
        assert tracing.begin_span("child") is None
        assert tracing.current_span() is None
        # the sentinel still renders a (sampled=00) traceparent so
        # downstream services can honor the decision
        tp = tracing.current_traceparent()
        assert tp is not None and tp.endswith("-00")
        tracing.end_span(sentinel)
        assert collector.spans() == []
        # context unwound: the next root starts clean
        with tracing.span("after") as sp:
            assert sp is None  # sample=0.0: never sampled

    def test_sampling_is_seeded_and_reproducible(self):
        a = tracing.TraceCollector(sample=0.5, seed=7)
        b = tracing.TraceCollector(sample=0.5, seed=7)
        decisions = [a.sample_decision() for _ in range(64)]
        assert decisions == [b.sample_decision() for _ in range(64)]
        assert any(decisions) and not all(decisions)

    def test_collector_caps_buffered_spans(self, monkeypatch):
        monkeypatch.setattr(tracing, "MAX_SPANS", 3)
        collector = tracing.enable_tracing()
        for i in range(5):
            with tracing.span(f"s{i}"):
                pass
        assert len(collector.spans()) == 3
        assert collector.dropped == 2
        assert collector.summary()["dropped"] == 2


class TestTraceparent:
    def test_roundtrip_matches_ambient_span(self):
        tracing.enable_tracing()
        with tracing.span("root"):
            cur = tracing.current_span()
            tp = tracing.current_traceparent()
            assert tracing.parse_traceparent(tp) == (
                cur.trace_id, cur.span_id, True)

    @pytest.mark.parametrize("bad", [
        None, "", "not-a-header", "00-abc-def-01",
        "00-" + "g" * 32 + "-" + "0" * 16 + "-01",   # non-hex trace id
        "00-" + "0" * 32 + "-" + "0" * 15 + "-01",   # short span id
    ])
    def test_malformed_headers_are_ignored_not_fatal(self, bad):
        assert tracing.parse_traceparent(bad) is None

    def test_incoming_header_overrides_probabilistic_sampling(self):
        # sampled flag forces recording even at sample=0
        collector = tracing.enable_tracing(sample=0.0)
        header = f"00-{'ab' * 16}-{'cd' * 8}-01"
        sp = tracing.begin_span("serve.request", traceparent=header)
        assert isinstance(sp, tracing.Span)
        assert sp.trace_id == "ab" * 16
        assert sp.parent_id == "cd" * 8
        tracing.end_span(sp)
        [rec] = collector.spans()
        assert rec["trace_id"] == "ab" * 16
        # ...and flags=00 forces suppression even at sample=1
        collector = tracing.enable_tracing(sample=1.0)
        sp = tracing.begin_span(
            "serve.request", traceparent=f"00-{'ab' * 16}-{'cd' * 8}-00")
        assert not isinstance(sp, tracing.Span)
        tracing.end_span(sp)
        assert collector.spans() == []


class TestThreadPropagation:
    def test_context_bound_carries_span_into_pool_worker(self):
        tracing.enable_tracing()
        seen = []
        with tracing.span("root") as root:

            def work():
                with tracing.span("pool.child") as child:
                    seen.append((child.trace_id, child.parent_id))

            with concurrent.futures.ThreadPoolExecutor(2) as pool:
                pool.submit(tracing.context_bound(work)).result()
        assert seen == [(root.trace_id, root.span_id)]

    def test_unbound_thread_starts_its_own_trace(self):
        tracing.enable_tracing()
        seen = []
        with tracing.span("root") as root:

            def work():
                with tracing.span("orphan") as sp:
                    seen.append((sp.trace_id, sp.parent_id))

            t = threading.Thread(target=work)
            t.start()
            t.join()
        [(trace_id, parent_id)] = seen
        assert trace_id != root.trace_id  # fresh context -> fresh root
        assert parent_id is None


class TestEventLogStorm:
    def test_eight_thread_storm_is_monotonic_and_untorn(self, tmp_path):
        """8 threads x 250 emits through the module-level emit path:
        every JSONL line must parse (no torn writes) and the seq
        column must be exactly 0..N-1 in file order."""
        path = str(tmp_path / "storm.jsonl")
        obs.set_event_log(obs.EventLog(path))
        filler = "/tiles/default/7/20/44.json" * 20  # force long lines

        def worker():
            for _ in range(250):
                obs.emit("http_request", route="tiles", status=200,
                         path=filler, ms=1.0)

        threads = [threading.Thread(target=worker) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        obs.get_event_log().close()
        obs.set_event_log(None)
        with open(path) as f:
            lines = f.read().splitlines()
        assert len(lines) == 2000
        records = [json.loads(line) for line in lines]  # untorn
        assert [r["seq"] for r in records] == list(range(2000))
        assert all(r["path"] == filler for r in records)


@pytest.fixture(scope="module")
def tile_artifacts(tmp_path_factory):
    from heatmap_tpu.io import open_sink, open_source
    from heatmap_tpu.pipeline import BatchJobConfig, run_job

    root = tmp_path_factory.mktemp("trace_artifacts")
    config = BatchJobConfig(detail_zoom=10, min_detail_zoom=5)
    with open_sink(f"arrays:{root}/levels") as sink:
        run_job(open_source("synthetic:2000:7"), sink, config)
    return f"arrays:{root}/levels"


def _pick_tile(app):
    from heatmap_tpu.tilemath.morton import morton_decode_np

    layer = app.store.layer("default")
    d = layer.detail_zooms[-1]
    delta = layer.result_delta
    code = int(layer.levels[d].codes[0]) >> (2 * delta)
    r, c = morton_decode_np(np.asarray([code], np.int64))
    return d - delta, int(c[0]), int(r[0])


class TestServeRequestTrace:
    def test_sampled_request_yields_connected_tree(self, tile_artifacts,
                                                   tmp_path):
        from heatmap_tpu.obs import slo
        from heatmap_tpu.serve import (ServeApp, TileCache, TileStore,
                                       serve_in_thread)

        obs.enable_metrics(True)
        collector = tracing.enable_tracing()
        slo.install_specs(["tiles-ok:error_rate:target=0.9,window_s=60"])
        ev_path = str(tmp_path / "ev.jsonl")
        obs.set_event_log(obs.EventLog(ev_path))
        # render_timeout_s routes renders through the worker pool, which
        # is the cross-thread propagation path under test
        app = ServeApp(TileStore(tile_artifacts),
                       TileCache(max_bytes=1 << 20), render_timeout_s=30.0)
        server, base = serve_in_thread(app)
        try:
            z, x, y = _pick_tile(app)
            resp = urllib.request.urlopen(
                f"{base}/tiles/default/{z}/{x}/{y}.json")
            assert resp.status == 200
            echoed = resp.headers.get("traceparent")
            health = json.loads(
                urllib.request.urlopen(f"{base}/healthz").read())
        finally:
            server.shutdown()
            server.server_close()
        obs.get_event_log().close()
        obs.set_event_log(None)

        spans = collector.spans()
        reqs = [s for s in spans if s["name"] == "serve.request"
                and "/tiles/" in s["attrs"].get("path", "")]
        assert len(reqs) == 1
        root = reqs[0]
        assert root["parent_id"] is None
        tree = [s for s in spans if s["trace_id"] == root["trace_id"]]
        ids = {s["span_id"] for s in tree}
        assert all(s["parent_id"] in ids for s in tree
                   if s["parent_id"] is not None)
        # the render ran in the pool thread yet joined the request tree
        [worker] = [s for s in tree if s["name"] == "tile.render.worker"]
        assert worker["tid"] != root["tid"]
        # the response echoes the request's trace identity
        assert echoed is not None
        assert tracing.parse_traceparent(echoed)[0] == root["trace_id"]
        # the http_request event carries the same identity
        tile_reqs = [r for r in obs.read_events(ev_path)
                     if r["event"] == "http_request"
                     and "/tiles/" in r.get("path", "")]
        assert [r["trace_id"] for r in tile_reqs] == [root["trace_id"]]
        # /healthz folds the live SLO status (served 200s -> ok)
        assert health["slo"]["ok"] is True
        assert [o["name"] for o in health["slo"]["objectives"]] == [
            "tiles-ok"]

    def test_incoming_traceparent_continues_client_trace(
            self, tile_artifacts):
        from heatmap_tpu.serve import (ServeApp, TileCache, TileStore,
                                       serve_in_thread)

        collector = tracing.enable_tracing(sample=0.0)  # header decides
        client_trace = "ab" * 16
        app = ServeApp(TileStore(tile_artifacts),
                       TileCache(max_bytes=1 << 20))
        server, base = serve_in_thread(app)
        try:
            z, x, y = _pick_tile(app)
            req = urllib.request.Request(
                f"{base}/tiles/default/{z}/{x}/{y}.json",
                headers={"traceparent": f"00-{client_trace}-{'cd' * 8}-01"})
            urllib.request.urlopen(req)
            # unsampled request: no spans recorded for it
            urllib.request.urlopen(f"{base}/tiles/default/{z}/{x}/{y}.json")
        finally:
            server.shutdown()
            server.server_close()
        spans = collector.spans()
        assert spans, "sampled flag must override sample=0.0"
        assert {s["trace_id"] for s in spans} == {client_trace}
        [root] = [s for s in spans if s["name"] == "serve.request"]
        assert root["parent_id"] == "cd" * 8  # parented to the client


class TestDeltaApplyTraceAndAnalysis:
    def test_apply_tree_export_and_critical_path(self, tmp_path):
        from heatmap_tpu import delta
        from heatmap_tpu.io import open_source
        from heatmap_tpu.pipeline import BatchJobConfig

        collector = tracing.enable_tracing()
        ev_path = str(tmp_path / "ev.jsonl")
        obs.set_event_log(obs.EventLog(ev_path))
        config = BatchJobConfig(detail_zoom=10, min_detail_zoom=5)
        delta.apply_batch(str(tmp_path / "store"),
                          open_source("synthetic:800:3"), config)
        obs.get_event_log().close()
        obs.set_event_log(None)

        # -- connected tree: one root, one trace, every parent present
        spans = collector.spans()
        roots = [s for s in spans if s["parent_id"] is None]
        assert [r["name"] for r in roots] == ["delta.apply"]
        assert len({s["trace_id"] for s in spans}) == 1
        ids = {s["span_id"] for s in spans}
        assert all(s["parent_id"] in ids for s in spans
                   if s["parent_id"] is not None)
        assert {"delta.compute", "run_job", "cascade"} <= {
            s["name"] for s in spans}

        # -- stage_end events are stamped with the same trace
        stage_recs = [r for r in obs.read_events(ev_path)
                      if r["event"] == "stage_end"]
        assert stage_recs
        assert {r["trace_id"] for r in stage_recs} == {
            roots[0]["trace_id"]}

        # -- Chrome export: valid, loadable, analyzable
        out = str(tmp_path / "trace.json")
        n = collector.export_chrome(out)
        assert n == len(spans)
        with open(out) as f:
            doc = json.load(f)
        assert doc["displayTimeUnit"] == "ms"
        phases = {e["ph"] for e in doc["traceEvents"]}
        assert phases == {"M", "X"}
        loaded = trace_analyze.load_events(out)
        assert len(loaded) == len(spans)

        # -- critical path + self-time attribution
        result = trace_analyze.analyze(loaded)
        assert result["n_traces"] == 1
        [row] = result["traces"]
        assert row["root"] == "delta.apply"
        # self-times over the tree sum to the root's wall within 5%
        assert row["self_sum_us"] == pytest.approx(row["wall_us"],
                                                   rel=0.05)
        path_names = [h["name"] for h in row["critical_path"]]
        assert path_names[0] == "delta.apply"
        assert len(path_names) >= 3
        # top_self covers every distinct span name
        assert {t["name"] for t in result["top_self"]} <= {
            s["name"] for s in spans}
        # the formatted report renders without error
        assert "critical path" in trace_analyze.format_report(result)


def _fresh_jit():
    """A function JAX has never compiled in this process."""
    import jax
    import jax.numpy as jnp

    salt = float(np.random.default_rng().random())

    @jax.jit
    def fresh_program(x):
        return jnp.sin(x) * salt + 1.0

    return fresh_program, jnp.arange(8.0)


class TestCompileSpans:
    def test_compile_phases_become_spans_under_the_ambient_span(self):
        from heatmap_tpu.utils.trace import span

        obs.enable_metrics(True)
        collector = tracing.enable_tracing()
        fn, x = _fresh_jit()
        with span("unit.compiles"):
            np.asarray(fn(x))
        records = collector.spans()
        [outer] = [r for r in records if r["name"] == "unit.compiles"]
        compiles = [r for r in records if r["name"] == tracing.COMPILE_SPAN
                    and "fresh_program" in r["attrs"]["fun_name"]]
        assert {r["attrs"]["phase"] for r in compiles} >= {
            "trace", "lower", "backend"}
        for r in compiles:
            assert r["parent_id"] == outer["span_id"]
            assert r["trace_id"] == outer["trace_id"]
            assert outer["start_s"] - 1e-3 <= r["start_s"]
            assert (r["start_s"] + r["dur_s"]
                    <= outer["start_s"] + outer["dur_s"] + 1e-3)
        [backend] = [r for r in compiles if r["attrs"]["phase"] == "backend"]
        assert "cache_hit" not in backend["attrs"]
        assert obs.JAX_COMPILES.value(fun_name="jit(fresh_program)") == 1

    def test_persistent_cache_load_is_a_hit_not_a_compile(self, tmp_path):
        import jax
        from jax._src import compilation_cache

        options = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
                   "jax_persistent_cache_min_compile_time_secs",
                   "jax_persistent_cache_min_entry_size_bytes")
        saved = {o: getattr(jax.config, o) for o in options}
        try:
            for option, value in zip(options, (True, str(tmp_path), 0, 0)):
                jax.config.update(option, value)
            compilation_cache.reset_cache()
            obs.enable_metrics(True)
            collector = tracing.enable_tracing()
            fn, x = _fresh_jit()
            np.asarray(fn(x))
            jax.clear_caches()
            np.asarray(fn(x))
        finally:
            for option, value in saved.items():
                jax.config.update(option, value)
            compilation_cache.reset_cache()
        backends = [r["attrs"] for r in collector.spans()
                    if r["attrs"].get("fun_name") == "jit(fresh_program)"
                    and r["attrs"]["phase"] == "backend"]
        assert [b.get("cache_hit", False) for b in backends] == [False, True]
        assert obs.JAX_COMPILES.value(fun_name="jit(fresh_program)") == 1
        assert obs.JAX_COMPILE_CACHE_HITS.value() >= 1

    def test_tracing_off_records_nothing(self):
        obs.enable_metrics(True)
        fn, x = _fresh_jit()
        collector = tracing.enable_tracing()
        tracing.disable_tracing()
        np.asarray(fn(x))
        assert collector.spans() == []
        assert obs.JAX_COMPILES.samples() == {}
        assert obs.JAX_COMPILE_CACHE_HITS.samples() == {}


class TestProfilerTimeline:
    def test_program_spans_sit_on_the_host_plane(self, tmp_path):
        import glob

        import jax

        from heatmap_tpu.utils.trace import span

        tracing.enable_tracing()
        jax.profiler.start_trace(str(tmp_path))
        try:
            with span("unit.outer"):
                with span("unit.inner"):
                    pass
        finally:
            jax.profiler.stop_trace()
        [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                           recursive=True)
        data = jax.profiler.ProfileData.from_file(path)
        host = {e.name: (e.start_ns, e.start_ns + e.duration_ns)
                for plane in data.planes if plane.name.startswith("/host:")
                for line in plane.lines for e in line.events}
        assert {"unit.outer", "unit.inner"} <= set(host)
        (os_, oe), (is_, ie) = host["unit.outer"], host["unit.inner"]
        assert os_ <= is_ <= ie <= oe

    def test_no_annotation_while_tracing_is_off(self, tmp_path):
        import glob

        import jax

        from heatmap_tpu.utils.trace import span

        jax.profiler.start_trace(str(tmp_path))
        try:
            with span("unit.untraced"):
                pass
        finally:
            jax.profiler.stop_trace()
        [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                           recursive=True)
        data = jax.profiler.ProfileData.from_file(path)
        assert not any(e.name == "unit.untraced" for plane in data.planes
                       for line in plane.lines for e in line.events)


class TestDispatchTimer:
    def test_enabled_under_span_tracing_alone(self):
        assert not obs.telemetry_enabled()
        assert not obs.DispatchTimer("single").enabled
        tracing.enable_tracing()
        timer = obs.DispatchTimer("single")
        assert timer.enabled
        timer.dispatched()
        host_s, device_s = timer.finished()
        assert host_s >= 0 and device_s >= 0
