"""The reduction from a profiler trace to busy time, idle gaps and
per-program device time, on a trace recorded here on the CPU and on
hand-made events."""

import os
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import devtrace, spans  # noqa: E402


def op(start, end, name="op", module="jit_m", device="/device:TPU:0"):
    return {"device": device, "name": name, "module": module,
            "start": start, "end": end}


def test_busy_merges_overlaps_and_averages_devices():
    ops = [op(0, 10), op(5, 20), op(30, 40),
           op(0, 5, device="/device:TPU:1")]
    assert devtrace.busy_intervals(ops[:3]) == [(0, 20), (30, 40)]
    assert devtrace.busy_ns(ops) == (30 + 5) / 2


def test_idle_gaps_longest_first_inside_the_window():
    ops = [op(10, 20), op(25, 30), op(60, 70)]
    assert devtrace.idle_gaps(ops, 0, 100) == [(30, 60), (70, 100), (0, 10),
                                               (20, 25)]
    assert devtrace.idle_gaps([], 0, 5) == [(0, 5)]


def test_clip_totals_and_module_time():
    ops = [op(0, 10, "sort", "jit_build_cascade"),
           op(5, 15, "add", "jit_build_cascade"),
           op(20, 30, "slice", "jit_slice")]
    inside = devtrace.clip(ops, 8, 25)
    assert [(o["start"], o["end"]) for o in inside] == [(8, 10), (8, 15),
                                                         (20, 25)]
    assert devtrace.totals(ops) == {"sort": 10, "add": 10, "slice": 10}
    assert devtrace.short_name("%fusion.12 = (u32[9]) fusion(u32[9] %x)",
                               "jit_build_cascade(167)") == "build_cascade:fusion"
    assert devtrace.totals(ops, "module") == {"jit_build_cascade": 20,
                                              "jit_slice": 10}
    assert devtrace.module_time_ns(ops, "build_cascade") == 15
    assert devtrace.module_time_ns(ops, "nothing") == 0


def test_outermost_counts_nested_operations_once():
    """A conditional and the fusion it ran: the breakdown counts the
    conditional alone. Devices are apart; an overlap is not a nesting."""
    ops = [op(0, 100, "conditional"), op(1, 99, "fusion"),
           op(2, 50, "inner"), op(100, 110, "sort"), op(105, 120, "copy"),
           op(0, 100, "fusion", device="/device:TPU:1")]
    kept = devtrace.outermost(ops)
    assert sorted((o["device"][-1], o["name"]) for o in kept) == [
        ("0", "conditional"), ("0", "copy"), ("0", "sort"), ("1", "fusion")]
    assert devtrace.totals(kept) == {"conditional": 100, "sort": 10,
                                     "copy": 15, "fusion": 100}
    assert devtrace.busy_ns(kept) == devtrace.busy_ns(ops)


def test_innermost_open_span_names_a_time():
    records = [{"name": "cascade", "span_id": "a", "parent_id": None,
                "start_s": 0.0, "dur_s": 10.0},
               {"name": "cascade.decode", "span_id": "b", "parent_id": "a",
                "start_s": 6.0, "dur_s": 2.0}]
    assert spans.innermost_at(records, 7.0) == "cascade.decode"
    assert spans.innermost_at(records, 1.0) == "cascade"
    assert spans.innermost_at(records, 11.0) is None
    assert spans.self_total(records, {"cascade"}) == 8.0
    assert spans.total(records, {"cascade.decode"}) == 2.0


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    """Three runs of a jitted program inside a ``bench.job`` mark, with
    host-only sleeps between them."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sort(x) * 2)
    x = jnp.arange(1 << 18)[::-1]
    f(x).block_until_ready()
    log_dir = str(tmp_path_factory.mktemp("profile"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.job"):
        for _ in range(3):
            f(x).block_until_ready()
            time.sleep(0.05)
    jax.profiler.stop_trace()
    return devtrace.read(devtrace.find_xplane(log_dir))


def test_cpu_trace_has_marks_and_operations(cpu_trace):
    marks = [m for m in cpu_trace["marks"] if m["name"] == "bench.job"]
    assert len(marks) == 1
    assert any(o["name"].endswith(":sort") for o in cpu_trace["ops"])


def test_cpu_trace_idle_share(cpu_trace):
    job = next(m for m in cpu_trace["marks"] if m["name"] == "bench.job")
    ops = devtrace.clip(cpu_trace["ops"], job["start"], job["end"])
    window = job["end"] - job["start"]
    busy = devtrace.busy_ns(ops)
    assert 0 < busy < window
    # Three sleeps of 50 ms leave the device idle for most of the job.
    idle = sum(e - s for s, e in devtrace.idle_gaps(ops, job["start"],
                                                    job["end"]))
    assert idle >= 0.12e9
    assert idle <= window - max(e - s for s, e in
                                devtrace.busy_intervals(ops))


def test_cpu_trace_operation_totals(cpu_trace):
    job = next(m for m in cpu_trace["marks"] if m["name"] == "bench.job")
    ops = devtrace.clip(cpu_trace["ops"], job["start"], job["end"])
    by_module = devtrace.totals(ops, "module")
    assert sum(by_module.values()) == pytest.approx(sum(devtrace.totals(ops).values()))
    assert any("lambda" in (m or "") for m in by_module)
