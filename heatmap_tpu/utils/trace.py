"""Tracing and throughput metrics.

The reference has zero instrumentation — no timers, no logging, not one
print (SURVEY.md §5 "tracing/profiling: none in-repo"; the ``time``
import at reference heatmap.py:10 is unused). This module provides the
greenfield replacement:

- ``span(name)`` — wall-clock span timer, nestable, recorded into a
  process-wide ``Tracer`` (per-name count / total / max).
- ``Tracer.add_items(name, n)`` — throughput accounting: items
  processed under a name, so ``report()`` yields points/sec.
- ``jax_profile(logdir)`` — context manager around ``jax.profiler``'s
  trace (TensorBoard-viewable XLA timeline), gated so CPU-only test
  environments without profiler support degrade to a no-op.

Spans measure *host* wall-clock. For device work inside a span, call
``block_until_ready`` on the result before the span closes, or the
span records only dispatch time (XLA is async). Device work inside the
one jitted cascade is named by ``jax.named_scope`` instead
(pipeline/cascade.py, ops/pyramid.py): the scope path rides on each
compiled operation into a profiler trace.

Closed spans also feed the telemetry subsystem (heatmap_tpu/obs): a
``stage_duration_seconds`` histogram sample plus a ``stage_end`` event —
both no-ops unless a metrics sink or event log is configured, so the
tracer stays usable standalone.
"""

from __future__ import annotations

import contextlib
import threading
import time

_obs = None  # lazily imported so importing trace never pulls in obs/jax

# Span-tree hooks, installed by obs.tracing.enable_tracing (and removed
# by disable_tracing). While set, every default-tracer span also opens a
# node in the hierarchical trace (obs/tracing.py) and enters
# ``_annotation(name)`` (jax.profiler.TraceAnnotation), so the span sits
# on the host plane of any profiler trace taken meanwhile. The aggregate
# API here is unchanged, and with tracing off the cost is one global
# read.
_tree_begin = None
_tree_end = None
_annotation = None


def _obs_record(name: str, wall_s: float, items, attrs: dict):
    global _obs
    if _obs is None:
        from heatmap_tpu import obs

        _obs = obs
    _obs.record_stage(name, wall_s, items=items, **attrs)


class _SpanStats:
    __slots__ = ("count", "total_s", "max_s", "items")

    def __init__(self):
        self.count = 0
        self.total_s = 0.0
        self.max_s = 0.0
        self.items = 0


class Tracer:
    """Per-name span statistics + item throughput, thread-safe."""

    def __init__(self):
        self._lock = threading.Lock()
        self._stats: dict[str, _SpanStats] = {}
        # Set by jax_profile when the profiler cannot start; surfaced
        # in obs.report.build_run_report's warnings.
        self.profiler_warning: str | None = None

    def _stat(self, name: str) -> _SpanStats:
        s = self._stats.get(name)
        if s is None:
            s = self._stats.setdefault(name, _SpanStats())
        return s

    @contextlib.contextmanager
    def span(self, name: str, items: int | None = None, **attrs):
        """Extra keyword attrs (e.g. ``backend="partitioned"``) ride
        along on the stage_end event when an event log is installed."""
        begin = _tree_begin
        tree_span = annotation = None
        if begin is not None and self is _default:
            tree_span = begin(name, attrs or None)
            annotate = _annotation
            if annotate is not None:
                annotation = annotate(name)
                annotation.__enter__()
        t0 = time.perf_counter()
        try:
            yield self
        finally:
            dt = time.perf_counter() - t0
            if annotation is not None:
                annotation.__exit__(None, None, None)
            with self._lock:
                s = self._stat(name)
                s.count += 1
                s.total_s += dt
                s.max_s = max(s.max_s, dt)
                if items:
                    s.items += int(items)
            if self is _default:
                # stage_end emits while the tree span is still ambient,
                # so the event is stamped with this span's identity.
                _obs_record(name, dt, items, attrs)
            if tree_span is not None:
                end = _tree_end
                if end is not None:  # may be unhooked mid-span in tests
                    end(tree_span)

    def add_items(self, name: str, n: int):
        """Attribute ``n`` processed items to ``name`` (throughput)."""
        with self._lock:
            self._stat(name).items += int(n)

    def report(self) -> dict:
        """{name: {count, total_s, max_s, mean_s, items, items_per_s}}."""
        out = {}
        with self._lock:
            for name, s in self._stats.items():
                out[name] = {
                    "count": s.count,
                    "total_s": s.total_s,
                    "max_s": s.max_s,
                    "mean_s": s.total_s / s.count if s.count else 0.0,
                    "items": s.items,
                    "items_per_s": s.items / s.total_s if s.total_s else 0.0,
                }
        return out

    def reset(self):
        with self._lock:
            self._stats.clear()
            self.profiler_warning = None

    def format_report(self) -> str:
        lines = []
        for name, r in sorted(self.report().items()):
            line = (
                f"{name:<28} n={r['count']:<6} total={r['total_s']:.3f}s "
                f"mean={r['mean_s'] * 1e3:.2f}ms max={r['max_s'] * 1e3:.2f}ms"
            )
            if r["items"]:
                line += (
                    f" items={r['items']} ({r['items_per_s'] / 1e6:.2f} M/s)"
                )
            lines.append(line)
        return "\n".join(lines)


_default = Tracer()


def get_tracer() -> Tracer:
    """The process-wide tracer the pipeline instruments into."""
    return _default


def span(name: str, items: int | None = None, **attrs):
    """Span on the default tracer: ``with span("binning", items=n): ...``"""
    return _default.span(name, items=items, **attrs)


@contextlib.contextmanager
def jax_profile(logdir: str):
    """Capture a jax.profiler trace (XLA timeline) into ``logdir``.

    No-op when the profiler is unavailable on the current backend: the
    failure is recorded on ``get_tracer().profiler_warning`` and, when
    an event log is installed, as a ``profiler_unavailable`` event —
    both surface in the run report's warnings.
    """
    import jax

    try:
        jax.profiler.start_trace(logdir)
        started = True
    except Exception as e:
        started = False
        _default.profiler_warning = (
            f"jax profiler unavailable ({type(e).__name__}: {e}); "
            f"no trace written to {logdir}")
        try:
            from heatmap_tpu.obs import events as _events

            _events.emit("profiler_unavailable", error=repr(e),
                         logdir=str(logdir))
        except Exception:
            pass
    try:
        yield
    finally:
        if started:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
