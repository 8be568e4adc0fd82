"""Compile seconds and compile count from ``jax.monitoring`` events.

The arithmetic of ``chip_smoke.PhaseClock``: the durations of JAX's
compile events are summed as compile seconds, and each backend compile
event counts one program. JAX records that event around the persistent
cache's lookup too, so a program loaded from the cache counts as a
cache hit and not as a compile. Unlike the smoke run's clock it counts
every thread together: the benchmark drives the program from one
thread, and a program that compiles on a helper thread still compiles
inside the window.
"""

from __future__ import annotations

import threading

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
BACKEND_COMPILE = COMPILE_EVENTS[-1]
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Running totals; read :meth:`snapshot` before and after a phase."""

    def __init__(self):
        self._lock = threading.Lock()
        self.seconds = 0.0
        self.programs = 0
        self.hits = 0

    def install(self) -> "CompileCounter":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self.on_event)
        jax.monitoring.register_event_listener(self.on_count)
        return self

    def on_count(self, event, **_):
        if event == CACHE_HIT:
            with self._lock:
                self.hits += 1

    def on_event(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            with self._lock:
                self.seconds += duration
                self.programs += event == BACKEND_COMPILE

    def snapshot(self) -> tuple[int, int, float]:
        """-> (compiles, cache hits, seconds in compile events)."""
        with self._lock:
            return self.programs - self.hits, self.hits, self.seconds
