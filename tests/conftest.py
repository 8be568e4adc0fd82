"""Test harness: 8 virtual CPU devices + x64, per SURVEY.md §4.3.

Tests run on the CPU: the driver's command sets ``JAX_PLATFORMS=cpu``,
and this file pins the platform again through ``jax.config`` and gives
the host 8 virtual devices. It must run before the first backend
initialization anywhere in the test session. The chip is reached only
through ``python chip_smoke.py`` (``--chips 4`` for the four-chip
phase), never from a test.

The persistent compilation cache is off by default here (and in every
subprocess a test starts), so tests neither read nor write
``.jax_cache``.

Sizing caveat for new mesh tests: virtual devices SERIALIZE on the
host's cores, and XLA's CPU collective rendezvous aborts the process
when a participant arrives >60s after the first — keep per-shard work
well under that (docs/DESIGN.md §4 verification-ladder caveat;
observed at 2M-point DP shapes on a 1-core host).
"""

import os

os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _assert_cpu_mesh():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {devs}"
    assert devs[0].platform == "cpu"


@pytest.fixture(scope="session")
def devices():
    return jax.devices()


@pytest.fixture(autouse=True)
def _reset_telemetry():
    """Isolate process-wide telemetry state between tests: the default
    tracer, the metrics registry (values + enabled flag), the installed
    event log and span tracing — so a test that enables any of them
    cannot leak instrumentation cost or state into later hot-path
    tests."""
    yield
    from heatmap_tpu import faults, obs
    from heatmap_tpu.delta import recover
    from heatmap_tpu.obs import (anomaly, incident, recorder, slo,
                                 timeseries, tracing)
    from heatmap_tpu.utils import trace

    trace.get_tracer().reset()
    obs.enable_metrics(False)
    obs.get_registry().reset()
    log = obs.get_event_log()
    if log is not None:
        log.close()
        obs.set_event_log(None)
    tracing.disable_tracing()  # unhooks trace/events integrations too
    slo.set_engine(None)
    incident.set_manager(None)
    timeseries.shutdown()  # stops any sampler thread + clears the store
    anomaly.set_engine(None)
    recorder.install(None)  # restores the tracing/events hooks to None
    faults.install(None)  # disarm any chaos a test left installed
    recover.clear_verified_cache()
