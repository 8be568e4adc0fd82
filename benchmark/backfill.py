"""Driver of backfill traffic: whole jobs through ``run_job``, one at a time.

Set-up makes the warm job's rows, the same in every run (job 0 of seed
0), runs that job into an ``arrays:`` sink to compile and load every
program the window uses, deletes its output, and makes the rows of the
window's jobs from the run's seed. Because the warm job never changes,
a checkout's later runs find every program of their set-up in the
persistent cache. The
window then runs jobs 1, 2, ... back to back, each into its own
``arrays:`` directory, from its start until ``seconds`` have passed: a
job that starts inside the window runs to its end, and the window ends
with the last job. After the window the device's peak memory is read,
one job of the window, drawn from the seed, is compared with the numpy
reference on its rows, and every output is deleted.

With ``trace`` the same window runs with the program's span trees and
event log on (the log turns on ``obs.DispatchTimer``, which waits for
the cascade's device work), and the profiler records the window's first
job.
"""

from __future__ import annotations

import contextlib
import os
import shutil
import sys
import tempfile
import time
import traceback

import numpy as np

from benchmark import compare, devtrace, reference, roofline, spans, traffic
from benchmark.compiles import CompileCounter

#: Jobs whose rows set-up makes ahead of the window, at most; a window
#: that runs more makes the rest as it goes and reports the wait.
MAX_PREMADE = 6
#: Programs whose device time is the cascade's, by name.
CASCADE_PROGRAM = "build_cascade"
CHECK_STREAM = 1 << 32  # the seed's stream that picks the compared job
WARM_SEED = 0


def job_config(cell):
    from heatmap_tpu.pipeline import BatchJobConfig

    job = dict(cell.config["job"])
    job["timespans"] = tuple(job["timespans"])
    return BatchJobConfig(**job)


def run_job(columns: dict, out_dir: str, config) -> None:
    """One job of the program, from columns to a servable pyramid."""
    from heatmap_tpu.io import open_sink
    from heatmap_tpu.pipeline import run_job as program_run_job

    with open_sink(f"arrays:{out_dir}") as sink:
        program_run_job(traffic.ColumnSource(columns), sink, config)


class Telemetry:
    """The program's span trees and event log, for the traced run."""

    def __init__(self, work: str):
        from heatmap_tpu import obs

        self.obs = obs
        self.collector = obs.tracing.enable_tracing()
        self.path = os.path.join(work, "events.jsonl")
        self.log = obs.EventLog(self.path)
        obs.set_event_log(self.log)

    def close(self) -> tuple[list, list]:
        records = self.collector.spans()
        self.obs.set_event_log(None)
        self.log.close()
        self.obs.tracing.disable_tracing()
        return records, self.obs.read_events(self.path)


class Profile:
    """The profiler around one job, with a mark that ties the program's
    span clock (``time.perf_counter``) to the trace's timeline."""

    def __init__(self, log_dir: str):
        import jax

        self.jax = jax
        self.log_dir = log_dir
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=options)
        with jax.profiler.TraceAnnotation("bench.sync"):
            self.sync_perf_ns = time.perf_counter_ns()

    def job(self):
        return self.jax.profiler.TraceAnnotation("bench.job")

    def stop(self) -> None:
        self.jax.profiler.stop_trace()

    def reduce(self, records: list, n_emissions: int, level_rows: dict,
               weighted: bool, device_kind: str) -> tuple[dict, dict]:
        """-> (what the per-layer readers take, the breakdown)."""
        trace = devtrace.read(devtrace.find_xplane(self.log_dir))
        marks = {m["name"]: m for m in trace["marks"]}
        job, sync = marks["bench.job"], marks["bench.sync"]
        offset_ns = sync["start"] - self.sync_perf_ns
        ops = devtrace.clip(trace["ops"], job["start"], job["end"])
        gaps = devtrace.idle_gaps(ops, job["start"], job["end"])[:10]
        named = [[spans.innermost_at(records, ((s + e) / 2 - offset_ns) / 1e9)
                  or "outside the program's spans", (e - s) / 1e9]
                 for s, e in gaps]
        top = sorted(devtrace.totals(devtrace.outermost(ops)).items(),
                     key=lambda kv: -kv[1])[:10]
        profile = {
            "busy_s": devtrace.busy_ns(ops) / 1e9,
            "window_s": (job["end"] - job["start"]) / 1e9,
            "cascade_device_s": devtrace.module_time_ns(
                ops, CASCADE_PROGRAM) / 1e9,
            "least_bytes": roofline.least_bytes(
                n_emissions, level_rows.values(), weighted),
            "device_kind": device_kind,
            "n_ops": len(ops),
        }
        breakdown = {"device_ops": [[name, ns / 1e9] for name, ns in top],
                     "idle_gaps": named}
        return profile, breakdown


def n_emissions(columns: dict, config) -> int:
    kept = int((np.asarray(columns["source"], object)
                != traffic.BACKGROUND).sum())
    return 2 * kept * len(config.timespans)


def memory_peak_bytes(chips: int) -> int | None:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run(cell, seed: int, seconds: float, trace: bool, t_start: float) -> dict:
    import jax

    config = job_config(cell)
    mix = cell.traffic
    compiles = CompileCounter().install()
    with tempfile.TemporaryDirectory(prefix="heatmap-bench-") as work:
        # -- set-up: the warm job compiles or loads every program.
        warm_dir = os.path.join(work, "warm")
        t = time.perf_counter()
        run_job(traffic.make_job(mix, WARM_SEED, 0), warm_dir, config)
        warm_s = time.perf_counter() - t
        shutil.rmtree(warm_dir)
        setup_compiles = compiles.snapshot()
        # Jobs as long as the warm one start in the window at 0, warm_s,
        # 2 warm_s, ... while that is under ``seconds``; one more is made
        # for a window whose jobs run faster than the warm one, which in a
        # checkout's first run also compiles.
        n_premade = min(MAX_PREMADE, 2 + int(seconds // warm_s))
        inputs = {j: traffic.make_job(mix, seed, j)
                  for j in range(1, 1 + n_premade)}
        telemetry = Telemetry(work) if trace else None
        profile = Profile(os.path.join(work, "profile")) if trace else None
        # What the window compiles depends on its jobs' rows (the decode
        # slices take each level's exact row count). Left out of the
        # persistent cache, it is compiled in every run, so a seed's
        # second run does the same work as its first.
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          float("inf"))

        # -- the window.
        jobs, ran, wait_s = [], [], 0.0
        c0, _, s0 = compiles.snapshot()
        t0 = time.perf_counter()
        while not jobs or time.perf_counter() - t0 < seconds:
            j = len(jobs) + 1
            columns = inputs.pop(j, None)
            if columns is None:
                t = time.perf_counter()
                columns = traffic.make_job(mix, seed, j)
                wait_s += time.perf_counter() - t
            out_dir = os.path.join(work, f"job{j}")
            traced = profile is not None and j == 1
            t = time.perf_counter()
            try:
                with profile.job() if traced else contextlib.nullcontext():
                    run_job(columns, out_dir, config)
                ok = True
            except Exception:  # noqa: BLE001 — a failed job is reported
                ok = False
                traceback.print_exc(file=sys.stderr)
            ran.append((columns, out_dir))
            jobs.append({"job": j, "rows": len(columns["latitude"]),
                         "wall_s": time.perf_counter() - t, "ok": ok})
            if traced:
                profile.stop()
        t1 = time.perf_counter()
        c1, _, s1 = compiles.snapshot()

        # -- after the window: memory, trace, correctness, clean-up.
        peak = memory_peak_bytes(cell.chips)
        run_record = {
            "setup_s": t0 - t_start,
            "window_s": t1 - t0,
            "rows": sum(job["rows"] for job in jobs if job["ok"]),
            "jobs": jobs,
            "window_compiles": c1 - c0,
            "window_compile_s": s1 - s0,
            "input_wait_s": wait_s,
        }
        device = {"memory_peak_bytes": peak}
        breakdown = None
        if trace:
            records, events = telemetry.close()
            run_record.update(spans=records, events=events)
            if jobs[0]["ok"]:
                columns, out_dir = ran[0]
                prof, breakdown = profile.reduce(
                    records, n_emissions(columns, config),
                    compare.level_rows(out_dir), config.weighted,
                    jax.devices()[0].device_kind)
                run_record["profile"] = prof
                device.update(busy_s=prof["busy_s"],
                              window_s=prof["window_s"])
        # A job that failed is compared like any other: its output is
        # missing or partial.
        t_check = time.perf_counter()
        pick = np.random.default_rng(
            [seed % (1 << 64), CHECK_STREAM]).integers(len(ran))
        columns, out_dir = ran[pick]
        names, ref = reference.reference_levels(columns, cell.config["job"])
        readings = compare.compare(out_dir, names, ref, cell.config["job"])
        correct, checks = compare.verdict(readings, cell.config["limits"])
        failed = sum(not job["ok"] for job in jobs)
        print(f"benchmark: set-up {run_record['setup_s']:.3f} s (warm job "
              f"{warm_s:.3f} s, {setup_compiles[0]} compiles, "
              f"{setup_compiles[1]} cache hits, {setup_compiles[2]:.3f} s "
              f"in compile events); window {run_record['window_s']:.3f} s, "
              f"{len(jobs)} jobs ({', '.join(repr(j['wall_s']) for j in jobs)}"
              f" s), {c1 - c0} compiles ({s1 - s0:.3f} s), "
              f"input wait {wait_s:.3f} s; trace and check "
              f"{t_check - t1:.3f} + {time.perf_counter() - t_check:.3f} s",
              file=sys.stderr, flush=True)
    return {
        "correct": bool(correct and failed == 0),
        "attempted": len(jobs),
        "failed": failed,
        "run": {**run_record, "weighted": config.weighted},
        "device": device,
        "breakdown": breakdown,
        "checks": checks,
    }
