"""Driver of planet GPS-trace backfill traffic: whole jobs of whole GPS
traces through ``run_job`` on every chip of the host, one job at a time.

The generator makes each job's rows from ``(seed, job index)`` and the
mix alone (``traffic/<name>.json``, parameters in :data:`REQUIRED`):

- population centres: ``cities`` points drawn once per mix from
  ``city_seed`` (uniform longitude, latitude uniform in the sine over
  ``city_lat_deg``), weighted by Zipf(``city_zipf``) over their rank;
- traces: each starts near a centre drawn by weight (Gaussian offset of
  ``start_spread_km``), lasts a whole number of fixes drawn uniformly
  from ``trace_points`` (then scaled so that the job holds exactly
  ``points_per_job`` points), moves at one of ``speeds_mps`` (drawn
  with ``speed_shares``) one fix every ``fix_interval_s``, and turns by
  a Gaussian ``turn_sd_rad`` a fix. Fixes along a walk share z21 tiles;
- uploaders: a job holds exactly ``uploaders_per_job`` of the pool's
  ``uploaders`` ids, drawn by Zipf(``uploader_zipf``) over the pool's
  rank without replacement; each of them uploads at least one trace,
  and the other traces go to them by the same weights. A fixed count
  keeps the job's group count, a static size of the cascade, fixed by
  the mix. No id starts with ``x`` or ``rt-`` and no row is background.

The window follows ``backfill.py``'s rules and helpers: a fixed warm job
(job 0 of seed 0) compiles or loads every program, the window's own
compiles stay out of the persistent cache, whole jobs run until
``seconds`` have passed, and one job of the window, drawn from the seed,
is compared with ``reference.py``.

With ``trace`` the profiler records the window's first job, and its
device time is reduced PER CHIP (:func:`per_chip`): the cascade
program's device time and the time under each named scope as the
largest over the chips, the idle share as the mean of each chip's own.
The cascade's compiled HLO text, which names each operation's scope, is
taken from the program's own jit entry after the job (no compile: the
process holds the executable).
"""

from __future__ import annotations

import contextlib
import os
import shutil
import sys
import tempfile
import time
import traceback
from collections import defaultdict

import numpy as np

from benchmark import compare, devtrace, reference, scopes
from benchmark.backfill import (
    CASCADE_PROGRAM,
    CHECK_STREAM,
    MAX_PREMADE,
    WARM_SEED,
    Telemetry,
    job_config,
    memory_peak_bytes,
    run_job,
)
from benchmark.compiles import CompileCounter

REQUIRED = ("points_per_job", "cities", "city_zipf", "city_seed",
            "city_lat_deg", "start_spread_km", "trace_points",
            "fix_interval_s", "speeds_mps", "speed_shares", "turn_sd_rad",
            "uploaders", "uploader_zipf", "uploaders_per_job", "t0_ms")
EARTH_M_PER_DEG = 111_320.0
FOREGROUND = "gps"
#: The named scopes of the mesh program, in program order.
MESH_SCOPES = ("mesh.route", "mesh.detail", "mesh.boundary", "mesh.egress")


def check(mix: dict) -> None:
    for key in REQUIRED:
        if key not in mix:
            raise ValueError(f"traces traffic: no {key!r}")
    if len(mix["speeds_mps"]) != len(mix["speed_shares"]):
        raise ValueError("traces traffic: speeds_mps and speed_shares differ "
                         "in length")
    lo, hi = mix["trace_points"]
    if not 1 <= lo <= hi:
        raise ValueError(f"traces traffic: trace_points {lo}..{hi}")
    if not 1 <= mix["uploaders_per_job"] <= mix["uploaders"]:
        raise ValueError("traces traffic: uploaders_per_job must lie in "
                         "1..uploaders")


def zipf_weights(n: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1) ** s
    return w / w.sum()


def cities(mix: dict) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(latitude, longitude, weight) of the mix's population centres."""
    rng = np.random.default_rng(int(mix["city_seed"]))
    lo, hi = np.radians(mix["city_lat_deg"])
    lat = np.degrees(np.arcsin(rng.uniform(np.sin(lo), np.sin(hi),
                                           mix["cities"])))
    lon = rng.uniform(-180.0, 180.0, mix["cities"])
    return lat, lon, zipf_weights(mix["cities"], mix["city_zipf"])


def job_rng(seed: int, job: int) -> np.random.Generator:
    # SeedSequence takes non-negative integers of any size.
    return np.random.default_rng([seed % (1 << 64), job])


def trace_lengths(mix: dict, rng) -> np.ndarray:
    """Fixes per trace, summing to exactly ``points_per_job``."""
    n = int(mix["points_per_job"])
    lo, hi = mix["trace_points"]
    count = max(1, round(n / ((lo + hi) / 2)))
    raw = rng.integers(lo, hi + 1, count).astype(np.float64)
    lengths = np.maximum(np.floor(raw * n / raw.sum()), 1).astype(np.int64)
    lengths[:n - lengths.sum()] += 1  # what the floors left, one a trace
    return lengths


def make_job(mix: dict, seed: int, job: int) -> dict:
    """The columns of job ``job`` of run ``seed``."""
    check(mix)
    rng = job_rng(seed, job)
    lengths = trace_lengths(mix, rng)
    n_traces, n = len(lengths), int(lengths.sum())
    if n_traces < mix["uploaders_per_job"]:
        raise ValueError(f"{n_traces} traces cannot hold all "
                         f"{mix['uploaders_per_job']} uploaders of a job")
    clat, clon, cw = cities(mix)
    home = rng.choice(len(cw), n_traces, p=cw)
    spread = mix["start_spread_km"] * 1000.0 / EARTH_M_PER_DEG
    lat0 = np.clip(clat[home] + rng.normal(0.0, spread, n_traces), -80, 80)
    lon0 = clon[home] + rng.normal(0.0, spread, n_traces)
    speed = np.asarray(mix["speeds_mps"], np.float64)[rng.choice(
        len(mix["speeds_mps"]), n_traces, p=mix["speed_shares"])]
    step = speed * mix["fix_interval_s"]

    # Per fix: the heading turns a little, the fix moves one step.
    trace = np.repeat(np.arange(n_traces), lengths)
    first = np.concatenate([[0], np.cumsum(lengths)[:-1]])
    turn = rng.normal(0.0, mix["turn_sd_rad"], n)
    turn[first] = rng.uniform(0.0, 2 * np.pi, n_traces)
    heading = _within_trace_cumsum(turn, first, lengths)
    north = _within_trace_cumsum(step[trace] * np.cos(heading), first,
                                 lengths)
    east = _within_trace_cumsum(step[trace] * np.sin(heading), first,
                                lengths)
    lat = lat0[trace] + north / EARTH_M_PER_DEG
    lon = lon0[trace] + east / (EARTH_M_PER_DEG
                                * np.cos(np.radians(lat0[trace])))
    lon = (lon + 180.0) % 360.0 - 180.0

    # The job's uploaders: a fixed number, heavy contributors likelier.
    pool_w = zipf_weights(mix["uploaders"], mix["uploader_zipf"])
    chosen = rng.choice(mix["uploaders"], mix["uploaders_per_job"],
                        replace=False, p=pool_w)
    who = rng.choice(chosen, n_traces, p=pool_w[chosen] / pool_w[chosen].sum())
    who[rng.permutation(n_traces)[:len(chosen)]] = chosen
    pool = np.array([f"user-{i}" for i in range(mix["uploaders"])],
                    dtype=object)

    start_s = rng.integers(0, 86_400, n_traces)
    offset = np.arange(n) - first[trace]
    return {
        "latitude": lat,
        "longitude": lon,
        "user_id": pool[who][trace],
        "source": np.full(n, FOREGROUND, dtype=object),
        "timestamp": (mix["t0_ms"] + (start_s[trace] + offset
                                      * mix["fix_interval_s"]) * 1000),
        "value": np.ones(n),
    }


def _within_trace_cumsum(x: np.ndarray, first: np.ndarray,
                         lengths: np.ndarray) -> np.ndarray:
    """Running sum of ``x`` that restarts at each trace's first fix."""
    total = np.cumsum(x)
    before = np.concatenate([[0.0], total[first[1:] - 1]])
    return total - np.repeat(before, lengths)


# -- the profile, per chip ----------------------------------------------------


class CascadeText:
    """Keeps the arguments of the cascade's jit entry while installed,
    and gives the compiled program's HLO text after the call."""

    def __init__(self):
        from heatmap_tpu.pipeline import cascade as cascade_mod

        self.cascade = cascade_mod
        self.call = None

    def _keep(self, entry):
        import jax

        def spec(a):
            # An array on one device is left to the default placement,
            # as the call left it.
            if isinstance(a, jax.Array):
                spread = len(a.sharding.device_set) > 1
                return jax.ShapeDtypeStruct(
                    a.shape, a.dtype, sharding=a.sharding if spread else None)
            return a

        def call(*args, **kwargs):
            self.call = (getattr(entry, "__wrapped__", entry),
                         [spec(a) for a in args],
                         {k: spec(v) for k, v in kwargs.items()})
            return entry(*args, **kwargs)

        return call

    @contextlib.contextmanager
    def installed(self):
        mod = self.cascade
        real_jit = mod._build_cascade_jit
        real_get = getattr(mod, "_get_donating_cascade_jit", None)
        mod._build_cascade_jit = self._keep(real_jit)
        if real_get is not None:
            mod._get_donating_cascade_jit = lambda: self._keep(real_get())
        try:
            yield self
        finally:
            mod._build_cascade_jit = real_jit
            if real_get is not None:
                mod._get_donating_cascade_jit = real_get

    def text(self) -> str | None:
        if self.call is None:
            return None
        fn, args, kwargs = self.call
        return fn.lower(*args, **kwargs).compile().as_text()


def per_chip(ops: list[dict], start: float, end: float, chips: int) -> dict:
    """The profile of one job from its operations, chip by chip.

    ``ops`` are ``scopes.read_ops`` records (``device``, ``module``,
    ``op_name``, times in ns) clipped to ``[start, end)``. Returns, in
    seconds: ``cascade_device_s`` and ``scope_s`` (each named scope's
    device time) as the largest over the chips, ``idle_pct`` as the mean
    over ``chips`` chips of each chip's own idle share, and the per-chip
    lists they come from. A chip that ran nothing is idle throughout.
    """
    window_ns = end - start
    by_device = defaultdict(list)
    for op in ops:
        by_device[op["device"]].append(op)
    busy, cascade, scope = [], [], []
    for device in sorted(by_device):
        device_ops = by_device[device]
        busy.append(devtrace.busy_ns(device_ops) / 1e9)
        mine = [op for op in device_ops
                if op["module"] and CASCADE_PROGRAM in op["module"]]
        cascade.append(devtrace.busy_ns(mine) / 1e9)
        split = scopes.scope_ns(mine)
        scope.append({name: scopes.under(split, name) / 1e9
                      for name in MESH_SCOPES})
    idle = [100.0 * (1.0 - b * 1e9 / window_ns) for b in busy]
    idle += [100.0] * (chips - len(idle))
    return {
        "window_s": window_ns / 1e9,
        "chips": len(busy),
        "busy_s": busy,
        "cascade_device_s": max(cascade, default=0.0),
        "cascade_device_s_per_chip": cascade,
        "scope_s": {name: max((s[name] for s in scope), default=0.0)
                    for name in MESH_SCOPES},
        "scope_s_per_chip": scope,
        "idle_pct": sum(idle) / len(idle) if idle else 100.0,
    }


class Profile:
    """The profiler around one job, reduced per chip after it."""

    def __init__(self, log_dir: str):
        import jax

        self.jax = jax
        self.log_dir = log_dir
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=options)

    def job(self):
        return self.jax.profiler.TraceAnnotation("bench.job")

    def stop(self) -> None:
        self.jax.profiler.stop_trace()

    def reduce(self, hlo_text: str | None, rows: int,
               device_kind: str, chips: int) -> tuple[dict, dict]:
        """-> (what the per-layer readers take, the breakdown)."""
        path = devtrace.find_xplane(self.log_dir)
        marks = {m["name"]: m for m in devtrace.read(path)["marks"]}
        job = marks["bench.job"]
        ops = devtrace.clip(scopes.read_ops(path, hlo_text or "",
                                            CASCADE_PROGRAM),
                            job["start"], job["end"])
        profile = per_chip(ops, job["start"], job["end"], chips)
        if hlo_text is None:
            profile["scope_s"] = {}
        profile.update(rows=rows, device_kind=device_kind,
                       mesh_chips=chips, n_ops=len(ops))
        top = sorted(devtrace.totals(devtrace.outermost(
            [{**op, "name": devtrace.short_name(op["op"], op["module"])}
             for op in ops])).items(), key=lambda kv: -kv[1])[:10]
        breakdown = {"device_ops": [[name, ns / 1e9 / max(1, profile["chips"])]
                                    for name, ns in top],
                     "scope_s_per_chip": profile["scope_s_per_chip"]}
        return profile, breakdown


# -- the window -----------------------------------------------------------------


def require_routed_exchange() -> None:
    """Refuse, in a second, a program whose Morton range program is not
    owner-routed: one that replicates the whole batch on every chip
    cannot hold a 2^24-point job (it needs 20.3 GB a chip for a 2x2
    v5e, and its compile runs minutes before it says so)."""
    from heatmap_tpu.parallel import partition

    if not hasattr(partition, "route_counts"):
        raise SystemExit(
            "traces: this program's Morton range program has no owner-"
            "routed exchange (parallel.partition.route_counts); it "
            "replicates the batch on every chip and cannot hold this "
            "cell's jobs")


def run(cell, seed: int, seconds: float, trace: bool, t_start: float) -> dict:
    import jax

    require_routed_exchange()
    config = job_config(cell)
    mix = cell.traffic
    compiles = CompileCounter().install()
    with tempfile.TemporaryDirectory(prefix="heatmap-bench-") as work:
        # -- set-up: the warm job compiles or loads every program.
        warm_dir = os.path.join(work, "warm")
        t = time.perf_counter()
        run_job(make_job(mix, WARM_SEED, 0), warm_dir, config)
        warm_s = time.perf_counter() - t
        shutil.rmtree(warm_dir)
        setup_compiles = compiles.snapshot()
        n_premade = min(MAX_PREMADE, 2 + int(seconds // warm_s))
        inputs = {j: make_job(mix, seed, j) for j in range(1, 1 + n_premade)}
        telemetry = Telemetry(work) if trace else None
        profile = Profile(os.path.join(work, "profile")) if trace else None
        text = CascadeText()
        # What the window compiles depends on its jobs' rows; left out
        # of the persistent cache, it is compiled in every run.
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
                columns = make_job(mix, seed, j)
                wait_s += time.perf_counter() - t
            out_dir = os.path.join(work, f"job{j}")
            traced = profile is not None and j == 1
            t = time.perf_counter()
            try:
                with (profile.job() if traced else contextlib.nullcontext()), \
                        (text.installed() if traced
                         else contextlib.nullcontext()):
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
                prof, breakdown = profile.reduce(
                    text.text(), jobs[0]["rows"],
                    jax.devices()[0].device_kind, cell.chips)
                run_record["profile"] = prof
                device.update(busy_s=max(prof["busy_s"], default=0.0),
                              window_s=prof["window_s"])
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
              f"input wait {wait_s:.3f} s; peak {peak!r} B; trace and check "
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
