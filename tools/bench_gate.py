#!/usr/bin/env python
"""Bench trend gate: fold BENCH artifacts into series, fail on regression.

Reads every bench artifact the repo's tooling writes —

- ``BENCH_delta.json``  (tools/bench_delta.py): per-ratio incremental
  apply seconds (lower is better) and full/incremental speedup;
- ``BENCH_serve.json``  (tools/load_gen.py): rps (higher) and p99
  latency ms (lower), plus the fleet scaling curve
  (``serve:fleet:rps[N]`` / ``p99_ms[N]``), kill-one-backend
  availability when ``--fleet`` was run, the flight-recorder A/B
  tax (``obs:recorder_overhead_pct``, lower, noise-floored at 5%),
  the telemetry-sampler A/B tax (``obs:telemetry_overhead_pct``,
  lower, same 5% floor) with the dashboard's ``/series`` polling
  latency (``obs:series_query_p99_ms``, lower, 1 ms floor),
  and — when ``--cold-vs-warm`` ran — the tilefs restart A/B
  (``serve:cold_p99_ms[cold|warmed]`` lower, the cold/warmed
  ``serve:cold_warm_speedup`` higher) plus the mapped/heap fleet
  memory ratio (``serve:fleet_rss_ratio``, lower);
- ``BENCH_adaptive.json`` (tools/load_gen.py --adaptive): overload-
  stage availability for the brownout ramp, controller on and off
  (``adaptive:availability[on|off]``, higher), and the hot-stage p99
  with the ladder active (``adaptive:p99_ms[on]``, lower);
- ``BENCH_ingest.json`` (tools/bench_ingest.py): per micro-batch and
  padding mode, sustained points/sec (higher), ingest->servable p99
  lag ms (lower), and the feeder's transfer-overlap share
  (``ingest:feed_overlap_pct[...]``, higher, noise-floored at 50%);
- ``BENCH_writeplane.json`` (tools/bench_writeplane.py): per writer
  count, multi-writer drain points/sec (``writeplane:pts_per_s[N]``,
  higher) and enqueue->servable p50 lag seconds
  (``writeplane:lag_p50_s[N]``, lower; cells that failed the byte gate
  against the single-writer reference are never folded);
- ``BENCH_synopsis.json`` (tools/bench_synopsis.py): wavelet-synopsis
  exact/synopsis bytes ratio (higher) and pair decode p99 ms (lower);
- ``BENCH_query.json`` (tools/bench_query.py): per-op integral-path
  /query p99 ms (lower), the integral-vs-fallback sum speedup
  (``query:speedup_p99[sum]``, higher — the acceptance bar is >= 10x
  on a warmed store), and fleet-router query RPS (higher) with its
  p99 (lower);
- ``BENCH_temporal.json`` (tools/bench_temporal.py): temporal-plane
  fold p99 ms per cut kind (``temporal:fold_p99_ms[...]``, lower),
  predicate-retraction rows/sec (``temporal:retract_rows_per_s``,
  higher), and ``op=topk_growth`` evaluator p99 ms (lower); nothing
  is folded when the all-time or retraction byte gate failed;
- ``BENCH_partition.json`` (tools/bench_job.py --partition-sweep):
  Morton-range vs uniform-DP modeled merge-volume ratio per dataset
  (``partition:merge_ratio[...]``, higher), the Morton leg's wall
  seconds (lower), and the Zipf plan's skew ratio (lower; rows that
  failed the byte gate are never folded);

— prints the folded trend table, and exits non-zero when the newest
value of any series regresses more than ``--threshold`` (default 15%)
against the best prior round of the same series. Missing artifacts and
series with no prior point are reported and skipped, never failed: the
gate only compares what has actually been measured twice.

The artifacts are single snapshots, so their history lives in a state
file (``--state``, default BENCH_trend.json): pass ``--update`` to fold
the current values in after a green run (CI does compare-only).

    python tools/bench_gate.py [--threshold 0.15] [--update]
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def _load(path: str):
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        return None
    except (OSError, json.JSONDecodeError) as e:
        print(f"bench_gate: skipping unreadable {path}: {e}",
              file=sys.stderr)
        return None


def snapshot_metrics(root: str) -> dict:
    """``{series_key: (value, higher_is_better)}`` from the snapshot
    artifacts (delta + serve benches)."""
    out: dict = {}
    doc = _load(os.path.join(root, "BENCH_delta.json"))
    if isinstance(doc, dict):
        for row in doc.get("results", []):
            ratio = row.get("ratio")
            if ratio is None:
                continue
            if isinstance(row.get("incremental_apply_s"), (int, float)):
                out[f"delta:apply_s[{ratio}]"] = (
                    float(row["incremental_apply_s"]), False)
            if isinstance(row.get("speedup"), (int, float)):
                out[f"delta:speedup[{ratio}]"] = (float(row["speedup"]),
                                                  True)
    doc = _load(os.path.join(root, "BENCH_serve.json"))
    if isinstance(doc, dict):
        if isinstance(doc.get("rps"), (int, float)):
            out["serve:rps"] = (float(doc["rps"]), True)
        p99 = (doc.get("latency_ms") or {}).get("p99")
        if isinstance(p99, (int, float)):
            out["serve:p99_ms"] = (float(p99), False)
        # Fleet scaling curve + kill-one availability (load_gen --fleet).
        fleet = doc.get("fleet") or {}
        for row in fleet.get("curve", []):
            n = row.get("n")
            if n is None:
                continue
            if isinstance(row.get("rps"), (int, float)):
                out[f"serve:fleet:rps[{n}]"] = (float(row["rps"]), True)
            p99 = (row.get("latency_ms") or {}).get("p99")
            if isinstance(p99, (int, float)):
                out[f"serve:fleet:p99_ms[{n}]"] = (float(p99), False)
        kill = fleet.get("kill_one") or {}
        if isinstance(kill.get("availability"), (int, float)):
            out["serve:fleet:kill_one_availability"] = (
                float(kill["availability"]), True)
        # Flight-recorder A/B tax (load_gen._recorder_overhead). Floored
        # at 5% before the relative comparison: the honest value hovers
        # near zero where bench noise would make a ratio gate flap, so
        # the gate only alarms once the recorder costs real throughput
        # (> 5% * (1 + threshold)). The raw value stays in
        # BENCH_serve.json.
        pct = (doc.get("obs") or {}).get("recorder_overhead_pct")
        if isinstance(pct, (int, float)):
            out["obs:recorder_overhead_pct"] = (max(float(pct), 5.0),
                                                False)
        # Telemetry-sampler A/B tax (load_gen._telemetry_overhead) under
        # the same 5% noise floor — the sampler is a background thread
        # with zero hot-path hooks, so any real regression here means
        # someone wired telemetry into the request path. The /series
        # query latency rides along: the dashboard polls it every few
        # seconds, so it must stay interactive.
        pct = (doc.get("obs") or {}).get("telemetry_overhead_pct")
        if isinstance(pct, (int, float)):
            out["obs:telemetry_overhead_pct"] = (max(float(pct), 5.0),
                                                 False)
        q99 = (((doc.get("obs") or {}).get("series_query_ms") or {})
               .get("p99"))
        if isinstance(q99, (int, float)):
            out["obs:series_query_p99_ms"] = (max(float(q99), 1.0),
                                              False)
        # tilefs cold-vs-warmed restart A/B (load_gen --cold-vs-warm):
        # first-touch p99 for both legs, the cold/warmed speedup (the
        # ISSUE bar is warmed materially below cold — a shrinking
        # speedup means the disk tier + prewarm stopped earning their
        # keep), and the fleet Pss ratio of N mmap'd backends vs N
        # heap backends (sub-linear fleet memory; lower is better).
        cw = doc.get("cold_warm") or {}
        for leg in ("cold", "warmed"):
            p99 = ((cw.get(leg) or {}).get("latency_ms") or {}).get("p99")
            if isinstance(p99, (int, float)):
                out[f"serve:cold_p99_ms[{leg}]"] = (float(p99), False)
        if isinstance(cw.get("speedup_p99"), (int, float)):
            out["serve:cold_warm_speedup"] = (float(cw["speedup_p99"]),
                                              True)
        ratio = (doc.get("fleet_rss") or {}).get("pss_ratio")
        if isinstance(ratio, (int, float)):
            out["serve:fleet_rss_ratio"] = (float(ratio), False)
    doc = _load(os.path.join(root, "BENCH_adaptive.json"))
    if isinstance(doc, dict):
        # Brownout ramp (load_gen --adaptive): availability over the
        # overload stages for both legs — the controller-on leg must
        # not quietly lose ground, and the controller-off leg anchors
        # what the same ramp does without the ladder — plus the hot
        # p99 with the ladder active.
        for leg in ("on", "off"):
            row = (doc.get("legs") or {}).get(leg) or {}
            if isinstance(row.get("overload_availability"), (int, float)):
                out[f"adaptive:availability[{leg}]"] = (
                    float(row["overload_availability"]), True)
        p99 = ((doc.get("legs") or {}).get("on") or {}).get(
            "overload_p99_ms")
        if isinstance(p99, (int, float)):
            out["adaptive:p99_ms[on]"] = (float(p99), False)
    doc = _load(os.path.join(root, "BENCH_ingest.json"))
    if isinstance(doc, dict):
        for row in doc.get("results", []):
            batch, mode = row.get("micro_batch"), row.get("mode")
            if batch is None or mode is None:
                continue
            cell = f"{batch},{mode}"
            if isinstance(row.get("pts_per_s"), (int, float)):
                out[f"ingest:pts_per_s[{cell}]"] = (
                    float(row["pts_per_s"]), True)
            p99 = (row.get("lag_ms") or {}).get("p99")
            if isinstance(p99, (int, float)):
                out[f"ingest:lag_p99_ms[{cell}]"] = (float(p99), False)
            # Feeder overlap (pipeline/feeder.py): the share of
            # host->device transfer time hidden behind tick compute
            # must not quietly collapse. Floored at 50% before the
            # relative comparison: on CPU the transfer is near-free
            # and the honest value hovers anywhere in 0..100 where a
            # ratio gate would flap; the raw value stays in
            # BENCH_ingest.json.
            if isinstance(row.get("feed_overlap_pct"), (int, float)):
                out[f"ingest:feed_overlap_pct[{cell}]"] = (
                    max(float(row["feed_overlap_pct"]), 50.0), True)
    doc = _load(os.path.join(root, "BENCH_partition.json"))
    if isinstance(doc, dict):
        # Morton-range sharding A/B (bench_job --partition-sweep): the
        # modeled merge-volume ratio must not shrink, the Morton wall
        # time must not regress, and the Zipf plan's skew must stay
        # bounded (the ISSUE gate is <= 2.0 after re-splitting).
        for row in doc.get("results", []):
            ds = row.get("dataset")
            if ds is None or not row.get("byte_identical"):
                continue
            if isinstance(row.get("merge_ratio"), (int, float)):
                out[f"partition:merge_ratio[{ds}]"] = (
                    float(row["merge_ratio"]), True)
            wall = (row.get("wall_s") or {}).get("morton")
            if isinstance(wall, (int, float)):
                out[f"partition:wall_s[{ds}]"] = (float(wall), False)
            if ds == "zipf" and isinstance(row.get("skew_ratio"),
                                           (int, float)):
                out["partition:skew_ratio[zipf]"] = (
                    float(row["skew_ratio"]), False)
    doc = _load(os.path.join(root, "BENCH_writeplane.json"))
    if isinstance(doc, dict):
        # Partitioned write plane (bench_writeplane): per writer count,
        # drain throughput (higher) and enqueue->servable p50 lag
        # seconds (lower); cells that failed the byte gate against the
        # single-writer reference are never folded.
        for row in doc.get("results", []):
            n = row.get("writers")
            if n is None or not row.get("byte_identical"):
                continue
            if isinstance(row.get("pts_per_s"), (int, float)):
                out[f"writeplane:pts_per_s[{n}]"] = (
                    float(row["pts_per_s"]), True)
            p50 = (row.get("lag_s") or {}).get("p50")
            if isinstance(p50, (int, float)):
                out[f"writeplane:lag_p50_s[{n}]"] = (float(p50), False)
    doc = _load(os.path.join(root, "BENCH_synopsis.json"))
    if isinstance(doc, dict):
        ratio = (doc.get("compression") or {}).get("bytes_ratio")
        if isinstance(ratio, (int, float)):
            out["synopsis:bytes_ratio"] = (float(ratio), True)
        p99 = ((doc.get("decode") or {}).get("decode_ms") or {}).get("p99")
        if isinstance(p99, (int, float)):
            out["synopsis:decode_p99"] = (float(p99), False)
    doc = _load(os.path.join(root, "BENCH_query.json"))
    if isinstance(doc, dict):
        # Range-query engine (bench_query): integral-path latency per
        # op, the sum A/B speedup (the ISSUE bar is >= 10x), and the
        # fleet-router throughput leg.
        for op, row in (doc.get("direct") or {}).items():
            p99 = (row.get("integral_ms") or {}).get("p99")
            if isinstance(p99, (int, float)):
                out[f"query:{op}_p99_ms"] = (float(p99), False)
            if op == "sum" and isinstance(row.get("speedup_p99"),
                                          (int, float)):
                out["query:speedup_p99[sum]"] = (
                    float(row["speedup_p99"]), True)
        router = doc.get("router") or {}
        if isinstance(router.get("rps"), (int, float)):
            out["query:router_rps"] = (float(router["rps"]), True)
        p99 = (router.get("latency_ms") or {}).get("p99")
        if isinstance(p99, (int, float)):
            out["query:router_p99_ms"] = (float(p99), False)
    doc = _load(os.path.join(root, "BENCH_temporal.json"))
    if isinstance(doc, dict):
        # Temporal plane (bench_temporal): fold latency per cut kind
        # and growth-query latency (lower), retraction throughput
        # (higher). The all-time byte gate guards every cell — a fast
        # fold that diverged from the un-bucketed overlay is a bug,
        # not a win.
        if doc.get("alltime_byte_identical"):
            for cut, row in (doc.get("fold") or {}).items():
                p99 = (row.get("ms") or {}).get("p99")
                if isinstance(p99, (int, float)):
                    out[f"temporal:fold_p99_ms[{cut}]"] = (float(p99),
                                                           False)
            p99 = ((doc.get("growth") or {}).get("ms") or {}).get("p99")
            if isinstance(p99, (int, float)):
                out["temporal:growth_p99_ms"] = (float(p99), False)
        retract = doc.get("retract") or {}
        if retract.get("byte_identical") and isinstance(
                retract.get("rows_per_s"), (int, float)):
            out["temporal:retract_rows_per_s"] = (
                float(retract["rows_per_s"]), True)
    return out


def regression(best_prior: float, current: float,
               higher_is_better: bool) -> float:
    """Fractional regression of ``current`` vs ``best_prior`` (>0 means
    worse); best_prior must be > 0."""
    if higher_is_better:
        return (best_prior - current) / best_prior
    return (current - best_prior) / best_prior


def main() -> int:
    ap = argparse.ArgumentParser(
        description="fail when the newest bench round regresses >15%")
    ap.add_argument("--root", default=".",
                    help="directory holding the BENCH artifacts")
    ap.add_argument("--threshold", type=float, default=0.15,
                    help="max tolerated fractional regression")
    ap.add_argument("--state", default="BENCH_trend.json",
                    help="trend history for the snapshot artifacts "
                    "(relative to --root)")
    ap.add_argument("--update", action="store_true",
                    help="fold current snapshot values into --state "
                    "after a green comparison")
    args = ap.parse_args()

    failures, compared, skipped = [], 0, 0

    # Snapshot artifacts vs the recorded trend state.
    state_path = os.path.join(args.root, args.state)
    state = _load(state_path) if os.path.exists(state_path) else None
    history = state.get("series", {}) if isinstance(state, dict) else {}
    current = snapshot_metrics(args.root)
    for key, (value, higher) in sorted(current.items()):
        prior = [v for v in history.get(key, [])
                 if isinstance(v, (int, float)) and v > 0]
        if not prior:
            skipped += 1
            print(f"  {key:32s} {value:g}  (no prior; skipped)")
            continue
        best = max(prior) if higher else min(prior)
        reg = regression(best, value, higher)
        compared += 1
        verdict = "REGRESSION" if reg > args.threshold else "ok"
        print(f"  {key:32s} {value:g} vs best {best:g}  "
              f"({-reg:+.1%})  {verdict}")
        if reg > args.threshold:
            failures.append(key)

    if failures:
        print(f"bench_gate: FAIL — {len(failures)} series regressed "
              f"past {args.threshold:.0%}: {', '.join(failures)}")
        return 1
    if args.update and current:
        for key, (value, _higher) in current.items():
            history.setdefault(key, []).append(value)
        tmp = state_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"series": history}, f, indent=2, sort_keys=True)
            f.write("\n")
        os.replace(tmp, state_path)
        print(f"bench_gate: folded {len(current)} series into "
              f"{state_path}")
    print(f"bench_gate: ok ({compared} compared, {skipped} skipped)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
