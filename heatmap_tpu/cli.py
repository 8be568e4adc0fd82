"""Command-line entry: the TPU-native ``submit-heatmap``.

A real flag system replacing the reference's three config mechanisms —
hard-coded module constants (reference heatmap.py:16-23), env vars
(reference heatmap.py:141-142), and spark-submit ``--conf`` flags
(reference submit-heatmap:7-14). ``--backend`` selects the device
platform (the BASELINE.json ``--backend=tpu`` switch); source/sink
specs replace the Cassandra/CosmosDB constants.

Subcommands:

- ``run``   — the batch job (reference batchMain, heatmap.py:152-158):
              source -> cascade -> blob sink.
- ``tiles`` — dense-window binning -> z/x/y PNG tile tree (new egress
              surface, BASELINE.md config 3).
- ``info``  — print resolved config + device inventory as JSON.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _add_backend_flags(p):
    p.add_argument(
        "--backend",
        choices=("tpu", "cpu"),
        default="tpu",
        help="device platform: tpu (default) requires JAX to find a TPU "
        "and fails otherwise; cpu forces the host platform",
    )
    p.add_argument(
        "--no-x64",
        action="store_true",
        help="keep JAX in 32-bit mode (the composite-key cascade needs "
        "x64; only the dense tiles path works without it)",
    )
    p.add_argument(
        "--chaos", default=None, metavar="SPEC",
        help="arm deterministic fault injection (heatmap_tpu.faults), "
        "e.g. 'seed=7,source.read=3x5,sink.write=p0.01'; also read "
        "from $HEATMAP_TPU_CHAOS (flag wins). See docs/robustness.md",
    )


def _require_tpu(jax) -> None:
    """``--backend tpu`` means a TPU: a job the user pinned to the chip
    must not quietly produce CPU results."""
    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"--backend tpu: JAX found no TPU (default platform is "
            f"{platform!r}); run with --backend cpu to use the host")


def _init_backend(args):
    import jax

    from heatmap_tpu import faults
    from heatmap_tpu.utils.compile_cache import enable_compile_cache

    faults.install_from_env(getattr(args, "chaos", None))
    if args.backend == "cpu":
        jax.config.update("jax_platforms", "cpu")
    if not args.no_x64:
        jax.config.update("jax_enable_x64", True)
    enable_compile_cache()
    # A multihost run checks after jax.distributed.initialize(): touching
    # the local backend first would make that initialization fail.
    if args.backend == "tpu" and not getattr(args, "multihost", False):
        _require_tpu(jax)
    return jax


def _sink_spec(spec: str) -> str:
    """argparse type= wrapper: reject a typo'd --output kind at parse
    time (one-line error listing the valid kinds) instead of after
    backend init and ingest."""
    from heatmap_tpu.io.sinks import validate_sink_spec

    try:
        return validate_sink_spec(spec)
    except ValueError as e:
        raise argparse.ArgumentTypeError(str(e)) from e


def _add_run_flags(p):
    p.add_argument("--input", required=True,
                   help="source spec: synthetic:N[:seed] | csv:P | jsonl:P "
                   "| parquet:P | hmpb:P | cassandra:[ENDPOINT] | cosmosdb:")
    p.add_argument("--output", default="jsonl:heatmaps.jsonl",
                   type=_sink_spec,
                   help="sink spec: jsonl:P | dir:P | memory: | "
                   "cassandra: | arrays:DIR (columnar per-level npz)")
    p.add_argument("--detail-zoom", type=int, default=21,
                   help="finest binning zoom (reference MAX_ZOOM_LEVEL + "
                   "DETAIL_ZOOM_DELTA = 21, heatmap.py:16-17,27)")
    p.add_argument("--min-detail-zoom", type=int, default=5,
                   help="cascade floor; detail levels run down to this+1 "
                   "(reference range(21, 5, -1), heatmap.py:109)")
    p.add_argument("--result-delta", type=int, default=5,
                   help="blob tiles are this many zooms coarser than "
                   "detail (reference DETAIL_ZOOM_DELTA, heatmap.py:16)")
    p.add_argument("--timespans", default="alltime",
                   help="comma list of alltime,year,month,day (reference "
                   "supports these but ships alltime-only, heatmap.py:62)")
    p.add_argument("--batch-size", type=int, default=1 << 20)
    p.add_argument("--max-points-in-flight", type=int, default=None,
                   metavar="N",
                   help="bound peak memory: run the cascade per chunk of "
                   "at most N points and merge per-level aggregates "
                   "(exact). Default: auto — sources estimated larger "
                   "than host RAM take the bounded path with a "
                   "RAM-derived chunk; 0 forces single-shot")
    p.add_argument("--merge-spill-dir", default=None, metavar="DIR",
                   help="bounded path only: spill per-chunk aggregates "
                   "to DIR and merge one level at a time at egress, "
                   "bounding the cross-chunk merge table too (for "
                   "near-unique-output shapes; exact results)")
    p.add_argument("--capacity", type=int, default=None,
                   help="unique-key capacity for the device cascade "
                   "(default: #emissions)")
    p.add_argument("--amplify-all", action="store_true",
                   help="reproduce the reference's 'all'-amplification "
                   "cascade quirk (SURVEY.md §8.1) for bit-parity")
    p.add_argument("--first-timespan-only", action="store_true",
                   help="reproduce the reference's early-return timespan "
                   "quirk (SURVEY.md §8.2)")
    p.add_argument("--cascade-backend", default="auto",
                   choices=("auto", "scatter", "partitioned"),
                   help="cascade reduction: auto (default — partitioned "
                   "MXU kernel for count jobs on TPU, scatter for "
                   "weighted jobs and off TPU), or pin either backend "
                   "explicitly")
    p.add_argument("--weighted", action="store_true",
                   help="sum the source's per-point 'value' column into "
                   "the heatmaps instead of counting points (works with "
                   "--fast on HMPB inputs converted from a weighted "
                   "source, and with --max-points-in-flight)")
    p.add_argument("--weight-bound", type=int, default=None, metavar="W",
                   help="declare the bounded-integer weight contract "
                   "(every 'value' an integer in [0, W]) — unlocks the "
                   "partitioned cascade backend for weighted jobs; "
                   "violations surface as capacity overflow, never a "
                   "rounded sum")
    p.add_argument("--data-parallel", choices=("auto", "on", "off"),
                   default="auto",
                   help="cascade data-parallelism over this process's "
                   "local devices: auto (default) engages past "
                   "--dp-min-emissions when >1 device is visible; on "
                   "forces the mesh path at any size; off pins the "
                   "single-device cascade. Blobs are identical either "
                   "way (counts bit-exact; fractional weighted sums up "
                   "to f64 summation order)")
    p.add_argument("--dp-min-emissions", type=int, default=None,
                   metavar="N",
                   help="auto-DP engagement threshold (emissions per "
                   "cascade call; default batch.AUTO_DP_MIN_EMISSIONS "
                   "= 2^18, calibrated on a CPU mesh only). Measure "
                   "the real crossover on your hardware with the "
                   "docs/OPERATIONS.md 'Calibrating auto-DP' recipe; "
                   "auto mode only")
    p.add_argument("--spatial-partition", choices=("auto", "morton", "off"),
                   default="auto",
                   help="Morton-range sharding of the data-parallel "
                   "cascade: each device owns one contiguous Z-order "
                   "code range and the cross-device merge shrinks to "
                   "boundary tiles only (docs/parallel-partitioning.md). "
                   "auto (default) plans ranges when the mesh engages "
                   "on real volume; morton forces it; off pins the "
                   "uniform round-robin dispatch. Blobs byte-identical "
                   "in every mode")
    p.add_argument("--fast", action="store_true",
                   help="force the integer-only native-decoder path "
                   "(csv/hmpb sources; dated timespans use the i64 "
                   "epoch-ms column; needs the native/ build for csv). "
                   "Eligible sources route here AUTOMATICALLY — this "
                   "flag only turns silent fallback into a hard error")
    p.add_argument("--no-fast", action="store_true",
                   help="disable the automatic fast-path routing and "
                   "run the generic per-row ingest")
    p.add_argument("--checkpoint-dir", default=None,
                   help="checkpoint ingest progress here and resume from "
                   "the latest checkpoint on rerun")
    p.add_argument("--checkpoint-every", type=int, default=8,
                   help="checkpoint every N source batches")
    p.add_argument("--profile", default=None, metavar="LOGDIR",
                   help="capture a jax.profiler trace into LOGDIR and "
                   "print the span/throughput report to stderr")
    p.add_argument("--metrics-dir", default=None, metavar="DIR",
                   help="enable the metrics registry and write a "
                   "Prometheus-text dump to DIR/metrics.prom at job end "
                   "(docs/observability.md)")
    p.add_argument("--events", default=None, metavar="PATH",
                   help="append structured run events to PATH (JSONL: "
                   "run_start manifest, stage_end timings with backend "
                   "attribution, backend_resolved, device_memory, "
                   "run_end — docs/observability.md)")
    p.add_argument("--report", nargs="?", const="run_report.json",
                   default=None, metavar="PATH",
                   help="fold tracer + metrics + events into a "
                   "run_report.json artifact at PATH (default "
                   "run_report.json) and print the span/throughput "
                   "table to stderr — no --profile required")
    p.add_argument("--multihost", action="store_true",
                   help="SPMD multi-host job: jax.distributed init, "
                   "per-process ingest shard (connector ranges or batch "
                   "slices), DCN blob merge, process 0 writes the sink; "
                   "single-process falls through to the plain job")
    p.add_argument("--multihost-egress",
                   choices=("auto", "gather", "sharded"), default="auto",
                   help="gather (the auto default): full DCN merge, "
                   "process 0 writes. sharded: blob keys partition "
                   "across processes and EVERY host writes its own sink "
                   "shard (path sinks get a per-host suffix "
                   "automatically) — the scalable reducer-write path; "
                   "required for columnar sinks on pods")
    p.add_argument("--heartbeat-deadline", type=float, default=None,
                   metavar="S",
                   help="arm straggler detection: at each multihost "
                   "phase boundary, a host whose heartbeat is older "
                   "than S seconds raises a typed StragglerTimeout "
                   "(docs/robustness.md) instead of hanging the next "
                   "collective")
    p.add_argument("--on-straggler", choices=("raise", "reassign"),
                   default="raise",
                   help="what a straggler timeout means: raise (the "
                   "default — job dies with the typed error) or "
                   "reassign (elastic execution: the stale host's "
                   "unfinished shards re-run on survivors from the "
                   "--elastic-dir lineage manifest, output "
                   "byte-identical to an unfailed run; needs a "
                   "columnar arrays: output)")
    p.add_argument("--elastic-dir", default=None, metavar="DIR",
                   help="shard-lineage manifest root for "
                   "--on-straggler reassign: completed shards persist "
                   "their partial pyramid here atomically, so finished "
                   "work survives a crash and re-runs are exactly-once "
                   "by shard hash")
    p.add_argument("--elastic-hosts", type=int, default=None, metavar="K",
                   help="simulated host count for elastic execution on "
                   "a single process (default 2); real multi-process "
                   "runs use one host per process")
    _add_trace_flags(p)


def _add_trace_flags(p):
    """--trace-out / --trace-sample / --slo, shared by run, update and
    serve (docs/observability.md)."""
    p.add_argument("--trace-out", default=None, metavar="PATH",
                   help="enable hierarchical span tracing and export "
                   "the span trees as Chrome/Perfetto trace-event JSON "
                   "to PATH at exit (load in chrome://tracing, "
                   "ui.perfetto.dev, or tools/trace_analyze.py)")
    p.add_argument("--trace-sample", type=float, default=1.0, metavar="P",
                   help="probability a new trace root is sampled "
                   "(decided once per root — e.g. per serve request; "
                   "default 1.0 records every trace)")
    p.add_argument("--slo", action="append", default=None, metavar="SPEC",
                   help="declare an SLO as NAME:KIND:k=v,... (kinds: "
                   "latency, error_rate, staleness; repeatable). "
                   "Error-budget burn rates fold into /healthz, the "
                   "run report, and slo_breach events")
    p.add_argument("--flight-recorder-spans", type=int, default=256,
                   metavar="N",
                   help="flight-recorder ring capacity: last N completed "
                   "spans per subsystem kept regardless of head "
                   "sampling, promoted into the trace on errors/5xx/"
                   "tail latency (0 disables the recorder; it only "
                   "arms when --trace-out, --events or --incident-dir "
                   "is also given — docs/observability.md)")
    p.add_argument("--incident-dir", default=None, metavar="DIR",
                   help="flush self-contained incident bundles here on "
                   "failure edges (SLO breach, shed, fault storm, "
                   "degraded-enter, uncaught exception); rate-limited "
                   "and pruned age-wins")
    p.add_argument("--tail-latency-ms", type=float, default=None,
                   metavar="MS",
                   help="tail-based retention threshold: any trace "
                   "slower than this is promoted from the flight "
                   "recorder into the trace as if head-sampled")
    p.add_argument("--telemetry-sample-interval", type=float, default=0.0,
                   metavar="SEC",
                   help="background telemetry sampler cadence: every "
                   "SEC seconds the obs registry is snapshotted into "
                   "the in-process time-series tiers that back "
                   "/series, /dashboard, and incident-bundle history "
                   "(docs/observability.md). 0 (the default) disables "
                   "the sampler entirely — zero threads, zero hot-path "
                   "cost")
    p.add_argument("--watch", action="append", default=None, metavar="SPEC",
                   help="watch a telemetry series for anomalies as "
                   "NAME:k=v,... (params: z, alpha, min_count, "
                   "clear_ratio; repeatable), e.g. "
                   "'ingest_lag_seconds:z=6'. Each rising edge emits "
                   "one anomaly_detected event and triggers an "
                   "incident bundle with the surrounding history "
                   "embedded; requires --telemetry-sample-interval > 0")


def _setup_tracing(args):
    """Wire --trace-out/--trace-sample/--slo; returns the live
    TraceCollector (None with tracing off)."""
    from heatmap_tpu import obs

    collector = None
    if getattr(args, "trace_out", None):
        try:
            collector = obs.enable_tracing(sample=args.trace_sample)
        except ValueError as e:
            raise SystemExit(f"--trace-sample: {e}") from e
    if getattr(args, "slo", None):
        try:
            obs.install_specs(args.slo)
        except ValueError as e:
            raise SystemExit(f"--slo: {e}") from e
    # Flight recorder + incident bundles. The recorder arms only when
    # some telemetry surface exists to promote/flush into, so a plain
    # run keeps every obs hook at None (blob byte-equality pinned by
    # tests/test_obs.py).
    spans = getattr(args, "flight_recorder_spans", 0) or 0
    if spans < 0:
        raise SystemExit(f"--flight-recorder-spans {spans}: must be >= 0")
    incident_dir = getattr(args, "incident_dir", None)
    armed = (collector is not None or incident_dir
             or getattr(args, "events", None))
    if spans and armed:
        tail_ms = getattr(args, "tail_latency_ms", None)
        if tail_ms is not None and tail_ms <= 0:
            raise SystemExit(
                f"--tail-latency-ms {tail_ms}: must be positive")
        obs.recorder.install(obs.FlightRecorder(
            max_spans=spans,
            tail_latency_s=None if tail_ms is None else tail_ms / 1000.0))
    if incident_dir:
        obs.incident.set_manager(obs.IncidentManager(incident_dir))
    # Telemetry sampler + anomaly watch list. Interval 0 (the default)
    # arms nothing: no store installed, no thread started, so the
    # sampler-off path is byte-identical to a build without this
    # subsystem (pinned in tests/test_timeseries.py).
    interval = getattr(args, "telemetry_sample_interval", 0.0) or 0.0
    if interval < 0:
        raise SystemExit(f"--telemetry-sample-interval {interval}: "
                         "must be >= 0")
    watches = getattr(args, "watch", None) or []
    if watches and not interval:
        raise SystemExit("--watch requires --telemetry-sample-interval "
                         "> 0 (detectors score sampler ticks)")
    if interval:
        from heatmap_tpu.obs import anomaly, timeseries

        engine = None
        if watches:
            try:
                specs = [anomaly.parse_watch_spec(s) for s in watches]
            except ValueError as e:
                raise SystemExit(f"--watch: {e}") from e
            engine = anomaly.AnomalyEngine(specs)
            anomaly.set_engine(engine)
        spill_dir = (os.path.join(incident_dir, "telemetry")
                     if incident_dir else None)
        timeseries.arm(interval, engine=engine, spill_dir=spill_dir)
    return collector


def _export_trace(args, collector):
    """End-of-job obs teardown: every command's exit path funnels
    through here, so the telemetry sampler is stopped (with a final
    crash-safe spill) before the trace export — both no-op when the
    respective subsystem was never armed."""
    from heatmap_tpu.obs import timeseries

    timeseries.shutdown()
    if collector is None:
        return
    n = collector.export_chrome(args.trace_out)
    line = {"trace_out": args.trace_out, "span_events": n,
            "dropped": collector.dropped}
    print(json.dumps(line), file=sys.stderr)


def _fail_telemetry(root_span, error):
    """Uncaught job exception: tail-promote the failed root's tree out
    of the flight recorder and flush an exception incident bundle.
    Both no-op when nothing is installed. Must run before end_span on
    the root so the root rides the live-forward path."""
    from heatmap_tpu.obs import incident, recorder

    recorder.maybe_promote(root_span, error=True)
    incident.trigger("exception", detail=repr(error))


def cmd_run(args) -> int:
    from heatmap_tpu.pipeline.timespan import VALID_TYPES

    requested = tuple(t.strip() for t in args.timespans.split(",") if t.strip())
    bad = [t for t in requested if t not in VALID_TYPES]
    if bad:
        raise SystemExit(
            f"--timespans: unknown type(s) {bad}; valid: {', '.join(VALID_TYPES)}"
        )
    _init_backend(args)
    import contextlib

    from heatmap_tpu.io import open_sink, open_source
    from heatmap_tpu.pipeline import (
        BatchJobConfig,
        run_job,
        run_job_fast,
        run_job_resumable,
    )
    from heatmap_tpu.utils.trace import get_tracer, jax_profile

    try:
        config = BatchJobConfig(
            detail_zoom=args.detail_zoom,
            min_detail_zoom=args.min_detail_zoom,
            result_delta=args.result_delta,
            timespans=requested,
            amplify_all=args.amplify_all,
            first_timespan_only=args.first_timespan_only,
            capacity=args.capacity,
            weighted=args.weighted,
            weight_bound=args.weight_bound,
            cascade_backend=args.cascade_backend,
            data_parallel={"auto": None, "on": True, "off": False}[
                args.data_parallel],
            dp_min_emissions=args.dp_min_emissions,
            spatial_partition=args.spatial_partition,
        )
    except ValueError as e:
        raise SystemExit(str(e)) from e
    if args.multihost_egress != "auto" and not args.multihost:
        # A forgotten --multihost would otherwise run the full plain
        # job on EVERY host of a per-host launch script, with all of
        # them writing the same output path.
        raise SystemExit("--multihost-egress requires --multihost")
    if not args.multihost and (args.on_straggler != "raise"
                               or args.elastic_dir or args.elastic_hosts
                               or args.heartbeat_deadline is not None):
        raise SystemExit("--heartbeat-deadline / --on-straggler / "
                         "--elastic-dir / --elastic-hosts require "
                         "--multihost")
    if args.on_straggler == "reassign" and not args.elastic_dir:
        raise SystemExit("--on-straggler reassign needs --elastic-dir "
                         "(the shard-lineage manifest is what makes "
                         "failover re-execution exactly-once)")
    if args.merge_spill_dir and args.checkpoint_dir:
        # The spill merge lives on the bounded path; checkpointing
        # never routes there — ignoring the flag would quietly run the
        # unbounded in-RAM merge the operator asked to avoid.
        # (--multihost composes: each process's bounded slice ingest
        # takes the same spill knob, run_job_multihost validates.)
        raise SystemExit("--merge-spill-dir applies to the bounded "
                         "(chunked) path only; it cannot combine with "
                         "--checkpoint-dir")
    # 0 means "explicitly single-shot", which composes with both
    # checkpointing and multihost; only a positive bound conflicts.
    if args.max_points_in_flight and args.checkpoint_dir:
        raise SystemExit("--max-points-in-flight and --checkpoint-dir are "
                         "mutually exclusive (chunk boundaries are not "
                         "batch boundaries)")
    if args.multihost and (args.fast or args.checkpoint_dir):
        raise SystemExit("--multihost runs the standard job path only "
                         "(not --fast / --checkpoint-dir); "
                         "--max-points-in-flight composes (each process "
                         "streams its slice through the bounded path)")
    fast_source = None
    if args.fast and args.no_fast:
        raise SystemExit("--fast and --no-fast are mutually exclusive")
    if args.fast:
        # Resolve through open_source so bare paths and prefixed specs
        # behave identically to every other subcommand.
        from heatmap_tpu.io.hmpb import HMPBDirSource, HMPBSource
        from heatmap_tpu.io.sources import CSVSource

        src = open_source(args.input, read_value=False)
        if isinstance(src, CSVSource):
            fast_source = src.path
        elif isinstance(src, (HMPBSource, HMPBDirSource)):
            fast_source = src
        else:
            raise SystemExit(
                f"--fast needs a csv or hmpb source, got {args.input!r}"
            )
    elif (not args.no_fast and not args.multihost
          and not args.checkpoint_dir):
        # AUTO fast-path routing: the default ingest should never pay
        # per-row Python when the native/mmap path produces identical
        # blobs (equality pinned by tests/test_cli.py
        # test_run_fast_csv_matches_plain and tests/test_pipeline.py
        # weighted-HMPB tests). Conservative by construction — only
        # configurations those tests cover switch over; --checkpoint-dir
        # stays on the standard resumable path so reruns never change
        # an existing checkpoint's format mid-flight. --no-fast opts
        # out; --fast makes ineligibility a hard error instead.
        # Sniff the spec kind BEFORE constructing anything: opening is
        # not free (an .hmpb probe header-parses and mmaps the whole
        # file), so ineligible kinds (synthetic:, jsonl:, ...) never pay
        # for a probe, and a probe-opened source becomes the job source
        # on every run that proceeds.
        kind = args.input.partition(":")[0]
        is_csv = kind == "csv" or args.input.endswith(".csv")
        is_hmpb = kind == "hmpb" or args.input.endswith(".hmpb")
        if is_csv and not args.weighted:
            try:
                from heatmap_tpu.native import parse_csv_batches  # noqa: F401
            except ImportError:
                pass  # native decoder unavailable: per-row path
            else:
                from heatmap_tpu.io.sources import CSVSource

                src = open_source(args.input, read_value=False)
                if isinstance(src, CSVSource):
                    fast_source = src.path
                src.close()  # only the path is kept either way
        elif is_hmpb:
            from heatmap_tpu.io.hmpb import HMPBDirSource, HMPBSource

            src = open_source(args.input, read_value=False)
            if isinstance(src, (HMPBSource, HMPBDirSource)) and (
                    not args.weighted or getattr(src, "has_value", False)):
                fast_source = src
            else:
                # Probe result discarded (e.g. weighted without a value
                # column): unmap now — the standard path re-opens the
                # input itself.
                src.close()
    if args.multihost:
        # Must run BEFORE anything that initializes the local backend —
        # the profiler's start_trace does — or jax.distributed.initialize
        # fails and every host silently runs the whole job alone.
        import jax

        from heatmap_tpu.parallel import initialize

        initialize()
        if args.backend == "tpu":
            _require_tpu(jax)
    output_spec = args.output
    if args.multihost and args.multihost_egress == "sharded":
        # Sharded egress: every process writes its own shard, so
        # path-backed sinks get this process's derived path (after
        # distributed init so process_index is final).
        import jax

        from heatmap_tpu.io.sinks import per_process_sink_spec

        output_spec = per_process_sink_spec(args.output, jax.process_index())
    # Telemetry (all opt-in; with every flag off the job path is
    # untouched and blobs are byte-identical — pinned by
    # tests/test_obs.py). --events installs the process event log,
    # --metrics-dir/--report enable the registry; the run report folds
    # whatever was collected at the end.
    telemetry = bool(args.metrics_dir or args.events
                     or args.report is not None)
    ev_log = None
    if telemetry:
        from heatmap_tpu import obs

        obs.enable_metrics(True)
        if args.events:
            ev_log = obs.EventLog(args.events)
            obs.set_event_log(ev_log)
            import dataclasses as _dc

            manifest = {k: (list(v) if isinstance(v, tuple) else v)
                        for k, v in _dc.asdict(config).items()}
            obs.emit("run_start", config=manifest, backend=args.backend,
                     devices=obs.device_topology(), argv=sys.argv[1:])
    from heatmap_tpu.obs import tracing as tracing_mod

    collector = _setup_tracing(args)
    root_span = tracing_mod.begin_span("run")
    t0 = time.perf_counter()
    prof = jax_profile(args.profile) if args.profile else contextlib.nullcontext()
    job_error = None
    blobs = None
    try:
        with prof:
            with open_sink(output_spec) as sink:
                if fast_source is not None:
                    blobs = run_job_fast(
                        fast_source, sink, config,
                        batch_size=args.batch_size,
                        checkpoint_dir=args.checkpoint_dir,
                        checkpoint_every=args.checkpoint_every,
                        max_points_in_flight=args.max_points_in_flight,
                        merge_spill_dir=args.merge_spill_dir,
                    )
                elif args.checkpoint_dir:
                    blobs = run_job_resumable(
                        open_source(args.input, read_value=args.weighted),
                        args.checkpoint_dir, sink,
                        config, batch_size=args.batch_size,
                        checkpoint_every=args.checkpoint_every,
                    )
                elif args.multihost:
                    from heatmap_tpu.parallel import run_job_multihost

                    blobs = run_job_multihost(
                        open_source(args.input, read_value=args.weighted),
                        sink, config, batch_size=args.batch_size,
                        max_points_in_flight=args.max_points_in_flight,
                        egress=args.multihost_egress,
                        merge_spill_dir=args.merge_spill_dir,
                        heartbeat_deadline_s=args.heartbeat_deadline,
                        on_straggler=args.on_straggler,
                        elastic_dir=args.elastic_dir,
                        elastic_hosts=args.elastic_hosts,
                    )
                else:
                    blobs = run_job(open_source(args.input,
                                                read_value=args.weighted),
                                    sink, config,
                                    batch_size=args.batch_size,
                                    max_points_in_flight=args.max_points_in_flight,
                                    merge_spill_dir=args.merge_spill_dir)
    except BaseException as e:  # noqa: BLE001 — run_end must record it
        _fail_telemetry(root_span, e)
        if not telemetry:
            tracing_mod.end_span(root_span)
            _export_trace(args, collector)
            raise
        job_error = e
    dt = time.perf_counter() - t0
    tracing_mod.end_span(root_span)
    if args.profile:
        print(get_tracer().format_report(), file=sys.stderr)
    if telemetry:
        from heatmap_tpu import obs

        obs.sample_device_memory()
        if ev_log is not None:
            end = {"status": "error" if job_error is not None else "ok",
                   "seconds": round(dt, 3)}
            if job_error is not None:
                end["error"] = repr(job_error)
            elif isinstance(blobs, dict) and str(
                    blobs.get("egress", "")).startswith("levels"):
                end["levels"] = blobs["levels"]
                end["rows"] = blobs["rows"]
            else:
                end["blobs"] = len(blobs)
                end["checksum"] = obs.blob_checksum(blobs)
            obs.emit("run_end", **end)
            obs.set_event_log(None)
            ev_log.close()
        if args.metrics_dir:
            obs.get_registry().write_prometheus(
                os.path.join(args.metrics_dir, "metrics.prom"))
        if args.report is not None:
            report = obs.build_run_report(
                tracer=get_tracer(), registry=obs.get_registry(),
                events_path=args.events)
            obs.write_run_report(args.report, report)
            print(obs.format_run_report(report), file=sys.stderr)
        if job_error is not None:
            _export_trace(args, collector)
            raise job_error
    _export_trace(args, collector)
    summary = {"seconds": round(dt, 3), "output": output_spec,
               "ingest": "fast" if fast_source is not None else "standard"}
    if isinstance(blobs, dict) and str(
            blobs.get("egress", "")).startswith("levels"):
        # "levels" (columnar) and "levels-sharded" (multihost columnar)
        summary["levels"] = blobs["levels"]
        summary["rows"] = blobs["rows"]
    else:
        summary["blobs"] = len(blobs)
    print(json.dumps(summary))
    return 0


def _scan_bounds(source, batch_size):
    """Data bounding box, padded 5%, or None for no finite coordinates.

    One pre-pass over the source (the fixed flag defaults cover the US
    Pacific Northwest; data elsewhere would silently bin to zero
    tiles). Sources iterate deterministically, so re-reading is safe.
    Raw lat/lon columns only — no load_columns (its per-row
    user_id/timestamp lists would double the job's Python cost for a
    min/max; background rows merely widen the covering window
    harmlessly). NaN coordinates are skipped; window_from_bounds
    clamps to the Mercator-valid band itself.
    """
    import numpy as np

    lat_lo = lon_lo = float("inf")
    lat_hi = lon_hi = float("-inf")
    for batch in source.batches(batch_size):
        lat = np.asarray(batch["latitude"], np.float64)
        lon = np.asarray(batch["longitude"], np.float64)
        if len(lat) == 0:
            continue
        # Finite coordinates only: NaN AND ±inf rows must not poison
        # the bbox (CSV float() happily parses "inf"); the projection
        # clamps latitude but an infinite longitude would overflow.
        finite = np.isfinite(lat) & np.isfinite(lon)
        if not finite.any():
            continue
        flat, flon = lat[finite], lon[finite]
        lat_lo = min(lat_lo, float(flat.min()))
        lat_hi = max(lat_hi, float(flat.max()))
        lon_lo = min(lon_lo, float(flon.min()))
        lon_hi = max(lon_hi, float(flon.max()))
    if lat_lo > lat_hi:
        return None
    pad_lat = max(0.05 * (lat_hi - lat_lo), 1e-3)
    pad_lon = max(0.05 * (lon_hi - lon_lo), 1e-3)
    return (lat_lo - pad_lat, lat_hi + pad_lat,
            lon_lo - pad_lon, lon_hi + pad_lon)


def cmd_tiles(args) -> int:
    if args.zoom < args.pixel_delta:
        raise SystemExit(
            f"--zoom {args.zoom} must be >= --pixel-delta {args.pixel_delta} "
            "(tile zoom = zoom - pixel_delta)"
        )
    if args.splat and (args.splat < 0 or args.splat % 2 == 0):
        raise SystemExit(f"--splat {args.splat}: kernel size must be odd")
    if args.sigma is not None and not args.sigma > 0:
        raise SystemExit(f"--sigma {args.sigma}: must be positive")
    _init_backend(args)
    import jax.numpy as jnp
    import numpy as np

    from heatmap_tpu.io import PNGTileSink, open_source
    from heatmap_tpu.ops import bin_points_window, window_from_bounds
    from heatmap_tpu.pipeline import load_columns

    proj_dtype = jnp.float32 if args.no_x64 else jnp.float64
    # Count-only runs skip the value column so weighted CSVs keep the
    # native fast parser; --weighted reads it (auto would too, but the
    # explicit hint makes the missing-column error come from this
    # command, not a parser heuristic).
    source = open_source(args.input, read_value=bool(args.weighted))
    if args.auto_bounds:
        bounds = _scan_bounds(source, args.batch_size)
        if bounds is None:
            print(json.dumps({"tiles": 0, "output": args.output}))
            return 0
        args.lat_min, args.lat_max, args.lon_min, args.lon_max = bounds
    window = window_from_bounds(
        (args.lat_min, args.lat_max),
        (args.lon_min, args.lon_max),
        zoom=args.zoom,
        align_levels=min(args.pixel_delta, args.zoom),
        pad_multiple=1 << args.pixel_delta,
    )
    raster = None
    t0 = time.perf_counter()
    for batch in source.batches(args.batch_size):
        cols = load_columns(batch)
        weights = None
        if args.weighted:
            if "value" not in cols:
                raise SystemExit(
                    "--weighted needs a 'value' column in the input "
                    "(CSV/JSONL/Parquet column named 'value')"
                )
            weights = jnp.asarray(cols["value"], jnp.float32)
        part = bin_points_window(
            jnp.asarray(cols["latitude"]),
            jnp.asarray(cols["longitude"]),
            window,
            weights=weights,
            proj_dtype=proj_dtype,
            backend=args.bin_backend,
        )
        raster = part if raster is None else raster + part
    if raster is None:
        print(json.dumps({"tiles": 0, "output": args.output}))
        return 0
    if args.splat:
        from heatmap_tpu.ops import gaussian_kernel_1d, splat_raster

        raster = splat_raster(
            raster, gaussian_kernel_1d(args.splat, args.sigma)
        )
    sink = PNGTileSink(args.output, pixel_delta=args.pixel_delta)
    n = sink.write_window(np.asarray(raster), window)
    dt = time.perf_counter() - t0
    print(
        json.dumps(
            {
                "tiles": n,
                "tile_zoom": args.zoom - args.pixel_delta,
                "bounds": [round(args.lat_min, 6), round(args.lat_max, 6),
                           round(args.lon_min, 6), round(args.lon_max, 6)],
                "seconds": round(dt, 3),
                "output": args.output,
            }
        )
    )
    return 0


def _live_dir(args) -> str:
    """Root for runtime tile artifacts (the --live-dir knob): explicit
    flag > checkpoint dir > system tmp — never the CWD, so streaming
    runs and tests stop littering the working directory."""
    if getattr(args, "live_dir", None):
        return args.live_dir
    if getattr(args, "checkpoint_dir", None):
        return args.checkpoint_dir
    import tempfile

    return os.path.join(tempfile.gettempdir(), "heatmap-tpu")


def cmd_stream(args) -> int:
    if args.output is None:
        args.output = os.path.join(_live_dir(args), "live_tiles")
    if args.half_life <= 0:
        raise SystemExit(f"--half-life {args.half_life}: must be positive")
    if args.zoom < args.pixel_delta:
        raise SystemExit(
            f"--zoom {args.zoom} must be >= --pixel-delta {args.pixel_delta} "
            "(tile zoom = zoom - pixel_delta)"
        )
    if args.checkpoint_dir and args.checkpoint_every < 1:
        raise SystemExit(
            f"--checkpoint-every {args.checkpoint_every}: must be >= 1"
        )
    _init_backend(args)
    import jax.numpy as jnp
    import numpy as np

    from heatmap_tpu.io import PNGTileSink, open_source
    from heatmap_tpu.ops import window_from_bounds
    from heatmap_tpu.pipeline import load_columns
    from heatmap_tpu.streaming import HeatmapStream, StreamConfig
    from heatmap_tpu.utils import CheckpointManager

    if args.auto_bounds:
        # Needs a re-iterable (file) source; same file on resume gives
        # the same window (restore() rejects a shifted one).
        bounds = _scan_bounds(open_source(args.input, read_value=False),
                              args.batch_points)
        if bounds is None:
            print(json.dumps({"batches": 0, "stream_seconds": 0.0,
                              "live_mass": 0.0, "tiles": 0,
                              "seconds": 0.0, "output": args.output}))
            return 0
        args.lat_min, args.lat_max, args.lon_min, args.lon_max = bounds
    window = window_from_bounds(
        (args.lat_min, args.lat_max),
        (args.lon_min, args.lon_max),
        zoom=args.zoom,
        align_levels=min(args.pixel_delta, args.zoom),
        pad_multiple=1 << args.pixel_delta,
    )
    proj_dtype = jnp.float32 if args.no_x64 else jnp.float64
    config = StreamConfig(
        window=window,
        half_life_s=args.half_life,
        proj_dtype=proj_dtype,
        pad_to=args.batch_points,
        backend=args.bin_backend,
    )
    stream = HeatmapStream(config)
    mgr = None
    if args.checkpoint_dir:
        mgr = CheckpointManager(args.checkpoint_dir)
        if mgr.latest_step() is not None:
            stream.restore(mgr, weighted=args.weighted)
    t0 = time.perf_counter()
    resumed = stream.n_batches
    t_stream = stream.t or 0.0
    i = 0
    for batch in open_source(
        args.input, read_value=args.weighted,
    ).batches(args.batch_points):
        i += 1
        if i <= resumed:
            continue  # deterministic source replay up to the checkpoint
        cols = load_columns(batch)
        t_stream += args.interval
        weights = None
        if args.weighted:
            if "value" not in cols:
                raise SystemExit(
                    "--weighted needs a 'value' column in the input "
                    "(CSV/JSONL/Parquet column named 'value')"
                )
            weights = cols["value"]
        stream.update(cols["latitude"], cols["longitude"], t_stream,
                      weights=weights)
        if mgr is not None and stream.n_batches % args.checkpoint_every == 0:
            stream.checkpoint(mgr, weighted=args.weighted)
    if mgr is not None:
        stream.checkpoint(mgr, weighted=args.weighted)
    snap = stream.snapshot()  # one device->host copy, reused below
    n_tiles = 0
    if args.output:
        sink = PNGTileSink(args.output, pixel_delta=args.pixel_delta)
        n_tiles = sink.write_window(snap, window)
    print(json.dumps({
        "batches": stream.n_batches,
        "stream_seconds": stream.t,
        "live_mass": float(np.sum(snap)),
        "bounds": [round(args.lat_min, 6), round(args.lat_max, 6),
                   round(args.lon_min, 6), round(args.lon_max, 6)],
        "tiles": n_tiles,
        "seconds": round(time.perf_counter() - t0, 3),
        "output": args.output,
    }))
    return 0


def _parse_layers(arg: str | None):
    """``--layers name=user|timespan,...`` -> {name: selector} or None
    (= expose every slice + the 'default' alias)."""
    if not arg:
        return None
    layers = {}
    for part in arg.split(","):
        part = part.strip()
        if not part:
            continue
        name, sep, sel = part.partition("=")
        layers[name.strip()] = (sel if sep else name).strip()
    return layers or None


def _follow_stream(args, app):
    """Live mode: pump micro-batches from --follow-stream into a
    LiveLayer on a daemon thread; each tick invalidates only the cache
    keys of tiles the batch touched. Returns a stop() callback."""
    _init_backend(args)
    import threading

    import jax.numpy as jnp

    from heatmap_tpu.io import open_source
    from heatmap_tpu.ops import window_from_bounds
    from heatmap_tpu.pipeline import load_columns
    from heatmap_tpu.serve import LiveLayer
    from heatmap_tpu.streaming import HeatmapStream, StreamConfig

    window = window_from_bounds(
        (args.lat_min, args.lat_max),
        (args.lon_min, args.lon_max),
        zoom=args.zoom,
    )
    config = StreamConfig(
        window=window,
        half_life_s=args.half_life,
        proj_dtype=jnp.float32 if args.no_x64 else jnp.float64,
        pad_to=args.batch_points,
    )
    layer = LiveLayer(HeatmapStream(config), name=args.live_layer)
    app.attach_layer(args.live_layer, layer)
    done = threading.Event()

    def _pump():
        t_stream = 0.0
        source = open_source(args.follow_stream, read_value=False)
        for batch in source.batches(args.batch_points):
            if done.is_set():
                break
            cols = load_columns(batch)
            t_stream += args.interval
            keys = layer.tick(cols["latitude"], cols["longitude"], t_stream)
            app.cache.invalidate_keys(keys)
            if args.tick_seconds > 0:
                done.wait(args.tick_seconds)

    thread = threading.Thread(target=_pump, name="serve-stream", daemon=True)
    thread.start()

    def stop():
        done.set()
        thread.join(timeout=5)

    return stop


def cmd_serve(args) -> int:
    """Tile HTTP server over a batch egress artifact (docs/serving.md).

    Numpy-only unless --follow-stream is given: serving a finished job
    never initializes a jax backend, so it needs no device at all.
    """
    from heatmap_tpu import faults, obs
    from heatmap_tpu.serve import ServeApp, TileCache, TileStore, make_server

    # serve skips _init_backend (numpy-only), so arm chaos here too.
    faults.install_from_env(getattr(args, "chaos", None))
    # /metrics is a first-class endpoint here, not an opt-in artifact.
    obs.enable_metrics(True)
    ev_log = None
    if args.events:
        ev_log = obs.EventLog(args.events)
        obs.set_event_log(ev_log)
    collector = _setup_tracing(args)
    if getattr(args, "fleet", None):
        if args.follow_stream:
            raise SystemExit("--fleet is incompatible with --follow-stream "
                             "(live layers are per-process state)")
        return _serve_fleet(args, collector, ev_log)
    ttl = args.ttl
    if args.follow_stream and not (ttl and ttl > 0):
        # Targeted invalidation only drops tiles a batch touched; decay
        # drifts every OTHER cached tile, so live mode needs its
        # staleness bounded by a finite TTL (serve/live.py).
        ttl = max(1.0, args.interval / 2)
    try:
        store = TileStore(args.store, layers=_parse_layers(args.layers))
    except (ValueError, OSError) as e:
        raise SystemExit(str(e)) from e
    cache = TileCache(max_bytes=args.cache_bytes,
                      ttl_s=ttl if (ttl and ttl > 0) else None)
    from heatmap_tpu.serve import degrade as degrade_mod

    try:
        controller = degrade_mod.controller_from_flags(
            getattr(args, "degrade", False),
            getattr(args, "degrade_dwell", 10.0),
            getattr(args, "degrade_hold", 30.0),
            getattr(args, "degrade_ladder", ""))
    except ValueError as e:
        raise SystemExit(f"--degrade-ladder: {e}") from e
    disk_cache = prewarm = None
    if getattr(args, "disk_cache", None):
        from heatmap_tpu.tilefs import DiskTileCache

        disk_cache = DiskTileCache(args.disk_cache,
                                   max_bytes=args.disk_cache_bytes)
    if getattr(args, "prewarm_events", None):
        from heatmap_tpu.tilefs import PrewarmConfig

        prewarm = PrewarmConfig(events=tuple(args.prewarm_events),
                                top_k=args.prewarm_top_k,
                                budget_s=args.prewarm_budget_s,
                                budget_bytes=args.prewarm_bytes)
    app = ServeApp(store, cache,
                   render_timeout_s=getattr(args, "render_timeout", None),
                   synopsis_default=getattr(args, "synopsis_default", False),
                   degrade=controller, disk_cache=disk_cache,
                   prewarm=prewarm)
    # Incident bundles capture the same state /healthz serves, plus the
    # mount fingerprint (no-ops without --incident-dir).
    from heatmap_tpu.obs import incident as incident_mod

    incident_mod.add_state_provider("healthz", app._health)
    incident_mod.add_state_provider("config", lambda: {
        "store": args.store, "layers": app.layer_names(),
        "cache_bytes": cache.max_bytes, "ttl_s": cache.ttl_s})
    stop_stream = None
    if args.follow_stream:
        stop_stream = _follow_stream(args, app)
    server = make_server(app, host=args.host, port=args.port)
    host, port = server.server_address[:2]
    # Warm before announcing readiness on stderr: a supervisor that
    # waits for the banner sees a server whose popular tiles are hot.
    # Budgeted (--prewarm-budget-s), so a huge log can't stall startup.
    app.prewarm_now(source="startup")
    print(json.dumps({
        "serving": f"http://{host}:{port}",
        "store": args.store,
        "layers": app.layer_names(),
        "cache_bytes": cache.max_bytes,
        "ttl_s": cache.ttl_s,
    }), file=sys.stderr, flush=True)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        if stop_stream is not None:
            stop_stream()
        server.server_close()
        _export_trace(args, collector)
        if ev_log is not None:
            obs.set_event_log(None)
            ev_log.close()
    return 0


def _serve_fleet(args, collector, ev_log) -> int:
    """``serve --fleet N``: supervisor + router on --host/--port.

    Each backend is a child serve process over the same store artifact
    (its own LRU); the router fronts them with the rendezvous ring,
    breakers, hedging, and admission control (docs/serving.md)."""
    from heatmap_tpu import obs
    from heatmap_tpu.serve import make_server
    from heatmap_tpu.serve import degrade as degrade_mod
    from heatmap_tpu.serve.fleet import FleetSupervisor

    degrade_opts = None
    if getattr(args, "degrade", False):
        degrade_opts = {"dwell_s": getattr(args, "degrade_dwell", 10.0),
                        "hold_s": getattr(args, "degrade_hold", 30.0),
                        "ladder_spec": getattr(args, "degrade_ladder", "")}
        try:
            # Fail fast in the supervisor, not in every backend child.
            degrade_mod.parse_ladder_spec(degrade_opts["ladder_spec"])
        except ValueError as e:
            raise SystemExit(f"--degrade-ladder: {e}") from e
    supervisor = FleetSupervisor(
        args.store, args.fleet,
        host=args.host, cache_bytes=args.cache_bytes,
        backend_max_inflight=args.max_inflight,
        render_timeout_s=getattr(args, "render_timeout", None),
        chaos=getattr(args, "chaos", None),
        max_inflight=args.max_inflight or 32,
        queue_deadline_s=args.queue_deadline,
        hedge_quantile=args.hedge_quantile,
        probe_interval_s=args.probe_interval,
        degrade_opts=degrade_opts,
        slo_specs=list(getattr(args, "slo", None) or []),
        telemetry_opts=(
            {"interval": args.telemetry_sample_interval,
             "watches": list(getattr(args, "watch", None) or [])}
            if getattr(args, "telemetry_sample_interval", 0.0) else None),
        disk_cache_opts=({"root": args.disk_cache,
                          "max_bytes": args.disk_cache_bytes}
                         if getattr(args, "disk_cache", None) else None),
        prewarm_opts=({"events": list(args.prewarm_events),
                       "top_k": args.prewarm_top_k,
                       "budget_s": args.prewarm_budget_s,
                       "budget_bytes": args.prewarm_bytes}
                      if getattr(args, "prewarm_events", None) else None))
    from heatmap_tpu.obs import incident as incident_mod

    # Lazy: supervisor.router is None until supervisor.start() below.
    incident_mod.add_state_provider(
        "healthz",
        lambda: supervisor.router._health() if supervisor.router else {})
    incident_mod.add_state_provider("config", lambda: {
        "store": args.store, "fleet": args.fleet,
        "backends": {bid: c.address for bid, c
                     in supervisor.router.backends.items()}})
    supervisor.start()
    server = make_server(supervisor.router, host=args.host, port=args.port)
    host, port = server.server_address[:2]
    print(json.dumps({
        "serving": f"http://{host}:{port}",
        "store": args.store,
        "fleet": {bid: client.address for bid, client
                  in supervisor.router.backends.items()},
    }), file=sys.stderr, flush=True)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        server.server_close()
        supervisor.stop()
        _export_trace(args, collector)
        if ev_log is not None:
            obs.set_event_log(None)
            ev_log.close()
    return 0


def cmd_render(args) -> int:
    """Stored heatmaps -> z/x/y PNG tile tree.

    Closes the loop the reference left to an external web app (its
    blobs went to Cassandra for some other service to draw, reference
    heatmap.py:149-150): reads a columnar levels directory
    (``arrays:DIR`` / ``arrays-parquet:DIR``) or a blob JSONL
    (``jsonl:PATH``), selects one (user, timespan, zoom) slice, and
    renders PNG tiles from the stored counts — no re-aggregation.
    """
    import numpy as np

    from heatmap_tpu.io import PNGTileSink
    from heatmap_tpu.io.sinks import JSONLBlobSink, LevelArraysSink

    kind, _, rest = args.input.partition(":")
    if kind in ("arrays", "arrays-parquet"):
        levels = LevelArraysSink.load(rest)
        if not levels:
            raise SystemExit(f"no level files under {rest!r}")
        zoom = args.zoom if args.zoom is not None else max(levels)
        if zoom not in levels:
            raise SystemExit(
                f"zoom {zoom} not stored; available: {sorted(levels)}"
            )
        lvl = levels[zoom]
        keep = ((lvl["user"] == args.user)
                & (lvl["timespan"] == args.timespan))
        rows = lvl["row"][keep].astype(np.int64)
        cols = lvl["col"][keep].astype(np.int64)
        vals = lvl["value"][keep]
    elif kind == "jsonl" or args.input.endswith((".jsonl", ".ndjson")):
        from heatmap_tpu.tilemath.keys import parse_tile_id

        path = rest if kind == "jsonl" else args.input
        blobs = JSONLBlobSink.load(path)
        # One pass: collect every matching (z, r, c, v); pick/filter
        # the zoom afterwards. Malformed ids drop, mirroring the
        # reference parser (tilemath.keys.parse_tile_id).
        entries = []
        for blob_id, heat in blobs.items():
            user, ts, _coarse = blob_id.split("|", 2)
            if user != args.user or ts != args.timespan:
                continue
            for tile_id, v in heat.items():
                parsed = parse_tile_id(tile_id)
                if parsed is not None:
                    entries.append((*parsed, float(v)))
        zooms_seen = {e[0] for e in entries}
        zoom = args.zoom if args.zoom is not None else (
            max(zooms_seen) if zooms_seen else None
        )
        if zoom is None or zoom not in zooms_seen:
            raise SystemExit(
                f"zoom {zoom} not stored for "
                f"{args.user!r}/{args.timespan!r}; "
                f"available: {sorted(zooms_seen)}"
            )
        sel = [e for e in entries if e[0] == zoom]
        rows = np.asarray([e[1] for e in sel], np.int64)
        cols = np.asarray([e[2] for e in sel], np.int64)
        vals = np.asarray([e[3] for e in sel], np.float64)
    else:
        raise SystemExit(
            f"render input must be arrays:DIR, arrays-parquet:DIR or "
            f"jsonl:PATH, got {args.input!r}"
        )

    if len(rows) == 0:
        print(json.dumps({"tiles": 0, "output": args.output,
                          "user": args.user, "timespan": args.timespan}))
        return 0
    pixel_delta = min(args.pixel_delta, zoom)
    px = 1 << pixel_delta
    # Rasterize PER OCCUPIED OUTPUT TILE, not over one bounding box: a
    # spread dataset (two cities in the 'all' slice) would make the
    # dense bounding raster at detail zoom gigabytes; per-tile blocks
    # bound memory at px*px regardless of extent. One shared vmax so
    # the colormap is consistent across tiles.
    from heatmap_tpu.ops import Window

    t0 = time.perf_counter()
    tile_key = (rows // px) * (1 << 40) + (cols // px)
    order = np.argsort(tile_key, kind="stable")
    sorted_keys = tile_key[order]
    starts = np.flatnonzero(
        np.concatenate([[True], sorted_keys[1:] != sorted_keys[:-1]])
    )
    bounds = np.append(starts, len(sorted_keys))
    sink = PNGTileSink(args.output, pixel_delta=pixel_delta)
    vmax = float(vals.max())
    n = 0
    for k, s in enumerate(starts):
        sel = order[s:bounds[k + 1]]
        ty = int(rows[sel[0]]) // px
        tx = int(cols[sel[0]]) // px
        block = np.zeros(px * px, np.float64)
        np.add.at(block, (rows[sel] - ty * px) * px + (cols[sel] - tx * px),
                  vals[sel])
        window = Window(zoom=zoom, row0=ty * px, col0=tx * px,
                        height=px, width=px)
        n += sink.write_window(block.reshape(px, px), window, vmax=vmax)
    print(json.dumps({
        "tiles": n,
        "tile_zoom": zoom - pixel_delta,
        "zoom": zoom,
        "aggregates": int(len(rows)),
        "seconds": round(time.perf_counter() - t0, 3),
        "output": args.output,
    }))
    return 0


def cmd_convert(args) -> int:
    from heatmap_tpu.io.hmpb import convert_to_hmpb

    stats = convert_to_hmpb(args.input, args.output,
                            batch_size=args.batch_size,
                            shard_rows=args.shard_rows)
    print(json.dumps(stats))
    return 0


def cmd_merge(args) -> int:
    """Merge per-host egress shards into one artifact (no devices)."""
    import os

    from heatmap_tpu.io.merge import merge_blob_files, merge_level_dirs
    from heatmap_tpu.io.sinks import LevelArraysSink, open_sink

    dirs = [os.path.isdir(p) for p in args.inputs]
    columnar_out = args.output.startswith("arrays:")
    if all(dirs):
        if not columnar_out:
            # Writing level arrays through a blob-spec path would
            # produce a directory of .npz files under a name the
            # operator believes is a JSONL file.
            raise SystemExit(
                "level-array inputs merge into a columnar sink; pass "
                "--output arrays:DIR (got "
                f"{args.output!r})"
            )
        levels = merge_level_dirs(args.inputs)
        rows = LevelArraysSink(
            args.output[len("arrays:"):]
        ).write_levels(levels)
        print(json.dumps({"mode": "levels", "inputs": len(args.inputs),
                          "levels": len(levels), "rows": rows,
                          "output": args.output}))
        return 0
    if any(dirs):
        raise SystemExit(
            "merge inputs must be all JSONL blob files or all "
            "level-array directories, not a mix"
        )
    if columnar_out:
        raise SystemExit(
            "blob inputs merge into a blob sink (jsonl:/dir:/memory:); "
            f"arrays: is columnar-only (got {args.output!r})"
        )
    blobs = merge_blob_files(args.inputs)
    with open_sink(args.output) as sink:
        sink.write((k, json.dumps(v)) for k, v in blobs.items())
    print(json.dumps({"mode": "blobs", "inputs": len(args.inputs),
                      "blobs": len(blobs), "output": args.output}))
    return 0


def _add_temporal_flags(p):
    g = p.add_argument_group(
        "temporal buckets",
        "pin the epoch-bucketed partial-pyramid config "
        "(docs/temporal.md). Byte-affecting for temporal folds, so it "
        "follows the config-fingerprint discipline: the first writer "
        "sets it, later runs must match. Compactions then fold history "
        "into buckets/ and serve answers ?as_of=/?window=/?decay= "
        "tiles and op=topk_growth queries.")
    g.add_argument("--bucket-width", type=float, default=None,
                   metavar="UNITS",
                   help="tier-0 bucket width in watermark units "
                   "(setting any --bucket-* flag enables the temporal "
                   "plane; default width 3600)")
    g.add_argument("--bucket-fanout", type=int, default=None,
                   help="geometric ladder fanout: tier-j buckets are "
                   "width * fanout**j wide (default 4)")
    g.add_argument("--bucket-keep", type=int, default=None,
                   help="newest intervals kept per tier before history "
                   "coarsens into the next tier (default 8)")
    g.add_argument("--bucket-tiers", type=int, default=None,
                   help="ladder height; the top tier is unbounded "
                   "(default 4)")
    g.add_argument("--bucket-unit-s", type=float, default=None,
                   metavar="S",
                   help="seconds per watermark unit — scales the named "
                   "?window= values (1h/1d/1w); ms timestamps use "
                   "0.001 (default 1)")


def _ensure_temporal(args, root: str):
    """Pin the temporal config when any --bucket-* flag was passed;
    returns the active config (None = temporal plane not enabled)."""
    overrides = {"width": args.bucket_width, "fanout": args.bucket_fanout,
                 "keep": args.bucket_keep, "tiers": args.bucket_tiers,
                 "unit_s": args.bucket_unit_s}
    if all(v is None for v in overrides.values()):
        return None
    from heatmap_tpu.temporal import ensure_config

    os.makedirs(root, exist_ok=True)
    try:
        return ensure_config(root, **overrides)
    except ValueError as e:
        raise SystemExit(str(e)) from e


def _add_update_flags(p):
    p.add_argument("--journal", required=True, metavar="ROOT",
                   help="delta store root (journal/ + base + delta "
                   "artifacts; created on first use — "
                   "docs/incremental.md)")
    p.add_argument("--input", default=None,
                   help="source spec of NEW points to apply as one "
                   "journaled delta batch")
    p.add_argument("--retractions", default=None,
                   help="source spec of points to RETRACT (a signed "
                   "delta batch: their counts are subtracted)")
    p.add_argument("--base", default=None, type=_sink_spec,
                   metavar="arrays:DIR",
                   help="adopt an existing columnar artifact as the "
                   "store's initial base pyramid (copied in; only "
                   "valid once)")
    p.add_argument("--compact-after", type=int, default=None, metavar="N",
                   help="fold the delta stack into a new base when "
                   "more than N live deltas remain after this update "
                   "(0 = compact whenever any delta is live)")
    p.add_argument("--retention", type=int, default=2,
                   help="journal entries kept after compaction as the "
                   "idempotency window (size to the upstream's "
                   "redelivery horizon)")
    p.add_argument("--detail-zoom", type=int, default=21)
    p.add_argument("--min-detail-zoom", type=int, default=5)
    p.add_argument("--result-delta", type=int, default=5)
    p.add_argument("--timespans", default="alltime")
    p.add_argument("--batch-size", type=int, default=1 << 20)
    p.add_argument("--weighted", action="store_true",
                   help="sum the source's per-point 'value' column "
                   "instead of counting points")
    p.add_argument("--cascade-backend", default="auto",
                   choices=("auto", "scatter", "partitioned"))
    p.add_argument("--data-parallel", choices=("auto", "on", "off"),
                   default="auto")
    p.add_argument("--metrics-dir", default=None, metavar="DIR",
                   help="enable the metrics registry and write "
                   "DIR/metrics.prom at command end")
    p.add_argument("--events", default=None, metavar="PATH",
                   help="append structured events to PATH (delta_applied, "
                   "compaction_start/end — docs/observability.md)")
    p.add_argument("--report", nargs="?", const="run_report.json",
                   default=None, metavar="PATH",
                   help="fold tracer + metrics + events into a run "
                   "report at PATH and print the span table to stderr")
    _add_temporal_flags(p)
    _add_trace_flags(p)


def cmd_update(args) -> int:
    """Incremental update: journaled delta applies + optional
    compaction against a delta store (heatmap_tpu.delta). The applied
    batches run the full cascade (auto routing included) over just the
    new points; the serving tier mounts the same root as ``serve
    --store delta:ROOT``."""
    from heatmap_tpu.pipeline.timespan import VALID_TYPES

    requested = tuple(t.strip() for t in args.timespans.split(",")
                      if t.strip())
    bad = [t for t in requested if t not in VALID_TYPES]
    if bad:
        raise SystemExit(
            f"--timespans: unknown type(s) {bad}; valid: "
            f"{', '.join(VALID_TYPES)}"
        )
    if not (args.input or args.retractions or args.base
            or args.compact_after is not None):
        raise SystemExit("nothing to do: pass --input and/or "
                         "--retractions, --base, or --compact-after")
    base_dir = None
    if args.base:
        # _sink_spec already validated the kind list; the store adopts
        # columnar artifacts only (that is the mergeable level format).
        if not args.base.startswith("arrays:"):
            raise SystemExit("--base must be a columnar arrays:DIR "
                             f"artifact, got {args.base!r}")
        base_dir = args.base[len("arrays:"):]
        if not os.path.isdir(base_dir):
            raise SystemExit(f"--base: {base_dir!r} is not a directory")
    config = None
    if args.input or args.retractions:
        _init_backend(args)
        from heatmap_tpu.pipeline import BatchJobConfig

        try:
            config = BatchJobConfig(
                detail_zoom=args.detail_zoom,
                min_detail_zoom=args.min_detail_zoom,
                result_delta=args.result_delta,
                timespans=requested,
                weighted=args.weighted,
                cascade_backend=args.cascade_backend,
                data_parallel={"auto": None, "on": True, "off": False}[
                    args.data_parallel],
            )
        except ValueError as e:
            raise SystemExit(str(e)) from e
    from heatmap_tpu import delta as delta_mod

    # Same opt-in telemetry contract as cmd_run: with every flag off
    # the update path emits/records nothing.
    telemetry = bool(args.metrics_dir or args.events
                     or args.report is not None)
    ev_log = None
    if telemetry:
        from heatmap_tpu import obs

        obs.enable_metrics(True)
        if args.events:
            ev_log = obs.EventLog(args.events)
            obs.set_event_log(ev_log)
            manifest = {}
            if config is not None:
                import dataclasses as _dc

                manifest = {k: (list(v) if isinstance(v, tuple) else v)
                            for k, v in _dc.asdict(config).items()}
            obs.emit("run_start", config=manifest, backend=args.backend,
                     devices=obs.device_topology(), argv=sys.argv[1:])
    from heatmap_tpu.obs import tracing as tracing_mod

    collector = _setup_tracing(args)
    from heatmap_tpu.obs import incident as incident_mod

    incident_mod.add_state_provider("delta", lambda: {
        "journal": args.journal,
        "live_deltas": len(delta_mod.live_entries(args.journal))})
    root_span = tracing_mod.begin_span("update")
    t0 = time.perf_counter()
    job_error = None
    summary = {"journal": args.journal}
    try:
        if base_dir is not None:
            delta_mod.init_store(args.journal, base_dir)
            summary["base_adopted"] = args.base
        tcfg = _ensure_temporal(args, args.journal)
        if tcfg is not None:
            summary["temporal"] = tcfg
        applied = []
        if args.input or args.retractions:
            from heatmap_tpu.io import open_source

            jobs = [(args.input, 1)] if args.input else []
            if args.retractions:
                jobs.append((args.retractions, -1))
            for spec, sign in jobs:
                res = delta_mod.apply_batch(
                    args.journal,
                    open_source(spec, read_value=args.weighted),
                    config, sign=sign, batch_size=args.batch_size)
                applied.append({
                    "input": spec, "epoch": res.epoch,
                    "points": res.points, "sign": res.sign,
                    "duplicate": res.duplicate, "rows": res.rows,
                    "affected_keys": len(res.affected_keys),
                })
        if applied:
            summary["applied"] = applied
        live = len(delta_mod.live_entries(args.journal))
        if args.compact_after is not None and live > args.compact_after:
            comp = delta_mod.compact(args.journal,
                                     retention=args.retention)
            summary["compaction"] = {
                k: comp.get(k) for k in ("status", "base",
                                         "applied_through", "rows",
                                         "pruned_entries")}
            live = len(delta_mod.live_entries(args.journal))
        summary["live_deltas"] = live
    except ValueError as e:
        # Config mismatch / double --base: operator errors, one line.
        _fail_telemetry(root_span, e)
        if not telemetry:
            tracing_mod.end_span(root_span)
            _export_trace(args, collector)
            raise SystemExit(str(e)) from e
        job_error = e
    except BaseException as e:  # noqa: BLE001 — run_end must record it
        _fail_telemetry(root_span, e)
        if not telemetry:
            tracing_mod.end_span(root_span)
            _export_trace(args, collector)
            raise
        job_error = e
    dt = time.perf_counter() - t0
    tracing_mod.end_span(root_span)
    if telemetry:
        from heatmap_tpu import obs
        from heatmap_tpu.utils.trace import get_tracer

        if ev_log is not None:
            end = {"status": "error" if job_error is not None else "ok",
                   "seconds": round(dt, 3)}
            if job_error is not None:
                end["error"] = repr(job_error)
            else:
                end["rows"] = int(sum(a["rows"] for a in
                                      summary.get("applied", [])))
            obs.emit("run_end", **end)
            obs.set_event_log(None)
            ev_log.close()
        if args.metrics_dir:
            obs.get_registry().write_prometheus(
                os.path.join(args.metrics_dir, "metrics.prom"))
        if args.report is not None:
            report = obs.build_run_report(
                tracer=get_tracer(), registry=obs.get_registry(),
                events_path=args.events)
            obs.write_run_report(args.report, report)
            print(obs.format_run_report(report), file=sys.stderr)
        if job_error is not None:
            _export_trace(args, collector)
            if isinstance(job_error, ValueError):
                raise SystemExit(str(job_error)) from job_error
            raise job_error
    _export_trace(args, collector)
    summary["seconds"] = round(dt, 3)
    print(json.dumps(summary))
    return 0


def cmd_retract(args) -> int:
    """Predicate retraction (delta/retract.py): scan the journal's
    point payloads for rows matching every --where clause, net them as
    a signed multiset, and apply exact sign=-1 counter-batches — one
    per (temporal bucket, column signature) group, so the all-time
    store AND every temporal fold converge to a clean recompute over
    the surviving points."""
    _init_backend(args)
    from heatmap_tpu.delta import retract as retract_mod

    pairs = list(args.where or [])
    if args.layer:
        pairs.append(f"user={args.layer}")
    try:
        where = retract_mod.parse_where(pairs)
    except ValueError as e:
        raise SystemExit(str(e)) from e
    ev_log = None
    if args.events:
        from heatmap_tpu import obs

        ev_log = obs.EventLog(args.events)
        obs.set_event_log(ev_log)
    try:
        summary = retract_mod.retract_predicate(
            args.journal, where, batch_size=args.batch_size)
    except ValueError as e:
        raise SystemExit(str(e)) from e
    finally:
        if ev_log is not None:
            from heatmap_tpu import obs

            obs.set_event_log(None)
            ev_log.close()
    out = {k: v for k, v in summary.items() if k != "results"}
    out["journal"] = args.journal
    out["where"] = {k: str(v) for k, v in sorted(where.items())}
    out["seconds"] = round(out["seconds"], 3)
    print(json.dumps(out))
    return 0


def _add_retract_flags(p):
    p.add_argument("--journal", required=True, metavar="ROOT",
                   help="delta store root whose journal is scanned")
    p.add_argument("--where", action="append", default=[],
                   metavar="COL=VALUE",
                   help="equality clause on a point column (repeatable; "
                   "clauses AND). Columns: user/user_id, source, "
                   "timestamp, latitude, longitude, value")
    p.add_argument("--layer", default=None, metavar="USER",
                   help="shorthand for --where user=USER (the serve "
                   "tier's layer name)")
    p.add_argument("--batch-size", type=int, default=1 << 20)
    p.add_argument("--events", default=None, metavar="PATH",
                   help="append structured events to PATH "
                   "(retraction_applied, delta_applied)")


def _add_ingest_flags(p):
    p.add_argument("--journal", required=True, metavar="ROOT",
                   help="delta store root the loop journals into "
                   "(created on first use; serve mounts it as "
                   "delta:ROOT — docs/ingest.md)")
    p.add_argument("--input", required=True,
                   help="source spec consumed as micro-batches "
                   "(each one journaled as its own signed epoch)")
    p.add_argument("--retract", action="store_true",
                   help="retract every batch instead of inserting "
                   "(sign=-1 epochs: counts are subtracted)")
    p.add_argument("--micro-batch", type=int, default=1 << 14,
                   help="points per tick (the journal/apply/publish "
                   "granularity)")
    p.add_argument("--queue-depth", type=int, default=4,
                   help="bounded-queue depth between the source reader "
                   "and the apply loop; a full queue blocks the "
                   "reader (back-pressure). 0 = synchronous, no "
                   "reader thread")
    p.add_argument("--max-ticks", type=int, default=None,
                   help="stop after N ticks (default: drain the source)")
    p.add_argument("--compact-every", type=int, default=16, metavar="N",
                   help="fold the delta stack into a new base whenever "
                   "N live deltas accumulate (0 = never)")
    p.add_argument("--compact-max-age", type=float, default=0.0,
                   metavar="S",
                   help="also compact when the oldest live delta is "
                   "older than S seconds (0 = never)")
    p.add_argument("--retention", type=int, default=2,
                   help="journal entries kept after compaction as the "
                   "idempotency window")
    p.add_argument("--pad-bucketing", default="pow2",
                   choices=("pow2", "geometric", "exact"),
                   help="bucketed-padding compile cache for the "
                   "cascade (pipeline/bucketing.py): pow2/geometric "
                   "reuse one compilation per size bucket; exact "
                   "compiles per distinct batch size")
    p.add_argument("--pad-bucket-min", type=int, default=1 << 12,
                   help="bucket floor: batches below this many "
                   "emissions share one compilation")
    p.add_argument("--serve-port", type=int, default=None, metavar="PORT",
                   help="serve the store over HTTP from this process "
                   "while ingesting (0 = ephemeral port; bound "
                   "address printed to stderr); each tick publishes "
                   "via targeted invalidation")
    p.add_argument("--detail-zoom", type=int, default=21)
    p.add_argument("--min-detail-zoom", type=int, default=5)
    p.add_argument("--result-delta", type=int, default=5)
    p.add_argument("--timespans", default="alltime")
    p.add_argument("--weighted", action="store_true",
                   help="sum the source's per-point 'value' column "
                   "instead of counting points")
    p.add_argument("--cascade-backend", default="auto",
                   choices=("auto", "scatter", "partitioned"))
    p.add_argument("--data-parallel", choices=("auto", "on", "off"),
                   default="auto")
    p.add_argument("--metrics-dir", default=None, metavar="DIR",
                   help="enable the metrics registry and write "
                   "DIR/metrics.prom at command end")
    p.add_argument("--events", default=None, metavar="PATH",
                   help="append structured events to PATH (ingest_tick, "
                   "delta_applied, compaction_start/end — "
                   "docs/observability.md)")
    p.add_argument("--report", nargs="?", const="run_report.json",
                   default=None, metavar="PATH",
                   help="fold tracer + metrics + events into a run "
                   "report at PATH and print the span table to stderr")
    _add_temporal_flags(p)
    _add_trace_flags(p)


def cmd_ingest(args) -> int:
    """Continuous ingest: drain a source through the bounded-queue
    loop (heatmap_tpu.ingest) — every micro-batch journals as a signed
    epoch, applies through the bucketed cascade, and (with
    --serve-port) publishes to an in-process tile server via targeted
    invalidation. A ``staleness`` SLO over tick recency rides the
    shared --slo flag, e.g. ``--slo fresh:staleness:max_age_s=30``."""
    from heatmap_tpu.pipeline.timespan import VALID_TYPES

    requested = tuple(t.strip() for t in args.timespans.split(",")
                      if t.strip())
    bad = [t for t in requested if t not in VALID_TYPES]
    if bad:
        raise SystemExit(
            f"--timespans: unknown type(s) {bad}; valid: "
            f"{', '.join(VALID_TYPES)}"
        )
    _init_backend(args)
    from heatmap_tpu import delta as delta_mod
    from heatmap_tpu import ingest as ingest_mod
    from heatmap_tpu.io import open_source
    from heatmap_tpu.pipeline import BatchJobConfig, bucketing

    try:
        config = BatchJobConfig(
            detail_zoom=args.detail_zoom,
            min_detail_zoom=args.min_detail_zoom,
            result_delta=args.result_delta,
            timespans=requested,
            weighted=args.weighted,
            cascade_backend=args.cascade_backend,
            data_parallel={"auto": None, "on": True, "off": False}[
                args.data_parallel],
            pad_bucketing=args.pad_bucketing,
            pad_bucket_min=args.pad_bucket_min,
        )
        ing = ingest_mod.IngestConfig(
            micro_batch=args.micro_batch,
            queue_depth=args.queue_depth or None,
            sign=-1 if args.retract else 1,
            compact_every=args.compact_every,
            compact_max_age_s=args.compact_max_age,
            retention=args.retention,
            max_ticks=args.max_ticks,
        )
    except ValueError as e:
        raise SystemExit(str(e)) from e

    telemetry = bool(args.metrics_dir or args.events
                     or args.report is not None)
    ev_log = None
    if telemetry:
        from heatmap_tpu import obs

        obs.enable_metrics(True)
        if args.events:
            ev_log = obs.EventLog(args.events)
            obs.set_event_log(ev_log)
            import dataclasses as _dc

            manifest = {k: (list(v) if isinstance(v, tuple) else v)
                        for k, v in _dc.asdict(config).items()}
            obs.emit("run_start", config=manifest, backend=args.backend,
                     devices=obs.device_topology(), argv=sys.argv[1:])
    from heatmap_tpu.obs import tracing as tracing_mod

    collector = _setup_tracing(args)
    from heatmap_tpu.obs import incident as incident_mod

    incident_mod.add_state_provider("delta", lambda: {
        "journal": args.journal,
        "live_deltas": len(delta_mod.live_entries(args.journal))})
    root_span = tracing_mod.begin_span("ingest")
    t0 = time.perf_counter()
    job_error = None
    server = None
    summary = {"journal": args.journal}
    try:
        delta_mod.init_store(args.journal)
        tcfg = _ensure_temporal(args, args.journal)
        if tcfg is not None:
            summary["temporal"] = tcfg
        store = cache = None
        if args.serve_port is not None:
            from heatmap_tpu.serve import (ServeApp, TileCache, TileStore,
                                           serve_in_thread)

            store = TileStore(f"delta:{args.journal}")
            cache = TileCache()
            server, base_url = serve_in_thread(
                ServeApp(store, cache), port=args.serve_port)
            summary["serving"] = base_url
            print(f"serving {base_url}/tiles/... while ingesting",
                  file=sys.stderr)
        stats = ingest_mod.run_ingest(
            args.journal, open_source(args.input, read_value=args.weighted),
            config, ingest=ing, store=store, cache=cache)
        summary.update({
            "ticks": stats.ticks, "points": stats.points,
            "epochs": len(stats.epochs), "duplicates": stats.duplicates,
            "watermark": stats.watermark,
            "max_queue_depth": stats.max_queue_depth,
            "compactions": stats.compactions,
            "keys_invalidated": stats.keys_invalidated,
            "live_deltas": len(delta_mod.live_entries(args.journal)),
            "compile_cache": bucketing.cache_stats(),
        })
    except ValueError as e:
        _fail_telemetry(root_span, e)
        if not telemetry:
            tracing_mod.end_span(root_span)
            _export_trace(args, collector)
            raise SystemExit(str(e)) from e
        job_error = e
    except BaseException as e:  # noqa: BLE001 — run_end must record it
        _fail_telemetry(root_span, e)
        if not telemetry:
            tracing_mod.end_span(root_span)
            _export_trace(args, collector)
            raise
        job_error = e
    finally:
        if server is not None:
            server.shutdown()
    dt = time.perf_counter() - t0
    tracing_mod.end_span(root_span)
    if telemetry:
        from heatmap_tpu import obs
        from heatmap_tpu.utils.trace import get_tracer

        if ev_log is not None:
            end = {"status": "error" if job_error is not None else "ok",
                   "seconds": round(dt, 3)}
            if job_error is not None:
                end["error"] = repr(job_error)
            else:
                end["rows"] = int(summary.get("points", 0))
            obs.emit("run_end", **end)
            obs.set_event_log(None)
            ev_log.close()
        if args.metrics_dir:
            obs.get_registry().write_prometheus(
                os.path.join(args.metrics_dir, "metrics.prom"))
        if args.report is not None:
            report = obs.build_run_report(
                tracer=get_tracer(), registry=obs.get_registry(),
                events_path=args.events)
            obs.write_run_report(args.report, report)
            print(obs.format_run_report(report), file=sys.stderr)
        if job_error is not None:
            _export_trace(args, collector)
            if isinstance(job_error, ValueError):
                raise SystemExit(str(job_error)) from job_error
            raise job_error
    _export_trace(args, collector)
    summary["seconds"] = round(dt, 3)
    print(json.dumps(summary))
    return 0


def _add_writeplane_flags(p):
    p.add_argument("--root", required=True, metavar="ROOT",
                   help="write-plane root (created on first use; serve "
                   "mounts it as writeplane:ROOT — docs/write-plane.md)")
    p.add_argument("--input", default=None,
                   help="insert source spec, drained as micro-batches "
                   "routed by Morton range")
    p.add_argument("--retractions", default=None,
                   help="retraction source spec (sign=-1 batches, "
                   "applied after --input)")
    p.add_argument("--writers", type=int, default=2,
                   help="ingest pumps = initial Morton ranges "
                   "(rebalance can add more)")
    p.add_argument("--micro-batch", type=int, default=1 << 14,
                   help="points per routed batch (the ledger/dedup "
                   "granularity — replays must use the same batching)")
    p.add_argument("--queue-depth", type=int, default=4,
                   help="bounded per-range queue depth between the "
                   "router and each pump")
    p.add_argument("--publish-every", type=int, default=1, metavar="N",
                   help="flip a manifest epoch every N finished batches")
    p.add_argument("--compact-every", type=int, default=16, metavar="N",
                   help="fold a range whenever N live deltas accumulate "
                   "(0 = never)")
    p.add_argument("--retention", type=int, default=2,
                   help="per-range journal entries kept after "
                   "compaction (refused below the retention floor or "
                   "the in-flight queue depth)")
    p.add_argument("--retention-floor", type=int, default=2,
                   help="hard floor under --retention (docs/"
                   "write-plane.md)")
    p.add_argument("--ledger-keep", type=int, default=64,
                   help="full-batch ledger entries retained (the "
                   "cross-rebalance dedup window)")
    p.add_argument("--max-ticks", type=int, default=None,
                   help="stop after N micro-batches (default: drain)")
    p.add_argument("--rebalance", action="store_true",
                   help="run one skew-triggered hot-range re-split "
                   "after the drain (docs/write-plane.md runbook)")
    p.add_argument("--pad-bucketing", default="pow2",
                   choices=("pow2", "geometric", "exact"),
                   help="bucketed-padding compile cache for the "
                   "cascade (pipeline/bucketing.py); routed sub-batch "
                   "sizes vary every tick, so exact mode compiles per "
                   "distinct size")
    p.add_argument("--pad-bucket-min", type=int, default=1 << 12,
                   help="bucket floor: sub-batches below this many "
                   "emissions share one compilation")
    p.add_argument("--detail-zoom", type=int, default=21)
    p.add_argument("--min-detail-zoom", type=int, default=5)
    p.add_argument("--result-delta", type=int, default=5)
    p.add_argument("--timespans", default="alltime")
    p.add_argument("--weighted", action="store_true")
    p.add_argument("--cascade-backend", default="auto",
                   choices=("auto", "scatter", "partitioned"))
    p.add_argument("--data-parallel", choices=("auto", "on", "off"),
                   default="auto")
    p.add_argument("--metrics-dir", default=None, metavar="DIR",
                   help="enable the metrics registry and write "
                   "DIR/metrics.prom at command end")
    p.add_argument("--events", default=None, metavar="PATH",
                   help="append structured events to PATH "
                   "(writeplane_append/publish/rebalance — "
                   "docs/observability.md)")
    p.add_argument("--report", nargs="?", const="run_report.json",
                   default=None, metavar="PATH")
    _add_trace_flags(p)


def cmd_writeplane(args) -> int:
    """Partitioned multi-writer ingest: batches route by Morton range
    to independent per-range delta stores (one pump each), unified for
    readers by an epoch-flipped manifest (heatmap_tpu.writeplane).
    Serve mounts the root as ``writeplane:ROOT``."""
    from heatmap_tpu.pipeline.timespan import VALID_TYPES

    requested = tuple(t.strip() for t in args.timespans.split(",")
                      if t.strip())
    bad = [t for t in requested if t not in VALID_TYPES]
    if bad:
        raise SystemExit(f"--timespans: unknown type(s) {bad}; valid: "
                         f"{', '.join(VALID_TYPES)}")
    _init_backend(args)
    import statistics

    from heatmap_tpu import writeplane as wp_mod
    from heatmap_tpu.io import open_source
    from heatmap_tpu.pipeline import BatchJobConfig

    try:
        config = BatchJobConfig(
            detail_zoom=args.detail_zoom,
            min_detail_zoom=args.min_detail_zoom,
            result_delta=args.result_delta,
            timespans=requested,
            weighted=args.weighted,
            cascade_backend=args.cascade_backend,
            data_parallel={"auto": None, "on": True, "off": False}[
                args.data_parallel],
            pad_bucketing=args.pad_bucketing,
            pad_bucket_min=args.pad_bucket_min,
        )
        plane_cfg = wp_mod.PlaneConfig(
            n_writers=args.writers,
            retention=args.retention,
            retention_floor=args.retention_floor,
            compact_every=args.compact_every,
            ledger_keep=args.ledger_keep,
        )
    except ValueError as e:
        raise SystemExit(str(e)) from e

    telemetry = bool(args.metrics_dir or args.events
                     or args.report is not None)
    ev_log = None
    if telemetry:
        from heatmap_tpu import obs

        obs.enable_metrics(True)
        if args.events:
            ev_log = obs.EventLog(args.events)
            obs.set_event_log(ev_log)
            import dataclasses as _dc

            manifest = {k: (list(v) if isinstance(v, tuple) else v)
                        for k, v in _dc.asdict(config).items()}
            obs.emit("run_start", config=manifest, backend=args.backend,
                     devices=obs.device_topology(), argv=sys.argv[1:])
    from heatmap_tpu.obs import tracing as tracing_mod

    collector = _setup_tracing(args)
    from heatmap_tpu.obs import incident as incident_mod

    incident_mod.add_state_provider("writeplane", lambda: {
        "root": args.root,
        "epoch": wp_mod.read_pointer(args.root)})
    root_span = tracing_mod.begin_span("writeplane")
    t0 = time.perf_counter()
    job_error = None
    summary = {"root": args.root}
    try:
        plane = wp_mod.WritePlane(args.root, config, plane_cfg)
        runs = []
        jobs = [(args.input, 1)] if args.input else []
        if args.retractions:
            jobs.append((args.retractions, -1))
        for spec, sign in jobs:
            stats = wp_mod.run_plane_ingest(
                plane, open_source(spec, read_value=args.weighted),
                micro_batch=args.micro_batch, sign=sign,
                queue_depth=args.queue_depth,
                publish_every=args.publish_every,
                max_ticks=args.max_ticks)
            runs.append({
                "input": spec, "sign": sign, "batches": stats.batches,
                "completed": stats.completed,
                "duplicates": stats.duplicates, "failed": stats.failed,
                "points": stats.points, "publishes": stats.publishes,
                "publish_errors": stats.publish_errors,
                "lag_p50_s": (round(statistics.median(stats.lags_s), 6)
                              if stats.lags_s else None),
            })
        if runs:
            summary["runs"] = runs
        if args.rebalance:
            rb = plane.rebalance()
            summary["rebalance"] = (
                None if rb is None else
                {k: rb[k] for k in ("range", "new_range", "split",
                                    "epoch")})
        summary["epoch"] = plane.publish()
        summary["ranges"] = plane.order
    except ValueError as e:
        _fail_telemetry(root_span, e)
        if not telemetry:
            tracing_mod.end_span(root_span)
            _export_trace(args, collector)
            raise SystemExit(str(e)) from e
        job_error = e
    except BaseException as e:  # noqa: BLE001 — run_end must record it
        _fail_telemetry(root_span, e)
        if not telemetry:
            tracing_mod.end_span(root_span)
            _export_trace(args, collector)
            raise
        job_error = e
    dt = time.perf_counter() - t0
    tracing_mod.end_span(root_span)
    if telemetry:
        from heatmap_tpu import obs
        from heatmap_tpu.utils.trace import get_tracer

        if ev_log is not None:
            end = {"status": "error" if job_error is not None else "ok",
                   "seconds": round(dt, 3)}
            if job_error is not None:
                end["error"] = repr(job_error)
            else:
                end["rows"] = int(sum(r["points"] for r in
                                      summary.get("runs", [])))
            obs.emit("run_end", **end)
            obs.set_event_log(None)
            ev_log.close()
        if args.metrics_dir:
            obs.get_registry().write_prometheus(
                os.path.join(args.metrics_dir, "metrics.prom"))
        if args.report is not None:
            report = obs.build_run_report(
                tracer=get_tracer(), registry=obs.get_registry(),
                events_path=args.events)
            obs.write_run_report(args.report, report)
            print(obs.format_run_report(report), file=sys.stderr)
        if job_error is not None:
            _export_trace(args, collector)
            if isinstance(job_error, ValueError):
                raise SystemExit(str(job_error)) from job_error
            raise job_error
    _export_trace(args, collector)
    summary["seconds"] = round(dt, 3)
    print(json.dumps(summary))
    return 0


def cmd_info(args) -> int:
    jax = _init_backend(args)
    from heatmap_tpu import native

    devs = jax.devices()
    print(
        json.dumps(
            {
                "backend": args.backend,
                "platform": devs[0].platform,
                "n_devices": len(devs),
                "n_processes": jax.process_count(),
                "x64": bool(jax.config.jax_enable_x64),
                "native": native.available(),
                "version": __import__("heatmap_tpu").__version__,
            }
        )
    )
    return 0


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="heatmap-tpu",
        description="TPU-native heatmap aggregation (reference parity: "
        "timfpark/heatmap batch job)",
    )
    sub = ap.add_subparsers(dest="cmd", required=True)

    p_run = sub.add_parser("run", help="batch job: points -> heatmap blobs")
    _add_backend_flags(p_run)
    _add_run_flags(p_run)
    p_run.set_defaults(fn=cmd_run)

    p_tiles = sub.add_parser("tiles", help="points -> z/x/y PNG tile tree")
    _add_backend_flags(p_tiles)
    p_tiles.add_argument("--input", required=True)
    p_tiles.add_argument("--output", default="tiles")
    p_tiles.add_argument("--zoom", type=int, default=16,
                         help="detail (pixel) zoom")
    p_tiles.add_argument("--pixel-delta", type=int, default=8,
                         help="tile zoom = zoom - pixel_delta; 8 -> 256px tiles")
    p_tiles.add_argument("--lat-min", type=float, default=45.0)
    p_tiles.add_argument("--lat-max", type=float, default=50.0)
    p_tiles.add_argument("--lon-min", type=float, default=-125.0)
    p_tiles.add_argument("--lon-max", type=float, default=-119.0)
    p_tiles.add_argument("--auto-bounds", action="store_true",
                         help="derive the window from the data's "
                         "bounding box (one extra pass over the "
                         "source) instead of the --lat/--lon flags")
    p_tiles.add_argument("--batch-size", type=int, default=1 << 20)
    p_tiles.add_argument("--splat", type=int, default=0, metavar="K",
                         help="smooth with a KxK Gaussian kernel before "
                         "rendering (e.g. 9; 0 = off)")
    p_tiles.add_argument("--sigma", type=float, default=None,
                         help="Gaussian sigma in cells (default K/4)")
    p_tiles.add_argument("--weighted", action="store_true",
                         help="sum the input's per-point 'value' column "
                         "instead of counting points (BASELINE config 3)")
    p_tiles.add_argument("--bin-backend", default="auto",
                         choices=("auto", "xla", "pallas", "partitioned"),
                         help="binning path (as in bench.py): auto routes "
                         "TPU windows to the measured-fastest kernel; xla "
                         "is the plain scatter")
    p_tiles.set_defaults(fn=cmd_tiles)

    p_stream = sub.add_parser(
        "stream",
        help="micro-batch streaming: decayed live raster -> PNG tiles "
        "(BASELINE.md config 4)",
    )
    _add_backend_flags(p_stream)
    p_stream.add_argument("--input", required=True,
                          help="source spec, consumed as micro-batches")
    p_stream.add_argument("--output", default=None,
                          help="PNG tile tree dir for the final snapshot "
                          "('' = none; default: live_tiles/ under "
                          "--live-dir)")
    p_stream.add_argument("--live-dir", default=None,
                          help="root for runtime tile artifacts (default: "
                          "--checkpoint-dir when given, else the system "
                          "tmp dir)")
    p_stream.add_argument("--batch-points", type=int, default=1 << 16,
                          help="points per micro-batch (one compiled step)")
    p_stream.add_argument("--bin-backend", default="auto",
                          choices=("auto", "xla", "pallas", "partitioned"),
                          help="binning backend for the update step "
                          "(StreamConfig.backend); tools/bench_stream.py "
                          "measures each backend")
    p_stream.add_argument("--interval", type=float, default=60.0,
                          help="stream seconds advanced per micro-batch")
    p_stream.add_argument("--half-life", type=float, default=3600.0,
                          help="decay half-life in stream seconds")
    p_stream.add_argument("--zoom", type=int, default=12)
    p_stream.add_argument("--pixel-delta", type=int, default=8)
    p_stream.add_argument("--lat-min", type=float, default=45.0)
    p_stream.add_argument("--lat-max", type=float, default=50.0)
    p_stream.add_argument("--lon-min", type=float, default=-125.0)
    p_stream.add_argument("--lon-max", type=float, default=-119.0)
    p_stream.add_argument("--auto-bounds", action="store_true",
                          help="derive the window from the data's "
                          "bounding box (file sources only: one extra "
                          "pass; resume keeps the same window for the "
                          "same file)")
    p_stream.add_argument("--checkpoint-dir", default=None)
    p_stream.add_argument("--checkpoint-every", type=int, default=16)
    p_stream.add_argument("--weighted", action="store_true",
                          help="sum the input's per-point 'value' column "
                          "into the decayed raster instead of counting")
    p_stream.set_defaults(fn=cmd_stream)

    p_serve = sub.add_parser(
        "serve",
        help="tile HTTP server over stored heatmaps: "
        "GET /tiles/{layer}/{z}/{x}/{y}.png|.json (docs/serving.md)",
    )
    _add_backend_flags(p_serve)  # used only by --follow-stream
    p_serve.add_argument("--store", required=True,
                         help="arrays:DIR (incl. multihost host*/ shard "
                         "dirs) | jsonl:PATH | dir:PATH — any batch "
                         "egress artifact")
    p_serve.add_argument("--host", default="127.0.0.1")
    p_serve.add_argument("--port", type=int, default=8000,
                         help="listen port (0 = ephemeral; the bound "
                         "address is printed to stderr)")
    p_serve.add_argument("--cache-bytes", type=int, default=256 << 20,
                         help="tile cache budget in bytes (LRU past it; "
                         "0 disables caching but keeps single-flight "
                         "render dedup)")
    p_serve.add_argument("--ttl", type=float, default=None,
                         help="tile cache TTL seconds (default: none for "
                         "static stores; live mode defaults to "
                         "interval/2 to bound decay drift)")
    p_serve.add_argument("--layers", default=None,
                         help="comma list of name=user|timespan layer "
                         "mounts (default: every slice in the artifact "
                         "plus 'default' -> all|alltime)")
    p_serve.add_argument("--synopsis-default", action="store_true",
                         help="serve coarse tiles from wavelet synopses "
                         "by default (docs/synopsis.md); per-request "
                         "?synopsis=0/1 always wins")
    p_serve.add_argument("--render-timeout", type=float, default=None,
                         metavar="S",
                         help="per-tile render deadline in seconds; a "
                         "render past it serves the last-good cached "
                         "bytes (stale-200) or a typed 503, never a "
                         "hung request (docs/robustness.md)")
    p_serve.add_argument("--fleet", type=int, default=None, metavar="N",
                         help="run N shared-nothing backend processes "
                         "behind a consistent-hash router on --port "
                         "(rendezvous ring, circuit breakers, hedged "
                         "reads, admission control; docs/serving.md). "
                         "Incompatible with --follow-stream")
    p_serve.add_argument("--max-inflight", type=int, default=None,
                         metavar="N",
                         help="admission bound: concurrent tile requests "
                         "per process (router: per backend); past it "
                         "requests shed with 503 + Retry-After. "
                         "Fleet default: 32")
    p_serve.add_argument("--queue-deadline", type=float, default=0.25,
                         metavar="S",
                         help="fleet router: how long a request may wait "
                         "for a backend slot before shedding")
    p_serve.add_argument("--hedge-quantile", type=float, default=0.95,
                         help="fleet router: hedge a request to the next "
                         "replica once it outlives this latency "
                         "quantile (first answer wins)")
    p_serve.add_argument("--probe-interval", type=float, default=1.0,
                         metavar="S",
                         help="fleet router: active health-probe period "
                         "(half-open probes re-admit recovered "
                         "backends)")
    p_serve.add_argument("--degrade", action="store_true",
                         help="arm the brownout controller: SLO burn "
                         "(--slo) steps a rung ladder that trades tile "
                         "fidelity for availability under overload "
                         "(docs/robustness.md). Off by default")
    p_serve.add_argument("--degrade-dwell", type=float, default=10.0,
                         metavar="S",
                         help="seconds the burn must stay above the up "
                         "threshold before the ladder steps up one rung")
    p_serve.add_argument("--degrade-hold", type=float, default=30.0,
                         metavar="S",
                         help="seconds the burn must stay below the down "
                         "threshold before the ladder steps back down")
    p_serve.add_argument("--degrade-ladder", default="", metavar="SPEC",
                         help="ladder tuning, comma list of k=v: "
                         "up=BURN,down=BURN,ttl=SCALE,shed=FRAC,max=RUNG "
                         "(default up=1.0,down=0.5,ttl=4,shed=0.5,max=3)")
    p_serve.add_argument("--disk-cache", default=None, metavar="DIR",
                         help="persist rendered tile bytes under DIR as "
                         "a second cache tier below the heap LRU "
                         "(docs/tilefs.md): survives restarts, torn "
                         "entries read as misses, keys carry the exact "
                         "invalidation epochs. Fleet mode gives each "
                         "backend DIR/<backend-id>")
    p_serve.add_argument("--disk-cache-bytes", type=int, default=1 << 30,
                         metavar="B",
                         help="disk cache size cap (mtime-LRU eviction)")
    p_serve.add_argument("--prewarm-events", action="append", default=None,
                         metavar="PATH",
                         help="replay the Zipf head of these http_request "
                         "event logs (--events from a prior run) into "
                         "the caches at startup and after /reload; "
                         "repeatable (docs/tilefs.md)")
    p_serve.add_argument("--prewarm-top-k", type=int, default=64,
                         metavar="K",
                         help="how many of the most popular tile paths "
                         "the prewarm replays (decayed frequency rank)")
    p_serve.add_argument("--prewarm-budget-s", type=float, default=10.0,
                         metavar="S",
                         help="wall-clock budget for one prewarm pass")
    p_serve.add_argument("--prewarm-bytes", type=int, default=64 << 20,
                         metavar="B",
                         help="rendered-byte budget for one prewarm pass")
    p_serve.add_argument("--events", default=None, metavar="PATH",
                         help="append http_request events to PATH (JSONL, "
                         "docs/observability.md)")
    _add_trace_flags(p_serve)
    p_serve.add_argument("--follow-stream", default=None, metavar="SPEC",
                         help="live mode: consume this source spec as "
                         "micro-batches into a decayed stream layer "
                         "(name via --live-layer); ticks invalidate "
                         "only the affected tile keys")
    p_serve.add_argument("--live-layer", default="live",
                         help="layer name the --follow-stream raster is "
                         "served under")
    p_serve.add_argument("--batch-points", type=int, default=1 << 16)
    p_serve.add_argument("--interval", type=float, default=60.0,
                         help="stream seconds advanced per micro-batch")
    p_serve.add_argument("--tick-seconds", type=float, default=1.0,
                         help="wall-clock pause between micro-batch "
                         "ticks (0 = consume as fast as possible)")
    p_serve.add_argument("--half-life", type=float, default=3600.0)
    p_serve.add_argument("--zoom", type=int, default=12,
                         help="live window detail zoom")
    p_serve.add_argument("--lat-min", type=float, default=45.0)
    p_serve.add_argument("--lat-max", type=float, default=50.0)
    p_serve.add_argument("--lon-min", type=float, default=-125.0)
    p_serve.add_argument("--lon-max", type=float, default=-119.0)
    p_serve.set_defaults(fn=cmd_serve)

    p_render = sub.add_parser(
        "render",
        help="stored heatmaps (arrays:DIR / jsonl:PATH) -> PNG tile tree",
    )
    p_render.add_argument("--input", required=True,
                          help="arrays:DIR, arrays-parquet:DIR or jsonl:PATH")
    p_render.add_argument("--output", default="rendered_tiles")
    p_render.add_argument("--user", default="all",
                          help="user slice to render (default 'all')")
    p_render.add_argument("--timespan", default="alltime")
    p_render.add_argument("--zoom", type=int, default=None,
                          help="stored detail zoom to render "
                          "(default: finest available)")
    p_render.add_argument("--pixel-delta", type=int, default=8)
    p_render.set_defaults(fn=cmd_render)

    p_conv = sub.add_parser(
        "convert",
        help="convert a source to the HMPB binary columnar point format "
        "(mmap ingest for --fast reruns)",
    )
    p_conv.add_argument("--input", required=True, help="any source spec")
    p_conv.add_argument("--output", required=True,
                        help="output .hmpb path (a directory of part "
                        "files with --shard-rows)")
    p_conv.add_argument("--batch-size", type=int, default=1 << 20)
    p_conv.add_argument("--shard-rows", type=int, default=None,
                        help="split the output into part-NNNNN.hmpb "
                        "files of at most this many rows (the "
                        "range-shardable multihost ingest layout)")
    p_conv.set_defaults(fn=cmd_convert)

    p_merge = sub.add_parser(
        "merge",
        help="merge egress shards (per-host jsonl blob files or "
             "level-array dirs) into one artifact; colliding blob ids "
             "sum, exactly like the cross-host merge",
    )
    p_merge.add_argument("--inputs", nargs="+", required=True,
                         help="JSONL blob files, or level-array dirs "
                         "(all one kind)")
    p_merge.add_argument("--output", required=True, type=_sink_spec,
                         help="blob sink spec (jsonl:/dir:/memory:) for "
                         "blob inputs; arrays:DIR for level-array "
                         "inputs")
    p_merge.set_defaults(fn=cmd_merge)

    p_update = sub.add_parser(
        "update",
        help="incremental update: journaled delta apply + compaction "
        "against a delta store (serve mounts it as delta:ROOT)")
    _add_backend_flags(p_update)
    _add_update_flags(p_update)
    p_update.set_defaults(fn=cmd_update)

    p_ingest = sub.add_parser(
        "ingest",
        help="continuous ingest: source -> bounded queue -> journaled "
        "epochs -> servable tiles, with the bucketed compile cache "
        "(docs/ingest.md)")
    _add_backend_flags(p_ingest)
    _add_ingest_flags(p_ingest)
    p_ingest.set_defaults(fn=cmd_ingest)

    p_retract = sub.add_parser(
        "retract",
        help="predicate retraction against a delta store: journal scan "
        "-> exact signed counter-batches, byte-identical to a "
        "recompute over the surviving points (docs/temporal.md)")
    _add_backend_flags(p_retract)
    _add_retract_flags(p_retract)
    p_retract.set_defaults(fn=cmd_retract)

    p_wp = sub.add_parser(
        "writeplane",
        help="partitioned multi-writer ingest: Morton-range-sharded "
        "journals + epoch-unified manifest (serve mounts the root as "
        "writeplane:ROOT — docs/write-plane.md)")
    _add_backend_flags(p_wp)
    _add_writeplane_flags(p_wp)
    p_wp.set_defaults(fn=cmd_writeplane)

    p_info = sub.add_parser("info", help="resolved config + devices")
    _add_backend_flags(p_info)
    p_info.set_defaults(fn=cmd_info)
    return ap


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
