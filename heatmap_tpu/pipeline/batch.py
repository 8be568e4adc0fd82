"""Batch job orchestration: the TPU-native ``batchMain``.

End-to-end equivalent of reference heatmap.py:152-158:

    rows -> dataframe_loader -> build_heatmaps -> heatmap_to_json -> sink

with the Spark RDD program replaced by: host-side ingest filtering +
vocab building (strings never reach the device), one f64 projection to
detail-zoom Morton codes, the single-sort composite-key cascade on
device (cascade.py), and host-side blob egress.
"""

from __future__ import annotations

import dataclasses
import functools
import os

import jax
import numpy as np

from heatmap_tpu import obs
from heatmap_tpu.pipeline import bucketing as bucketing_mod
from heatmap_tpu.pipeline import cascade as cascade_mod
from heatmap_tpu.tilemath import mercator, morton
from heatmap_tpu.pipeline.groups import ALL_GROUP, EXCLUDED, UserVocab
from heatmap_tpu.pipeline.timespan import TS_MISSING, TimespanVocab

BACKGROUND_SOURCE = "background"  # dropped at ingest, reference heatmap.py:28-29


@dataclasses.dataclass(frozen=True)
class BatchJobConfig:
    """Flags replacing the reference's hard-coded constants
    (reference heatmap.py:16-23; SURVEY.md §5 "config system")."""

    detail_zoom: int = 21
    min_detail_zoom: int = 5
    result_delta: int = 5
    timespans: tuple = ("alltime",)
    # Reference-compat quirks (SURVEY.md §8.1, §8.2), off by default:
    amplify_all: bool = False
    first_timespan_only: bool = False
    capacity: int | None = None
    #: Sum the source's per-point 'value' column instead of counting
    #: (the cascade accumulates in f64; blob values become the sums).
    #: The reference counts 1.0 per row (heatmap.py:35) — weighted jobs
    #: are a capability extension, not a parity surface.
    weighted: bool = False
    #: Cascade reduction backend: "auto" (default), "scatter", or
    #: "partitioned" (multi-channel MXU segment reduction,
    #: ops/sparse_partitioned.py). "auto" routes COUNT jobs on TPU to the
    #: partitioned kernel and weighted jobs to scatter (the weighted
    #: cascade stays opt-in: partitioned takes weighted jobs only
    #: under the bounded-integer contract ``weight_bound``, and only
    #: when requested explicitly). "scatter" is the escape hatch that
    #: pins the old kernel everywhere.
    cascade_backend: str = "auto"
    #: Bounded-integer weight contract for weighted partitioned jobs:
    #: every 'value' is an integer in [0, weight_bound]. Lifts the
    #: weighted lockout on the partitioned backend (the exactness slab
    #: shrinks to 2^24 // bound; violations are detected on device and
    #: surface as capacity overflow — ops/sparse_partitioned.py).
    #: Fractional weights CANNOT take this contract (f32 products
    #: round before accumulation; no slab restores exactness) — they
    #: stay on the scatter backend.
    weight_bound: int | None = None
    #: Shrink deep cascade levels to the real unique counts (one scalar
    #: sync per level; identical results — see
    #: ops.pyramid.pyramid_sparse_morton). Measured on CPU: ~1.1x warm,
    #: but the data-dependent level shapes cost ~16 extra XLA compiles
    #: cold (6x slower first run at 500k pts) — so OFF by default until
    #: the on-chip stage balance shows the per-level scatters dominating
    #: enough to pay for the compiles.
    adaptive_capacity: bool = False
    #: Data-parallelize the cascade over the process's LOCAL devices
    #: (reference scale-out analog: Spark's elastic executors,
    #: submit-heatmap:10-13). None (default) auto-enables when
    #: ``jax.local_device_count() > 1`` AND the call's emission count
    #: reaches AUTO_DP_MIN_EMISSIONS — a single-process v5e-8 host
    #: drives all 8 chips from the same ``run_job`` call on real
    #: workloads, while tiny inputs (and single chips) skip the mesh
    #: dispatch they'd only lose to. True forces the mesh path at any
    #: size and device count (the gspmd programs of parallel/gspmd.py
    #: run, results unchanged); False pins the single-device cascade.
    #: Counts and integer-valued weighted
    #: sums are bit-identical either way; fractional weighted sums
    #: agree up to f64 summation-order rounding (see
    #: cascade.build_cascade ``mesh``). Composes with multi-process
    #: runs (run_job_multihost): each process data-parallelizes its
    #: slice over its own local devices.
    data_parallel: bool | None = None
    #: Auto-DP engagement threshold override (emission count at which
    #: ``data_parallel=None`` engages the mesh). None uses the module
    #: default ``AUTO_DP_MIN_EMISSIONS``, which is calibrated from a
    #: CPU-mesh data point only — a v5e-8 operator should measure the
    #: real crossover (docs/OPERATIONS.md "Calibrating auto-DP") and
    #: set this (CLI ``--dp-min-emissions``). Meaningful for auto mode
    #: only; explicit True/False ignore the threshold, so combining is
    #: rejected at config time.
    dp_min_emissions: int | None = None
    #: Bucketed-padding compile cache (pipeline/bucketing.py): "exact"
    #: (default — shapes follow the input, every distinct batch size
    #: compiles fresh), "pow2" or "geometric" (pad emissions up to a
    #: power-of-two / 1.25x-geometric bucket with masked pad lanes, so
    #: arbitrary-size applies and streaming ticks reuse one compilation
    #: per bucket). Byte-neutral: decode truncates to real unique
    #: counts, pinned in tests/test_ingest.py. This knob is runtime
    #: tuning, NOT data semantics — delta/compact.CONFIG_FIELDS
    #: deliberately excludes it, so stores accept mixed settings.
    pad_bucketing: str = "exact"
    #: Bucket floor for pad_bucketing != "exact": batches below this
    #: many emissions share one compilation (bucketing.bucket_size).
    pad_bucket_min: int = 1 << 12
    #: Morton-range spatial sharding of the data-parallel cascade
    #: (parallel/partition.py): "auto" (default — when the mesh path
    #: engages AND the emission count reaches the auto-DP threshold,
    #: plan P-1 split codes from sampled quantiles; the gspmd range
    #: program routes each lane on-device to the shard owning its
    #: contiguous Z-order range and shrinks the cross-chip merge to
    #: boundary tiles only), "morton" (force range sharding whenever a
    #: mesh engages, any size), "off" (the uniform gspmd program).
    #: Byte-neutral: counts and integer-valued weighted sums are
    #: bit-identical to "off" (tests/test_partition.py pins blobs
    #: across backends); a degenerate plan (one range holds ~all
    #: sampled mass) falls back to the uniform program with a
    #: backend_resolved audit event (_dp_mesh_for). Composes with
    #: pad_bucketing and adaptive_capacity: the exchange widths sit on
    #: a geometric ladder, so jobs of one size share one compile.
    spatial_partition: str = "auto"

    def __post_init__(self):
        from heatmap_tpu.pipeline.bucketing import BUCKETING_MODES

        if self.pad_bucketing not in BUCKETING_MODES:
            raise ValueError(
                f"unknown pad_bucketing {self.pad_bucketing!r} (valid: "
                f"{', '.join(BUCKETING_MODES)}) — rejected at config "
                "time so a typo fails before a multi-hour ingest"
            )
        if self.pad_bucket_min < 1:
            raise ValueError(
                f"pad_bucket_min must be >= 1, got {self.pad_bucket_min}"
            )
        if self.dp_min_emissions is not None:
            if self.data_parallel is not None:
                raise ValueError(
                    "dp_min_emissions tunes AUTO data-parallel routing "
                    "only; data_parallel=True/False ignore the "
                    "threshold — rejected at config time so a "
                    "calibration flag that silently does nothing "
                    "cannot ship"
                )
            if self.dp_min_emissions < 0:
                raise ValueError(
                    f"dp_min_emissions must be >= 0, got "
                    f"{self.dp_min_emissions}"
                )
        if self.cascade_backend not in ("auto", "scatter", "partitioned"):
            raise ValueError(
                f"unknown cascade backend {self.cascade_backend!r} "
                "(valid: auto, scatter, partitioned) — rejected at "
                "config time so a typo fails before a multi-hour ingest"
            )
        if (self.weighted and self.cascade_backend == "partitioned"
                and self.weight_bound is None):
            raise ValueError(
                "cascade backend 'partitioned' takes weighted jobs "
                "only under the bounded-integer contract: set "
                "weight_bound (every 'value' an integer in "
                "[0, weight_bound]); fractional weights use the "
                "scatter backend — rejected at config time so the "
                "combination fails before ingest"
            )
        if self.weight_bound is not None:
            if not self.weighted:
                raise ValueError(
                    "weight_bound declares the weighted integer "
                    "contract and needs weighted=True — rejected at "
                    "config time so a silently ignored bound cannot "
                    "ship"
                )
            if self.weight_bound < 1:
                raise ValueError(
                    f"weight_bound must be >= 1, got {self.weight_bound}"
                )
            # The partitioned cascade runs at the kernel's default
            # geometry (chunk=1024, streams=1), where the f32
            # exactness slab 2^24 // bound must hold at least one
            # chunk row — beyond that NO slab size keeps weighted
            # sums exact (ops/sparse_partitioned.py refuses too, but
            # a config-time rejection beats a mid-job one).
            max_bound = (1 << 24) // 1024
            if (self.cascade_backend == "partitioned"
                    and self.weight_bound > max_bound):
                raise ValueError(
                    f"weight_bound {self.weight_bound} exceeds the "
                    f"partitioned backend's exactness limit "
                    f"{max_bound} (slab 2^24 // bound must hold one "
                    "1024-element chunk) — use the scatter backend "
                    "for larger weights"
                )
        if self.spatial_partition not in ("auto", "morton", "off"):
            raise ValueError(
                f"unknown spatial_partition {self.spatial_partition!r} "
                "(valid: auto, morton, off) — rejected at config time "
                "so a typo fails before a multi-hour ingest"
            )
        if self.spatial_partition == "morton":
            if self.data_parallel is False:
                raise ValueError(
                    "spatial_partition='morton' range-shards the "
                    "data-parallel cascade; data_parallel=False pins "
                    "the single-device path — rejected at config time "
                    "so a silently ignored partition cannot ship"
                )

    @property
    def resolved_dispatch(self) -> str:
        """The one mesh formulation of the data-parallel cascade:
        "gspmd", the programs of parallel/gspmd.py (read-only; the
        ``dispatch`` field of ``backend_resolved`` events)."""
        return "gspmd"

    @property
    def resolved_cascade_backend(self) -> str:
        """The backend the cascade actually runs: on TPU, "auto"
        resolves to the partitioned MXU kernel for count jobs (the
        measured 1.8x cascade win, bit-identical blobs) and to scatter
        for weighted jobs — the weighted partitioned route needs the
        bounded-integer contract and stays an explicit request. Off
        TPU "auto" stays on scatter: the pallas kernel only runs in
        interpret mode there (orders slower than the native XLA
        scatter), the same platform gate ops/histogram._pick_backend
        applies. An explicit "partitioned" is honored anywhere."""
        if self.cascade_backend != "auto":
            return self.cascade_backend
        if self.weighted:
            return "scatter"
        import jax

        on_tpu = jax.devices()[0].platform == "tpu"
        return "partitioned" if on_tpu else "scatter"

    def cascade_config(self) -> cascade_mod.CascadeConfig:
        return cascade_mod.CascadeConfig(
            detail_zoom=self.detail_zoom,
            min_detail_zoom=self.min_detail_zoom,
            result_delta=self.result_delta,
            amplify_all=self.amplify_all,
        )


def _row_get(row, key, default=None):
    """Mapping-style ``.get`` for dicts AND pyspark-Row-shaped rows.

    ``pyspark.sql.Row`` is a tuple subclass with ``__getitem__`` by
    field name but NO ``.get`` method — the exact objects a
    ``df.rdd.mapPartitions`` body receives (the Spark-boundary
    contract of spark_adapter.py; the reference's mappers indexed Rows
    by name the same way, reference heatmap.py:27-35). Missing fields
    raise ValueError there, KeyError on mappings — both mean
    ``default``.
    """
    getter = getattr(row, "get", None)
    if getter is not None:
        return getter(key, default)
    try:
        return row[key]
    except (KeyError, ValueError, IndexError, TypeError):
        return default


def load_rows(rows):
    """Ingest filter + column extraction (reference dataframe_loader,
    heatmap.py:25-36): drops ``source == "background"`` rows, keeps
    (latitude, longitude, user_id, timestamp) and, when any row
    carries one, the optional ``value`` weight column (absent values
    default 1.0 — the reference counts 1.0 per row, heatmap.py:35).

    ``rows``: iterable of dicts OR pyspark-Row-shaped objects with the
    reference's column names. Returns dict of host arrays/lists.
    """
    lats, lons, users, stamps, vals = [], [], [], [], []
    _missing = object()
    any_value = False
    for row in rows:
        if _row_get(row, "source") == BACKGROUND_SOURCE:
            continue
        lats.append(row["latitude"])
        lons.append(row["longitude"])
        users.append(row["user_id"])
        stamps.append(_row_get(row, "timestamp"))
        # Keyed on field PRESENCE, not non-None values: a partition
        # whose rows all carry value=None must still emit the column
        # (nulls default 1.0) — otherwise the same weighted job fails
        # or succeeds depending on partition placement.
        v = _row_get(row, "value", _missing)
        any_value = any_value or v is not _missing
        vals.append(None if v is _missing else v)
    out = {
        "latitude": np.asarray(lats, np.float64),
        "longitude": np.asarray(lons, np.float64),
        "user_id": users,
        "timestamp": stamps,
    }
    if any_value:
        out["value"] = np.asarray(
            [1.0 if v is None else float(v) for v in vals], np.float64
        )
    return out


def project_detail_codes(lat: np.ndarray, lon: np.ndarray, detail_zoom: int,
                         prefer_device: bool = True):
    """f64 projection to detail-zoom Morton codes + validity.

    When x64 is enabled the projection and bit-interleave run ON DEVICE
    in float64/int64, bit-identical to the CPython-double oracle at z21
    (host numpy projection would otherwise sit on every job's ingest
    path). Both
    implementations follow the same IEEE-double op order (reference
    tile.py:17,21), so the paths agree bit-for-bit and are
    cross-checked in tests. Without x64 (or with
    ``prefer_device=False``) the host numpy path is used — device f32
    cannot place z21 points.
    """
    import jax

    if prefer_device and jax.config.jax_enable_x64:
        import jax.numpy as jnp

        codes, valid = _project_codes_jit(
            jnp.asarray(lat, jnp.float64), jnp.asarray(lon, jnp.float64),
            detail_zoom,
        )
        return np.asarray(codes), np.asarray(valid)
    row, col, valid = mercator.project_points_np(lat, lon, detail_zoom)
    return morton.morton_encode_np(row, col), valid


@functools.partial(jax.jit, static_argnames=("zoom",))
def _project_codes_jit(lat, lon, zoom):
    import jax.numpy as jnp

    row, col, valid = mercator.project_points(lat, lon, zoom,
                                              dtype=jnp.float64)
    return morton.morton_encode(row, col, dtype=jnp.int64, zoom=zoom), valid


#: Auto data-parallel engages only past this many emissions (explicit
#: ``data_parallel=True`` always engages). Below it the per-device
#: slices are too small for the mesh dispatch + cross-chip merge
#: to pay for themselves on ANY backend — measured 9x SLOWER on the
#: 8-device CPU mesh with 150-point bounded chunks (eager per-chunk
#: dispatch), and a real chip gains nothing from sharding a few
#: thousand rows eight ways. Auto-routing must never slow down jobs
#: that were fine (the _auto_points_in_flight rule applied to DP).
AUTO_DP_MIN_EMISSIONS = 1 << 18


def _dp_mesh(config: BatchJobConfig):
    """Mesh over the process's local devices for the cascade's
    data-parallel route, or None for the single-device cascade.

    Capability gate only — the per-call size gate is
    :func:`_dp_mesh_for`. Auto (``data_parallel=None``) engages only
    past one local device: the mesh path is bit-identical but adds a
    mesh dispatch that a single chip gains nothing from. Both cascade
    backends and adaptive capacities compose with the mesh
    (parallel/gspmd.py).
    """
    if config.data_parallel is False:
        return None
    if config.data_parallel is None and jax.local_device_count() < 2:
        return None
    from heatmap_tpu.parallel.mesh import make_mesh

    return make_mesh(devices=jax.local_devices())


def _dp_mesh_for(mesh, config: BatchJobConfig, n_emissions: int,
                 plan=None):
    """The mesh to pass this cascade call, or None: auto engages only
    at AUTO_DP_MIN_EMISSIONS and up; explicit True always engages.

    ``plan`` makes the decision plan-aware rather than a function of
    ``n_emissions`` alone: a proposed Morton partition plan
    (parallel.partition.PartitionPlan) whose sampled mass is degenerate
    — effectively one non-empty range — must NOT ride the range-sharded
    path, because routing all mass to one shard serializes the cascade
    (strictly worse than the uniform-DP mesh this threshold was
    calibrated for). The call then keeps the uniform mesh and records
    the fallback as a ``backend_resolved`` event
    (reason="degenerate partition plan...") so the routing decision
    stays auditable; callers must drop the plan when it is degenerate
    (_run_grouped and the elastic planner do).
    """
    if mesh is None:
        return None
    threshold = (AUTO_DP_MIN_EMISSIONS if config.dp_min_emissions is None
                 else config.dp_min_emissions)
    if config.data_parallel is None and n_emissions < threshold:
        return None
    if plan is not None and plan.degenerate and obs.telemetry_enabled():
        obs.emit(
            "backend_resolved",
            requested=f"spatial_partition={config.spatial_partition}",
            resolved="uniform-dp",
            reason=("degenerate partition plan (max shard mass "
                    f"{max(plan.shard_mass or [0.0]):.3f}) would "
                    "serialize the cascade on one shard — falling "
                    "back to uniform DP"),
            spatial_partition=config.spatial_partition,
            n_emissions=int(n_emissions),
        )
    return mesh


def _cascade_codes(lat, lon, detail_zoom):
    """Codes + validity feeding the cascade: DEVICE-RESIDENT under x64
    (projection through emission assembly to the cascade sort never
    round-trips the big code column through the host), host numpy
    otherwise."""
    if jax.config.jax_enable_x64:
        import jax.numpy as jnp

        return _project_codes_jit(
            jnp.asarray(lat, jnp.float64), jnp.asarray(lon, jnp.float64),
            detail_zoom,
        )
    return project_detail_codes(lat, lon, detail_zoom, prefer_device=False)


def build_emissions(codes, valid, group_ids, timestamps,
                    config: BatchJobConfig, ts_vocab: TimespanVocab | None = None,
                    weights=None):
    """Expand points into (code, slot) emissions + slot name table.

    Mirrors the reference mapper's group expansion (heatmap.py:64-75):
    each point emits once for 'all' and once for its routed group (if
    not excluded), for each requested timespan. With
    ``first_timespan_only`` (reference early-return quirk, SURVEY.md
    §8.2) only the first timespan emits. Pass a shared ``ts_vocab`` to
    keep timespan ids consistent across chunked calls.

    ``codes``/``valid`` may be device arrays (the x64 ingest path keeps
    them device-resident from projection to cascade — no host
    round-trip of the big code column). In that case the slot ids are
    assembled on device as well, from int32 uploads of the host-vocab
    id columns (half the transfer of pre-built int64 slots, no host
    concatenation). ``group_ids`` must be numpy.

    ``weights`` (per-point values, weighted jobs) expand exactly like
    the codes — each emission carries its point's weight; the returned
    weights entry is None when not given.
    """
    ts_vocab = ts_vocab if ts_vocab is not None else TimespanVocab()
    timespans = (
        config.timespans[:1] if config.first_timespan_only else config.timespans
    )
    per_ts_ids = [ts_vocab.label_ids(t, timestamps) for t in timespans]
    n_groups = int(group_ids.max(initial=ALL_GROUP)) + 1
    on_device = not isinstance(codes, np.ndarray)
    if on_device:
        import jax.numpy as jnp
    xp = jnp if on_device else np
    keep = group_ids != EXCLUDED
    keep_x = xp.asarray(keep)
    routed = np.where(keep, group_ids, 0).astype(np.int32)
    routed_x = xp.asarray(routed)
    weights_x = None if weights is None else xp.asarray(weights)
    emit_codes, emit_slots, emit_valid = [], [], []
    for ts_ids in per_ts_ids:
        ts_x = xp.asarray(ts_ids.astype(np.int32))
        ts64 = ts_x.astype(xp.int64)
        # 'all' emission for every point.
        emit_codes.append(codes)
        emit_slots.append(ts64 * n_groups + ALL_GROUP)
        emit_valid.append(valid)
        # per-user emission for non-excluded points.
        emit_codes.append(codes)
        emit_slots.append(ts64 * n_groups + routed_x)
        emit_valid.append(valid & keep_x)
    n_copies = 2 * len(per_ts_ids)
    e_weights = (
        None if weights_x is None
        else xp.concatenate([weights_x] * n_copies)
    )
    return (
        xp.concatenate(emit_codes),
        xp.concatenate(emit_slots),
        xp.concatenate(emit_valid),
        ts_vocab,
        n_groups,
        e_weights,
    )


def load_columns(batch):
    """Vectorized ingest filter over a columnar source batch
    (heatmap_tpu.io.sources layout): drops ``source == "background"``
    rows (reference heatmap.py:28-29) without touching per-row Python.
    """
    src = batch.get("source")
    lat = np.asarray(batch["latitude"], np.float64)
    lon = np.asarray(batch["longitude"], np.float64)
    users = batch["user_id"]
    stamps = batch.get("timestamp")
    values = batch.get("value")  # optional per-point weight (config 3)
    if values is not None:
        values = np.asarray(values, np.float64)
    if stamps is None or len(stamps) == 0:
        stamps = [None] * len(lat)
    if src is not None and len(src):
        keep = np.asarray(src, object) != BACKGROUND_SOURCE
        if not keep.all():
            idx = np.flatnonzero(keep)
            lat, lon = lat[idx], lon[idx]
            users = [users[i] for i in idx]
            stamps = [stamps[i] for i in idx]
            if values is not None:
                values = values[idx]
    out = {
        "latitude": lat,
        "longitude": lon,
        "user_id": list(users),
        "timestamp": list(stamps),
    }
    if values is not None:
        out["value"] = values
    return out


def run_job(source, sink=None, config: BatchJobConfig | None = None,
            batch_size: int = 1 << 20,
            max_points_in_flight: int | None = None,
            overlap_ingest: bool = True,
            merge_spill_dir: str | None = None):
    """Source-to-sink job over columnar batches (the production entry;
    reference batchMain shape with get_rows/write_heatmap_dataframes
    replaced by heatmap_tpu.io sources/sinks, heatmap.py:152-158).

    Accumulates host columns across source batches, runs the cascade
    once on device, writes blobs to ``sink`` (upsert-by-id). Returns
    the blob dict; if ``sink`` is given also writes into it.

    ``max_points_in_flight`` bounds peak memory for sources larger than
    host RAM (BASELINE.md config 5 shape): the cascade runs per chunk of
    at most that many points and per-level aggregates merge on the host
    — exact, because every level is a linear (key, sum) reduction, the
    same property the Spark adapter's partition merge relies on
    (spark_adapter.merge_heatmaps). (Counts and integer-valued weights
    are bit-identical to the unchunked path; fractional weighted sums
    agree up to f64 summation-order rounding.) Peak footprint is then
    O(chunk + unique aggregate keys) instead of O(total points).
    ``overlap_ingest`` double-buffers the bounded path: a prefetch
    thread parses chunk N+1 while the device cascades chunk N (see
    _run_job_bounded; identical results, up to 3 chunks resident).

    ``max_points_in_flight=None`` (default) AUTO-ROUTES: when the
    source's estimated point count would not fit host RAM single-shot
    (_auto_points_in_flight heuristic — declared/estimated source rows
    vs MemAvailable), the job takes the bounded path with a RAM-derived
    chunk size instead of requiring the operator to know the knob (the
    default run on a bigger-than-RAM CSV must not OOM). Pass ``0`` to force the single-shot path, or an explicit
    point count to pick the chunk size yourself. The bounded path's
    in-RAM cross-chunk merge is O(unique output keys);
    ``merge_spill_dir`` lifts that too, spilling
    per-chunk aggregates to disk and merging one level at a time at
    egress (_SpillMerge) — for near-unique-output shapes whose merge
    table outgrows RAM.
    """
    from heatmap_tpu.obs import tracing
    from heatmap_tpu.utils.trace import get_tracer

    config = config or BatchJobConfig()
    if max_points_in_flight is None:
        max_points_in_flight = _auto_points_in_flight(source)
    if merge_spill_dir is not None and not max_points_in_flight:
        raise ValueError(
            "merge_spill_dir lives on the bounded path, but this job "
            "routed single-shot (source fits host RAM, is unsizeable, "
            "or bounding was disabled with 0); pass "
            "max_points_in_flight > 0 to chunk — silently ignoring the "
            "spill request would run the in-RAM merge it exists to avoid"
        )
    # Tree-only span (no aggregate entry): a bare run_job call under
    # tracing yields ONE connected tree whose ingest/cascade/egress
    # tracer spans all parent here (root-on-demand when no CLI root).
    with tracing.span("run_job", bounded=bool(max_points_in_flight)):
        if max_points_in_flight:  # 0/None -> single-shot
            return _run_job_bounded(
                source, sink, config, batch_size, max_points_in_flight,
                overlap_ingest=overlap_ingest, spill_dir=merge_spill_dir,
            )
        tracer = get_tracer()
        data = ingest_columns(source.batches(batch_size), config)
        if data is None:
            return {}
        with tracer.span("cascade", items=len(data["latitude"])):
            blobs = _run_loaded(data, config, as_json=True, sink=sink)
        return blobs


#: Rough host bytes per point on the string ingest path: two f64
#: coords (16) + a user-id pointer/str share (~60) + a timestamp list
#: slot (~40) + concatenate/emission slack. Deliberately conservative —
#: the cost of underestimating is an OOM, of overestimating a slightly
#: smaller chunk.
_HOST_BYTES_PER_POINT = 160

#: Text-source row-size floor (bytes) for estimating points from file
#: size: a minimal "lat,lon,user" CSV row. Underestimating bytes/row
#: overestimates points, which errs toward bounding — the safe side.
_MIN_TEXT_ROW_BYTES = 32

#: Bounded path: convert the in-RAM cross-chunk merge table to the
#: disk-spill merge once it exceeds this many aggregate rows (~200 MB
#: of columns; the spilled runs are 24 B/row in the system temp dir).
#: Past this size the iterative fold's per-chunk re-scan of the whole
#: table loses to one egress-time sort per level. Small-output jobs
#: never cross it and never touch disk.
AUTO_SPILL_ROWS = 8_000_000

#: Directory for AUTOMATIC spill (None -> tempfile.gettempdir()).
#: Set this (or pass merge_spill_dir explicitly) to redirect; the
#: TMPDIR env var works too, via gettempdir().
AUTO_SPILL_DIR: str | None = None


def _mount_fstype(path: str, mounts_file: str = "/proc/mounts") -> str | None:
    """Filesystem type of the longest mount-point prefix of ``path``
    (Linux), or None when undeterminable (non-Linux: best effort)."""
    try:
        real = os.path.realpath(path)
        best, fstype = "", None
        with open(mounts_file) as f:
            for line in f:
                parts = line.split()
                if len(parts) < 3:
                    continue
                mnt, typ = parts[1], parts[2]
                if real == mnt or real.startswith(mnt.rstrip("/") + "/") \
                        or mnt == "/":
                    if len(mnt) > len(best):
                        best, fstype = mnt, typ
        return fstype
    except OSError:
        return None


def _free_disk_bytes(path: str) -> int | None:
    """Free bytes available to this process on ``path``'s filesystem,
    or None when unknowable (the caller keeps its measured default)."""
    try:
        st = os.statvfs(path)
        return st.f_bavail * st.f_frsize
    except (OSError, AttributeError):
        return None


def _auto_spill_projection_fits(spill_dir: str, table_rows: int,
                                chunks_done: int,
                                total_chunks_est: int | None,
                                max_chunk_rows: int) -> bool:
    """Will the projected spill volume fit the target filesystem?

    Auto-spill must never convert a job that was finishing fine in RAM
    into an ENOSPC failure on a small disk-backed temp dir (tmpfs is
    already refused by _auto_spill_target; SIZE was not checked before
    this). Projection: the accumulated table spills immediately
    (24 B/row) and each remaining chunk adds at most the largest
    chunk's output seen so far; when the source size is unknowable,
    assume as many chunks remain as have run. 25% headroom — the
    projection errs conservative, and the write-failure fallback below
    still catches a filesystem that fills anyway.
    """
    free = _free_disk_bytes(spill_dir)
    if free is None:
        return True
    remaining = (chunks_done if total_chunks_est is None
                 else max(total_chunks_est - chunks_done, 0))
    projected = 24 * (table_rows + remaining * max_chunk_rows)
    return projected + projected // 4 <= free


def _auto_spill_target() -> str | None:
    """Directory for automatic spill, or None to stay in-RAM.

    RAM-backed candidates (tmpfs/ramfs — /tmp on many distros) are
    refused: spilling there moves pages from process RSS into tmpfs,
    which the OOM killer counts all the same, and a size-capped tmpfs
    would ENOSPC a job the in-RAM fold finishes. Explicit
    ``merge_spill_dir`` is never second-guessed.
    """
    import tempfile

    cand = AUTO_SPILL_DIR or tempfile.gettempdir()
    if _mount_fstype(cand) in ("tmpfs", "ramfs"):
        return None
    return cand


def _available_ram_bytes() -> int | None:
    """MemAvailable from /proc/meminfo (Linux), else total RAM via
    sysconf, else None (no auto-routing without a signal)."""
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    try:
        import os as _os

        return _os.sysconf("SC_PAGE_SIZE") * _os.sysconf("SC_PHYS_PAGES")
    except (ValueError, OSError, AttributeError):
        return None


def _estimate_source_points(source) -> int | None:
    """Best-effort source row count: a declared ``n`` (Synthetic, HMPB)
    beats a file-size estimate (text sources); None when unknowable
    (generators, network sources — those scale via multihost range
    sharding instead)."""
    import os as _os

    n = getattr(source, "n", None)
    if n is not None:
        return int(n)
    path = source if isinstance(source, str) else getattr(source, "path", None)
    if isinstance(path, str):
        try:
            if _os.path.isdir(path):
                size = sum(
                    e.stat().st_size for e in _os.scandir(path) if e.is_file()
                )
            else:
                size = _os.path.getsize(path)
        except OSError:
            return None
        return size // _MIN_TEXT_ROW_BYTES
    return None


def _auto_points_in_flight(source, ram_budget: int | None = None,
                           shard_count: int = 1,
                           fast: bool = False,
                           n_timespans: int = 1,
                           weighted: bool = False) -> int | None:
    """Bounded-path chunk size when the source won't fit RAM, else None.

    Half of MemAvailable is the working budget; a source whose
    estimated host columns exceed it routes to the bounded path with a
    chunk of a quarter of what fits (cascade state + double-buffered
    ingest + device arrays share the budget). Sources that fit keep
    the faster single-shot path — auto-routing must never slow down
    jobs that were fine.

    ``shard_count``: divide the estimate by the number of processes
    sharing the source (run_job_multihost ingests ~1/k of the rows per
    host, so the fit decision is about the slice, not the whole file).

    ``fast`` (run_job_fast's auto call): consult the source's
    ``fast_host_bytes_per_point`` — HMPB mmap ingest is near-zero-copy
    (~30 B/point of materialized routed columns vs 160 B of string
    ingest), so a large HMPB file that fits single-shot must not be
    silently demoted to the chunked path by the string-path constant.
    ``n_timespans`` scales the per-emission share added
    on top of the declared rate (each timespan doubles the emission
    arrays). The string path never reads the attribute: the same
    source consumed through ``batches()`` materializes Python strings
    at the conservative rate.
    """
    est = _estimate_source_points(source)
    if est is None:
        return None
    est = -(-est // max(shard_count, 1))
    if ram_budget is None:
        avail = _available_ram_bytes()
        if avail is None:
            return None
        ram_budget = avail // 2
    bytes_per_point = _HOST_BYTES_PER_POINT
    if fast:
        declared = getattr(source, "fast_host_bytes_per_point", None)
        if declared is not None:
            # The declared rate covers resident ingest columns only;
            # the emission/sort arrays (i64 code + i64 slot + valid,
            # ~2x transiently under the cascade sort ≈ 32 B/emission,
            # 2 emissions per timespan per point) share the same
            # budget — on host-memory backends they ARE host RAM, so
            # the fit check must include them or a "fitting" file can
            # materialize several times the budget single-shot.
            bytes_per_point = declared + 64 * max(n_timespans, 1)
            if weighted:
                # Weighted jobs carry an f64 value column (+8 B/pt)
                # and expand f64 e_weights per emission with the same
                # 2x transient factor (+32 B/timespan/pt).
                bytes_per_point += 8 + 32 * max(n_timespans, 1)
    fits = ram_budget // bytes_per_point
    if est <= fits:
        return None
    # A quarter of what fits (up to 3 chunks resident under
    # overlap_ingest, plus merge state), floored at 64k points so tiny
    # hosts still get device-worthy batches — the floor must stay well
    # UNDER the budget or auto-bounding would itself overrun the RAM it
    # exists to protect.
    return max(1 << 16, fits // 4)


def ingest_columns(batches, config: BatchJobConfig):
    """Accumulate source batches into the ``_run_loaded`` data dict.

    Shared by run_job and the multi-process run_job_multihost ingest so
    weighted-column validation and assembly can't drift between them.
    Returns None when the batches carried no rows.
    """
    from heatmap_tpu.utils.trace import get_tracer

    tracer = get_tracer()
    lats, lons, users, stamps, vals = [], [], [], [], []
    for batch in batches:
        with tracer.span("ingest.batch"):
            with tracer.span("ingest.load_columns"):
                cols = load_columns(batch)
            lats.append(cols["latitude"])
            lons.append(cols["longitude"])
            users.extend(cols["user_id"])
            stamps.extend(cols["timestamp"])
            if config.weighted:
                _require_value_column(cols)
                vals.append(cols["value"])
        tracer.add_items("ingest.batch", len(cols["latitude"]))
    if not lats or sum(len(a) for a in lats) == 0:
        return None
    data = {
        "latitude": np.concatenate(lats),
        "longitude": np.concatenate(lons),
        "user_id": users,
        "timestamp": stamps,
    }
    if config.weighted:
        data["value"] = np.concatenate(vals)
    return data


class _FastRouter:
    """Maps fast-batch reader group ids into a shared UserVocab.

    Fast batches carry ``routed`` ids into a reader-side ``names``
    table that grows via ``new_group_names``; vocab ids are assigned in
    first-use order of KEPT rows so they match the string path's
    assignment order exactly (run_job_fast and the fast bounded path
    share this logic — divergence here would silently shuffle user
    attribution between paths).
    """

    def __init__(self, vocab: UserVocab):
        self.vocab = vocab
        self.names: list = []
        self._map = np.full(1024, -2, np.int32)  # -2 = not yet mapped

    def observe(self, batch):
        """Grow the reader name table (REQUIRED for every batch, even
        ones whose rows are skipped — later batches reference ids first
        named earlier)."""
        self.names.extend(batch["new_group_names"])

    def route(self, batch):
        """-> (lat, lon, gids, ts_i64, values_or_None), background rows
        dropped. ``values`` comes through when the fast batch carries a
        'value' column (HMPB with a value section), filtered by the
        same keep mask."""
        if len(self.names) > len(self._map):
            grown = np.full(max(len(self.names), 2 * len(self._map)),
                            -2, np.int32)
            grown[: len(self._map)] = self._map
            self._map = grown
        keep = ~batch["background"]
        routed = batch["routed"][keep]
        ref_ids = routed[routed >= 0]
        unmapped = self._map[ref_ids] == -2
        if unmapped.any():
            first_use = ref_ids[unmapped]
            _, order = np.unique(first_use, return_index=True)
            for rid in first_use[np.sort(order)]:
                if self._map[rid] == -2:
                    self._map[rid] = self.vocab.id_for(self.names[rid])
        gids = np.where(
            routed >= 0, self._map[np.maximum(routed, 0)], EXCLUDED
        ).astype(np.int32)
        ts = batch.get("timestamp")
        ts64 = (
            np.full(int(keep.sum()), TS_MISSING, np.int64)
            if ts is None else np.asarray(ts, np.int64)[keep]
        )
        vals = batch.get("value")
        if vals is not None:
            vals = np.asarray(vals, np.float64)[keep]
        return (batch["latitude"][keep], batch["longitude"][keep], gids,
                ts64, vals)


def _check_checkpoint_weighted(meta, config: BatchJobConfig,
                               checkpoint_dir: str):
    """Refuse to resume a checkpoint under the other ingest mode —
    mixing counted and weighted rows in one accumulation would corrupt
    every blob. Checkpoints without the key are counted: they predate
    weighted checkpointing, which refused weighted+checkpoint outright,
    so treating the absence as counted=True keeps the refusal message
    (instead of a bare KeyError on the missing values array)."""
    ck = bool(meta.get("weighted", False))
    if ck != bool(config.weighted):
        raise RuntimeError(
            f"checkpoint at {checkpoint_dir!r} was written by a "
            f"{'weighted' if ck else 'counted'} job; resume with the "
            f"matching weighted setting or a fresh checkpoint dir"
        )


def _require_value_column(cols):
    """Shared guard for weighted string-path ingest: the source batch
    must carry a 'value' column."""
    if "value" not in cols:
        raise ValueError(
            "weighted job needs a 'value' column in the source "
            "(CSV/JSONL/Parquet column named 'value')"
        )


def _require_fast_weights(values):
    """Shared guard for weighted fast ingest: fast batches must carry a
    'value' column (HMPB with a value section)."""
    if values is None:
        raise ValueError(
            "weighted fast job needs a 'value' column in the fast "
            "batches (convert the source to HMPB from an input with a "
            "'value' column)"
        )


def _fast_batches_for(source, batch_size, checkpointing=False):
    """The run_job_fast input contract: CSV path -> native decoder,
    else an object with ``fast_batches``."""
    if isinstance(source, str):
        try:
            from heatmap_tpu.native import parse_csv_batches
        except ImportError as e:
            raise RuntimeError(
                "run_job_fast on a CSV path needs the native decoder "
                "(native/ build failed or disabled); use "
                "run_job(CSVSource(path)) instead"
            ) from e
        return parse_csv_batches(
            source, batch_size, fast=True,
            n_workers=1 if checkpointing else None,
        )
    if hasattr(source, "fast_batches"):
        return source.fast_batches(batch_size)
    raise TypeError(
        f"run_job_fast needs a CSV path or a fast-batch source "
        f"(got {type(source).__name__}); use run_job for generic "
        f"sources"
    )


def _resolve_backend(config: BatchJobConfig, n_emissions: int | None = None,
                     data_parallel: bool = False) -> str:
    """Resolve the cascade backend once per job and leave an audit
    trail: a ``backend_resolved`` event recording how ``"auto"`` routed
    (and why), plus the ``points_binned_total`` ingress counter when the
    emission count is known at resolution time. Pure pass-through of
    ``config.resolved_cascade_backend`` when telemetry is off.

    When the mesh engages (``data_parallel``), the event also carries
    ``dispatch``: the mesh formulation that runs ("gspmd").
    """
    resolved = config.resolved_cascade_backend
    if not obs.telemetry_enabled():
        return resolved
    if config.cascade_backend != "auto":
        reason = "explicit request"
    elif config.weighted:
        reason = ("weighted jobs stay on scatter (the bounded-integer "
                  "partitioned contract is an explicit opt-in)")
    elif resolved == "partitioned":
        reason = "count job on tpu -> partitioned MXU kernel"
    else:
        reason = "non-tpu platform -> xla scatter"
    if n_emissions is not None:
        obs.POINTS_BINNED.inc(int(n_emissions), backend=resolved)
    fields = {"requested": config.cascade_backend, "resolved": resolved,
              "reason": reason, "weighted": bool(config.weighted),
              "data_parallel": bool(data_parallel)}
    if data_parallel:
        fields["dispatch"] = config.resolved_dispatch
    if n_emissions is not None:
        fields["n_emissions"] = int(n_emissions)
    obs.emit("backend_resolved", **fields)
    return resolved


def _run_job_bounded(source, sink, config: BatchJobConfig,
                     batch_size: int, max_points: int,
                     overlap_ingest: bool = True, fast: bool = False,
                     spill_dir: str | None = None):
    """Chunked cascade with host-side per-level aggregate merge.

    Spark streams partitions through executors (reference
    heatmap.py:111-117); the analog here: chunks of at most
    ``max_points`` points run the full device cascade, and the decoded
    per-level (timespan, group, code) -> sum aggregates fold into one
    running table per level. UserVocab / TimespanVocab are shared
    across chunks so ids stay consistent; slot packing is re-derived
    from the FINAL vocab sizes at egress (per-chunk packing uses the
    chunk-local group count, which decode inverts exactly).

    ``overlap_ingest`` (the PP analog of SURVEY.md §2.3: the reference
    ran zoom stages strictly sequentially): a producer thread parses /
    group-routes the NEXT chunk while the device runs the cascade on
    the current one, double-buffered through a depth-1 queue. Chunk
    order — and therefore every vocab id and merge result — is
    identical to the sequential path; peak footprint grows to at most
    3 chunks (building + queued + in-cascade). Set False for the
    strict 1-chunk memory bound.

    ``spill_dir``: write per-chunk level aggregates to disk instead of
    folding them into an in-RAM table, merging one level at a time at
    egress (_SpillMerge) — for near-unique-output shapes where the
    merge table itself outgrows RAM. Byte-identical results.

    Without an explicit ``spill_dir`` the job still AUTO-SPILLS (to
    the system temp dir, or AUTO_SPILL_DIR) once the in-RAM table
    crosses AUTO_SPILL_ROWS: the running table converts to spill run 0
    and later chunks spill directly; small-output jobs never touch disk, and a RAM-backed
    temp dir (tmpfs /tmp) disables auto-spill rather than fake the
    memory win (_auto_spill_target).
    """
    from heatmap_tpu.utils.trace import get_tracer

    if max_points < 1:
        raise ValueError(f"max_points_in_flight must be >= 1, got {max_points}")
    tracer = get_tracer()
    vocab = UserVocab()
    ts_vocab = TimespanVocab()
    ccfg = config.cascade_config()
    n_levels = ccfg.n_levels + 1
    empty = {
        "ts": np.empty(0, np.int64), "g": np.empty(0, np.int64),
        "code": np.empty(0, np.int64), "value": np.empty(0, np.float64),
    }
    merged = [dict(empty) for _ in range(n_levels)]
    spill = _SpillMerge(spill_dir, n_levels) if spill_dir is not None else None
    spill_runs = 0
    spill_is_auto = False
    # Candidate dir for automatic spill; None = RAM-backed temp (or
    # redirected off) -> keep the in-RAM fold, the pre-round-3 behavior.
    auto_spill_dir = _auto_spill_target() if spill is None else None
    # Spill-volume projection inputs (the free-space check at auto
    # conversion): total chunk count when the source size is estimable.
    est_points = _estimate_source_points(source)
    total_chunks_est = (
        None if est_points is None else -(-est_points // max_points)
    )
    chunks_done = 0
    max_chunk_rows = 0

    def chunks():
        """Sequential chunk builder: ingest batches, cut at max_points.

        ``fast`` consumes the integer fast-batch layout (native CSV
        decoder / HMPB mmap) routed through the shared _FastRouter;
        the string path goes through load_columns + vocab routing.
        Either way a chunk is (lat, lon, gids, stamps, weights) with
        stamps an i64 array (fast) or a Python list (string) —
        build_emissions' timespan labeler accepts both — and weights
        an f64 array for weighted jobs, None otherwise.
        """
        lats, lons, gids, stamps, vals = [], [], [], [], []
        pending = 0

        def cut():
            nonlocal pending
            chunk = (
                np.concatenate(lats),
                np.concatenate(lons),
                np.concatenate(gids).astype(np.int32),
                np.concatenate(stamps) if fast
                else [s for b in stamps for s in b],
                np.concatenate(vals) if config.weighted else None,
            )
            lats.clear(); lons.clear(); gids.clear(); stamps.clear()
            vals.clear()
            pending = 0
            return chunk

        if fast:
            router = _FastRouter(vocab)
            batches = _fast_batches_for(source, min(batch_size, max_points))
        else:
            batches = source.batches(min(batch_size, max_points))
        for batch in batches:
            with tracer.span("ingest.batch"):
                if fast:
                    router.observe(batch)
                    lat, lon, g, ts, v = router.route(batch)
                    if config.weighted:
                        _require_fast_weights(v)
                else:
                    cols = load_columns(batch)
                    lat = cols["latitude"]
                    lon = cols["longitude"]
                    g = vocab.group_ids(cols["user_id"])
                    ts = cols["timestamp"]
                    v = cols.get("value")
                    if config.weighted and v is None:
                        _require_value_column(cols)
                m = len(lat)
                # Cut BEFORE appending when the batch would overshoot,
                # so a chunk never exceeds max_points (batches are read
                # at most max_points long).
                if pending and pending + m > max_points:
                    yield cut()
                lats.append(lat)
                lons.append(lon)
                gids.append(g)
                stamps.append(ts)
                if config.weighted:
                    vals.append(v)
                pending += m
            tracer.add_items("ingest.batch", m)
            if pending >= max_points:
                yield cut()
        if pending:
            yield cut()

    dp_mesh = _dp_mesh(config)
    # Resolved ONCE for the whole job (the property probes jax.devices()
    # on every read) and audited via backend_resolved; per-chunk
    # dispatch details land in cascade_dispatch events.
    resolved_backend = _resolve_backend(
        config, data_parallel=dp_mesh is not None)

    def process(chunk):
        lat, lon, group_ids, flat_stamps, weights = chunk
        with tracer.span("cascade.chunk", items=len(lat),
                         backend=resolved_backend):
            import jax.numpy as jnp

            codes, valid = _cascade_codes(lat, lon, config.detail_zoom)
            e_codes, e_slots, e_valid, _, n_groups, e_weights = (
                build_emissions(
                    codes, valid, group_ids, flat_stamps, config,
                    ts_vocab=ts_vocab, weights=weights,
                )
            )
            if obs.metrics_enabled():
                obs.POINTS_BINNED.inc(int(len(e_codes)),
                                      backend=resolved_backend)
            # jit=False: chunk emission shapes (and sometimes
            # n_slots) vary call to call on the bounded path, so the
            # jitted entry would recompile the whole cascade per chunk.
            level_data = cascade_mod.run_cascade(
                e_codes, e_slots, ccfg,
                n_slots=len(ts_vocab) * n_groups,
                valid=e_valid,
                capacity=min(config.capacity or len(e_codes), len(e_codes)),
                weights=e_weights,
                acc_dtype=jnp.float64 if e_weights is not None else None,
                adaptive=config.adaptive_capacity,
                jit=False,
                backend=resolved_backend,
                mesh=_dp_mesh_for(dp_mesh, config, len(e_codes)),
                weight_bound=config.weight_bound,
            )
            levels = cascade_mod.decode_levels(level_data, ccfg)
        with tracer.span("merge.chunk"):
            nonlocal spill, spill_runs, spill_is_auto, auto_spill_dir
            nonlocal chunks_done, max_chunk_rows
            chunks_done += 1
            max_chunk_rows = max(
                max_chunk_rows, sum(len(lvl["code"]) for lvl in levels)
            )
            if spill is not None:
                failed_level = None
                try:
                    for i, lvl in enumerate(levels):
                        failed_level = i
                        spill.add_level(
                            spill_runs, i, lvl["slot"] // n_groups,
                            lvl["slot"] % n_groups, lvl["code"],
                            lvl["value"],
                        )
                except OSError as e:
                    if not spill_is_auto:
                        raise  # explicit merge_spill_dir: operator's call
                    # AUTO spill hit a disk error (ENOSPC and kin) on a
                    # job the in-RAM fold might still finish: fold every
                    # spilled run — plus this chunk's unwritten levels —
                    # back into RAM and carry on diskless. Run order is
                    # preserved, so results stay byte-identical to the
                    # never-spilled fold.
                    import warnings

                    # The level that raised may have all four files
                    # PRESENT but the last one truncated (ENOSPC mid
                    # np.save) — existence is not completeness there,
                    # so drop its files outright and re-merge it from
                    # the in-memory chunk data.
                    spill.discard_level(spill_runs, failed_level)
                    written = spill.complete_levels(spill_runs)
                    written.discard(failed_level)
                    for i in range(n_levels):
                        base = spill.merge_level(i, spill_runs + 1)
                        if i not in written:
                            lvl = levels[i]
                            base = _merge_sorted_level(
                                base, lvl["slot"] // n_groups,
                                lvl["slot"] % n_groups, lvl["code"],
                                lvl["value"],
                            )
                        merged[i] = base
                    spill.cleanup()
                    spill = None
                    spill_is_auto = False
                    auto_spill_dir = None
                    warnings.warn(
                        f"auto-spill write failed ({e}); folded spilled "
                        "runs back into RAM and continuing without disk "
                        "(set TMPDIR/AUTO_SPILL_DIR to a larger "
                        "filesystem to re-enable)",
                        RuntimeWarning, stacklevel=2,
                    )
                else:
                    spill_runs += 1
                return
            for i, lvl in enumerate(levels):
                merged[i] = _merge_sorted_level(
                    merged[i], lvl["slot"] // n_groups,
                    lvl["slot"] % n_groups, lvl["code"], lvl["value"],
                )
            table_rows = sum(len(m["code"]) for m in merged)
            if auto_spill_dir is not None and table_rows > AUTO_SPILL_ROWS:
                # The in-RAM fold re-scans this whole table every chunk
                # — past this size the disk-spill merge is better.
                # Convert the accumulated table to spill run
                # 0; later chunks spill directly. Run order preserves
                # chunk-order summation, so results stay byte-identical.
                # But only onto a filesystem the projected volume fits
                # (a small disk-backed /tmp must not ENOSPC a job that
                # fits in RAM); refusal and write failure both fall back to
                # the in-RAM fold with a warning.
                import warnings

                if not _auto_spill_projection_fits(
                        auto_spill_dir, table_rows, chunks_done,
                        total_chunks_est, max_chunk_rows):
                    warnings.warn(
                        f"auto-spill skipped: projected spill volume "
                        f"does not fit {auto_spill_dir!r}; keeping the "
                        "in-RAM merge (set TMPDIR/AUTO_SPILL_DIR to a "
                        "larger filesystem, or pass merge_spill_dir)",
                        RuntimeWarning, stacklevel=2,
                    )
                    auto_spill_dir = None
                    return
                # Construction (makedirs + mkdtemp) can itself raise on
                # a full or unwritable filesystem — that too must fall
                # back to the in-RAM fold, not fail the job.
                converting = None
                try:
                    converting = _SpillMerge(auto_spill_dir, n_levels)
                    for i, m in enumerate(merged):
                        converting.add_level(
                            0, i, m["ts"], m["g"], m["code"], m["value"]
                        )
                except OSError as e:
                    if converting is not None:
                        converting.cleanup()
                    auto_spill_dir = None
                    warnings.warn(
                        f"auto-spill conversion failed ({e}); keeping "
                        "the in-RAM merge",
                        RuntimeWarning, stacklevel=2,
                    )
                else:
                    spill = converting
                    spill_is_auto = True
                    for i in range(n_levels):
                        merged[i] = dict(empty)
                    spill_runs = 1

    # Any failure between the first spilled run and egress must still
    # remove the spill tempdir (tens of GB at the shapes spill
    # targets), so ingest runs under the same cleanup as egress.
    try:
        if not overlap_ingest:
            for chunk in chunks():
                process(chunk)
        else:
            # Double-buffer through the shared host->device feeder
            # (pipeline/feeder.py): the worker thread builds chunk N+1
            # (source IO, parsing, group routing) AND device-feeds its
            # numeric columns while this thread runs chunk N's cascade
            # + merge. Depth-1 queue keeps the same peak-footprint
            # bound as the old host-only prefetch (at most 3 chunks:
            # building + queued + in-cascade); chunk ORDER — and
            # therefore every vocab id and merge result — is identical
            # to the sequential path.
            from heatmap_tpu.pipeline import feeder as feeder_mod

            def feed_chunk(chunk):
                if not jax.config.jax_enable_x64:
                    return chunk  # device_put would downcast (feeder.py)
                lat, lon, g, ts, v = chunk
                return (jax.device_put(lat), jax.device_put(lon), g, ts,
                        None if v is None else jax.device_put(v))

            for item in feeder_mod.feed(chunks(), feed_chunk, depth=1,
                                        thread_name="ingest-prefetch"):
                process(item)
    except BaseException:
        if spill is not None:
            spill.cleanup()
        raise

    # Egress: re-pack slots with the complete vocabs, then the shared
    # finalize + blob path.
    n_groups = len(vocab)
    slot_names = _slot_names(vocab, ts_vocab, n_groups)

    def assemble(m, i):
        rows, cols_ = morton.morton_decode_np(m["code"])
        return {
            "zoom": ccfg.detail_zoom - i,
            "slot": m["ts"] * n_groups + m["g"],
            "code": m["code"],
            "row": rows,
            "col": cols_,
            "value": m["value"],
        }

    try:
        if spill is not None:
            if spill.rows_spilled == 0:
                return {}
            if not config.amplify_all:
                # True one-level-at-a-time egress: merge, finalize and
                # write each level before touching the next — peak is
                # O(chunk + largest single level). Blob ids never
                # collide across levels (the coarse zoom is part of
                # the id) and sinks upsert per blob / per level, so
                # per-level _finish_blobs calls compose exactly.
                out = None
                for i in range(n_levels):
                    part = _finish_blobs(
                        [assemble(spill.merge_level(i, spill_runs), i)],
                        ccfg, slot_names, as_json=True, sink=sink,
                    )
                    if (isinstance(part, dict)
                            and part.get("egress") == "levels"):
                        if out is None:
                            out = {"egress": "levels", "levels": 0,
                                   "rows": 0}
                        out["levels"] += part["levels"]
                        out["rows"] += part["rows"]
                    else:
                        if out is None:
                            out = {}
                        out.update(part)
                return {} if out is None else out
            # amplify_all's cross-level recurrence needs every level in
            # hand (cascade._patch_amplified); materialize the merged
            # levels once, like the unbounded path.
            merged = [spill.merge_level(i, spill_runs)
                      for i in range(n_levels)]
        elif all(len(m["code"]) == 0 for m in merged):
            return {}

        return _finish_blobs(
            [assemble(m, i) for i, m in enumerate(merged)],
            ccfg, slot_names, as_json=True, sink=sink,
        )
    finally:
        if spill is not None:
            spill.cleanup()


class _SpillMerge:
    """Disk-backed cross-chunk merge for the bounded path.

    The in-RAM merge table is O(unique output keys) — the one bound
    ``max_points_in_flight`` cannot give;
    near-unique-output shapes (output ~= input) made it 12 GB RSS at
    20M adversarial points. Spilling instead writes each chunk's
    decoded level aggregates as flat column files (24 B/row:
    int32 ts/g + int64 code + f64 value) and aggregates ONE LEVEL AT A
    TIME at egress via mmap-concat + one stable sort + reduceat, so
    peak host memory is O(chunk + largest single level) instead of
    O(all levels' uniques + merge temporaries) — except under
    ``amplify_all``, whose cross-level recurrence forces all merged
    levels resident at egress (ingest-time memory is still O(chunk)).
    Values sum in chunk order per key — byte-identical to the
    iterative two-run merge.
    The reference analog is Spark's shuffle spill to local disk
    (reference submit-heatmap:14, spark.local.dir).
    """

    def __init__(self, root: str, n_levels: int):
        import tempfile

        os.makedirs(root, exist_ok=True)
        self.dir = tempfile.mkdtemp(prefix="merge-spill-", dir=root)
        self.n_levels = n_levels
        self.rows_spilled = 0

    def _base(self, run: int, level: int) -> str:
        return os.path.join(self.dir, f"run{run:05d}_l{level:02d}")

    def add_level(self, run: int, level: int, ts, g, code, value) -> None:
        if len(code) == 0:
            return  # empty runs simply have no files
        base = self._base(run, level)
        np.save(base + "_ts.npy", np.asarray(ts, np.int32))
        np.save(base + "_g.npy", np.asarray(g, np.int32))
        np.save(base + "_code.npy", np.asarray(code, np.int64))
        np.save(base + "_value.npy", np.asarray(value, np.float64))
        self.rows_spilled += len(code)

    def discard_level(self, run: int, level: int | None) -> None:
        """Remove whatever ``(run, level)`` files exist — a save that
        raised may have left the LAST file truncated-but-present, so
        the failing level must be dropped by name, not by existence."""
        if level is None:
            return
        base = self._base(run, level)
        for name in ("ts", "g", "code", "value"):
            try:
                os.remove(f"{base}_{name}.npy")
            except OSError:
                pass

    def complete_levels(self, run: int) -> set:
        """Levels of ``run`` whose four column files all exist.

        A save that died mid-write (ENOSPC) leaves a partial file set;
        partial levels are DELETED here so a later merge_level never
        reads a half-written run (it keys existence off _code.npy,
        which may exist while _value.npy does not). Used by the
        auto-spill write-failure recovery in _run_job_bounded.
        """
        done = set()
        for level in range(self.n_levels):
            base = self._base(run, level)
            paths = [f"{base}_{name}.npy"
                     for name in ("ts", "g", "code", "value")]
            present = [p for p in paths if os.path.exists(p)]
            if len(present) == len(paths):
                done.add(level)
            else:
                for p in present:
                    try:
                        os.remove(p)
                    except OSError:
                        pass
        return done

    def merge_level(self, level: int, n_runs: int) -> dict:
        cols = {"ts": [], "g": [], "code": [], "value": []}
        for run in range(n_runs):
            base = self._base(run, level)
            if not os.path.exists(base + "_code.npy"):
                continue
            for name in cols:
                cols[name].append(
                    np.load(f"{base}_{name}.npy", mmap_mode="r")
                )
        if not cols["code"]:
            return {
                "ts": np.empty(0, np.int64), "g": np.empty(0, np.int64),
                "code": np.empty(0, np.int64),
                "value": np.empty(0, np.float64),
            }
        ts = np.concatenate(cols["ts"]).astype(np.int64)
        g = np.concatenate(cols["g"]).astype(np.int64)
        code = np.concatenate(cols["code"])
        value = np.concatenate(cols["value"])
        return _aggregate_runs(ts, g, code, value)

    def cleanup(self) -> None:
        import shutil

        shutil.rmtree(self.dir, ignore_errors=True)


def _aggregate_runs(ts, g, code, value) -> dict:
    """Sum values over equal (ts, g, code) keys across concatenated
    runs; output sorted by (ts, g, code). Stable sort keeps run order
    within a key, so f64 sums accumulate in chunk order — the same
    order as the iterative _merge_sorted_level fold."""
    pack = _level_key_packer(ts, g, code)
    if pack is not None:
        order = np.argsort(pack(ts, g, code), kind="stable")
    else:  # pathological widths: correct but slower full sort
        order = np.lexsort((code, g, ts))
    ts, g, code, value = ts[order], g[order], code[order], value[order]
    first = np.empty(len(code), bool)
    first[:1] = True
    first[1:] = (ts[1:] != ts[:-1]) | (g[1:] != g[:-1]) \
        | (code[1:] != code[:-1])
    starts = np.flatnonzero(first)
    return {
        "ts": ts[starts],
        "g": g[starts],
        "code": code[starts],
        "value": np.add.reduceat(value, starts) if len(starts)
        else value[:0],
    }


def _level_key_packer(ts, g, code):
    """Closure packing (ts, g, code) rows into ONE comparable int64 —
    field widths taken from THESE arrays (pass the union of everything
    you will pack) — or None when the widths don't fit 62 bits (the
    cascade's own composite keys already prove slot<<code_bits fits;
    the global G here can only be larger by the vocab tail, so guard).
    Single source of truth for the merge paths: the spill merge's
    byte-identical-to-in-RAM guarantee rests on both using THIS key
    order."""
    code_bits = int(code.max(initial=0)).bit_length()
    gmax = int(g.max(initial=0)) + 1
    tmax = int(ts.max(initial=0)) + 1
    if code_bits + (gmax * tmax).bit_length() >= 62:
        return None

    def pack(t_, g_, c_):
        # int64 up front: ts/g arrive int32 off the native key
        # decoder, and << code_bits (up to 42 at z21) would silently
        # wrap in int32 — unsorted pack keys then corrupt the merges.
        return ((t_.astype(np.int64) * gmax + g_) << code_bits) | c_

    return pack


def _merge_sorted_level(m, ts2, g2, code2, value2):
    """Fold one chunk's level aggregates into the running table.

    Both sides arrive sorted by (ts, g, code): the running table is the
    previous merge's output, and decode_levels emits ascending
    composite-key order, which for slot = ts*G + g (g < G) IS the
    (ts, g, code) lexicographic order. That makes this a two-sorted-run
    merge — O(K log K) binary searches, not a full re-sort of the
    accumulated table per chunk. Equal keys dedupe by summing.
    """
    ts = np.concatenate([m["ts"], ts2])
    g = np.concatenate([m["g"], g2])
    code = np.concatenate([m["code"], code2])
    value = np.concatenate([m["value"], value2])
    if len(code) == 0:
        return m
    pack = _level_key_packer(ts, g, code)
    if pack is not None:
        pa = pack(m["ts"], m["g"], m["code"])
        pb = pack(ts2, g2, code2)
        if len(pa) and len(pb):
            pos_a = np.arange(len(pa)) + np.searchsorted(pb, pa, side="left")
            pos_b = np.arange(len(pb)) + np.searchsorted(pa, pb, side="right")
            order = np.empty(len(pa) + len(pb), np.int64)
            order[pos_a] = np.arange(len(pa))
            order[pos_b] = len(pa) + np.arange(len(pb))
        else:
            order = np.arange(len(code))
    else:  # pathological widths: correct but slower full sort
        order = np.lexsort((code, g, ts))
    ts, g, code, value = ts[order], g[order], code[order], value[order]
    new = np.concatenate([[True],
                          (ts[1:] != ts[:-1]) | (g[1:] != g[:-1])
                          | (code[1:] != code[:-1])])
    seg = np.cumsum(new) - 1
    keep = np.flatnonzero(new)
    return {
        "ts": ts[keep], "g": g[keep], "code": code[keep],
        "value": np.bincount(seg, weights=value),
    }


def _slot_names(vocab, ts_vocab, n_groups):
    """slot id -> (user name, timespan label) table shared by every
    egress path (slot = timespan*G + group)."""
    return {
        t * n_groups + g: (vocab.name_for(g), ts_vocab.label_for(t))
        for t in range(len(ts_vocab))
        for g in range(n_groups)
    }


def _finish_blobs(decoded_levels, ccfg, slot_names, as_json, sink=None):
    """Shared egress tail: finalize decoded levels, then either stream
    columns into a columnar sink (anything with ``write_levels``, e.g.
    io.sinks.LevelArraysSink — no per-blob Python objects at all) or
    build reference-format blobs and upsert them into ``sink``.

    Returns the blob dict on the blob path; on the columnar path a
    small stats dict ``{"egress": "levels", "levels": n, "rows": n}``
    (materializing 100M blob dicts just to return them would defeat
    the columnar sink's point).
    """
    from heatmap_tpu.utils.trace import get_tracer

    tracer = get_tracer()
    with tracer.span("egress.finalize"):
        finalized = cascade_mod.finalize_level_arrays(
            decoded_levels, ccfg, slot_names
        )
    if sink is not None and hasattr(sink, "write_levels"):
        with tracer.span("egress"):
            rows = sink.write_levels(finalized)
        return {"egress": "levels", "levels": len(finalized), "rows": rows}
    with tracer.span("egress.blobs"):
        if as_json:
            # Vectorized direct-to-JSON egress: no per-aggregate dicts
            # and no per-blob json.dumps (the dict assembly dominated
            # large jobs ~10:1 over the device cascade).
            blobs = cascade_mod.json_blobs_from_level_arrays(finalized)
        else:
            blobs = cascade_mod.blobs_from_level_arrays(finalized)
    if sink is not None:
        with tracer.span("egress"):
            sink.write(blobs.items())
    return blobs


def run_job_fast(source, sink=None, config: BatchJobConfig | None = None,
                 batch_size: int = 1 << 20,
                 checkpoint_dir: str | None = None,
                 checkpoint_every: int = 8,
                 fault_injector=None,
                 max_points_in_flight: int | None = None,
                 overlap_ingest: bool = True,
                 merge_spill_dir: str | None = None):
    """Integer-fast-path job: no per-row Python objects anywhere.

    ``source`` is a CSV path (the native C++ decoder parses, routes
    user ids per reference heatmap.py:64-70 and flags background rows
    per heatmap.py:28-29 in its reader threads) or any object with a
    ``fast_batches(batch_size)`` method (io.hmpb.HMPBSource memory-maps
    pre-routed columns). This side only maps the small routed-name
    table into the UserVocab (O(unique users), not O(rows)) and
    filters with numpy masks. Same blobs as the string path.

    Dated timespans work here: fast batches carry an i64 epoch-ms
    ``timestamp`` column (TS_MISSING sentinel), which the factorized
    unique-day labeler consumes without per-row Python; a sentinel row
    under a dated timespan raises exactly like timestamp=None does on
    the string path.

    ``checkpoint_dir`` enables checkpoint/resume with
    run_job_resumable's semantics: ingest progress is checkpointed
    every ``checkpoint_every`` batches, a rerun skips the row-work of
    already-checkpointed batches (the reader still streams them for its
    intern table). Resume-by-batch-index requires a deterministic batch
    order, so checkpointing forces the native CSV reader to a single
    worker (parallel byte-range parsing reorders batches run to run);
    HMPB batches are always in file order.

    ``max_points_in_flight`` bounds peak memory exactly like run_job's
    knob — the cascade runs per chunk of at most that many points with
    fast-path ingest, per-level aggregates merged host-side (the
    BASELINE config-5 shape with mmap/native ingest). Mutually
    exclusive with ``checkpoint_dir`` (chunk boundaries are not batch
    boundaries, so batch-index resume would not line up).

    ``max_points_in_flight=None`` auto-routes oversized sources to the
    bounded path exactly like run_job (same heuristic; ``0`` forces
    single-shot) — unless checkpointing or fault injection is
    configured, which are bounded-path-incompatible and keep the
    operator's explicit choice.
    """
    config = config or BatchJobConfig()
    if checkpoint_every < 1:
        raise ValueError(f"checkpoint_every must be >= 1, got {checkpoint_every}")
    if (max_points_in_flight is None and checkpoint_dir is None
            and fault_injector is None):
        max_points_in_flight = _auto_points_in_flight(
            source, fast=True,
            n_timespans=(1 if config.first_timespan_only
                         else len(config.timespans)),
            weighted=config.weighted,
        )
    if merge_spill_dir is not None and not max_points_in_flight:
        raise ValueError(
            "merge_spill_dir lives on the bounded path, but this job "
            "routed single-shot; pass max_points_in_flight > 0 to "
            "chunk (see run_job)"
        )
    if max_points_in_flight:  # 0/None -> single-shot
        if checkpoint_dir is not None:
            raise ValueError(
                "max_points_in_flight and checkpoint_dir are mutually "
                "exclusive on the fast path"
            )
        if fault_injector is not None:
            # Silently accepting-and-ignoring the injector would make a
            # recovery test pass without exercising anything.
            raise ValueError(
                "fault_injector is not supported with "
                "max_points_in_flight (no batch-index resume on the "
                "chunked path)"
            )
        return _run_job_bounded(
            source, sink, config, batch_size, max_points_in_flight,
            overlap_ingest=overlap_ingest, fast=True,
            spill_dir=merge_spill_dir,
        )
    from heatmap_tpu.utils.trace import get_tracer

    def make_batches():
        return _fast_batches_for(
            source, batch_size, checkpointing=checkpoint_dir is not None
        )

    vocab = UserVocab()
    router = _FastRouter(vocab)
    tracer = get_tracer()
    lats, lons, gids, tss, vals = [], [], [], [], []
    mgr = None
    done = 0
    if checkpoint_dir is not None:
        from heatmap_tpu.utils import CheckpointManager

        mgr = CheckpointManager(checkpoint_dir)
        if mgr.latest_step() is not None:
            arrays, meta = mgr.load()
            # Batch indices only mean the same rows under the reader
            # that wrote them — refuse checkpoints from the string path
            # (run_job_resumable) instead of resuming into corruption.
            kind = meta.get("job_path", "string")
            if kind != "fast":
                raise RuntimeError(
                    f"checkpoint at {checkpoint_dir!r} was written by the "
                    f"{kind!r} job path; resume it with the same path "
                    "(run_job_resumable / drop --fast) or point --fast at "
                    "a fresh checkpoint dir"
                )
            _check_checkpoint_weighted(meta, config, checkpoint_dir)
            lats = [arrays["latitude"]]
            lons = [arrays["longitude"]]
            gids = [arrays["group_ids"]]
            tss = [arrays["timestamps_ms"]]
            if config.weighted:
                vals = [arrays["values"]]
            for name in meta["group_names"][1:]:  # [0] is always 'all'
                vocab.id_for(name)
            done = meta["batches_done"]

    def checkpoint(step):
        arrays = {
            "latitude": np.concatenate(lats) if lats else np.empty(0),
            "longitude": np.concatenate(lons) if lons else np.empty(0),
            "group_ids": (
                np.concatenate(gids) if gids else np.empty(0, np.int32)
            ),
            "timestamps_ms": (
                np.concatenate(tss) if tss else np.empty(0, np.int64)
            ),
        }
        if config.weighted:
            arrays["values"] = np.concatenate(vals) if vals else np.empty(0)
        mgr.save(step, arrays, {
            "group_names": list(vocab.names),
            "batches_done": step,
            "job_path": "fast",
            "weighted": config.weighted,
        })
        # Collapse accumulated chunks so later checkpoints don't recopy
        # a growing list-of-arrays.
        lats[:] = [arrays["latitude"]]
        lons[:] = [arrays["longitude"]]
        gids[:] = [arrays["group_ids"]]
        tss[:] = [arrays["timestamps_ms"]]
        if config.weighted:
            vals[:] = [arrays["values"]]

    with tracer.span("ingest.fast"):
        for i, b in enumerate(make_batches()):
            # The intern table must grow even for skipped batches: a
            # post-resume batch may reference reader ids first named
            # before the checkpoint. (id_for inside route() is
            # get-or-create, so names restored from a checkpoint keep
            # their original ids on resume.)
            router.observe(b)
            if i < done:
                continue  # rows already checkpointed on a previous run
            if fault_injector is not None:
                fault_injector.check(i)
            tracer.add_items("ingest.fast", len(b["latitude"]))
            lat, lon, g, ts64, v = router.route(b)
            if config.weighted:
                _require_fast_weights(v)
                vals.append(v)
            lats.append(lat)
            lons.append(lon)
            gids.append(g)
            tss.append(ts64)
            done = i + 1
            if mgr is not None and done % checkpoint_every == 0:
                with tracer.span("checkpoint"):
                    checkpoint(done)
    if not lats or sum(len(a) for a in lats) == 0:
        return {}
    lat = np.concatenate(lats)
    with tracer.span("cascade", items=len(lat)):
        blobs = _run_grouped(
            lat,
            np.concatenate(lons),
            np.concatenate(gids),
            np.concatenate(tss),
            vocab,
            config,
            as_json=True,
            sink=sink,
            weights=np.concatenate(vals) if config.weighted else None,
        )
    return blobs


def run_job_resumable(source, checkpoint_dir: str, sink=None,
                      config: BatchJobConfig | None = None,
                      batch_size: int = 1 << 20,
                      checkpoint_every: int = 8,
                      fault_injector=None):
    """``run_job`` with checkpoint/resume over source batches.

    The reference recomputes everything from Cassandra on any failure
    (no checkpointing anywhere, SURVEY.md §5). Here ingest progress is
    checkpointed every ``checkpoint_every`` batches (atomic npz via
    utils.checkpoint); a rerun with the same source/batch_size resumes
    after the last checkpointed batch. The source is still *streamed*
    from the start on resume — pre-checkpoint batches are read and
    discarded; what's skipped is the load_columns/vocab/accumulation
    work and, on the earlier run, everything after the checkpoint.
    Sources must iterate deterministically for resume to be exact — every
    built-in source does (files byte-ordered, synthetic seeded).

    ``fault_injector`` (utils.recovery.FaultInjector) fails chosen
    batch indices for recovery testing.
    """
    from heatmap_tpu.utils import CheckpointManager
    from heatmap_tpu.utils.trace import get_tracer

    config = config or BatchJobConfig()
    if checkpoint_every < 1:
        raise ValueError(f"checkpoint_every must be >= 1, got {checkpoint_every}")
    tracer = get_tracer()
    mgr = CheckpointManager(checkpoint_dir)
    vocab = UserVocab()
    lats, lons, gids, stamps, vals = [], [], [], [], []
    done = 0
    if mgr.latest_step() is not None:
        arrays, meta = mgr.load()
        kind = meta.get("job_path", "string")
        if kind != "string":
            raise RuntimeError(
                f"checkpoint at {checkpoint_dir!r} was written by the "
                f"{kind!r} job path; resume it with run_job_fast "
                "(--fast) or point this run at a fresh checkpoint dir"
            )
        _check_checkpoint_weighted(meta, config, checkpoint_dir)
        lats, lons = [arrays["latitude"]], [arrays["longitude"]]
        gids = [arrays["group_ids"]]
        if config.weighted:
            vals = [arrays["values"]]
        if "timestamps_ms" in arrays:
            from heatmap_tpu.pipeline.timespan import TS_MISSING

            stamps = [[None if t == TS_MISSING else int(t)
                       for t in arrays["timestamps_ms"]]]
        elif "timestamps_str" in arrays:
            if "timestamps_valid" in arrays:
                stamps = [[s if v else None
                           for s, v in zip(arrays["timestamps_str"],
                                           arrays["timestamps_valid"])]]
            else:
                stamps = [list(arrays["timestamps_str"])]
        else:
            stamps = [[None] * len(arrays["latitude"])]
        for name in meta["group_names"][1:]:  # [0] is always 'all'
            vocab.id_for(name)
        done = meta["batches_done"]

    def checkpoint(step):
        lat = np.concatenate(lats) if lats else np.empty(0)
        arrays = {
            "latitude": lat,
            "longitude": np.concatenate(lons) if lons else np.empty(0),
            "group_ids": np.concatenate(gids) if gids else np.empty(0, np.int32),
        }
        if config.weighted:
            arrays["values"] = (
                np.concatenate(vals) if vals else np.empty(0)
            )
        flat_stamps = [s for chunk in stamps for s in chunk]
        if flat_stamps and any(s is not None for s in flat_stamps):
            # Mixed None/real streams must round-trip: None persists as
            # the TS_MISSING int64 sentinel (or a validity mask on the
            # string path), never by dropping the whole column — a
            # resumed run has to bucket dated timespans exactly like an
            # uninterrupted one.
            from heatmap_tpu.pipeline.timespan import TS_MISSING

            valid = np.asarray([s is not None for s in flat_stamps], bool)
            present = [s for s in flat_stamps if s is not None]
            try:
                ms_present = np.asarray(present, np.int64)
            except (ValueError, TypeError):
                # datetime/date objects: epoch-ms round-trips through
                # timespan._to_date (UTC). Anything else keeps its
                # string form — resumes behave exactly like the
                # original run would have (float()-able strings work,
                # junk raises in _to_date either way).
                import datetime as _dt

                def to_ms(s):
                    if isinstance(s, _dt.datetime):
                        if s.tzinfo is None:
                            s = s.replace(tzinfo=_dt.timezone.utc)
                        return int(s.timestamp() * 1000)
                    if isinstance(s, _dt.date):
                        return int(_dt.datetime(
                            s.year, s.month, s.day,
                            tzinfo=_dt.timezone.utc,
                        ).timestamp() * 1000)
                    return None

                ms = [to_ms(s) for s in present]
                ms_present = (
                    np.asarray(ms, np.int64)
                    if all(m is not None for m in ms) else None
                )
            if ms_present is not None:
                full = np.full(len(flat_stamps), TS_MISSING, np.int64)
                full[valid] = ms_present
                arrays["timestamps_ms"] = full
            else:
                arrays["timestamps_str"] = np.asarray(
                    ["" if s is None else str(s) for s in flat_stamps]
                )
                arrays["timestamps_valid"] = valid
        mgr.save(step, arrays, {
            "group_names": list(vocab.names),
            "batches_done": step,
            "job_path": "string",
            "weighted": config.weighted,
        })
        # Collapse accumulated chunks so later checkpoints don't recopy
        # a growing list-of-arrays.
        lats[:] = [arrays["latitude"]]
        lons[:] = [arrays["longitude"]]
        gids[:] = [arrays["group_ids"]]
        stamps[:] = [flat_stamps]
        if config.weighted:
            vals[:] = [arrays["values"]]

    for i, batch in enumerate(source.batches(batch_size)):
        if i < done:
            continue  # already checkpointed on a previous run
        if fault_injector is not None:
            fault_injector.check(i)
        with tracer.span("ingest.batch"):
            with tracer.span("ingest.load_columns"):
                cols = load_columns(batch)
            lats.append(cols["latitude"])
            lons.append(cols["longitude"])
            gids.append(vocab.group_ids(cols["user_id"]))
            stamps.append(cols["timestamp"])
            if config.weighted:
                _require_value_column(cols)
                vals.append(cols["value"])
        tracer.add_items("ingest.batch", len(cols["latitude"]))
        done = i + 1
        if done % checkpoint_every == 0:
            with tracer.span("checkpoint"):
                checkpoint(done)
    if not lats or sum(len(a) for a in lats) == 0:
        return {}
    flat_stamps = [s for chunk in stamps for s in chunk]
    with tracer.span("cascade"):
        blobs = _run_grouped(
            np.concatenate(lats),
            np.concatenate(lons),
            np.concatenate(gids).astype(np.int32),
            flat_stamps,
            vocab,
            config,
            as_json=True,
            sink=sink,
            weights=np.concatenate(vals) if config.weighted else None,
        )
    return blobs


def run_batch(rows, config: BatchJobConfig | None = None, as_json: bool = False):
    """The full job: rows in, heatmap blobs out (reference batchMain).

    Returns {"user|timespan|coarseTileId": {detailTileId: count}} — or
    with ``as_json=True`` the inner dicts as JSON strings, matching the
    reference's (id, heatmap-json) output records
    (reference heatmap.py:156-157).
    """
    config = config or BatchJobConfig()
    data = load_rows(rows)
    if len(data["latitude"]) == 0:
        return {}
    return _run_loaded(data, config, as_json=as_json)


def _run_loaded(data, config: BatchJobConfig, as_json: bool, sink=None):
    vocab = UserVocab()
    group_ids = vocab.group_ids(data["user_id"])
    return _run_grouped(
        data["latitude"], data["longitude"], group_ids,
        data["timestamp"], vocab, config, as_json, sink=sink,
        weights=data.get("value") if config.weighted else None,
    )


def _run_grouped(lat, lon, group_ids, timestamps, vocab,
                 config: BatchJobConfig, as_json: bool, sink=None,
                 weights=None):
    from heatmap_tpu.utils.trace import get_tracer

    if config.weighted and weights is None:
        raise ValueError("config.weighted needs per-point weights "
                         "(a 'value' column in the source)")
    tracer = get_tracer()
    with tracer.span("cascade.project", items=len(lat)):
        codes, valid = _cascade_codes(lat, lon, config.detail_zoom)
    with tracer.span("cascade.emissions"):
        e_codes, e_slots, e_valid, ts_vocab, n_groups, e_weights = (
            build_emissions(
                codes, valid, group_ids, timestamps, config,
                weights=weights if config.weighted else None,
            )
        )
    n_slots = len(ts_vocab) * n_groups

    ccfg = config.cascade_config()
    if config.pad_bucketing != "exact":
        # Pad BEFORE backend/mesh routing so the auto-DP threshold and
        # shard math see the bucket length: routing then is a pure
        # function of the bucket, keeping the compile count bounded by
        # the bucket count rather than by routing crossovers.
        with tracer.span("cascade.bucket", items=len(e_codes)):
            target = bucketing_mod.bucket_size(
                len(e_codes), config.pad_bucketing, config.pad_bucket_min)
            e_codes, e_slots, e_valid, e_weights = (
                bucketing_mod.pad_emissions(
                    e_codes, e_slots, e_valid, e_weights, target))
            n_slots = bucketing_mod.bucket_slots(n_slots)
    mesh0 = _dp_mesh(config)
    plan = route = None
    if mesh0 is not None and config.spatial_partition != "off":
        from heatmap_tpu.parallel import partition as partition_mod
        from heatmap_tpu.parallel.mesh import _shard_axes

        _, ndev = _shard_axes(mesh0)
        threshold = (AUTO_DP_MIN_EMISSIONS
                     if config.dp_min_emissions is None
                     else config.dp_min_emissions)
        # "auto" plans only at real scale: below the DP threshold the
        # host-side planning pass would cost more than the boundary
        # merge saves (the same never-slow-down rule as auto-DP).
        # "morton" forces the plan whenever a mesh engages.
        if ndev >= 2 and (config.spatial_partition == "morton"
                          or len(e_codes) >= threshold):
            with tracer.span("mesh.plan", items=len(e_codes)):
                host_codes = np.asarray(e_codes)
                host_valid = (None if e_valid is None
                              else np.asarray(e_valid))
                plan = partition_mod.plan_partition(
                    host_codes, ndev, detail_zoom=config.detail_zoom,
                    valid=host_valid,
                    n_levels=config.cascade_config().n_levels)
                if not plan.degenerate:
                    # The owner-routed program's static exchange widths,
                    # on a fixed 1.25x ladder so that jobs of one size
                    # compile once.
                    route = partition_mod.route_counts(
                        plan, host_codes, host_valid,
                        bucket=functools.partial(
                            bucketing_mod.bucket_size, mode="geometric"))
    dp_mesh = _dp_mesh_for(mesh0, config, len(e_codes), plan=plan)
    if plan is not None and (dp_mesh is None or plan.degenerate):
        plan = route = None  # fallback recorded by _dp_mesh_for
    timer = obs.DispatchTimer("single" if dp_mesh is None
                              else config.resolved_dispatch)
    if route is not None:
        obs.record_mesh_route(route)
    if dp_mesh is not None:
        # The gspmd programs take the stream in contiguous per-chip
        # blocks: pad to whole blocks (pad lanes valid=False) and place
        # each block on its chip, so no chip receives the whole batch.
        from heatmap_tpu.parallel.gspmd import point_sharding
        from heatmap_tpu.parallel.mesh import _shard_axes

        _, ndev = _shard_axes(dp_mesh)
        e_codes, e_slots, e_valid, e_weights = bucketing_mod.pad_emissions(
            e_codes, e_slots, e_valid, e_weights,
            -(-len(e_codes) // ndev) * ndev)
        sharding = point_sharding(dp_mesh)
        e_codes, e_slots, e_valid, e_weights = (
            None if a is None else jax.device_put(a, sharding)
            for a in (e_codes, e_slots, e_valid, e_weights))
    backend = _resolve_backend(config, n_emissions=len(e_codes),
                               data_parallel=dp_mesh is not None)
    with tracer.span("cascade.device", backend=backend):
        import jax.numpy as jnp

        acc_dtype = jnp.float64 if e_weights is not None else None
        capacity = config.capacity or len(e_codes)
        if not config.adaptive_capacity:
            # Mirror the jit cache key (shapes + every static arg of
            # _build_cascade_jit) so bucket hit/miss counters track
            # actual compiles without poking jax internals.
            bucketing_mod.note_dispatch(
                (
                    int(e_codes.shape[0]),
                    str(e_codes.dtype),
                    str(e_slots.dtype),
                    e_valid is not None,
                    None if e_weights is None else str(e_weights.dtype),
                    ccfg,
                    n_slots,
                    capacity,
                    None if acc_dtype is None else str(acc_dtype),
                    backend,
                    None if dp_mesh is None
                    else tuple(sorted(dp_mesh.shape.items())),
                    config.weight_bound,
                    # Partition term: the range program is a distinct
                    # trace, but splits are TRACED, so every plan of the
                    # same shard count shares one compile.
                    None if plan is None else ("morton", len(plan.splits)),
                    None if route is None
                    else (route.pair_capacity, route.recv_capacity),
                ),
                config.pad_bucketing,
            )
        partition_splits = (None if plan is None
                            else jnp.asarray(plan.splits, jnp.int64))
        levels = cascade_mod.run_cascade(
            e_codes,
            e_slots,
            ccfg,
            n_slots=n_slots,
            valid=e_valid,
            capacity=capacity,
            weights=e_weights,
            # Weighted sums accumulate in f64 (f32 would both round and
            # stop moving near 2^24-scale cell sums; counts use the
            # int32 path, SURVEY.md §8.8).
            acc_dtype=acc_dtype,
            adaptive=config.adaptive_capacity,
            backend=backend,
            mesh=dp_mesh,
            weight_bound=config.weight_bound,
            partition_splits=partition_splits,
            route_capacity=None if route is None
            else (route.pair_capacity, route.recv_capacity),
        )
        timer.dispatched()
        if timer.enabled:
            # Force execution so the host/device split measures the
            # program, not async dispatch latency.
            levels = jax.block_until_ready(levels)
        timer.finished(items=len(e_codes))
    with tracer.span("cascade.decode"):
        decoded = cascade_mod.decode_levels(levels, ccfg)
    return _finish_blobs(
        decoded,
        ccfg,
        _slot_names(vocab, ts_vocab, n_groups),
        as_json,
        sink=sink,
    )
