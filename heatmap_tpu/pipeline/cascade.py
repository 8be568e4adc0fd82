"""The zoom cascade: one sorted composite key, sixteen levels, zero shuffles.

The reference runs 16 Spark stages, each re-projecting every aggregate's
tile center and shuffling twice (reference heatmap.py:107-118;
SURVEY.md §3.3: 32 shuffles). Here the whole cascade is ONE device-side
sparse pyramid over composite integer keys:

    key = slot * 4^detail_zoom + morton_code,  slot = timespan*G + group

Because the slot multiplier is a power of four, ``key >> 2`` coarsens
the Morton part one zoom while leaving the (timespan, group) slot
intact, and preserves sort order — so every cascade level is a plain
segment-sum over the order established by a single sort
(ops/pyramid.pyramid_sparse_morton).

Blob regrouping (reference map_to_resultset + groupByKey,
heatmap.py:79-90,112) happens host-side at egress: the blob id is just
``key >> 2*result_delta``, no second shuffle.

The reference's '`all`'-amplification quirk (SURVEY.md §8.1:
``all_z = 2*all_{z+1} + sum_users user_{z+1}``) is reproduced on demand
by ``amplify_all=True`` as a host-side post-pass over the correct
per-level aggregates; per-user counts are identical in both modes, as
they are in the reference.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from heatmap_tpu.obs import events as obs_events
from heatmap_tpu.obs import tracing
from heatmap_tpu.ops import pyramid as pyramid_ops
from heatmap_tpu.tilemath.morton import morton_decode_np
from heatmap_tpu.utils import trace


@dataclasses.dataclass(frozen=True)
class CascadeConfig:
    """Static cascade parameters (reference constants, heatmap.py:16-17).

    Levels run at detail zooms ``detail_zoom`` down to
    ``min_detail_zoom + 1`` inclusive (reference range(21, 5, -1) ->
    z21..z6); each level's blobs are keyed by the tile ``result_delta``
    zooms coarser (z16..z1).
    """

    detail_zoom: int = 21
    min_detail_zoom: int = 5
    result_delta: int = 5
    amplify_all: bool = False

    @property
    def n_levels(self) -> int:
        return self.detail_zoom - self.min_detail_zoom - 1

    def __post_init__(self):
        if self.min_detail_zoom + 1 > self.detail_zoom:
            raise ValueError(f"empty cascade: {self}")
        if self.detail_zoom - self.n_levels - self.result_delta < 0:
            raise ValueError(
                f"result tiles would go below zoom 0: {self} "
                f"(min detail zoom {self.min_detail_zoom + 1} needs "
                f"result_delta <= {self.min_detail_zoom + 1})"
            )


def composite_keys(codes, slots, detail_zoom: int, n_slots: int):
    """Pack (slot, morton_code) into one sortable, shiftable int64 key."""
    import jax

    if not jax.config.jax_enable_x64:
        raise RuntimeError(
            "the composite-key cascade needs int64 keys; enable x64 "
            "(jax.config.update('jax_enable_x64', True)) first"
        )
    code_bits = 2 * detail_zoom
    if code_bits + max(1, int(np.ceil(np.log2(max(n_slots, 2))))) >= 63:
        raise ValueError(
            f"composite keys overflow int64: zoom {detail_zoom} with {n_slots} slots"
        )
    codes = jnp.asarray(codes, jnp.int64)
    slots = jnp.asarray(slots, jnp.int64)
    return (slots << code_bits) | codes


def decode_level_keys(level_keys: np.ndarray, detail_zoom: int, level: int):
    """Host-side inverse at pyramid ``level``: -> (slot, morton_code)."""
    code_bits = 2 * (detail_zoom - level)
    k = np.asarray(level_keys, np.int64)
    return k >> code_bits, k & ((1 << code_bits) - 1)


def build_cascade(codes, slots, config: CascadeConfig, n_slots: int,
                  weights=None, valid=None, capacity=None, acc_dtype=None,
                  adaptive: bool = False, backend: str = "scatter",
                  mesh=None, weight_bound: int | None = None,
                  partition_splits=None, route_capacity=None):
    """Device-side cascade: per-level (composite key, sum) aggregates.

    Args:
      codes: detail-zoom Morton codes per emission.
      slots: (timespan*G + group) slot id per emission.
      weights/valid/capacity/acc_dtype/adaptive: as in
        ops.pyramid.pyramid_sparse_morton (weighted jobs pass f64
        weights + acc_dtype=f64 for exact-at-scale sums; the eager job
        paths pass adaptive=True only when
        BatchJobConfig.adaptive_capacity opts in — deep levels then
        shrink to the real unique counts at the cost of per-shape
        recompiles).

    ``backend``: "scatter" (aggregate_sorted_keys) or "partitioned"
    (multi-channel MXU segment reduction, ops/sparse_partitioned.py;
    weighted jobs only under the bounded-integer ``weight_bound``
    contract). The production default is routed by
    BatchJobConfig.resolved_cascade_backend.

    ``mesh``: a jax.sharding.Mesh to data-parallelize the cascade over
    (parallel/gspmd.py, one compiled program over the mesh): without
    ``partition_splits``, ``pyramid_gspmd_uniform`` — emissions padded
    to the shard count, each shard reduces its contiguous block at the
    detail zoom, and the compact partials merge and roll up on the
    global view. Composite keys shift exactly like plain Morton codes
    (the slot bits ride above the code bits), so the
    shift-preserves-sort property the single-device cascade relies on
    holds per level unchanged. Counts and integer-valued weighted sums
    are BIT-IDENTICAL to the single-device cascade (same sorted unique
    keys, exact integer addition in any order); fractional weighted
    sums agree up to f64 summation-order rounding — the same contract
    as the bounded path's cross-chunk merge (pipeline/batch.py
    run_job). Composes with BOTH backends — "partitioned" swaps each
    shard's detail reduction for the MXU segment kernel, same compact
    (keys, sums, count) contract — and with ``adaptive``.

    ``partition_splits``: a TRACED ``(n_shards - 1,)`` int array of
    detail-zoom Morton split codes from a parallel.partition plan.
    Requires a mesh; the emissions arrive UNROUTED, in contiguous
    per-shard blocks, and ``pyramid_gspmd_range`` routes each lane
    ON-DEVICE to the shard owning its code, so the cross-chip merge is
    boundary tiles only. Traced — every plan of one shard count and
    one ``route_capacity`` shares one compile.

    ``route_capacity``: the range program's static exchange widths
    ``(pair_capacity, recv_capacity)``, from the plan's exact lane
    counts (parallel.partition.route_counts); required with
    ``partition_splits``.
    """
    if partition_splits is not None and mesh is None:
        raise ValueError(
            "partition_splits is the mesh path's range plan; it needs "
            "a mesh — plan routing happens in pipeline/batch.py"
        )
    if backend == "partitioned":
        # These hold on the mesh path too: every shard runs the same
        # kernel on the same key layout, so the single-device
        # contracts gate the data-parallel route identically.
        slot_bits = max(1, int(np.ceil(np.log2(max(n_slots, 2)))))
        if 2 * config.detail_zoom + slot_bits > 60:
            raise ValueError(
                f"cascade backend 'partitioned' reconstructs keys from "
                f"three 20-bit channels (60-bit limit); zoom "
                f"{config.detail_zoom} with {n_slots} slots needs "
                f"{2 * config.detail_zoom + slot_bits} bits — use the "
                "scatter backend"
            )
        if weights is not None and weight_bound is None:
            raise ValueError(
                "cascade backend 'partitioned' takes weighted jobs "
                "only under the bounded-integer contract (weights "
                "integer in [0, weight_bound]; exactness slab = "
                "2^24 // bound — ops/sparse_partitioned.py): pass "
                "weight_bound, or use the scatter backend (required "
                "for fractional weights)"
            )
        if adaptive:
            raise ValueError(
                "cascade backend 'partitioned' reduces every level from "
                "the full stream; adaptive capacities do not apply"
            )
    elif backend != "scatter":
        raise ValueError(f"unknown cascade backend {backend!r}")
    with jax.named_scope("cascade.keys"):
        ck = composite_keys(codes, slots, config.detail_zoom, n_slots)
    # Zoom-clamped per-level capacities: level l's key space is at most
    # n_slots * 4^(detail_zoom - l) — a STATIC bound that no data can
    # exceed — so coarse levels get small arrays instead of n-sized
    # padding. On the scatter backend (which feeds each level from the
    # previous level's capacity-sized aggregates) this shrinks the deep
    # half of the cascade's compute outright; on the partitioned
    # backend it shrinks the per-level output buffers. Unlike
    # adaptive_capacity this costs no extra compiles and no device
    # syncs (everything stays shape-static). Callers passing an
    # explicit per-level LIST keep full control.
    if capacity is None or isinstance(capacity, int):
        base = capacity or max(int(codes.shape[0]), 1)
        capacity = [
            min(base, n_slots << (2 * (config.detail_zoom - lvl)))
            for lvl in range(config.n_levels + 1)
        ]
    if mesh is not None:
        return _build_cascade_sharded(
            ck, config, mesh, weights=weights, valid=valid,
            capacity=capacity, acc_dtype=acc_dtype, backend=backend,
            weight_bound=weight_bound if weights is not None else None,
            partition_splits=partition_splits, n_slots=n_slots,
            adaptive=adaptive, route_capacity=route_capacity,
        )
    if backend == "partitioned":
        return pyramid_ops.pyramid_sparse_morton_partitioned(
            ck,
            valid=valid,
            levels=config.n_levels,
            capacity=capacity,
            weights=weights,
            weight_bound=weight_bound if weights is not None else None,
        )
    return pyramid_ops.pyramid_sparse_morton(
        ck,
        weights=weights,
        valid=valid,
        levels=config.n_levels,
        capacity=capacity,
        acc_dtype=acc_dtype,
        adaptive=adaptive,
    )


def _build_cascade_sharded(ck, config: CascadeConfig, mesh,
                           weights=None, valid=None, capacity=None,
                           acc_dtype=None, backend: str = "scatter",
                           weight_bound: int | None = None,
                           partition_splits=None, n_slots: int = 1,
                           adaptive: bool = False, route_capacity=None):
    """Pad composite keys to the mesh shard count and run the gspmd
    pyramid (see build_cascade's ``mesh`` doc). Pad lanes carry
    valid=False, the masking path every kernel already drops; with
    ``partition_splits`` the emissions arrive UNROUTED, in contiguous
    per-shard blocks, and are routed on-device to their owners
    (``route_capacity`` sizes the exchange)."""
    from heatmap_tpu.parallel import gspmd as gspmd_kernels
    from heatmap_tpu.parallel.mesh import _shard_axes

    _, ndev = _shard_axes(mesh)
    n = int(ck.shape[0])
    if n == 0:
        # Zero-row shards would size the per-device stage at zero
        # capacity; the replicated pyramid handles empty inputs already
        # and there is nothing to parallelize.
        return pyramid_ops.pyramid_sparse_morton(
            ck, weights=weights, valid=valid, levels=config.n_levels,
            capacity=capacity, acc_dtype=acc_dtype,
        )
    pad = (-n) % ndev
    v = (jnp.ones((n,), bool) if valid is None
         else jnp.asarray(valid, bool))
    if pad:
        ck = jnp.concatenate([ck, jnp.zeros((pad,), ck.dtype)])
        v = jnp.concatenate([v, jnp.zeros((pad,), bool)])
        if weights is not None:
            weights = jnp.asarray(weights)
            weights = jnp.concatenate(
                [weights, jnp.zeros((pad,), weights.dtype)]
            )
    if partition_splits is not None:
        if route_capacity is None:
            raise ValueError(
                "the gspmd range program needs route_capacity "
                "(pair, recv) from partition.route_counts")
        pair_capacity, recv_capacity = route_capacity
        return gspmd_kernels.pyramid_gspmd_range(
            ck, mesh, partition_splits,
            code_bits=2 * config.detail_zoom, slot_bound=n_slots,
            pair_capacity=pair_capacity, recv_capacity=recv_capacity,
            weights=weights, valid=v, levels=config.n_levels,
            capacity=capacity, acc_dtype=acc_dtype, backend=backend,
            weight_bound=weight_bound, adaptive=adaptive,
        )
    return gspmd_kernels.pyramid_gspmd_uniform(
        ck, mesh, weights=weights, valid=v, levels=config.n_levels,
        capacity=capacity, acc_dtype=acc_dtype, backend=backend,
        weight_bound=weight_bound, adaptive=adaptive,
    )


#: build_cascade under one jit: a single dispatch instead of ~130
#: eager op dispatches, and cross-level XLA fusion of the
#: shift/compare/cumsum chains. Static args recompile per (config,
#: n_slots, capacity, acc_dtype), i.e. once per job shape.
_build_cascade_jit = functools.partial(
    jax.jit,
    static_argnames=("config", "n_slots", "capacity", "acc_dtype",
                     "backend", "mesh", "weight_bound", "route_capacity"),
)(build_cascade)

#: Lazily-built donating twin of _build_cascade_jit for the mesh
#: cascade: the emission buffers (codes/slots/weights/valid)
#: are donated to the program, letting XLA reuse their device memory
#: for the pyramid accumulators in-place on TPU/GPU. Built on first
#: use because donation support depends on the initialized backend
#: (parallel/gspmd.py donating_jit drops donation on CPU but keeps the
#: ledger guard, so re-feeding a consumed buffer is a typed error on
#: every platform).
_donating_cascade_jit = None


def _get_donating_cascade_jit():
    global _donating_cascade_jit
    if _donating_cascade_jit is None:
        from heatmap_tpu.parallel import gspmd as gspmd_kernels

        _donating_cascade_jit = gspmd_kernels.donating_jit(
            build_cascade,
            donate_argnums=(0, 1),  # codes, slots
            donate_argnames=("weights", "valid"),
            static_argnames=("config", "n_slots", "capacity",
                             "acc_dtype", "backend", "mesh",
                             "weight_bound", "route_capacity"),
        )
    return _donating_cascade_jit


def run_cascade(codes, slots, config: CascadeConfig, n_slots: int,
                weights=None, valid=None, capacity=None, acc_dtype=None,
                adaptive: bool = False, jit: bool = True,
                backend: str = "scatter", mesh=None,
                weight_bound: int | None = None,
                partition_splits=None, route_capacity=None):
    """The production cascade entry: jitted whole, unless ``adaptive``
    (which must read concrete per-level unique counts and therefore
    runs eagerly — see ops.pyramid.pyramid_sparse_morton) or
    ``jit=False`` (callers whose input shapes vary call to call — e.g.
    the bounded chunked path — would recompile the whole graph per
    call and should stay eager). ``mesh`` (hashable, a valid static
    arg) routes the cascade through the data-parallel gspmd pyramid —
    see build_cascade."""
    # Tree-only span around the dispatch: the cascade_dispatch event is
    # emitted inside it, so the audit record carries this span's
    # trace_id/span_id (events.py stamps _TRACE_STAMPED types).
    tsp = tracing.begin_span("cascade.dispatch", {"backend": backend})
    try:
        if obs_events._current is not None:
            # Audit every dispatch: what the cascade actually executed
            # (shape info is static even on tracers, so this is safe in
            # eager AND pre-jit contexts). backend_resolved in batch.py
            # records the routing *decision*; this records each execution.
            extra = {"dispatch": "gspmd"} if mesh is not None else {}
            obs_events.emit(
                "cascade_dispatch", backend=backend,
                jit=bool(jit and not adaptive), mesh=mesh is not None,
                n_emissions=int(codes.shape[0]),
                n_slots=int(n_slots),
                partition=partition_splits is not None, **extra)
        if adaptive or not jit:
            return build_cascade(
                codes, slots, config, n_slots, weights=weights, valid=valid,
                capacity=capacity, acc_dtype=acc_dtype, adaptive=adaptive,
                backend=backend, mesh=mesh, weight_bound=weight_bound,
                partition_splits=partition_splits,
                route_capacity=route_capacity,
            )
        if isinstance(capacity, list):
            capacity = tuple(capacity)  # static args must be hashable
        # Donation engages only when the emission buffers are already
        # device-resident jax Arrays (the feeder's put, or an upstream
        # jnp producer): donating host numpy inputs would be a silent
        # no-op on TPU plus a "donated buffer not usable" warning.
        jit_entry = _build_cascade_jit
        if mesh is not None and isinstance(codes, jax.Array):
            jit_entry = _get_donating_cascade_jit()
        return jit_entry(
            codes, slots, config=config, n_slots=n_slots, weights=weights,
            valid=valid, capacity=capacity, acc_dtype=acc_dtype,
            backend=backend, mesh=mesh, weight_bound=weight_bound,
            partition_splits=partition_splits,
            route_capacity=route_capacity,
        )
    finally:
        tracing.end_span(tsp)


def _on_accelerator(x) -> bool:
    """True when ``x`` is a jax array living on a non-CPU device."""
    try:
        return any(d.platform != "cpu" for d in x.devices())
    except AttributeError:
        return False  # plain numpy


def decode_levels(level_data, config: CascadeConfig):
    """One decode pass shared by all egress consumers.

    Returns per-level dicts of numpy arrays:
    {slot, code, row, col, zoom, value} — values float64 (reference
    emits float counts, SURVEY.md §8.8). Raises on capacity overflow.
    """
    # Device->host in one batched device_get: on accelerators the
    # arrays are first truncated to their real row counts ON DEVICE
    # (they are padded to full capacity — 16 levels x capacity x 16B
    # of mostly-pad otherwise crosses to the host), and the single
    # device_get moves every level in one transfer instead of 32+
    # serial np.asarray calls. On CPU the transfer is free and a device
    # slice would only add a copy, so slice host-side there.
    n_lvls = config.n_levels + 1
    # The first read of the cascade's outputs: where nothing blocked
    # before, the host waits here for the device to finish.
    with trace.span("cascade.decode.wait"):
        if any(_on_accelerator(level_data[lvl][2]) for lvl in range(n_lvls)):
            # Batch the count scalars too: int() per level would block
            # on one device round trip each before the main transfer.
            counts = [int(c) for c in jax.device_get(
                [level_data[lvl][2] for lvl in range(n_lvls)]
            )]
        else:
            counts = [int(level_data[lvl][2]) for lvl in range(n_lvls)]
    for level, n in enumerate(counts):
        if n > level_data[level][0].shape[0]:
            raise ValueError(
                f"cascade level {level} overflowed capacity "
                f"({n} uniques > {level_data[level][0].shape[0]}); "
                f"raise `capacity`"
            )
    if any(_on_accelerator(level_data[lvl][0])
           for lvl in range(config.n_levels + 1)):
        # One slice program per distinct row count: a job's compiles
        # land in this span.
        with trace.span("cascade.decode.slice"):
            sliced = [(level_data[lvl][0][:n], level_data[lvl][1][:n])
                      for lvl, n in enumerate(counts)]
        with trace.span("cascade.decode.transfer"):
            host = jax.device_get(sliced)
    else:
        with trace.span("cascade.decode.transfer"):
            host = [
                (np.asarray(level_data[lvl][0])[:n],
                 np.asarray(level_data[lvl][1])[:n])
                for lvl, n in enumerate(counts)
            ]

    out = []
    with trace.span("cascade.decode.host"):
        for level, (keys_arr, sums) in enumerate(host):
            # Lazy import (native asserts against pipeline.timespan at
            # load; module-level would be circular). One threaded C pass
            # replaces the ~8 single-threaded numpy passes when
            # available.
            from heatmap_tpu import native as _native

            code_bits = 2 * (config.detail_zoom - level)
            # The native decoder returns int32 slots. Slot ids are
            # bounded by key >> code_bits; with code_bits >= 33 they fit
            # int32 by construction, below that check the actual max
            # (one cheap pass) and fall back to the int64 numpy path if
            # they don't.
            native_ok = _native.decode_keys is not None and (
                code_bits >= 33
                or keys_arr.size == 0
                or int(keys_arr.max()) >> code_bits < 2**31
            )
            if native_ok:
                slot_ids, codes, rows, cols = _native.decode_keys(
                    keys_arr, code_bits
                )
            else:
                slot_ids, codes = decode_level_keys(
                    keys_arr, config.detail_zoom, level
                )
                rows, cols = morton_decode_np(codes)
            out.append(
                {
                    "zoom": config.detail_zoom - level,
                    "slot": slot_ids,
                    "code": codes,
                    "row": rows,
                    "col": cols,
                    "value": sums.astype(np.float64),
                }
            )
    return out


def emit_level_arrays(level_data, config: CascadeConfig, slot_names):
    """Columnar egress (the production path): per-level numpy arrays.

    Adds coarse (blob) tile coordinates and resolves slot names to
    (user, timespan) index arrays; sinks can write these columns
    directly (files/Arrow/Cassandra batches) without any per-element
    Python. Applies the amplify_all compat patch when configured.
    """
    return finalize_level_arrays(
        decode_levels(level_data, config), config, slot_names
    )


def finalize_level_arrays(levels, config: CascadeConfig, slot_names):
    """Second half of :func:`emit_level_arrays`, for callers that build
    decoded levels themselves (e.g. the bounded-memory chunk merge in
    pipeline.batch): resolve slot names, add coarse tile coordinates,
    apply the amplify_all compat patch.

    User/timespan columns are DICTIONARY-ENCODED: per-row int32
    ``user_idx``/``timespan_idx`` into the small ``user_names``/
    ``timespan_names`` tables. Materializing per-row unicode columns
    (the previous contract) cost more host wall-clock than the entire
    device cascade at 25M aggregates — and every consumer either wants
    columns (sinks: dictionary encoding is smaller and faster) or only
    touches blob-run starts (JSON egress). Use :func:`level_strings`
    where full string columns are genuinely needed.
    """
    if config.amplify_all:
        with trace.span("egress.amplify"):
            _patch_amplified(levels, slot_names)
    # Dictionary encoding, with the coarse tile coordinates.
    with trace.span("egress.dictionary"):
        n_slots = max(slot_names) + 1
        users = np.array([slot_names.get(s, ("?", "?"))[0]
                          for s in range(n_slots)])
        tss = np.array([slot_names.get(s, ("?", "?"))[1]
                        for s in range(n_slots)])
        # Unique name tables + per-slot index maps (tiny: O(n_slots)).
        user_names, slot_to_uidx = np.unique(users, return_inverse=True)
        ts_names, slot_to_tidx = np.unique(tss, return_inverse=True)
        slot_to_uidx = slot_to_uidx.astype(np.int32)
        slot_to_tidx = slot_to_tidx.astype(np.int32)
        for lvl in levels:
            lvl["user_idx"] = slot_to_uidx[lvl["slot"]]
            lvl["timespan_idx"] = slot_to_tidx[lvl["slot"]]
            lvl["user_names"] = user_names
            lvl["timespan_names"] = ts_names
            lvl["coarse_zoom"] = lvl["zoom"] - config.result_delta
            lvl["coarse_row"] = lvl["row"] >> config.result_delta
            lvl["coarse_col"] = lvl["col"] >> config.result_delta
    return levels


def level_strings(lvl, sel=None):
    """(user, timespan) string arrays for a finalized level — full
    columns, or only rows ``sel`` (any numpy index)."""
    ui, ti = lvl["user_idx"], lvl["timespan_idx"]
    if sel is not None:
        ui, ti = ui[sel], ti[sel]
    return lvl["user_names"][ui], lvl["timespan_names"][ti]


def emit_blobs(level_data, config: CascadeConfig, slot_names):
    """Reference-format blob egress.

    Returns {"user|timespan|coarseTileId": {detailTileId: float count}}
    exactly like the reference write path (reference heatmap.py:54-55,
    79-90,128-129). String/dict building is vectorized with np.char;
    the per-blob dict assembly is inherently Python-object bound — use
    :func:`emit_level_arrays` for bulk sinks.
    """
    return blobs_from_level_arrays(
        emit_level_arrays(level_data, config, slot_names)
    )


def _level_blob_columns(lvl):
    """(blob_ids, detail_ids, values) string/float columns for a level."""
    sep = "|"  # reference KEY_SEPERATOR [sic], heatmap.py:18
    users, tss = level_strings(lvl)
    blob_ids = np.char.add(
        np.char.add(users, sep + tss + sep),
        _tile_id_strings(lvl["coarse_zoom"], lvl["coarse_row"], lvl["coarse_col"]),
    )
    detail_ids = _tile_id_strings(lvl["zoom"], lvl["row"], lvl["col"])
    return blob_ids, detail_ids, lvl["value"]


def blobs_from_level_arrays(levels):
    """Reference-format blobs from finalized level arrays
    (:func:`finalize_level_arrays` output)."""
    blobs: dict[str, dict[str, float]] = {}
    for lvl in levels:
        if len(lvl["slot"]) == 0:
            continue
        blob_ids, detail_ids, values = _level_blob_columns(lvl)
        # Group by blob id: sort once, slice runs.
        order = np.argsort(blob_ids, kind="stable")
        sorted_ids = blob_ids[order]
        starts = np.flatnonzero(
            np.concatenate([[True], sorted_ids[1:] != sorted_ids[:-1]])
        )
        bounds = np.append(starts, len(sorted_ids))
        for k, s in enumerate(starts):
            e = bounds[k + 1]
            idx = order[s:e]
            blobs.setdefault(str(sorted_ids[s]), {}).update(
                zip(detail_ids[idx].tolist(), values[idx].tolist())
            )
    return blobs


def json_blobs_from_level_arrays(levels):
    """{blob_id: json_string} egress without per-aggregate Python.

    Produces a dict EQUAL to ``{k: json.dumps(v) for k, v in
    blobs_from_level_arrays(levels).items()}`` — same keys, and each
    value byte-identical (numpy's shortest-roundtrip repr matches
    json.dumps for doubles; within-blob entry order is preserved).
    Key INSERTION order differs: composite-key order here vs
    string-sorted there, so sequential sink output is not diffable
    byte-for-byte against the old path. Per level, the JSON fragments are
    assembled with vectorized string ops, concatenated into ONE Python
    string with NUL markers at blob starts, and split back into
    per-blob documents — the only O(blobs) Python work left is the
    final dict construction. Measured ~1.5x the dict+json.dumps path
    at 3.5M blobs / ~60M aggregates (the remaining floor is numpy's
    per-aggregate int/float-to-string formatting, ~8 passes over every
    aggregate). Jobs at that scale should prefer the columnar
    LevelArraysSink, which skips string egress entirely.

    Blob ids never collide across levels (the coarse zoom is part of
    the id), so per-level construction is complete — the dict-merge in
    blobs_from_level_arrays exists only for generic robustness.
    """
    sep = "|"  # reference KEY_SEPERATOR [sic], heatmap.py:18
    out: dict[str, str] = {}
    for lvl in levels:
        if len(lvl["slot"]) == 0:
            continue
        # Level arrays arrive sorted by (slot, code), so blob runs —
        # same slot, same coarse tile — are already CONTIGUOUS: no
        # string sort needed, and blob-id strings (the widest in play)
        # are built only at run starts, #blobs not #aggregates.
        slots = lvl["slot"]
        is_start = np.concatenate([[True], (
            (slots[1:] != slots[:-1])
            | (lvl["coarse_row"][1:] != lvl["coarse_row"][:-1])
            | (lvl["coarse_col"][1:] != lvl["coarse_col"][:-1])
        )])
        sidx = np.flatnonzero(is_start)
        from heatmap_tpu import native as _native

        if _native.format_blob_ids is not None:
            ids = _native.format_blob_ids(
                lvl["user_idx"][sidx], lvl["timespan_idx"][sidx],
                lvl["coarse_row"][sidx], lvl["coarse_col"][sidx],
                int(lvl["coarse_zoom"]),
                lvl["user_names"], lvl["timespan_names"],
            )
        else:
            users, tss = level_strings(lvl, sidx)
            ids = np.char.add(
                np.char.add(users, sep + tss + sep),
                _tile_id_strings(lvl["coarse_zoom"],
                                 lvl["coarse_row"][sidx],
                                 lvl["coarse_col"][sidx]),
            ).tolist()
        out.update(zip(ids, _blob_bodies(lvl, is_start)))
    return out


def _blob_bodies(lvl, is_start):
    """Per-blob '{...}' JSON documents for one level, in order.

    The multithreaded native formatter handles the common case —
    integral values, i.e. every count job and any weighted job whose
    sums happen to be whole numbers — at C speed; the numpy join/split
    path formats fractional weighted sums and doubles as the formatting
    oracle (tested equal byte-for-byte on integral inputs).
    """
    values = lvl["value"]
    # Lazy import: native asserts against pipeline.timespan at load, so
    # a module-level import here would be circular.
    from heatmap_tpu import native as _native

    if _native.format_blob_bodies is not None and bool(
        np.all((values == np.floor(values)) & (np.abs(values) < 1e15))
    ):
        return _native.format_blob_bodies(
            lvl["row"], lvl["col"], values, is_start, int(lvl["zoom"])
        )
    # '"<detail>": <value>' fragments, json.dumps separators.
    frag = np.char.add(
        np.char.add(
            np.char.add(
                '"', _tile_id_strings(lvl["zoom"], lvl["row"], lvl["col"])
            ),
            '": ',
        ),
        values.astype(str),
    )
    # Run-start fragments open a new document ('}\x00{' closes the
    # previous one); the rest continue with ', '. One join, one split,
    # zero per-blob concatenation.
    parts = np.char.add(np.where(is_start, "}\x00{", ", "), frag)
    big = "".join(parts.tolist()) + "}"
    return big.split("\x00")[1:]  # [0] is the artifact '}' head


def _tile_id_strings(zoom, rows, cols):
    """Vectorized reference tile-id strings "zoom_row_col"."""
    z = np.char.add(np.asarray(zoom).astype(str), "_")
    return np.char.add(
        np.char.add(np.char.add(z, rows.astype(str)), "_"), cols.astype(str)
    )


def _sorted_lookup(sorted_keys, sorted_vals, queries):
    """Value per query from a sorted (keys, vals) table; 0.0 on miss."""
    if len(sorted_keys) == 0 or len(queries) == 0:
        return np.zeros(len(queries), np.float64)
    pos = np.clip(np.searchsorted(sorted_keys, queries), 0,
                  len(sorted_keys) - 1)
    return np.where(sorted_keys[pos] == queries, sorted_vals[pos], 0.0)


def _patch_amplified(levels, slot_names):
    """In-place 'all' amplification (SURVEY.md §8.1 recurrence):

    A_0 = all_0 (correct);  A_L = 2 * rollup(A_{L-1}) + sum_users user_L.
    Per-user counts untouched, exactly as in the reference. Fully
    vectorized: every step works on packed ``(slot << code_bits) |
    code`` int64 keys (sorted, since level arrays arrive in ascending
    composite-key order) via unique/bincount folds and searchsorted
    lookups — no per-aggregate Python.
    """
    is_all_slot = np.array(
        [slot_names.get(s, ("?",))[0] == "all" for s in range(max(slot_names) + 1)]
    )
    prev_s = prev_c = np.empty(0, np.int64)
    prev_v = np.empty(0, np.float64)
    for level, lvl in enumerate(levels):
        slots = np.asarray(lvl["slot"], np.int64)
        codes = np.asarray(lvl["code"], np.int64)
        vals = np.asarray(lvl["value"], np.float64)
        cb = 2 * lvl["zoom"]  # codes at this level are < 4**zoom
        all_mask = (
            is_all_slot[slots] if len(slots) else np.zeros(0, bool)
        )
        a_s, a_c = slots[all_mask], codes[all_mask]
        if level == 0:
            new_all = vals[all_mask]
        else:
            # rollup(A_{L-1}): parent key folds the 4 children.
            rk = (prev_s << cb) | (prev_c >> 2)
            ruk, rinv = np.unique(rk, return_inverse=True)
            rv = (
                np.bincount(rinv, weights=prev_v)
                if len(rk) else np.empty(0, np.float64)
            )
            # sum over non-all slots, keyed by the all-slot of their
            # timespan (slot = ts*G + g with g=0 the all group).
            um = ~all_mask
            if um.any():
                utk = (_all_slot_of(slots[um], is_all_slot) << cb) | codes[um]
                uuk, uinv = np.unique(utk, return_inverse=True)
                uv = np.bincount(uinv, weights=vals[um])
            else:
                uuk = np.empty(0, np.int64)
                uv = np.empty(0, np.float64)
            ak = (a_s << cb) | a_c
            new_all = (
                2.0 * _sorted_lookup(ruk, rv, ak)
                + _sorted_lookup(uuk, uv, ak)
            )
        if len(slots):
            patched = vals.copy()
            patched[all_mask] = new_all
            lvl["value"] = patched
        prev_s, prev_c, prev_v = a_s, a_c, new_all


def _all_slot_of(slots, is_all_slot):
    """Map each slot to the 'all' slot of its timespan block.

    Slots are ts*G + g with g=0 the 'all' group, so the all-slot is the
    largest all-slot <= slot: computed via searchsorted over the sorted
    all-slot ids.
    """
    all_ids = np.flatnonzero(is_all_slot)
    pos = np.searchsorted(all_ids, slots, side="right") - 1
    return all_ids[pos]
