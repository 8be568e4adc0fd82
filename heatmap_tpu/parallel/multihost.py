"""Multi-host execution: DCN-aware meshes, ingest sharding, egress merge.

The reference scales out by adding Spark executors behind a k8s external
shuffle service (reference submit-heatmap:9-13); its "communication
backend" is the JVM shuffle over the pod network (SURVEY.md §2.3). The
TPU-native equivalent (BASELINE.md config 5, 10B points on v5e-64):

- every host runs this same program (SPMD) after ``initialize()``
  (``jax.distributed`` — on TPU pods coordinator/process-id/count
  auto-detect from the runtime environment);
- ingest is sharded by process: each host reads only its slice of the
  source (``process_shard_bounds`` — the Cassandra-token-range analog),
  then shards its points over its local devices on the mesh's data
  axis;
- device collectives (psum / psum_scatter in parallel.sharded) ride
  ICI within a host and DCN across hosts. ``make_hybrid_mesh`` orders
  devices so consecutive data-axis neighbors are ICI-local (XLA then
  hierarchically decomposes cross-host reductions: reduce over ICI
  first, DCN once per host);
- egress: ``gather_blobs`` (DCN byte-level allgather, every host gets
  the full merged dict, process 0 writes) is the default / small-job
  path — the analog of the reference's driver-side collect
  (heatmap.py:156-158). Tile-space-sharded egress is the explicit
  opt-in (``egress="sharded"``, per-host sink paths required):
  ``scatter_blobs`` partitions the blob keyspace deterministically
  over processes (``blob_owner``; ``scatter_levels`` uses the
  equivalent name+tile hash ``_level_row_owner`` for columnar rows)
  and one all-to-all moves each blob to its owner, which writes its
  own sink shard — the analog of the reference's Spark reducers each
  writing their hash partition to Cassandra (heatmap.py:149-150).

Everything degrades to a no-op on a single process, so the same job
script runs unchanged from a laptop CPU to a v5e-64 pod.
"""

from __future__ import annotations

import json
import zlib

import jax
import numpy as np

from heatmap_tpu import obs
from heatmap_tpu.obs import tracing
from heatmap_tpu.io.sinks import LevelArraysSink as _LevelArraysSink
# Merge semantics live in the jax-free io.merge module (the CLI's
# offline shard merge uses them without an accelerator stack);
# re-exported here because every distributed egress path and its
# tests address them through this module.
from heatmap_tpu.io.merge import (  # noqa: F401
    _merge_blob_values,
    merge_blob_parts,
    merge_level_parts,
)
from heatmap_tpu.parallel.mesh import make_mesh, shard_map


class StragglerTimeout(RuntimeError):
    """A host's heartbeat went stale past the configured deadline.

    Raised by :func:`check_heartbeats` so a straggling or dead host
    turns into a typed, catchable error at the next phase boundary
    instead of the job hanging in a collective forever. Carries the
    offending ``{process: age_s}`` map as ``.stale``.
    """

    def __init__(self, deadline_s: float, stale: dict):
        detail = ", ".join(f"process {p}: {age:.1f}s"
                           for p, age in sorted(stale.items()))
        super().__init__(
            f"heartbeat deadline {deadline_s}s exceeded ({detail})")
        self.deadline_s = float(deadline_s)
        self.stale = dict(stale)


def check_heartbeats(deadline_s: float, now: float | None = None,
                     expected=None) -> dict:
    """Raise :class:`StragglerTimeout` if any host's last heartbeat is
    older than ``deadline_s``; otherwise return the age map.

    Reads ``obs.heartbeat_ages()`` (the ``multihost_last_heartbeat_ts``
    gauge), so it only sees hosts whose heartbeats reach this process's
    registry — per-process in the current transport, which is exactly
    the lost-heartbeat failure mode the ``multihost.heartbeat`` fault
    site injects. A disabled registry yields no ages and never times
    out (monitoring off means no straggler detection, not a crash).

    A host that *never* heartbeats is invisible to the age map — it has
    no gauge sample to go stale. ``expected`` closes that gap: an
    iterable of process labels that MUST have beaten at least once;
    any expected label absent from the ages is reported stale with age
    ``inf`` (caught at the first phase boundary, not after a hang).
    Without ``expected`` the historical observed-hosts-only semantics
    are unchanged — see docs/robustness.md for the distinction.
    """
    if deadline_s is None or deadline_s <= 0:
        raise ValueError("deadline_s must be a positive number of seconds")
    ages = obs.heartbeat_ages(now)
    stale = {p: age for p, age in ages.items() if age > deadline_s}
    if expected is not None:
        for p in expected:
            if str(p) not in ages:
                stale[str(p)] = float("inf")
    if stale:
        raise StragglerTimeout(deadline_s, stale)
    return ages


def initialize(coordinator_address: str | None = None,
               num_processes: int | None = None,
               process_id: int | None = None):
    """Bring up jax.distributed (no-op if already initialized or
    single-process with no coordinator configured).

    On TPU pods all three arguments auto-detect; on CPU/GPU clusters
    pass them explicitly (the JAX_COORDINATOR_ADDRESS /
    JAX_NUM_PROCESSES / JAX_PROCESS_ID env vars also work).
    """
    import os

    # Detect a prior distributed init WITHOUT touching the backend:
    # jax.process_count() would initialize the local backend, after
    # which distributed.initialize() always raises and the job would
    # silently run single-process.
    try:
        from jax._src import distributed as _dist

        if getattr(_dist.global_state, "client", None) is not None:
            return  # already distributed
    except (ImportError, AttributeError):
        # private API moved/renamed; fall through to initialize
        pass
    explicit = (
        coordinator_address is not None
        or num_processes is not None
        or process_id is not None
        or any(os.environ.get(v) for v in (
            "JAX_COORDINATOR_ADDRESS", "JAX_NUM_PROCESSES", "JAX_PROCESS_ID",
        ))
    )
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    except (ValueError, RuntimeError):
        if explicit:
            # The caller configured a cluster; failing to join it is an
            # error, not a single-process fallback.
            raise
        # Single-process environment (no coordinator discoverable) — fine.
        pass


def make_hybrid_mesh(tile: int = 1, devices=None) -> jax.sharding.Mesh:
    """A (data, tile) mesh whose data-axis device order is DCN-aware.

    Multi-process: devices are ordered host-major (each host's local
    devices contiguous), so neighboring data-axis positions are
    ICI-connected and XLA lowers data-axis reductions hierarchically
    (ICI ring per host, then one DCN hop per host pair) — the layout
    "How to Scale Your Model" prescribes for DP over pods. The axis
    NAME stays ``data``, so every kernel in parallel.sharded works
    unchanged on a pod.

    Single-process: identical to ``make_mesh``.
    """
    if devices is None:
        devices = jax.devices()
    if jax.process_count() > 1:
        # jax.devices() is already process-major on TPU pods, but make
        # it explicit (and stable) rather than relying on enumeration
        # order: sort by (process_index, local id).
        devices = sorted(
            devices, key=lambda d: (d.process_index, d.id)
        )
    return make_mesh(tile=tile, devices=devices)


def process_shard_bounds(n: int, process_count: int | None = None,
                         process_index: int | None = None) -> tuple[int, int]:
    """[start, end) slice of an n-element source this process ingests.

    Balanced like Spark's even token-range split: first ``n % k``
    shards get one extra element. Deterministic, so failed-host
    re-execution re-reads exactly the same slice (SURVEY.md §5
    fault-tolerance model).
    """
    k = jax.process_count() if process_count is None else process_count
    i = jax.process_index() if process_index is None else process_index
    if not 0 <= i < k:
        raise ValueError(f"process_index {i} out of range for {k} processes")
    base, extra = divmod(n, k)
    start = i * base + min(i, extra)
    return start, start + base + (1 if i < extra else 0)


def shard_source_rows(source_batches, n_total: int, batch_size: int,
                      process_count: int | None = None,
                      process_index: int | None = None):
    """Yield only this process's batches from a deterministic source.

    ``source_batches`` must yield fixed-size batches (``batch_size``
    rows, last one ragged) in a deterministic order; batch indices are
    partitioned by ``process_shard_bounds`` over the batch count. The
    host-level analog of the per-device point sharding inside the mesh.
    """
    n_batches = -(-n_total // batch_size) if n_total else 0
    lo, hi = process_shard_bounds(n_batches, process_count, process_index)
    for i, batch in enumerate(source_batches):
        if i >= hi:
            break
        if i >= lo:
            yield batch


def gather_blobs(local_blobs: dict, max_bytes: int = 1 << 30) -> dict:
    """Merge per-process blob dicts across hosts (DCN allgather).

    Values must be JSON-serializable (the pipeline emits JSON strings
    already). Key collisions across hosts SUM — with process-sharded
    ingest and slot-complete cascades, collisions only occur for
    result tiles whose detail tiles straddle host shards, where the
    inner dicts are disjoint-or-summable by construction; a
    non-summable collision therefore indicates corruption and raises
    (_merge_blob_values), never resolves last-process-wins.

    Single-process: returns ``local_blobs`` unchanged.
    """
    if jax.process_count() == 1:
        return local_blobs
    from jax.experimental import multihost_utils

    payload = json.dumps(local_blobs).encode()
    if len(payload) > max_bytes:
        raise ValueError(
            f"local blob payload {len(payload)}B exceeds max_bytes; "
            f"raise max_bytes or write per-host sinks instead"
        )
    # Fixed-width frame: [length:8][payload][zero pad] so allgather is
    # a dense u8 array.
    n = np.asarray([len(payload)], np.int64)
    max_len = int(multihost_utils.process_allgather(n).max())
    frame = np.zeros(max_len + 8, np.uint8)
    frame[:8] = np.frombuffer(np.int64(len(payload)).tobytes(), np.uint8)
    frame[8 : 8 + len(payload)] = np.frombuffer(payload, np.uint8)
    frames = multihost_utils.process_allgather(frame)  # (k, max_len+8)
    merged: dict = {}
    for row in np.asarray(frames):
        ln = int(np.frombuffer(row[:8].tobytes(), np.int64)[0])
        part = json.loads(row[8 : 8 + ln].tobytes().decode())
        for key, val in part.items():
            if key in merged:
                merged[key] = _merge_blob_values(merged[key], val)
            else:
                merged[key] = val
    return merged


def blob_owner(blob_id: str, process_count: int) -> int:
    """Deterministic owner process of a blob key (tile-space sharding).

    crc32 of the blob id ("user|timespan|z_r_c"), mod process count —
    stable across hosts, runs and Python processes (unlike built-in
    ``hash``, which is salted). Every row of a blob maps to the same
    owner, so per-host egress shards are disjoint at blob granularity —
    the analog of the reference's Spark reducers each writing their own
    hash partition of tile space (reference heatmap.py:149-150).
    """
    return zlib.crc32(blob_id.encode()) % process_count


def partition_blobs(local_blobs: dict, process_count: int) -> list[dict]:
    """Split a local blob dict into per-owner sub-dicts (see blob_owner)."""
    parts: list[dict] = [{} for _ in range(process_count)]
    for key, val in local_blobs.items():
        parts[blob_owner(key, process_count)][key] = val
    return parts


#: Per-collective buffer bound for the byte exchange: a shift round
#: wider than this splits into chunked ppermutes, so device frames
#: never exceed it no matter how large a single payload is.
_EXCHANGE_CHUNK_BYTES = 1 << 28


def _process_mesh():
    """1 device per process, process-ordered, so mesh position ==
    process index and shift arithmetic addresses real processes."""
    firsts: dict[int, object] = {}
    for dev in sorted(jax.devices(), key=lambda d: (d.process_index, d.id)):
        firsts.setdefault(dev.process_index, dev)
    return jax.sharding.Mesh(np.asarray(list(firsts.values())), ("p",))


def _alltoall_bytes(dest_payloads: list[bytes],
                    process_count: int | None = None,
                    transport=None,
                    max_bytes: int = 1 << 30,
                    chunk_bytes: int = _EXCHANGE_CHUNK_BYTES) -> list[bytes]:
    """All-to-all byte exchange: ``dest_payloads[d]`` goes to process
    d; returns the k payloads this process received (index = source).

    The sharded-egress transport: unlike gather_blobs' allgather, each
    pair moves only its own payload, so no host ever receives (or
    holds) the full blob set. Single-process: identity. ``transport``
    (tests, alternative backends) overrides the default implementation:
    a callable ``(dest_payloads) -> received_payloads``.

    Default multi-process transport rides the same device fabric as
    the compute collectives (DCN across hosts — "How to Scale Your
    Model"'s host-transfer recipe, not a sidecar TCP mesh), SKEW-PROOF
    by construction (a dense (k, global-max) frame would let one hot
    pair pad every row):

    1. one small allgather publishes the k×k length matrix, so every
       process knows every pair's exact payload size;
    2. the exchange decomposes into k-1 ``lax.ppermute`` shift rounds
       (round s: p -> p+s mod k). Each round's buffer is sized by THAT
       shift class's maximum only, so a single 500 MB pair inflates
       its own round, not the other k-2;
    3. a round wider than ``chunk_bytes`` splits into chunked
       ppermutes — per-collective device memory is bounded regardless
       of payload size.

    ``max_bytes`` now guards what this process actually has to HOLD
    (the sum of payloads addressed to it — unavoidable memory for its
    owned shard) rather than a padding artifact; hitting it means the
    keyspace itself is skewed (rebalance partitioning or raise the
    cap), not that the transport framed badly.
    """
    k = jax.process_count() if process_count is None else process_count
    if len(dest_payloads) != k:
        raise ValueError(f"expected {k} payloads, got {len(dest_payloads)}")
    if transport is not None:
        return list(transport(dest_payloads))
    if k == 1:
        return [dest_payloads[0]]
    from jax.experimental import multihost_utils
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    me = jax.process_index()
    lens = np.asarray([len(p) for p in dest_payloads], np.int64)
    # L[p, d] = bytes process p sends to process d.
    L = np.asarray(multihost_utils.process_allgather(lens))
    owned = int(L[:, me].sum())
    if owned > max_bytes:
        raise ValueError(
            f"process {me} would receive {owned}B of payloads "
            f"(> max_bytes {max_bytes}); its owned shard is this large "
            "regardless of transport — rebalance the key partition or "
            "raise max_bytes"
        )
    mesh = _process_mesh()
    spec = NamedSharding(mesh, P("p"))

    received: list = [b""] * k
    received[me] = dest_payloads[me]
    for s in range(1, k):
        dst = (me + s) % k
        src = (me - s) % k
        width = int(max(L[p, (p + s) % k] for p in range(k)))
        if width == 0:
            continue
        perm = [(p, (p + s) % k) for p in range(k)]

        def body(b, perm=perm):
            return lax.ppermute(b, "p", perm)

        fn = jax.jit(shard_map(
            body, mesh=mesh, in_specs=P("p"), out_specs=P("p")
        ))
        chunks = []
        payload = dest_payloads[dst]
        need = int(L[src, me])
        for off in range(0, width, chunk_bytes):
            w = min(chunk_bytes, width - off)
            buf = np.zeros(w, np.uint8)
            part = payload[off:off + w]
            if part:
                buf[:len(part)] = np.frombuffer(part, np.uint8)
            garr = jax.make_array_from_process_local_data(spec, buf[None])
            out = fn(garr)
            # Keep only the bytes THIS process's incoming payload
            # actually occupies — a bystander in a hot pair's round
            # must not accumulate the round's full padded width on the
            # host (it participates in the collective, then drops the
            # padding chunk by chunk).
            keep = max(0, min(w, need - off))
            if keep:
                chunks.append(np.asarray(
                    list(out.addressable_shards)[0].data
                )[0][:keep])
        received[src] = b"".join(c.tobytes() for c in chunks)
    return received


def scatter_blobs(local_blobs: dict,
                  process_count: int | None = None,
                  transport=None,
                  max_bytes: int = 1 << 30) -> dict:
    """Tile-space-sharded egress merge: each process ends with the
    fully-merged blobs it OWNS (blob_owner partition) — and nothing
    else. The scalable replacement for gather_blobs: total bytes moved
    equal the blob volume once, and per-host memory is the owned shard,
    not the whole result (reference analog: distributed reducer
    writes, heatmap.py:149-150).

    Single-process: returns ``local_blobs`` unchanged.
    """
    k = jax.process_count() if process_count is None else process_count
    if k == 1 and transport is None:
        return local_blobs
    parts = partition_blobs(local_blobs, k)
    payloads = [json.dumps(p).encode() for p in parts]
    received = _alltoall_bytes(payloads, process_count=k,
                               transport=transport, max_bytes=max_bytes)
    return merge_blob_parts(json.loads(r.decode()) for r in received)


def _level_row_owner(lvl, process_count: int) -> np.ndarray:
    """Owner process per aggregate row of a finalized level.

    Depends only on cross-host-consistent values (user/timespan NAMES
    — per-host vocab indices differ host to host — plus the coarse
    tile and zoom), so every host routes rows of the same blob to the
    same owner. Vectorized: crc32 only over the small name tables.
    """
    mix = np.uint64(0x9E3779B97F4A7C15)
    uh = np.asarray([zlib.crc32(str(s).encode()) for s in lvl["user_names"]],
                    np.uint64)
    th = np.asarray(
        [zlib.crc32(str(s).encode()) for s in lvl["timespan_names"]],
        np.uint64,
    )
    h = uh[np.asarray(lvl["user_idx"])] * mix
    h ^= th[np.asarray(lvl["timespan_idx"])]
    h *= mix
    h ^= (np.asarray(lvl["coarse_row"], np.uint64) << np.uint64(24)) \
        ^ np.asarray(lvl["coarse_col"], np.uint64) \
        ^ (np.uint64(int(lvl["coarse_zoom"])) << np.uint64(48))
    h *= mix
    return (h % np.uint64(process_count)).astype(np.int64)


# The per-row level schema IS the columnar sink schema — one source of
# truth, so a column added there can't silently drop from the exchange.
_LEVEL_ROW_COLS = _LevelArraysSink.COLUMNS


def partition_levels(levels, process_count: int) -> list[list[dict]]:
    """Split finalized level arrays into per-owner row subsets.

    Returns ``parts[d]`` = the levels list destined to process d (same
    level schema, rows selected; name tables ride along whole — they
    are O(unique users), tiny next to the rows).
    """
    parts: list[list[dict]] = [[] for _ in range(process_count)]
    for lvl in levels:
        owner = _level_row_owner(lvl, process_count)
        for d in range(process_count):
            sel = np.flatnonzero(owner == d)
            sub = {k: np.asarray(lvl[k])[sel] for k in _LEVEL_ROW_COLS}
            sub["zoom"] = int(lvl["zoom"])
            sub["coarse_zoom"] = int(lvl["coarse_zoom"])
            sub["user_names"] = np.asarray(lvl["user_names"])
            sub["timespan_names"] = np.asarray(lvl["timespan_names"])
            parts[d].append(sub)
    return parts


def _levels_to_bytes(levels) -> bytes:
    import io as _io

    arrays = {"n_levels": np.asarray(len(levels))}
    for j, lvl in enumerate(levels):
        for key in _LEVEL_ROW_COLS + ("user_names", "timespan_names"):
            arrays[f"l{j}_{key}"] = np.asarray(lvl[key])
        arrays[f"l{j}_zoom"] = np.asarray(lvl["zoom"])
        arrays[f"l{j}_coarse_zoom"] = np.asarray(lvl["coarse_zoom"])
    buf = _io.BytesIO()
    np.savez(buf, **arrays)
    return buf.getvalue()


def _levels_from_bytes(payload: bytes) -> list[dict]:
    import io as _io

    with np.load(_io.BytesIO(payload), allow_pickle=False) as z:
        n = int(z["n_levels"])
        out = []
        for j in range(n):
            lvl = {
                key: z[f"l{j}_{key}"]
                for key in _LEVEL_ROW_COLS + ("user_names", "timespan_names")
            }
            lvl["zoom"] = int(z[f"l{j}_zoom"])
            lvl["coarse_zoom"] = int(z[f"l{j}_coarse_zoom"])
            out.append(lvl)
    return out


def scatter_levels(levels,
                   process_count: int | None = None,
                   transport=None,
                   max_bytes: int = 1 << 30) -> list[dict]:
    """Columnar analog of scatter_blobs: exchange finalized level rows
    so each process owns complete, merged rows for its blob-key shard —
    the egress that lets every host write its own LevelArraysSink
    (per-host .npz/.parquet shards of one logical columnar result).

    Single-process: returns ``levels`` unchanged.
    """
    k = jax.process_count() if process_count is None else process_count
    if k == 1 and transport is None:
        return list(levels)
    parts = partition_levels(levels, k)
    payloads = [_levels_to_bytes(p) for p in parts]
    received = _alltoall_bytes(payloads, process_count=k,
                               transport=transport, max_bytes=max_bytes)
    return merge_level_parts(_levels_from_bytes(r) for r in received)


def shard_source(source, process_count: int | None = None,
                 process_index: int | None = None):
    """This process's view of a range-shardable source.

    Sources that expose ``shard_index``/``shard_count`` dataclass
    fields (CassandraSource token ranges, CosmosDBSource partition key
    ranges) re-instantiate with this process's interleaved assignment —
    the real connector-style input-split sharding, no row counting
    needed. Returns None for sources without native sharding (callers
    fall back to row slicing).
    """
    import dataclasses

    if not (dataclasses.is_dataclass(source)
            and hasattr(source, "shard_index")
            and hasattr(source, "shard_count")):
        return None
    k = jax.process_count() if process_count is None else process_count
    i = jax.process_index() if process_index is None else process_index
    if source.shard_count != 1:
        raise ValueError(
            "source already carries a shard assignment "
            f"(shard {source.shard_index}/{source.shard_count}); pass an "
            "unsharded source to run_job_multihost"
        )
    return dataclasses.replace(source, shard_index=i, shard_count=k)


class _CaptureLevels:
    """In-memory ``write_levels`` sink: captures finalized level arrays
    so the multihost columnar path can scatter them before the real
    sink write. Accumulates across calls — the bounded path's spill
    egress finalizes one level per call, the single-shot path all
    levels in one."""

    def __init__(self):
        self.levels: list[dict] = []

    def write_levels(self, levels) -> int:
        new = list(levels)
        self.levels.extend(new)
        return sum(len(lvl["value"]) for lvl in new)


class _SliceSource:
    """This process's row-sharded slice as a re-cuttable source.

    The batch-index shard assignment is pinned at the CONSTRUCTION
    batch size: every process must cut the source at the same
    granularity or the partition drops/duplicates rows, and the
    bounded path re-reads batches at ``min(batch_size, max_points)``
    — a per-host value when the chunk size was auto-derived from that
    host's RAM. So ``batches(bs)`` always shards at the pinned size
    and re-cuts oversized batches afterwards, host-locally.
    """

    def __init__(self, source, n_total: int, batch_size: int):
        self.source = source
        self.n_total = n_total
        self.batch_size = batch_size

    def batches(self, bs: int):
        sliced = shard_source_rows(
            self.source.batches(self.batch_size), self.n_total,
            self.batch_size,
        )
        if bs >= self.batch_size:
            yield from sliced
            return
        for batch in sliced:
            n = len(batch["latitude"])
            if n <= bs:
                yield batch
                continue
            for i in range(0, n, bs):
                yield {k: v[i:i + bs] for k, v in batch.items()}


def run_job_multihost(source, sink=None, config=None,
                      batch_size: int = 1 << 20,
                      n_total: int | None = None,
                      egress: str = "auto",
                      max_points_in_flight: int | None = None,
                      egress_max_bytes: int = 1 << 30,
                      merge_spill_dir: str | None = None,
                      heartbeat_deadline_s: float | None = None,
                      on_straggler: str = "raise",
                      elastic_dir: str | None = None,
                      elastic_hosts: int | None = None,
                      elastic_opts: dict | None = None):
    """Process-sharded ``run_job``: each host ingests its slice of the
    source and aggregates on its local devices; egress then either

    - ``"sharded"`` (tile-space-sharded, the scalable path): blob keys
      partition deterministically across processes (blob_owner); an
      all-to-all moves each blob to its owner once, and EVERY process
      writes its owned shard to its own ``sink`` — the analog of the
      reference's distributed reducer writes (heatmap.py:149-150). No
      step materializes all blobs on one host. Returns this process's
      owned shard. Columnar sinks (``write_levels``) are supported:
      level rows scatter by blob key (scatter_levels) and each host
      writes per-host .npz/.parquet shards — point per-host sinks at
      distinct paths on shared storage.
    - ``"gather"``: the small-job path — gather_blobs allgathers and
      merges everything on every host; only process 0 writes. Returns
      the full blob dict everywhere. Refuses columnar sinks.
    - ``"auto"`` (default): "gather" — sharded egress means every
      process writes through ITS OWN sink, so it must be an explicit
      choice made with per-host sink paths (a shared path would have k
      hosts clobbering each other's files); auto never silently flips
      an existing gather caller into that contract. Columnar sinks on
      multiple processes therefore raise under auto, with guidance.

    Range-shardable sources (``shard_index``/``shard_count`` fields —
    Cassandra token ranges, CosmosDB partition key ranges) shard by
    range assignment via :func:`shard_source`. Otherwise ``n_total``
    (total source rows) enables exact batch-count sharding; without
    it, single-process falls through to run_job and multi-process
    raises (sources must declare their size to shard — SyntheticSource
    has ``n``; files can be pre-counted).

    ``max_points_in_flight`` composes with multi-process runs: each
    process streams ITS SLICE through the chunked cascade + host merge
    (run_job's bounded path, auto-spill included), so per-host memory
    is O(chunk + unique output keys) instead of the whole slice in one
    shot — BASELINE config 5's per-host memory story (the Spark
    analog: executors stream partitions and spill,
    submit-heatmap:14). ``None`` auto-routes exactly like run_job,
    with the fit decision made about the 1/k slice; ``0`` forces the
    single-shot slice ingest. ``merge_spill_dir`` passes through to
    the bounded path's disk-spill cross-chunk merge (run_job's knob;
    requires a positive/auto bound, same refusal rule).
    ``egress_max_bytes`` caps the egress collective's memory
    (gather_blobs' payload / the bytes a process must hold of the
    sharded exchange — the transport itself is skew-proof, see
    _alltoall_bytes) so a pathologically skewed keyspace fails loudly
    instead of OOMing a host — raise it here when a big job
    legitimately needs more.

    ``heartbeat_deadline_s`` arms straggler detection: after each phase
    boundary heartbeat, :func:`check_heartbeats` raises a typed
    :class:`StragglerTimeout` if any observed host's heartbeat is older
    than the deadline — the bounded-wait alternative to hanging in the
    next collective (docs/robustness.md). ``None`` (default) keeps the
    historical hang-and-hope behavior.

    ``on_straggler`` decides what a straggler timeout means:
    ``"raise"`` (default, today's semantics) surfaces the typed error
    and the job dies; ``"reassign"`` routes the whole job through the
    elastic execution layer (parallel/elastic.py — shard-lineage
    manifest under ``elastic_dir``, orphaned shards of a stale host
    re-executed on survivors, byte-identical output). Reassign mode
    requires ``elastic_dir`` and a columnar (``write_levels``) sink or
    no sink; ``elastic_hosts`` sets the simulated host count on a
    single process (default 2), and ``elastic_opts`` forwards advanced
    knobs (speculation, chaos wedge hooks) to
    :func:`heatmap_tpu.parallel.elastic.run_job_elastic`.
    """
    from heatmap_tpu.pipeline import BatchJobConfig, run_job
    from heatmap_tpu.pipeline.batch import (
        _auto_points_in_flight, _run_job_bounded, _run_loaded,
        ingest_columns,
    )

    config = config or BatchJobConfig()
    if on_straggler not in ("raise", "reassign"):
        raise ValueError(f"unknown on_straggler mode {on_straggler!r}")
    if on_straggler == "reassign":
        if elastic_dir is None:
            raise ValueError(
                "on_straggler='reassign' needs elastic_dir: the shard-"
                "lineage manifest is what makes failover re-execution "
                "exactly-once (parallel/elastic.py)")
        from heatmap_tpu.parallel.elastic import run_job_elastic

        return run_job_elastic(
            source, sink, config, batch_size=batch_size, n_total=n_total,
            lineage_dir=elastic_dir, n_hosts=elastic_hosts,
            heartbeat_deadline_s=heartbeat_deadline_s,
            **(elastic_opts or {}))
    if elastic_dir is not None or elastic_hosts is not None \
            or elastic_opts is not None:
        raise ValueError(
            "elastic_dir/elastic_hosts/elastic_opts only apply with "
            "on_straggler='reassign'")
    if egress not in ("auto", "gather", "sharded"):
        raise ValueError(f"unknown egress mode {egress!r}")
    columnar = sink is not None and hasattr(sink, "write_levels")
    if columnar and egress != "sharded":
        # The gather egress merges reference-format blob dicts on one
        # host; a columnar sink would crash at the final write. Refuse
        # at submit time — and never auto-pick sharded for it, because
        # sharded egress writes through every process's sink and needs
        # deliberately per-host paths.
        if jax.process_count() > 1 or egress == "gather":
            raise ValueError(
                "gather egress is blob-based; columnar sinks "
                "(arrays:/LevelArraysSink) need egress='sharded' with "
                "per-host sink paths (each process writes its own "
                "level-array shard)"
            )
    if egress == "auto":
        egress = "gather"
    if jax.process_count() == 1:
        return run_job(source, sink, config, batch_size=batch_size,
                       max_points_in_flight=max_points_in_flight,
                       merge_spill_dir=merge_spill_dir)
    sharded = shard_source(source)
    if sharded is not None:
        slice_source = sharded
    else:
        if n_total is None:
            n_total = getattr(source, "n", None)
            if n_total is None:
                raise ValueError(
                    "multi-host sharding needs n_total (source row count) "
                    "or a range-shardable source"
                )
        slice_source = _SliceSource(source, n_total, batch_size)
    if max_points_in_flight is None:
        max_points_in_flight = _auto_points_in_flight(
            source, shard_count=jax.process_count()
        )
    if merge_spill_dir is not None and not max_points_in_flight:
        raise ValueError(
            "merge_spill_dir lives on the bounded path; pass "
            "max_points_in_flight > 0 to chunk the per-process slice "
            "(run_job's refusal rule — silently ignoring the spill "
            "request would run the in-RAM merge it exists to avoid)"
        )
    # Ingest this process's slice into either captured level arrays
    # (columnar sinks) or local blobs; the egress tail below is shared
    # by both ingest routes. Each phase boundary heartbeats (per-host
    # liveness + uptime gauges, obs.heartbeat): the spread of the
    # multihost_phase_uptime_seconds gauge across processes at one
    # phase IS the straggler gap.
    def _phase(name: str):
        obs.heartbeat(name)
        if heartbeat_deadline_s is not None:
            check_heartbeats(heartbeat_deadline_s)

    _phase("ingest_start")
    cap = _CaptureLevels() if columnar else None
    # Phase regions are spans (heartbeats at their edges carry the
    # ambient traceparent, so a collector on another host can stitch
    # the per-host trees of one job together by trace_id).
    with tracing.span("multihost.ingest",
                      process=int(jax.process_index())):
        if max_points_in_flight:
            # Bounded slice ingest: chunked cascade + host-side merge
            # (auto-spill / explicit spill included) — blobs equal the
            # single-shot slice run by the same linearity the bounded
            # path already guarantees.
            local = _run_job_bounded(slice_source, cap, config,
                                     batch_size, max_points_in_flight,
                                     spill_dir=merge_spill_dir)
        else:
            data = ingest_columns(slice_source.batches(batch_size),
                                  config)
            if data is not None:
                # Cross-host blob merge sums colliding numeric dicts,
                # which is exactly the weighted semantics too (f64 sums
                # are linear across host shards).
                local = _run_loaded(data, config, as_json=True, sink=cap)
            else:
                local = {}
    _phase("ingest_done")
    with tracing.span("multihost.egress",
                      egress="levels-sharded" if columnar else egress):
        if columnar:
            owned = scatter_levels(cap.levels, max_bytes=egress_max_bytes)
            rows = sink.write_levels(owned)
            _phase("egress_done")
            return {"egress": "levels-sharded", "levels": len(owned),
                    "rows": rows}
        if egress == "sharded":
            owned = scatter_blobs(local, max_bytes=egress_max_bytes)
            if sink is not None:
                sink.write(owned.items())
            _phase("egress_done")
            return owned
        blobs = gather_blobs(local, max_bytes=egress_max_bytes)
        if sink is not None and jax.process_index() == 0:
            sink.write(blobs.items())
        _phase("egress_done")
        return blobs
