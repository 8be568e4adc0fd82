"""Device-resident GSPMD cascade: global-view NamedSharding programs.

The data-parallel cascade's two pyramids — uniform data-parallel and
Morton-range partitioned — as programs that take the emission stream
spread over the mesh (:func:`point_sharding`), so the WHOLE cascade —
emission routing, range-local rollup, boundary merge, and canonical
egress ordering — is one compiled program with no host round-trips
between stages:

- the uniform program is global view: every per-shard stage is a
  ``vmap`` over a leading ``(n_shards,)`` axis pinned to the mesh's
  point axes, reducing contiguous 1/n_shards slices; the merge and
  rollup run over the flattened compact partials;
- the range program routes on-device against a TRACED splits array:
  each lane goes to the shard that owns its detail code through one
  ``all_to_all`` whose static widths come from the plan's exact lane
  counts (partition.route_counts), so each chip holds and scans only
  its own lanes. Traced splits are also what let ``adaptive_capacity``
  compose with Morton partitioning. Its boundary merge and canonical
  egress run in the same shard_map body, and its only sort is the
  detail stage's own.

Byte identity with the single-device cascade is the contract (pinned
by tests/test_gspmd.py and the chaos ``dispatch`` phase): counts and
bounded-integer weighted sums are exact in any summation order.

Donation: `donating_jit` adds ``donate_argnums`` where the platform
supports in-place donation (TPU/GPU) and drops it where it does not
(CPU), while a platform-independent :class:`DonationLedger` makes
re-use of a donated buffer a typed :class:`DonatedBufferError` on
every backend — the classic pjit footgun caught at the API boundary
rather than as a backend-specific crash.
"""

from __future__ import annotations

import weakref

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh

from heatmap_tpu.ops import (
    pyramid as pyramid_ops,
    sparse as sparse_ops,
    sparse_partitioned,
)
from heatmap_tpu.parallel.mesh import (
    DATA_AXIS,
    TILE_AXIS,
    _shard_axes,
    named_sharding,
    shard_map,
)

__all__ = [
    "DonatedBufferError",
    "DonationLedger",
    "donating_jit",
    "donation_supported",
    "ledger",
    "pyramid_gspmd_range",
    "pyramid_gspmd_uniform",
    "patch_boundary",
    "place_canonical",
    "point_sharding",
    "route_on_device",
]


# ---------------------------------------------------------------------------
# sharding helpers


def _point_spec(mesh: Mesh):
    """The PartitionSpec leading-axis entry the point-parallel programs
    shard their ``(n_shards, ...)`` layout over — the NamedSharding
    analog of mesh._shard_axes (tile==1 keeps the single data axis,
    else the leading axis flattens over both)."""
    axes, ndev = _shard_axes(mesh)
    return (axes[0] if len(axes) == 1 else tuple(axes)), ndev


def point_sharding(mesh: Mesh):
    """The input layout of both programs: a point stream spread over
    the mesh's point axes in contiguous per-chip blocks."""
    spec, _ = _point_spec(mesh)
    return named_sharding(mesh, spec)


def _ones_like_weights(weights, n, dtype):
    return jnp.ones((n,), dtype) if weights is None else jnp.asarray(weights, dtype)


def _local_detail_stage(backend, counts_only, local_capacity, acc_dtype,
                        sentinel, weight_bound=None):
    """The per-shard reduce-by-key of the uniform program's detail
    level: "scatter" (ops/sparse.py sort + segment reduce) or
    "partitioned" (sort + the multi-channel MXU segment kernel,
    ops/sparse_partitioned.py). Both return the same compact
    (unique[cap], sums[cap], n_unique) contract — sorted uniques,
    sentinel/zero padding, n_unique past capacity on overflow — so the
    merge and rollup are backend-agnostic and results stay
    bit-identical (counts and bounded-integer weighted sums are exact
    in any summation order)."""
    if backend == "scatter":
        def stage(k, w, v):
            return sparse_ops.aggregate_keys(
                k, weights=w, valid=v, capacity=local_capacity,
                acc_dtype=acc_dtype,
            )
        return stage
    if backend != "partitioned":
        raise ValueError(f"unknown cascade backend {backend!r}")

    def stage(k, w, v):
        masked = jnp.where(v, k, sentinel)
        if counts_only:
            # Unstable sort: equal keys are indistinguishable payloads.
            u, s, n = sparse_partitioned.aggregate_sorted_keys_partitioned(
                jnp.sort(masked), local_capacity, sentinel=sentinel,
            )
        else:
            order = jnp.argsort(masked, stable=True)
            u, s, n = sparse_partitioned.aggregate_sorted_keys_partitioned(
                masked[order], local_capacity, sentinel=sentinel,
                sorted_weights=w[order], weight_bound=weight_bound,
            )
        # The kernel upcasts keys (and its sentinel pad) to int64; the
        # stage contract is scatter's — uniques in the INPUT dtype.
        # The merged rollup derives its pad sentinel from the array
        # dtype, so an int64 partial from int32 keys would make it
        # treat int64-max pad lanes as real keys (they no longer equal
        # the int32-max sentinel). Real keys and the sentinel both fit
        # the input dtype by construction, so the cast is lossless.
        return u.astype(k.dtype), s.astype(acc_dtype), n

    return stage


def _mapped_stage(stage, mesh: Mesh, spec, backend: str):
    """Map the per-shard detail stage over the leading shard axis.

    The scatter stage is plain gather/segment arithmetic — ``vmap``
    keeps it global-view and the SPMD partitioner places each row on
    its owning device. The partitioned stage wraps a pallas_call, and
    vmapping a pallas_call whose scalar-prefetch operands are batched
    falls back to jax's sequential batch loop; the partitioner then
    threads every grid step's dynamic slices through cross-device
    collectives (and, under x64, trips an s64-vs-s32 HLO verifier
    error against its own s32 shard offsets — the CHANGES.md line 19
    failure). Run that stage under shard_map instead: the body is
    device-local by construction, so the kernel never meets the
    batching fallback or the partitioner. Same stage function, same
    per-shard blocks, byte-identical outputs.
    """
    if backend != "partitioned":
        return jax.vmap(stage)
    from jax.sharding import PartitionSpec as P

    row = P(spec, None)

    def body(k, w, v):
        u, s, n = stage(k[0], w[0], v[0])
        return u[None], s[None], jnp.asarray(n)[None]

    return shard_map(body, mesh, in_specs=(row, row, row),
                     out_specs=(row, row, P(spec)), check_vma=False)


def _constrain(x, mesh: Mesh, *spec):
    """``with_sharding_constraint`` under trace, ``device_put`` eagerly.

    The gspmd programs run both jitted (the production path — the
    constraint tells the SPMD partitioner where each stage lives) and
    eagerly (stage tracing, adaptive_capacity reads concrete counts);
    eager jax rejects bare sharding constraints, so commit the array
    instead — same placement, same values.
    """
    sharding = named_sharding(mesh, *spec)
    if isinstance(x, jax.core.Tracer):
        return lax.with_sharding_constraint(x, sharding)
    return jax.device_put(x, sharding)


# ---------------------------------------------------------------------------
# on-device routing


def route_on_device(keys, valid, splits, weights=None, *, code_bits: int,
                    axes, n_shards: int, pair_capacity: int,
                    recv_capacity: int):
    """One chip's side of the owner-routed exchange, inside a shard_map
    body over the point ``axes``.

    Each local lane goes to the shard that owns its detail code (the
    low ``code_bits`` bits of its key): ``#{splits <= code}`` against
    the traced splits, the planner's convention
    (partition.PartitionPlan.shard_of_codes). The chip packs the
    lanes bound for shard ``d`` into row ``d`` of a fixed
    ``pair_capacity``-wide send buffer, in lane order (a running count
    per owner places each lane: no sort), and one ``all_to_all``
    delivers row ``d`` to shard ``d``. The received rows are packed
    back to back into ``recv_capacity`` lanes. Both widths are static
    and come from the plan's exact lane counts
    (partition.route_counts), so no lane is dropped; if a plan
    understates them anyway, the returned overflow flag is True and the
    caller's loud-overflow contract reports it.

    Returns ``(keys, weights, overflow)``: the lanes this shard owns, in
    global lane order (source chip, then lane), padded with sentinel
    keys (the key dtype's max) and zero weights. ``weights`` is None
    for a count job, which ships keys alone.
    """
    sentinel = jnp.iinfo(keys.dtype).max
    code = keys & ((1 << code_bits) - 1)
    owner = jnp.searchsorted(splits.astype(keys.dtype), code, side="right")
    dest = jnp.where(valid, owner, n_shards)
    rank = jnp.zeros(dest.shape, jnp.int32)
    sizes = []
    for d in range(n_shards):
        mine = dest == d
        seen = jnp.cumsum(mine, dtype=jnp.int32)
        rank = jnp.where(mine, seen - 1, rank)
        sizes.append(seen[-1])
    sizes = jnp.stack(sizes)
    fits = (dest < n_shards) & (rank < pair_capacity)
    at = jnp.where(fits, dest * pair_capacity + rank,
                   n_shards * pair_capacity)
    cols = [(keys, sentinel)] + ([] if weights is None else [(weights, 0)])
    recv_sizes = lax.all_to_all(sizes, axes, 0, 0)
    offsets = jnp.cumsum(recv_sizes) - recv_sizes
    packed = []
    for col, pad in cols:
        send = jnp.full((n_shards * pair_capacity,), pad, col.dtype).at[
            at].set(col, mode="drop", unique_indices=True)
        rows = lax.all_to_all(send.reshape(n_shards, pair_capacity),
                              axes, 0, 0)
        # Row s lands after rows 0..s-1; its padded tail is overwritten
        # by row s+1, and the last tail stays past the end.
        buf = jnp.full((recv_capacity + pair_capacity,), pad, col.dtype)
        for src in range(n_shards):
            buf = lax.dynamic_update_slice(buf, rows[src], (offsets[src],))
        packed.append(buf[:recv_capacity])
    overflow = (jnp.any(sizes > pair_capacity)
                | (jnp.sum(recv_sizes) > recv_capacity))
    return packed[0], (packed[1] if weights is not None else None), overflow


def _edge_codes(splits, me, lvl: int, n_shards: int, code_space: int):
    """The level-``lvl`` tiles that hold this shard's two range ends,
    where a split is not tile-aligned there: the only tiles another
    shard can also hold. Returns ``(codes (2,), valid (2,))``."""
    if n_shards == 1:  # one range: no split, no shared tile
        return jnp.zeros((2,), splits.dtype), jnp.zeros((2,), bool)
    lo = jnp.where(me > 0, splits[jnp.maximum(me - 1, 0)], 0)
    hi = jnp.where(me < n_shards - 1,
                   splits[jnp.minimum(me, n_shards - 2)], code_space)
    blk = (1 << (2 * lvl)) - 1
    lo_ok = (me > 0) & ((lo & blk) != 0)
    hi_ok = (me < n_shards - 1) & ((hi & blk) != 0)
    codes = jnp.stack([lo >> (2 * lvl), hi >> (2 * lvl)])
    # A range inside one straddling tile has one edge tile, not two.
    hi_ok = hi_ok & ~(lo_ok & (codes[1] == codes[0]))
    return codes, jnp.stack([lo_ok, hi_ok])


def patch_boundary(u, s, splits, lvl: int, *, code_bits: int,
                   slot_bound: int, axes, me, n_shards: int):
    """Merge one level's boundary tiles across range shards, inside a
    shard_map body over ``axes``.

    ``u``/``s`` are this shard's sorted level-``lvl`` keys and sums
    (sentinel-padded). A tile can have partials on several shards only
    if it straddles a split, and then it holds an end of each such
    shard's range (:func:`_edge_codes`); so each shard looks up its
    ``2 * slot_bound`` candidate keys (each slot at each edge tile),
    and every shard gathers all candidates. The FIRST holder (lowest
    shard holding the key) takes the cross-shard total; the others drop
    their row. Totals are plain sums of the holders' partials, exact in
    any order for counts and bounded-integer weighted sums.

    Returns ``(sums, kept)``: sums with the totals written in, and the
    rows this shard keeps (real and not dropped). Keys stay in place,
    so ``u`` stays sorted.
    """
    sentinel = jnp.iinfo(u.dtype).max
    shift = code_bits - 2 * lvl
    codes, ok = _edge_codes(splits.astype(u.dtype), me, lvl, n_shards,
                            1 << code_bits)
    slot = jnp.arange(slot_bound, dtype=u.dtype)
    cand = (slot[:, None] << shift) | codes[None, :]      # (slots, 2)
    pos = jnp.clip(jnp.searchsorted(u, cand.reshape(-1)), 0,
                   u.shape[0] - 1).reshape(cand.shape)
    hit = ok[None, :] & (u[pos] == cand)
    val = jnp.where(hit, s[pos], jnp.zeros((), s.dtype))
    g_key = lax.all_gather(jnp.where(hit, cand, sentinel), axes)
    g_val = lax.all_gather(val, axes)                     # (ndev, slots, 2)
    # held[k, i, j]: shard k holds my candidate (i, j), under either of
    # its own edge tiles.
    match = g_key[:, :, None, :] == cand[None, :, :, None]
    held = jnp.any(match, axis=3) & hit[None]
    total = jnp.sum(jnp.where(match, g_val[:, :, None, :],
                              jnp.zeros((), s.dtype)), axis=(0, 3),
                    dtype=s.dtype)
    first = jnp.argmax(held, axis=0) == me
    at = lambda m: jnp.where(m, pos, u.shape[0]).reshape(-1)  # noqa: E731
    s = s.at[at(hit & first)].set(total.reshape(-1), mode="drop")
    kept = (u != sentinel).at[at(hit & ~first)].set(False, mode="drop")
    return s, kept


def place_canonical(u, s, kept, *, shift: int, slot_bound: int, cap: int,
                    axes, me, n_shards: int):
    """One level's canonical egress, inside a shard_map body: this
    shard's slice of the level's ``(keys, sums)`` in sorted order.

    Shards own disjoint code ranges in shard order, so within one slot
    every row of shard ``k`` sorts before every row of shard ``k + 1``:
    a kept row's global rank is the rows of earlier slots, plus the
    rows of its slot on earlier shards, plus its rank within its slot
    here. Each shard scatters its rows to their ranks in a
    ``cap``-wide buffer (``cap`` a multiple of the shard count), one
    ``all_to_all`` brings every shard the pieces of its own slice, and
    since each rank has one writer, their sum is the slice. No sort.

    Returns ``(keys, sums, n)``: the slice, sentinel past the level's
    ``n`` real rows, and ``n`` itself.
    """
    sentinel = jnp.iinfo(u.dtype).max
    before = jnp.cumsum(kept, dtype=jnp.int32) - kept
    before = jnp.concatenate([before, jnp.sum(kept, dtype=jnp.int32)[None]])
    bounds = jnp.arange(slot_bound + 1, dtype=u.dtype) << shift
    starts = jnp.searchsorted(u, bounds)                  # (slots + 1,)
    count = before[starts[1:]] - before[starts[:-1]]      # (slots,)
    g_count = lax.all_gather(count, axes)                 # (ndev, slots)
    per_slot = jnp.sum(g_count, axis=0)
    earlier = jnp.sum(jnp.where(jnp.arange(n_shards)[:, None] < me,
                                g_count, 0), axis=0)
    offset = jnp.cumsum(per_slot) - per_slot + earlier
    slot = jnp.clip(u >> shift, 0, slot_bound - 1)
    rank = offset[slot] + before[:-1] - before[starts[slot]]
    at = jnp.where(kept & (rank < cap), rank, cap)
    chunk = cap // n_shards
    out = []
    for col in (u, s):
        buf = jnp.zeros((cap,), col.dtype).at[at].set(
            jnp.where(kept, col, jnp.zeros((), col.dtype)), mode="drop",
            unique_indices=True)
        pieces = lax.all_to_all(buf.reshape(n_shards, chunk), axes, 0, 0)
        out.append(jnp.sum(pieces, axis=0, dtype=col.dtype))
    n = jnp.sum(per_slot)
    real = me * chunk + jnp.arange(chunk) < n
    return jnp.where(real, out[0], sentinel), out[1], n


# ---------------------------------------------------------------------------
# uniform data-parallel program


def pyramid_gspmd_uniform(
    codes,
    mesh: Mesh,
    weights=None,
    valid=None,
    levels: int = 0,
    capacity=None,
    acc_dtype=None,
    backend: str = "scatter",
    weight_bound: int | None = None,
    adaptive: bool = False,
):
    """Global-view uniform-DP sparse pyramid: counts and
    bounded-integer weighted sums byte-identical to the single-device
    ``ops.pyramid.pyramid_sparse_morton``.

    Each shard reduces its contiguous 1/n_shards slice at the detail
    zoom (at most ``min(caps[0], n // n_shards)`` uniques), then the
    merge + rollup run over the flattened compact partials. The shard
    axis is an explicit leading dimension constrained to the mesh's
    point axes, so the whole pyramid is one partitionable program (jit
    it together with projection and egress).

    ``adaptive`` (EAGER callers only, like ops.pyramid) forwards to the
    merged rollup: deep levels shrink to the real unique counts. The
    rollup runs on the global view, so the composition is free — and
    result-neutral, the dropped slots are sentinel padding.
    """
    spec, ndev = _point_spec(mesh)
    codes = jnp.asarray(codes)
    n = codes.shape[0]
    if n % ndev != 0:
        raise ValueError(
            f"gspmd uniform cascade needs n % n_shards == 0, got "
            f"{n} % {ndev} (pad with mesh.pad_to_multiple)")
    caps = pyramid_ops._level_caps(capacity, n, levels)
    local_capacity = max(1, min(caps[0], n // ndev))
    if acc_dtype is None:
        acc_dtype = jnp.int32 if weights is None else jnp.float32
    counts_only = weights is None
    w = _ones_like_weights(weights, n, acc_dtype)
    v = jnp.ones((n,), bool) if valid is None else jnp.asarray(valid, bool)
    sentinel = jnp.iinfo(codes.dtype).max
    stage = _local_detail_stage(backend, counts_only, local_capacity,
                                acc_dtype, sentinel,
                                weight_bound=weight_bound)

    shard = n // ndev
    ck = _constrain(codes.reshape(ndev, shard), mesh, spec, None)
    cw = _constrain(w.reshape(ndev, shard), mesh, spec, None)
    cv = _constrain(v.reshape(ndev, shard), mesh, spec, None)
    u, s, ln = _mapped_stage(stage, mesh, spec, backend)(ck, cw, cv)
    u = _constrain(u, mesh, spec, None)
    s = _constrain(s, mesh, spec, None)
    gu, gs = u.reshape(-1), s.reshape(-1)
    out = pyramid_ops.pyramid_sparse_morton(
        gu,
        weights=gs,
        valid=gu != sentinel,
        levels=levels,
        capacity=caps,
        acc_dtype=acc_dtype,
        adaptive=adaptive,
    )
    local_overflow = (ln > local_capacity).any()
    return [
        (
            lu,
            ls,
            jnp.where(local_overflow, jnp.maximum(lnn, caps[lvl] + 1), lnn),
        )
        for lvl, (lu, ls, lnn) in enumerate(out)
    ]


# ---------------------------------------------------------------------------
# Morton-range partitioned program (owner-routed)


def pyramid_gspmd_range(
    keys,
    mesh: Mesh,
    splits,
    *,
    code_bits: int,
    slot_bound: int,
    pair_capacity: int,
    recv_capacity: int,
    weights=None,
    valid=None,
    levels: int = 0,
    capacity=None,
    acc_dtype=None,
    backend: str = "scatter",
    weight_bound: int | None = None,
    adaptive: bool = False,
):
    """Range-partitioned sparse pyramid with ON-DEVICE owner routing.

    Input is UNROUTED: the emission stream sharded over the point axes
    in contiguous blocks (``n`` a multiple of the shard count), plus
    the traced ``(n_shards - 1,)`` split codes. One shard_map body per
    chip then runs, with no sort but the detail stage's own:

    - ``mesh.route``: :func:`route_on_device` sends every lane to the
      shard that owns its detail code (one ``all_to_all``); a shard then
      holds only its own lanes, at most ``recv_capacity`` of them;
    - ``mesh.detail``: the single-device pyramid of ``backend`` on those
      lanes: every level's local partials, from the partitioned kernel
      (each level reduced from the owned stream) or the scatter rollup;
    - ``mesh.boundary``: per coarse level, :func:`patch_boundary`. Each
      level is patched on its own: a shard's level-``l`` partial of a
      tile sums that shard's lanes under it, so the holders' partials
      add up to the tile's total whatever the level below holds.
      Routing is by detail code, so the detail level has no boundary;
    - ``mesh.egress``: :func:`place_canonical` puts every level in the
      canonical sorted order, each chip holding a slice.

    Counts and bounded-integer weighted sums come out byte-identical to
    the single-device cascade. ``pair_capacity`` and ``recv_capacity``
    are static widths from the plan's exact counts
    (partition.route_counts). The loud-overflow contract holds: lanes
    past either width, or a level past its capacity, force every
    level's count past capacity.

    ``adaptive`` (EAGER callers only) shrinks each returned level to the
    next power of two above its real count, as the single-device
    adaptive pyramid does; the shard-local work keeps static widths.
    """
    from jax.sharding import PartitionSpec as P

    spec, ndev = _point_spec(mesh)
    axes, _ = _shard_axes(mesh)
    keys = jnp.asarray(keys)
    n = keys.shape[0]
    if n % ndev != 0:
        raise ValueError(
            f"gspmd range cascade needs n % n_shards == 0, got "
            f"{n} % {ndev} (pad with valid=False lanes)")
    splits = jnp.asarray(splits)
    if splits.shape != (ndev - 1,):
        raise ValueError(
            f"need {ndev - 1} split codes for {ndev} shards, got "
            f"shape {splits.shape}")
    caps = pyramid_ops._level_caps(capacity, n, levels)
    # A shard's uniques at any level are at most its owned lanes.
    lcaps = [max(1, min(caps[lvl], recv_capacity))
             for lvl in range(levels + 1)]
    pcaps = [-(-cap // ndev) * ndev for cap in caps]
    if acc_dtype is None:
        acc_dtype = jnp.int32 if weights is None else jnp.float32
    counts_only = weights is None
    v = jnp.ones((n,), bool) if valid is None else jnp.asarray(valid, bool)
    sentinel = jnp.iinfo(keys.dtype).max

    def local_pyramid(k, w):
        real = k != sentinel
        if backend == "partitioned":
            return pyramid_ops.pyramid_sparse_morton_partitioned(
                k, valid=real, levels=levels, capacity=lcaps, weights=w,
                weight_bound=weight_bound)
        return pyramid_ops.pyramid_sparse_morton(
            k, weights=w, valid=real, levels=levels, capacity=lcaps,
            acc_dtype=acc_dtype)

    def body(k, v, spl, *w):
        me = lax.axis_index(axes[0])
        for ax in axes[1:]:
            me = me * mesh.shape[ax] + lax.axis_index(ax)
        with jax.named_scope("mesh.route"):
            rk, rw, over = route_on_device(
                k, v, spl, w[0] if w else None, code_bits=code_bits,
                axes=axes, n_shards=ndev, pair_capacity=pair_capacity,
                recv_capacity=recv_capacity)
        with jax.named_scope("mesh.detail"):
            per_level = local_pyramid(rk, rw)
        outs = []
        for lvl, (u, s, ln) in enumerate(per_level):
            s = s.astype(acc_dtype)
            over = over | (ln > lcaps[lvl])
            if lvl:
                with jax.named_scope("mesh.boundary"):
                    s, kept = patch_boundary(
                        u, s, spl, lvl, code_bits=code_bits,
                        slot_bound=slot_bound, axes=axes, me=me,
                        n_shards=ndev)
            else:
                kept = u != sentinel
            with jax.named_scope("mesh.egress"):
                outs.extend(place_canonical(
                    u, s, kept, shift=code_bits - 2 * lvl,
                    slot_bound=slot_bound, cap=pcaps[lvl], axes=axes,
                    me=me, n_shards=ndev))
            outs[-1] = outs[-1][None]
        any_over = lax.psum(over.astype(jnp.int32), axes) > 0
        return (*outs, any_over[None])

    row = P(spec)
    args = (keys, v, splits) + (
        () if counts_only else (jnp.asarray(weights, acc_dtype),))
    # jit: one program eagerly too (adaptive callers), not one dispatch
    # per operation of the body.
    fn = jax.jit(shard_map(
        body, mesh,
        in_specs=(row, row, P()) + (() if counts_only else (row,)),
        out_specs=(row,) * (3 * (levels + 1) + 1),
        check_vma=False))
    parts = fn(*args)
    any_over = parts[-1][0]
    out = []
    for lvl, cap in enumerate(caps):
        su, ss, ln = parts[3 * lvl][:cap], parts[3 * lvl + 1][:cap], \
            parts[3 * lvl + 2][0]
        if adaptive:
            # Eager: the counts are concrete. Rows are sorted with
            # sentinels last, so the dropped tail is padding; an
            # overflowed level keeps its width.
            n_real = int(ln)
            keep = max(64, 1 << max(0, n_real - 1).bit_length())
            if n_real <= su.shape[0] and keep < su.shape[0]:
                su, ss = su[:keep], ss[:keep]
        out.append((su, ss, jnp.where(any_over,
                                      jnp.maximum(ln, su.shape[0] + 1), ln)))
    return out


# ---------------------------------------------------------------------------
# donation


def donation_supported(platform: str | None = None) -> bool:
    """True where XLA honors ``donate_argnums`` (TPU/GPU; CPU emits a
    "donated buffers were not usable" warning and copies instead)."""
    platform = platform or jax.default_backend()
    return platform in ("tpu", "gpu", "cuda", "rocm")


class DonatedBufferError(ValueError):
    """A buffer donated to a previous dispatch was passed again.

    On TPU/GPU the donated buffer's memory was reused in place, so a
    second read is undefined; on CPU donation is a no-op and the read
    would silently "work" — the ledger raises on every platform so the
    bug cannot hide behind the backend.
    """


class DonationLedger:
    """Tracks buffers consumed by donating dispatches, by identity.

    Entries are weak so the ledger never extends a donated buffer's
    lifetime (which would defeat donation); a collected buffer cannot
    be re-passed, so dropping its entry is safe.
    """

    def __init__(self):
        self._spent: dict[int, object] = {}

    def mark(self, *arrays) -> None:
        for a in arrays:
            if a is None or not isinstance(a, jax.Array):
                continue
            key = id(a)
            try:
                self._spent[key] = weakref.ref(
                    a, lambda _r, k=key: self._spent.pop(k, None))
            except TypeError:  # pragma: no cover - non-weakrefable array
                self._spent[key] = None

    def check(self, *arrays) -> None:
        for a in arrays:
            if a is not None and id(a) in self._spent:
                raise DonatedBufferError(
                    "buffer was donated to a previous cascade dispatch "
                    "and may have been overwritten in place; re-feed the "
                    "batch (pipeline/feeder.py) instead of re-passing it")

    def clear(self) -> None:
        self._spent.clear()


#: Process-wide ledger for the cascade dispatch path.
ledger = DonationLedger()


def donating_jit(fn, *, donate_argnums=(), donate_argnames=(),
                 static_argnames=(), ledger=None):
    """``jax.jit`` with donation where supported, ledger-guarded always.

    Returns a callable with the jitted function's signature plus two
    attributes: ``donation_active`` (whether donation was actually
    passed to jit on this platform) and ``ledger``. Donated arguments
    (positional via ``donate_argnums``, keyword via ``donate_argnames``)
    are checked against the ledger before dispatch and marked consumed
    after — so re-using a donated buffer raises
    :class:`DonatedBufferError` on CPU exactly as it would corrupt on
    TPU, and the byte-identity tests can run the same assertions on
    both.
    """
    active = donation_supported() and bool(donate_argnums
                                           or donate_argnames)
    jfn = jax.jit(fn, static_argnames=static_argnames,
                  donate_argnums=donate_argnums if active else (),
                  donate_argnames=donate_argnames if active else ())
    led = ledger if ledger is not None else globals()["ledger"]
    donate_argnums = tuple(donate_argnums)
    donate_argnames = tuple(donate_argnames)

    def call(*args, **kwargs):
        donated = [args[i] for i in donate_argnums if i < len(args)]
        donated += [kwargs[k] for k in donate_argnames if k in kwargs]
        led.check(*donated)
        out = jfn(*args, **kwargs)
        led.mark(*donated)
        return out

    call.donation_active = active
    call.ledger = led
    call.__wrapped__ = jfn
    return call
