"""shard_map kernels: data-parallel binning with collective merges.

Communication mapping from the reference (SURVEY.md §2.3):

| reference (Spark)                   | here (XLA collectives)            |
|-------------------------------------|-----------------------------------|
| RDD partitions of `locations`       | points sharded on the data axis   |
| reduceByKey shuffle (heatmap.py:111)| lax.psum of partial rasters       |
| groupByKey shuffle (heatmap.py:112) | lax.psum_scatter (sharded raster) |
|                                     | / all_gather + local re-reduce    |
| external shuffle service            | — (ICI/DCN, no spill)             |

All kernels are pure and shard_map-traced over the mesh from
parallel.mesh; wrap in ``jax.jit`` for the compiled path. On a 2D
(data, tile) mesh the point-parallel kernels shard points over BOTH
axes (collectives run over the flattened axes), and
``bin_points_bandsharded`` uses the tile axis as true tile-space
parallelism: an ``all_to_all`` regroups points so each device only
ever materializes its own raster band — the groupByKey analog for
rasters too big for one device's HBM.

The data-parallel cascade's pyramids live in parallel/gspmd.py; no
cascade path imports this module.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from heatmap_tpu.ops import (
    histogram,
    pyramid as pyramid_ops,
    sparse as sparse_ops,
)
from heatmap_tpu.parallel.gspmd import _ones_like_weights
from heatmap_tpu.parallel.mesh import (
    DATA_AXIS,
    TILE_AXIS,
    _shard_axes,
    shard_map,
)
from heatmap_tpu.tilemath import mercator


def bin_points_replicated(
    latitude,
    longitude,
    window: histogram.Window,
    mesh: Mesh,
    weights=None,
    valid=None,
    proj_dtype=None,
    dtype=None,
    backend: str = "auto",
):
    """Bin sharded points into a window raster, psum-merged -> replicated.

    The direct reduceByKey replacement: every device bins its point
    shard into a full local (H, W) raster, then one ``lax.psum`` over
    ICI merges them. Point arrays must be divisible by the number of
    point shards (see mesh.pad_to_multiple).

    ``backend`` routes the shard-local binning (ops.histogram backends;
    "auto" picks the measured-fastest kernel per window/platform — the
    same 2.2x partitioned-MXU routing single-chip jobs get). Count jobs
    keep the count-only kernels: the unit weights materialized for the
    uniform shard_map specs are NOT passed to the histogram.
    """
    axes, _ = _shard_axes(mesh)
    if dtype is None:
        dtype = jnp.int32 if weights is None else jnp.float32
    counts_only = weights is None
    n = latitude.shape[0]
    w = _ones_like_weights(weights, n, dtype)
    v = jnp.ones((n,), bool) if valid is None else jnp.asarray(valid, bool)

    def local(la, lo, w, v):
        raster = histogram.bin_points_window(
            la, lo, window, weights=None if counts_only else w, valid=v,
            proj_dtype=proj_dtype, dtype=dtype, backend=backend,
        )
        return lax.psum(raster, axes)

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axes), P(axes), P(axes), P(axes)),
        out_specs=P(),
        # pallas_call outputs carry no varying-mesh-axes metadata, so
        # the vma check rejects backend="pallas"/"partitioned" routing;
        # collective placement here is pinned by the mesh equality tests.
        check_vma=False,
    )
    return fn(latitude, longitude, w, v)


def bin_points_rowsharded(
    latitude,
    longitude,
    window: histogram.Window,
    mesh: Mesh,
    weights=None,
    valid=None,
    proj_dtype=None,
    dtype=None,
    backend: str = "auto",
):
    """Bin sharded points into a raster left row-sharded across devices.

    The groupByKey replacement: ``lax.psum_scatter`` merges partial
    rasters AND leaves device i owning row block i — each device holds
    its slice of merged tile space, like a Spark reducer holding its key
    range, but the "shuffle" rides ICI as one fused collective. Global
    result shape (H, W), sharded (H/shards, W) per device;
    window.height must divide by the number of point shards.
    ``backend`` as in bin_points_replicated (shard-local kernel
    routing; count jobs keep the count-only kernels).
    """
    axes, ndev = _shard_axes(mesh)
    if window.height % ndev:
        raise ValueError(f"window height {window.height} not divisible by {ndev}")
    if dtype is None:
        dtype = jnp.int32 if weights is None else jnp.float32
    counts_only = weights is None
    n = latitude.shape[0]
    w = _ones_like_weights(weights, n, dtype)
    v = jnp.ones((n,), bool) if valid is None else jnp.asarray(valid, bool)

    def local(la, lo, w, v):
        raster = histogram.bin_points_window(
            la, lo, window, weights=None if counts_only else w, valid=v,
            proj_dtype=proj_dtype, dtype=dtype, backend=backend,
        )
        return lax.psum_scatter(raster, axes, scatter_dimension=0, tiled=True)

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axes), P(axes), P(axes), P(axes)),
        out_specs=P(axes),
        check_vma=False,  # same pallas-routing rationale as above
    )
    return fn(latitude, longitude, w, v)


def pyramid_rowsharded(raster, levels: int, mesh: Mesh):
    """Pyramid over a row-sharded raster (output of bin_points_rowsharded).

    Levels coarsen locally (vma-checked shard_map) while every device's
    row block stays evenly divisible; the remaining coarse levels run
    as plain jit ops on the by-then-tiny global array, with GSPMD
    choosing their layout. Returns ``levels+1`` rasters: the first
    ``local_levels+1`` row-sharded; for the trailing levels use the
    VALUES, not ``.sharding`` — their placement is the compiler's.
    """
    axes, ndev = _shard_axes(mesh)
    h, w = raster.shape
    block_h = h // ndev
    local_levels = 0
    while local_levels < levels and (block_h >> local_levels) % 2 == 0:
        local_levels += 1
    gather_levels = levels - local_levels

    def body(block):
        outs = [block]
        for _ in range(local_levels):
            block = pyramid_ops.coarsen_raster(block)
            outs.append(block)
        return tuple(outs)

    out_specs = tuple([P(axes)] * (local_levels + 1))
    # vma-checked: every in-shard_map output is genuinely row-sharded.
    # The remaining coarse levels (shard rows no longer divisible by 2)
    # run outside as plain jit ops on the global array — GSPMD gathers
    # the by-then-tiny raster instead of an explicit all_gather.
    fn = shard_map(body, mesh=mesh, in_specs=(P(axes),), out_specs=out_specs)
    outs = list(fn(raster))
    full = outs[-1]
    for _ in range(gather_levels):
        full = pyramid_ops.coarsen_raster(full)
        outs.append(full)
    return outs


def aggregate_keys_sharded(
    keys, mesh: Mesh, weights=None, valid=None, capacity=None, acc_dtype=None,
    local_capacity=None,
):
    """Global reduce-by-key over sharded keys -> replicated uniques/sums.

    Per-device sort+segment-sum (ops/sparse.py), then an ``all_gather``
    of the compact per-device results and a local re-reduce — the
    all-reduce formulation of reduceByKey for sparse keys. ``capacity``
    bounds the merged unique count; ``local_capacity`` the per-device
    stage (default ``min(capacity, n // ndev)``, clamped to the shard
    row count — a shard can never hold more distinct keys than rows).
    Lower it when shards are known to carry few distinct keys: the
    all_gather moves ndev * local_capacity entries, so a tight bound
    directly shrinks the collective.
    """
    axes, ndev = _shard_axes(mesh)
    keys = jnp.asarray(keys)
    n = keys.shape[0]
    capacity = n if capacity is None else capacity
    # Per-device stage: an evenly-distributed shard holds at most
    # n//ndev distinct keys, so sizing it by the global capacity would
    # only inflate the all_gather.
    if local_capacity is None:
        local_capacity = min(capacity, n // ndev)
    # A shard can never hold more distinct keys than its row count, so
    # anything above n//ndev only pads the all_gather for nothing.
    local_capacity = max(1, min(local_capacity, n // ndev))
    if acc_dtype is None:
        acc_dtype = jnp.int32 if weights is None else jnp.float32
    w = _ones_like_weights(weights, n, acc_dtype)
    v = jnp.ones((n,), bool) if valid is None else jnp.asarray(valid, bool)
    sentinel = jnp.iinfo(keys.dtype).max

    def body(k, w, v):
        u, s, local_n = sparse_ops.aggregate_keys(
            k, weights=w, valid=v, capacity=local_capacity, acc_dtype=acc_dtype
        )
        return u, s, local_n[None]

    # The per-device compact partials come back as ordinary sharded
    # global arrays; the merge re-reduce runs OUTSIDE shard_map as
    # plain jit ops (GSPMD inserts the gather for the global sort).
    # Keeping the collective stage vma-checked means a spec regression
    # here fails at trace time instead of producing wrong numbers.
    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axes), P(axes), P(axes)),
        out_specs=(P(axes), P(axes), P(axes)),
    )
    gu, gs, gn = fn(keys, w, v)
    mu, ms, mn = sparse_ops.aggregate_keys(
        gu, weights=gs, valid=gu != sentinel, capacity=capacity,
        acc_dtype=acc_dtype,
    )
    # Keep the documented overflow contract (ops/sparse.py): if ANY
    # device overflowed its local stage, keys were already dropped
    # before the merge and the merged count can look clean — force
    # the returned n_unique past capacity so callers detect it.
    local_overflow = (gn > local_capacity).any()
    mn = jnp.where(local_overflow, jnp.maximum(mn, capacity + 1), mn)
    return mu, ms, mn


def splat_rowsharded(raster, kernel_1d, mesh: Mesh):
    """Gaussian splat over a row-sharded raster via halo exchange.

    The stencil analog of the binning path's collectives: each device
    owns a horizontal band of the raster (as produced by
    bin_points_rowsharded); the vertical convolution needs
    ``len(kernel)//2`` rows from each neighbor, exchanged with two
    ``lax.ppermute`` shifts over ICI (zeros arrive at the global
    edges, matching SAME zero padding). The horizontal pass is purely
    local. Compute stays distributed — no device ever holds the full
    raster.
    """
    axes, ndev = _shard_axes(mesh)
    k = jnp.asarray(kernel_1d)
    if k.ndim != 1 or k.shape[0] % 2 == 0:
        raise ValueError(f"kernel must be 1D with odd length, got shape {k.shape}")
    half = (k.shape[0] - 1) // 2
    h, w = raster.shape
    if h % ndev:
        raise ValueError(f"raster height {h} not divisible by {ndev} devices")
    if half and h // ndev < half:
        raise ValueError(
            f"shard height {h // ndev} smaller than kernel half-width "
            f"{half}: halo exchange needs >= one kernel radius per shard"
        )

    def body(block):
        out_dtype = (
            block.dtype
            if jnp.issubdtype(block.dtype, jnp.floating)
            else k.dtype
        )
        x = block.astype(out_dtype)
        if half == 0:
            padded = x
        else:
            # Halo exchange: my last rows -> next device's top halo; my
            # first rows -> previous device's bottom halo. ppermute
            # yields zeros where no source sends (global edges).
            down = [(i, i + 1) for i in range(ndev - 1)]
            up = [(i, i - 1) for i in range(1, ndev)]
            top_halo = lax.ppermute(x[-half:], axes, down)
            bot_halo = lax.ppermute(x[:half], axes, up)
            padded = jnp.concatenate([top_halo, x, bot_halo], axis=0)
        kd = k.astype(out_dtype)
        # Vertical pass VALID over the halo-padded block, horizontal
        # pass SAME — same math as ops.splat.splat_raster globally.
        y = lax.conv_general_dilated(
            padded[None, None], kd[None, None, :, None], (1, 1),
            [(0, 0), (0, 0)],
        )
        y = lax.conv_general_dilated(
            y, kd[None, None, None, :], (1, 1), [(0, 0), (half, half)]
        )
        return y[0, 0]

    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axes, None),),
        out_specs=P(axes, None),
    )
    return fn(raster)


def bin_points_bandsharded(
    latitude,
    longitude,
    window: histogram.Window,
    mesh: Mesh,
    weights=None,
    valid=None,
    proj_dtype=None,
    dtype=None,
    send_capacity: int | None = None,
    backend: str = "xla",
):
    """Tile-space-parallel binning: no device materializes the raster.

    The true groupByKey analog (SURVEY.md §2.3 spatial parallelism;
    reference heatmap.py:112 hash-partitions tile space across
    reducers): points are sharded over the whole (data, tile) mesh;
    each device projects its shard, an ``lax.all_to_all`` over the
    ``tile`` axis regroups points to the device owning their horizontal
    raster band, and each device bins ONLY its (H/T, W) band —
    per-device raster memory is H*W/T, unlike
    bin_points_rowsharded, whose psum_scatter needs the full local
    (H, W) raster before scattering. Copies across the data axis merge
    with a psum. Returns the (H, W) raster row-sharded over the tile
    axis (replicated over data).

    Returns ``(band_raster, dropped)`` — always a pair, regardless of
    arguments, so the call site's unpacking cannot depend on which
    knobs were passed. ``send_capacity`` bounds the per-destination
    all_to_all buffer (default: the per-device point count, which
    cannot overflow — ``dropped`` is then structurally zero). Smaller
    values save memory but drop points past the capacity; ``dropped``
    is the replicated global count of points lost to the cap — the
    ops/sparse.py overflow contract applied to the collective: callers
    must check ``dropped == 0`` and fail/retry with a larger capacity
    rather than trust a skew assumption (the pattern is pinned by
    tests/test_parallel.py's skewed-band test).

    ``backend`` routes the band binning; unlike the replicated /
    rowsharded kernels it defaults to "xla", not "auto": this function
    needs tile >= 2 — i.e. real multi-chip hardware — so no 1-device
    on-chip gate can verify its pallas routing (docs/DESIGN.md §9
    verification ladder); opt in explicitly once a pod run verifies it.
    """
    T = mesh.shape[TILE_AXIS]
    D = mesh.shape[DATA_AXIS]
    if T < 2:
        raise ValueError(
            "bin_points_bandsharded needs a tile axis >= 2 "
            "(use bin_points_replicated/rowsharded on a data-only mesh)"
        )
    if window.height % T:
        raise ValueError(f"window height {window.height} not divisible by tile={T}")
    band_h = window.height // T
    if dtype is None:
        dtype = jnp.int32 if weights is None else jnp.float32
    n = latitude.shape[0]
    if n % (D * T):
        raise ValueError(f"{n} points not divisible by {D * T} devices")
    n_local = n // (D * T)
    cap = n_local if send_capacity is None else min(send_capacity, n_local)
    band_window = histogram.Window(
        zoom=window.zoom, row0=0, col0=0, height=band_h, width=window.width
    )

    counts_only = weights is None
    w = _ones_like_weights(weights, n, dtype)
    v = jnp.ones((n,), bool) if valid is None else jnp.asarray(valid, bool)

    def local(la, lo, w, v):
        row, col, pvalid = mercator.project_points(
            la, lo, window.zoom, dtype=proj_dtype
        )
        r = jnp.asarray(row, jnp.int32) - window.row0
        c = jnp.asarray(col, jnp.int32) - window.col0
        ok = (
            pvalid & v
            & (r >= 0) & (r < window.height)
            & (c >= 0) & (c < window.width)
        )
        dest = jnp.where(ok, r // band_h, T).astype(jnp.int32)
        # Sort by destination band so each band's points are contiguous
        # (invalid points sort last under sentinel T), then scatter
        # whole runs into fixed (T, cap) send buffers.
        order = jnp.argsort(dest)
        sd = dest[order]
        m = sd.shape[0]
        bounds = jnp.searchsorted(sd, jnp.arange(T + 1, dtype=sd.dtype))
        starts = bounds[:T]
        # Points past a destination's capacity fall out of the send
        # buffer (mode="drop" below); count them so the loss is LOUD —
        # psum'd across the whole mesh and returned to the caller.
        per_dest = bounds[1:] - bounds[:T]
        local_dropped = jnp.maximum(per_dest - cap, 0).sum().astype(jnp.int32)
        slot = jnp.arange(m, dtype=jnp.int32) - starts[jnp.clip(sd, 0, T - 1)]
        send_r = jnp.full((T, cap), -1, jnp.int32).at[sd, slot].set(
            r[order], mode="drop"
        )
        send_c = jnp.zeros((T, cap), jnp.int32).at[sd, slot].set(
            c[order], mode="drop"
        )
        send_w = jnp.zeros((T, cap), dtype).at[sd, slot].set(
            w[order], mode="drop"
        )
        # The regroup "shuffle": row t of the send buffer goes to tile
        # position t; row j of the result came from tile position j.
        recv_r = lax.all_to_all(send_r, TILE_AXIS, 0, 0, tiled=True)
        recv_c = lax.all_to_all(send_c, TILE_AXIS, 0, 0, tiled=True)
        recv_w = lax.all_to_all(send_w, TILE_AXIS, 0, 0, tiled=True)
        t_idx = lax.axis_index(TILE_AXIS)
        rloc = recv_r.reshape(-1) - t_idx * band_h
        # Count jobs drop the regrouped unit weights (fill lanes carry
        # r=-1 and are masked by `valid` alone), keeping the band bin
        # on the count-only kernels under backend="auto".
        band = histogram.bin_rowcol_window(
            rloc,
            recv_c.reshape(-1),
            band_window,
            weights=None if counts_only else recv_w.reshape(-1),
            valid=recv_r.reshape(-1) >= 0,
            dtype=dtype,
            backend=backend,
        )
        # Different data-axis rows hold disjoint point shards of the
        # same band: merge, leaving the band replicated over data.
        merged = lax.psum(band, DATA_AXIS)
        dropped = lax.psum(local_dropped, (DATA_AXIS, TILE_AXIS))
        return merged, dropped

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P((DATA_AXIS, TILE_AXIS)),
            P((DATA_AXIS, TILE_AXIS)),
            P((DATA_AXIS, TILE_AXIS)),
            P((DATA_AXIS, TILE_AXIS)),
        ),
        out_specs=(P(TILE_AXIS, None), P()),
        check_vma=False,
    )
    return fn(latitude, longitude, w, v)
