"""Zoom-pyramid rollups: reshape-sums (dense) and Morton shifts (sparse).

``pyramid_sparse_morton`` reduces each level from the previous one by a
segmented scan and a compaction (ops/sparse.py, the "scatter" backend);
``pyramid_sparse_morton_partitioned`` is the count-only MXU
reformulation (ops/sparse_partitioned.py) that reduces EVERY level
from the original sorted point stream under ``key >> 2i`` — unit
weights at every level, which is what keeps the slab-wise f32
accumulation exact (re-aggregating a previous level's counts as
weights would overflow the f32 slab bound).

The reference coarsens one zoom per Spark stage by round-tripping every
aggregate through inverse+forward projection (reference heatmap.py:60-61,
109-117) — 15 redundant trig passes and 32 shuffles. With integer tile
keys the parent relation is a bit shift (tilemath/keys.py), so:

- dense: a full pyramid from a window raster is a chain of 2x2
  reshape+sums, entirely on-device, zero trig;
- sparse: Morton codes sorted once at detail zoom stay sorted under the
  ``>> 2`` parent shift, so every coarser level is a plain segment-sum
  over the already-sorted order (ops/sparse.py).

Equivalence to the reference's center-re-projection is property-tested
in tests/test_keys.py::test_parent_equals_reference_center_reprojection.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from heatmap_tpu.ops import sparse as sparse_ops


def coarsen_raster(raster):
    """Sum 2x2 blocks: (..., H, W) -> (..., H//2, W//2).

    Requires even H, W (Window.aligned_to guarantees this for aligned
    windows).
    """
    *batch, h, w = raster.shape
    if h % 2 or w % 2:
        raise ValueError(f"raster {raster.shape} not 2x2-coarsenable")
    r = raster.reshape(*batch, h // 2, 2, w // 2, 2)
    return r.sum(axis=(-3, -1))


def pyramid_from_raster(raster, levels: int):
    """Full rollup: returns [raster, coarsen(raster), ...] — levels+1 entries.

    The i-th entry is the detail raster coarsened i zooms; with an
    aligned Window the entry at level i covers rows
    [row0>>i, (row0+H)>>i) of the global grid at zoom-i.
    """
    out = [raster]
    for _ in range(levels):
        raster = coarsen_raster(raster)
        out.append(raster)
    return out


def _level_caps(capacity, n: int, levels: int) -> list:
    """Normalize the per-level capacity spec (int / None / list)."""
    caps = (
        [capacity or n] * (levels + 1)
        if capacity is None or isinstance(capacity, int)
        else list(capacity)
    )
    if len(caps) != levels + 1:
        raise ValueError(f"need {levels + 1} capacities, got {len(caps)}")
    return caps


def pyramid_sparse_morton(
    codes,
    weights=None,
    valid=None,
    levels: int = 0,
    capacity=None,
    acc_dtype=None,
    adaptive: bool = False,
):
    """Sparse pyramid: per-level (unique Morton codes, sums) from point codes.

    Sorts once at detail zoom, then re-reduces the shifted (still
    sorted) codes per level. Levels beyond the first operate on the
    previous level's unique codes (capacity-sized), not the raw points,
    so total work is O(N log N + N + levels * capacity).

    Returns a list of (codes[capacity_i], sums[capacity_i], n_unique),
    entry 0 at detail zoom, entry i coarsened by i zooms.
    ``capacity`` may be an int (same for all levels) or a per-level list.

    ``adaptive=True`` (EAGER callers only — it reads each level's true
    unique count from the device) shrinks every subsequent level's
    arrays to the next power of two above the previous level's unique
    count. Each level's reduction is a scan and a compaction over its
    input width, so on collapsing data this turns ``levels * capacity``
    lanes of work into ~``2-3 * n_unique_0`` — results are identical
    (the dropped slots are sentinel padding).
    Under jit the counts are tracers and this flag must stay False.
    """
    codes = jnp.asarray(codes)
    n = codes.shape[0]
    caps = _level_caps(capacity, n, levels)

    out = []
    with jax.named_scope("cascade.level0"):
        uniq, sums, count = sparse_ops.aggregate_keys(
            codes, weights=weights, valid=valid, capacity=caps[0],
            acc_dtype=acc_dtype,
        )
    out.append((uniq, sums, count))
    sentinel = jnp.iinfo(codes.dtype).max
    for lvl in range(1, levels + 1):
        if adaptive:
            # One scalar sync per level; slots past n_unique are pure
            # sentinel padding, so the slice changes nothing but the
            # amount of padding the next reduction drags through HBM.
            # The INPUT slice must never go below n_real (dropping real
            # aggregates pre-reduction would falsify the unique count
            # that overflow detection relies on) — a caller-configured
            # caps[lvl] smaller than that bounds only the OUTPUT below,
            # where n_unique > capacity stays detectable. An overflowed
            # previous level (n_real > its array) skips shrinking.
            n_real = int(count)
            if n_real <= uniq.shape[0]:
                keep = max(64, 1 << max(0, n_real - 1).bit_length())
                if keep < uniq.shape[0]:
                    uniq = uniq[:keep]
                    sums = sums[:keep]
        with jax.named_scope(f"cascade.level{lvl}"):
            # Parent codes of the previous level's uniques; sentinel
            # slots must stay sentinel (a plain shift would corrupt them
            # into plausible-looking codes).
            parents = jnp.where(uniq == sentinel, sentinel, uniq >> 2)
            with jax.named_scope("cascade.segment-reduce"):
                uniq, sums, count = sparse_ops.aggregate_sorted_keys(
                    parents, sums, min(caps[lvl], uniq.shape[0]) if adaptive
                    else caps[lvl],
                    sentinel=sentinel,
                )
        out.append((uniq, sums, count))
    return out


def pyramid_sparse_morton_partitioned(
    codes,
    valid=None,
    levels: int = 0,
    capacity=None,
    chunk: int | None = None,
    block_cells: int | None = None,
    slab: int | None = None,
    interpret: bool | None = None,
    streams: int = 1,
    weights=None,
    weight_bound: int | None = None,
):
    """Sparse pyramid on the multi-channel MXU reduction.

    Same contract as :func:`pyramid_sparse_morton` (keys int64 with
    int64-max sentinel padding, per-level capacities), but every level
    is reduced from the ORIGINAL sorted stream shifted by ``2*level``
    — one sort, then ``levels+1`` kernel passes in place of a scan and
    a compaction per level (ops/sparse_partitioned.py rationale). Every
    pass reads the same ``n``-lane stream, padded to whole chunk rows:
    ``slab`` caps the lanes one f32 accumulation sees, it is not a
    size every pass pads to. Counts
    (``weights=None``, int32 sums) or bounded-integer weights
    (``weights`` + static ``weight_bound``: integers in
    [0, weight_bound], f64 sums, exactness via the shrunk slab;
    violations poison n_unique — see
    sparse_partitioned.aggregate_sorted_keys_partitioned). Fractional
    weights stay on the scatter pyramid. Keys must fit 60 bits.
    Tunables default to sparse_partitioned's DEFAULT_* values.
    """
    from heatmap_tpu.ops import sparse_partitioned as sp

    chunk = sp.DEFAULT_CHUNK if chunk is None else chunk
    block_cells = sp.DEFAULT_BLOCK_CELLS if block_cells is None else block_cells
    slab = sp.DEFAULT_SLAB if slab is None else slab

    codes = jnp.asarray(codes)
    if codes.dtype != jnp.int64:
        codes = codes.astype(jnp.int64)
    n = codes.shape[0]
    caps = _level_caps(capacity, n, levels)

    sentinel = jnp.iinfo(jnp.int64).max
    with jax.named_scope("cascade.sort"):
        keys = codes if valid is None else jnp.where(valid, codes, sentinel)
        if weights is None:
            skeys = jnp.sort(keys, stable=False)
            sw = None
        else:
            # Weights ride the same order as their keys (integer sums
            # are order-free, so the unstable argsort is fine).
            order = jnp.argsort(keys, stable=False)
            skeys = keys[order]
            sw = jnp.asarray(weights)[order]

    out = []
    for lvl in range(levels + 1):
        # Right shifts preserve the sort; the shifted sentinel
        # (intmax >> 2*lvl) still exceeds every real (< 2^60) key at
        # the shifted width, so it keeps sorting last and masking out.
        with jax.named_scope(f"cascade.level{lvl}"):
            with jax.named_scope("cascade.segment-reduce"):
                uniq, sums, n_unique = sp.aggregate_sorted_keys_partitioned(
                    skeys >> (2 * lvl),
                    caps[lvl],
                    sentinel=sentinel >> (2 * lvl),
                    chunk=chunk,
                    block_cells=block_cells,
                    slab=slab,
                    interpret=interpret,
                    streams=streams,
                    sorted_weights=sw,
                    weight_bound=weight_bound,
                )
            # Normalize padding to the repo-wide int64-max sentinel (the
            # per-level call pads with its SHIFTED sentinel, which a
            # `uniq != intmax` consumer mask would let through as
            # phantom zero-count cells). The kernel already sentinels
            # zero-sum segments via its presence channel, so masking on
            # the sums here would be wrong for weighted zero totals —
            # mask on the SHIFTED sentinel instead.
            uniq = jnp.where(uniq == (sentinel >> (2 * lvl)), sentinel, uniq)
        out.append((uniq, sums, n_unique))
    return out
