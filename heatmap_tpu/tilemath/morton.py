"""Morton (Z-order) codes for tile keys.

Why Morton codes: the pyramid parent of a Morton code is ``code >> 2``,
and the right shift *preserves sort order*. So one device-side sort of
detail-zoom codes serves every level of the rollup — each coarser level
is a segment-sum over already-sorted keys. This replaces the reference's
per-level reduceByKey/groupByKey shuffle pair (reference
heatmap.py:109-117; 32 shuffles per run, SURVEY.md §3.3) with zero
re-sorts and zero re-projections.

Two widths:
- int32 codes hold zooms <= 15 (2x15 = 30 bits) — the fast TPU path and
  enough for the z0-z15 north-star pyramid (BASELINE.md).
- int64 codes hold zooms <= 29 — covers the reference's z21 detail grid
  (reference heatmap.py:27); requires x64.
"""

from __future__ import annotations

import jax.numpy as jnp


def _part1by1_32(x):
    """Spread the low 16 bits of int32 x into the even bit positions."""
    x = x & 0x0000FFFF
    x = (x | (x << 8)) & 0x00FF00FF
    x = (x | (x << 4)) & 0x0F0F0F0F
    x = (x | (x << 2)) & 0x33333333
    x = (x | (x << 1)) & 0x55555555
    return x


def _compact1by1_32(x):
    """Inverse of :func:`_part1by1_32`."""
    x = x & 0x55555555
    x = (x | (x >> 1)) & 0x33333333
    x = (x | (x >> 2)) & 0x0F0F0F0F
    x = (x | (x >> 4)) & 0x00FF00FF
    x = (x | (x >> 8)) & 0x0000FFFF
    return x


def _part1by1_64(x):
    """Spread the low 32 bits of int64 x into the even bit positions."""
    x = x & 0x00000000FFFFFFFF
    x = (x | (x << 16)) & 0x0000FFFF0000FFFF
    x = (x | (x << 8)) & 0x00FF00FF00FF00FF
    x = (x | (x << 4)) & 0x0F0F0F0F0F0F0F0F
    x = (x | (x << 2)) & 0x3333333333333333
    x = (x | (x << 1)) & 0x5555555555555555
    return x


def _compact1by1_64(x):
    """Inverse of :func:`_part1by1_64`."""
    x = x & 0x5555555555555555
    x = (x | (x >> 1)) & 0x3333333333333333
    x = (x | (x >> 2)) & 0x0F0F0F0F0F0F0F0F
    x = (x | (x >> 4)) & 0x00FF00FF00FF00FF
    x = (x | (x >> 8)) & 0x0000FFFF0000FFFF
    x = (x | (x >> 16)) & 0x00000000FFFFFFFF
    return x


def morton_encode(row, col, dtype=jnp.int32, zoom=None):
    """Interleave (row, col) into a Z-order code; row occupies odd bits.

    ``dtype=jnp.int32`` supports zooms <= 15; ``jnp.int64`` (x64 only)
    supports zooms <= 29. Coordinates beyond the dtype's range would be
    silently bit-truncated into aliased codes, so pass the static
    ``zoom`` whenever it is known to get a loud error instead.
    """
    if zoom is not None:
        limit = 15 if jnp.dtype(dtype).itemsize == 4 else 29
        if zoom > limit:
            raise ValueError(
                f"morton {jnp.dtype(dtype).name} codes hold zooms <= {limit}, "
                f"got zoom={zoom}; use a wider dtype"
            )
    if jnp.dtype(dtype).itemsize == 4:
        r = jnp.asarray(row, jnp.int32)
        c = jnp.asarray(col, jnp.int32)
        return (_part1by1_32(r) << 1) | _part1by1_32(c)
    import jax

    if not jax.config.jax_enable_x64:
        raise RuntimeError(
            "morton int64 codes need x64 (jax.config.update"
            "('jax_enable_x64', True)); without it the request would "
            "silently downgrade to int32 and fail on the 64-bit masks"
        )
    r = jnp.asarray(row, jnp.int64)
    c = jnp.asarray(col, jnp.int64)
    return (_part1by1_64(r) << 1) | _part1by1_64(c)


def morton_decode(code):
    """Z-order code -> (row, col), dtype-matched to the code."""
    code = jnp.asarray(code)
    if code.dtype.itemsize == 4:
        return _compact1by1_32(code >> 1), _compact1by1_32(code)
    return (
        _compact1by1_64(code >> 1).astype(jnp.int64),
        _compact1by1_64(code).astype(jnp.int64),
    )


def morton_parent(code, levels=1):
    """The ancestor code ``levels`` zooms coarser: a right shift by 2*levels.

    Order-preserving — sorted codes stay sorted after this, which is the
    whole point (module docstring).
    """
    return code >> (2 * levels)


# ---------------------------------------------------------------------------
# Host-side numpy variants (single source of truth for host pipelines —
# pipeline/batch.py encodes with these, pipeline/cascade.py decodes).
# ---------------------------------------------------------------------------

import numpy as np  # noqa: E402


def morton_encode_np(row, col) -> np.ndarray:
    """Numpy 64-bit Morton encode (zooms <= 29, like the jnp int64 path)."""

    def part(x):
        x = np.asarray(x, np.uint64) & np.uint64(0xFFFFFFFF)
        x = (x | (x << np.uint64(16))) & np.uint64(0x0000FFFF0000FFFF)
        x = (x | (x << np.uint64(8))) & np.uint64(0x00FF00FF00FF00FF)
        x = (x | (x << np.uint64(4))) & np.uint64(0x0F0F0F0F0F0F0F0F)
        x = (x | (x << np.uint64(2))) & np.uint64(0x3333333333333333)
        x = (x | (x << np.uint64(1))) & np.uint64(0x5555555555555555)
        return x

    return ((part(row) << np.uint64(1)) | part(col)).astype(np.int64)


def morton_decode_np(code) -> tuple[np.ndarray, np.ndarray]:
    """Numpy 64-bit Morton decode -> (row, col) int32.

    int32 is always sufficient: a 64-bit Morton code interleaves at
    most 31 bits per axis (2*zoom <= 62), so row/col < 2^31. Halving
    the row/col width matters at egress scale (tens of millions of
    aggregates per job flow through these columns and their coarse
    shifted copies).
    """
    code = np.asarray(code, np.uint64)
    if code.ndim == 1 and code.size > 100_000:
        # Threaded C decode for bulk egress arrays (code_bits=0 makes
        # hm_decode_keys a plain Morton de-interleave). Lazy import:
        # native -> pipeline -> tilemath would cycle at module level.
        from heatmap_tpu import native as _native

        if _native.decode_keys is not None:
            _, _, row, col = _native.decode_keys(
                code.astype(np.int64, copy=False), 0, morton_only=True
            )
            return row, col
    return _morton_decode_np_pure(code)


def morton_range_shards_np(splits, codes) -> np.ndarray:
    """Shard index per detail code under sorted split codes.

    A code belongs to shard ``k`` iff exactly ``k`` splits are <= it
    (``searchsorted(side="right")``), i.e. a split code itself opens the
    range to its right. This is THE ownership convention: the planner,
    the lane counts of partition.route_counts, and the on-device router
    of the range program must all agree on it or boundary tiles get
    double-counted.
    """
    return np.searchsorted(
        np.asarray(splits, np.int64), np.asarray(codes, np.int64),
        side="right").astype(np.int32)


def split_boundary_codes_np(splits, levels: int) -> np.ndarray:
    """Ancestor codes ``levels`` zooms coarser whose tile straddles a split.

    A tile at ``levels`` above detail covers the contiguous detail range
    ``[c << 2L, (c+1) << 2L)``; a split ``s`` falls strictly inside it
    iff ``s >> 2L == c`` and ``s`` is not aligned to the tile's start
    (``s % 4^L != 0``). At ``levels == 0`` no integer split can be
    strictly inside a single-code range, so the set is empty — the
    detail level never needs a cross-shard merge.
    """
    s = np.unique(np.asarray(splits, np.int64))
    if levels <= 0 or s.size == 0:
        return np.empty(0, np.int64)
    block = np.int64(1) << np.int64(2 * levels)
    inner = s[(s % block) != 0]
    return np.unique(inner >> np.int64(2 * levels))


def _morton_decode_np_pure(code) -> tuple[np.ndarray, np.ndarray]:
    """The numpy-only decode: fallback and oracle for the native path."""
    code = np.asarray(code, np.uint64)

    def compact(x):
        x &= np.uint64(0x5555555555555555)
        x = (x | (x >> np.uint64(1))) & np.uint64(0x3333333333333333)
        x = (x | (x >> np.uint64(2))) & np.uint64(0x0F0F0F0F0F0F0F0F)
        x = (x | (x >> np.uint64(4))) & np.uint64(0x00FF00FF00FF00FF)
        x = (x | (x >> np.uint64(8))) & np.uint64(0x0000FFFF0000FFFF)
        x = (x | (x >> np.uint64(16))) & np.uint64(0x00000000FFFFFFFF)
        return x

    return (
        compact(code >> np.uint64(1)).astype(np.int32),
        compact(code).astype(np.int32),
    )
