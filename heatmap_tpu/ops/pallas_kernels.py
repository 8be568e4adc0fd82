"""Pallas TPU kernels for the hot binning op.

The reference's hot loop is per-record Python trigonometry + string
keys shuffled by Spark (reference heatmap.py:60-75, tile.py:16-21).
The XLA path here (ops.histogram) replaces it with projection +
scatter-add. This module adds a **Pallas MXU formulation** of the
scatter: binning a chunk of points into an (H, W) window is the matmul

    raster += R @ (w * C)      R: (H, N) row one-hot
                               C: (N, W) col one-hot

— a histogram expressed as systolic-array work instead of serialized
scatter updates. One-hots are built in VMEM with ``broadcasted_iota``
comparisons (never materialized in HBM), the raster accumulates in a
VMEM scratch across a sequential grid over point chunks, and a single
HBM write emits the result in the last grid step. Invalid/out-of-window
points are encoded as row=-1, which matches no one-hot row and thus
contributes nothing — branch-free masking.

Cost: N*H*W MACs per N points — ideal for the blob-sized windows the
pipeline actually uses (a 32x32 or 256x256 coarse-tile raster,
reference heatmap.py:16,89 fan-in), where the MXU turns the whole
histogram into a handful of matmul passes. For very large windows the
one-hot cost grows past the scatter path; ops.histogram stays the
default and callers opt in by calling ``bin_points_window_pallas`` /
``bin_rowcol_window_pallas`` directly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from heatmap_tpu.ops.histogram import IMAP_ZERO, Window
from heatmap_tpu.tilemath import mercator

# Lane-friendly defaults: chunk is a multiple of 128 lanes; 8-row
# sublane alignment comes from H/W being tile multiples in practice.
# 1024 is the measured knee on v5e (smaller chunks under-fill the MXU
# passes; larger ones don't help — the kernel is VPU-bound on one-hot
# construction, ~3x faster than XLA scatter either way).
DEFAULT_CHUNK = 1024


def _histogram_kernel(
    rc_ref, w_ref, out_ref, acc_ref, *, height, width, chunk, precision,
    onehot_dtype
):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    rows = rc_ref[0, :]  # (chunk,) int32, -1 = dropped
    cols = rc_ref[1, :]
    weights = w_ref[0, :]  # (chunk,) f32

    # bf16 one-hots halve the VPU->MXU operand traffic and stay exact:
    # 0 and 1 are representable, and accumulation is f32 regardless
    # (preferred_element_type). Only the *weighted* path needs f32
    # operands, because arbitrary weights don't survive bf16's 8-bit
    # mantissa — the caller picks via onehot_dtype.
    r_ids = jax.lax.broadcasted_iota(jnp.int32, (height, chunk), 0)
    row_onehot = (r_ids == rows[None, :]).astype(onehot_dtype)
    c_ids = jax.lax.broadcasted_iota(jnp.int32, (chunk, width), 1)
    col_onehot = (c_ids == cols[:, None]).astype(onehot_dtype)
    if onehot_dtype == jnp.float32:
        col_onehot = col_onehot * weights[:, None]

    acc_ref[:] += jnp.dot(
        row_onehot,
        col_onehot,
        preferred_element_type=jnp.float32,
        precision=precision,
    )

    @pl.when(i == pl.num_programs(0) - 1)
    def _():
        out_ref[:] = acc_ref[:]


@functools.partial(
    jax.jit, static_argnames=("window", "chunk", "interpret", "onehot_dtype")
)
def bin_rowcol_window_pallas(
    row,
    col,
    window: Window,
    weights=None,
    valid=None,
    chunk: int = DEFAULT_CHUNK,
    interpret: bool = False,
    onehot_dtype=None,
):
    """Pallas MXU histogram: pre-projected points -> (H, W) f32 raster.

    Same contract as ops.histogram.bin_rowcol_window (drop out-of-window
    and invalid points) with f32 accumulation — exact for < 2^24 counts
    per cell per call. ``interpret=True`` runs the kernel in interpreter
    mode (CPU tests).
    """
    h, w = window.height, window.width
    r = jnp.asarray(row, jnp.int32) - window.row0
    c = jnp.asarray(col, jnp.int32) - window.col0
    ok = (r >= 0) & (r < h) & (c >= 0) & (c < w)
    if valid is not None:
        ok = ok & valid
    r = jnp.where(ok, r, -1)
    c = jnp.where(ok, c, 0)
    wts = (
        jnp.ones(r.shape, jnp.float32)
        if weights is None
        else jnp.asarray(weights, jnp.float32)
    )
    # Zero dropped points' weights too: row=-1 alone keeps them out of
    # the row one-hot, but a NaN/inf weight would still poison the
    # col-one-hot product (0 * nan = nan).
    wts = jnp.where(ok, wts, 0.0)

    n = r.shape[0]
    n_pad = -(-max(n, 1) // chunk) * chunk
    if n_pad != n:
        pad = n_pad - n
        r = jnp.concatenate([r, jnp.full(pad, -1, jnp.int32)])
        c = jnp.concatenate([c, jnp.zeros(pad, jnp.int32)])
        wts = jnp.concatenate([wts, jnp.zeros(pad, jnp.float32)])
    rc = jnp.stack([r, c])  # (2, n_pad)
    wts = wts[None, :]  # (1, n_pad)

    # 0/1 one-hots and unit weights are exact in the MXU's default
    # bf16 passes; arbitrary weights need full f32 precision or the
    # TPU matmul rounds them to 8 mantissa bits. The count path goes
    # further and feeds bf16 one-hot *operands* (half the VPU->MXU
    # traffic, still exact — counts accumulate in f32).
    precision = (
        jax.lax.Precision.DEFAULT if weights is None
        else jax.lax.Precision.HIGHEST
    )
    if onehot_dtype is None:
        onehot_dtype = jnp.bfloat16 if weights is None else jnp.float32
    elif weights is not None and onehot_dtype != jnp.float32:
        raise ValueError(
            "weighted binning requires f32 one-hots (bf16 would round "
            "the weights); leave onehot_dtype unset"
        )
    kernel = functools.partial(
        _histogram_kernel, height=h, width=w, chunk=chunk,
        precision=precision, onehot_dtype=onehot_dtype,
    )
    z = IMAP_ZERO  # concrete int32; see histogram.IMAP_ZERO
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((h, w), jnp.float32),
        grid=(n_pad // chunk,),
        in_specs=[
            pl.BlockSpec((2, chunk), lambda i: (z, i)),
            pl.BlockSpec((1, chunk), lambda i: (z, i)),
        ],
        out_specs=pl.BlockSpec((h, w), lambda i: (z, z)),
        scratch_shapes=[pltpu_vmem((h, w), jnp.float32)],
        interpret=interpret,
    )(rc, wts)


def pltpu_vmem(shape, dtype):
    """VMEM scratch constructor, importable lazily so CPU-only installs
    without the TPU plugin still import this module."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)


def bin_points_window_pallas(
    latitude,
    longitude,
    window: Window,
    weights=None,
    valid=None,
    proj_dtype=None,
    chunk: int = DEFAULT_CHUNK,
    interpret: bool = False,
):
    """Fused projection + Pallas MXU histogram (bin_points_window's
    opt-in fast path)."""
    rowf, colf, proj_valid = mercator.project_points(
        latitude, longitude, window.zoom, dtype=proj_dtype
    )
    if valid is not None:
        proj_valid = proj_valid & valid
    return bin_rowcol_window_pallas(
        rowf, colf, window,
        weights=weights, valid=proj_valid, chunk=chunk, interpret=interpret,
    )
