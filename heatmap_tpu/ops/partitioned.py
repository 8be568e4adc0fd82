"""Sort-partitioned MXU binning for LARGE windows.

The whole-window Pallas histogram (pallas_kernels.py) pays H*W MACs per
point, so it only wins for blob-sized windows; the XLA scatter path
pays a flat cost *per update*, which is the bottleneck for big rasters
(a z15 metro window is ~1.3M cells). This module restores MXU locality for big windows:

1. project to linear cell indices and **sort** (XLA's comparison sort
   is the one fast reshuffling primitive on this chip);
2. cut the sorted stream into fixed chunks; a chunk whose cells all
   land in one aligned ``block_cells`` region is **good** — after
   sorting, that's the common case for clustered GPS data;
3. chunk block ids are non-decreasing in place (the stream is sorted),
   so no reorder pass is needed — bad chunks are simply masked;
4. a Pallas kernel walks the good chunks with a scalar-prefetched
   output-block index per chunk (bases are monotone by construction,
   so each output block's visits are consecutive): each chunk becomes
   a side x side one-hot matmul into its block — the same MXU
   formulation as the small-window kernel, but against one aligned
   ``block_cells``-cell block instead of the whole raster;
5. the bad chunks (sparse fringes, block-straddlers, padding) are
   gathered by row and go through the ordinary scatter, bounded to
   1/``bad_frac`` of the points instead of the full stream;
6. if an adversarial distribution makes more than that fraction of
   chunks bad, ``lax.cond`` falls back to the plain full scatter —
   correctness never depends on the data being friendly.

Counts accumulate in f32 inside the kernel (exact < 2^24 per cell per
call) and int32 on the scatter tail; the merged raster is returned in
the requested dtype.

Weighted binning (BASELINE.md config 3) rides the same machinery: the
sort carries the weight as a ``lax.sort`` payload operand (XLA's sort
permutes payloads in-pass — no separate gather, which would cost as
much as the scatter being avoided), and the per-chunk matmul
scales the column one-hot by the weight, so each good chunk is
``row_onehot @ (col_onehot * w)``. Weighted sums accumulate in f32:
bit-exact vs the scatter path for integer-valued weights with per-cell
sums < 2^24 (the oracle-testable contract), within f32 rounding
otherwise (summation order differs from the scatter path).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from heatmap_tpu.ops.histogram import IMAP_ZERO, Window

DEFAULT_CHUNK = 1024
#: Independently sorted rows per call (1 = one flat sort). 8 came from
#: an earlier on-chip sweep (not re-measured on today's code): the
#: per-stream slab accumulation pipelines the pallas grid better than
#: one giant visit-run sequence, and 8 keeps fewer slabs than 32 (less
#: zero-padding and a smaller output-blocks buffer).
DEFAULT_STREAMS = 8

#: Cap on the summed per-stream output-slab footprint (bytes). Each
#: stream accumulates its own (n_blocks * block_cells) f32 slab, so
#: streams multiplies output memory x8 by default; a window near the
#: int32 cell-id cap (~8 GiB of cells) fits HBM at streams=1 but not
#: x8. 4 GiB leaves the z15 headline window (256 MiB slab -> 16
#: streams allowed) untouched while clamping the giant-
#: window tail down to what fits.
STREAM_SLAB_BUDGET = 4 << 30
#: Cells per aligned output block (a side x side one-hot factor pair).
#: Smaller blocks cut the per-point one-hot construction (VPU, 2*side
#: compares+casts per point) and the MXU MACs quadratically, at the
#: price of a lower good-chunk rate on dispersed data (a chunk must
#: land inside ONE aligned block). 2^16 = 256x256 came from an earlier
#: on-chip sweep; sweep block_cells on the chip before changing it.
DEFAULT_BLOCK_CELLS = 1 << 16


def default_interpret() -> bool:
    """What ``interpret=None`` means for the Pallas kernels: interpret
    mode on CPU (Pallas has no compiled CPU lowering), the compiled
    Mosaic kernel on TPU. Any other platform is an error rather than a
    silent interpreter run."""
    platform = jax.devices()[0].platform
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"the Pallas kernels run on tpu (compiled) or cpu (interpret "
        f"mode); the default device is {platform!r}")


def masked_local_rc(block_start, good, stream, block_cells, side):
    """(row-in-block, col-in-block) for one chunk's sorted cell ids,
    with dropped lanes as row=-1/col=0 (matching no one-hot row).

    Shared by every partitioned-MXU kernel (count / weighted /
    multi-channel segment). Every constant is explicitly int32: under
    ``jax_enable_x64`` (the batch job's z21 precision policy) weak
    Python-int literals trace as int64 scalars inside Pallas kernels,
    and Mosaic's int64->int32 convert lowering recurses forever
    (pinned by tests/test_lowering.py)."""
    bc = jnp.int32(block_cells)
    sd = jnp.int32(side)
    local = stream - block_start * bc
    ok = (good == jnp.int32(1)) & (local >= jnp.int32(0)) & (local < bc)
    rloc = jnp.where(ok, local // sd, jnp.int32(-1))
    cloc = jnp.where(ok, local % sd, jnp.int32(0))
    return rloc, cloc


def _partition_kernel(base_ref, good_ref, first_ref, last_ref, s_ref,
                      zeros_ref, out_ref, acc_ref, *, chunk, block_cells,
                      side, n_blocks):
    # Count path (weighted binning goes through the separate
    # _partition_kernel_weighted twin); zeros_ref only alias-inits the
    # output.
    del zeros_ref
    i = pl.program_id(0)

    @pl.when(first_ref[i] == 1)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # base_ref holds FLAT output-slab ids stream*n_blocks + block; the
    # cell offset inside the window depends only on the block part.
    rloc, cloc = masked_local_rc(
        base_ref[i] % jnp.int32(n_blocks), good_ref[i], s_ref[0, 0, :],
        block_cells, side,
    )

    r_ids = lax.broadcasted_iota(jnp.int32, (side, chunk), 0)
    c_ids = lax.broadcasted_iota(jnp.int32, (chunk, side), 1)
    row_onehot = (r_ids == rloc[None, :]).astype(jnp.bfloat16)
    col_onehot = (c_ids == cloc[:, None]).astype(jnp.bfloat16)
    acc_ref[0] += jnp.dot(
        row_onehot, col_onehot, preferred_element_type=jnp.float32
    )

    @pl.when(last_ref[i] == 1)
    def _():
        out_ref[:] = acc_ref[:]


def _partition_kernel_weighted(base_ref, good_ref, first_ref, last_ref,
                               s_ref, w_ref, zeros_ref, out_ref, acc_ref, *,
                               chunk, block_cells, side, n_blocks):
    """Weighted twin of :func:`_partition_kernel` (kept as a SEPARATE
    kernel, not a kwarg branch, so the count path stays byte-stable):
    the column one-hot is scaled by the point's weight, making each
    chunk's contribution ``row_onehot @ (col_onehot * w)``. Masked /
    out-of-block lanes zero out through the all-false one-hot row."""
    del zeros_ref
    i = pl.program_id(0)

    @pl.when(first_ref[i] == 1)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    rloc, cloc = masked_local_rc(
        base_ref[i] % jnp.int32(n_blocks), good_ref[i], s_ref[0, 0, :],
        block_cells, side,
    )

    r_ids = lax.broadcasted_iota(jnp.int32, (side, chunk), 0)
    c_ids = lax.broadcasted_iota(jnp.int32, (chunk, side), 1)
    # f32 one-hots here: the weight factor makes bf16 lossy (weights are
    # arbitrary f32), and the f32/bf16 gap measured ~0 at >= 256x256.
    row_onehot = (r_ids == rloc[None, :]).astype(jnp.float32)
    col_w = (c_ids == cloc[:, None]).astype(jnp.float32) * w_ref[0, 0, :][:, None]
    # HIGHEST: the default f32 matmul may execute as one bf16 pass on
    # the MXU (8 mantissa bits), which would round the weights — the
    # same contract as the small-window kernel (pallas_kernels.py).
    acc_ref[0] += jnp.dot(
        row_onehot, col_w, preferred_element_type=jnp.float32,
        precision=lax.Precision.HIGHEST,
    )

    @pl.when(last_ref[i] == 1)
    def _():
        out_ref[:] = acc_ref[:]


def _partitioned_path(s2, good2, n_blocks, hw, chunk,
                      bad_cap_chunks, interpret, block_cells, side,
                      w2=None):
    """Good chunks -> pallas blocks; bad tail -> bounded scatter.

    ``s2`` is (streams, L): each row independently sorted (one flat
    sort is the streams=1 case). ``good2`` is the per-(stream, chunk)
    mask computed by the caller — the SAME mask that sized the bounded
    tail via n_bad, so the tail provably covers every chunk this path
    masks out. Each stream accumulates into its own slab of output
    blocks; the slabs sum at the end (counts are linear), which keeps
    every output block's visits consecutive WITHIN the flattened grid
    without any cross-stream ordering requirement.

    ``w2`` (same shape as ``s2``, f32, already permuted by the caller's
    pair sort) switches to the weighted kernel and a weighted f32
    scatter tail.
    """
    streams, L = s2.shape
    nck = L // chunk
    n_chunks = streams * nck
    first = s2[:, ::chunk]
    fblk = first // block_cells

    # Each row is sorted, so chunk block ids are non-decreasing within
    # a stream — no reorder pass over the point stream is needed.
    # Forward-fill bad chunks with the last good base per stream
    # (cummax works because good bases are non-decreasing); leading
    # bads clamp to block 0, fully masked; a bad chunk between two
    # blocks joins the previous block's visit run and writes nothing.
    base2 = jnp.maximum(
        lax.cummax(jnp.where(good2, fblk, -1), axis=1), 0
    )
    # Flat output-slab id: stream*n_blocks + block. Monotone within a
    # stream and strictly increasing across stream boundaries' slabs,
    # so visit runs stay consecutive over the flattened grid.
    ob = (
        jnp.arange(streams, dtype=base2.dtype)[:, None] * n_blocks + base2
    ).reshape(-1)
    good = good2.reshape(-1)
    gi = good.astype(jnp.int32)
    first_visit = jnp.concatenate(
        [jnp.ones(1, jnp.int32),
         (ob[1:] != ob[:-1]).astype(jnp.int32)]
    )
    last_visit = jnp.concatenate(
        [(ob[1:] != ob[:-1]).astype(jnp.int32),
         jnp.ones(1, jnp.int32)]
    )

    from jax.experimental.pallas import tpu as pltpu

    # (n_chunks, 1, chunk) so the last-two block dims (1, chunk)
    # satisfy the TPU tiling rule: sublane block == array dim
    # (1 == 1), lane block divisible by 128.  A flat
    # (n_chunks, chunk) array with block (1, chunk) is rejected
    # by Mosaic (sublane 1 neither 8-divisible nor full).
    z = IMAP_ZERO  # concrete int32; see histogram.IMAP_ZERO
    stream_spec = pl.BlockSpec((1, 1, chunk), lambda i, *_: (i, z, z))
    block_spec = pl.BlockSpec(
        (1, side, side), lambda i, base, *_: (base[i], z, z)
    )
    weighted = w2 is not None
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_chunks,),
        in_specs=(
            [stream_spec, stream_spec, block_spec] if weighted
            else [stream_spec, block_spec]
        ),
        out_specs=block_spec,
        scratch_shapes=[pltpu.VMEM((1, side, side), jnp.float32)],
    )
    kernel = _partition_kernel_weighted if weighted else _partition_kernel
    zeros = jnp.zeros((streams * n_blocks, side, side), jnp.float32)
    operands = [ob, gi, first_visit, last_visit,
                s2.reshape(n_chunks, 1, chunk)]
    if weighted:
        operands.append(w2.reshape(n_chunks, 1, chunk))
    operands.append(zeros)
    blocks = pl.pallas_call(
        functools.partial(kernel, chunk=chunk,
                          block_cells=block_cells, side=side,
                          n_blocks=n_blocks),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (streams * n_blocks, side, side), jnp.float32
        ),
        # zeros operand -> output (position counts the scalar prefetches)
        input_output_aliases={6 if weighted else 5: 0},
        interpret=interpret,
    )(*operands)
    dense = (
        blocks.reshape(streams, n_blocks * block_cells).sum(axis=0)[:hw]
        if streams > 1
        else blocks.reshape(n_blocks * block_cells)[:hw]
    )

    # Bounded scatter over the bad chunks only: gather exactly their
    # rows (the cond guarantees there are at most bad_cap_chunks of
    # them, so the fixed-size nonzero captures ALL of them); the
    # fill rows read as sentinel, and sentinel/out-of-range cells drop
    # in the scatter, so no weight masking is needed.
    bad_idx = jnp.nonzero(~good, size=bad_cap_chunks,
                          fill_value=n_chunks)[0]
    bad_rows = jnp.take(
        s2.reshape(n_chunks, chunk), bad_idx, axis=0,
        mode="fill", fill_value=hw,
    )
    if weighted:
        bad_w = jnp.take(
            w2.reshape(n_chunks, chunk), bad_idx, axis=0,
            mode="fill", fill_value=0.0,
        )
        tail = (
            jnp.zeros(hw, jnp.float32)
            .at[bad_rows.reshape(-1)]
            .add(bad_w.reshape(-1), mode="drop")
        )
        return dense + tail
    tail = (
        jnp.zeros(hw, jnp.int32)
        .at[bad_rows.reshape(-1)]
        .add(1, mode="drop")
    )
    return dense.astype(jnp.int32) + tail


def clamp_streams(streams: int, window: Window,
                  block_cells: int = DEFAULT_BLOCK_CELLS) -> int:
    """Largest stream count <= ``streams`` whose summed output slabs
    fit STREAM_SLAB_BUDGET for this window (always >= 1)."""
    hw = window.height * window.width
    slab_bytes = -(-hw // block_cells) * block_cells * 4
    return max(1, min(streams, STREAM_SLAB_BUDGET // max(slab_bytes, 1)))


def bin_rowcol_window_partitioned(
    row,
    col,
    window: Window,
    weights=None,
    valid=None,
    chunk: int = DEFAULT_CHUNK,
    bad_frac: int = 128,
    interpret: bool | None = None,
    dtype=None,
    block_cells: int = DEFAULT_BLOCK_CELLS,
    streams: int = DEFAULT_STREAMS,
):
    """Sort-partitioned binning of pre-projected points into a large window.

    Contract matches ops.histogram.bin_rowcol_window: out-of-window /
    invalid points drop. ``weights=None`` counts occurrences (int32,
    bit-exact vs the scatter path); ``weights`` given sums them in f32
    (bit-exact vs scatter for integer-valued weights with per-cell sums
    < 2^24, within f32 rounding otherwise — the pair sort changes
    summation order). ``bad_frac``: the scatter tail is sized
    n/bad_frac points; distributions badder than that fall back to the
    full scatter inside the same jit (lax.cond). The 128 default came
    from an earlier on-chip sweep: the tail rarely fills, so a smaller
    bound frees HBM and scatter work without changing results.
    ``interpret=None`` follows :func:`default_interpret`.
    ``block_cells`` sets the aligned output-block size
    (must be an even power of two >= 2^12 so the side is a
    lane-friendly square; see DEFAULT_BLOCK_CELLS). ``streams`` splits
    the cell-id stream into that many independently sorted rows (one
    batched row sort instead of one flat sort; each row can be
    VMEM-resident), each accumulating its own output-block slab, summed
    at the end — same raster bit-for-bit, different sort-cost/memory
    tradeoff. streams=1 is the flat-sort baseline.

    ``streams`` is clamped so the summed per-stream output slabs
    (streams * n_blocks * block_cells f32, ~32 B/cell at the x8
    default) stay under STREAM_SLAB_BUDGET: windows near the int32
    cell-id cap fit HBM at streams=1 and must not OOM just because
    backend="auto" routed here with the streams default.
    """
    if interpret is None:
        interpret = default_interpret()
    if dtype is None:
        dtype = jnp.int32 if weights is None else jnp.float32
    streams = clamp_streams(streams, window, block_cells)
    return _bin_partitioned_jit(
        row, col, window, weights, valid, chunk=chunk, bad_frac=bad_frac,
        interpret=interpret, dtype=dtype, block_cells=block_cells,
        streams=streams,
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "window", "chunk", "bad_frac", "interpret", "dtype", "block_cells",
        "streams",
    ),
)
def _bin_partitioned_jit(
    row,
    col,
    window: Window,
    weights=None,
    valid=None,
    chunk: int = DEFAULT_CHUNK,
    bad_frac: int = 128,
    interpret: bool = False,
    dtype=jnp.int32,
    block_cells: int = DEFAULT_BLOCK_CELLS,
    streams: int = 1,
):
    h, w = window.height, window.width
    hw = h * w
    if hw >= (1 << 31):
        raise ValueError(f"window too large for int32 cell ids: {window}")
    side = 1 << (block_cells.bit_length() // 2)
    if side * side != block_cells or side < 64:
        raise ValueError(
            f"block_cells must be an even power of two >= 4096 "
            f"(a square side of >= 64 lanes), got {block_cells}"
        )
    if streams < 1:
        raise ValueError(f"streams must be >= 1, got {streams}")
    n_blocks = -(-hw // block_cells)
    sentinel = n_blocks * block_cells  # beyond every block, drops everywhere

    r = jnp.asarray(row, jnp.int32) - window.row0
    c = jnp.asarray(col, jnp.int32) - window.col0
    ok = (r >= 0) & (r < h) & (c >= 0) & (c < w)
    if valid is not None:
        ok = ok & valid
    idx = jnp.where(ok, r * w + c, sentinel)
    weighted = weights is not None
    if weighted:
        # Dropped lanes carry weight 0 as well as the sentinel cell id,
        # so every downstream path (matmul mask, bounded tail, full-
        # scatter fallback) is doubly safe.
        wts = jnp.where(ok, jnp.asarray(weights, jnp.float32), 0.0)

    n = idx.shape[0]
    # Pad so each of the `streams` rows is a whole number of chunks.
    per_stream = -(-max(n, 1) // (streams * chunk)) * chunk
    n_pad = streams * per_stream
    if n_pad != n:
        idx = jnp.concatenate(
            [idx, jnp.full(n_pad - n, sentinel, jnp.int32)]
        )
        if weighted:
            wts = jnp.concatenate([wts, jnp.zeros(n_pad - n, jnp.float32)])
    n_chunks = n_pad // chunk
    # Padding sentinels land in the trailing rows and sort to each
    # row's end, so they can mark up to ~streams extra chunks bad on
    # top of the data-dependent ones.
    bad_cap_chunks = max(streams + 1, n_chunks // bad_frac)

    # Unstable sort: for counts, cell ids are the only payload, so equal
    # keys are indistinguishable and stability would only cost time.
    # Weighted, the weight rides as a lax.sort payload operand — XLA
    # permutes it in-pass, avoiding a separate gather that would cost
    # as much as the scatter being avoided. With
    # streams > 1 this is one batched row sort (axis -1).
    if weighted:
        s2, w2 = lax.sort(
            (idx.reshape(streams, per_stream),
             wts.reshape(streams, per_stream)),
            dimension=1, num_keys=1, is_stable=False,
        )
    else:
        s2 = jnp.sort(idx.reshape(streams, per_stream), axis=-1, stable=False)
        w2 = None
    # The single source of truth for chunk goodness: fully inside one
    # aligned block AND free of sentinels. The bounded tail in
    # _partitioned_path covers exactly the chunks this marks bad, and
    # the cond below guarantees they fit.
    first = s2[:, ::chunk]
    last = s2[:, chunk - 1 :: chunk]
    good2 = (first // block_cells == last // block_cells) & (last < sentinel)
    n_bad = (~good2).sum()

    if weighted:
        raster = lax.cond(
            n_bad <= bad_cap_chunks,
            lambda s_, ww_, good_: _partitioned_path(
                s_, good_, n_blocks, hw, chunk, bad_cap_chunks,
                interpret, block_cells, side, w2=ww_,
            ),
            lambda s_, ww_, good_: (
                jnp.zeros(hw, jnp.float32)
                .at[s_.reshape(-1)]
                .add(ww_.reshape(-1), mode="drop")
            ),
            s2,
            w2,
            good2,
        )
    else:
        raster = lax.cond(
            n_bad <= bad_cap_chunks,
            lambda s_, good_: _partitioned_path(
                s_, good_, n_blocks, hw, chunk, bad_cap_chunks,
                interpret, block_cells, side,
            ),
            lambda s_, good_: (
                jnp.zeros(hw, jnp.int32).at[s_.reshape(-1)].add(1, mode="drop")
            ),
            s2,
            good2,
        )
    return raster.reshape(h, w).astype(dtype)
