"""Partitioned-MXU segment reduction for the cascade's sorted streams.

``aggregate_sorted_keys`` (ops/sparse.py) reduces each cascade level
from the previous level's aggregates with a segmented scan and a
compaction, in the weights' dtype, which the TPU emulates for
float64. This module reduces EVERY level from the original sorted
stream in one pass of the sort-partitioned one-hot-matmul machinery
(ops/partitioned.py), on the MXU in f32, exploiting that the cascade's
inputs are already sorted:

- the segment index ``seg = cumsum(first) - 1`` is a sorted, dense
  cell id into [0, capacity) — exactly the stream shape the
  partitioned kernel bins, with NO sort needed;
- counts stay exact at any fan-in by processing the stream in SLABS
  of at most 2^24 elements: per-slab f32 accumulation cannot round
  (every partial sum is an integer < 2^24), and slabs combine in f64
  on the way out. The work follows the input: a stream pads only to
  whole chunk rows, so its last (often only) slab is as short as what
  remains of it;
- every chunk of a slab goes to exactly one place. A chunk whose
  in-range lanes (cell < capacity) sit inside one output block rides
  the kernel, which masks its drop lanes lane by lane; a chunk whose
  in-range lanes straddle a block boundary goes to a float64 scatter
  tail; a chunk with no in-range lane adds nothing. Cell ids are
  non-decreasing along a sorted stream (drops sort last), so at most
  ``n_blocks - 1`` chunks of a slab straddle, and that bound sizes the
  tail statically — no data-dependent fallback exists;
- the unique key of a segment is reconstructed through three extra
  matmul CHANNELS: the segment's FIRST element contributes its key
  split into 20-bit pieces (each < 2^20, exactly one contribution per
  segment globally, so f32 holds them exactly), and the pieces
  reassemble as ``lo | mid<<20 | hi<<40`` — covering keys up to 60
  bits, which includes the cascade's 58-bit composite keys. The
  one-hot construction (the VPU cost that bounds the partitioned
  kernel) is SHARED by all four channels; the extra matmuls ride the
  MXU.

Counts AND bounded-integer weights: the slab argument extends to
weights that are integers in ``[0, weight_bound]`` — per-slab
per-cell partials are integers <= ``slab * weight_bound``, exact in
f32 when the slab shrinks to ``2^24 // weight_bound`` elements, and
slabs still combine exactly in f64 (sums < 2^53). Weighted calls add
a fifth channel (segment PRESENCE, one f32 unit per segment) so
zero-sum segments survive with their keys — bit-parity with the
scatter path. FRACTIONAL weights genuinely cannot ride this kernel:
f32 products of non-integer weights round before accumulation, and
there is no slab size that restores exactness — those stay on the
scatter path. Keys must fit 60 bits (a caller contract; the cascade's
composite keys do by the int64 packing check in
pipeline/cascade.composite_keys).

Interpret mode is pinned bit-equal to aggregate_sorted_keys, including
the multi-slab combine and the straddle tail, and the tail's bound is
property-tested (tests/test_sparse_partitioned.py);
the compiled kernel is pinned by tests/test_lowering.py and compared
with the scatter cascade on the chip by ``chip_smoke.py``.
BatchJobConfig's "auto" cascade backend routes count jobs on TPU here.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from heatmap_tpu.obs import events as obs_events
from heatmap_tpu.ops.histogram import IMAP_ZERO
from heatmap_tpu.ops.partitioned import default_interpret, masked_local_rc

DEFAULT_CHUNK = 1024
DEFAULT_BLOCK_CELLS = 1 << 16
#: Max elements per exactness slab: f32 integer accumulation is exact
#: below 2^24, and a slab contributes at most ``slab`` to any count.
DEFAULT_SLAB = 1 << 24
#: Bits per key-reconstruction channel (3 channels -> 60-bit keys).
KEY_BITS = 20
N_CHANNELS = 4  # counts + 3 key pieces
N_CHANNELS_WEIGHTED = 5  # weighted sums + presence + 3 key pieces


def _segment_kernel(base_ref, good_ref, first_v_ref, last_v_ref,
                    s_ref, w_ref, zeros_ref, out_ref, acc_ref, *,
                    chunk, block_cells, side, n_blocks, capacity,
                    n_channels=N_CHANNELS):
    """Multi-channel twin of partitioned._partition_kernel: one shared
    one-hot pair per chunk, ``n_channels`` weighted matmuls into a
    (1, n_channels, side, side) accumulator."""
    del zeros_ref
    i = pl.program_id(0)

    @pl.when(first_v_ref[i] == 1)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # base_ref holds FLAT output-slab ids stream*n_blocks + block
    # (streams=1 makes this the plain block id); the cell offset
    # depends only on the block part — same scheme as the window
    # kernel (ops/partitioned.py).
    cells = s_ref[0, 0, :]
    rloc, cloc = masked_local_rc(
        base_ref[i] % jnp.int32(n_blocks), good_ref[i], cells,
        block_cells, side,
    )
    # Drop lanes (sentinel padding, segments past capacity) match no row.
    rloc = jnp.where(cells < jnp.int32(capacity), rloc, jnp.int32(-1))

    r_ids = lax.broadcasted_iota(jnp.int32, (side, chunk), 0)
    c_ids = lax.broadcasted_iota(jnp.int32, (chunk, side), 1)
    row_onehot = (r_ids == rloc[None, :]).astype(jnp.float32)
    col_onehot = (c_ids == cloc[:, None]).astype(jnp.float32)
    for ch in range(n_channels):  # static unroll; one-hots shared
        # HIGHEST, not the MXU default: channel values run to 2^20 (key
        # pieces) and the default f32 matmul may execute as one bf16
        # pass (8 mantissa bits — observed on-chip 2026-08-02, keys
        # truncated to 1024-multiples at slab=2^20). The one-hot factor
        # is exact in any precision; the VALUE factor is not.
        acc_ref[0, ch] += jnp.dot(
            row_onehot, col_onehot * w_ref[0, ch, :][:, None],
            preferred_element_type=jnp.float32,
            precision=lax.Precision.HIGHEST,
        )

    @pl.when(last_v_ref[i] == 1)
    def _():
        out_ref[:] = acc_ref[:]


def _chunk_kinds(cells, chunk, block_cells, capacity):
    """Per-chunk (kernel, straddle) masks of one slab of cell ids.

    Cell ids run non-decreasing along a slab: dense segment ids, then
    the drop id ``capacity`` for sentinel lanes (segments past
    capacity keep ids >= capacity). With every id clamped into the last
    block, a chunk's first and last lane therefore bound the blocks its
    in-range lanes touch. A chunk straddles when they differ (the one
    chunk where in-range lanes end may count so through the clamp);
    it rides the kernel when they agree and its first lane is in range;
    a chunk whose first lane is already out of range holds no in-range
    lane and is neither. The clamped block ids climb from 0 to at most
    ``n_blocks - 1`` over the slab, and every straddling chunk climbs
    at least one, so at most ``n_blocks - 1`` chunks straddle
    (_tail_chunks)."""
    first = cells[::chunk]
    top = capacity - 1
    lo = jnp.minimum(first, top) // block_cells
    hi = jnp.minimum(cells[chunk - 1 :: chunk], top) // block_cells
    straddle = lo != hi
    return (first < capacity) & ~straddle, straddle


def _tail_chunks(n_chunks, capacity, block_cells):
    """Static size of a slab's straddle tail: the most chunks that can
    straddle a block boundary (_chunk_kinds)."""
    return min(n_chunks, -(-capacity // block_cells) - 1)


def _tail_path(cells, chans, straddle, capacity, chunk, size, n_channels):
    """The straddling chunks of a slab, scattered in f64 (exact: every
    channel is integer-valued below 2^52). ``size`` is the static
    bound on their number; drop lanes fall out through mode="drop"."""
    nck = cells.shape[0] // chunk
    idx = jnp.nonzero(straddle, size=size, fill_value=nck)[0]
    tail_cells = jnp.take(cells.reshape(nck, chunk), idx, axis=0,
                          mode="fill", fill_value=capacity).reshape(-1)
    tails = []
    for ch in range(n_channels):
        w = jnp.take(chans[ch].reshape(nck, chunk), idx, axis=0,
                     mode="fill", fill_value=0.0).reshape(-1)
        tails.append(
            jnp.zeros(capacity, jnp.float64)
            .at[tail_cells]
            .add(w.astype(jnp.float64), mode="drop")
        )
    return jnp.stack(tails)


def _channel_path(cells, chans, good, capacity, n_blocks, chunk,
                  interpret, block_cells, side, streams=1,
                  n_channels=N_CHANNELS):
    """Kernel chunks (``good``, from _chunk_kinds) -> multi-channel
    pallas blocks, as f64 (n_channels, capacity) sums. Every other
    chunk is visited with all lanes masked.

    ``streams`` splits the (globally sorted) slab into that many
    contiguous sub-streams, each accumulating into its own slab of
    output blocks, summed at the end — the same grid-pipelining trick
    as the window kernel's streams default. Identical math: counts and
    key-piece channels are linear, every segment's FIRST element lands in
    exactly one sub-stream, and chunk boundaries are unchanged
    (sub-streams are whole runs of chunks), so the straddle tail is
    untouched. The sub-slab sums stay f32-exact: each slab holds at
    most 2^24 elements total, so every per-cell partial and the
    cross-stream integer sum are <= 2^24."""
    L = cells.shape[0]
    nck = L // chunk
    # Forward-fill the other chunks with the last kernel chunk's block
    # id per sub-stream (each sub-stream is a contiguous slice of the
    # sorted slab, so those ids are non-decreasing within it); leading
    # ones clamp to block 0, fully masked.
    first2 = cells.reshape(streams, L // streams)[:, ::chunk]
    good2 = good.reshape(streams, nck // streams)
    base2 = jnp.maximum(
        lax.cummax(jnp.where(good2, first2 // block_cells, -1), axis=1), 0
    ).astype(jnp.int32)
    # Flat output-slab id stream*n_blocks + block: monotone within a
    # sub-stream, strictly increasing across sub-stream boundaries'
    # slabs -> visit runs stay consecutive over the flattened grid.
    base = (
        jnp.arange(streams, dtype=jnp.int32)[:, None] * jnp.int32(n_blocks)
        + base2
    ).reshape(-1)
    gi = good.astype(jnp.int32)
    first_visit = jnp.concatenate(
        [jnp.ones(1, jnp.int32), (base[1:] != base[:-1]).astype(jnp.int32)]
    )
    last_visit = jnp.concatenate(
        [(base[1:] != base[:-1]).astype(jnp.int32), jnp.ones(1, jnp.int32)]
    )

    from jax.experimental.pallas import tpu as pltpu

    z = IMAP_ZERO  # concrete int32; see histogram.IMAP_ZERO
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(nck,),
        in_specs=[
            # (nck, 1, chunk): last-two block dims (1, chunk) satisfy
            # the TPU tiling rule (sublane == array dim, lane % 128).
            pl.BlockSpec((1, 1, chunk), lambda i, *_: (i, z, z)),
            # (nck, n_channels, chunk): channel dim taken whole.
            pl.BlockSpec((1, n_channels, chunk), lambda i, *_: (i, z, z)),
            pl.BlockSpec(
                (1, n_channels, side, side),
                lambda i, base_, *_: (base_[i], z, z, z),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, n_channels, side, side),
            lambda i, base_, *_: (base_[i], z, z, z),
        ),
        scratch_shapes=[
            pltpu.VMEM((1, n_channels, side, side), jnp.float32)
        ],
    )
    zeros = jnp.zeros((streams * n_blocks, n_channels, side, side),
                      jnp.float32)

    def _kernel_call(base_, gi_, first_, last_, cells_, chans_, zeros_):
        return pl.pallas_call(
            functools.partial(_segment_kernel, chunk=chunk,
                              block_cells=block_cells, side=side,
                              n_blocks=n_blocks, capacity=capacity,
                              n_channels=n_channels),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(
                (streams * n_blocks, n_channels, side, side), jnp.float32
            ),
            input_output_aliases={6: 0},  # zeros operand -> output
            interpret=interpret,
        )(base_, gi_, first_, last_, cells_, chans_, zeros_)

    # vmap of a pallas_call whose scalar-prefetch operands are batched
    # (the gspmd dispatch vmaps this whole stage over the shard axis)
    # falls back to jax's explicit batch loop, whose weak-typed
    # fori_loop counter lands as s64 under x64; the SPMD partitioner
    # then compares that s64 update index against its own s32 shard
    # offsets and the HLO verifier rejects the module ("Binary op
    # compare with different element types: s64[] and s32[]"). The
    # batch axis is the static shard count, so unroll it instead:
    # constant-index slices of a shard-dim-sharded operand are exactly
    # what the partitioner handles natively — no dynamic update index
    # of either width, and no per-iteration collectives either.
    kernel_call = jax.custom_batching.custom_vmap(_kernel_call)

    @kernel_call.def_vmap
    def _kernel_vmap_rule(axis_size, in_batched, *args):
        outs = [
            _kernel_call(*[a[i] if b else a
                           for a, b in zip(args, in_batched)])
            for i in range(axis_size)
        ]
        return jnp.stack(outs), True

    blocks = kernel_call(
        base, gi, first_visit, last_visit,
        cells.reshape(nck, 1, chunk),
        chans.reshape(n_channels, nck, chunk).transpose(1, 0, 2),
        zeros)
    if streams > 1:
        blocks = blocks.reshape(
            streams, n_blocks, n_channels, side, side
        ).sum(axis=0)
    dense = blocks.transpose(1, 0, 2, 3).reshape(
        n_channels, n_blocks * block_cells
    )[:, :capacity]
    return dense.astype(jnp.float64)


@functools.partial(
    jax.jit,
    static_argnames=("capacity", "chunk", "block_cells", "slab",
                     "interpret", "streams", "weight_bound"),
)
def aggregate_sorted_keys_partitioned(
    sorted_keys,
    capacity: int,
    sentinel=None,
    chunk: int = DEFAULT_CHUNK,
    block_cells: int = DEFAULT_BLOCK_CELLS,
    slab: int = DEFAULT_SLAB,
    interpret: bool | None = None,
    streams: int = 1,
    sorted_weights=None,
    weight_bound: int | None = None,
):
    """``aggregate_sorted_keys`` on the partitioned kernel.

    Contract matches ops.sparse.aggregate_sorted_keys: returns
    (unique[capacity] int64, sums[capacity], n_unique); slots past
    n_unique hold sentinel/zero; exact at ANY per-key fan-in
    (slab-wise f32 accumulation, f64 combine). With the default unit
    weights, sums are int32 counts. ``slab`` is the most lanes one
    slab may hold (a parameter so tests can exercise the multi-slab
    combine at small sizes); it must be a multiple of
    ``streams * chunk``. The stream pads only to a whole number of
    ``streams * chunk`` lanes, so every slab but the last holds
    ``slab`` lanes and the last holds what remains. Each slab's
    straddling chunks go to a scatter tail of at most
    ``ceil(capacity / block_cells) - 1`` chunks, a bound the sorted
    stream guarantees (_chunk_kinds). ``streams`` splits each slab into
    contiguous sub-streams with per-stream output slabs (see
    _channel_path; bit-identical results, measured for grid pipelining
    on-chip before any default flips — costs ``streams`` x the
    output-blocks buffer).

    ``sorted_weights`` (same order as ``sorted_keys``) switches to the
    weighted 5-channel form: sums are f64 per-key weight totals, exact
    PROVIDED every weight is an integer in ``[0, weight_bound]``
    (required, static) — the exactness slab shrinks to
    ``2^24 // weight_bound`` elements (see module docstring). Weights
    violating the contract are detected ON DEVICE and poison
    ``n_unique`` past ``capacity`` — the repo-wide overflow signal —
    so a fractional or oversized weight can never produce a silently
    rounded sum. Fractional weights are fundamentally outside this
    kernel (f32 products round before accumulation; no slab size
    restores exactness): use the scatter path.
    """
    if interpret is None:
        interpret = default_interpret()
    # Runs at trace time: the mode this kernel is lowered in.
    obs_events.emit("pallas_kernel", kernel="segment",
                    interpret=bool(interpret), n=int(sorted_keys.shape[0]))
    keys = jnp.asarray(sorted_keys)
    if sentinel is None:
        sentinel = jnp.iinfo(keys.dtype).max
    if keys.dtype != jnp.int64:
        keys = keys.astype(jnp.int64)
        sentinel = jnp.int64(sentinel)
    n = keys.shape[0]
    if streams < 1:
        raise ValueError(f"streams must be >= 1, got {streams}")
    weighted = sorted_weights is not None
    if weighted:
        if weight_bound is None or weight_bound < 1:
            raise ValueError(
                "weighted partitioned reduction needs a positive "
                "static weight_bound (exactness slab = 2^24 // bound)"
            )
        # Shrink the slab so per-cell per-slab partials stay integers
        # < 2^24 (f32-exact); it must stay a multiple of streams*chunk.
        # When the bound is so large that even ONE chunk row per stream
        # exceeds the exactness budget, no slab size can keep the f32
        # accumulator exact — refuse loudly instead of silently
        # flooring the slab and rounding sums (the kernel's whole
        # contract is "never a silently rounded sum").
        unit = streams * chunk
        exact_slab = ((1 << 24) // weight_bound) // unit * unit
        if exact_slab < unit:
            raise ValueError(
                f"weight_bound {weight_bound} is too large for the "
                f"exactness slab: 2^24 // bound = "
                f"{(1 << 24) // weight_bound} elements, below one "
                f"chunk row per stream (streams*chunk = {unit}) — "
                f"shrink chunk/streams or the bound (max bound at "
                f"this geometry: {(1 << 24) // unit}), or use the "
                "scatter backend"
            )
        slab = min(slab, exact_slab)
    if slab % (streams * chunk):
        raise ValueError(
            f"slab {slab} must be a multiple of streams*chunk "
            f"({streams}*{chunk})"
        )
    side = 1 << (block_cells.bit_length() // 2)
    if side * side != block_cells or side < 64:
        raise ValueError(
            f"block_cells must be an even power of two >= 4096, "
            f"got {block_cells}"
        )

    is_real = keys != sentinel
    first = jnp.concatenate(
        [jnp.ones((1,), bool), keys[1:] != keys[:-1]]
    ) & is_real
    seg = jnp.cumsum(first.astype(jnp.int32)) - 1
    cells = jnp.where(is_real, seg, capacity)  # capacity == drop
    n_unique = jnp.sum(first.astype(jnp.int32))

    # Channels: counts (or weighted sums + presence) + the
    # segment-first element's key in 20-bit pieces (one nonzero
    # contribution per segment -> f32-exact).
    fw = first.astype(jnp.float32)
    mask = (1 << KEY_BITS) - 1
    pieces = [
        fw * ((keys >> 0) & mask).astype(jnp.float32),
        fw * ((keys >> KEY_BITS) & mask).astype(jnp.float32),
        fw * ((keys >> (2 * KEY_BITS)) & mask).astype(jnp.float32),
    ]
    if weighted:
        wts = jnp.asarray(sorted_weights)
        # Contract check ON DEVICE: integers in [0, weight_bound].
        # Violations poison n_unique (the overflow signal) below —
        # never a silently rounded sum.
        wf64 = wts.astype(jnp.float64)
        bad_weights = (
            (wf64 != jnp.floor(wf64)) | (wf64 < 0)
            | (wf64 > weight_bound)
        ) & is_real
        weights_invalid = bad_weights.any()
        w32 = jnp.where(is_real, wts.astype(jnp.float32), 0.0)
        chans = jnp.stack([w32, is_real.astype(jnp.float32)] + pieces)
        n_channels = N_CHANNELS_WEIGHTED
    else:
        chans = jnp.stack([is_real.astype(jnp.float32)] + pieces)
        n_channels = N_CHANNELS

    # Pad to whole chunk rows only: the last slab is as long as what
    # remains of the stream, not a whole ``slab``.
    unit = streams * chunk
    n_pad = max(unit, -(-n // unit) * unit)
    if n_pad != n:
        cells = jnp.concatenate(
            [cells, jnp.full(n_pad - n, capacity, cells.dtype)]
        )
        chans = jnp.concatenate(
            [chans, jnp.zeros((n_channels, n_pad - n), jnp.float32)], axis=1
        )

    n_blocks = -(-capacity // block_cells)
    sums = jnp.zeros((n_channels, capacity), jnp.float64)
    for start in range(0, n_pad, slab):  # static unroll: ~n/2^24 slabs
        c_slab = cells[start : start + slab]
        ch_slab = chans[:, start : start + slab]
        good, straddle = _chunk_kinds(c_slab, chunk, block_cells, capacity)
        with jax.named_scope("slab.channel"):
            sums = sums + _channel_path(
                c_slab, ch_slab, good, capacity, n_blocks, chunk,
                interpret, block_cells, side, streams=streams,
                n_channels=n_channels,
            )
        size = _tail_chunks(straddle.shape[0], capacity, block_cells)
        if size:  # one block straddles nothing
            with jax.named_scope("slab.tail"):
                sums = sums + _tail_path(c_slab, ch_slab, straddle,
                                         capacity, chunk, size, n_channels)

    pc = 1 if weighted else 0  # presence channel index
    present = jnp.round(sums[pc]) > 0
    key_lo = jnp.round(sums[pc + 1]).astype(jnp.int64)
    key_mid = jnp.round(sums[pc + 2]).astype(jnp.int64)
    key_hi = jnp.round(sums[pc + 3]).astype(jnp.int64)
    unique = key_lo | (key_mid << KEY_BITS) | (key_hi << (2 * KEY_BITS))
    unique = jnp.where(present, unique, sentinel)
    if weighted:
        totals = jnp.where(present, sums[0], 0.0)
        n_unique = jnp.where(
            weights_invalid,
            jnp.maximum(n_unique, capacity + 1), n_unique,
        )
        return unique, totals, n_unique
    counts = jnp.round(sums[0]).astype(jnp.int32)
    return unique, counts, n_unique
