"""Partitioned-MXU segment reduction for the cascade's sorted streams.

``aggregate_sorted_keys`` (ops/sparse.py) costs two scatters per
cascade level — the sums ``.at[seg].add`` and the unique-keys
``.at[seg].set`` — 32 scatters across a 16-level cascade. This module
reformulates BOTH as one pass of the sort-partitioned one-hot-matmul
machinery (ops/partitioned.py), exploiting that the cascade's inputs
are already sorted:

- the segment index ``seg = cumsum(first) - 1`` is a sorted, dense
  cell id into [0, capacity) — exactly the stream shape the
  partitioned kernel bins, with NO sort needed;
- counts stay exact at any fan-in by processing the stream in SLABS
  of at most 2^24 elements: per-slab f32 accumulation cannot round
  (every partial sum is an integer < 2^24), and slabs combine in f64
  on the way out;
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
the multi-slab and fallback paths (tests/test_sparse_partitioned.py);
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
                    chunk, block_cells, side, n_blocks,
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
    rloc, cloc = masked_local_rc(
        base_ref[i] % jnp.int32(n_blocks), good_ref[i], s_ref[0, 0, :],
        block_cells, side,
    )

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


def _good_of(cells, chunk, block_cells, capacity):
    """Per-chunk goodness: fully inside one aligned block AND free of
    dropped lanes (cell id == capacity)."""
    first = cells[::chunk]
    last = cells[chunk - 1 :: chunk]
    return (first // block_cells == last // block_cells) & (last < capacity)


def _channel_path(cells, chans, good, capacity, n_blocks, chunk,
                  bad_cap_chunks, interpret, block_cells, side,
                  streams=1, n_channels=N_CHANNELS):
    """Good chunks -> multi-channel pallas blocks; bad chunks ->
    bounded f64 scatter tails (exact: every channel is integer-valued
    below 2^52). ``good`` is the caller's per-chunk mask — the same
    one that sized the bounded tail.

    ``streams`` splits the (globally sorted) slab into that many
    contiguous sub-streams, each accumulating into its own slab of
    output blocks, summed at the end — the same grid-pipelining trick
    as the window kernel's streams default. Identical math: counts and
    key-piece channels are linear, every segment's FIRST element lands in
    exactly one sub-stream, and chunk boundaries are unchanged
    (sub-streams are whole runs of chunks), so the bad-chunk tail is
    untouched. The sub-slab sums stay f32-exact: each slab holds at
    most 2^24 elements total, so every per-cell partial and the
    cross-stream integer sum are <= 2^24."""
    L = cells.shape[0]
    nck = L // chunk
    # Forward-fill bad chunks with the last good block id per
    # sub-stream (each sub-stream is a contiguous slice of the sorted
    # slab, so good block ids are non-decreasing within it); leading
    # bads clamp to block 0, fully masked.
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
                              n_blocks=n_blocks, n_channels=n_channels),
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

    bad_idx = jnp.nonzero(~good, size=bad_cap_chunks, fill_value=nck)[0]
    bad_cells = jnp.take(cells.reshape(nck, chunk), bad_idx, axis=0,
                         mode="fill", fill_value=capacity).reshape(-1)
    tails = []
    for ch in range(n_channels):
        bad_w = jnp.take(chans[ch].reshape(nck, chunk), bad_idx, axis=0,
                         mode="fill", fill_value=0.0).reshape(-1)
        tails.append(
            jnp.zeros(capacity, jnp.float64)
            .at[bad_cells]
            .add(bad_w.astype(jnp.float64), mode="drop")
        )
    return dense.astype(jnp.float64) + jnp.stack(tails)


@functools.partial(
    jax.jit,
    static_argnames=("capacity", "chunk", "block_cells", "bad_frac",
                     "slab", "interpret", "streams", "weight_bound"),
)
def aggregate_sorted_keys_partitioned(
    sorted_keys,
    capacity: int,
    sentinel=None,
    chunk: int = DEFAULT_CHUNK,
    block_cells: int = DEFAULT_BLOCK_CELLS,
    bad_frac: int = 8,
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
    weights, sums are int32 counts. ``slab`` is a parameter so tests
    can exercise the multi-slab combine at small sizes; it must be a
    multiple of ``streams * chunk``. ``streams`` splits each slab into
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

    # Pad to whole slabs of whole chunks.
    n_slabs = max(1, -(-max(n, 1) // slab))
    n_pad = n_slabs * slab
    if n_pad != n:
        cells = jnp.concatenate(
            [cells, jnp.full(n_pad - n, capacity, cells.dtype)]
        )
        chans = jnp.concatenate(
            [chans, jnp.zeros((n_channels, n_pad - n), jnp.float32)], axis=1
        )

    n_blocks = -(-capacity // block_cells)
    sums = jnp.zeros((n_channels, capacity), jnp.float64)
    for s in range(n_slabs):  # static unroll: ~n/2^24 iterations
        c_slab = cells[s * slab : (s + 1) * slab]
        ch_slab = chans[:, s * slab : (s + 1) * slab]
        nck = slab // chunk
        bad_cap = max(2, nck // bad_frac)
        good_slab = _good_of(c_slab, chunk, block_cells, capacity)
        n_bad = (~good_slab).sum()

        # Each branch opens its own named scope, so a device trace
        # tells the kernel's slab from the full scatter fallback.
        def channel(c_, ch_, g_):
            with jax.named_scope("slab.channel"):
                return _channel_path(
                    c_, ch_, g_, capacity, n_blocks, chunk, bad_cap,
                    interpret, block_cells, side, streams=streams,
                    n_channels=n_channels,
                )

        def scatter_all(c_, ch_, g_):
            with jax.named_scope("slab.fallback"):
                return jnp.stack([
                    jnp.zeros(capacity, jnp.float64)
                    .at[c_]
                    .add(ch_[ch].astype(jnp.float64), mode="drop")
                    for ch in range(n_channels)
                ])

        slab_sums = lax.cond(
            n_bad <= bad_cap,
            channel,
            scatter_all,
            c_slab,
            ch_slab,
            good_slab,
        )
        sums = sums + slab_sums

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
