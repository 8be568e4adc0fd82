"""Multi-channel partitioned segment reduction (ops.sparse_partitioned),
interpret mode: bit-equal to ops.sparse.aggregate_sorted_keys on every
path — kernel-chunk matmuls, the bounded straddle tail, and the
multi-slab exactness combine — with the tail's static bound held on
random sorted streams."""

import functools

import jax
import jax.extend.core as jex
import numpy as np
import jax.numpy as jnp
import pytest

from heatmap_tpu.ops import sparse_partitioned as sp
from heatmap_tpu.ops.sparse import aggregate_sorted_keys
from heatmap_tpu.ops.sparse_partitioned import (
    aggregate_sorted_keys_partitioned,
)

SENTINEL = np.iinfo(np.int64).max


def _diff(sorted_keys, capacity, **kw):
    sorted_keys = jnp.asarray(np.sort(np.asarray(sorted_keys)), jnp.int64)
    want_u, want_s, want_n = aggregate_sorted_keys(
        sorted_keys, jnp.ones(len(sorted_keys), jnp.int32), capacity,
        sentinel=SENTINEL,
    )
    got_u, got_s, got_n = aggregate_sorted_keys_partitioned(
        sorted_keys, capacity, interpret=True, **kw
    )
    assert int(got_n) == int(want_n)
    n = min(int(want_n), capacity)
    np.testing.assert_array_equal(np.asarray(got_u)[:n],
                                  np.asarray(want_u)[:n])
    np.testing.assert_array_equal(np.asarray(got_s)[:n],
                                  np.asarray(want_s)[:n])
    # Padding slots: sentinel keys, zero counts — both contracts.
    assert (np.asarray(got_u)[n:] == SENTINEL).all()
    assert (np.asarray(got_s)[n:] == 0).all()
    return int(want_n)


def test_clustered_runs_good_chunks():
    """Long runs (few segments per chunk) take the matmul path."""
    rng = np.random.default_rng(0)
    keys = np.repeat(rng.choice(1 << 40, 40, replace=False),
                     rng.integers(100, 900, 40))
    assert _diff(keys, capacity=1 << 12) == 40


def test_mostly_unique_keys():
    """Run length ~1: every chunk spans many segments, but segments are
    dense so chunks still land inside blocks."""
    rng = np.random.default_rng(1)
    keys = rng.choice(1 << 50, 30_000, replace=False)
    _diff(keys, capacity=30_000)


def test_sentinel_padding_and_drop():
    rng = np.random.default_rng(2)
    keys = np.concatenate([
        rng.integers(0, 1 << 30, 5000),
        np.full(3000, SENTINEL),
    ])
    _diff(keys, capacity=8192)


def test_multi_slab_combine_exact():
    """slab smaller than the stream: per-slab partials must combine to
    the global counts, including segments straddling slab boundaries
    and per-key fan-in far above one slab's contribution."""
    rng = np.random.default_rng(3)
    keys = np.repeat(rng.choice(1 << 35, 13, replace=False),
                     rng.integers(500, 4000, 13))
    n = _diff(keys, capacity=4096, slab=4096)
    assert n == 13


def test_single_hot_key_fanin_beyond_slab():
    """One segment larger than several slabs: counts must stay exact
    (the f32-per-slab / f64-combine design point)."""
    keys = np.full(40_000, 123456789)
    got_u, got_s, got_n = aggregate_sorted_keys_partitioned(
        jnp.asarray(keys, jnp.int64), 64, slab=8192, interpret=True,
    )
    assert int(got_n) == 1
    assert int(got_s[0]) == 40_000
    assert int(got_u[0]) == 123456789


def test_58_bit_keys_reconstruct():
    """Cascade-scale composite keys (58 bits) round-trip through the
    three 20-bit channels."""
    rng = np.random.default_rng(4)
    keys = rng.integers(1 << 57, 1 << 58, 3000, dtype=np.int64)
    _diff(keys, capacity=4096)


def test_hostile_distribution_through_bounded_tail():
    """Capacity-spanning sparse segments with a tiny block size: the
    input that once forced the full-scatter fallback is bit-equal, its
    last real lanes riding the bounded tail (their chunk ends in drop
    lanes, whose ids clamp into the last of 64 blocks)."""
    rng = np.random.default_rng(5)
    keys = rng.choice(1 << 45, 20_000, replace=False)
    _diff(keys, capacity=1 << 18, block_cells=1 << 12)
    assert _straddles(keys, 1 << 18, 1 << 12) == [1]


def _straddles(keys, capacity, block_cells, chunk=sp.DEFAULT_CHUNK,
               slab=sp.DEFAULT_SLAB, streams=1):
    """Straddling chunks per slab, as the kernel lays the stream out
    (dense segment ids, drop lanes at ``capacity``, padded to whole
    ``streams * chunk`` rows), each checked against the static tail."""
    k = np.sort(np.asarray(keys, np.int64))
    real = k != SENTINEL
    first = np.concatenate([[True], k[1:] != k[:-1]])[: k.size] & real
    cells = np.where(real, np.cumsum(first) - 1, capacity)
    unit = streams * chunk
    n_pad = max(unit, -(-k.size // unit) * unit)
    cells = np.concatenate([cells, np.full(n_pad - k.size, capacity)])
    out = []
    for start in range(0, n_pad, slab):
        part = jnp.asarray(cells[start : start + slab], jnp.int32)
        good, straddle = sp._chunk_kinds(part, chunk, block_cells, capacity)
        got = int(straddle.sum())
        assert got <= sp._tail_chunks(straddle.shape[0], capacity,
                                      block_cells)
        assert not bool((good & straddle).any())
        out.append(got)
    return out


def _sorted_stream(seed, n, n_unique, n_sentinel):
    """Sorted keys with random run lengths (segments start mid-chunk)
    and a sentinel tail."""
    rng = np.random.default_rng(seed)
    uniq = rng.choice(1 << 50, n_unique, replace=False)
    keys = uniq[rng.integers(0, n_unique, n - n_sentinel)]
    return np.concatenate([keys, np.full(n_sentinel, SENTINEL)])


@pytest.mark.parametrize(
    "n,n_unique,n_sentinel,capacity,streams,slab,overflow", [
        (20_000, 9_000, 3_000, 9_000, 1, sp.DEFAULT_SLAB, False),
        (20_000, 15_000, 500, 9_000, 1, sp.DEFAULT_SLAB, True),
        (30_000, 20_000, 1_000, 20_000, 2, sp.DEFAULT_SLAB, False),
        (30_000, 20_000, 1_000, 20_000, 4, sp.DEFAULT_SLAB, False),
        (25_000, 16_000, 2_000, 16_384, 1, 4_096, False),
        (25_000, 16_000, 2_000, 10_000, 2, 2_048, True),
    ])
def test_straddle_tail_bound_holds(n, n_unique, n_sentinel, capacity,
                                   streams, slab, overflow):
    """Random sorted streams at the smallest block, with sentinel tails,
    several slabs or streams, and more segments than capacity: the
    straddling chunks of every slab fit the static tail, and the result
    is bit-equal to the scatter reduction."""
    keys = _sorted_stream(n + capacity + streams, n, n_unique, n_sentinel)
    block_cells, chunk = 1 << 12, 128
    per_slab = _straddles(keys, capacity, block_cells, chunk=chunk,
                          slab=slab, streams=streams)
    assert len(per_slab) == -(-n // slab)
    assert sum(per_slab) > 0  # the tail carries data in every case
    n_segments = _diff(keys, capacity, chunk=chunk, block_cells=block_cells,
                       slab=slab, streams=streams)
    assert (n_segments > capacity) == overflow


def test_straddle_tail_full_to_its_bound():
    """One repeated first key shifts every block boundary off the chunk
    grid: every boundary straddles, the tail is exactly full, and the
    result is still exact."""
    capacity, block_cells, chunk = 5 * 4096, 4096, 128
    keys = np.concatenate([[0], np.arange(capacity)])
    assert _straddles(keys, capacity, block_cells, chunk=chunk) == [
        sp._tail_chunks(-(-keys.size // chunk), capacity, block_cells)
    ] == [4]
    _diff(keys, capacity, chunk=chunk, block_cells=block_cells)


def _primitives(jaxpr):
    """Primitive names of a jaxpr and its sub-jaxprs, Pallas kernel
    bodies excepted (their ``pl.when`` steps are the grid's own)."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, jex.ClosedJaxpr):
                    yield from _primitives(sub.jaxpr)
                elif isinstance(sub, jex.Jaxpr):
                    yield from _primitives(sub)


@pytest.mark.parametrize("weighted", [False, True])
def test_no_data_dependent_branch(weighted):
    """The work is fixed by static shapes: no ``cond`` picks a path."""
    keys = jnp.asarray(np.sort(np.random.default_rng(8).integers(
        0, 1 << 40, 5000)))
    f = functools.partial(aggregate_sorted_keys_partitioned,
                          capacity=20_000, block_cells=1 << 12, chunk=128,
                          interpret=True)
    if weighted:
        jaxpr = jax.make_jaxpr(lambda k, w: f(
            k, sorted_weights=w, weight_bound=8))(keys, jnp.ones(5000))
    else:
        jaxpr = jax.make_jaxpr(f)(keys)
    names = set(_primitives(jaxpr.jaxpr))
    assert "pallas_call" in names and "scatter-add" in names
    assert "cond" not in names


def test_empty_and_tiny():
    _diff(np.empty(0, np.int64), capacity=64)
    _diff(np.asarray([7]), capacity=64)
    _diff(np.asarray([7, 7, 8]), capacity=64)


def test_pyramid_partitioned_matches_scatter_pyramid():
    """The full count pyramid: kernel variant == scatter variant at
    every level, including invalid lanes and per-level capacities."""
    from heatmap_tpu.ops.pyramid import (
        pyramid_sparse_morton,
        pyramid_sparse_morton_partitioned,
    )

    rng = np.random.default_rng(7)
    n = 20_000
    # Clustered codes with repeats (collapsing pyramid) + invalid tail.
    codes = np.sort(rng.choice(1 << 26, 700, replace=False))[
        rng.integers(0, 700, n)
    ].astype(np.int64)
    valid = rng.random(n) < 0.9
    levels = 6
    want = pyramid_sparse_morton(
        jnp.asarray(codes), valid=jnp.asarray(valid), levels=levels,
        capacity=n,
    )
    got = pyramid_sparse_morton_partitioned(
        jnp.asarray(codes), valid=jnp.asarray(valid), levels=levels,
        capacity=n, interpret=True,
    )
    for lvl, ((wu, ws, wn), (gu, gs, gn)) in enumerate(zip(want, got)):
        m = int(wn)
        assert int(gn) == m, lvl
        np.testing.assert_array_equal(np.asarray(wu)[:m],
                                      np.asarray(gu)[:m])
        np.testing.assert_array_equal(np.asarray(ws)[:m],
                                      np.asarray(gs)[:m])
        # Padding normalized to the repo-wide int64-max sentinel at
        # EVERY level (the shifted per-level sentinel must not leak).
        assert (np.asarray(gu)[m:] == SENTINEL).all(), lvl


# -- bounded-integer weighted form -----------------------------------------


def _diff_weighted(keys, weights, capacity, weight_bound, **kw):
    order = np.argsort(np.asarray(keys), kind="stable")
    sk = jnp.asarray(np.asarray(keys)[order], jnp.int64)
    sw = jnp.asarray(np.asarray(weights, np.float64)[order])
    want_u, want_s, want_n = aggregate_sorted_keys(
        sk, sw, capacity, sentinel=SENTINEL
    )
    got_u, got_s, got_n = aggregate_sorted_keys_partitioned(
        sk, capacity, interpret=True, sorted_weights=sw,
        weight_bound=weight_bound, **kw,
    )
    assert int(got_n) == int(want_n)
    n = min(int(want_n), capacity)
    np.testing.assert_array_equal(np.asarray(got_u)[:n],
                                  np.asarray(want_u)[:n])
    # Integer weights: exact f64 integers on both paths — bitwise.
    np.testing.assert_array_equal(np.asarray(got_s)[:n],
                                  np.asarray(want_s)[:n])
    assert (np.asarray(got_u)[n:] == SENTINEL).all()
    assert (np.asarray(got_s)[n:] == 0).all()


def test_weighted_integer_bit_exact():
    """Clustered integer weights: bit-equal to the f64 scatter path."""
    rng = np.random.default_rng(11)
    keys = np.repeat(rng.choice(1 << 40, 40, replace=False),
                     rng.integers(100, 900, 40))
    w = rng.integers(0, 1000, keys.size)
    _diff_weighted(keys, w, capacity=1 << 12, weight_bound=1000)


def test_weighted_zero_sum_segment_survives():
    """A segment whose weights all sum to zero must keep its key (the
    presence channel exists exactly for this)."""
    keys = np.asarray([5, 5, 9, 9, 9, 12], np.int64)
    w = np.asarray([0, 0, 3, 4, 0, 7], np.float64)
    _diff_weighted(keys, w, capacity=64, weight_bound=8)


def test_weighted_slab_shrinks_and_fanin_exact():
    """Fan-in far past the shrunk slab: per-slab integer partials
    combine exactly in f64 (weight_bound scales the slab down; force a
    tiny slab to cross boundaries many times)."""
    keys = np.full(40_000, 987654321)
    w = np.full(40_000, 255.0)
    got_u, got_s, got_n = aggregate_sorted_keys_partitioned(
        jnp.asarray(keys, jnp.int64), 64, slab=8192, interpret=True,
        sorted_weights=jnp.asarray(w), weight_bound=255,
    )
    assert int(got_n) == 1
    assert float(got_s[0]) == 40_000 * 255.0
    assert int(got_u[0]) == 987654321


@pytest.mark.parametrize("bad_w", [2.5, -1.0, 2000.0])
def test_weighted_contract_violation_is_loud(bad_w):
    """A fractional, negative, or over-bound weight poisons n_unique
    past capacity (the repo-wide overflow signal) — never a silently
    rounded sum."""
    keys = np.sort(np.random.default_rng(12).integers(0, 1000, 5000))
    w = np.ones(5000)
    w[1234] = bad_w
    _, _, got_n = aggregate_sorted_keys_partitioned(
        jnp.asarray(keys, jnp.int64), 2048, interpret=True,
        sorted_weights=jnp.asarray(w), weight_bound=1000,
    )
    assert int(got_n) > 2048


def test_weighted_requires_bound():
    with pytest.raises(ValueError, match="weight_bound"):
        aggregate_sorted_keys_partitioned(
            jnp.zeros(8, jnp.int64), 8, interpret=True,
            sorted_weights=jnp.ones(8),
        )


def test_weighted_bound_too_large_for_exactness_refused():
    """A bound whose exactness slab would fall below one chunk row per
    stream cannot be made exact by ANY slab size — it must raise, not
    silently floor the slab and round sums (review finding, round 5)."""
    with pytest.raises(ValueError, match="too large for the exactness"):
        aggregate_sorted_keys_partitioned(
            jnp.zeros(2048, jnp.int64), 64, interpret=True, chunk=1024,
            sorted_weights=jnp.ones(2048), weight_bound=20_000,
        )
    # The same bound is fine with a smaller chunk (budget restored).
    u, s, n = aggregate_sorted_keys_partitioned(
        jnp.zeros(2048, jnp.int64), 64, interpret=True, chunk=128,
        block_cells=1 << 14,
        sorted_weights=jnp.full(2048, 20_000.0), weight_bound=20_000,
    )
    assert int(n) == 1 and float(s[0]) == 2048 * 20_000.0


def test_pyramid_partitioned_weighted_matches_scatter():
    """The weighted pyramid: kernel variant == scatter variant at every
    level (f64 integer sums, invalid lanes, zero weights mixed in)."""
    from heatmap_tpu.ops.pyramid import (
        pyramid_sparse_morton,
        pyramid_sparse_morton_partitioned,
    )

    rng = np.random.default_rng(13)
    n = 20_000
    codes = np.sort(rng.choice(1 << 26, 700, replace=False))[
        rng.integers(0, 700, n)
    ].astype(np.int64)
    valid = rng.random(n) < 0.9
    w = rng.integers(0, 50, n).astype(np.float64)
    levels = 6
    want = pyramid_sparse_morton(
        jnp.asarray(codes), weights=jnp.asarray(w),
        valid=jnp.asarray(valid), levels=levels, capacity=n,
        acc_dtype=jnp.float64,
    )
    got = pyramid_sparse_morton_partitioned(
        jnp.asarray(codes), valid=jnp.asarray(valid), levels=levels,
        capacity=n, interpret=True, weights=jnp.asarray(w),
        weight_bound=50,
    )
    for lvl, ((wu, ws, wn), (gu, gs, gn)) in enumerate(zip(want, got)):
        m = int(wn)
        assert int(gn) == m, lvl
        np.testing.assert_array_equal(np.asarray(wu)[:m],
                                      np.asarray(gu)[:m])
        np.testing.assert_array_equal(np.asarray(ws)[:m],
                                      np.asarray(gs)[:m])
        assert (np.asarray(gu)[m:] == SENTINEL).all(), lvl


def test_matches_cascade_shift_reaggregation():
    """The cascade use case: re-reduce a shifted (still sorted) unique
    stream, sentinels preserved — exactly pyramid_sparse_morton's
    per-level step."""
    rng = np.random.default_rng(6)
    base = np.sort(rng.choice(1 << 30, 10_000, replace=False))
    u0, s0, n0 = aggregate_sorted_keys(
        jnp.asarray(base, jnp.int64), jnp.ones(len(base), jnp.int32),
        len(base), sentinel=SENTINEL,
    )
    parents = jnp.where(u0 == SENTINEL, SENTINEL, u0 >> 2)
    want = aggregate_sorted_keys(parents, s0, len(base), sentinel=SENTINEL)
    got = aggregate_sorted_keys_partitioned(parents, len(base),
                                            interpret=True)
    # Counts path only matches when the previous sums are unit counts
    # re-aggregated; here s0 are counts of 1 so parent sums == segment
    # sizes — the partitioned variant counts elements, which only
    # coincides when every input element carries weight 1. Verify the
    # keys agree and counts equal the number of child uniques folded in.
    nw = int(want[2])
    np.testing.assert_array_equal(np.asarray(got[0])[:nw],
                                  np.asarray(want[0])[:nw])
    np.testing.assert_array_equal(np.asarray(got[1])[:nw],
                                  np.asarray(want[1])[:nw])


def test_streams_variant_bit_equal():
    """streams>1 (per-sub-stream output slabs, summed) must be
    bit-identical to streams=1 and to the scatter contract — the
    cascade analog of the window kernel's streams=8 default."""
    rng = np.random.default_rng(11)
    n = 1 << 14
    keys = rng.choice(1 << 42, n // 16, replace=False)[
        rng.integers(0, n // 16, n)
    ].astype(np.int64)
    for streams in (2, 4):
        _diff(keys, n, slab=1 << 13, chunk=512, streams=streams)


def test_streams_with_sentinel_padding():
    rng = np.random.default_rng(12)
    n = 3000  # pads to whole slabs/chunks internally
    keys = np.concatenate([
        rng.choice(1 << 40, n - 500, replace=False).astype(np.int64),
        np.full(500, SENTINEL, np.int64),
    ])
    _diff(keys, n, slab=1 << 12, chunk=512, streams=4)


def test_streams_rejects_bad_slab():
    with pytest.raises(ValueError, match="streams"):
        aggregate_sorted_keys_partitioned(
            jnp.zeros(8, jnp.int64), 8, interpret=True,
            slab=1 << 12, chunk=512, streams=3,
        )
