"""Multi-channel partitioned segment reduction (ops.sparse_partitioned),
interpret mode: bit-equal to ops.sparse.aggregate_sorted_keys on every
path — good-chunk matmuls, bounded bad tails, the full-scatter
fallback, and the multi-slab exactness combine."""

import numpy as np
import jax.numpy as jnp
import pytest

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


@pytest.mark.slow
def test_clustered_runs_good_chunks():
    """Long runs (few segments per chunk) take the matmul path."""
    rng = np.random.default_rng(0)
    keys = np.repeat(rng.choice(1 << 40, 40, replace=False),
                     rng.integers(100, 900, 40))
    assert _diff(keys, capacity=1 << 12) == 40


@pytest.mark.slow
def test_mostly_unique_keys():
    """Run length ~1: every chunk spans many segments, but segments are
    dense so chunks still land inside blocks."""
    rng = np.random.default_rng(1)
    keys = rng.choice(1 << 50, 30_000, replace=False)
    _diff(keys, capacity=30_000)


@pytest.mark.slow
def test_sentinel_padding_and_drop():
    rng = np.random.default_rng(2)
    keys = np.concatenate([
        rng.integers(0, 1 << 30, 5000),
        np.full(3000, SENTINEL),
    ])
    _diff(keys, capacity=8192)


@pytest.mark.slow
def test_multi_slab_combine_exact():
    """slab smaller than the stream: per-slab partials must combine to
    the global counts, including segments straddling slab boundaries
    and per-key fan-in far above one slab's contribution."""
    rng = np.random.default_rng(3)
    keys = np.repeat(rng.choice(1 << 35, 13, replace=False),
                     rng.integers(500, 4000, 13))
    n = _diff(keys, capacity=4096, slab=4096)
    assert n == 13


@pytest.mark.slow
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


@pytest.mark.slow
def test_58_bit_keys_reconstruct():
    """Cascade-scale composite keys (58 bits) round-trip through the
    three 20-bit channels."""
    rng = np.random.default_rng(4)
    keys = rng.integers(1 << 57, 1 << 58, 3000, dtype=np.int64)
    _diff(keys, capacity=4096)


@pytest.mark.slow
def test_hostile_distribution_falls_back():
    """capacity-spanning sparse segments make most chunks straddle
    blocks -> the lax.cond scatter fallback must match too."""
    rng = np.random.default_rng(5)
    # Unique keys + big capacity: segments land far apart in cell space
    # relative to block_cells, so chunks straddle constantly with a
    # tiny block size.
    keys = rng.choice(1 << 45, 20_000, replace=False)
    _diff(keys, capacity=1 << 18, block_cells=1 << 12)


@pytest.mark.slow
def test_empty_and_tiny():
    _diff(np.empty(0, np.int64), capacity=64)
    _diff(np.asarray([7]), capacity=64)
    _diff(np.asarray([7, 7, 8]), capacity=64)


@pytest.mark.slow
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


@pytest.mark.slow
def test_weighted_integer_bit_exact():
    """Clustered integer weights: bit-equal to the f64 scatter path."""
    rng = np.random.default_rng(11)
    keys = np.repeat(rng.choice(1 << 40, 40, replace=False),
                     rng.integers(100, 900, 40))
    w = rng.integers(0, 1000, keys.size)
    _diff_weighted(keys, w, capacity=1 << 12, weight_bound=1000)


@pytest.mark.slow
def test_weighted_zero_sum_segment_survives():
    """A segment whose weights all sum to zero must keep its key (the
    presence channel exists exactly for this)."""
    keys = np.asarray([5, 5, 9, 9, 9, 12], np.int64)
    w = np.asarray([0, 0, 3, 4, 0, 7], np.float64)
    _diff_weighted(keys, w, capacity=64, weight_bound=8)


@pytest.mark.slow
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


@pytest.mark.slow
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


@pytest.mark.slow
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


@pytest.mark.slow
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
