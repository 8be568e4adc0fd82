"""Tests for aggregation ops: dense histograms, sparse reduce, pyramids."""

import collections
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from heatmap_tpu.ops import (
    Window,
    bin_points_window,
    bin_rowcol_window,
    coarsen_raster,
    pyramid_from_raster,
    pyramid_sparse_morton,
    window_from_bounds,
    aggregate_keys,
    aggregate_sorted_keys,
)
from heatmap_tpu.tilemath import mercator, morton
import oracle


def _rand_points(n, seed=0, lat=(30.0, 60.0), lon=(-10.0, 30.0)):
    rng = np.random.default_rng(seed)
    return rng.uniform(*lat, n), rng.uniform(*lon, n)


# -- Window ----------------------------------------------------------------


def test_window_validation():
    Window(zoom=4, row0=0, col0=0, height=16, width=16)
    with pytest.raises(ValueError):
        Window(zoom=4, row0=8, col0=0, height=16, width=16)
    with pytest.raises(ValueError):
        Window(zoom=4, row0=0, col0=-1, height=4, width=4)


def test_window_from_bounds_covers_points():
    lats, lons = _rand_points(2000, seed=1)
    win = window_from_bounds((30.0, 60.0), (-10.0, 30.0), zoom=10, align_levels=3)
    assert win.aligned_to(3)
    row, col, valid = mercator.project_points(lats, lons, 10)
    assert bool(valid.all())
    r = np.asarray(row)
    c = np.asarray(col)
    assert (r >= win.row0).all() and (r < win.row0 + win.height).all()
    assert (c >= win.col0).all() and (c < win.col0 + win.width).all()


def test_window_pad_multiple_stays_in_grid():
    win = window_from_bounds((84.0, 85.0), (170.0, 179.9), zoom=6, pad_multiple=16)
    assert win.row0 + win.height <= 1 << 6
    assert win.col0 + win.width <= 1 << 6
    assert win.height % 16 == 0


def test_window_rejects_empty_and_polar_bounds():
    with pytest.raises(ValueError):
        Window(zoom=4, row0=0, col0=0, height=0, width=4)
    with pytest.raises(ValueError):
        Window(zoom=4, row0=0, col0=0, height=-8, width=4)
    # Bbox entirely poleward of the mercator edge covers no tiles.
    with pytest.raises(ValueError):
        window_from_bounds((86.0, 89.0), (10.0, 20.0), zoom=8)


def test_window_pad_uses_lcm_not_product():
    # align 2^3=8 with pad_multiple=16 -> quantum lcm=16, not 128.
    win = window_from_bounds(
        (52.4, 52.6), (13.3, 13.5), zoom=12, align_levels=3, pad_multiple=16
    )
    assert win.height % 16 == 0 and win.width % 16 == 0
    assert win.aligned_to(3)
    assert win.height <= 32 and win.width <= 32


def test_morton_encode_zoom_guard():
    with pytest.raises(ValueError):
        morton.morton_encode(np.int32(0), np.int32(0), dtype=jnp.int32, zoom=16)
    morton.morton_encode(np.int32(0), np.int32(0), dtype=jnp.int32, zoom=15)


# -- dense histogram -------------------------------------------------------


def test_bin_points_window_matches_numpy():
    lats, lons = _rand_points(10_000, seed=2)
    zoom = 10
    win = window_from_bounds((30.0, 60.0), (-10.0, 30.0), zoom=zoom)
    raster = np.asarray(bin_points_window(lats, lons, win))
    assert raster.sum() == 10_000

    expected = np.zeros(win.shape, np.int64)
    for la, lo in zip(lats, lons):
        r = int(oracle.row_from_latitude(la, zoom)) - win.row0
        c = int(oracle.column_from_longitude(lo, zoom)) - win.col0
        expected[r, c] += 1
    np.testing.assert_array_equal(raster, expected)


def test_bin_weighted_and_out_of_window_drop():
    win = Window(zoom=5, row0=8, col0=8, height=4, width=4)
    rows = np.array([8, 8, 9, 0, 31], np.int32)  # last two outside
    cols = np.array([8, 8, 11, 0, 31], np.int32)
    w = np.array([1.5, 2.5, 3.0, 100.0, 100.0], np.float32)
    raster = np.asarray(bin_rowcol_window(rows, cols, win, weights=w))
    assert raster.dtype == np.float32
    assert raster.sum() == pytest.approx(7.0)
    assert raster[0, 0] == pytest.approx(4.0)
    assert raster[1, 3] == pytest.approx(3.0)


def test_bin_respects_valid_mask():
    win = Window(zoom=8, row0=0, col0=0, height=8, width=8)
    rows = np.array([0, 1], np.int32)
    cols = np.array([0, 1], np.int32)
    valid = np.array([True, False])
    raster = np.asarray(bin_rowcol_window(rows, cols, win, valid=valid))
    assert raster.sum() == 1


def test_bin_points_jit_compatible():
    win = Window(zoom=10, row0=0, col0=0, height=64, width=64)
    lats = np.full(100, 84.5)
    lons = np.full(100, -179.0)

    fn = jax.jit(lambda la, lo: bin_points_window(la, lo, win))
    raster = np.asarray(fn(lats, lons))
    assert raster.sum() == 100


# -- pyramid (dense) -------------------------------------------------------


def test_coarsen_raster():
    r = jnp.arange(16, dtype=jnp.int32).reshape(4, 4)
    c = np.asarray(coarsen_raster(r))
    np.testing.assert_array_equal(c, [[10, 18], [42, 50]])
    with pytest.raises(ValueError):
        coarsen_raster(jnp.zeros((3, 4)))


def test_pyramid_preserves_totals_and_alignment():
    lats, lons = _rand_points(5000, seed=3)
    zoom, levels = 12, 5
    win = window_from_bounds((30.0, 60.0), (-10.0, 30.0), zoom=zoom, align_levels=levels)
    raster = bin_points_window(lats, lons, win)
    pyr = pyramid_from_raster(raster, levels)
    assert len(pyr) == levels + 1
    for lvl, level_raster in enumerate(pyr):
        assert int(level_raster.sum()) == 5000
        assert level_raster.shape == (win.height >> lvl, win.width >> lvl)

    # Level counts must equal direct binning at the coarser zoom
    # (the shift-pyramid == reference center-re-projection contract).
    for lvl in (1, 3, 5):
        sub_zoom = zoom - lvl
        sub_win = Window(
            zoom=sub_zoom,
            row0=win.row0 >> lvl,
            col0=win.col0 >> lvl,
            height=win.height >> lvl,
            width=win.width >> lvl,
        )
        direct = np.asarray(bin_points_window(lats, lons, sub_win))
        np.testing.assert_array_equal(np.asarray(pyr[lvl]), direct)


# -- sparse ----------------------------------------------------------------


def test_aggregate_keys_matches_counter():
    rng = np.random.default_rng(4)
    keys = rng.integers(0, 50, 1000).astype(np.int32)
    uniq, sums, n = aggregate_keys(keys)
    n = int(n)
    expected = collections.Counter(keys.tolist())
    assert n == len(expected)
    got = dict(zip(np.asarray(uniq[:n]).tolist(), np.asarray(sums[:n]).tolist()))
    assert got == {int(k): int(v) for k, v in expected.items()}
    # Sorted ascending, sentinel-padded.
    assert np.all(np.diff(np.asarray(uniq[:n])) > 0)
    assert np.all(np.asarray(uniq[n:]) == np.iinfo(np.int32).max)
    assert np.asarray(sums[n:]).sum() == 0


def test_aggregate_keys_weighted_valid_capacity():
    keys = np.array([5, 5, 3, 3, 3, 9], np.int32)
    w = np.array([1.0, 2.0, 10.0, 20.0, 30.0, 7.0], np.float32)
    valid = np.array([True, True, True, True, True, False])
    uniq, sums, n = aggregate_keys(keys, weights=w, valid=valid, capacity=4)
    assert int(n) == 2
    np.testing.assert_array_equal(np.asarray(uniq[:2]), [3, 5])
    np.testing.assert_allclose(np.asarray(sums[:2]), [60.0, 3.0])


def test_aggregate_keys_capacity_overflow_drops():
    keys = np.array([1, 2, 3, 4], np.int32)
    uniq, sums, n = aggregate_keys(keys, capacity=2)
    # n reports true uniques; only first `capacity` sorted keys materialize.
    assert int(n) == 4
    np.testing.assert_array_equal(np.asarray(uniq), [1, 2])


def test_aggregate_keys_jit():
    fn = jax.jit(lambda k: aggregate_keys(k, capacity=8))
    uniq, sums, n = fn(jnp.asarray(np.array([2, 2, 7], np.int32)))
    assert int(n) == 2


SEGMENT_CASES = ("fractional", "counts", "sentinel_lanes", "all_invalid",
                 "one_segment", "n1", "capacity_below", "capacity_above")


def _segment_case(case):
    """-> (keys, weights or None, valid, capacity) for one contract case."""
    rng = np.random.default_rng(sorted(SEGMENT_CASES).index(case))
    n, distinct, capacity, dtype = 3000, 400, None, np.int64
    if case == "counts":
        dtype = np.int32
    elif case == "one_segment":
        distinct = 1
    elif case == "n1":
        n = 1
    elif case == "capacity_below":
        capacity = 100
    elif case == "capacity_above":
        capacity = 2 * n
    keys = rng.integers(0, distinct, n).astype(dtype) * 7 + 3
    # Day-scale dwell seconds: fractional, spread over four decades.
    weights = None if case == "counts" else rng.exponential(30.0, n) * 10.0 ** (
        rng.integers(-2, 2, n))
    valid = np.ones(n, bool)
    if case in ("sentinel_lanes", "counts"):
        valid = rng.random(n) < 0.8
    elif case == "all_invalid":
        valid[:] = False
    return keys, weights, valid, n if capacity is None else capacity


@pytest.mark.parametrize("entry", ["aggregate_keys", "aggregate_sorted_keys"])
@pytest.mark.parametrize("case", SEGMENT_CASES)
def test_segment_reduce_contract(case, entry):
    """Both entry points against a numpy oracle that sums each segment
    with ``math.fsum``: ascending uniques, sentinel key and zero sum past
    ``n_unique``, the first ``capacity`` segments kept when there are
    more, ``n_unique`` the true count, and masked lanes adding nothing."""
    keys, weights, valid, capacity = _segment_case(case)
    sentinel = np.iinfo(keys.dtype).max
    acc = np.int32 if weights is None else np.float64
    w = np.ones(len(keys), acc) if weights is None else weights
    if entry == "aggregate_keys":
        uniq, sums, n = aggregate_keys(
            keys, weights=weights, valid=valid, capacity=capacity,
            acc_dtype=acc)
    else:
        # Masked lanes become sentinel keys carrying a weight they must
        # not add anywhere.
        order = np.argsort(np.where(valid, keys, sentinel), kind="stable")
        sk = np.where(valid, keys, sentinel)[order]
        sw = np.where(valid, w, 1000)[order]
        uniq, sums, n = aggregate_sorted_keys(
            jnp.asarray(sk), jnp.asarray(sw.astype(acc)), capacity)
    uniq, sums = np.asarray(uniq), np.asarray(sums)

    real = keys[valid]
    want_u = np.unique(real)
    want_s = [math.fsum(w[valid][real == u]) for u in want_u[:capacity]]
    kept = min(len(want_u), capacity)
    assert int(n) == len(want_u)
    assert uniq.shape == sums.shape == (capacity,)
    assert sums.dtype == acc
    np.testing.assert_array_equal(uniq[:kept], want_u[:capacity])
    assert (uniq[kept:] == sentinel).all() and (sums[kept:] == 0).all()
    if weights is None:
        np.testing.assert_array_equal(sums[:kept], want_s)
    else:
        np.testing.assert_allclose(sums[:kept], want_s, rtol=1e-12, atol=0)


# -- sparse morton pyramid -------------------------------------------------


def test_pyramid_sparse_morton_matches_counters():
    rng = np.random.default_rng(5)
    zoom, levels = 12, 4
    rows = rng.integers(0, 1 << zoom, 3000).astype(np.int32)
    cols = rng.integers(0, 1 << zoom, 3000).astype(np.int32)
    codes = np.asarray(morton.morton_encode(rows, cols, dtype=jnp.int32))

    out = pyramid_sparse_morton(jnp.asarray(codes), levels=levels, capacity=3000)
    assert len(out) == levels + 1
    for lvl, (uniq, sums, n) in enumerate(out):
        n = int(n)
        expected = collections.Counter(
            zip((rows >> lvl).tolist(), (cols >> lvl).tolist())
        )
        assert n == len(expected)
        u = np.asarray(uniq[:n])
        s = np.asarray(sums[:n])
        dec_r, dec_c = morton.morton_decode(jnp.asarray(u))
        got = dict(
            zip(
                zip(np.asarray(dec_r).tolist(), np.asarray(dec_c).tolist()),
                s.tolist(),
            )
        )
        assert got == dict(expected)
        assert int(s.sum()) == 3000


@pytest.mark.slow
def test_pyramid_sparse_morton_adaptive_matches_fixed():
    """adaptive=True shrinks level arrays but the aggregates (and the
    true unique counts overflow detection relies on) are identical."""
    rng = np.random.default_rng(9)
    codes = jnp.asarray(rng.integers(0, 1 << 18, 5000), jnp.int64)
    fixed = pyramid_sparse_morton(codes, levels=6)
    adapt = pyramid_sparse_morton(codes, levels=6, adaptive=True)
    for (fk, fs, fn), (ak, as_, an) in zip(fixed, adapt):
        n = int(fn)
        assert int(an) == n
        np.testing.assert_array_equal(np.asarray(fk)[:n], np.asarray(ak)[:n])
        np.testing.assert_array_equal(np.asarray(fs)[:n], np.asarray(as_)[:n])
    assert adapt[-1][0].shape[0] < fixed[-1][0].shape[0]


def test_pyramid_sparse_morton_adaptive_keeps_overflow_detectable():
    """A per-level capacity smaller than the real unique count must
    still report the TRUE count under adaptive=True — the input slice
    may never drop real aggregates pre-reduction (that would falsify
    n_unique and silently truncate sums)."""
    rng = np.random.default_rng(10)
    # ~2000 distinct level-0 codes whose parents stay ~distinct.
    codes = jnp.asarray(rng.permutation(1 << 14)[:2000] * 4, jnp.int64)
    caps = [4096, 64]  # level-1 capacity far below the real uniques
    fixed = pyramid_sparse_morton(codes, levels=1, capacity=caps)
    adapt = pyramid_sparse_morton(codes, levels=1, capacity=caps,
                                  adaptive=True)
    true_n = int(fixed[1][2])
    assert true_n > 64  # the scenario is real
    assert int(adapt[1][2]) == true_n  # overflow stays detectable


def test_pyramid_sparse_morton_weighted_with_invalid():
    zoom = 6
    rows = np.array([1, 1, 2, 3], np.int32)
    cols = np.array([1, 1, 2, 3], np.int32)
    codes = morton.morton_encode(rows, cols, dtype=jnp.int32)
    w = np.array([1.0, 2.0, 4.0, 8.0], np.float32)
    valid = np.array([True, True, True, False])
    out = pyramid_sparse_morton(
        codes, weights=w, valid=valid, levels=zoom, capacity=4
    )
    # Top level: everything in one root tile, sum excludes invalid lane.
    uniq, sums, n = out[-1]
    assert int(n) == 1
    assert float(sums[0]) == pytest.approx(7.0)
    assert int(uniq[0]) == 0


def test_aggregate_keys_sentinel_reservation_documented():
    # intmax keys are reserved as sentinel and dropped; pinned behavior.
    uniq, sums, n = aggregate_keys(np.array([5, np.iinfo(np.int32).max], np.int32))
    assert int(n) == 1 and int(sums[0]) == 1


def test_window_from_bounds_rejects_impossible_alignment():
    with pytest.raises(ValueError):
        window_from_bounds((30, 60), (-10, 30), zoom=3, align_levels=5)


def test_pick_backend_weighted_large_window_routes_partitioned(monkeypatch):
    """On TPU, auto routes large-window WEIGHTED binning to the
    partitioned MXU path. The platform is faked: the routing decision is host-side and must not
    need a chip to be testable."""
    import types

    from heatmap_tpu.ops import histogram

    monkeypatch.setattr(
        jax, "devices",
        lambda *a, **k: [types.SimpleNamespace(platform="tpu")])
    big = histogram.Window(zoom=15, row0=0, col0=0, height=1024, width=1280)
    assert big.height * big.width > histogram.PALLAS_AUTO_MAX_CELLS
    assert histogram._pick_backend("auto", big, weighted=True) == "partitioned"
    assert histogram._pick_backend("auto", big, weighted=False) == "partitioned"
    # Small windows keep the pallas route; explicit backends pass through.
    small = histogram.Window(zoom=10, row0=0, col0=0, height=64, width=64)
    assert histogram._pick_backend("auto", small, weighted=True) == "pallas"
    assert histogram._pick_backend("xla", big, weighted=True) == "xla"
