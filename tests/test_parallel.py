"""Sharded kernels on the 8-device virtual CPU mesh (SURVEY.md §4.3)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from heatmap_tpu.ops import (
    bin_points_window,
    pyramid_from_raster,
    aggregate_keys,
    window_from_bounds,
)
from heatmap_tpu.parallel import (
    aggregate_keys_sharded,
    bin_points_replicated,
    bin_points_rowsharded,
    make_mesh,
    pad_to_multiple,
    pyramid_rowsharded,
)
from heatmap_tpu.tilemath import mercator


@pytest.fixture(scope="module")
def mesh():
    return make_mesh()


def _points(n=10_007, seed=0):  # deliberately not divisible by 8
    rng = np.random.default_rng(seed)
    return (
        rng.uniform(35.0, 55.0, n),
        rng.uniform(-5.0, 20.0, n),
    )


def test_make_mesh_shapes():
    m = make_mesh()
    assert m.shape == {"data": 8, "tile": 1}
    m2 = make_mesh(data=4, tile=2)
    assert m2.shape == {"data": 4, "tile": 2}
    with pytest.raises(ValueError):
        make_mesh(data=5, tile=2)


def test_pad_to_multiple():
    a = np.arange(10, dtype=np.float32)
    (pa,), mask = pad_to_multiple([a], 8)
    assert pa.shape == (16,) and mask.sum() == 10
    (pb,), mask2 = pad_to_multiple([a], 5)
    assert pb.shape == (10,) and mask2.all()


@pytest.mark.slow
def test_replicated_binning_matches_single_device(mesh):
    lats, lons = _points()
    win = window_from_bounds((35.0, 55.0), (-5.0, 20.0), zoom=10, align_levels=3)
    (pla, plo), valid = pad_to_multiple([lats, lons], 8)
    got = np.asarray(
        bin_points_replicated(jnp.asarray(pla), jnp.asarray(plo), win, mesh,
                              valid=jnp.asarray(valid))
    )
    want = np.asarray(bin_points_window(lats, lons, win))
    np.testing.assert_array_equal(got, want)
    assert got.sum() == len(lats)


@pytest.mark.slow
def test_rowsharded_binning_matches_single_device(mesh):
    lats, lons = _points(seed=1)
    win = window_from_bounds(
        (35.0, 55.0), (-5.0, 20.0), zoom=10, align_levels=3, pad_multiple=8
    )
    (pla, plo), valid = pad_to_multiple([lats, lons], 8)
    sharded = bin_points_rowsharded(
        jnp.asarray(pla), jnp.asarray(plo), win, mesh, valid=jnp.asarray(valid)
    )
    assert sharded.shape == win.shape  # global logical shape
    want = np.asarray(bin_points_window(lats, lons, win))
    np.testing.assert_array_equal(np.asarray(sharded), want)


@pytest.mark.slow
def test_rowsharded_weighted(mesh):
    lats, lons = _points(seed=2)
    w = np.random.default_rng(3).uniform(0.0, 2.0, len(lats)).astype(np.float32)
    win = window_from_bounds(
        (35.0, 55.0), (-5.0, 20.0), zoom=9, align_levels=0, pad_multiple=8
    )
    (pla, plo, pw), valid = pad_to_multiple([lats, lons, w], 8)
    got = np.asarray(
        bin_points_rowsharded(
            jnp.asarray(pla), jnp.asarray(plo), win, mesh,
            weights=jnp.asarray(pw), valid=jnp.asarray(valid),
        )
    )
    want = np.asarray(bin_points_window(lats, lons, win, weights=w))
    np.testing.assert_allclose(got, want, rtol=1e-6)


@pytest.mark.slow
def test_pyramid_rowsharded_matches_dense(mesh):
    lats, lons = _points(seed=4)
    win = window_from_bounds(
        (35.0, 55.0), (-5.0, 20.0), zoom=11, align_levels=6, pad_multiple=8
    )
    (pla, plo), valid = pad_to_multiple([lats, lons], 8)
    sharded = bin_points_rowsharded(
        jnp.asarray(pla), jnp.asarray(plo), win, mesh, valid=jnp.asarray(valid)
    )
    levels = 6
    pyr = pyramid_rowsharded(sharded, levels, mesh)
    want_raster = bin_points_window(lats, lons, win)
    want_pyr = pyramid_from_raster(want_raster, levels)
    assert len(pyr) == levels + 1
    for got, want in zip(pyr, want_pyr):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.slow
def test_aggregate_keys_sharded_matches_local(mesh):
    rng = np.random.default_rng(5)
    keys = rng.integers(0, 500, 8 * 1000).astype(np.int32)
    w = rng.uniform(0, 1, keys.size).astype(np.float32)
    gu, gs, gn = aggregate_keys_sharded(
        jnp.asarray(keys), mesh, weights=jnp.asarray(w), capacity=1024
    )
    lu, ls, ln = aggregate_keys(jnp.asarray(keys), weights=jnp.asarray(w), capacity=8192)
    n = int(gn)
    assert n == int(ln)
    np.testing.assert_array_equal(np.asarray(gu[:n]), np.asarray(lu[:n]))
    np.testing.assert_allclose(np.asarray(gs[:n]), np.asarray(ls[:n]), rtol=1e-5)


def test_sharded_kernels_under_jit(mesh):
    # The compiled path used in production: whole step under jax.jit.
    lats, lons = _points(seed=7, n=8 * 512)
    win = window_from_bounds(
        (35.0, 55.0), (-5.0, 20.0), zoom=8, align_levels=2, pad_multiple=8
    )

    @jax.jit
    def step(la, lo):
        raster = bin_points_rowsharded(la, lo, win, mesh)
        return pyramid_rowsharded(raster, 2, mesh)

    pyr = step(jnp.asarray(lats), jnp.asarray(lons))
    want = pyramid_from_raster(bin_points_window(lats, lons, win), 2)
    for got, w in zip(pyr, want):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(w))


@pytest.mark.slow
def test_aggregate_keys_sharded_local_overflow_signal(mesh):
    # Review repro: device-local capacity overflow must surface in
    # n_unique even when the merged count looks clean.
    keys = np.concatenate(
        [np.array([0, 1, 2, 3, 4, 5], np.int32)]
        + [np.array([0, 1, 2, 3, 4, 0], np.int32)] * 7
    )
    gu, gs, gn = aggregate_keys_sharded(jnp.asarray(keys), mesh, capacity=5)
    assert int(gn) > 5  # overflow signalled (device 0 dropped key 5)
    # With capacity covering the global uniques the same data is exact.
    gu, gs, gn = aggregate_keys_sharded(jnp.asarray(keys), mesh, capacity=6)
    assert int(gn) == 6
    np.testing.assert_array_equal(np.asarray(gu[:6]), np.arange(6))


@pytest.mark.slow
def test_aggregate_keys_sharded_local_capacity_exact(mesh):
    # The knob changes padding, never results.
    rng = np.random.default_rng(8)
    keys = rng.integers(0, 100, 8 * 256).astype(np.int32)
    want_u, want_s, want_n = aggregate_keys(jnp.asarray(keys), capacity=2048)
    for lc in (100, 256, 4096):
        gu, gs, gn = aggregate_keys_sharded(
            jnp.asarray(keys), mesh, capacity=256, local_capacity=lc
        )
        n = int(want_n)
        assert int(gn) == n
        np.testing.assert_array_equal(np.asarray(gu[:n]), np.asarray(want_u[:n]))
        np.testing.assert_array_equal(np.asarray(gs[:n]), np.asarray(want_s[:n]))


# -- 2D (data x tile) meshes ----------------------------------------------


@pytest.fixture(scope="module", params=[(4, 2), (2, 4)],
                ids=["4x2", "2x4"])
def mesh2d(request):
    data, tile = request.param
    return make_mesh(data=data, tile=tile)


@pytest.mark.slow
def test_point_kernels_on_2d_mesh_match_single_device(mesh2d):
    """Existing point-parallel kernels shard over the flattened
    (data, tile) axes — tile > 1 uses all devices, same results."""
    lats, lons = _points(seed=11)
    win = window_from_bounds(
        (35.0, 55.0), (-5.0, 20.0), zoom=10, align_levels=3, pad_multiple=8
    )
    (pla, plo), valid = pad_to_multiple([lats, lons], 8)
    la, lo, v = jnp.asarray(pla), jnp.asarray(plo), jnp.asarray(valid)
    want = np.asarray(bin_points_window(lats, lons, win))
    np.testing.assert_array_equal(
        np.asarray(bin_points_replicated(la, lo, win, mesh2d, valid=v)), want
    )
    sharded = bin_points_rowsharded(la, lo, win, mesh2d, valid=v)
    np.testing.assert_array_equal(np.asarray(sharded), want)
    pyr = pyramid_rowsharded(sharded, 3, mesh2d)
    for got, w in zip(pyr, pyramid_from_raster(jnp.asarray(want), 3)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(w))


@pytest.mark.slow
def test_sparse_kernels_on_2d_mesh_match_local(mesh2d):
    rng = np.random.default_rng(12)
    keys = rng.integers(0, 300, 8 * 512).astype(np.int32)
    gu, gs, gn = aggregate_keys_sharded(jnp.asarray(keys), mesh2d, capacity=512)
    lu, ls, ln = aggregate_keys(jnp.asarray(keys), capacity=len(keys))
    n = int(gn)
    assert n == int(ln)
    np.testing.assert_array_equal(np.asarray(gu[:n]), np.asarray(lu[:n]))
    np.testing.assert_array_equal(np.asarray(gs[:n]), np.asarray(ls[:n]))


@pytest.mark.slow
def test_bandsharded_binning_matches_single_device(mesh2d):
    """The all_to_all tile-space regroup (groupByKey analog): counts
    match the single-device raster exactly, output sharded by band."""
    from heatmap_tpu.parallel import bin_points_bandsharded

    lats, lons = _points(seed=13)
    win = window_from_bounds(
        (35.0, 55.0), (-5.0, 20.0), zoom=10, align_levels=3, pad_multiple=8
    )
    (pla, plo), valid = pad_to_multiple([lats, lons], 8)
    got, dropped = bin_points_bandsharded(
        jnp.asarray(pla), jnp.asarray(plo), win, mesh2d,
        valid=jnp.asarray(valid),
    )
    assert int(dropped) == 0  # default capacity: structurally zero
    want = np.asarray(bin_points_window(lats, lons, win))
    np.testing.assert_array_equal(np.asarray(got), want)
    assert got.sharding.spec[0] == "tile"  # rows band-sharded


@pytest.mark.slow
def test_bandsharded_weighted(mesh2d):
    from heatmap_tpu.parallel import bin_points_bandsharded

    lats, lons = _points(seed=14)
    w = np.random.default_rng(15).uniform(0.0, 2.0, len(lats)).astype(np.float32)
    win = window_from_bounds(
        (35.0, 55.0), (-5.0, 20.0), zoom=9, align_levels=0, pad_multiple=8
    )
    (pla, plo, pw), valid = pad_to_multiple([lats, lons, w], 8)
    got, _ = bin_points_bandsharded(
        jnp.asarray(pla), jnp.asarray(plo), win, mesh2d,
        weights=jnp.asarray(pw), valid=jnp.asarray(valid),
    )
    want = np.asarray(bin_points_window(lats, lons, win, weights=w))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)


@pytest.mark.slow
def test_bandsharded_under_jit(mesh2d):
    from heatmap_tpu.parallel import bin_points_bandsharded

    lats, lons = _points(seed=16, n=8 * 256)
    win = window_from_bounds(
        (35.0, 55.0), (-5.0, 20.0), zoom=8, align_levels=2, pad_multiple=8
    )

    @jax.jit
    def step(la, lo):
        return bin_points_bandsharded(la, lo, win, mesh2d)[0]

    got = np.asarray(step(jnp.asarray(lats), jnp.asarray(lons)))
    want = np.asarray(bin_points_window(lats, lons, win))
    np.testing.assert_array_equal(got, want)


@pytest.mark.slow
def test_bandsharded_send_capacity_overflow_is_loud(mesh2d):
    """A skewed band (every point in one raster band) past
    send_capacity must be COUNTED, not silently dropped
    (ops/sparse.py overflow contract applied to the all_to_all)."""
    from heatmap_tpu.parallel import bin_points_bandsharded

    T = mesh2d.shape["tile"]
    win = window_from_bounds(
        (35.0, 55.0), (-5.0, 20.0), zoom=10, align_levels=3, pad_multiple=8
    )
    band_h = win.height // T
    # All points in the FIRST band: rows [row0, row0+band_h) only.
    n = 8 * 64
    rng = np.random.default_rng(21)
    rows = win.row0 + rng.integers(0, band_h, n)
    cols = win.col0 + rng.integers(0, win.width, n)
    lats = np.asarray(mercator.latitude_from_row(rows + 0.5, win.zoom))
    lons = np.asarray(mercator.longitude_from_column(cols + 0.5, win.zoom))
    cap = 16  # per-destination slots; n // (D*T) points/device, all -> dest 0
    band, dropped = bin_points_bandsharded(
        jnp.asarray(lats), jnp.asarray(lons), win, mesh2d, send_capacity=cap
    )
    n_dev = mesh2d.devices.size
    expect_dropped = n - n_dev * min(cap, n // n_dev)
    assert int(dropped) == expect_dropped > 0
    # Kept points all landed in the raster (none lost untracked).
    assert int(np.asarray(band).sum()) == n - int(dropped)

    # Adequate capacity: zero drops and exact counts.
    band2, dropped2 = bin_points_bandsharded(
        jnp.asarray(lats), jnp.asarray(lons), win, mesh2d,
        send_capacity=n // n_dev,
    )
    assert int(dropped2) == 0
    want = np.asarray(bin_points_window(np.asarray(lats), np.asarray(lons), win))
    np.testing.assert_array_equal(np.asarray(band2), want)


def test_bandsharded_rejects_tile1():
    from heatmap_tpu.parallel import bin_points_bandsharded

    win = window_from_bounds((35.0, 55.0), (-5.0, 20.0), zoom=8,
                             align_levels=2, pad_multiple=8)
    with pytest.raises(ValueError):
        bin_points_bandsharded(
            jnp.zeros(8), jnp.zeros(8), win, make_mesh()
        )


@pytest.mark.slow
def test_replicated_binning_partitioned_backend(mesh):
    """Shard-local kernel routing: backend="partitioned" (interpret on
    CPU) under shard_map must match the xla-scatter mesh result — the
    multi-chip analog of the single-chip backend-equality tests."""
    lats, lons = _points(seed=5)
    win = window_from_bounds((35.0, 55.0), (-5.0, 20.0), zoom=10,
                             align_levels=3, pad_multiple=8)
    (pla, plo), valid = pad_to_multiple([lats, lons], 8)
    args = (jnp.asarray(pla), jnp.asarray(plo), win, mesh)
    got = np.asarray(bin_points_replicated(
        *args, valid=jnp.asarray(valid), backend="partitioned"))
    want = np.asarray(bin_points_replicated(
        *args, valid=jnp.asarray(valid), backend="xla"))
    np.testing.assert_array_equal(got, want)
    assert got.sum() == len(lats)


# -- compiled-HLO collective placement ------------------------------------


def _collectives(fn, *args):
    """Sorted set of collective op kinds in the OPTIMIZED module."""
    import re

    txt = jax.jit(fn).lower(*args).compile().as_text()
    return sorted(set(re.findall(
        r"(all-reduce|reduce-scatter|all-to-all|all-gather"
        r"|collective-permute)", txt)))


def test_collective_placement_pinned_in_hlo(mesh, mesh2d):
    """Structural pin for the three check_vma=False kernels: the vma
    check cannot cover pallas-routing shard_maps, so assert the
    compiled module's collective set directly —

    - replicated binning: exactly one psum family (all-reduce), and
      crucially NO all-to-all / reduce-scatter;
    - rowsharded binning: reduce-scatter ONLY — XLA keeping the
      psum_scatter form (an all-reduce here would mean every device
      materializes the full raster, the exact cost the kernel exists
      to avoid);
    - bandsharded binning: the tile-axis all-to-all regroup plus the
      data-axis all-reduce, nothing else.

    Value-equality tests cannot distinguish these programs; the HLO
    can."""
    from heatmap_tpu.ops import window_from_bounds
    from heatmap_tpu.parallel import (
        bin_points_bandsharded, bin_points_replicated,
        bin_points_rowsharded,
    )

    win = window_from_bounds((35.0, 55.0), (-5.0, 20.0), zoom=8,
                             align_levels=3, pad_multiple=8)
    n = 8 * 256
    lat, lon = jnp.zeros(n), jnp.zeros(n)

    assert _collectives(
        lambda a, b: bin_points_replicated(a, b, win, mesh), lat, lon
    ) == ["all-reduce"]
    assert _collectives(
        lambda a, b: bin_points_rowsharded(a, b, win, mesh), lat, lon
    ) == ["reduce-scatter"]
    assert _collectives(
        lambda a, b: bin_points_bandsharded(a, b, win, mesh2d)[0],
        lat, lon,
    ) == ["all-reduce", "all-to-all"]


def test_sharded_aggregation_collectives_stay_compact(mesh):
    """The sparse aggregation path must move only COMPACT per-device
    partials through collectives — never the n-sized key stream. The
    merge re-reduce runs outside shard_map as plain jit ops, so GSPMD
    is free to pick the collective kinds; what the design pins is that
    every collective operand is O(ndev * local_capacity), which is the
    whole point of the local-reduce-then-merge formulation."""
    import re

    from heatmap_tpu.parallel import aggregate_keys_sharded

    n, cap = 8 * 8192, 256
    keys = jnp.zeros(n, jnp.int64)
    txt = jax.jit(
        lambda k: aggregate_keys_sharded(k, mesh, capacity=cap)[0]
    ).lower(keys).compile().as_text()
    ops = ("all-reduce", "reduce-scatter", "all-to-all", "all-gather",
           "collective-permute")
    # Scan WHOLE instruction lines and take every array shape on them
    # (results AND operands, tuple-shaped variadic combiners included):
    # a reduce-scatter's small RESULT must not hide its n-sized
    # operand, and an XLA combiner pass must not make shapes invisible
    # to the match.
    sizes = []
    for line in txt.splitlines():
        if not any(f" {op}(" in line or f" {op}-" in line
                   for op in ops):
            continue
        for dims in re.findall(r"\[([\d,]+)\]", line):
            sizes.append(
                int(np.prod([int(d) for d in dims.split(",") if d]))
            )
    assert sizes, "expected at least one collective in the merge"
    # Compact partials are ndev * local_capacity = 2048 elements; any
    # n-derived size is at least n/ndev = 8192. The bound sits strictly
    # between, so n-sized movement fails however GSPMD spells it.
    assert max(sizes) < n // 8, (max(sizes), sorted(set(sizes)))
