"""Sort-partitioned MXU binning (ops.partitioned), interpret mode.

Every case is diffed bit-exact against the XLA scatter contract
(ops.histogram.bin_rowcol_window), including the lax.cond fallback for
hostile distributions.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from heatmap_tpu.ops import Window
from heatmap_tpu.ops.histogram import bin_rowcol_window
from heatmap_tpu.ops.partitioned import bin_rowcol_window_partitioned

WINDOW = Window(zoom=12, row0=512, col0=256, height=1024, width=640)


def _diff(row, col, window=WINDOW, valid=None, **kw):
    row = jnp.asarray(row, jnp.int32)
    col = jnp.asarray(col, jnp.int32)
    expected = bin_rowcol_window(row, col, window, valid=valid)
    got = bin_rowcol_window_partitioned(
        row, col, window, valid=valid, interpret=True, **kw
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(expected))
    return np.asarray(expected)


def test_clustered_mostly_good_chunks():
    rng = np.random.default_rng(0)
    n = 1 << 15
    row = rng.integers(520, 620, n)
    col = rng.integers(300, 500, n)
    row[:500] = rng.integers(0, 4096, 500)  # sparse fringe + out-of-window
    col[:500] = rng.integers(0, 4096, 500)
    assert _diff(row, col).sum() > 0


@pytest.mark.parametrize("block_cells", [1 << 12, 1 << 14, 1 << 16])
def test_block_cells_sweep_bit_exact(block_cells):
    """Every supported block size (64/128/256 side) is bit-exact,
    including block-boundary straddles at that size's alignment."""
    rng = np.random.default_rng(6)
    n = 1 << 14
    row = np.concatenate([
        rng.integers(520, 560, n // 2),
        # dense run straddling this block size's boundary
        np.full(n // 2, 512 + (block_cells // WINDOW.width)),
    ])
    col = rng.integers(300, 500, n)
    _diff(row, col, block_cells=block_cells)


def test_bad_block_cells_rejected():
    rng = np.random.default_rng(7)
    row = rng.integers(520, 560, 256)
    col = rng.integers(300, 340, 256)
    for bad in (1 << 13, 100, 1 << 10):
        with pytest.raises(ValueError, match="block_cells"):
            bin_rowcol_window_partitioned(
                jnp.asarray(row, jnp.int32), jnp.asarray(col, jnp.int32),
                WINDOW, interpret=True, block_cells=bad,
            )


def test_uniform_triggers_fallback():
    """Uniform over the window makes most chunks straddle blocks; the
    cond fallback must still be bit-exact."""
    rng = np.random.default_rng(1)
    n = 1 << 14
    _diff(rng.integers(512, 1536, n), rng.integers(256, 896, n))


def test_all_out_of_window():
    rng = np.random.default_rng(2)
    assert _diff(
        rng.integers(0, 500, 300), rng.integers(0, 250, 300)
    ).sum() == 0


def test_tiny_and_empty():
    _diff(np.asarray([515, 516]), np.asarray([300, 301]))
    _diff(np.empty(0, np.int64), np.empty(0, np.int64))


def test_valid_mask():
    rng = np.random.default_rng(3)
    n = 4096
    valid = jnp.asarray(rng.random(n) < 0.5)
    _diff(rng.integers(515, 530, n), rng.integers(300, 330, n), valid=valid)


def test_single_block_window():
    w = Window(zoom=12, row0=512, col0=256, height=128, width=128)
    rng = np.random.default_rng(4)
    _diff(rng.integers(500, 660, 5000), rng.integers(250, 400, 5000),
          window=w)


def test_block_boundary_straddle():
    """Dense runs exactly on an aligned block boundary (cells 65535 and
    65536 of the window) exercise straddling-chunk bad-path routing."""
    w = WINDOW
    cells = np.concatenate([
        np.full(3000, (1 << 16) - 1),
        np.full(3000, 1 << 16),
        np.arange(6000) % (w.height * w.width),
    ])
    row = cells // w.width + w.row0
    col = cells % w.width + w.col0
    _diff(row, col)


def test_backend_plumbing_counts_and_weighted():
    rng = np.random.default_rng(5)
    row = jnp.asarray(rng.integers(500, 700, 1000), jnp.int32)
    col = jnp.asarray(rng.integers(280, 360, 1000), jnp.int32)
    valid = jnp.asarray(rng.random(1000) < 0.7)
    # Positive dispatch: backend="partitioned" through the public
    # entry forwards valid= and dtype= and matches the scatter path.
    via_backend = bin_rowcol_window(
        row, col, WINDOW, valid=valid, backend="partitioned",
        dtype=jnp.float32,
    )
    expected = bin_rowcol_window(row, col, WINDOW, valid=valid,
                                 dtype=jnp.float32)
    assert via_backend.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(via_backend),
                                  np.asarray(expected))
    # Weighted dispatch through the public entry (integer-valued f32
    # weights: order-independent sums, so exact equality holds).
    w = jnp.asarray(rng.integers(0, 8, 1000), jnp.float32)
    via_w = bin_rowcol_window(
        row, col, WINDOW, weights=w, valid=valid, backend="partitioned",
    )
    exp_w = bin_rowcol_window(row, col, WINDOW, weights=w, valid=valid)
    assert via_w.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(via_w), np.asarray(exp_w))


@pytest.mark.parametrize("streams", [2, 4, 8])
def test_streams_bit_exact_clustered(streams):
    """k-stream variant (batched row sorts, per-stream output slabs
    summed) must match the scatter contract exactly, including padding
    chunks landing in the trailing streams."""
    rng = np.random.default_rng(11)
    n = (1 << 15) + 777  # deliberately not a multiple of streams*chunk
    row = rng.integers(520, 620, n)
    col = rng.integers(300, 500, n)
    row[:500] = rng.integers(0, 4096, 500)
    col[:500] = rng.integers(0, 4096, 500)
    assert _diff(row, col, streams=streams).sum() > 0


def test_streams_uniform_fallback_and_pileup():
    rng = np.random.default_rng(12)
    n = 1 << 14
    # Uniform over the whole window: mostly bad chunks -> in-jit
    # full-scatter fallback must reshape the stream matrix correctly.
    row = rng.integers(0, 4096, n)
    col = rng.integers(0, 4096, n)
    _diff(row, col, streams=4)
    # Single-cell pileup + out-of-window fringe.
    row2 = np.full(n, 600)
    col2 = np.full(n, 400)
    row2[: n // 8] = rng.integers(-100, 5000, n // 8)
    col2[: n // 8] = rng.integers(-100, 5000, n // 8)
    _diff(row2, col2, streams=4)


def test_clamp_streams_bounds_slab_memory():
    """streams must shrink for giant windows (the x8 default would OOM
    where streams=1 fits HBM) and stay untouched for measured configs."""
    from heatmap_tpu.ops.partitioned import (
        STREAM_SLAB_BUDGET, clamp_streams,
    )
    from heatmap_tpu.ops.histogram import Window

    # Headline-class window (8192^2 = 256 MiB slab): default untouched.
    z15 = Window(zoom=15, row0=0, col0=0, height=8192, width=8192)
    assert clamp_streams(8, z15) == 8
    # Near the int32 cell-id cap (~8 GiB of cells): forced to 1.
    giant = Window(zoom=21, row0=0, col0=0, height=1 << 16, width=1 << 15)
    assert clamp_streams(8, giant) == 1
    # Mid-size: partial clamp, and the budget is actually respected.
    mid = Window(zoom=18, row0=0, col0=0, height=1 << 14, width=1 << 14)
    k = clamp_streams(8, mid)
    assert 1 <= k < 8
    assert k * (1 << 28) * 4 <= STREAM_SLAB_BUDGET
    # Tiny windows never exceed the requested count.
    small = Window(zoom=10, row0=0, col0=0, height=256, width=256)
    assert clamp_streams(8, small) == 8


def test_streams_one_equals_flat_path():
    rng = np.random.default_rng(13)
    n = 1 << 14
    row = rng.integers(520, 620, n)
    col = rng.integers(300, 500, n)
    a = _diff(row, col, streams=1)
    b = _diff(row, col, streams=8)
    np.testing.assert_array_equal(a, b)


def _diff_weighted(row, col, weights, window=WINDOW, valid=None, exact=True,
                   **kw):
    """Weighted twin of _diff. ``exact`` for integer-valued weights
    (order-independent f32 sums); otherwise allclose within f32
    reordering tolerance."""
    row = jnp.asarray(row, jnp.int32)
    col = jnp.asarray(col, jnp.int32)
    weights = jnp.asarray(weights, jnp.float32)
    expected = bin_rowcol_window(row, col, window, weights=weights,
                                 valid=valid)
    got = bin_rowcol_window_partitioned(
        row, col, window, weights=weights, valid=valid, interpret=True, **kw
    )
    assert got.dtype == jnp.float32
    if exact:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(expected))
    else:
        # Summation-order difference grows with per-cell fan-in: a few
        # ulps of the cell sum (observed ~15 ulps at 100k-point
        # pileups), so the relative tolerance is the meaningful one.
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                                   rtol=1e-5, atol=1e-4)
    return np.asarray(expected)


def test_weighted_clustered_bit_exact():
    rng = np.random.default_rng(20)
    n = (1 << 15) + 333  # not a multiple of chunk: exercises weight padding
    row = rng.integers(520, 620, n)
    col = rng.integers(300, 500, n)
    row[:500] = rng.integers(0, 4096, 500)  # fringe + out-of-window
    col[:500] = rng.integers(0, 4096, 500)
    w = rng.integers(0, 16, n).astype(np.float32)
    assert _diff_weighted(row, col, w).sum() > 0


def test_weighted_uniform_fallback():
    """Hostile distribution routes to the weighted full-scatter
    fallback inside the cond; must still match exactly."""
    rng = np.random.default_rng(21)
    n = 1 << 14
    w = rng.integers(1, 4, n).astype(np.float32)
    _diff_weighted(rng.integers(512, 1536, n), rng.integers(256, 896, n), w)


def test_weighted_valid_mask_and_pileup():
    rng = np.random.default_rng(22)
    n = 1 << 14
    valid = jnp.asarray(rng.random(n) < 0.6)
    # Single-cell pileup: per-cell sum ~n*mean(w) stays far below 2^24.
    row = np.full(n, 600)
    col = np.full(n, 400)
    row[: n // 8] = rng.integers(-100, 5000, n // 8)
    col[: n // 8] = rng.integers(-100, 5000, n // 8)
    w = rng.integers(0, 8, n).astype(np.float32)
    _diff_weighted(row, col, w, valid=valid)


@pytest.mark.parametrize("streams", [2, 8])
def test_weighted_streams(streams):
    rng = np.random.default_rng(23)
    n = (1 << 14) + 77
    row = rng.integers(520, 620, n)
    col = rng.integers(300, 500, n)
    w = rng.integers(0, 8, n).astype(np.float32)
    _diff_weighted(row, col, w, streams=streams)


def test_weighted_float_weights_close():
    """Arbitrary float weights: summation order differs from the
    scatter path, so the contract is allclose, not bit-equal."""
    rng = np.random.default_rng(24)
    n = 1 << 14
    row = rng.integers(520, 620, n)
    col = rng.integers(300, 500, n)
    w = rng.random(n).astype(np.float32) * 3.7
    _diff_weighted(row, col, w, exact=False)


def test_weighted_empty_and_zero_weights():
    _diff_weighted(np.empty(0, np.int64), np.empty(0, np.int64),
                   np.empty(0, np.float32))
    rng = np.random.default_rng(25)
    n = 4096
    out = _diff_weighted(rng.integers(520, 620, n),
                         rng.integers(300, 500, n),
                         np.zeros(n, np.float32))
    assert out.sum() == 0


@pytest.mark.parametrize("platform,want", [("cpu", True), ("tpu", False),
                                           ("gpu", RuntimeError)])
def test_default_interpret_follows_the_platform(monkeypatch, platform, want):
    """interpret=None means the interpreter on CPU, the compiled kernel
    on TPU, and an error anywhere else (never a silent interpreter run
    on an accelerator)."""
    import types

    import jax

    from heatmap_tpu.ops.partitioned import default_interpret

    monkeypatch.setattr(jax, "devices",
                        lambda: [types.SimpleNamespace(platform=platform)])
    if want is RuntimeError:
        with pytest.raises(RuntimeError, match="gpu"):
            default_interpret()
    else:
        assert default_interpret() is want
