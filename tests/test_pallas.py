"""Pallas MXU histogram kernel tests (interpret mode on the CPU mesh;
the compiled kernel is pinned by tests/test_lowering.py).

Parity oracle: the XLA scatter path (ops.histogram), itself verified
against the pure-Python reference oracle in test_ops.py.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from heatmap_tpu.ops import Window, bin_points_window, bin_rowcol_window
from heatmap_tpu.ops.pallas_kernels import (
    bin_points_window_pallas,
    bin_rowcol_window_pallas,
)

WINDOW = Window(zoom=10, row0=320, col0=256, height=64, width=128)


def _points(n=3000, seed=0):
    rng = np.random.default_rng(seed)
    return (
        rng.uniform(25.0, 55.0, n),  # some out-of-window
        rng.uniform(-95.0, -60.0, n),
        rng.exponential(1.5, n),
    )


def test_rowcol_parity_with_xla_scatter():
    rng = np.random.default_rng(1)
    row = rng.integers(300, 400, 5000)  # straddles the window rows
    col = rng.integers(230, 400, 5000)
    expected = bin_rowcol_window(
        jnp.asarray(row), jnp.asarray(col), WINDOW, dtype=jnp.float32
    )
    got = bin_rowcol_window_pallas(
        jnp.asarray(row, jnp.int32), jnp.asarray(col, jnp.int32), WINDOW,
        interpret=True,
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(expected))
    assert float(got.sum()) > 0


def test_weighted_parity():
    rng = np.random.default_rng(2)
    row = rng.integers(320, 384, 2000)
    col = rng.integers(256, 384, 2000)
    w = rng.exponential(1.0, 2000).astype(np.float32)
    expected = bin_rowcol_window(
        jnp.asarray(row), jnp.asarray(col), WINDOW,
        weights=jnp.asarray(w), dtype=jnp.float32,
    )
    got = bin_rowcol_window_pallas(
        jnp.asarray(row, jnp.int32), jnp.asarray(col, jnp.int32), WINDOW,
        weights=jnp.asarray(w), interpret=True,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(expected), rtol=1e-6)


def test_valid_mask_and_padding():
    # 700 points (not a chunk multiple) with every other point masked.
    row = np.full(700, 330, np.int32)
    col = np.full(700, 300, np.int32)
    valid = (np.arange(700) % 2) == 0
    got = bin_rowcol_window_pallas(
        jnp.asarray(row), jnp.asarray(col), WINDOW,
        valid=jnp.asarray(valid), chunk=256, interpret=True,
    )
    assert float(got[10, 44]) == 350.0  # row 330-320, col 300-256
    assert float(got.sum()) == 350.0


def test_empty_input():
    got = bin_rowcol_window_pallas(
        jnp.zeros(0, jnp.int32), jnp.zeros(0, jnp.int32), WINDOW,
        interpret=True,
    )
    assert float(got.sum()) == 0.0


def test_fused_projection_parity():
    lat, lon, w = _points()
    expected = bin_points_window(
        jnp.asarray(lat), jnp.asarray(lon), WINDOW,
        weights=jnp.asarray(w, jnp.float32),
        proj_dtype=jnp.float64, dtype=jnp.float32,
    )
    got = bin_points_window_pallas(
        jnp.asarray(lat), jnp.asarray(lon), WINDOW,
        weights=jnp.asarray(w, jnp.float32),
        proj_dtype=jnp.float64, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(expected), rtol=1e-6
    )


def test_bf16_and_f32_onehots_identical():
    rng = np.random.default_rng(4)
    row = rng.integers(300, 420, 4000)
    col = rng.integers(230, 400, 4000)
    args = (jnp.asarray(row, jnp.int32), jnp.asarray(col, jnp.int32), WINDOW)
    bf = bin_rowcol_window_pallas(*args, interpret=True,
                                  onehot_dtype=jnp.bfloat16)
    f32 = bin_rowcol_window_pallas(*args, interpret=True,
                                   onehot_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(bf), np.asarray(f32))


def test_weighted_rejects_bf16_onehots():
    import pytest

    with pytest.raises(ValueError):
        bin_rowcol_window_pallas(
            jnp.zeros(8, jnp.int32), jnp.zeros(8, jnp.int32), WINDOW,
            weights=jnp.ones(8, jnp.float32), interpret=True,
            onehot_dtype=jnp.bfloat16,
        )


def test_backend_selection_in_histogram():
    """bin_rowcol_window backend plumbing: auto falls back to xla off-TPU;
    explicit pallas matches (via interpret-free path only on TPU, so here
    just check auto==xla result on CPU)."""
    from heatmap_tpu.ops.histogram import _pick_backend

    assert _pick_backend("auto", WINDOW) == "xla"  # CPU test env
    assert _pick_backend("pallas", WINDOW) == "pallas"
    assert _pick_backend("xla", WINDOW) == "xla"
    rng = np.random.default_rng(5)
    row = jnp.asarray(rng.integers(300, 400, 1000), jnp.int32)
    col = jnp.asarray(rng.integers(230, 400, 1000), jnp.int32)
    a = bin_rowcol_window(row, col, WINDOW, backend="auto")
    b = bin_rowcol_window(row, col, WINDOW, backend="xla")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
