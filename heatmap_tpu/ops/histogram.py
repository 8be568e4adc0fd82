"""Dense window-raster histograms: points -> per-tile counts.

This is the hot binning path (BASELINE.md north star). A ``Window`` is a
static, axis-aligned block of the global tile grid at one zoom; points
are projected, localized, and scatter-added into an (H, W) raster. The
reference's storage unit — a coarse tile holding a 32x32 dict of detail
counts (reference heatmap.py:16,89) — is a special case: a 32x32 window
5 zooms below the coarse tile.

Accumulation dtype policy (SURVEY.md §8.8): the reference sums float
1.0s, which silently stops incrementing at 2^24 per tile in f32. Counts
accumulate in int32 here (weights=None) and only become floats at the
egress boundary; weighted sums accumulate in f32 by default.
"""

from __future__ import annotations

import dataclasses
import math

import jax.numpy as jnp
import numpy as _np

from heatmap_tpu.tilemath import mercator
from heatmap_tpu.tilemath import tile as _tile


@dataclasses.dataclass(frozen=True)
class Window:
    """A static (hashable -> jit-friendly) tile-grid window at one zoom.

    Covers rows [row0, row0+height) x cols [col0, col0+width) at ``zoom``.
    """

    zoom: int
    row0: int
    col0: int
    height: int
    width: int

    def __post_init__(self):
        n = 1 << self.zoom
        if self.height <= 0 or self.width <= 0:
            raise ValueError(f"window has empty extent: {self}")
        if not (0 <= self.row0 and self.row0 + self.height <= n):
            raise ValueError(f"window rows outside grid at z{self.zoom}: {self}")
        if not (0 <= self.col0 and self.col0 + self.width <= n):
            raise ValueError(f"window cols outside grid at z{self.zoom}: {self}")

    @property
    def shape(self):
        return (self.height, self.width)

    def aligned_to(self, levels: int) -> bool:
        """True if the window sits on 2^levels tile boundaries, so a
        ``levels``-deep reshape-sum pyramid stays aligned to the global
        grid (ops/pyramid.py)."""
        a = 1 << levels
        return (
            self.row0 % a == 0
            and self.col0 % a == 0
            and self.height % a == 0
            and self.width % a == 0
        )


def window_from_bounds(
    lat_range,
    lon_range,
    zoom: int,
    align_levels: int = 0,
    pad_multiple: int = 1,
) -> Window:
    """Smallest Window covering a lat/lon bounding box, grid-aligned.

    ``align_levels`` rounds the window out to 2^levels boundaries (for
    pyramid alignment); ``pad_multiple`` additionally pads height/width
    up to a multiple (e.g. 256 to keep rasters TPU-lane friendly).
    Alignment is guaranteed (or a ValueError); the pad multiple is
    best-effort — it clamps to the grid size when the z``zoom`` grid is
    smaller than the requested multiple, so callers needing exact
    divisibility (e.g. row-sharding) must check the returned shape.
    """
    if align_levels > zoom:
        raise ValueError(
            f"align_levels={align_levels} exceeds zoom={zoom}: the grid has "
            f"only 2^{zoom} tiles per side, so 2^{align_levels}-alignment is "
            "impossible"
        )
    lat_lo, lat_hi = min(lat_range), max(lat_range)
    lon_lo, lon_hi = min(lon_range), max(lon_range)
    n = 1 << zoom
    # Rows grow southward: the high latitude gives the low row.
    r_lo = int(_tile._row_from_latitude(min(lat_hi, mercator.MAX_LATITUDE), zoom))
    r_hi = int(_tile._row_from_latitude(max(lat_lo, -mercator.MAX_LATITUDE), zoom))
    c_lo = int(_tile._column_from_longitude(lon_lo, zoom))
    c_hi = int(_tile._column_from_longitude(lon_hi, zoom))
    r_lo, c_lo = max(r_lo, 0), max(c_lo, 0)
    r_hi, c_hi = min(r_hi, n - 1), min(c_hi, n - 1)
    if r_hi < r_lo or c_hi < c_lo:
        raise ValueError(
            f"bounds lat={lat_range} lon={lon_range} cover no tiles at z{zoom}"
        )

    a = 1 << align_levels
    row0 = (r_lo // a) * a
    col0 = (c_lo // a) * a
    height = -((-(r_hi + 1 - row0)) // a) * a
    width = -((-(c_hi + 1 - col0)) // a) * a

    def _pad(extent, origin):
        # Quantum must satisfy BOTH constraints: lcm(pad_multiple, a).
        m = math.lcm(pad_multiple, a)
        padded = min(-((-extent) // m) * m, n)
        # Keep inside the global grid by sliding the origin back if needed.
        origin = min(origin, max(0, n - padded))
        return padded, origin

    height, row0 = _pad(height, row0)
    width, col0 = _pad(width, col0)
    win = Window(zoom=zoom, row0=row0, col0=col0, height=height, width=width)
    if align_levels and not win.aligned_to(align_levels):
        raise ValueError(
            f"could not align window to 2^{align_levels} boundaries within "
            f"the z{zoom} grid: {win}"
        )
    return win


#: Windows at or below this cell count route to the Pallas MXU kernel
#: under backend="auto" on TPU; above it the N*H*W MAC term overtakes
#: the scatter cost. Set by an earlier on-chip sweep; not re-measured
#: on today's code.
PALLAS_AUTO_MAX_CELLS = 256 * 256

#: The zero constant for Pallas BlockSpec index maps, shared by every
#: kernel module. Must be a CONCRETE int32 (numpy scalar, not jnp —
#: index maps may not capture tracers): under jax_enable_x64 a literal
#: Python 0 traces as int64 and the Mosaic backend fails to legalize
#: the index-map function ("failed to legalize operation 'func.func'",
#: caught on the real chip 2026-07-31 — a stage past what
#: tests/test_lowering.py's jax.export lowering reaches, so only
#: on-chip runs exercise it; that is why this lives in ONE place).
IMAP_ZERO = _np.int32(0)


def _pick_backend(backend: str, window: Window, weighted: bool = False) -> str:
    if backend != "auto":
        return backend
    import jax

    on_tpu = jax.devices()[0].platform == "tpu"
    if not on_tpu:
        return "xla"
    if window.height * window.width <= PALLAS_AUTO_MAX_CELLS:
        return "pallas"
    # Large windows: sort-partitioned MXU binning, for counts and for
    # weighted sums (pair-sorted weights + weight-scaled one-hots).
    return "partitioned"


def bin_rowcol_window(row, col, window: Window, weights=None, valid=None,
                      dtype=None, backend: str = "xla"):
    """Scatter-add pre-projected (row, col) points into a window raster.

    Out-of-window and invalid points are dropped via scatter mode='drop'
    (index -1), the vectorized analog of the reference's filter-by-key
    partitioning. Returns an (H, W) raster.

    ``backend``: "xla" (scatter-add), "pallas" (MXU one-hot matmul
    kernel, TPU only), "partitioned" (sort + per-block MXU kernel for
    LARGE windows, counts and weighted sums; ops/partitioned.py), or
    "auto" (pallas on TPU for windows up to PALLAS_AUTO_MAX_CELLS
    cells). The pallas paths accumulate in f32 — exact for < 2^24
    counts per cell per call — and are cast to the requested ``dtype``.
    """
    if dtype is None:
        dtype = jnp.int32 if weights is None else jnp.float32
    picked = _pick_backend(backend, window, weighted=weights is not None)
    if picked == "partitioned":
        from heatmap_tpu.ops.partitioned import bin_rowcol_window_partitioned

        return bin_rowcol_window_partitioned(
            row, col, window, weights=weights, valid=valid, dtype=dtype
        )
    if picked == "pallas":
        from heatmap_tpu.ops.pallas_kernels import bin_rowcol_window_pallas

        raster = bin_rowcol_window_pallas(
            row, col, window, weights=weights, valid=valid
        )
        return raster.astype(dtype)
    r = jnp.asarray(row, jnp.int32) - window.row0
    c = jnp.asarray(col, jnp.int32) - window.col0
    in_win = (r >= 0) & (r < window.height) & (c >= 0) & (c < window.width)
    if valid is not None:
        in_win = in_win & valid
    # Drop index must be out-of-bounds HIGH: negative indices wrap (JAX
    # normalizes them before the mode="drop" bounds check).
    idx = jnp.where(in_win, r * window.width + c, window.height * window.width)
    w = jnp.ones(idx.shape, dtype) if weights is None else jnp.asarray(weights, dtype)
    flat = jnp.zeros(window.height * window.width, dtype).at[idx].add(w, mode="drop")
    return flat.reshape(window.height, window.width)


def bin_points_window(
    latitude,
    longitude,
    window: Window,
    weights=None,
    valid=None,
    proj_dtype=None,
    dtype=None,
    backend: str = "xla",
):
    """Project lat/lon points and scatter-add them into a window raster.

    ``proj_dtype`` picks the projection precision (mercator.py policy:
    f64 exact when x64 is on, f32 fast otherwise). ``valid`` ANDs with
    the projection validity mask (used e.g. for padding lanes).
    ``backend`` as in bin_rowcol_window.
    """
    row, col, proj_valid = mercator.project_points(
        latitude, longitude, window.zoom, dtype=proj_dtype
    )
    if valid is not None:
        proj_valid = proj_valid & valid
    return bin_rowcol_window(
        row, col, window, weights=weights, valid=proj_valid, dtype=dtype,
        backend=backend,
    )
