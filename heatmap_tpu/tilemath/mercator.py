"""Vectorized Web-Mercator projection (forward and inverse).

Behavioral contract — matches the reference scalar math exactly
(reference tile.py:16-30), including its quirks (SURVEY.md §8.5):

- ``floor`` semantics (round toward -inf), not truncation, so negative
  intermediate values round *down* (reference tile.py:17,21).
- **No pole clamping**: |lat| >= 90 yields non-finite rows; latitudes
  beyond ±85.0511° yield rows outside [0, 2^zoom).
- **No antimeridian wrap**: lon == 180 yields column == 2^zoom.

Out-of-range / non-finite results are *reported* via ``project_points``'s
validity mask rather than silently clamped, so callers choose the policy.

Precision policy (SURVEY.md §7 "hard parts"): the fractional Mercator y
needs ~zoom+2 bits of mantissa for correct binning at zoom z. float32
(24-bit mantissa) is safe through z≈15 away from tile boundaries and is
the fast TPU path; float64 (requires ``jax_enable_x64``) reproduces the
CPython-double reference semantics through z21 and is the default when
x64 is enabled. The f64 projection is bit-identical to the
CPython-double oracle at z21, while f32 misbins most points at z21 —
so detail-zoom device binning always runs under x64.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# Latitude of the square Web-Mercator world edge: atan(sinh(pi)). Used by
# data generators and validity docs; the projection itself never clamps.
MAX_LATITUDE = math.degrees(math.atan(math.sinh(math.pi)))  # 85.05112877980659

_PI = math.pi


def default_float_dtype():
    """float64 when x64 is enabled (exact reference semantics), else float32."""
    return jnp.float64 if jax.config.jax_enable_x64 else jnp.float32


def _as_float(x, dtype):
    dtype = dtype or default_float_dtype()
    return jnp.asarray(x, dtype=dtype)


def mercator_y(latitude, dtype=None):
    """Normalized Mercator y in [0, 1) for latitudes in the mercator range.

    Operation order mirrors the reference formula (reference tile.py:17)
    so float64 results agree with CPython doubles:
    ``(1 - log(tan(phi) + sec(phi)) / pi) / 2`` with ``phi = lat*pi/180``.
    """
    lat = _as_float(latitude, dtype)
    phi = lat * _PI / 180.0
    return (1.0 - jnp.log(jnp.tan(phi) + 1.0 / jnp.cos(phi)) / _PI) / 2.0


def mercator_x(longitude, dtype=None):
    """Normalized Mercator x in [0, 1); lon == 180 maps to exactly 1.0."""
    lon = _as_float(longitude, dtype)
    return (lon + 180.0) / 360.0


def row_from_latitude(latitude, zoom, dtype=None):
    """Floored tile row at ``zoom`` (float dtype; may be non-finite at poles).

    Matches reference tile.py:16-17.
    """
    return jnp.floor(mercator_y(latitude, dtype) * float(1 << zoom))


def column_from_longitude(longitude, zoom, dtype=None):
    """Floored tile column at ``zoom`` (float dtype; 180° -> 2^zoom).

    Matches reference tile.py:20-21.
    """
    return jnp.floor(mercator_x(longitude, dtype) * float(1 << zoom))


def latitude_from_row(row, zoom, dtype=None):
    """North-edge latitude of tile ``row`` at ``zoom``.

    Matches reference tile.py:24-26: ``atan(sinh(n))`` written as
    ``atan(0.5*(e^n - e^-n))`` with ``n = pi - 2*pi*row/2^zoom``.
    """
    r = _as_float(row, dtype)
    n = _PI - 2.0 * _PI * r / float(1 << zoom)
    return 180.0 / _PI * jnp.arctan(0.5 * (jnp.exp(n) - jnp.exp(-n)))


def longitude_from_column(column, zoom, dtype=None):
    """West-edge longitude of tile ``column`` at ``zoom`` (reference tile.py:29-30)."""
    c = _as_float(column, dtype)
    return c / float(1 << zoom) * 360.0 - 180.0


def project_points(latitude, longitude, zoom, dtype=None):
    """Project point arrays to integer (row, col) at ``zoom`` with validity.

    Returns ``(row, col, valid)`` where row/col are int32 (rows/cols fit
    int32 for every zoom <= 30) and ``valid`` marks points whose row and
    column are finite and inside [0, 2^zoom) — the vectorized analog of
    the reference's implicit "garbage in, garbage out" behavior
    (SURVEY.md §8.5), made explicit so kernels can mask instead of crash.
    """
    n = float(1 << zoom)
    frow = row_from_latitude(latitude, zoom, dtype)
    fcol = column_from_longitude(longitude, zoom, dtype)
    valid = (
        jnp.isfinite(frow)
        & jnp.isfinite(fcol)
        & (frow >= 0.0)
        & (frow < n)
        & (fcol >= 0.0)
        & (fcol < n)
    )
    # Zero out invalid lanes before the int cast: clip alone propagates
    # NaN, and NaN->int is backend-dependent garbage. Invalid points are
    # excluded by the mask; the zeroing just guarantees in-range indices
    # for masked scatters.
    frow = jnp.where(valid, frow, 0.0)
    fcol = jnp.where(valid, fcol, 0.0)
    row = jnp.clip(frow, 0.0, n - 1.0).astype(jnp.int32)
    col = jnp.clip(fcol, 0.0, n - 1.0).astype(jnp.int32)
    return row, col, valid


def project_points_np(latitude, longitude, zoom):
    """Host-side numpy-f64 projection: -> (row, col, valid) int64/bool.

    The exact-precision host path (same operation order as the jnp
    version above and reference tile.py:17,21); used by the batch
    pipeline so device dtype policy can't affect ingest binning.
    """
    import numpy as np

    n = 1 << zoom
    lat = np.asarray(latitude, np.float64)
    lon = np.asarray(longitude, np.float64)
    with np.errstate(all="ignore"):
        phi = lat * _PI / 180
        y = (1 - np.log(np.tan(phi) + 1 / np.cos(phi)) / _PI) / 2
        frow = np.floor(y * n)
        fcol = np.floor((lon + 180.0) / 360.0 * n)
    valid = (
        np.isfinite(frow) & np.isfinite(fcol)
        & (frow >= 0) & (frow < n) & (fcol >= 0) & (fcol < n)
    )
    row = np.where(valid, frow, 0).astype(np.int64)
    col = np.where(valid, fcol, 0).astype(np.int64)
    return row, col, valid


def tile_center_latlon(row, column, zoom, dtype=None):
    """Center (lat, lon) of tiles, as the reference computes it.

    The reference's tile center is the *arithmetic mean of the edge
    latitudes* (reference tile.py:45-52), not the inverse projection of
    the Mercator-y midpoint; reproduced here because the cascade re-bins
    tile centers (reference heatmap.py:60-61).
    """
    lat_n = latitude_from_row(row, zoom, dtype)
    r = _as_float(row, dtype)
    lat_s = latitude_from_row(r + 1.0, zoom, dtype)
    lon_w = longitude_from_column(column, zoom, dtype)
    c = _as_float(column, dtype)
    lon_e = longitude_from_column(c + 1.0, zoom, dtype)
    return (lat_n + lat_s) / 2.0, (lon_e + lon_w) / 2.0
