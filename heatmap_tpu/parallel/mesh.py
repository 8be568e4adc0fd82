"""Mesh construction and host-side sharding helpers."""

from __future__ import annotations

import os

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# Canonical axis names. ``data`` shards points (the RDD-partition analog);
# ``tile`` shards raster/tile space (the reducer-partition analog,
# SURVEY.md §2.3 "spatial parallelism").
DATA_AXIS = "data"
TILE_AXIS = "tile"


def shard_map(body, mesh, in_specs, out_specs, check_vma: bool = True):
    """``jax.shard_map`` with positional mesh/specs; every mesh kernel in
    this package routes through it."""
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def _shard_axes(mesh: Mesh):
    """(axis names, total shards) the point-parallel kernels span.

    tile == 1 keeps the single ``data`` axis; tile > 1 flattens points
    over (data, tile) so a 2D mesh still uses every device — the tile
    axis only becomes *spatial* in sharded.bin_points_bandsharded.
    """
    if mesh.shape[TILE_AXIS] == 1:
        return (DATA_AXIS,), mesh.shape[DATA_AXIS]
    return (DATA_AXIS, TILE_AXIS), mesh.shape[DATA_AXIS] * mesh.shape[TILE_AXIS]


def named_sharding(mesh, *spec):
    """``NamedSharding(mesh, PartitionSpec(*spec))`` — the annotation the
    GSPMD cascade (parallel/gspmd.py) puts on its global-view arrays."""
    return NamedSharding(mesh, PartitionSpec(*spec))


def force_cpu_devices(n_devices: int) -> None:
    """Pin the process to an ``n_devices``-wide virtual CPU backend.

    The multi-chip dry-run setup shared by every entry point. Must run
    before (or while clearing) backend initialization: the
    ``XLA_FLAGS`` host device count is read when the backend starts,
    and ``jax_num_cpu_devices`` may not change while one is live.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    import jax.extend.backend as _jb

    _jb.clear_backends()
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n_devices)


def make_mesh(data: int | None = None, tile: int = 1, devices=None) -> Mesh:
    """Build a (data, tile) mesh over ``devices``.

    ``data=None`` uses all remaining devices on the data axis. On a
    multi-host platform, pass ``jax.devices()`` after
    ``jax.distributed.initialize()`` and the same code spans DCN.
    """
    devices = list(jax.devices()) if devices is None else list(devices)
    n = len(devices)
    if data is None:
        if n % tile:
            raise ValueError(f"{n} devices not divisible by tile={tile}")
        data = n // tile
    if data * tile > n:
        raise ValueError(f"mesh {data}x{tile} needs {data * tile} devices, have {n}")
    grid = np.asarray(devices[: data * tile]).reshape(data, tile)
    return Mesh(grid, (DATA_AXIS, TILE_AXIS))


def pad_to_multiple(arrays, multiple: int, valid=None):
    """Pad 1-D point arrays to a length multiple with an explicit mask.

    shard_map needs the sharded dimension divisible by the mesh axis
    size; the pad lanes are marked invalid so kernels drop them (the
    same masking path used for out-of-range points).

    Returns (padded_arrays_list, valid_mask).
    """
    n = arrays[0].shape[0]
    for a in arrays:
        if a.shape[0] != n:
            raise ValueError("point arrays must share their leading dimension")
    pad = (-n) % multiple
    mask = np.ones(n, bool) if valid is None else np.asarray(valid, bool).copy()
    if pad == 0:
        return list(arrays), mask
    padded = [np.concatenate([np.asarray(a), np.zeros((pad,), a.dtype)]) for a in arrays]
    mask = np.concatenate([mask, np.zeros(pad, bool)])
    return padded, mask
