"""Mosaic TPU lowering and compile regression tests (no chip needed).

Two rungs, both from a CPU-only process:

- ``jax.export`` lowers a jitted function for the *tpu* platform,
  running the real Mosaic kernel-lowering pass that ``interpret=True``
  tests skip. Under ``jax_enable_x64`` (which the whole test session
  and the production cascade run with — conftest.py, pipeline z21
  precision policy), weak Python-int literals inside a Pallas kernel
  trace as int64 scalars, and Mosaic's int64->int32 convert lowering
  recurses until RecursionError; these tests pin every shipping
  kernel's TPU lowering under x64.
- The compile tests go one stage further: they compile the main path's
  kernels and one whole z21 cascade for a *described* v5e chip
  (``jax.experimental.topologies``), at production widths and 2^20
  points. The chip's compiler refuses what interpret mode accepts —
  unaligned slices, too much fast memory, a program over the device's
  16 GB — and these tests catch that here. The topology is described
  only inside the ``topo`` fixture, so under pytest-xdist only the
  worker that runs this file loads the TPU library.

Nothing executes on a chip here: bit-exactness is covered by the
interpret-mode tests and by ``chip_smoke.py``'s byte comparisons.
"""

from __future__ import annotations

import functools
import re

import jax
import jax.export
import jax.numpy as jnp
import numpy as np
import pytest

from heatmap_tpu.ops.histogram import Window
from heatmap_tpu.ops.pallas_kernels import bin_rowcol_window_pallas
from heatmap_tpu.ops.partitioned import bin_rowcol_window_partitioned
from heatmap_tpu.ops.sparse import aggregate_sorted_keys
from heatmap_tpu.ops.sparse_partitioned import (
    aggregate_sorted_keys_partitioned,
)

N = 1 << 12


def _export_tpu(fn, *args):
    """Lower ``jit(fn)`` for the TPU platform; raises on Mosaic bugs."""
    return jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)


@pytest.fixture(scope="module")
def rowcol():
    rng = np.random.default_rng(7)
    # int64 inputs on purpose: the x64 batch job hands the kernels
    # int64 rows/cols; the kernels must cast internally.
    row = jnp.asarray(rng.integers(0, 512, N), jnp.int64)
    col = jnp.asarray(rng.integers(0, 640, N), jnp.int64)
    return row, col


def test_partitioned_count_lowers_for_tpu(rowcol):
    win = Window(zoom=15, row0=0, col0=0, height=512, width=640)
    f = functools.partial(bin_rowcol_window_partitioned, window=win,
                          interpret=False)
    _export_tpu(lambda r, c: f(r, c), *rowcol)


def test_partitioned_count_streams_lowers_for_tpu(rowcol):
    win = Window(zoom=15, row0=0, col0=0, height=512, width=640)
    f = functools.partial(bin_rowcol_window_partitioned, window=win,
                          interpret=False, streams=8)
    _export_tpu(lambda r, c: f(r, c), *rowcol)


def test_partitioned_weighted_lowers_for_tpu(rowcol):
    win = Window(zoom=15, row0=0, col0=0, height=512, width=640)
    w = jnp.asarray(np.random.default_rng(8).integers(1, 16, N), jnp.float32)
    f = functools.partial(bin_rowcol_window_partitioned, window=win,
                          interpret=False)
    _export_tpu(lambda r, c, w_: f(r, c, weights=w_), *rowcol, w)


def test_pallas_window_kernel_lowers_for_tpu(rowcol):
    win = Window(zoom=12, row0=0, col0=0, height=256, width=256)
    f = functools.partial(bin_rowcol_window_pallas, window=win,
                          interpret=False)
    _export_tpu(lambda r, c: f(r, c), *rowcol)


def test_segment_kernel_lowers_for_tpu():
    keys = np.sort(
        np.random.default_rng(9).integers(0, 1 << 42, N).astype(np.int64)
    )
    f = functools.partial(aggregate_sorted_keys_partitioned,
                          capacity=1 << 14, interpret=False)
    _export_tpu(f, jnp.asarray(keys))


def test_segment_kernel_streams_lowers_for_tpu():
    keys = np.sort(
        np.random.default_rng(10).integers(0, 1 << 42, N).astype(np.int64)
    )
    f = functools.partial(aggregate_sorted_keys_partitioned,
                          capacity=1 << 14, interpret=False,
                          slab=1 << 12, chunk=512, streams=4)
    _export_tpu(f, jnp.asarray(keys))


# -- compiled for a described v5e chip --------------------------------------

#: 2^20 points: the compile tests' stream length (the cascade doubles it
#: into emissions, the point plus its 'all' slot).
POINTS = 1 << 20
HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # A compile for a described chip cannot be read back without one:
    # keep such entries out of the persistent cache.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _shape(sharding, n, dtype):
    return jax.ShapeDtypeStruct((n,), dtype, sharding=sharding)


def _compile_for_chip(fn, *args, kernel: bool = True, **kwargs):
    """Compile ``fn`` for the described chip and check what the chip's
    compiler made of it: a Mosaic kernel (where ``kernel``), within the
    device's memory."""
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    compiled = jitted.lower(*args, **kwargs).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == kernel
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < HBM_BYTES, mem
    return compiled


def test_pallas_window_kernel_compiles_for_v5e(one_chip):
    win = Window(zoom=12, row0=0, col0=0, height=256, width=256)
    f = functools.partial(bin_rowcol_window_pallas, window=win,
                          interpret=False)
    _compile_for_chip(lambda r, c: f(r, c),
                      _shape(one_chip, POINTS, jnp.int64),
                      _shape(one_chip, POINTS, jnp.int64))


@pytest.mark.parametrize("variant", ["count", "streams8", "weighted"])
def test_partitioned_window_kernel_compiles_for_v5e(one_chip, variant):
    # The ~1024x1280 z15 metro window the binning path routes here.
    win = Window(zoom=15, row0=0, col0=0, height=1024, width=1280)
    f = functools.partial(bin_rowcol_window_partitioned, window=win,
                          interpret=False,
                          streams=8 if variant == "streams8" else 1)
    rc = [_shape(one_chip, POINTS, jnp.int64)] * 2
    if variant == "weighted":
        _compile_for_chip(lambda r, c, w: f(r, c, weights=w), *rc,
                          _shape(one_chip, POINTS, jnp.float32))
    else:
        _compile_for_chip(lambda r, c: f(r, c), *rc)


#: The benchmark's count job: 9.5M emissions a level, not a whole number
#: of 1024-lane chunks, reduced into as many cells.
BENCH_EMISSIONS = 9_500_000


@pytest.mark.parametrize("variant", ["count", "streams", "weighted",
                                     "unaligned-9.5m"])
def test_segment_kernel_compiles_for_v5e(one_chip, variant):
    n = BENCH_EMISSIONS if variant == "unaligned-9.5m" else 2 * POINTS
    keys = _shape(one_chip, n, jnp.int64)
    if variant == "weighted":
        f = functools.partial(aggregate_sorted_keys_partitioned,
                              capacity=n, interpret=False, weight_bound=64)
        _compile_for_chip(lambda k, w: f(k, sorted_weights=w), keys,
                          _shape(one_chip, n, jnp.float64))
    else:
        f = functools.partial(aggregate_sorted_keys_partitioned,
                              capacity=n, interpret=False,
                              streams=4 if variant == "streams" else 1)
        _compile_for_chip(f, keys)


def test_weighted_segment_reduce_compiles_for_v5e(one_chip):
    """One level of the weighted (float64) cascade at the benchmark's
    width, as the chip compiles it: a scan and a compaction with no
    scatter, within the device's memory."""
    n = BENCH_EMISSIONS
    compiled = _compile_for_chip(
        functools.partial(aggregate_sorted_keys, capacity=n),
        _shape(one_chip, n, jnp.int64), _shape(one_chip, n, jnp.float64),
        kernel=False)
    assert not re.search(r"\sscatter\(", compiled.as_text())


def test_z21_cascade_compiles_for_v5e(one_chip, monkeypatch):
    """One whole production cascade program (z21 detail, 16 stored
    levels) on the partitioned backend, compiled as the chip runs it."""
    from heatmap_tpu.ops import sparse_partitioned
    from heatmap_tpu.pipeline import cascade as cascade_mod

    # The cascade leaves ``interpret`` at its default, which follows the
    # default device — the CPU here. Steer it to the compiled kernel.
    monkeypatch.setattr(sparse_partitioned, "default_interpret",
                        lambda: False)
    jax.clear_caches()
    n = 2 * POINTS
    ccfg = cascade_mod.CascadeConfig(detail_zoom=21, min_detail_zoom=5,
                                     result_delta=5)
    try:
        _compile_for_chip(
            cascade_mod._build_cascade_jit,
            _shape(one_chip, n, jnp.int64), _shape(one_chip, n, jnp.int64),
            config=ccfg, n_slots=34, valid=_shape(one_chip, n, jnp.bool_),
            capacity=n, backend="partitioned")
    finally:
        jax.clear_caches()


#: The planet cell's job: 2^24 points, 2^25 emissions over a 2x2 v5e,
#: 20,001 groups, the exchange widths its plans bucket to.
MESH_POINTS = 1 << 24


def test_routed_range_cascade_compiles_for_v5e_2x2(topo, one_chip,
                                                   monkeypatch):
    """The owner-routed Morton range program at 2^24 points, compiled
    for four described v5e chips: it fits a chip's 16 GB and exchanges
    lanes with an all-to-all (no replicated (ndev, n) operand)."""
    from heatmap_tpu.ops import sparse_partitioned
    from heatmap_tpu.parallel.mesh import DATA_AXIS, make_mesh, named_sharding
    from heatmap_tpu.pipeline import bucketing
    from heatmap_tpu.pipeline import cascade as cascade_mod

    monkeypatch.setattr(sparse_partitioned, "default_interpret",
                        lambda: False)
    jax.clear_caches()
    mesh = make_mesh(devices=topo.devices)
    n = 2 * MESH_POINTS
    rows = named_sharding(mesh, DATA_AXIS)
    route = tuple(bucketing.bucket_size(m, "geometric")
                  for m in (n // 16 + n // 400, n // 4 + n // 400))
    ccfg = cascade_mod.CascadeConfig(detail_zoom=21, min_detail_zoom=5,
                                     result_delta=5)
    try:
        compiled = _compile_for_chip(
            cascade_mod._build_cascade_jit,
            _shape(rows, n, jnp.int64), _shape(rows, n, jnp.int64),
            config=ccfg, n_slots=20001, valid=_shape(rows, n, jnp.bool_),
            capacity=n, backend="partitioned", mesh=mesh,
            partition_splits=jax.ShapeDtypeStruct(
                (3,), jnp.int64, sharding=named_sharding(mesh)),
            route_capacity=route)
        assert "all-to-all" in compiled.as_text()
    finally:
        jax.clear_caches()
