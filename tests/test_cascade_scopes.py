"""The jitted cascade names its stages with ``jax.named_scope``: the scope
path reaches every level's operations in the compiled program, down to
the segment kernel's ``slab.channel`` and ``slab.tail`` and the scatter
backend's ``segment.scan`` and ``segment.compact``, so a device trace
can split the cascade's time by stage."""

import functools
import re

import jax
import jax.numpy as jnp
import pytest

from heatmap_tpu.pipeline import cascade

CONFIG = cascade.CascadeConfig(detail_zoom=8, min_detail_zoom=2,
                               result_delta=2)
N = 4096


@functools.lru_cache(maxsize=None)
def compiled_text(backend: str, weighted: bool, capacity: int = N) -> str:
    spec = jax.ShapeDtypeStruct
    weights = spec((N,), jnp.float64) if weighted else None
    lowered = cascade._build_cascade_jit.lower(
        spec((N,), jnp.int64), spec((N,), jnp.int32), config=CONFIG,
        n_slots=4, valid=spec((N,), jnp.bool_), weights=weights,
        acc_dtype=jnp.float64 if weighted else None, capacity=capacity,
        backend=backend)
    return lowered.compile().as_text()


def compiled_op_names(backend: str, weighted: bool, capacity: int = N) -> set:
    return set(re.findall(r'op_name="([^"]*)"',
                          compiled_text(backend, weighted, capacity)))


def holds(names: set, path: str) -> bool:
    return any(path in name for name in names)


@pytest.mark.parametrize("backend,weighted", [("partitioned", False),
                                              ("scatter", True)])
def test_every_stage_and_level_is_scoped(backend, weighted):
    names = compiled_op_names(backend, weighted)
    assert holds(names, "jit(build_cascade)/cascade.keys/")
    assert holds(names, "cascade.sort/")
    for level in range(CONFIG.n_levels + 1):
        assert holds(names, f"cascade.level{level}/cascade.segment-reduce/")
    assert not holds(names, f"cascade.level{CONFIG.n_levels + 1}/")
    assert holds(names, "slab.channel/") == (backend == "partitioned")
    # One output block (capacity N) leaves nothing to straddle.
    assert not holds(names, "slab.tail/")
    assert not holds(names, "slab.fallback/")


def test_slab_scopes_sit_inside_the_cond_branches():
    """The kernel and its straddle tail sit straight under every level's
    segment reduce; no ``cond`` branch chooses between paths."""
    names = compiled_op_names("partitioned", False, capacity=2 * (1 << 16))
    for scope in ("slab.channel", "slab.tail"):
        inside = re.compile(r"cascade\.level\d+/cascade\.segment-reduce/"
                            r"(jit\(\w+\)/)?" + re.escape(scope) + "/")
        assert any(inside.search(n) for n in names)
    assert not any("cond/branch" in n for n in names)


@pytest.mark.parametrize("weighted", [True, False])
def test_scatter_backend_reduces_each_level_without_a_scatter(weighted):
    """Every level's segment reduce is a segmented scan and a compaction;
    no scatter instruction sits under any of them."""
    names = compiled_op_names("scatter", weighted)
    for level in range(CONFIG.n_levels + 1):
        reduce = f"cascade.level{level}/cascade.segment-reduce/"
        assert holds(names, reduce + "segment.scan/")
        assert holds(names, reduce + "segment.compact/")
    scatters = [line for line in compiled_text("scatter", weighted).splitlines()
                if re.search(r"\sscatter\(", line)
                and "cascade.segment-reduce" in line]
    assert not scatters
