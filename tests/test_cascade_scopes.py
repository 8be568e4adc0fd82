"""The jitted cascade names its stages with ``jax.named_scope``: the scope
path reaches every level's operations in the compiled program, down to
the segment kernel's ``slab.channel`` and ``slab.tail``, so a device
trace can split the cascade's time by stage."""

import re

import jax
import jax.numpy as jnp
import pytest

from heatmap_tpu.pipeline import cascade

CONFIG = cascade.CascadeConfig(detail_zoom=8, min_detail_zoom=2,
                               result_delta=2)
N = 4096


def compiled_op_names(backend: str, weighted: bool, capacity: int = N) -> set:
    spec = jax.ShapeDtypeStruct
    weights = spec((N,), jnp.float64) if weighted else None
    lowered = cascade._build_cascade_jit.lower(
        spec((N,), jnp.int64), spec((N,), jnp.int32), config=CONFIG,
        n_slots=4, valid=spec((N,), jnp.bool_), weights=weights,
        acc_dtype=jnp.float64 if weighted else None, capacity=capacity,
        backend=backend)
    return set(re.findall(r'op_name="([^"]*)"', lowered.compile().as_text()))


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
