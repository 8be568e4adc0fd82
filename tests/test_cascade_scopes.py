"""The jitted cascade names its stages with ``jax.named_scope``: the scope
path reaches every level's operations in the compiled program, inside
the segment kernel's ``lax.cond`` branches too, so a device trace can
split the cascade's time by stage."""

import re

import jax
import jax.numpy as jnp
import pytest

from heatmap_tpu.pipeline import cascade

CONFIG = cascade.CascadeConfig(detail_zoom=8, min_detail_zoom=2,
                               result_delta=2)
N = 4096


def compiled_op_names(backend: str, weighted: bool) -> set:
    spec = jax.ShapeDtypeStruct
    weights = spec((N,), jnp.float64) if weighted else None
    lowered = cascade._build_cascade_jit.lower(
        spec((N,), jnp.int64), spec((N,), jnp.int32), config=CONFIG,
        n_slots=4, valid=spec((N,), jnp.bool_), weights=weights,
        acc_dtype=jnp.float64 if weighted else None, capacity=N,
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
    slab = {holds(names, "slab.channel/"), holds(names, "slab.fallback/")}
    assert slab == ({True} if backend == "partitioned" else {False})


def test_slab_scopes_sit_inside_the_cond_branches():
    names = compiled_op_names("partitioned", False)
    for branch in ("slab.channel", "slab.fallback"):
        inside = re.compile(r"cascade\.segment-reduce/(.+/)?cond/branch_\d+_fun/"
                            + re.escape(branch) + "/")
        assert any(inside.search(n) for n in names)
