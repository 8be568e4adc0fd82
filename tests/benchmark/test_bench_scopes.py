"""Device time by named scope (``benchmark/scopes.py``): each instant of
busy time goes to the innermost operation running then, and from it to
its scopes; an operation inside a called computation takes the scopes
of the operation that encloses it."""

import glob
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import devtrace, scopes  # noqa: E402

ROOT = "jit(build_cascade)/"


def op(start, end, op_name, device="/device:TPU:0"):
    return {"device": device, "op": "x", "module": "jit_build_cascade(1)",
            "start": start, "end": end, "op_name": op_name}


def test_scopes_of_an_op_name():
    name = ROOT + "cascade.level3/cascade.segment-reduce/jit(f)/cond"
    assert scopes.scopes(name) == ("cascade.level3", "cascade.segment-reduce")
    assert scopes.rooted(name)
    assert not scopes.rooted("cond/branch_0_fun/slab.fallback/scatter-add")
    assert scopes.scopes(None) == ()


def test_nested_conditional_splits_busy_time():
    ops = [
        op(0, 100, ROOT + "cascade.level3/cascade.segment-reduce/cond"),
        op(10, 60, "cond/branch_0_fun/slab.fallback/scatter-add"),
        op(60, 90, "cond/branch_0_fun/slab.fallback/fusion"),
        op(100, 130, ROOT + "cascade.sort/sort"),
        op(150, 160, None),
        op(150, 155, "copy"),  # no path of its own: takes the enclosing one
    ]
    got = scopes.scope_ns(ops)
    assert got == {
        "cascade.level3/cascade.segment-reduce": 20,
        "cascade.level3/cascade.segment-reduce/slab.fallback": 80,
        "cascade.sort": 30,
        "": 10,
    }
    assert sum(got.values()) == devtrace.busy_ns(ops)
    assert scopes.under(got, "cascade.segment-reduce", "slab.fallback") == 20
    assert scopes.under(got, "slab.fallback") == 80
    assert scopes.levels(got) == {
        3: {"reduce": 20, "fallback": 80},
        None: {"sort": 30, "other": 10},
    }


def test_devices_are_averaged_and_overlap_counts_once():
    ops = [op(0, 10, ROOT + "cascade.sort/sort", device="/device:TPU:0"),
           op(0, 30, ROOT + "cascade.sort/sort", device="/device:TPU:1"),
           op(20, 40, ROOT + "cascade.keys/or", device="/device:TPU:1"),
           op(25, 35, ROOT + "cascade.level0/cascade.segment-reduce/f",
              device="/device:TPU:1")]
    got = scopes.scope_ns(ops)
    assert sum(got.values()) == devtrace.busy_ns(ops) == 25
    assert got["cascade.sort"] == (10 + 20) / 2


def test_hlo_text_names_instructions_and_fusions():
    hlo = """
%fused_computation.3 (param_0: s64[8]) -> s64[8] {
  %param_0 = s64[8]{0} parameter(0)
  ROOT %or.1 = s64[8]{0} or(%param_0, %param_0), metadata={op_name="jit(build_cascade)/cascade.keys/or"}
}

ENTRY %main.9 (codes: s64[8]) -> s64[8] {
  %codes = s64[8]{0} parameter(0), metadata={op_name="codes"}
  %sort.2 = s64[8]{0} sort(%codes), dimensions={0}, to_apply=%lt, metadata={op_name="jit(build_cascade)/cascade.sort/sort"}
  ROOT %fusion.3 = s64[8]{0} fusion(%sort.2), kind=kLoop, calls=%fused_computation.3
}
"""
    names = scopes.hlo_op_names(hlo)
    assert names["sort.2"] == ROOT + "cascade.sort/sort"
    assert names["fusion.3"] == ROOT + "cascade.keys/or"  # its root's
    assert scopes.instruction(
        "%fusion.3 = s64[8]{0} fusion(%sort.2), kind=kLoop") == "fusion.3"


def test_recorded_cpu_trace_through_the_hlo_text(tmp_path):
    """A small weighted scatter cascade, run under the profiler on the
    CPU: its operations carry only instruction names, which the compiled
    HLO text maps to their scopes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from heatmap_tpu.pipeline import cascade

    jax.config.update("jax_enable_x64", True)
    config = cascade.CascadeConfig(detail_zoom=8, min_detail_zoom=2,
                                   result_delta=2)
    rng = np.random.default_rng(0)
    n = 1 << 15
    args = (jnp.asarray(rng.integers(0, 1 << 16, n), jnp.int64),
            jnp.asarray(rng.integers(0, 4, n), jnp.int32))
    kwargs = dict(config=config, n_slots=4,
                  weights=jnp.asarray(rng.random(n)),
                  valid=jnp.asarray(rng.random(n) < 0.9),
                  acc_dtype=jnp.float64, capacity=n, backend="scatter")
    jax.block_until_ready(cascade._build_cascade_jit(*args, **kwargs))
    hlo = cascade._build_cascade_jit.lower(*args, **kwargs).compile().as_text()
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(cascade._build_cascade_jit(*args, **kwargs))
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)

    ops = [o for o in scopes.read_ops(path, hlo, module="build_cascade")
           if o["module"] and "build_cascade" in o["module"]]
    assert ops and any(o["op_name"] for o in ops)
    got = scopes.scope_ns(ops)
    assert sum(got.values()) == pytest.approx(devtrace.busy_ns(ops))
    assert scopes.under(got, "cascade.sort") > 0
    assert scopes.under(got, "cascade.segment-reduce") > 0
    assert set(scopes.levels(got)) >= set(range(config.n_levels + 1))
    assert scopes.under(got, "slab.fallback") == 0
