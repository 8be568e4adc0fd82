"""The harness: a cell assembled from files by name, run, and reported.

``BENCHMARK.json`` names each cell's configuration, traffic mix and
metrics. Everything that belongs to one of them sits in a file of its
own, found by that name under the benchmark's directory:

- ``configs/<config>.json`` (the path the manifest's ``file`` gives):
  the job's settings (``job``), the comparison's ``limits``, and the
  source, cuts, assumptions, guarantees and layout of the deployment;
- ``traffic/<traffic>.json``: the ``driver`` module that runs that kind
  of traffic (``benchmark/<driver>.py``, with ``run(cell, seed=,
  seconds=, trace=, t_start=)``), and the parameters that driver reads
  and checks;
- ``metrics/<metric>.py``: a reader ``read(run) -> float | None`` that
  takes one metric from what the driver recorded. ``None`` leaves the
  metric out of the result line.

Adding a cell, a mix or a metric adds files and edits none.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import sys

BENCH_DIR = "benchmark"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    bench_dir: str


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: str, workload: str) -> Cell:
    """The cell named ``workload`` in ``<root>/BENCHMARK.json``."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    w = cells[workload]
    config_entry = {c["name"]: c for c in manifest["configs"]}[w["config"]]
    with open(os.path.join(root, config_entry["file"])) as f:
        config = json.load(f)
    bench_dir = os.path.join(root, BENCH_DIR)
    with open(os.path.join(bench_dir, "traffic", f"{w['traffic']}.json")) as f:
        mix = json.load(f)
    return Cell(
        name=workload, chips=int(w["chips"]), config_name=w["config"],
        config=config, traffic=mix,
        end_to_end=[m for m in manifest["end_to_end"]
                    if _applies(m, workload)],
        per_layer=[m for m in manifest["per_layer"] if _applies(m, workload)],
        bench_dir=bench_dir,
    )


def load_reader(bench_dir: str, metric: str):
    """The ``read`` function of ``metrics/<metric>.py``."""
    path = os.path.join(bench_dir, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + metric.replace(".", "_").replace("-", "_"),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def read_metrics(cell: Cell, run: dict, trace: bool) -> dict:
    out = {}
    for m in cell.per_layer if trace else cell.end_to_end:
        value = load_reader(cell.bench_dir, m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def device_record() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def check_devices(cell: Cell) -> str | None:
    """Why this machine cannot run the cell, or None."""
    dev = device_record()
    if dev["platform"] != "tpu":
        return f"no TPU: the default JAX platform is {dev['platform']!r}"
    if dev["count"] < cell.chips:
        return f"the cell needs {cell.chips} chips, JAX found {dev['count']}"
    return None


def enable_caches() -> str:
    """The program's persistent compile cache, holding every program
    set-up compiles: a later run of the cell in this checkout loads them
    all. (The driver keeps the window's own programs out of it.)"""
    import jax

    from heatmap_tpu.utils.compile_cache import enable_compile_cache

    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def driver(cell: Cell):
    return importlib.import_module(f"benchmark.{cell.traffic['driver']}")


def report(cell: Cell, outcome: dict, trace: bool) -> dict:
    """The result line, with the compared numbers last."""
    metrics = read_metrics(cell, outcome["run"], trace)
    result = {
        "correct": outcome["correct"],
        "attempted": outcome["attempted"],
        "failed": outcome["failed"],
        "metrics": metrics,
        "device": outcome["device"],
    }
    if trace and outcome.get("breakdown"):
        result["breakdown"] = outcome["breakdown"]
    result["checks"] = outcome["checks"]
    return result


def main(cell: Cell, seed: int, seconds: float, trace: bool,
         t_start: float, require_chip: bool = True) -> int:
    import jax

    if require_chip:
        why = check_devices(cell)
        if why is not None:
            print(f"benchmark: {why}", file=sys.stderr)
            return 2
    cache = enable_caches()
    jax.config.update("jax_enable_x64", True)
    print(f"benchmark: {cell.name} seed {seed}, compile cache {cache}",
          file=sys.stderr, flush=True)
    outcome = driver(cell).run(cell, seed=seed, seconds=seconds, trace=trace,
                               t_start=t_start)
    outcome["device"] = {**device_record(), **outcome["device"]}
    result = report(cell, outcome, trace)
    for name, check in outcome["checks"].items():
        print(f"check {name} {check['value']!r} limit {check['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
