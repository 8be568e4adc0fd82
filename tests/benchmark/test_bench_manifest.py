"""The manifest loader, and ``BENCHMARK.json`` against the rules it keeps."""

import json
import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness  # noqa: E402

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def write(path, text):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(text)


def test_fixture_cell_from_its_files_alone(tmp_path):
    """A cell is assembled from a config file, a traffic file and metric
    files, found by the names in the manifest."""
    root = str(tmp_path)
    mix = json.load(open(os.path.join(REPO, "benchmark", "traffic",
                                      "fleet-day-5m.json")))
    write(os.path.join(root, "benchmark", "traffic", "tiny.json"),
          json.dumps({**mix, "points_per_job": 10}))
    write(os.path.join(root, "benchmark", "configs", "cfg.json"),
          json.dumps({"job": {"detail_zoom": 12}, "limits": {"x": 0}}))
    write(os.path.join(root, "benchmark", "metrics", "rate.py"),
          "def read(run):\n    return run['n'] * 2\n")
    write(os.path.join(root, "benchmark", "metrics", "quiet.layer.py"),
          "def read(run):\n    return None\n")
    write(os.path.join(root, "BENCHMARK.json"), json.dumps({
        "configs": [{"name": "cfg", "file": "benchmark/configs/cfg.json"}],
        "workloads": [{"name": "cfg.tiny", "config": "cfg", "traffic": "tiny",
                       "chips": 1}],
        "end_to_end": [{"name": "rate", "unit": "points/s"}],
        "per_layer": [{"name": "quiet.layer", "unit": "%",
                       "workloads": ["cfg.tiny"]},
                      {"name": "elsewhere", "unit": "%",
                       "workloads": ["other.cell"]}],
    }))
    # A mix for another driver: the loader reads it as it stands and
    # leaves its parameters to that driver.
    write(os.path.join(root, "benchmark", "traffic", "live.json"),
          json.dumps({"driver": "ingest", "ticks_per_s": 4}))
    write(os.path.join(root, "BENCHMARK.json"), json.dumps({
        "configs": [{"name": "cfg", "file": "benchmark/configs/cfg.json"}],
        "workloads": [{"name": "cfg.tiny", "config": "cfg", "traffic": "tiny",
                       "chips": 1},
                      {"name": "cfg.live", "config": "cfg", "traffic": "live",
                       "chips": 1}],
        "end_to_end": [{"name": "rate", "unit": "points/s"}],
        "per_layer": [{"name": "quiet.layer", "unit": "%",
                       "workloads": ["cfg.tiny"]},
                      {"name": "elsewhere", "unit": "%",
                       "workloads": ["other.cell"]}],
    }))
    live = harness.load_cell(root, "cfg.live")
    assert live.traffic == {"driver": "ingest", "ticks_per_s": 4}
    assert live.per_layer == []
    cell = harness.load_cell(root, "cfg.tiny")
    assert cell.config == {"job": {"detail_zoom": 12}, "limits": {"x": 0}}
    assert cell.traffic["points_per_job"] == 10
    assert [m["name"] for m in cell.per_layer] == ["quiet.layer"]
    run = {"n": 21}
    assert harness.read_metrics(cell, run, trace=False) == {
        "rate": {"value": 42, "unit": "points/s"}}
    # A reader that finds nothing leaves its metric out of the line.
    assert harness.read_metrics(cell, run, trace=True) == {}
    with pytest.raises(SystemExit, match="no workload"):
        harness.load_cell(root, "cfg.absent")


def test_manifest_keys():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["command"] == ["python3", "benchmark/run.py"]
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in MANIFEST["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in MANIFEST["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in MANIFEST["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


def test_run_seconds_fit_a_full_check():
    rs = MANIFEST["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("metric", METRICS, ids=[m["name"] for m in METRICS])
def test_metric_names_units_and_reader(metric):
    assert NAME.match(metric["name"])
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    reader = harness.load_reader(os.path.join(REPO, "benchmark"),
                                 metric["name"])
    assert callable(reader)
    if "moves" in metric:
        assert metric["moves"] in {m["name"] for m in MANIFEST["end_to_end"]}
        assert set(metric.get("workloads", CELLS)) <= set(CELLS)


@pytest.mark.parametrize("workload", CELLS)
def test_every_cell_loads_and_reports_enough(workload):
    cell = harness.load_cell(REPO, workload)
    names = [m["name"] for m in cell.end_to_end]
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    assert cell.traffic["driver"]
    assert cell.config["limits"]
    assert NAME.match(workload)
    entry = next(c for c in MANIFEST["configs"] if c["name"] == cell.config_name)
    assert entry["file"].startswith(tuple(p + "/" for p in MANIFEST["paths"]))
    assert cell.config["name"] == cell.config_name
    assert set(entry["reduced"]) <= set(cell.config["job"])
