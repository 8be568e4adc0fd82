"""A whole benchmark run on the CPU, past the look for a chip, at a small
size: sound, it reads correct; with the timed path broken underneath,
once for each fault a backfill cell can have, it reads not correct.

The cells run on one chip and exchange nothing between chips, so the
fault of a lost exchange does not arise.
"""

import json
import os
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness, roofline  # noqa: E402

CONFIGS = ["refjob-z21", "dwell-z21"]


@pytest.fixture
def cell_of(monkeypatch):
    monkeypatch.setattr(harness, "enable_caches", lambda: "off")

    def make(config):
        cell = harness.load_cell(REPO, f"{config}.fleet-day-5m")
        cell.traffic = {**cell.traffic, "points_per_job": 12000,
                        "users": 60, "excluded_users": 8, "route_users": 8}
        return cell

    return make


def drive(cell, capsys, seed, trace=False):
    rc = harness.main(cell, seed=seed, seconds=0, trace=trace,
                      t_start=time.perf_counter(), require_chip=False)
    out, err = capsys.readouterr()
    assert rc == 0
    assert err.strip().splitlines()[-1].startswith("check ")
    return json.loads(out.strip().splitlines()[-1])


def half_the_rows(monkeypatch):
    """Half of every batch left out before the program sees it."""
    import heatmap_tpu.pipeline as pipeline

    real = pipeline.run_job

    class Half:
        def __init__(self, source):
            self.source = source

        def batches(self, batch_size=1 << 20):
            for b in self.source.batches(batch_size):
                n = len(b["latitude"]) // 2
                yield {k: v[:n] for k, v in b.items()}

    monkeypatch.setattr(pipeline, "run_job",
                        lambda source, *a, **k: real(Half(source), *a, **k))


def altered_answer(monkeypatch):
    """One sum changed where the cascade's levels are decoded."""
    from heatmap_tpu.pipeline import cascade

    real = cascade.decode_levels

    def decode(level_data, config):
        out = real(level_data, config)
        out[0]["value"][0] *= 1.001
        return out

    monkeypatch.setattr(cascade, "decode_levels", decode)


def state_unchanged(monkeypatch):
    """The job returns without producing anything."""
    import heatmap_tpu.pipeline as pipeline

    monkeypatch.setattr(pipeline, "run_job", lambda *a, **k: {})


def job_raises(monkeypatch):
    """The window's job fails (the warm job runs): counted as failed,
    and its missing output compared like any other."""
    import heatmap_tpu.pipeline as pipeline

    real, calls = pipeline.run_job, []

    def fail_after_warm_job(*a, **k):
        calls.append(1)
        if len(calls) == 1:
            return real(*a, **k)
        raise RuntimeError("planted failure")

    monkeypatch.setattr(pipeline, "run_job", fail_after_warm_job)


FAULTS = {"half_the_rows": half_the_rows, "altered_answer": altered_answer,
          "state_unchanged": state_unchanged, "job_raises": job_raises}


@pytest.mark.parametrize("config", CONFIGS)
def test_sound_run_is_correct(cell_of, capsys, config):
    result = drive(cell_of(config), capsys, seed=2**35 + 1)
    assert result["correct"] is True
    assert result["attempted"] == 1 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"backfill_points_per_s", "setup_s"}
    assert result["device"]["platform"] == "cpu"


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("config", CONFIGS)
def test_broken_timed_path_is_not_correct(cell_of, capsys, monkeypatch,
                                          config, fault):
    FAULTS[fault](monkeypatch)
    result = drive(cell_of(config), capsys, seed=2**35 + 2)
    assert result["correct"] is False


def test_traced_run_reports_every_per_layer_metric(cell_of, capsys,
                                                   monkeypatch, tmp_path):
    peaks = tmp_path / "peaks.json"
    peaks.write_text(json.dumps({"cpu": {"hbm_bytes_per_s": 1e10}}))
    monkeypatch.setattr(roofline, "PEAKS", str(peaks))
    cell = cell_of("refjob-z21")
    result = drive(cell, capsys, seed=5, trace=True)
    assert result["correct"] is True
    assert set(result["metrics"]) == {m["name"] for m in cell.per_layer}
    assert all(m["value"] is not None for m in result["metrics"].values())
    assert result["breakdown"]["device_ops"]
    assert result["breakdown"]["idle_gaps"]
    assert 0 < result["device"]["busy_s"] <= result["device"]["window_s"]
