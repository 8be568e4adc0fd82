"""chip_smoke.py on the CPU: its phases at a tiny size (Pallas kernels in
interpret mode), and its refusal to run anywhere but on a TPU."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

#: Tiny CLI run: CPU, the partitioned cascade asked for explicitly (auto
#: resolves to scatter off TPU); z12 detail keeps the oracle fast.
CPU_FLAGS = ("--backend", "cpu", "--cascade-backend", "partitioned")


@pytest.fixture(autouse=True)
def _restore_cache_dir():
    # The CLI points the compile cache at the checkout; keep that out of
    # the rest of this worker's tests.
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def _phases(out: str) -> dict:
    phases = {}
    for line in out.splitlines():
        if line.startswith('{"phase"'):
            rec = json.loads(line)
            phases[rec["phase"]] = rec
    return phases


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_exits_nonzero_without_a_tpu(argv):
    r = subprocess.run(
        [sys.executable, "chip_smoke.py", *argv], cwd=REPO,
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO))
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_one_chip_phases_pass_on_cpu(tmp_path, capsys):
    clock = chip_smoke.PhaseClock()
    chip_smoke.run_one_chip(str(tmp_path), clock, n=3000, oracle_n=600,
                            zooms=(12, 5), extra=CPU_FLAGS,
                            expect_backend="partitioned",
                            expect_interpret=True)
    phases = _phases(capsys.readouterr().out)
    assert set(phases) >= {"native", "backfill", "scatter", "oracle",
                           "serve"}
    assert phases["backfill"]["resolved"] == ["partitioned"]
    assert phases["backfill"]["interpret"] == [True]
    assert phases["scatter"]["backends"] == ["scatter"]
    assert phases["scatter"]["identical"]
    assert phases["oracle"]["chunks"] == 4
    assert phases["serve"]["z1_total"] == phases["serve"]["points"]
    # The comparison runs are removed once compared; the backfill stays.
    assert sorted(p.name for p in tmp_path.iterdir() if p.is_dir()) == [
        "backfill"]


def test_backfill_check_rejects_the_wrong_backend(tmp_path):
    with pytest.raises(chip_smoke.SmokeFailure, match="resolved to"):
        chip_smoke.run_one_chip(
            str(tmp_path), chip_smoke.PhaseClock(), n=500, oracle_n=100,
            zooms=(12, 5), extra=CPU_FLAGS[:2],
            expect_backend="partitioned", expect_interpret=True)


def _write_level(path, **cols):
    base = {"user_idx": [0, 0, 1], "timespan_idx": [0, 0, 0],
            "coarse_row": [1, 1, 1], "coarse_col": [2, 3, 3],
            "value": [1.0, 2.0, 3.0]}
    base.update(cols)
    path.mkdir(exist_ok=True)
    np.savez(path / "level_z06.npz",
             **{k: np.asarray(v) for k, v in base.items()})


def test_levels_identical_sees_one_changed_value(tmp_path):
    _write_level(tmp_path / "a")
    _write_level(tmp_path / "b")
    _write_level(tmp_path / "c", value=[1.0, 2.0, 3.5])
    assert chip_smoke.levels_identical(str(tmp_path / "a"),
                                       str(tmp_path / "b"))
    assert not chip_smoke.levels_identical(str(tmp_path / "a"),
                                           str(tmp_path / "c"))


def test_blob_count_counts_distinct_coarse_tiles(tmp_path):
    _write_level(tmp_path / "a")
    # (user 0, tile 1/2), (user 0, tile 1/3), (user 1, tile 1/3)
    assert chip_smoke.blob_count(str(tmp_path / "a")) == 3


def test_oracle_slice_matches_the_reference():
    assert chip_smoke.oracle_slice(400, chip_smoke.SEED, 12, 5) > 0


def test_source_points_drops_background_rows():
    from heatmap_tpu.io.sources import SyntheticSource

    n = 2000
    batch = next(SyntheticSource(n=n, seed=3).batches(n))
    want = sum(s != "background" for s in batch["source"])
    assert chip_smoke.source_points(n, 3) == want < n


def test_serve_tiles_rejects_a_wrong_point_total(tmp_path):
    from heatmap_tpu.io import open_sink, open_source
    from heatmap_tpu.pipeline import BatchJobConfig, run_job

    with open_sink(f"arrays:{tmp_path}") as sink:
        run_job(open_source("synthetic:800:2"), sink,
                BatchJobConfig(detail_zoom=12, min_detail_zoom=5))
    total = chip_smoke.source_points(800, 2)
    assert chip_smoke.serve_tiles(str(tmp_path), total)["z1_total"] == total
    with pytest.raises(chip_smoke.SmokeFailure, match="z1 tiles sum"):
        chip_smoke.serve_tiles(str(tmp_path), total + 1)


def test_four_chip_phase_on_four_virtual_devices(tmp_path):
    code = (
        "import sys, jax\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "from heatmap_tpu.parallel.mesh import force_cpu_devices\n"
        "force_cpu_devices(4)\n"
        "jax.config.update('jax_enable_x64', True)\n"
        "import chip_smoke as cs\n"
        f"cs.run_four_chips({str(tmp_path)!r}, cs.PhaseClock(), n=3000,\n"
        "                  detail_zoom=12, min_detail_zoom=5)\n"
    )
    env = dict(os.environ, PYTHONPATH=REPO)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    phases = _phases(r.stdout)
    assert phases["dp_morton_compare"]["identical"]
    assert phases["dp_off_compare"]["identical"]
    assert len(phases["single_device"]["placements"]) == 1
    for name in ("dp_morton", "dp_off"):
        assert phases[name]["mesh_devices"] == 4
        assert len(phases[name]["placements"]) == 1
        assert phases[name]["placements"][0]["out"] == [4]
        # run_job places the gspmd cascade's inputs in per-chip blocks.
        assert phases[name]["inputs_spread"] is True
