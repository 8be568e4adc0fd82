"""bench.py prints ONE JSON line with the headline metric; pin its shape,
and pin that a failure is an error rather than a fallback."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_bench(*extra):
    return subprocess.run(
        [sys.executable, "bench.py", "--cpu", "--n", "262144",
         "--steps", "2", "--baseline-n", "65536", *extra],
        capture_output=True, text=True, timeout=420, cwd=REPO,
        env=dict(os.environ, PYTHONPATH=REPO),
    )


def test_bench_emits_one_json_line():
    r = _run_bench()
    assert r.returncode == 0, r.stderr
    line = r.stdout.strip().splitlines()[-1]
    out = json.loads(line)
    assert out["unit"] == "points/sec"
    assert out["value"] > 0
    assert out["vs_baseline"] > 0
    assert out["device"] == "cpu"
    assert out["bin_backend_resolved"] == "xla"  # auto on CPU


def test_bench_backend_failure_exits_nonzero():
    # pallas has no compiled CPU lowering: the bench must fail loudly,
    # not swap in the scatter path or print a result.
    r = _run_bench("--bin-backend", "pallas")
    assert r.returncode != 0
    assert '"value"' not in r.stdout


def test_bench_without_cpu_flag_needs_a_tpu():
    r = subprocess.run(
        [sys.executable, "bench.py", "--n", "4096", "--steps", "1"],
        capture_output=True, text=True, timeout=120, cwd=REPO,
        env=dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu"),
    )
    assert r.returncode != 0
    assert "found no TPU" in r.stderr
    assert '"value"' not in r.stdout
