"""Without a TPU, and without the program, the benchmark prints no result
and exits non-zero."""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CMD = [sys.executable, "benchmark/run.py", "--workload",
       "dwell-z21.fleet-day-5m", "--seed", "1", "--seconds", "1",
       "--trace", "0"]


def run_in(cwd):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_ENABLE_COMPILATION_CACHE": "false"}
    return subprocess.run(CMD, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=300)


def no_result(stdout):
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return False
        except (ValueError, TypeError):
            continue
    return True


def test_no_tpu_exits_nonzero_without_result():
    proc = run_in(REPO)
    assert proc.returncode != 0
    assert no_result(proc.stdout)
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        paths = json.load(f)["paths"]
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for p in paths:
        shutil.copytree(os.path.join(REPO, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = run_in(str(tmp_path))
    assert proc.returncode != 0
    assert no_result(proc.stdout)
