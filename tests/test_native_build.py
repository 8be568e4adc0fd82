"""The native library builds once and loads everywhere when several
processes import ``heatmap_tpu.native`` at once against an empty build
directory, as parallel test workers do in a fresh checkout."""

import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Loads a copy of heatmap_tpu/native.py as ``heatmap_tpu.native``: the
# copy's native/ directory (sources, no build) sits beside it.
LOAD = """
import importlib.util, sys
import heatmap_tpu
spec = importlib.util.spec_from_file_location("heatmap_tpu.native", sys.argv[1])
native = importlib.util.module_from_spec(spec)
sys.modules["heatmap_tpu.native"] = native
spec.loader.exec_module(native)
print(native.available())
"""


def test_parallel_imports_against_an_empty_build_dir_all_load(tmp_path):
    shutil.copytree(os.path.join(REPO, "native"), tmp_path / "native",
                    ignore=shutil.ignore_patterns("build"))
    (tmp_path / "heatmap_tpu").mkdir()
    module = tmp_path / "heatmap_tpu" / "native.py"
    shutil.copy(os.path.join(REPO, "heatmap_tpu", "native.py"), module)
    env = {k: v for k, v in os.environ.items()
           if k not in ("HEATMAP_TPU_NATIVE_LIB", "HEATMAP_TPU_NO_NATIVE_BUILD")}
    env.update(PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    procs = [subprocess.Popen([sys.executable, "-c", LOAD, str(module)],
                              env=env, stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True)
             for _ in range(4)]
    results = [p.communicate(timeout=300) for p in procs]
    for p, (out, err) in zip(procs, results):
        assert p.returncode == 0, err
        assert out.strip().splitlines()[-1] == "True", err
    assert os.path.exists(tmp_path / "native" / "build"
                          / "libheatmap_native.so")
