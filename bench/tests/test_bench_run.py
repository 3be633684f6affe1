"""The harness refuses to run, and prints no result, without a TPU or
without the program beside the benchmark."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

from bench.lib import cell


def _run(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "matching-mid.solve",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_result_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = _run(cell.ROOT, env)
    assert out.returncode != 0 and out.stdout == ""
    assert "no TPU" in out.stderr


def test_no_result_without_the_program(tmp_path):
    shutil.copy(os.path.join(cell.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(cell.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    out = _run(str(tmp_path), env)
    assert out.returncode != 0 and out.stdout == ""
