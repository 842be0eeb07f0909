"""run.py refuses to measure anywhere but on a TPU, and without the
program beside it."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys

from chipbench_cells import BENCH, CHECKOUT

ARGS = ["--workload", "femnist.cyclepsl", "--seed", str(2 ** 33 + 1),
        "--seconds", "1", "--trace", "0"]


def run_py(root, env_extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(env_extra)
    return subprocess.run(
        [sys.executable, str(root / "benchmarks" / "chip" / "run.py"), *ARGS],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_exits_nonzero_without_a_tpu():
    out = run_py(CHECKOUT, {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert not out.stdout.strip()


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(CHECKOUT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = run_py(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert not out.stdout.strip()
