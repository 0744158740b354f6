"""Where there is nothing to measure, or no chip to measure on, a run exits
non-zero and prints no result line."""

import os
import shutil
import subprocess
import sys

from run import ROOT

ARGS = ["--workload", "join_sort_hot", "--seed", "1", "--seconds", "1", "--trace", "0"]


def run_py(root, env=None):
    return subprocess.run([sys.executable, os.path.join(root, "bench", "run.py"), *ARGS],
                          cwd=root, capture_output=True, text=True, timeout=300,
                          env={**os.environ, **(env or {})})


def test_without_a_tpu_there_is_no_result():
    done = run_py(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert done.returncode != 0
    assert '"correct"' not in done.stdout and "No CPU fallback" in done.stderr
    assert not os.path.exists(os.path.join(ROOT, "data", "bench", "tpch_join_1chip_seed1"))


def test_a_directory_with_only_the_benchmark_has_nothing_to_measure(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    done = run_py(str(tmp_path))
    assert done.returncode != 0 and done.stdout.strip() == ""
