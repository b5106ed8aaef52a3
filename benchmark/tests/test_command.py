"""The command's refusals that need no polishing job."""

import os
import shutil
import subprocess
import sys

from bench_paths import BENCH, ROOT


def test_a_directory_with_only_the_benchmark_fails_and_prints_no_result(
        tmp_path):
    """``BENCHMARK.json`` and the files under ``paths`` without the
    program: another exit code than 0, nothing on stdout."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "cache"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "bact2m-paf30x",
         "--seed", "1", "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=tmp_path, env=env, text=True, capture_output=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "racon_tpu" in proc.stderr


def test_an_unknown_workload_fails_and_prints_no_result():
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "no-such-cell", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"}, text=True,
        capture_output=True, timeout=300)
    assert proc.returncode != 0 and proc.stdout == ""
    assert "no workload" in proc.stderr
