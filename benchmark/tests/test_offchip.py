"""Off the chip the benchmark exits non-zero and prints no metric; so it
does in a directory that holds only BENCHMARK.json and the benchmark."""

import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
ARGS = ["--workload", "pgbench-s10-null.insert-paced", "--seed", "1",
        "--seconds", "1", "--trace", "0"]


def test_no_chip_no_result():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), *ARGS],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 3
    assert out.stdout == ""
    assert "refusing to run" in out.stderr


def test_alone_in_a_directory(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", *ARGS], cwd=tmp_path,
        capture_output=True, text=True, timeout=300,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert out.returncode != 0
    assert out.stdout == ""
