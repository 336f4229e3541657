"""`bench/run.py` refuses to run off a TPU: non-zero exit, no result."""

import os
import subprocess
import sys

import smoke

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = smoke.CELLS[0]


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", CELL, "--seed", str(2**33 + 1),
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300, cwd=ROOT)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "needs 1 TPU" in p.stderr


def test_benchmark_files_alone_give_no_result(tmp_path):
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300,
        cwd=tmp_path)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "No module named 'repro'" in p.stderr
