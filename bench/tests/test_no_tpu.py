"""Without a TPU the benchmark exits non-zero and prints no result line."""
import os
import shutil
import subprocess
import sys

from conftest import REPO


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "hpcg104-pcg",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(p):
    return not any(line.startswith("{") for line in p.stdout.splitlines())


def test_refuses_to_run_on_the_cpu():
    p = _run(REPO)
    assert p.returncode != 0
    assert _no_result(p)
    assert p.stdout.startswith("device: platform=cpu")
    assert "needs a TPU" in p.stderr


def test_refuses_to_run_without_the_program(tmp_path):
    # a directory that holds only BENCHMARK.json and the benchmark's files
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    p = _run(tmp_path)
    assert p.returncode != 0
    assert _no_result(p)
