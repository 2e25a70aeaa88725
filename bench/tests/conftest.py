"""CPU tests of the benchmark harness: ``python -m pytest bench/tests``.

They run at tiny sizes on JAX's CPU backend, with Pallas kernels in the
interpreter; the chip runs are ``bench/run.py``'s.
"""
import json
import os
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO / "src"), str(REPO)):
    if p not in sys.path:
        sys.path.insert(0, p)

#: the real cells cut to sizes a test can hold, keyed by configuration
TINY = {"hpcg-104": {"nx": 16, "ny": 16, "nz": 16},
        "gap-kron20": {"scale": 10, "undirected_edges": 10000}}


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout holding the benchmark with every configuration cut to a
    tiny size; the cells, traffic, drivers and metrics are the real ones."""
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for name, sizes in TINY.items():
        path = tmp_path / "bench" / "configs" / f"{name}.json"
        cfg = json.loads(path.read_text())
        cfg.update(sizes)
        path.write_text(json.dumps(cfg))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return tmp_path


@pytest.fixture
def no_chip_check(monkeypatch):
    """Skip the harness's look for a TPU: the rest of a run goes on."""
    from bench import harness

    monkeypatch.setattr(harness, "require_chip", lambda devices, chips: None)
    return harness


@pytest.fixture
def run_cell(no_chip_check, capsys):
    """Drive one harness run in this process; returns its result line."""

    def run(root, workload, seed=3000000007, seconds=0.5):
        rc = no_chip_check.run(["--workload", workload, "--seed", str(seed),
                                "--seconds", str(seconds), "--trace", "0"],
                               root=root)
        out = capsys.readouterr().out.strip().splitlines()
        assert rc == 0
        return json.loads(out[-1])

    return run


os.environ.setdefault("JAX_PLATFORMS", "cpu")
