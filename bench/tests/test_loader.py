"""A cell, configuration, traffic mix, driver and metric defined only by new
files are found by name and run, with no edit to the harness."""
import json
import textwrap

import pytest

from bench.loader import Bench, BenchError

TOY_DRIVER = textwrap.dedent('''
    import jax.numpy as jnp
    import numpy as np


    class Problem:
        def __init__(self, config, traffic, seed):
            self.n, self.scale = config["n"], traffic["scale"]
            self.limit = traffic["limits"]["gap"]

        def summarize(self, out):
            return {"iters": 1, "failed": False}

        def check(self, outs):
            gap = max(float(np.abs(o - self.scale).max()) for o in outs)
            return [{"name": "gap", "value": gap, "limit": self.limit}]


    class Cell(Problem):
        def __init__(self, config, traffic, seed):
            super().__init__(config, traffic, seed)
            self.clocks = {"host_setup_s": 0.0, "tune_s": 0.0}
            self.work = {"nnz": self.n, "nrows": self.n, "ncols": self.n}
            self.chosen = "toy"
            self.x = jnp.full((self.n,), self.scale)

        def solve(self):
            return self.x * 1.0

        def probes(self):
            return {}

        def release(self):
            self.x = None


    def setup(config, traffic, seed):
        return Cell(config, traffic, seed)
''')


@pytest.fixture
def toy_root(tmp_path):
    spec = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 1,
        "configs": [{"name": "toy-cfg", "source": "none", "reduced": [],
                     "file": "bench/configs/toy-cfg.json", "why": "toy"}],
        "workloads": [{"name": "toy.cell", "config": "toy-cfg",
                       "traffic": "toy_mix", "chips": 1, "why": "toy"}],
        "end_to_end": [{"name": "solve_s", "unit": "s", "better": "lower",
                        "bound": 0.05, "source": "host_clock"}],
        "per_layer": [{"name": "toy_count", "unit": "n", "better": "higher",
                       "source": "program_counter", "layer": "toy",
                       "moves": "solve_s"}],
    }
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    files = {
        "configs/toy-cfg.json": json.dumps({"n": 8}),
        "traffic/toy_mix.json": json.dumps({"driver": "toy", "scale": 3.0,
                                            "limits": {"gap": 0.0}}),
        "drivers/toy.py": TOY_DRIVER,
        "metrics/solve_s.py": "def read(record):\n    return record.solve_s\n",
        "metrics/toy_count.py":
            "def read(record):\n    return len(record.counters['iters'])\n",
    }
    for rel, text in files.items():
        path = tmp_path / "bench" / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(text)
    return tmp_path


def test_loader_finds_every_piece_by_name(toy_root):
    b = Bench(toy_root)
    cell = b.cell("toy.cell")
    assert cell["config"] == {"n": 8}
    assert cell["traffic"]["driver"] == "toy"
    assert hasattr(b.driver("toy"), "setup")
    assert b.driver("toy") is b.driver("toy")  # one module per process
    assert [m["name"] for m in b.metrics_for("toy.cell", "end_to_end")] == ["solve_s"]
    assert [m["name"] for m in b.metrics_for("toy.cell", "per_layer")] == ["toy_count"]
    with pytest.raises(BenchError, match="unknown workload"):
        b.workload("nope")
    with pytest.raises(BenchError, match="no metrics module"):
        b.metric_reader("nope")


def test_per_layer_metric_follows_its_cells_or_what_it_moves(toy_root):
    b = Bench(toy_root)
    b.spec["per_layer"].append({"name": "other", "moves": "setup_s",
                                "unit": "s", "layer": "x"})
    b.spec["per_layer"].append({"name": "listed", "moves": "setup_s",
                                "unit": "s", "layer": "x",
                                "workloads": ["toy.cell"]})
    names = [m["name"] for m in b.metrics_for("toy.cell", "per_layer")]
    assert names == ["toy_count", "listed"]


def test_a_cell_made_of_new_files_runs_end_to_end(toy_root, run_cell):
    res = run_cell(toy_root, "toy.cell", seconds=0.2)
    assert res["correct"] is True
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"solve_s"}
    assert res["metrics"]["solve_s"]["unit"] == "s"
    assert list(res)[-1] == "checks"
    assert res["checks"] == {"gap": {"value": 0.0, "limit": 0.0},
                             "kernel_fallbacks": {"value": 0, "limit": 0}}
    assert res["device"]["count"] >= 1
