"""Find a cell's pieces by the names ``BENCHMARK.json`` gives them.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own under ``bench/``, found by name, so a later change adds a
cell by adding files and entries and never edits one that is there:

    BENCHMARK.json                       the cells and metrics (the contract)
    bench/configs/<config>.json          sizes, source, ``reduced``, ``assumed``
    bench/traffic/<traffic>.json         parameters, incl. ``driver`` and limits
    bench/drivers/<driver>.py            ``setup``, ``problem``, ``control``
    bench/metrics/<metric>.py            ``read(record) -> float | None``
"""
from __future__ import annotations

import importlib.util
import json
import sys
import zlib
from pathlib import Path
from types import ModuleType
from typing import Dict, List


class BenchError(Exception):
    """A cell, file or entry that the benchmark cannot use."""


class Bench:
    """The benchmark rooted at ``root`` (the directory of ``BENCHMARK.json``)."""

    def __init__(self, root):
        self.root = Path(root)
        self.dir = self.root / "bench"
        path = self.root / "BENCHMARK.json"
        try:
            self.spec = json.loads(path.read_text())
        except FileNotFoundError:
            raise BenchError(f"no BENCHMARK.json at {self.root}") from None

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        known = ", ".join(w["name"] for w in self.spec["workloads"])
        raise BenchError(f"unknown workload {name!r} (known: {known})")

    def _json(self, kind: str, name: str) -> dict:
        path = self.dir / kind / f"{name}.json"
        if not path.is_file():
            raise BenchError(f"no {kind} file {path}")
        return json.loads(path.read_text())

    def config(self, name: str) -> dict:
        return self._json("configs", name)

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name)

    def _module(self, kind: str, name: str) -> ModuleType:
        path = self.dir / kind / f"{name}.py"
        if not path.is_file():
            raise BenchError(f"no {kind} module {path}")
        # loaded once per process and path, so every caller holds one module
        mod_name = f"_bench_{kind}_{name}_{zlib.crc32(str(path).encode()):08x}"
        mod_name = mod_name.replace(".", "_").replace("-", "_")
        if mod_name not in sys.modules:
            spec = importlib.util.spec_from_file_location(mod_name, path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            sys.modules[mod_name] = mod
        return sys.modules[mod_name]

    def driver(self, name: str) -> ModuleType:
        return self._module("drivers", name)

    def metric_reader(self, name: str) -> ModuleType:
        return self._module("metrics", name)

    def metrics_for(self, workload: str, group: str) -> List[dict]:
        """The ``group`` ("end_to_end" or "per_layer") metrics this cell
        reports: those that list it, and those that list no cells (for a
        per-layer metric, when the cell reports the metric it moves)."""
        e2e = [m for m in self.spec["end_to_end"]
               if workload in m.get("workloads", [workload])]
        if group == "end_to_end":
            return e2e
        names = {m["name"] for m in e2e}
        return [m for m in self.spec["per_layer"]
                if (workload in m["workloads"] if "workloads" in m
                    else m["moves"] in names)]

    def cell(self, workload: str) -> Dict[str, object]:
        """The workload entry with its configuration and traffic loaded."""
        w = self.workload(workload)
        return {"workload": w, "config": self.config(w["config"]),
                "traffic": self.traffic(w["traffic"])}
