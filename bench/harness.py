"""One run of one cell: set-up, a measured window, a trace, the check.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Phases, each on the one process that holds the chip:

1. The device is printed first. A run on anything but a TPU, or on fewer
   chips than the cell asks for, exits 2 with no result line.
2. Set-up: the cell's driver builds the problem from ``--seed``, places it,
   races the tuner's candidates and compiles; the harness then makes one
   warm solve. ``setup_s`` runs from the start of the process to here.
3. Window: whole solves back to back until ``--seconds`` have passed; the
   solve running at the deadline finishes and counts. ``solve_s`` is the
   window's length over the solves completed. Compiles inside it are
   counted, and should be none.
4. With ``--trace 1``: a profiler trace of a few whole solves and of the
   driver's probe calls, reduced to the per-layer metrics.
5. The device's memory peak is read, the program's state freed, and every
   solve of the window is compared with the configuration's plain
   reference on the host. Each number compared is printed beside its limit.
   One more, ``kernel_fallbacks`` (limit 0), counts the kernels that the
   program's dispatch replaced with the next lane of an operator's chain
   from the warm solve on, and the operators whose own rules would not run
   their first backend: the lanes the tuner chose are the ones timed.

The last line of standard output is the result, one JSON object.
"""
from __future__ import annotations

import argparse
import json
import math
import shutil
import statistics
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict

from bench import trace as T
from bench.compile_stats import compile_stats
from bench.loader import Bench, BenchError

ROOT = Path(__file__).resolve().parents[1]
#: whole solves in the traced section of a ``--trace 1`` run
TRACED_SOLVES = 2
#: calls of each probe in the traced section
PROBE_CALLS = 10


class NoChip(Exception):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass
class Record:
    """What a metric reader gets: everything one run measured."""

    device_kind: str
    setup_s: float = 0.0
    solve_s: float = 0.0
    clocks: Dict[str, float] = field(default_factory=dict)
    counters: Dict[str, list] = field(default_factory=dict)
    work: Dict[str, int] = field(default_factory=dict)
    probe_s: Dict[str, float] = field(default_factory=dict)
    device: Dict[str, float] = field(default_factory=dict)


def require_chip(devices, chips: int) -> None:
    """Refuse anything but ``chips`` or more TPU devices."""
    platform = devices[0].platform
    if platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found platform {platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devices)}")


def _enable_cache(root: Path) -> str:
    """The persistent compile cache at a fixed path inside the checkout:
    the path is part of the cache's key, so it never moves."""
    import jax

    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _parse(argv):
    ap = argparse.ArgumentParser(description="One run of one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _window(cell, seconds: float, stats: dict):
    import jax

    before = (stats["compiles"], stats["cache_hits"] + stats["cache_misses"])
    outs = []
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while True:
        outs.append(jax.block_until_ready(cell.solve()))
        if time.perf_counter() >= deadline:
            break
    elapsed = time.perf_counter() - t0
    compiles = (stats["compiles"] - before[0],
                stats["cache_hits"] + stats["cache_misses"] - before[1])
    return outs, elapsed, compiles


def _traced(cell, record: Record) -> dict:
    """Trace a few whole solves and the probes; fill the record's device
    numbers and probe times, and return the breakdown."""
    import jax

    tdir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        probes = cell.probes()
        jax.profiler.start_trace(tdir)
        try:
            for _ in range(TRACED_SOLVES):
                with jax.profiler.TraceAnnotation("solve"):
                    jax.block_until_ready(cell.solve())
                with jax.profiler.TraceAnnotation("between_solves"):
                    time.perf_counter()  # all the window does between solves
            for name, (fn, x) in probes.items():
                with jax.profiler.TraceAnnotation(f"probe_{name}"):
                    for _ in range(PROBE_CALLS):
                        x = fn(x)
                    jax.block_until_ready(x)
        finally:
            jax.profiler.stop_trace()
        tr = T.load(tdir)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    window = tr.span_window("solve")
    summary = T.device_summary(tr, window)
    record.device = {"busy_s": summary["busy_s"], "window_s": summary["window_s"]}
    for name in probes:
        secs = T.module_seconds(tr, f"probe_{name}")
        if len(secs) == PROBE_CALLS:
            record.probe_s[name] = statistics.fmean(secs)
        else:
            print(f"probe {name}: found {len(secs)} of {PROBE_CALLS} program "
                  f"runs in the trace; its metrics are left out", flush=True)
    return summary["breakdown"]


def _kernel_failures() -> int:
    """Kernel raises and non-finite outputs the program's dispatch has
    recorded (``repro.core.health``) so far in this process: after each,
    dispatch ran the next lane of the operator's chain in its place."""
    from repro.core import health

    keys = health.registry().snapshot()["keys"]
    return sum(v["failures"] + v["nonfinite"] for v in keys.values())


def _fallback_lanes(cell) -> list:
    """The operators the cell holds whose dispatch would not run the first
    backend of their policy: a kernel refused by its own rules falls to the
    next one silently, and the tuner's choice would not be what runs."""
    import jax
    from repro.core import SparseOperator, select_spmv
    from repro.core.operator import current_policy

    is_op = lambda x: isinstance(x, SparseOperator)  # noqa: E731
    found = []
    for op in jax.tree_util.tree_leaves(list(vars(cell).values()), is_leaf=is_op):
        if not is_op(op):
            continue
        policy = op.policy if op.policy is not None else current_policy()
        ran = select_spmv(op.container, policy).key.backend
        if ran != policy.backends[0]:
            found.append(f"{op.format} {op.shape}: {ran}, not {policy.backends[0]}")
    return found


def _memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def run(argv=None, root: Path = ROOT, t_start: float = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = _parse(argv)
    try:
        bench = Bench(root)
        spec = bench.cell(args.workload)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    chips = int(spec["workload"]["chips"])

    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    try:
        require_chip(devices, chips)
        import repro  # noqa: F401  (the system under test)
    except (NoChip, ImportError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    used = devices[:chips]
    print(f"compile cache: {_enable_cache(root)}", flush=True)
    stats = compile_stats(t_start)

    driver = bench.driver(spec["traffic"]["driver"])
    cell = driver.setup(spec["config"], spec["traffic"], args.seed)
    fallbacks = _fallback_lanes(cell)
    failures_before = _kernel_failures()
    jax.block_until_ready(cell.solve())
    if args.trace:
        for fn, x in cell.probes().values():
            jax.block_until_ready(fn(fn(x)))
    setup_s = time.perf_counter() - t_start
    print(f"setup: {setup_s:.3f} s = host {cell.clocks['host_setup_s']:.3f} s"
          f" + tune {cell.clocks['tune_s']:.3f} s + the rest (imports, "
          f"placement, warm solve); {stats['compiles']} compiles in "
          f"{stats['compile_s']:.3f} s, persistent cache {stats['cache_hits']} "
          f"hits / {stats['cache_misses']} misses; chosen {cell.chosen}",
          flush=True)

    outs, elapsed, (compiles, lookups) = _window(cell, args.seconds, stats)
    print(f"window: {len(outs)} solves in {elapsed:.6f} s, {compiles} "
          f"compiles and {lookups} cache lookups inside", flush=True)

    record = Record(dev.device_kind, setup_s=setup_s,
                    solve_s=elapsed / len(outs), clocks=dict(cell.clocks),
                    work=dict(cell.work))
    breakdown = _traced(cell, record) if args.trace else None
    kernel_failures = _kernel_failures() - failures_before
    for lane in fallbacks:
        print(f"fallback: {lane}", flush=True)

    device ={"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": _memory_peak(used)}
    host_outs = jax.device_get(outs)
    del outs
    cell.release()
    summaries = [cell.summarize(o) for o in host_outs]
    record.counters = {k: [s[k] for s in summaries] for k in summaries[0]}
    failed = sum(bool(s["failed"]) for s in summaries)
    checks = cell.check(host_outs) + [
        {"name": "kernel_fallbacks", "value": kernel_failures + len(fallbacks),
         "limit": 0}]
    correct =failed == 0 and all(c["value"] <= c["limit"] for c in checks)

    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in bench.metrics_for(args.workload, group):
        value = bench.metric_reader(m["name"]).read(record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": correct, "attempted": len(host_outs), "failed": failed,
              "metrics": metrics, "device": device}
    if args.trace:
        device.update(record.device)
        result["breakdown"] = breakdown
    for c in checks:  # JSON has no infinity: a non-finite answer reads as
        if not math.isfinite(c["value"]):  # the largest float, past any limit
            c["value"] = sys.float_info.max
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    for c in checks:
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {c['name']}: {c['value']!r} limit {c['limit']!r} {ok}",
              file=sys.stderr)
    print(f"check failed solves: {failed} of {len(host_outs)} limit 0 "
          f"{'ok' if failed == 0 else 'FAIL'}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
