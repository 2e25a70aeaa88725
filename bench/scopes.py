"""Device seconds by program scope inside whole solves.

The program names its device work with ``repro.core.obs.scope``: every
HLO instruction carries its scope path (``cg/precond/mg/L0/presmooth/
symgs/fwd/masked_spmv/dia/plain``) in its ``op_name`` metadata. This module
traces a few whole solves, maps each device op of the trace to its scope
path through the compiled program's HLO text (keyed by instruction name),
and sums the ops' self times per path:

    ops, outs = trace_solves(solve, 3)
    numbers = split(ops, op_paths(compiled_hlo_text))
    numbers["cg/vector_s"]  # device seconds under ``cg/vector``

A fused op carries the ``op_name`` of its root instruction, so an op fused
across a scope boundary counts under its root's scope.

The harness's record has no field for these numbers; a driver that reports
them traces its solves itself when the harness first asks it for probes
(only ``--trace 1`` runs do) and hands the sums over in ``clocks``. A
program without scopes (one that predates ``repro.core.obs``) gives no
sums, and the metrics that read them are left out.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
from typing import Callable, Dict, Iterable, List, Optional

from bench import trace as T

#: an instruction's name and its ``op_name`` in compiled HLO text
_HLO_OP = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?op_name="([^"]*)"',
                     re.MULTILINE)

#: the regions of a CG solve each metric reads, as layer patterns
REGIONS = ("cg/spmv", "cg/vector", "cg/precond", "symgs")


def _obs():
    try:
        from repro.core import obs
    except ImportError:  # a program without scopes
        return None
    return obs


def op_paths(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: scope path}`` of a compiled module's HLO text."""
    obs = _obs()
    if obs is None:
        return {}
    return {name: obs.scope_path(op) for name, op in _HLO_OP.findall(hlo_text)}


def device_ops(trace_dir: str) -> List[T.Event]:
    """The device ops of the first device in the ``.xplane.pb`` under
    ``trace_dir``: a TPU's ``XLA Ops`` line, or, on a CPU, the events of the
    host threads that carry an ``hlo_op``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    planes = list(ProfileData.from_file(paths[0]).planes)
    for plane in sorted((p for p in planes if p.name.startswith("/device:")),
                        key=lambda p: p.name):
        for line in plane.lines:
            if line.name == T.OPS_LINE:
                return [(T.op_name(e.name), e.start_ns, e.end_ns)
                        for e in line.events]
    ops = []
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    stats = dict(e.stats)
                    if "hlo_op" in stats:
                        ops.append((str(stats["hlo_op"]), e.start_ns, e.end_ns))
    return ops


def scope_seconds(ops: Iterable[T.Event], paths: Dict[str, str]) -> Dict[str, float]:
    """Device self seconds of the ops per scope path; ops the HLO text does
    not name, and ops outside every scope, count under ``""``."""
    out: Dict[str, float] = {}
    for name, _, _, own in T.self_ns(list(ops)):
        path = paths.get(name, "")
        out[path] = out.get(path, 0.0) + own * 1e-9
    return out


def under(seconds: Dict[str, float], pattern: str) -> float:
    """Seconds of the paths that lie under the layer pattern ``pattern``."""
    obs = _obs()
    return sum(s for p, s in seconds.items() if obs.in_layer(p, pattern))


def split(ops: List[T.Event], paths: Dict[str, str]) -> Optional[Dict[str, float]]:
    """The numbers a driver hands over: ``busy_s`` (the union of the ops),
    ``scoped_s`` (self time under any of the program's layer scopes) and
    ``<region>_s`` for each of ``REGIONS``; ``None`` when no op lies under a
    layer scope (a program without scopes)."""
    obs = _obs()
    if obs is None or not ops:
        return None
    secs = scope_seconds(ops, paths)
    scoped = sum(s for p, s in secs.items()
                 if any(obs.in_layer(p, pat) for pat in obs.LAYER_SCOPES))
    if not scoped:
        return None
    lo, hi = min(s for _, s, _ in ops), max(e for _, _, e in ops)
    out = {"busy_s": T.busy_ns(ops, lo, hi) * 1e-9, "scoped_s": scoped}
    out.update({f"{r}_s": under(secs, r) for r in REGIONS})
    return out


def trace_solves(solve: Callable, solves: int):
    """Trace ``solves`` whole solves (each ``solve()`` dispatches one) in a
    profiler session of its own; returns the device ops and the outputs."""
    import jax

    tdir = tempfile.mkdtemp(prefix="bench_scopes_")
    try:
        jax.profiler.start_trace(tdir)
        try:
            outs = [jax.block_until_ready(solve()) for _ in range(solves)]
        finally:
            jax.profiler.stop_trace()
        return device_ops(tdir), outs
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
