"""Reduce a profiler trace to device busy time, idle gaps and op times.

A trace is read into three lists of ``(name, start_ns, end_ns)``:

- ``ops``: device operations, per device (the ``XLA Ops`` line of each
  ``/device:`` plane), named by their HLO instruction;
- ``modules``: whole compiled programs, per device (``XLA Modules``);
- ``spans``: the benchmark's host spans (``jax.profiler.TraceAnnotation``)
  whose names are in ``SPANS``.

The reduction works on those lists alone, so it is checked on a small
recorded trace without a chip. In a v5e's trace the device's timestamps
read about a millisecond earlier than the host's (a program's device events
begin before the host span that dispatched it): negligible against windows
of seconds, but an idle gap's label is only as good as that.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]  # (name, start_ns, end_ns)

#: the host spans the harness opens; idle gaps are labelled by them
SPANS = ("solve", "between_solves", "probe_spmv", "probe_vcycle")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: entries in each list of the result line's ``breakdown``
TOP = 10


@dataclass
class Trace:
    ops: Dict[str, List[Event]] = field(default_factory=dict)
    modules: Dict[str, List[Event]] = field(default_factory=dict)
    spans: List[Event] = field(default_factory=list)

    def span_window(self, name: str) -> Optional[Tuple[float, float]]:
        """From the first span ``name`` opens to the last one closes."""
        ss = [s for s in self.spans if s[0] == name]
        if not ss:
            return None
        return min(s[1] for s in ss), max(s[2] for s in ss)


def op_name(hlo: str) -> str:
    """``fusion.12`` from the op's HLO text ``%fusion.12 = f32[...] ...``:
    XLA names an instruction by its opcode and a number."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def load(trace_dir: str) -> Trace:
    """Read the ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    tr = Trace()
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    tr.ops[plane.name] = [(op_name(e.name), e.start_ns, e.end_ns)
                                          for e in line.events]
                elif line.name == MODULES_LINE:
                    tr.modules[plane.name] = [(e.name, e.start_ns, e.end_ns)
                                              for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.spans += [(e.name, e.start_ns, e.end_ns)
                             for e in line.events if e.name in SPANS]
    return tr


def merged(events: Sequence[Event], lo: float, hi: float) -> List[Tuple[float, float]]:
    """The union of the events' intervals, clipped to ``[lo, hi]``."""
    ivs = sorted((max(s, lo), min(e, hi)) for _, s, e in events
                 if e > lo and s < hi)
    out: List[List[float]] = []
    for s, e in ivs:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(events: Sequence[Event], lo: float, hi: float) -> float:
    """Time in ``[lo, hi]`` in which at least one event runs."""
    return sum(e - s for s, e in merged(events, lo, hi))


def gaps(events: Sequence[Event], lo: float, hi: float) -> List[Tuple[float, float]]:
    """The idle intervals of ``[lo, hi]``: where no event runs."""
    out, t = [], lo
    for s, e in merged(events, lo, hi):
        if s > t:
            out.append((t, s))
        t = e
    if hi > t:
        out.append((t, hi))
    return out


def label(t: float, spans: Sequence[Event]) -> str:
    """The innermost host span open at time ``t``, or ``"none"``."""
    open_ = [s for s in spans if s[1] <= t < s[2]]
    return min(open_, key=lambda s: s[2] - s[1])[0] if open_ else "none"


def self_ns(events: Sequence[Event]) -> List[Tuple[str, float, float, float]]:
    """``(name, start, end, self time)`` of each op: its time less that of
    the ops nested directly inside it (a ``while`` op spans its body's ops
    on the same line)."""
    evs = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    own = [e - s for _, s, e in evs]
    stack: List[int] = []
    for i, (_, s, e) in enumerate(evs):
        while stack and evs[stack[-1]][2] <= s:
            stack.pop()
        if stack and evs[stack[-1]][2] >= e:
            own[stack[-1]] -= e - s
        stack.append(i)
    return [(n, s, e, t) for (n, s, e), t in zip(evs, own)]


def top_ops(events: Sequence[Event], lo: float, hi: float, k: int = TOP):
    """The ``k`` op names with the most self time among the ops whose middle
    lies inside ``[lo, hi]``."""
    tot: Dict[str, float] = {}
    for name, s, e, own in self_ns(events):
        if lo <= (s + e) / 2 <= hi:
            tot[name] = tot.get(name, 0.0) + own
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns * 1e-9] for name, ns in best]


def device_summary(tr: Trace, window: Tuple[float, float]) -> dict:
    """Busy and window seconds averaged over the devices, and the breakdown
    of the first device: its ops with the most time and its longest idle
    gaps, each labelled by the host span open at its middle."""
    lo, hi = window
    devices = sorted(tr.ops)
    if not devices:
        raise RuntimeError("the trace holds no device operations")
    busy = [busy_ns(tr.ops[d], lo, hi) for d in devices]
    first = tr.ops[devices[0]]
    idle = sorted(gaps(first, lo, hi), key=lambda g: g[0] - g[1])[:TOP]
    return {
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "breakdown": {
            "device_ops": top_ops(first, lo, hi),
            "idle_gaps": [[label((s + e) / 2, tr.spans), (e - s) * 1e-9]
                          for s, e in idle],
        },
    }


def module_seconds(tr: Trace, name: str) -> List[float]:
    """Device seconds of each run of the programs whose name holds ``name``,
    on the first device."""
    devices = sorted(tr.modules)
    if not devices:
        return []
    return [(e - s) * 1e-9 for n, s, e in tr.modules[devices[0]] if name in n]
