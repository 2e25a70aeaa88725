"""Spans, scopes and counters inside the library: where set-up and solves
spend their time.

Two mechanisms, one per side of ``jit``:

- :func:`scope` names device work. It wraps ``jax.named_scope``, so every
  HLO instruction traced inside it carries the scope path in its
  ``op_name`` metadata (``cg/precond/mg/L0/presmooth/symgs/fwd/...``), and
  a profiler trace of the compiled program can be summed per scope. It
  only touches tracing, never the compiled code, so it is always on.
- :func:`span` and :func:`count` name host work (builds, colourings,
  conversions, tuner races). They record only inside :func:`recording`;
  outside it ``span`` returns one shared no-op context after a single
  module-global read, the same zero-cost-when-off pattern as
  ``health._FAULT_PLAN``, and ``count`` returns after the same read.

While recording, each span also opens a ``jax.profiler.TraceAnnotation``
of its name, so a span opened while the profiler runs lands in the trace
beside the device ops; compile seconds (the ``backend_compile_duration``
event of ``jax.monitoring``) are added to the innermost open span, or to
the recorder's ``outside_compile_s`` when none is open. Spans stay in
memory; whoever holds the recorder writes them out once, at the end::

    from repro.core import obs

    with obs.recording() as rec:
        vc = build_mg(16, 16, 16)
    json.dump(rec.to_json(), open("setup_spans.json", "w"))

This module imports nothing outside ``repro.core``'s own dependencies.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional

import jax

#: Every device scope name the library opens. A scope's path component is
#: one of these, optionally followed by sub-names (a format and backend, a
#: level index) given to :func:`scope` as further arguments.
SCOPES = (
    "cg", "spmv", "precond", "vector",                  # solvers/cg.py
    "mg", "presmooth", "residual", "restrict",          # solvers/mg.py
    "prolong", "postsmooth", "coarse",
    "symgs", "fwd", "bwd",                              # solvers/symgs.py
    "masked_spmv", "spmm",                              # core/spmv.py lanes
)

#: The scopes that say which layer did a piece of device work. A pattern's
#: components match consecutive components of an op's scope path, ``*``
#: matching any one (``mg/*/restrict`` is every level's restriction).
LAYER_SCOPES = ("spmv", "masked_spmv", "spmm", "cg/vector", "symgs",
                "mg/*/restrict", "mg/*/prolong")

#: Every host span name the library opens.
SPANS = (
    "mg.build", "mg.level", "matrix", "mg.transfer",
    "symgs.build", "symgs.colour", "symgs.schedule",
    "convert",
    "tune.race", "tune.candidate", "tune.first_call", "tune.time",
    "tune.retarget", "tune.predict",
)

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def scope(name: str, *sub: str):
    """``jax.named_scope`` of ``name/sub/...`` for code traced under ``jit``.

    ``name`` must be in :data:`SCOPES`; ``sub`` adds finer components (the
    lane's format and backend, a level's index).
    """
    if name not in SCOPES:
        raise ValueError(f"unknown scope {name!r}; known: {SCOPES}")
    return jax.named_scope("/".join((name,) + sub))


#: name-stack components JAX adds itself around control flow and calls
#: (transformations such as ``jit(f)`` carry parentheses and go too)
_JAX_PARTS = frozenset(("while", "body", "cond", "closed_call", "scan",
                        "remat", "checkpoint", "pjit", "core_call"))


def scope_path(op_name: str) -> str:
    """The library's scope path in an HLO instruction's ``op_name``: the
    name stack less the final primitive and the components JAX adds.

    Example:
        >>> scope_path("jit(_solve)/cg/while/body/spmv/spmv/dia/plain/mul")
        'cg/spmv/spmv/dia/plain'
    """
    parts = op_name.split("/")[:-1]
    return "/".join(p for p in parts if p not in _JAX_PARTS and "(" not in p)


def in_layer(path: str, pattern: str) -> bool:
    """Whether the scope ``path`` (``a/b/c``) lies under the layer scope
    ``pattern``: its components appear consecutively in the path.

    Example:
        >>> in_layer("cg/precond/mg/L1/restrict/spmv/coo/plain", "mg/*/restrict")
        True
        >>> in_layer("cg/vector", "spmv")
        False
    """
    comps, pat = path.split("/"), pattern.split("/")
    for i in range(len(comps) - len(pat) + 1):
        if all(p in ("*", c) for p, c in zip(pat, comps[i:i + len(pat)])):
            return True
    return False


@dataclass
class SpanRecord:
    """One closed (or still open) host span."""

    name: str
    parent: Optional[int]          # index in ``Recorder.spans``, or None
    start_ns: int                  # time.perf_counter_ns
    end_ns: Optional[int] = None
    attrs: Dict[str, object] = field(default_factory=dict)
    compile_s: float = 0.0         # backend compiles while innermost

    def to_json(self) -> dict:
        return {"name": self.name, "parent": self.parent,
                "start_ns": self.start_ns, "end_ns": self.end_ns,
                "attrs": self.attrs, "compile_s": self.compile_s}


@dataclass
class Recorder:
    """What one :func:`recording` collected."""

    spans: List[SpanRecord] = field(default_factory=list)
    counts: Dict[str, float] = field(default_factory=dict)
    #: compile seconds with no program span open
    outside_compile_s: float = 0.0
    _stack: List[int] = field(default_factory=list)

    def to_json(self) -> dict:
        return {"spans": [s.to_json() for s in self.spans],
                "counts": dict(self.counts),
                "outside_compile_s": self.outside_compile_s}


class _Noop:
    """The span every call returns while nothing records."""

    __slots__ = ()
    nested = False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def __bool__(self):
        return False

    def set(self, **attrs) -> None:
        pass


_NOOP = _Noop()
_RECORDER: Optional[Recorder] = None
_LISTENING = False


class _Span:
    __slots__ = ("_rec", "_idx", "_ann", "nested")

    def __init__(self, rec: Recorder, name: str, attrs: dict):
        if name not in SPANS:
            raise ValueError(f"unknown span {name!r}; known: {SPANS}")
        parent = rec._stack[-1] if rec._stack else None
        self.nested = False
        p = parent
        while p is not None:  # a span inside one of its own name
            if rec.spans[p].name == name:
                self.nested = True
                break
            p = rec.spans[p].parent
        self._rec = rec
        self._idx = len(rec.spans)
        rec.spans.append(SpanRecord(name, parent, 0, attrs=attrs))
        self._ann = jax.profiler.TraceAnnotation(name)

    def __bool__(self):
        return True

    def __enter__(self):
        self._ann.__enter__()
        self._rec._stack.append(self._idx)
        self._rec.spans[self._idx].start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self._rec.spans[self._idx].end_ns = time.perf_counter_ns()
        self._rec._stack.pop()
        self._ann.__exit__(*exc)
        return False

    def set(self, **attrs) -> None:
        """Add attributes known only once the work is done."""
        self._rec.spans[self._idx].attrs.update(attrs)


def span(name: str, **attrs):
    """A context naming host work ``name`` (one of :data:`SPANS`), with
    ``attrs``. The context is falsy while nothing records, so a caller can
    skip computing attributes: ``if s: s.set(bytes=...)``. ``s.nested`` is
    true inside a span of the same name (a conversion inside a conversion)."""
    rec = _RECORDER
    if rec is None:
        return _NOOP
    return _Span(rec, name, attrs)


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to counter ``name`` of the active recording, if any."""
    rec = _RECORDER
    if rec is not None:
        rec.counts[name] = rec.counts.get(name, 0) + n


def _on_duration(event: str, duration: float, **_) -> None:
    rec = _RECORDER
    if rec is None or event != _COMPILE_EVENT:
        return
    if rec._stack:
        rec.spans[rec._stack[-1]].compile_s += duration
    else:
        rec.outside_compile_s += duration


@contextlib.contextmanager
def recording() -> Iterator[Recorder]:
    """Record spans, counts and compile seconds until the block ends.

    Recordings do not nest: the inner one would steal the outer's spans.
    """
    global _RECORDER, _LISTENING
    if _RECORDER is not None:
        raise RuntimeError("a recording is already active")
    if not _LISTENING:
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _LISTENING = True
    rec = Recorder()
    _RECORDER = rec
    try:
        yield rec
    finally:
        _RECORDER = None
