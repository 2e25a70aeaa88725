"""repro.core.obs: host spans, counters, compile attribution, device scopes
in the compiled solvers, and the stable names of the Pallas kernels."""
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DispatchKey, ExecutionPolicy, as_operator, convert, obs
from repro.core import matrices as M
from repro.solvers import build_mg, cg


def test_span_while_not_recording_is_the_shared_noop(monkeypatch):
    opened = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda name: opened.append(name))
    assert obs._RECORDER is None
    s = obs.span("convert", fmt="dia")
    assert s is obs.span("mg.build") is obs._NOOP
    with s as inner:
        assert not inner and not inner.nested
        inner.set(bytes=1)
    obs.count("convert.calls")
    assert opened == []
    with obs.recording() as rec:
        pass
    assert rec.spans == [] and rec.counts == {}


def test_recording_keeps_nesting_parents_attrs_and_counts():
    with obs.recording() as rec:
        with obs.span("mg.build", grid=(8, 8, 8)):
            with obs.span("mg.level", level=0) as lvl:
                lvl.set(rows=512)
                obs.count("convert.calls")
                obs.count("convert.bytes", 100)
            with obs.span("mg.level", level=1):
                obs.count("convert.bytes", 20)
        with obs.span("tune.race"):
            pass
    assert obs._RECORDER is None
    names = [(s.name, s.parent) for s in rec.spans]
    assert names == [("mg.build", None), ("mg.level", 0), ("mg.level", 0),
                     ("tune.race", None)]
    assert rec.spans[1].attrs == {"level": 0, "rows": 512}
    assert rec.counts == {"convert.calls": 1, "convert.bytes": 120}
    for s in rec.spans:
        assert s.start_ns <= s.end_ns
    outer, first, second = rec.spans[:3]
    assert outer.start_ns <= first.start_ns <= first.end_ns <= second.start_ns
    assert second.end_ns <= outer.end_ns
    js = rec.to_json()
    assert js["spans"][1]["parent"] == 0 and js["counts"] == rec.counts


def test_recordings_do_not_nest_and_spans_come_from_the_table():
    with obs.recording():
        with pytest.raises(RuntimeError, match="already active"):
            with obs.recording():
                pass
        with pytest.raises(ValueError, match="unknown span"):
            obs.span("colouring")


def test_compile_seconds_land_on_the_innermost_span():
    x = jnp.arange(5.0)
    with obs.recording() as rec:
        with obs.span("tune.race"):
            with obs.span("tune.first_call"):
                jax.block_until_ready(jax.jit(lambda v: jnp.cos(v) * 3.0)(x))
        jax.block_until_ready(jax.jit(lambda v: jnp.sin(v) - 7.0)(x))
    race, first = rec.spans
    assert first.compile_s > 0
    assert race.compile_s == 0
    assert rec.outside_compile_s > 0


def test_a_nested_conversion_counts_once():
    s = M.fdm27(4, 4, 4)
    A = as_operator(s, "csr").container
    with obs.recording() as rec:
        B = convert(A, "dia")
    conv = [sp for sp in rec.spans if sp.name == "convert"]
    assert [c.parent for c in conv] == [None, 0]
    outer, inner = conv
    nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(B))
    assert outer.attrs == {"fmt": "dia", "nnz": B.nnz, "bytes": nbytes}
    assert inner.attrs == {"fmt": "dia"}
    assert rec.counts == {"convert.calls": 1, "convert.bytes": nbytes}


def test_setup_spans_of_a_multigrid_build_and_a_race():
    with obs.recording() as rec:
        vc = build_mg(8, 8, 8, depth=2, fmt="dia")
        vc.retuned([DispatchKey("dia", "plain")], mode="predict")
    names = {s.name for s in rec.spans}
    assert {"mg.build", "mg.level", "matrix", "mg.transfer", "symgs.build",
            "symgs.colour", "symgs.schedule", "convert", "tune.retarget",
            "tune.predict"} <= names
    assert names <= set(obs.SPANS)
    by = {i: s for i, s in enumerate(rec.spans)}
    colour = next(s for s in rec.spans if s.name == "symgs.colour")
    assert by[colour.parent].name == "symgs.build"
    assert by[by[colour.parent].parent].name == "mg.level"


def test_spans_land_in_the_profiler_trace_on_the_recorded_clock(tmp_path):
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.recording() as rec:
            with obs.span("mg.build"):
                with obs.span("mg.level"):
                    M.fdm27(6, 6, 6)  # opens "matrix" itself
                np.linalg.svd(np.ones((60, 60)))
            with obs.span("tune.race"):
                sum(range(20000))
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    found = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in obs.SPANS:
                    found.setdefault(e.name, []).append((e.start_ns, e.end_ns))
    assert {k: len(v) for k, v in found.items()} == {
        "mg.build": 1, "mg.level": 1, "matrix": 1, "tune.race": 1}
    # one offset, from one span present in both, puts them on one clock
    first = rec.spans[0]
    offset = first.start_ns - found["mg.build"][0][0]
    for s in rec.spans:
        (start, end), = found[s.name]
        assert abs(start + offset - s.start_ns) < 1e6, s.name
        assert abs(end + offset - s.end_ns) < 1e6, s.name


def _op_paths(compiled_text):
    return {obs.scope_path(op)
            for op in re.findall(r'op_name="([^"]*)"', compiled_text)}


def test_compiled_pcg_with_a_vcycle_carries_scope_paths():
    vc = build_mg(8, 8, 8, depth=2, fmt="dia", coarse_sweeps=1)
    A = vc.levels[0].A
    b = jnp.ones(512, jnp.float32)
    solve = jax.jit(lambda A, mg, b: cg(A, b, tol=1e-6, maxiter=20, precond=mg))
    paths = _op_paths(solve.lower(A, vc, b).compile().as_text())
    assert "cg/spmv/spmv/dia/plain" in paths
    assert "cg/vector" in paths
    assert "cg/precond/mg/L0/presmooth/symgs/fwd/masked_spmv/dia/plain" in paths
    assert "cg/precond/mg/L0/postsmooth/symgs/bwd/masked_spmv/dia/plain" in paths
    assert "cg/precond/mg/L0/restrict/spmv/coo/plain" in paths
    assert "cg/precond/mg/L0/prolong/spmv/coo/plain" in paths
    assert "cg/precond/mg/L0/mg/L1/coarse/symgs/fwd/masked_spmv/dia/plain" in paths
    layered = [p for p in paths
               if any(obs.in_layer(p, pat) for pat in obs.LAYER_SCOPES)]
    assert layered and all(p.startswith("cg") for p in paths if p)


def test_compiled_coo_spmv_carries_its_lane():
    A = as_operator(M.fdm27(4, 4, 4), "coo")
    paths = _op_paths(jax.jit(lambda A, x: A @ x).lower(
        A, jnp.ones(64, jnp.float32)).compile().as_text())
    assert "spmv/coo/plain" in paths


def test_scope_names_come_from_the_table():
    with pytest.raises(ValueError, match="unknown scope"):
        obs.scope("smoother")
    assert obs.scope_path("jit(f)/cg/while/body/vector/closed_call/mul") == "cg/vector"
    assert obs.in_layer("cg/precond/mg/L2/prolong/spmv/coo/plain", "mg/*/prolong")
    assert not obs.in_layer("cg/precond/mg/L0/residual/spmv/dia/plain", "cg/spmv")
    assert obs.in_layer("cg/precond/mg/L0/residual/spmv/dia/plain", "spmv")


_RESIDENT = ExecutionPolicy(backends=("pallas",), allow_fallback=False)
_TILED = _RESIDENT.replace(max_resident_cols=128)


def _pallas_names(jaxpr):
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for p in eqn.params.values():
            for sub in p if isinstance(p, (tuple, list)) else (p,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out += _pallas_names(sub)
    return out


@pytest.mark.parametrize("fmt,policy,k,name", [
    ("dia", _RESIDENT, 0, "dia_spmv"),
    ("dia", _TILED, 0, "dia_spmv_tiled"),
    ("ell", _RESIDENT, 0, "ell_spmv"),
    ("ell", _TILED, 0, "ell_spmv_tiled"),
    ("coo", _RESIDENT, 0, "coo_spmv"),
    ("coo", _TILED, 0, "scoo_spmv_tiled"),
    ("sell", _RESIDENT, 0, "sell_spmv"),
    ("bsr", _RESIDENT, 8, "bsr_spmm"),
])
def test_every_pallas_kernel_has_a_stable_name(fmt, policy, k, name):
    A = as_operator(M.fdm27(8, 8, 8), fmt, policy=policy)
    x = jnp.ones((512, k) if k else (512,), jnp.float32)
    assert _pallas_names(jax.make_jaxpr(lambda v: A @ v)(x).jaxpr) == [name]
