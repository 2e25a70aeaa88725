"""Device seconds by program scope (``bench/scopes.py``), the readers of
``vector_ms``, ``solve_spmv_roofline`` and ``scoped_pct``, and the
``hpcg104-cg`` cell at a tiny size: sound, its control and its planted
faults."""
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import scopes
from bench.harness import Record
from bench.loader import Bench

PATHS = {"fusion.1": "cg/spmv/spmv/dia/plain", "fusion.2": "cg/vector",
         "dia_spmv.3": "cg/precond/mg/L0/presmooth/symgs/fwd/masked_spmv/dia/pallas",
         "fusion.4": "cg/precond/mg/L0/restrict/spmv/coo/plain",
         "while.5": "cg", "copy.6": ""}


def _ops():
    # a while op spanning its body; an op the HLO text does not name
    return [("while.5", 0, 100), ("fusion.1", 0, 20), ("fusion.2", 20, 25),
            ("dia_spmv.3", 25, 85), ("fusion.4", 85, 90), ("copy.6", 90, 95),
            ("fusion.1", 110, 130), ("mystery.7", 130, 140)]


def test_scope_seconds_are_self_times_per_path():
    secs = scopes.scope_seconds(_ops(), PATHS)
    assert secs == pytest.approx({
        "cg/spmv/spmv/dia/plain": 40e-9, "cg/vector": 5e-9,
        "cg/precond/mg/L0/presmooth/symgs/fwd/masked_spmv/dia/pallas": 60e-9,
        "cg/precond/mg/L0/restrict/spmv/coo/plain": 5e-9,
        "cg": 5e-9, "": 15e-9})
    assert scopes.under(secs, "cg/precond") == pytest.approx(65e-9)
    assert scopes.under(secs, "spmv") == pytest.approx(45e-9)


def test_split_hands_over_busy_scoped_and_regions():
    out = scopes.split(_ops(), PATHS)
    assert out == pytest.approx({
        "busy_s": 140e-9 - 10e-9,  # [0,100) and [110,140)
        "scoped_s": 110e-9,        # all but the while's own, copy.6, mystery.7
        "cg/spmv_s": 40e-9, "cg/vector_s": 5e-9, "cg/precond_s": 65e-9,
        "symgs_s": 60e-9})


def test_a_program_without_scopes_gives_nothing():
    assert scopes.split(_ops(), {}) is None
    assert scopes.split([], PATHS) is None
    assert scopes.op_paths("%fusion.1 = f32[4]{0} fusion(%p), "
                           'metadata={op_name="jit(f)/while/body/mul"}') == {
        "fusion.1": ""}


def test_op_paths_read_compiled_hlo_text():
    text = ('  %fusion.12 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, '
            'calls=%fc, metadata={op_name="jit(_solve)/cg/while/body/vector/add" '
            'source_file="x.py"}\n'
            '  ROOT %dia_spmv.3 = f32[8,128]{1,0} custom-call(%a), '
            'metadata={op_name="jit(_solve)/cg/while/body/spmv/spmv/dia/pallas/'
            'dia_spmv/pallas_call"}\n')
    assert scopes.op_paths(text) == {
        "fusion.12": "cg/vector", "dia_spmv.3": "cg/spmv/spmv/dia/pallas/dia_spmv"}


def _record(**clocks):
    return Record("TPU v5 lite", clocks=clocks,
                  work={"nnz": 29_791_000, "nrows": 1_124_864, "ncols": 1_124_864})


@pytest.mark.parametrize("metric,clocks,value", [
    ("vector_ms", {"cg/vector_s": 0.03, "traced_iters": 300.0}, 0.1),
    ("solve_spmv_roofline", {"cg/spmv_s": 0.567, "traced_iters": 300.0},
     100 * 4 * (29_791_000 + 2 * 1_124_864) / 819e9 / (0.567 / 300)),
    ("scoped_pct", {"scoped_s": 0.99, "busy_s": 1.0}, 99.0),
])
def test_new_readers(metric, clocks, value):
    reader = Bench(Path(__file__).resolve().parents[2]).metric_reader(metric)
    assert reader.read(_record(**clocks)) == pytest.approx(value)
    # a run whose program has no scopes hands over none of these
    assert reader.read(_record(host_setup_s=1.0, tune_s=2.0)) is None


def test_cg_cell_at_16_cubed(tiny_root, run_cell):
    res = run_cell(tiny_root, "hpcg104-cg")
    assert res["correct"] is True, res
    assert set(res["metrics"]) == {"solve_s", "setup_s"}
    assert res["checks"]["rel_residual"]["value"] < 2e-6


def test_cg_cell_traces_its_own_solves(tiny_root):
    b = Bench(tiny_root)
    spec = b.cell("hpcg104-cg")
    cell = b.driver("cg").setup(spec["config"], spec["traffic"], 3000000007)
    jax.block_until_ready(cell.solve())
    assert set(cell.clocks) == {"host_setup_s", "tune_s"}
    probes = cell.probes()
    assert set(probes) == {"spmv"}
    c = cell.clocks
    iters = int(cell.solve()[1])
    assert c["traced_iters"] == 3 * iters
    assert 0 < c["cg/spmv_s"] and 0 < c["cg/vector_s"]
    assert c["cg/precond_s"] == 0 and c["symgs_s"] == 0
    assert c["cg/spmv_s"] + c["cg/vector_s"] <= c["scoped_s"] <= c["busy_s"]
    cell.probes()  # asked again in the traced section: traced once
    assert cell.clocks["traced_iters"] == 3 * iters
    cell.release()


def test_cg_control_in_bfloat16_is_not_correct(tiny_root):
    b = Bench(tiny_root)
    spec = b.cell("hpcg104-cg")
    driver = b.driver("cg")
    problem = driver.problem(spec["config"], spec["traffic"], 5)
    (check,) = problem.check([jax.device_get(driver.control(problem, jnp.bfloat16))])
    assert check["value"] > 3 * check["limit"], check
    (check,) = problem.check([jax.device_get(driver.control(problem, jnp.float32))])
    assert check["value"] < check["limit"], check


def _cg_unchanged(real):
    def solve(A, b, *, tol, maxiter):
        return jnp.zeros_like(b), jnp.int32(1), jnp.float32(0.0)
    return solve


def _cg_altered(real):
    def solve(A, b, *, tol, maxiter):
        x, k, r = real(A, b, tol=tol, maxiter=maxiter)
        return x.at[7].add(1.0), k, r
    return solve


def _cg_capped(real):
    def solve(A, b, *, tol, maxiter):
        return real(A, b, tol=tol, maxiter=3)
    return solve


@pytest.mark.parametrize("fault", [_cg_unchanged, _cg_altered, _cg_capped],
                         ids=["unchanged", "altered", "capped"])
def test_fault_in_the_cg_cell_is_not_correct(tiny_root, run_cell, monkeypatch,
                                             fault):
    b = Bench(tiny_root)
    driver = b.driver("cg")
    monkeypatch.setattr(driver, "_solve", fault(driver._solve))
    res = run_cell(tiny_root, "hpcg104-cg", seconds=0.2)
    assert res["correct"] is False, res
