"""The real cells at tiny sizes: a sound run is correct, the control and each
fault the cell can have are not.

The faults are planted underneath a harness run whose look for a chip is
skipped: a solve that returns its state unchanged, an answer altered where
it is produced, and the tuned kernel replaced by the next lane of its
chain in the timed solve. The cells are one-chip solves with no batch, so the
other faults (half of a batch left out, the exchange between chips left out)
do not exist in them.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from bench.loader import Bench


def _driver(root, cell):
    b = Bench(root)
    return b.driver(b.cell(cell)["traffic"]["driver"])


def test_hpcg_traffic_at_16_cubed_interpret_mode(tiny_root, run_cell):
    # both candidates race; dia/pallas runs in the Pallas interpreter
    res = run_cell(tiny_root, "hpcg104-pcg")
    assert res["correct"] is True, res
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"solve_s", "setup_s"}
    assert res["checks"]["rel_residual"]["value"] < 2e-6


def test_pagerank_traffic_at_scale_10(tiny_root, run_cell):
    res = run_cell(tiny_root, "kron20-pagerank")
    assert res["correct"] is True, res
    assert res["checks"]["l1_gap"]["value"] < 1e-6


@pytest.mark.parametrize("cell,number", [("hpcg104-pcg", "rel_residual"),
                                         ("kron20-pagerank", "l1_gap")])
def test_control_in_bfloat16_is_not_correct(tiny_root, cell, number):
    b = Bench(tiny_root)
    spec = b.cell(cell)
    driver = b.driver(spec["traffic"]["driver"])
    for seed in (1, 2, 3000000003):
        problem = driver.problem(spec["config"], spec["traffic"], seed)
        out = jax.device_get(driver.control(problem, jnp.bfloat16))
        (check,) = problem.check([out])
        assert check["name"] == number
        assert check["value"] > 3 * check["limit"], (seed, check)
        # the same control in the configuration's own precision passes
        out = jax.device_get(driver.control(problem, jnp.float32))
        (check,) = problem.check([out])
        assert check["value"] < check["limit"], (seed, check)


def _pcg_unchanged(real):
    def solve(A, mg, b, *, tol, maxiter):
        return jnp.zeros_like(b), jnp.int32(1), jnp.float32(0.0)
    return solve


def _pcg_altered(real):
    def solve(A, mg, b, *, tol, maxiter):
        x, k, r = real(A, mg, b, tol=tol, maxiter=maxiter)
        return x.at[7].add(1.0), k, r
    return solve


def _pr_unchanged(real):
    def solve(A, inv_deg, *, damping, tol, maxiter):
        n = inv_deg.shape[0]
        return (jnp.full((n,), 1.0 / n, jnp.float32), jnp.float32(0.0),
                jnp.int32(1))
    return solve


def _pr_altered(real):
    def solve(A, inv_deg, **rule):
        scores, err, k = real(A, inv_deg, **rule)
        return scores.at[7].add(1e-3), err, k
    return solve


@pytest.mark.parametrize("cell,attr,fault", [
    ("hpcg104-pcg", "_solve", _pcg_unchanged),
    ("hpcg104-pcg", "_solve", _pcg_altered),
    ("kron20-pagerank", "pagerank", _pr_unchanged),
    ("kron20-pagerank", "pagerank", _pr_altered),
], ids=["pcg-unchanged", "pcg-altered", "pagerank-unchanged", "pagerank-altered"])
def test_fault_in_the_timed_path_is_not_correct(tiny_root, run_cell, monkeypatch,
                                                cell, attr, fault):
    driver = _driver(tiny_root, cell)
    monkeypatch.setattr(driver, attr, fault(getattr(driver, attr)))
    res = run_cell(tiny_root, cell, seconds=0.2)
    assert res["correct"] is False, res


def _raises(entry):
    def fn(*args):
        raise RuntimeError("planted kernel failure")
    return dataclasses.replace(entry, fn=fn)


def _refused(entry):
    return dataclasses.replace(entry, supports=lambda A, policy: False)


@pytest.mark.parametrize("plant", [_raises, _refused], ids=["raises", "refused"])
def test_kernel_fallback_in_the_timed_path_is_not_correct(tiny_root, run_cell,
                                                          monkeypatch, plant):
    # the finest operator prefers dia/pallas, as when the tuner picks it;
    # the kernel then fails, or its rules refuse the matrix, and dispatch
    # runs plain in its place: the answer is right, the lane is not
    import importlib

    from repro.core import DispatchKey

    spmv = importlib.import_module("repro.core.spmv")

    driver = _driver(tiny_root, "hpcg104-pcg")
    real_setup = driver.setup

    def setup(*args):
        cell = real_setup(*args)
        cell.A = cell.A.using("pallas")
        key = DispatchKey("dia", "pallas")
        monkeypatch.setitem(spmv._SPMV, key, plant(spmv._SPMV[key]))
        return cell

    monkeypatch.setattr(driver, "setup", setup)
    res = run_cell(tiny_root, "hpcg104-pcg", seconds=0.2)
    assert res["checks"]["rel_residual"]["value"] < 2e-6, res
    assert res["checks"]["kernel_fallbacks"]["value"] >= 1, res
    assert res["correct"] is False, res
