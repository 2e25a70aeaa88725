"""Traffic driver: repeated PCG solves of HPCG's stencil to a stated accuracy.

``Problem`` is the yardstick's side: the grid, HPCG's right-hand side
``b = A @ ones`` by the plain reference (f64, rounded to f32), and the
check, which takes every solve of the window and reports the reference's
relative residual ``||b - A x|| / ||b||`` in f64 of the worst. HPCG states
its right-hand side, so the seed changes nothing here: every seed solves
the same system, as every HPCG run does.

``Cell`` adds the system under test: the program builds the stencil and
its multigrid hierarchy (``fdm27``, ``build_mg`` on host DIA containers),
races the configuration's tuner candidates for the finest operator with
``autotune_spmv`` and retargets the coarser levels with
``VCycle.retuned`` in its zero-run ``predict`` mode. One solve is
``repro.solvers.cg`` from zero with the tuned V-cycle as preconditioner,
until the relative residual is below the traffic's ``tol`` or ``maxiter``
iterations have run, jitted with the operator and the hierarchy as
arguments.

``control`` puts the plain reference, computed below the configuration's
precision, in the program's place (``bench/control.py``): plain CG on the
reference stencil, solved to the same ``tol``.
"""
from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import stencil27
from repro.core import DispatchKey, autotune_spmv
from repro.core import matrices as M
from repro.solvers import build_mg, cg

#: the control's iteration cap, in multiples of the traffic's: plain CG
#: needs more iterations than PCG, and a control that stops at its cap
#: still gives its reading
CONTROL_MAXITER = 10


def probe_spmv(A, x):
    return A @ x


def probe_vcycle(mg, r):
    z = mg(r)
    return z / jnp.max(jnp.abs(z))


# one jitted object each, so that the warm-up's programs are the traced ones
_probe_spmv, _probe_vcycle = jax.jit(probe_spmv), jax.jit(probe_vcycle)


def _solve(A, mg, b, *, tol, maxiter):
    return cg(A, b, tol=tol, maxiter=maxiter, precond=mg)


class Problem:
    """The system, its right-hand side and the check; a solve's output is
    ``(x, iterations, relative residual)``, as ``CGInfo`` holds them."""

    def __init__(self, config, traffic, seed):
        self.grid = (config["nx"], config["ny"], config["nz"])
        self.tol = float(traffic["tol"])
        self.maxiter = int(traffic["maxiter"])
        self.limit = float(traffic["limits"]["rel_residual"])
        ones = np.ones(int(np.prod(self.grid)))
        self.b_host = stencil27.apply(ones, self.grid).astype(np.float32)

    def summarize(self, out):
        iters = int(out[1])
        finite = bool(np.all(np.isfinite(out[0]))) and bool(np.isfinite(out[2]))
        return {"iters": iters, "failed": not finite or iters >= self.maxiter}

    def check(self, outs):
        worst = max(stencil27.rel_residual(o[0], self.b_host, self.grid)
                    for o in outs)
        return [{"name": "rel_residual", "value": worst, "limit": self.limit}]


class Cell(Problem):
    def __init__(self, config, traffic, seed):
        t0 = time.perf_counter()
        super().__init__(config, traffic, seed)
        A_sp = M.fdm27(*self.grid)
        mg = build_mg(*self.grid, depth=config["mg_levels"],
                      pre=config["pre_smooth"], post=config["post_smooth"],
                      coarse_sweeps=config["coarse_sweeps"],
                      fmt=config["host_format"])
        t1 = time.perf_counter()
        cands = [DispatchKey(f, i) for f, i in config["tuner_candidates"]]
        tune = autotune_spmv(A_sp, candidates=cands)
        self.A = tune.operator
        # the coarser levels take the program's zero-run choice: a race there
        # is a coin toss between launches, which moved the solve from run to run
        self.mg = mg.retuned(cands, mode="predict", finest=self.A)
        t2 = time.perf_counter()
        self.clocks = {"host_setup_s": t1 - t0, "tune_s": t2 - t1}
        self.work = {"nnz": A_sp.nnz, "nrows": A_sp.shape[0],
                     "ncols": A_sp.shape[1]}
        self.chosen = self.mg.describe() + "; finest race (us): " + ", ".join(
            [f"{f}/{i} {us:.0f}" for (f, i), us in tune.table.items()]
            + [f"{f}/{i} {why}" for f, i, why in tune.skipped])
        self.b = jax.device_put(self.b_host)
        self._solve = jax.jit(partial(_solve, tol=self.tol, maxiter=self.maxiter))

    def solve(self):
        return self._solve(self.A, self.mg, self.b)

    def probes(self):
        return {"spmv": (partial(_probe_spmv, self.A),
                         jnp.full(self.b.shape, 2.0 ** -100, jnp.float32)),
                "vcycle": (partial(_probe_vcycle, self.mg),
                           self.b / jnp.max(jnp.abs(self.b)))}

    def release(self):
        self.A = self.mg = self.b = self._solve = None


def setup(config, traffic, seed):
    return Cell(config, traffic, seed)


def problem(config, traffic, seed):
    return Problem(config, traffic, seed)


def control(problem: Problem, dtype):
    """Plain CG on the reference stencil with its vectors in ``dtype``."""
    x, k = stencil27.cg_lowp(problem.b_host, problem.grid, tol=problem.tol,
                             maxiter=CONTROL_MAXITER * problem.maxiter, dtype=dtype)
    x = np.asarray(x, np.float64)
    return x, int(k), stencil27.rel_residual(x, problem.b_host, problem.grid)
