"""Traffic driver: repeated CG solves of HPCG's stencil with no preconditioner.

``Problem`` is ``pcg.Problem``: the same grid, HPCG's right-hand side
``b = A @ ones`` and the same check of every solve of the window; the
control is ``pcg.control``, plain CG on the reference stencil below the
configuration's precision.

``Cell`` adds the system under test: the program builds the stencil
(``fdm27``) and races the configuration's tuner candidates for it with
``autotune_spmv``; no V-cycle is built. One solve is ``repro.solvers.cg``
from zero with no preconditioner, until the relative residual is below the
traffic's ``tol`` or ``maxiter`` iterations have run, jitted with the
operator as an argument: the SpMV-plus-vector loop every Krylov solver of
the library shares.

Only ``--trace 1`` runs ask for probes during set-up. The first time they
do, the cell also traces ``SCOPE_SOLVES`` whole solves in a profiler
session of its own and hands the device seconds under the program's
scopes (``bench/scopes.py``) to the record through ``clocks``:
``traced_iters``, ``busy_s``, ``scoped_s`` and ``<region>_s``.
"""
from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp

from bench import scopes
from bench.drivers.pcg import Problem, control  # noqa: F401  (the driver API)
from repro.core import DispatchKey, autotune_spmv
from repro.core import matrices as M
from repro.solvers import cg

#: whole solves in the cell's own traced section
SCOPE_SOLVES = 3


def probe_spmv(A, x):
    return A @ x


_probe_spmv = jax.jit(probe_spmv)


def _solve(A, b, *, tol, maxiter):
    return cg(A, b, tol=tol, maxiter=maxiter)


class Cell(Problem):
    def __init__(self, config, traffic, seed):
        t0 = time.perf_counter()
        super().__init__(config, traffic, seed)
        A_sp = M.fdm27(*self.grid)
        t1 = time.perf_counter()
        cands = [DispatchKey(f, i) for f, i in config["tuner_candidates"]]
        tune = autotune_spmv(A_sp, candidates=cands)
        self.A = tune.operator
        t2 = time.perf_counter()
        self.clocks = {"host_setup_s": t1 - t0, "tune_s": t2 - t1}
        self.work = {"nnz": A_sp.nnz, "nrows": A_sp.shape[0],
                     "ncols": A_sp.shape[1]}
        self.chosen = f"{tune.format}/{tune.impl}; race (us): " + ", ".join(
            [f"{f}/{i} {us:.0f}" for (f, i), us in tune.table.items()]
            + [f"{f}/{i} {why}" for f, i, why in tune.skipped])
        self.b = jax.device_put(self.b_host)
        self._solve = jax.jit(partial(_solve, tol=self.tol, maxiter=self.maxiter))
        self._scoped = False

    def solve(self):
        return self._solve(self.A, self.b)

    def _trace_scopes(self):
        hlo = self._solve.lower(self.A, self.b).compile().as_text()
        ops, outs = scopes.trace_solves(self.solve, SCOPE_SOLVES)
        split = scopes.split(ops, scopes.op_paths(hlo))
        if split is not None:
            self.clocks["traced_iters"] = float(sum(int(o[1]) for o in outs))
            self.clocks.update(split)

    def probes(self):
        if not self._scoped:
            self._scoped = True
            self._trace_scopes()
        return {"spmv": (partial(_probe_spmv, self.A),
                         jnp.full(self.b.shape, 2.0 ** -100, jnp.float32))}

    def release(self):
        self.A = self.b = self._solve = None


def setup(config, traffic, seed):
    return Cell(config, traffic, seed)


def problem(config, traffic, seed):
    return Problem(config, traffic, seed)
