"""Traffic driver: repeated GAP PageRank solves over a Kronecker graph.

``Problem`` is the yardstick's side: the configuration's graph, made on the
device from the seed (``bench/kronecker.py``), GAP's rule, and the check,
which takes every solve of the window and reports the L1 distance of its
scores from the plain reference's (scipy, f64, the same rule; the nearest
of the answers the rule gives under rounding of its stop test), for the
worst one.

``Cell`` adds the system under test: the program races the configuration's
tuner candidates for the graph (``autotune_spmv``). One solve is GAP's pull
PageRank from the uniform ``1/n`` start, one SpMV through the tuned operator
per iteration, inside one jitted ``lax.while_loop`` with the operator as an
argument.

``control`` puts the plain reference, computed below the configuration's
precision, in the program's place (``bench/control.py``).
"""
from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import kronecker
from bench.reference import pagerank as ref
from repro.core import DispatchKey, autotune_spmv


def probe_spmv(A, x):
    return A @ x


_probe_spmv = jax.jit(probe_spmv)


def pagerank(A, inv_deg, *, damping, tol, maxiter):
    """``(scores, last L1 change, iterations)`` of GAP's rule."""
    n = inv_deg.shape[0]
    base = (1.0 - damping) / n

    def cond(s):
        _, err, k = s
        return (err >= tol) & (k < maxiter)

    def body(s):
        scores, _, k = s
        new = base + damping * (A @ (scores * inv_deg))
        return new, jnp.sum(jnp.abs(new - scores)), k + 1

    s0 = (jnp.full((n,), 1.0 / n, jnp.float32), jnp.float32(jnp.inf),
          jnp.int32(0))
    return jax.lax.while_loop(cond, body, s0)


class Problem:
    """The graph, the rule and the check; a solve's output is
    ``(scores, last L1 change, iterations)``."""

    def __init__(self, config, traffic, seed):
        self.rule = {"damping": float(traffic["damping"]),
                     "tol": float(traffic["tol"]),
                     "maxiter": int(traffic["maxiter"])}
        self.limit = float(traffic["limits"]["l1_gap"])
        self.graph = kronecker.generate(seed, scale=config["scale"],
                                        edgefactor=config["edgefactor"],
                                        initiator=config["initiator"],
                                        undirected_edges=config["undirected_edges"])

    def summarize(self, out):
        scores, err, iters = out[0], out[1], int(out[2])
        finite = bool(np.isfinite(err)) and bool(np.all(np.isfinite(scores)))
        return {"iters": iters,
                "failed": not finite or iters >= self.rule["maxiter"]}

    def check(self, outs):
        answers, _ = ref.pagerank(self.graph, **self.rule)
        worst = max(ref.l1_gap(o[0], answers) for o in outs)
        return [{"name": "l1_gap", "value": worst, "limit": self.limit}]


class Cell(Problem):
    def __init__(self, config, traffic, seed):
        t0 = time.perf_counter()
        super().__init__(config, traffic, seed)
        inv_deg = ref.inverse_degree(self.graph).astype(np.float32)
        t1 = time.perf_counter()
        cands = [DispatchKey(f, i) for f, i in config["tuner_candidates"]]
        tune = autotune_spmv(self.graph, candidates=cands)
        self.A = tune.operator
        t2 = time.perf_counter()
        self.clocks = {"host_setup_s": t1 - t0, "tune_s": t2 - t1}
        n = self.graph.shape[0]
        self.work = {"nnz": self.graph.nnz, "nrows": n, "ncols": n}
        self.chosen = f"{tune.format}/{tune.impl}; race (us): " + ", ".join(
            [f"{f}/{i} {us:.0f}" for (f, i), us in tune.table.items()]
            + [f"{f}/{i} {why}" for f, i, why in tune.skipped])
        self.inv_deg = jax.device_put(inv_deg)
        self._solve = jax.jit(partial(pagerank, **self.rule))

    def solve(self):
        return self._solve(self.A, self.inv_deg)

    def probes(self):
        return {"spmv": (partial(_probe_spmv, self.A),
                         jnp.full(self.inv_deg.shape, 2.0 ** -100, jnp.float32))}

    def release(self):
        self.A = self.inv_deg = self._solve = None


def setup(config, traffic, seed):
    return Cell(config, traffic, seed)


def control(problem: Problem, dtype):
    """GAP's rule on the reference's segment sum with scores in ``dtype``."""
    return ref.pagerank_lowp(problem.graph, dtype=dtype, **problem.rule)


def problem(config, traffic, seed):
    return Problem(config, traffic, seed)
