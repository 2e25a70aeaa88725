"""Plain reference of GAP's PageRank (``pr_spmv``), independent of the program.

Pull-direction power iteration on an undirected graph, as in the GAP
Benchmark Suite (Beamer, Asanovic and Patterson, arXiv:1508.03619): scores
start at ``1/n``; each iteration gives every vertex
``(1 - d)/n + d * sum(score[v] / degree[v] for v in neighbours)`` and stops
once the L1 change of the scores is below ``tol`` or after ``maxiter``
iterations. A vertex without edges passes nothing on.

``pagerank`` runs it in float64 with scipy on the host. ``pagerank_lowp``
is the control: the same iteration with the scores and contributions
stored in a lower precision (sums in f32), standing where the program's
SpMV loop stands.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp


def inverse_degree(csr: sp.csr_matrix) -> np.ndarray:
    deg = np.diff(csr.indptr).astype(np.float64)
    return np.divide(1.0, deg, out=np.zeros_like(deg), where=deg > 0)


#: GAP's stop test compares an L1 sum with ``tol``; a sum computed in f32
#: can land on the other side of ``tol`` than the f64 one when the two lie
#: this close (relative), and the rule then stops one iteration apart
STOP_SLACK = 0.01


def pagerank(csr: sp.csr_matrix, *, damping: float, tol: float, maxiter: int):
    """``(answers, iterations)`` in float64. ``answers`` holds the scores at
    the reference's stop and, where its stop test read within
    ``STOP_SLACK`` of ``tol``, the scores one iteration either side of it:
    every answer the rule gives under rounding of that test."""
    n = csr.shape[0]
    adj = sp.csr_matrix((np.ones(csr.nnz), csr.indices, csr.indptr),
                        shape=csr.shape)
    inv = inverse_degree(csr)
    base = (1.0 - damping) / n
    step = lambda s: base + damping * (adj @ (s * inv))  # noqa: E731
    near = lambda err: abs(err - tol) <= STOP_SLACK * tol  # noqa: E731
    prev, s, errs = None, np.full(n, 1.0 / n), []
    for _ in range(maxiter):
        prev, s = s, step(s)
        errs.append(np.abs(s - prev).sum())
        if errs[-1] < tol:
            break
    k = len(errs)
    answers = [s]
    if k > 1 and near(errs[-2]):
        answers.append(prev)
    if k < maxiter and near(errs[-1]):
        answers.append(step(s))
    return answers, k


def l1_gap(scores, answers) -> float:
    """The L1 distance of ``scores`` from the nearest of ``answers``;
    infinite when ``scores`` is not finite."""
    scores = np.asarray(scores, np.float64)
    if not np.all(np.isfinite(scores)):
        return float("inf")
    return float(min(np.abs(scores - a).sum() for a in answers))


def pagerank_lowp(csr: sp.csr_matrix, *, damping: float, tol: float,
                  maxiter: int, dtype):
    """The control: scores and contributions stored in ``dtype``, each
    neighbour sum accumulated in f32 by a segment sum. Returns
    ``(scores, last L1 change, iterations)`` as jax arrays."""
    import jax
    import jax.numpy as jnp

    n = csr.shape[0]
    rows = np.repeat(np.arange(n, dtype=np.int32), np.diff(csr.indptr))
    base = (1.0 - damping) / n

    def solve(rows, cols, inv):
        def cond(s):
            _, err, k = s
            return (err >= tol) & (k < maxiter)

        def body(s):
            scores, _, k = s
            contrib = (scores.astype(jnp.float32) * inv).astype(dtype)
            y = jax.ops.segment_sum(contrib[cols].astype(jnp.float32), rows,
                                    num_segments=n, indices_are_sorted=True)
            new = (base + damping * y).astype(dtype)
            err = jnp.sum(jnp.abs(new.astype(jnp.float32)
                                  - scores.astype(jnp.float32)))
            return new, err, k + 1

        s0 = (jnp.full(n, 1.0 / n, dtype), jnp.float32(jnp.inf), jnp.int32(0))
        return jax.lax.while_loop(cond, body, s0)

    return jax.jit(solve)(jnp.asarray(rows), jnp.asarray(csr.indices, jnp.int32),
                          jnp.asarray(inverse_degree(csr), jnp.float32))
