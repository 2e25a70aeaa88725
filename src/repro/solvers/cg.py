"""Conjugate-Gradient solvers over ``SparseOperator`` matvecs.

Extracted from ``apps/hpcg.py`` so every HPCG phase shares one CG core:

  - ``cg_solve``  : the original fixed-iteration CG (bit-identical to the
    pre-refactor loop) — used for the *timed* phases, where a fixed SpMV
    count keeps runtimes comparable across formats/backends.
  - ``pcg_solve`` : fixed-iteration preconditioned CG (same loop shape,
    ``precond`` applied each step).
  - ``cg``        : residual-tolerance stopping via ``lax.while_loop``,
    preconditioned or not — the *convergence* entry point (HPCG's
    "50 iterations to 1e-6" criterion lives here).

All three take a matvec callable (``lambda p: A @ p`` for a SparseOperator),
so the format/backend dispatch of PR 1 applies to every CG flavour.

**Distributed runs.** The loops use the :func:`pdot` / :func:`pnorm` /
:func:`axpy` primitives below. On one device these are exactly
``jnp.vdot`` / ``jnp.linalg.norm`` / ``a*x + y``; when the vectors are
sharded over a mesh axis (a ``DistributedOperator`` matvec keeps them so),
XLA's SPMD partitioner lowers each dot product to a per-shard partial
reduction followed by an ``all-reduce`` — HPCG's ``MPI_Allreduce`` — and
the AXPYs stay purely local. The *same* solver source therefore runs
single- and multi-device, which is the point of the abstraction.

**Device scopes.** Each solver traces under the scope ``cg``, with the
matvec under ``cg/spmv``, every application of the preconditioner under
``cg/precond`` and the dots, AXPYs and stop test under ``cg/vector``
(``repro.core.obs``), so a profile of the solve splits by these.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.obs import scope


def as_matvec(A) -> Callable:
    """Normalise ``A`` into a matvec callable.

    Args:
        A: a ``SparseOperator`` / ``DistributedOperator`` (anything
            supporting ``A @ p``) or an already-callable matvec.

    Returns:
        ``lambda p: A @ p`` (or ``A`` itself when callable).

    Example:
        >>> import numpy as np
        >>> mv = as_matvec(lambda p: 2.0 * p)
        >>> float(mv(np.ones(3))[0])
        2.0
    """
    return A if callable(A) else (lambda p: A @ p)


def pdot(x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """Global dot product ``<x, y>`` — the distributed reduction of CG.

    Single-device this is ``jnp.vdot``; over sharded operands XLA inserts
    the per-shard partial sum + all-reduce (the ``MPI_Allreduce`` of HPCG's
    ``ComputeDotProduct``). Keeping it as a named primitive makes the
    solver's communication points explicit.

    Example:
        >>> import numpy as np
        >>> float(pdot(np.ones(4, np.float32), np.full(4, 2.0, np.float32)))
        8.0
    """
    return jnp.vdot(x, y)


def pnorm(x: jnp.ndarray) -> jnp.ndarray:
    """Global 2-norm ``||x||`` (sharding-transparent, like :func:`pdot`).

    Example:
        >>> import numpy as np
        >>> float(pnorm(np.asarray([3.0, 4.0], np.float32)))
        5.0
    """
    return jnp.linalg.norm(x)


def axpy(a, x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """``a*x + y`` — the (communication-free) vector update of CG.

    Elementwise, so under sharding it is purely rank-local: no collective
    is emitted. Named to mirror HPCG's ``ComputeWAXPBY``.

    Example:
        >>> import numpy as np
        >>> [float(v) for v in axpy(2.0, np.ones(2, np.float32),
        ...                         np.ones(2, np.float32))]
        [3.0, 3.0]
    """
    return a * x + y


def cg_solve(spmv_fn: Callable, b: jnp.ndarray, iters: int):
    """Fixed-iteration CG (no preconditioner).

    Args:
        spmv_fn: the matvec ``p -> A @ p``.
        b: right-hand side; the iterate inherits its sharding.
        iters: exact number of iterations to run (the *timed* HPCG phases
            fix this so every format/backend executes the same op mix).

    Returns:
        ``(x, rs)`` — the final iterate and final squared residual norm.
    """

    def body(_, state):
        x, r, p, rs = state
        with scope("spmv"):
            Ap = spmv_fn(p)
        with scope("vector"):
            alpha = rs / jnp.maximum(pdot(p, Ap), 1e-30)
            x = axpy(alpha, p, x)
            r = axpy(-alpha, Ap, r)
            rs_new = pdot(r, r)
            p = axpy(rs_new / jnp.maximum(rs, 1e-30), p, r)
        return x, r, p, rs_new

    with scope("cg"):
        with scope("vector"):
            state = (jnp.zeros_like(b), b, b, pdot(b, b))
        x, r, p, rs = jax.lax.fori_loop(0, iters, body, state)
    return x, rs


def pcg_solve(spmv_fn: Callable, b: jnp.ndarray, iters: int,
              precond: Optional[Callable] = None):
    """Fixed-iteration preconditioned CG.

    Args:
        spmv_fn: the matvec ``p -> A @ p``.
        b: right-hand side.
        iters: exact iteration count (see :func:`cg_solve`).
        precond: ``r -> M^-1 r``; must be a symmetric positive-definite
            linear map (SymGS and the multigrid V-cycle are). ``None``
            degenerates to the :func:`cg_solve` recurrence.

    Returns:
        ``(x, rs)`` — final iterate and final squared residual norm.
    """
    M = precond if precond is not None else (lambda r: r)

    def body(_, state):
        x, r, p, rz = state
        with scope("spmv"):
            Ap = spmv_fn(p)
        with scope("vector"):
            alpha = rz / jnp.maximum(pdot(p, Ap), 1e-30)
            x = axpy(alpha, p, x)
            r = axpy(-alpha, Ap, r)
        with scope("precond"):
            z = M(r)
        with scope("vector"):
            rz_new = pdot(r, z)
            p = axpy(rz_new / jnp.maximum(rz, 1e-30), p, z)
        return x, r, p, rz_new

    with scope("cg"):
        with scope("precond"):
            z0 = M(b)
        with scope("vector"):
            state = (jnp.zeros_like(b), b, z0, pdot(b, z0))
        x, r, p, rz = jax.lax.fori_loop(0, iters, body, state)
        with scope("vector"):
            return x, pdot(r, r)


class CGInfo(NamedTuple):
    """Result of a tolerance-stopping CG run (jnp scalars; jit-transparent)."""

    x: jnp.ndarray
    iters: jnp.ndarray    # iterations actually taken
    rel_res: jnp.ndarray  # final ||r|| / ||b||


def cg(A, b: jnp.ndarray, *, tol: float = 1e-6, maxiter: int = 500,
       precond: Optional[Callable] = None) -> CGInfo:
    """(P)CG with relative-residual stopping.

    Runs until ``||r|| <= tol * ||b||`` or ``maxiter`` — HPCG's convergence
    criterion. Works unchanged on sharded operands (see module docstring).

    Args:
        A: a ``SparseOperator`` / ``DistributedOperator`` or a matvec
            callable.
        b: right-hand side; the solution inherits its sharding.
        tol: relative residual target.
        maxiter: iteration cap.
        precond: optional SPD preconditioner ``r -> M^-1 r``.

    Returns:
        :class:`CGInfo` with the solution, iterations taken, and final
        relative residual.

    Example:
        >>> import numpy as np, scipy.sparse as sp
        >>> from repro.core import as_operator
        >>> A = as_operator(sp.eye(8, format="csr") * 4.0)
        >>> info = cg(A, np.ones(8, np.float32), tol=1e-8)
        >>> int(info.iters), round(float(info.x[0]), 6)
        (1, 0.25)
    """
    spmv_fn = as_matvec(A)
    M = precond if precond is not None else (lambda r: r)

    def cond(state):
        _, r, _, _, k = state
        with scope("vector"):
            rn = pnorm(r)
            # non-finite residual must exit the loop, not spin to maxiter: the
            # NaN case already does (NaN > t is False) but +Inf would not
            return jnp.isfinite(rn) & (rn > tol * bnorm) & (k < maxiter)

    def body(state):
        x, r, p, rz, k = state
        with scope("spmv"):
            Ap = spmv_fn(p)
        with scope("vector"):
            alpha = rz / jnp.maximum(pdot(p, Ap), 1e-30)
            x = axpy(alpha, p, x)
            r = axpy(-alpha, Ap, r)
        with scope("precond"):
            z = M(r)
        with scope("vector"):
            rz_new = pdot(r, z)
            p = axpy(rz_new / jnp.maximum(rz, 1e-30), p, z)
            return x, r, p, rz_new, k + 1

    with scope("cg"):
        with scope("vector"):
            bnorm = jnp.maximum(pnorm(b), 1e-30)
        with scope("precond"):
            z0 = M(b)
        with scope("vector"):
            state = (jnp.zeros_like(b), b, z0, pdot(b, z0), jnp.int32(0))
        x, r, _, _, k = jax.lax.while_loop(cond, body, state)
        with scope("vector"):
            return CGInfo(x, k, pnorm(r) / bnorm)


class CGDiagnostics(NamedTuple):
    """Post-run divergence analysis of a :class:`CGInfo` (host-side bools —
    build it on *concrete* results, after the jitted solve returned)."""

    converged: bool   # rel_res <= tol
    finite: bool      # rel_res (and hence the residual) is finite
    stalled: bool     # hit maxiter with rel_res still above tol
    rel_res: float
    iters: int


def diagnose_cg(info: CGInfo, *, tol: float, maxiter: int) -> CGDiagnostics:
    """Classify a finished CG run: converged / non-finite / stalled.

    Example:
        >>> import jax.numpy as jnp
        >>> info = CGInfo(jnp.zeros(2), jnp.int32(500), jnp.float32(0.5))
        >>> d = diagnose_cg(info, tol=1e-6, maxiter=500)
        >>> (d.converged, d.finite, d.stalled)
        (False, True, True)
    """
    rel = float(info.rel_res)
    iters = int(info.iters)
    finite = bool(jnp.isfinite(info.rel_res))
    converged = finite and rel <= tol
    stalled = finite and not converged and iters >= maxiter
    return CGDiagnostics(converged=converged, finite=finite, stalled=stalled,
                         rel_res=rel, iters=iters)


def cg_guarded(A, b: jnp.ndarray, *, tol: float = 1e-6, maxiter: int = 500,
               precond: Optional[Callable] = None,
               restart: bool = False):
    """:func:`cg` that fails loudly on divergence instead of returning junk.

    Runs :func:`cg`, then :func:`diagnose_cg` on the concrete result. A
    non-finite residual (a NaN/Inf matvec — e.g. a corrupted kernel) or a
    stalled run (``maxiter`` without reaching ``tol``) raises
    :class:`~repro.core.errors.SolverDivergenceError` carrying the
    diagnostics; with ``restart=True`` a non-finite run first retries once
    on the always-correct degraded matvec (``plain``-chain dispatch) before
    giving up — the solver-side analogue of the engine's
    retry-with-degradation.

    Returns:
        ``(CGInfo, CGDiagnostics)`` on success.
    """
    from repro.core.errors import SolverDivergenceError

    info = cg(A, b, tol=tol, maxiter=maxiter, precond=precond)
    diag = diagnose_cg(info, tol=tol, maxiter=maxiter)
    if not diag.finite and restart:
        info = cg(_degraded_matvec(A), b, tol=tol, maxiter=maxiter,
                  precond=precond)
        diag = diagnose_cg(info, tol=tol, maxiter=maxiter)
    if not diag.finite:
        raise SolverDivergenceError(
            f"CG produced a non-finite residual after {diag.iters} "
            f"iterations (rel_res={diag.rel_res}) — kernel fault or "
            f"ill-posed input")
    if diag.stalled:
        raise SolverDivergenceError(
            f"CG stalled: {diag.iters} iterations reached rel_res="
            f"{diag.rel_res:.3e}, target {tol:.3e}")
    return info, diag


def _degraded_matvec(A) -> Callable:
    """The restart lane: ``A``'s matvec forced onto the plain-first chain
    (reference kernels, fallback allowed) when ``A`` carries a policy;
    callables and policy-less operators pass through unchanged."""
    pol = getattr(A, "_effective_policy", None)
    with_policy = getattr(A, "with_policy", None)
    if pol is None or with_policy is None:
        return as_matvec(A)
    base = pol()
    chain = ("plain",) + tuple(b for b in base.backends if b != "plain")
    return as_matvec(with_policy(base.replace(backends=chain,
                                              allow_fallback=True)))
