"""The resilience lane's structured exception taxonomy + boundary validators.

Dispatch, admission, and the solvers used to fail with whatever the failing
layer happened to raise (a bare ``RuntimeError`` from a kernel, a ``KeyError``
from the warm pool, NaNs silently iterated on by CG). The serving layer needs
to *classify* failures — retry an execution error, back off an admission
error, never retry a poisoned input — so every failure that crosses a layer
boundary is wrapped in one of these types (docs/resilience.md has the full
taxonomy table):

  - ``SparseInputError``     : the caller's operands are unusable (NaN/Inf
                               right-hand side, malformed container indices).
                               Not retryable — retrying the same input fails
                               the same way.
  - ``KernelExecutionError`` : a dispatched kernel raised or produced
                               non-finite output; the chain (or the serving
                               retry loop) may degrade to the next backend.
  - ``AdmissionError``       : building/tuning an operator for the warm pool
                               failed after the engine's bounded retries.
  - ``SolverDivergenceError``: CG's residual went non-finite — HPCG fails
                               loudly instead of iterating on NaNs.
  - ``BackendUnsupportedError``: fallback disabled and the preferred backend
                               cannot run (predates this module; now part of
                               the shared taxonomy).
  - ``InjectedFault``        : raised by ``repro.resilience.faults`` at an
                               instrumented site — deliberately *not* a
                               ``ResilienceError`` so nothing can classify an
                               injected failure as a real one.

The validators at the bottom are the ``ExecutionPolicy.check_finite``
implementation: concrete-only (tracers pass through untouched — validation
under ``jit`` would either fail to trace or bake a stale answer into the
cache), raising ``SparseInputError`` with enough context to identify the
offending operand.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


class ResilienceError(RuntimeError):
    """Base of the structured failure taxonomy (docs/resilience.md)."""


class BackendUnsupportedError(ResilienceError):
    """Raised when fallback is disabled and the preferred backend rejects."""


class SparseInputError(ResilienceError):
    """The operands are unusable: non-finite rhs or a malformed container.

    Never retried — the serving layer resolves the ticket immediately
    (``ServeError.kind == "input"``) instead of burning retry budget."""


class KernelExecutionError(ResilienceError):
    """A dispatched kernel raised, or produced non-finite output under
    ``check_finite``; carries the original failure as ``__cause__``."""


class AdmissionError(ResilienceError):
    """Admission (build + tune + warm-pool insert) failed after the engine's
    bounded retries; tickets for the fingerprint resolve to this."""


class SolverDivergenceError(ResilienceError):
    """An iterative solve produced a non-finite residual or iterate."""


class InjectedFault(RuntimeError):
    """Raised by an active ``FaultPlan`` at an instrumented site.

    Intentionally outside the ``ResilienceError`` hierarchy: handlers that
    catch the taxonomy cannot mistake an injected failure for a real one,
    while the generic recovery paths (``except Exception``) still exercise
    exactly the code a real failure would."""


# ------------------------------------------------------------- validators ----


def _is_tracer(x: Any) -> bool:
    return isinstance(x, jax.core.Tracer)


def _all_finite(x) -> bool:
    """True when every element of a *concrete* array is finite; tracers are
    vacuously finite (the check is an eager-boundary guard, not a jit op)."""
    if _is_tracer(x):
        return True
    if not jnp.issubdtype(jnp.asarray(x).dtype, jnp.inexact):
        return True
    return bool(jnp.all(jnp.isfinite(x)))


def validate_rhs(x, context: str = "rhs") -> None:
    """``check_finite`` input guard: reject a non-finite right-hand side.

    Raises:
        SparseInputError: when ``x`` is concrete and contains NaN/Inf.
    """
    if not _all_finite(x):
        raise SparseInputError(
            f"{context} contains non-finite values "
            f"(shape {tuple(jnp.shape(x))}); refusing to dispatch")


def validate_container(A) -> None:
    """``check_finite`` container guard: value arrays must be finite and
    index arrays in range (pad sentinels — ``-1`` entries, COO's ``nrows``
    row bucket — are allowed).

    Concrete-only, like :func:`validate_rhs`; a traced container passes.

    Raises:
        SparseInputError: naming the offending field.
    """
    leaves = jax.tree_util.tree_leaves(A)
    if any(_is_tracer(l) for l in leaves):
        return
    fmt = getattr(A, "format", "?")
    nrows, ncols = (int(s) for s in A.shape)

    def _bad(field, why):
        raise SparseInputError(
            f"malformed {fmt} container: {field} {why} "
            f"(shape {(nrows, ncols)})")

    for l in leaves:
        arr = np.asarray(l)
        if np.issubdtype(arr.dtype, np.inexact) and not np.all(np.isfinite(arr)):
            _bad("values", "contain non-finite entries")
    if fmt in ("ell", "sell", "csr"):
        idx = np.asarray(A.indices)
        if idx.size and (idx.min() < -1 or idx.max() >= ncols):
            _bad("indices", f"out of range [-1, {ncols})")
    elif fmt == "coo":
        row, col = np.asarray(A.row), np.asarray(A.col)
        # pad sentinels land in the scatter's +1 overflow bucket (row==nrows)
        if row.size and (row.min() < 0 or row.max() > nrows):
            _bad("row", f"out of range [0, {nrows}]")
        if col.size and (col.min() < 0 or col.max() >= ncols):
            _bad("col", f"out of range [0, {ncols})")
        # the in-place row sums read each row's run through the row pointer
        if A.rowptr is not None:
            counts = np.bincount(row[row < nrows], minlength=nrows)
            if (np.any(np.diff(row) < 0) or not np.array_equal(
                    np.asarray(A.rowptr), np.concatenate([[0], np.cumsum(counts)]))):
                _bad("rowptr", "does not point into sorted rows")
