"""Plain reference of HPCG's 27-point stencil, independent of the program.

Row ``i + nx*(j + ny*k)`` of the operator holds 26 on the diagonal and -1
for each of its up to 26 neighbours inside the grid (HPCG's
``GenerateProblem``). ``apply`` computes ``A @ x`` in float64 from the grid
alone, as 27 times ``x`` less the 3x3x3 box sum of ``x`` with zero padding.

``cg_lowp`` is the control: plain conjugate gradients on the same stencil
with every vector stored in a lower precision (products and sums in f32).
It stands where the program's solver stands, to show that the comparison
fails a solve computed below the configuration's precision.
"""
from __future__ import annotations

import numpy as np


def _box3(u, axis):
    """``u`` plus its two neighbours along ``axis`` (zero outside)."""
    t = u.copy()
    lo = [slice(None)] * u.ndim
    hi = [slice(None)] * u.ndim
    lo[axis], hi[axis] = slice(1, None), slice(None, -1)
    t[tuple(lo)] += u[tuple(hi)]
    t[tuple(hi)] += u[tuple(lo)]
    return t


def apply(x: np.ndarray, grid) -> np.ndarray:
    """``A @ x`` in float64 for the (nx, ny, nz) stencil."""
    nx, ny, nz = grid
    u = np.asarray(x, np.float64).reshape(nz, ny, nx)
    box = _box3(_box3(_box3(u, 0), 1), 2)
    return (27.0 * u - box).ravel()


def rel_residual(x: np.ndarray, b: np.ndarray, grid) -> float:
    """``||b - A x|| / ||b||`` in float64; infinite when ``x`` is not finite."""
    x = np.asarray(x, np.float64)
    if not np.all(np.isfinite(x)):
        return float("inf")
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(b - apply(x, grid)) / np.linalg.norm(b))


def cg_lowp(b, grid, *, tol: float, maxiter: int, dtype):
    """Plain CG from zero with ``x``, ``r`` and ``p`` stored in ``dtype``;
    the stencil, dot products and updates are computed in f32 and rounded
    back. Returns ``(x, iterations)`` as jax arrays."""
    import jax
    import jax.numpy as jnp

    nx, ny, nz = grid
    f32 = jnp.float32

    def box3(u, axis):
        z = jnp.zeros_like(jax.lax.slice_in_dim(u, 0, 1, axis=axis))
        prev = jnp.concatenate([z, jax.lax.slice_in_dim(u, 0, -1, axis=axis)], axis)
        nxt = jnp.concatenate([jax.lax.slice_in_dim(u, 1, None, axis=axis), z], axis)
        return u + prev + nxt

    def matvec(x):
        u = x.astype(f32).reshape(nz, ny, nx)
        return (27.0 * u - box3(box3(box3(u, 0), 1), 2)).ravel()

    def solve(b):
        b = b.astype(dtype)
        bnorm = jnp.linalg.norm(b.astype(f32))

        def cond(s):
            _, r, _, k = s
            rn = jnp.linalg.norm(r.astype(f32))
            return jnp.isfinite(rn) & (rn > tol * bnorm) & (k < maxiter)

        def body(s):
            x, r, p, k = s
            r32, p32 = r.astype(f32), p.astype(f32)
            ap = matvec(p)
            rr = jnp.vdot(r32, r32)
            alpha = rr / jnp.maximum(jnp.vdot(p32, ap), 1e-30)
            x = (x.astype(f32) + alpha * p32).astype(dtype)
            r32 = r32 - alpha * ap
            beta = jnp.vdot(r32, r32) / jnp.maximum(rr, 1e-30)
            return x, r32.astype(dtype), (r32 + beta * p32).astype(dtype), k + 1

        x0 = jnp.zeros_like(b)
        x, _, _, k = jax.lax.while_loop(cond, body, (x0, b, b, jnp.int32(0)))
        return x, k

    return jax.jit(solve)(jnp.asarray(b))
