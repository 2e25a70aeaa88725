"""Symmetric Gauss-Seidel (HPCG's smoother) as a SparseOperator client.

Two interchangeable schedules:

  - ``reference``  : textbook forward/backward triangular sweeps in natural
    row order, run as a sequential ``lax.scan`` over rows. Exact GS semantics,
    O(nrows) dependent steps — the oracle the fast path is tested against.
  - ``multicolor`` : rows are greedily colored so no two coupled rows share a
    color; each color updates *in parallel* as one row-masked SpMV through
    the core dispatch table (``SparseOperator.masked_matvec``). A full sweep
    walks colors forward then backward, so the induced preconditioner
    M = (D+L_pi) D^-1 (D+U_pi) stays symmetric (pi = the color ordering).

Because the color sweeps are ordinary dispatch-table SpMVs, SymGS retargets
across formats and backends exactly like any other kernel — the point of the
Morpheus abstraction, now covering HPCG's dominant non-SpMV phase.

A sweep traces under the device scopes ``symgs/fwd`` and ``symgs/bwd``; the
host build records the spans ``symgs.build``, ``symgs.colour`` and
``symgs.schedule`` (``repro.core.obs``).
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp

from repro.core import SparseOperator, as_operator, obs
from repro.core.convert import _as_scipy


def greedy_coloring(s: sp.spmatrix) -> np.ndarray:
    """Greedy distance-1 coloring of the (symmetrised) adjacency of ``s``.

    Rows sharing a color have no off-diagonal coupling, so a Gauss-Seidel
    update of a whole color is order-independent. The 27-point stencil
    colors in 8 (the 2x2x2 parity classes); greedy natural order finds it.
    """
    with obs.span("symgs.colour", nrows=s.shape[0]):
        s = s.tocsr()
        pattern = ((s != 0) + (s != 0).T).tocsr()  # symmetrise: GS couples both ways
        n = s.shape[0]
        colors = np.full(n, -1, np.int32)
        indptr, indices = pattern.indptr, pattern.indices
        for i in range(n):
            neigh = indices[indptr[i]:indptr[i + 1]]
            used = {colors[j] for j in neigh if j != i and colors[j] >= 0}
            c = 0
            while c in used:
                c += 1
            colors[i] = c
        return colors


def _padded_offdiag(s: sp.csr_matrix) -> Tuple[np.ndarray, np.ndarray]:
    """Strictly off-diagonal entries of each row, ELL-padded (idx=-1, val=0)."""
    s = s.tocsr()
    n = s.shape[0]
    counts = np.diff(s.indptr)
    w = max(1, int(counts.max()) if n else 1)
    idx = np.full((n, w), -1, np.int32)
    val = np.zeros((n, w), np.float64)
    for i in range(n):
        lo, hi = s.indptr[i], s.indptr[i + 1]
        cols, vals = s.indices[lo:hi], s.data[lo:hi]
        off = cols != i
        k = int(off.sum())
        idx[i, :k] = cols[off]
        val[i, :k] = vals[off]
    return idx, val


@partial(jax.tree_util.register_dataclass,
         data_fields=["A", "diag", "masks", "off_idx", "off_val"],
         meta_fields=["method"])
@dataclass(frozen=True)
class SymGS:
    """One symmetric Gauss-Seidel sweep, ``__call__`` = apply M^-1 from zero.

    ``A`` drives the multicolor path (masked SpMV per color through the
    dispatch table); ``diag``/``masks`` are host-built schedule data. The
    reference path carries the padded off-diagonal triangle arrays instead.
    A pytree, so a jitted solver can take it as an argument.
    """

    A: SparseOperator
    diag: jnp.ndarray                       # (n,) float
    masks: Optional[jnp.ndarray] = None     # (ncolors, n) bool, multicolor only
    off_idx: Optional[jnp.ndarray] = None   # (n, w) int32, reference only
    off_val: Optional[jnp.ndarray] = None   # (n, w) float, reference only
    method: str = "multicolor"

    @classmethod
    def build(cls, a, operator: Optional[SparseOperator] = None,
              method: str = "multicolor", dtype=jnp.float32) -> "SymGS":
        """``a`` is anything ``as_operator`` accepts; ``operator`` optionally
        overrides the SpMV operator (e.g. a tuned one) while the schedule is
        still derived from ``a``'s host-side structure."""
        with obs.span("symgs.build", method=method):
            s = _as_scipy(a).tocsr()
            n = s.shape[0]
            d = np.asarray(s.diagonal(), np.float64)
            if not np.all(d != 0):
                raise ValueError("SymGS needs a nonzero diagonal on every row")
            op = operator if operator is not None else as_operator(s, "csr")
            diag = jnp.asarray(d, dtype)
            if method == "multicolor":
                colors = greedy_coloring(s)
                with obs.span("symgs.schedule"):
                    ncolors = int(colors.max()) + 1 if n else 1
                    masks = jnp.asarray(
                        np.stack([colors == c for c in range(ncolors)]) if n
                        else np.ones((1, 0), bool))
                return cls(op, diag, masks=masks, method=method)
            if method == "reference":
                with obs.span("symgs.schedule"):
                    idx, val = _padded_offdiag(s)
                return cls(op, diag, off_idx=jnp.asarray(idx),
                           off_val=jnp.asarray(val, dtype), method=method)
            raise ValueError(f"unknown SymGS method {method!r}")

    @property
    def ncolors(self) -> int:
        return 0 if self.masks is None else int(self.masks.shape[0])

    def with_operator(self, op: SparseOperator) -> "SymGS":
        """Same schedule, retargeted SpMV operator (per-level tuning hook).

        ``op`` may be any object with the ``masked_matvec(x, mask)``
        protocol — a ``SparseOperator`` or a ``DistributedOperator``.
        """
        return replace(self, A=op)

    def distribute(self, op) -> "SymGS":
        """This smoother retargeted onto a ``DistributedOperator``.

        Only the ``multicolor`` schedule distributes: each color update is
        one row-masked SpMV (``op.masked_matvec``), which the distributed
        operator runs as local+remote masked SpMV with a fresh halo
        exchange per color — exactly HPCG's multicolored distributed SymGS.
        The schedule itself (coloring, diagonal) is global host data and is
        re-placed with the operator's row sharding; semantics are identical
        to the single-device multicolor sweep because the color ordering is
        unchanged.

        Args:
            op: a ``DistributedOperator`` over the same matrix (its
                ``sharding()``/``mesh`` decide the placement).

        Returns:
            A new ``SymGS`` whose sweeps take and return sharded vectors.
        """
        if self.method != "multicolor":
            raise ValueError(
                "only the multicolor schedule distributes (the reference "
                "triangular sweep is a sequential scan over global rows)")
        import jax as _jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        row = op.sharding()
        mask_sh = NamedSharding(op.mesh, P(None, op.axis))
        return replace(self, A=op,
                       diag=_jax.device_put(self.diag, row),
                       masks=_jax.device_put(self.masks, mask_sh))

    # -- sweeps (jittable) ---------------------------------------------------

    def _color_half(self, r, x, masks):
        def step(x, mask):
            y = self.A.masked_matvec(x, mask)  # (A x) restricted to the color
            return jnp.where(mask, x + (r - y) / self.diag, x), None

        x, _ = jax.lax.scan(step, x, masks)
        return x

    def _tri_half(self, r, x, reverse: bool):
        n = r.shape[0]
        rows = jnp.arange(n, dtype=jnp.int32)

        def step(x, i):
            idx, val = self.off_idx[i], self.off_val[i]
            acc = jnp.sum(val * x[jnp.maximum(idx, 0)])  # val=0 at pads
            return x.at[i].set((r[i] - acc) / self.diag[i]), None

        x, _ = jax.lax.scan(step, x, rows, reverse=reverse)
        return x

    def sweep(self, r, x=None) -> jnp.ndarray:
        """One symmetric sweep (forward then backward) from iterate ``x``."""
        if x is None:
            x = jnp.zeros_like(r)
        with obs.scope("symgs"):
            if self.method == "multicolor":
                with obs.scope("fwd"):
                    x = self._color_half(r, x, self.masks)
                with obs.scope("bwd"):
                    return self._color_half(r, x, self.masks[::-1])
            with obs.scope("fwd"):
                x = self._tri_half(r, x, reverse=False)
            with obs.scope("bwd"):
                return self._tri_half(r, x, reverse=True)

    def __call__(self, r) -> jnp.ndarray:
        """Apply the SymGS preconditioner: M^-1 r (sweep from zero)."""
        return self.sweep(r, jnp.zeros_like(r))
