"""Geometric multigrid V-cycle for the HPCG 27-point stencil.

HPCG's multigrid: at every level, pre-smooth with SymGS, restrict the
residual by *injection* onto the 2x-coarsened grid, recurse, prolong the
coarse correction back (injection transpose), post-smooth. Coarse operators
are re-discretised 27-point stencils (``matrices.fdm27`` at halved dims),
exactly as the reference benchmark does.

Every linear piece is a ``SparseOperator``: the level matrices (tunable
per-level, Table III style — each level's sparsity pattern may pick a
different winning format/backend), and the restriction/prolongation maps
(COO containers with one unit entry per coarse point). The V-cycle is
therefore jittable end-to-end and retargets with the dispatch table.

Level ``i`` of a cycle traces under the device scope ``mg/L<i>``, its
steps under ``presmooth``, ``residual``, ``restrict``, ``prolong``,
``postsmooth`` and, at the coarsest level, ``coarse``; the host build
records the spans ``mg.build``, ``mg.level`` and ``mg.transfer``
(``repro.core.obs``).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp

from repro.core import SparseOperator, as_operator
from repro.core import matrices as M
from repro.core import obs
from repro.core.autotune import autotune_spmv

from .symgs import SymGS


def injection_operators(nx: int, ny: int, nz: int,
                        dtype=jnp.float32) -> Tuple[SparseOperator, SparseOperator]:
    """(R, P) for one 2x geometric coarsening step, as COO SparseOperators.

    R is (nc, nf) with R[ic, f2c[ic]] = 1 (injection); P = R^T, so the coarse
    correction scatters back onto the injected points and the V-cycle stays a
    symmetric preconditioner.
    """
    with obs.span("mg.transfer", grid=(nx, ny, nz)):
        f2c = M.coarsen_injection(nx, ny, nz)
        nf, nc = nx * ny * nz, len(f2c)
        ones = np.ones(nc, np.float64)
        R = sp.csr_matrix((ones, (np.arange(nc), f2c)), shape=(nc, nf))
        P = sp.csr_matrix((ones, (f2c, np.arange(nc))), shape=(nf, nc))
        return (as_operator(R, "coo", dtype=dtype),
                as_operator(P, "coo", dtype=dtype))


@partial(jax.tree_util.register_dataclass,
         data_fields=["A", "smoother", "R", "P"], meta_fields=["grid"])
@dataclass(frozen=True)
class MGLevel:
    grid: Tuple[int, int, int]
    A: SparseOperator
    smoother: SymGS
    R: Optional[SparseOperator] = None  # to the next (coarser) level
    P: Optional[SparseOperator] = None  # back from it

    @property
    def chosen(self) -> str:
        pol = self.A.policy
        backend = pol.backends[0] if pol is not None and pol.backends else "plain"
        return f"{self.A.format}/{backend}"


@partial(jax.tree_util.register_dataclass, data_fields=["levels"],
         meta_fields=["pre", "post", "coarse_sweeps"])
@dataclass(frozen=True)
class VCycle:
    """Recursive V-cycle, ``__call__(r) ~= A^-1 r`` — a symmetric
    positive-definite preconditioner when pre == post (SymGS is symmetric and
    P = R^T), so it drops straight into preconditioned CG. A pytree: pass it
    to a jitted solver as an argument, so its arrays are not baked into the
    executable as constants."""

    levels: Tuple[MGLevel, ...]
    pre: int = 1
    post: int = 1
    coarse_sweeps: int = 4

    @property
    def depth(self) -> int:
        return len(self.levels)

    def describe(self) -> str:
        return " | ".join(f"{'x'.join(map(str, l.grid))}:{l.chosen}"
                          for l in self.levels)

    def retuned(self, candidates=None, mode: str = "run",
                finest: Optional[SparseOperator] = None) -> "VCycle":
        """Retarget every level's operators to a fresh (format, backend)
        choice — the per-level format choice of Table III. Schedules
        (coloring, diag, R/P) are reused; only the SpMV operators change.

        ``mode="run"`` races candidates per level with the run-first tuner;
        ``mode="predict"`` uses the zero-run feature selector instead
        (``SparseOperator.tune(mode="predict")``) — no kernel executes
        during setup, which is the cheap path deep hierarchies want.
        ``finest`` is an operator already tuned for the finest level's
        matrix (the solver's own, in HPCG): it is installed there instead
        of racing that level a second time.
        """
        if mode not in ("run", "predict"):
            raise ValueError(f"retuned mode {mode!r}: expected 'run' or 'predict'")
        levels = []
        with obs.span("tune.retarget", mode=mode):
            for li, l in enumerate(self.levels):
                if li == 0 and finest is not None:
                    op = finest
                elif mode == "predict":
                    with obs.span("tune.predict", level=li):
                        op = l.A.tune(candidates=candidates, mode="predict")
                else:
                    op = autotune_spmv(l.A, candidates=candidates).operator
                levels.append(MGLevel(l.grid, op, l.smoother.with_operator(op),
                                      l.R, l.P))
        return VCycle(tuple(levels), self.pre, self.post, self.coarse_sweeps)

    def _apply(self, li: int, r: jnp.ndarray) -> jnp.ndarray:
        lvl = self.levels[li]
        with obs.scope("mg", f"L{li}"):
            x = jnp.zeros_like(r)
            if li == len(self.levels) - 1:  # coarsest: smooth it out
                with obs.scope("coarse"):
                    for _ in range(self.coarse_sweeps):
                        x = lvl.smoother.sweep(r, x)
                return x
            with obs.scope("presmooth"):
                for _ in range(self.pre):
                    x = lvl.smoother.sweep(r, x)
            with obs.scope("residual"):
                res = r - lvl.A @ x
            with obs.scope("restrict"):
                rc = lvl.R @ res
            xc = self._apply(li + 1, rc)
            with obs.scope("prolong"):
                x = x + lvl.P @ xc
            with obs.scope("postsmooth"):
                for _ in range(self.post):
                    x = lvl.smoother.sweep(r, x)
            return x

    def __call__(self, r: jnp.ndarray) -> jnp.ndarray:
        return self._apply(0, r)


def coarsenable(grid: Sequence[int], min_dim: int = 4) -> bool:
    """Whether a stencil grid admits another 2x geometric coarsening step.

    Example:
        >>> coarsenable((8, 8, 8)), coarsenable((8, 8, 7)), coarsenable((2, 2, 2))
        (True, False, False)
    """
    return all(d % 2 == 0 and d // 2 >= min_dim // 2 and d > 2 for d in grid)


def distributable_depth(nx: int, ny: int, nz: int, nparts: int,
                        depth: int = 4) -> int:
    """Deepest hierarchy where ``nparts`` divides every level's row count.

    Distributed levels shard rows evenly over the mesh axis, so a level with
    ``n % nparts != 0`` cannot be built; the hierarchy is truncated above it.

    Example:
        >>> distributable_depth(16, 16, 16, 4)   # 4096, 512, 64, 8 all divide 4
        4
        >>> distributable_depth(4, 4, 8, 4)      # 128, 16; next level is 2
        2
    """
    d, grid = 0, (nx, ny, nz)
    while d < depth:
        if (grid[0] * grid[1] * grid[2]) % nparts:
            break
        d += 1
        if not coarsenable(grid):
            break
        grid = tuple(g // 2 for g in grid)
    if d == 0:
        raise ValueError(f"finest grid {nx}x{ny}x{nz} is not divisible by "
                         f"{nparts} parts")
    return d


def distribute_vcycle(vc: VCycle, mesh, axis: str = "data", *,
                      tune: bool = False, candidates=None,
                      dtype=jnp.float32, fmt: str = "csr") -> VCycle:
    """The V-cycle with every level's linear algebra sharded over ``mesh``.

    Per level (the tentpole wiring of the distributed HPCG):

      - ``A``  -> a ``DistributedOperator`` (local/remote split, halo
        exchange picked automatically per level — fine levels get the
        nearest-neighbour ``ppermute`` window, coarse levels whose stencil
        reach exceeds the shard fall back to ``all_gather``);
      - the SymGS smoother -> ``smoother.distribute(A)`` (multicolor masked
        sweeps through the distributed dispatch, schedule unchanged);
      - ``R``/``P`` -> distributed operators too. With the stencil's
        z-major numbering the injection transfers are rank-aligned, so
        their remote parts are empty and they run collective-free.

    Args:
        vc: a host-built hierarchy from :func:`build_mg`. Every level's row
            count must be divisible by the mesh axis size (see
            :func:`distributable_depth`).
        mesh / axis: 1-D device axis to shard over.
        tune: per-partition run-first tune of each level's operator
            (Table III per-process choices), otherwise ``fmt``/plain.
        candidates: candidate ``DispatchKey``s when tuning.
        dtype: container value dtype.
        fmt: local and remote format of the untuned level operators.

    Returns:
        A ``VCycle`` whose ``__call__`` maps sharded residuals to sharded
        corrections — it drops into ``pcg_solve``/``cg`` unchanged.
    """
    from repro.core.convert import _as_scipy
    from repro.distributed_op import DistributedOperator

    nparts = int(mesh.shape[axis])
    levels = []
    for l in vc.levels:
        s = _as_scipy(l.A)
        if s.shape[0] % nparts:
            raise ValueError(
                f"level {l.grid} has {s.shape[0]} rows, not divisible by "
                f"{nparts} parts — clamp depth with distributable_depth()")
        A_d = DistributedOperator.build(s, mesh, axis, local=fmt,
                                        remote=fmt, mode="auto", dtype=dtype)
        if tune:
            A_d = A_d.tune(candidates)
        R_d = P_d = None
        if l.R is not None:
            R_d = DistributedOperator.build(_as_scipy(l.R), mesh, axis,
                                            local="csr", remote="csr",
                                            mode="auto", dtype=dtype)
            P_d = DistributedOperator.build(_as_scipy(l.P), mesh, axis,
                                            local="csr", remote="csr",
                                            mode="auto", dtype=dtype)
        levels.append(MGLevel(l.grid, A_d, l.smoother.distribute(A_d),
                              R_d, P_d))
    return VCycle(tuple(levels), vc.pre, vc.post, vc.coarse_sweeps)


def build_mg(nx: int, ny: int, nz: int, *, depth: int = 4, pre: int = 1,
             post: int = 1, coarse_sweeps: int = 4, fmt: str = "csr",
             method: str = "multicolor", tune: bool = False,
             candidates=None, dtype=jnp.float32) -> VCycle:
    """Build the HPCG multigrid hierarchy for an (nx, ny, nz) stencil grid.

    ``depth`` caps the number of levels; coarsening stops early when a dim
    goes odd or too small. ``tune=True`` runs the run-first auto-tuner on
    every level's re-discretised matrix and installs the winning
    (format, backend) operator — the per-level format choice of Table III
    (equivalent to ``build_mg(...).retuned(candidates)``, which is the cheap
    way to derive a tuned hierarchy from an already-built one: schedules and
    transfer operators are shared, not rebuilt).
    ``fmt`` is the (reference) format when not tuning.
    """
    levels = []
    grid = (nx, ny, nz)
    with obs.span("mg.build", grid=grid, depth=depth, fmt=fmt):
        for li in range(depth):
            with obs.span("mg.level", level=li, grid=grid):
                A_sp = M.fdm27(*grid)
                op = as_operator(A_sp, fmt).using("plain")
                smoother = SymGS.build(A_sp, operator=op, method=method,
                                       dtype=dtype)
                last = li == depth - 1 or not coarsenable(grid)
                R = P = None
                if not last:
                    R, P = injection_operators(*grid, dtype=dtype)
                levels.append(MGLevel(grid, op, smoother, R, P))
            if last:
                break
            grid = tuple(d // 2 for d in grid)
        vc = VCycle(tuple(levels), pre=pre, post=post,
                    coarse_sweeps=coarse_sweeps)
        return vc.retuned(candidates) if tune else vc
