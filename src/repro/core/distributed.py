"""Distributed SpMV with local/remote format split (paper §VII-D, Table III).

The paper's distributed HPCG partitions matrix rows across MPI ranks and
*physically splits* each rank's rows into a structured **local** block
(columns the rank owns) and an unstructured **remote** block (halo columns),
choosing a storage format for each independently via the run-first
auto-tuner — landing on DIA(local) + COO(remote) for the SVE version.

JAX mapping (per the brief: jax-native collectives, not MPI emulation):

  - row partition  -> 1-D device axis, containers stacked on a parts axis and
                      consumed under ``shard_map``
  - MPI halo recv  -> ``neighbor`` mode: ``lax.ppermute`` of boundary slices
                      (faithful to HPCG's nearest-neighbour exchange), or
    MPI allgather  -> ``allgather`` mode: ``lax.all_gather`` of x (general
                      matrices whose remote columns are not halo-local)
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from .convert import to_coo, to_csr, to_dia, to_ell
from .operator import ExecutionPolicy, policy_for_impl
from .spmv import spmv


# ------------------------------------------------------------ splitting ----

def partition_rows(n: int, nparts: int, even: bool = True) -> List[Tuple[int, int]]:
    """Contiguous row ranges ``[(r0, r1), ...]`` assigning ``n`` rows to
    ``nparts`` parts.

    Args:
        n: total number of rows (>= 0).
        nparts: number of partitions (> 0).
        even: with the default ``True``, every part must get exactly
            ``n // nparts`` rows — the stacked-container layout shard_map
            consumes requires equal shards — and a non-dividing ``n`` raises
            ``ValueError`` (pad upstream, or pass ``even=False``). With
            ``even=False`` the split is HPCG-style balanced: the first
            ``n % nparts`` parts get one extra row, and parts beyond ``n``
            rows come back empty (``r0 == r1``), so ``nparts > n`` is legal.

    Returns:
        A list of ``nparts`` half-open ``(r0, r1)`` ranges covering ``[0, n)``
        in order.

    Example:
        >>> partition_rows(8, 4)
        [(0, 2), (2, 4), (4, 6), (6, 8)]
        >>> partition_rows(7, 3, even=False)
        [(0, 3), (3, 5), (5, 7)]
    """
    if nparts <= 0:
        raise ValueError(f"nparts must be positive, got {nparts}")
    if n < 0:
        raise ValueError(f"row count must be non-negative, got {n}")
    if even:
        if n % nparts != 0:
            raise ValueError(
                f"rows {n} must be divisible by {nparts} parts for an even "
                f"partition (pad upstream, or pass even=False for a "
                f"balanced one)")
        m = n // nparts
        return [(p * m, (p + 1) * m) for p in range(nparts)]
    base, extra = divmod(n, nparts)
    bounds = [0]
    for p in range(nparts):
        bounds.append(bounds[-1] + base + (1 if p < extra else 0))
    return [(bounds[p], bounds[p + 1]) for p in range(nparts)]


def split_local_remote(s: sp.spmatrix, nparts: int, halo="auto"):
    """Split ``s`` into per-part **local** (own columns) and **remote**
    matrices — the physical split of the paper's distributed HPCG (§VII-D).

    Rows are partitioned evenly into ``nparts`` blocks of ``mr`` rows;
    columns into blocks of ``mc`` (for the square matrices of SpMV
    ``mr == mc``; rectangular matrices such as multigrid restriction /
    prolongation maps are partitioned along both axes independently, so
    both dims must be divisible by ``nparts``). Part ``p``'s local matrix
    is its
    ``(mr, mc)`` own-column block; everything else lands in its remote
    matrix.

    Args:
        s: scipy sparse matrix, ``(nr, nc)`` with ``nr % nparts == 0`` and
            ``nc % nparts == 0``.
        nparts: number of row partitions.
        halo: ``"auto"`` measures the maximum column reach of any remote
            entry and uses window coordinates when a finite halo covers it;
            ``None`` forces global-coordinate remotes (the allgather path);
            an ``int`` forces that window half-width.

    Returns:
        ``(locals, remotes, halo)``. ``locals[p]`` is ``(mr, mc)``. When the
        returned ``halo`` is an int, ``remotes[p]`` is ``(mr, mc + 2*halo)``
        in *window* coordinates — part ``p``'s own column range extended by
        ``halo`` on both sides, own columns zeroed — ready for a
        nearest-neighbour ``ppermute`` exchange. When it is ``None``,
        ``remotes[p]`` is ``(mr, nc)`` in global coordinates for use with
        ``all_gather``.
    """
    s = s.tocsr()
    nr, nc = s.shape
    parts = partition_rows(nr, nparts)
    cparts = partition_rows(nc, nparts)
    mc = nc // nparts

    coo = s.tocoo()
    max_reach = 0
    for (r0, r1), (c0, c1) in zip(parts, cparts):
        sel = (coo.row >= r0) & (coo.row < r1)
        if not sel.any():
            continue
        reach = np.abs(coo.col[sel] - np.clip(coo.col[sel], c0, c1 - 1)).max()
        max_reach = max(max_reach, int(reach))
    if halo == "auto":
        halo = max_reach if max_reach <= mc else None

    locals_, remotes = [], []
    for (r0, r1), (c0, c1) in zip(parts, cparts):
        mr = r1 - r0
        blk = s[r0:r1]
        local = blk[:, c0:c1].tocsr()
        # remote: the nonzero entries outside the own columns, selected
        # entry-wise (zeroing a column range of a LIL matrix builds dense
        # (mr, mc) index arrays, hundreds of GB at HPCG's grid sizes)
        rc = blk.tocoo()
        off = ((rc.col < c0) | (rc.col >= c1)) & (rc.data != 0)
        rows, cols, width = rc.row[off], rc.col[off], nc
        if halo is not None:
            cols, width = cols - (c0 - halo), mc + 2 * halo
            assert ((cols >= 0) & (cols < width)).all(), \
                "halo window does not cover remote entries"
        remotes.append(sp.csr_matrix((rc.data[off], (rows, cols)),
                                     shape=(mr, width), dtype=s.dtype))
        locals_.append(local)
    return locals_, remotes, halo


def split_rowblocks(s: sp.spmatrix, nparts: int) -> List[sp.csr_matrix]:
    """Per-part full row blocks ``s[r0:r1, :]`` — **no** column split.

    The exact-arithmetic layout: every row keeps all its entries in the
    global CSR order, so a per-part plain-CSR SpMV against the allgathered
    ``x`` accumulates each row in exactly the same order as the
    single-device kernel — the bit-for-bit validation mode of the
    distributed pipeline (``DistributedOperator`` ``mode="rowblock"``).
    """
    s = s.tocsr()
    return [s[r0:r1] for r0, r1 in partition_rows(s.shape[0], nparts)]


# ------------------------------------------------------- container stack ----

def build_stacked(mats: Sequence[sp.spmatrix], fmt: str, dtype=jnp.float32):
    """Convert each part to ``fmt`` with common padded sizes, stack leaves.

    Column-tile ``KernelPlan``s are disabled (``col_tile=False``): per-part
    plan arrays have data-dependent shapes that do not stack, so a per-rank
    ``(fmt, "pallas")`` choice that needs one falls back down the group's
    policy chain instead (see docs/architecture.md).
    """
    mats = [m.tocsr() for m in mats]
    if fmt == "coo":
        nnz = max(1, max(int(m.nnz) for m in mats))
        cs = [to_coo(m, dtype=dtype, pad_to=None, col_tile=False) for m in mats]
        # no row pointer: the padded parts must share one structure to stack
        cs = [_pad_coo(dataclasses.replace(c, rowptr=None), nnz) for c in cs]
    elif fmt == "csr":
        nnz = max(1, max(int(m.nnz) for m in mats))
        cs = [_pad_csr(to_csr(m, dtype=dtype, plan=False), nnz) for m in mats]
    elif fmt == "dia":
        from repro.kernels.dia_spmv import dia_lanes

        cs = [to_dia(m, dtype=dtype, col_tile=False) for m in mats]
        nd = max(c.ndiags for c in cs)
        # extent is static aux data: parts must share one value to stack, and
        # the max across parts is a valid (if loose) bound for each
        ext = max((c.extent or 0) for c in cs)
        # the resident kernel's value layout stacks too, from the padded
        # values, when every part's band lets x stay resident
        lanes = all(c.lanes is not None for c in cs)
        cs = [_pad_dia(c, nd) for c in cs]
        cs = [dataclasses.replace(
            c, extent=ext, lanes=dia_lanes(c.data) if lanes else None)
            for c in cs]
    elif fmt == "ell":
        w = max(1, max(int(np.diff(m.indptr).max() if m.nnz else 1) for m in mats))
        cs = [to_ell(m, dtype=dtype, width=w, col_tile=False) for m in mats]
    else:
        raise ValueError(f"unsupported distributed format {fmt!r}")
    return jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *cs)


def _pad_coo(c, nnz):
    from .formats import COO
    pad = nnz - c.row.shape[0]
    if pad <= 0:
        return c
    return COO(
        jnp.concatenate([c.row, jnp.full((pad,), c.shape[0], jnp.int32)]),
        jnp.concatenate([c.col, jnp.zeros((pad,), jnp.int32)]),
        jnp.concatenate([c.val, jnp.zeros((pad,), c.val.dtype)]),
        c.shape,
    )


def _pad_csr(c, nnz):
    from .formats import CSR
    pad = nnz - c.data.shape[0]
    if pad <= 0:
        return c
    return CSR(
        c.indptr,
        jnp.concatenate([c.indices, jnp.zeros((pad,), jnp.int32)]),
        jnp.concatenate([c.data, jnp.zeros((pad,), c.data.dtype)]),
        c.shape,
    )


def _pad_dia(c, nd):
    from .formats import DIA
    pad = nd - c.ndiags
    if pad <= 0:
        return c
    return DIA(
        jnp.concatenate([c.offsets, jnp.zeros((pad,), jnp.int32)]),
        jnp.concatenate([c.data, jnp.zeros((pad, c.data.shape[1]), c.data.dtype)]),
        c.shape,
    )


def _take_part(c):
    return jax.tree_util.tree_map(lambda l: l[0], c)


# --------------------------------------------------------------- operator ----

@dataclass
class DistributedSpMV:
    """y = A @ x over a 1-D mesh axis with split local/remote formats.

    ``local_fmt``/``remote_fmt`` default to the paper's SVE-version winners
    (Table III): DIA local, COO remote. ``impl`` maps to the kernel version
    ('plain' | 'pallas'); ``policy`` overrides it with a full ExecutionPolicy.
    """

    mesh: Mesh
    axis: str
    local: object       # stacked container, leading dim = nparts
    remote: object
    halo: Optional[int]
    n: int
    local_fmt: str
    remote_fmt: str
    impl: str = "plain"
    policy: Optional[ExecutionPolicy] = None

    def execution_policy(self) -> ExecutionPolicy:
        return self.policy if self.policy is not None else policy_for_impl(self.impl)

    @classmethod
    def build(cls, s: sp.spmatrix, mesh: Mesh, axis: str = "data",
              local_fmt: str = "dia", remote_fmt: str = "coo",
              impl: str = "plain", dtype=jnp.float32, mode: str = "auto",
              policy: Optional[ExecutionPolicy] = None):
        nparts = mesh.shape[axis]
        locals_, remotes, halo = split_local_remote(
            s, nparts, halo=None if mode == "allgather" else "auto")
        lc = build_stacked(locals_, local_fmt, dtype)
        rc = build_stacked(remotes, remote_fmt, dtype)
        return cls(mesh, axis, lc, rc, halo, s.shape[0], local_fmt, remote_fmt,
                   impl, policy)

    @property
    def nparts(self) -> int:
        return self.mesh.shape[self.axis]

    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        spec = P(self.axis)
        fn = shard_map(
            self._shard_fn, mesh=self.mesh,
            in_specs=(spec, spec, spec), out_specs=spec, check_vma=False,
        )
        return fn(self.local, self.remote, x)

    def sharding(self):
        return NamedSharding(self.mesh, P(self.axis))

    def _shard_fn(self, local, remote, x):
        pol = self.execution_policy()
        local, remote = _take_part(local), _take_part(remote)
        y = spmv(local, x, policy=pol)
        if self.halo is None:
            xg = jax.lax.all_gather(x, self.axis, tiled=True)
            return y + spmv(remote, xg, policy=pol)
        h = self.halo
        m = x.shape[0]
        nparts = self.nparts
        if nparts == 1:
            xw = jnp.concatenate([jnp.zeros((h,), x.dtype), x, jnp.zeros((h,), x.dtype)])
        else:
            right = jax.lax.ppermute(  # my left boundary, sent rightwards
                x[m - h:], self.axis, [(i, (i + 1) % nparts) for i in range(nparts)])
            left = jax.lax.ppermute(
                x[:h], self.axis, [(i, (i - 1) % nparts) for i in range(nparts)])
            idx = jax.lax.axis_index(self.axis)
            right = jnp.where(idx == 0, 0, right)          # zero Dirichlet edges
            left = jnp.where(idx == nparts - 1, 0, left)
            xw = jnp.concatenate([right, x, left])
        return y + spmv(remote, xw, policy=pol)


def autotune_distributed(s: sp.spmatrix, mesh: Mesh, axis: str = "data",
                         candidates=(("dia", "coo"), ("csr", "csr"),
                                     ("csr", "coo"), ("ell", "coo")),
                         impl: str = "plain", iters: int = 5):
    """Run-first tuner over (local_fmt, remote_fmt) pairs (Table III)."""
    import time

    n = s.shape[0]
    x = jax.device_put(
        np.random.default_rng(0).standard_normal(n).astype(np.float32),
        NamedSharding(mesh, P(axis)))
    best, best_t, table = None, float("inf"), {}
    for lf, rf in candidates:
        try:
            op = DistributedSpMV.build(s, mesh, axis, lf, rf, impl)
        except Exception as e:
            table[(lf, rf)] = f"build failed: {type(e).__name__}"
            continue
        jax.block_until_ready(op(x))
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter_ns()
            jax.block_until_ready(op(x))
            ts.append(time.perf_counter_ns() - t0)
        t = float(np.median(ts)) / 1e3
        table[(lf, rf)] = t
        if t < best_t:
            best, best_t = op, t
    return best, table
