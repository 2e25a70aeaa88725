"""``DistributedOperator`` — a row-sharded sparse operator over a device mesh.

This is the distribution layer of the three-layer stack (see
``docs/architecture.md``): it shards a sparse matrix row-wise across a 1-D
mesh axis and runs SpMV the way the Morpheus-enabled HPCG does (paper
§VII-D) — each rank's rows are *physically split* into a structured
**local** block (the columns the rank owns) and an unstructured **remote**
block (halo columns), and the SpMV is

    1. issue the halo exchange of the remote x entries   (ppermute/all_gather)
    2. local-part SpMV against the rank's own x shard    (no communication)
    3. remote-part SpMV against the gathered halo window

The exchange is issued *before* the local SpMV in the traced graph and has
no data dependency on it, so XLA's latency-hiding scheduler can overlap the
collective with the local compute — the analogue of HPCG's MPI_Irecv /
compute / MPI_Wait overlap.

Per-rank format choices (Table III: the run-first tuner lands on different
formats per process) are SPMD-compatible via **format groups**: ranks that
picked the same ``DispatchKey(format, backend)`` share one stacked
container; ranks outside a group hold an empty (all-padding) part in it, so
every device runs the same program and a rank's rows are only ever produced
by its own group. With a homogeneous choice there is exactly one group and
zero overhead. Every per-shard kernel goes through the same
``DispatchKey`` dispatch table as single-device SpMV (``core/spmv.py``).

Modes:
  - ``"auto"``      : halo (ppermute) exchange when a finite halo covers all
                      remote entries, else allgather.
  - ``"halo"``      : require the finite-halo neighbour exchange.
  - ``"allgather"`` : force global-coordinate remotes + ``all_gather`` of x.
  - ``"rowblock"``  : no column split — each rank keeps its full ``(mr, nc)``
                      row block and multiplies against the allgathered x.
                      Every row accumulates in exactly the global CSR entry
                      order, so csr/plain results are **bit-for-bit**
                      identical to the single-device kernel: the validation
                      mode of the distributed HPCG pipeline.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from functools import partial
from typing import Any, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from repro.core import health as _health
from repro.core.convert import _as_scipy
from repro.core.distributed import (
    _take_part,
    build_stacked,
    split_local_remote,
    split_rowblocks,
)
from repro.core.operator import DEFAULT_POLICY, ExecutionPolicy
from repro.core.spmv import DispatchKey, masked_spmv, spmv

#: Formats whose containers can be padded to a common shape and stacked on a
#: leading parts axis (the shard_map layout). SELL's per-slice ragged layout
#: and BSR's block grid don't stack without format-specific padding rules.
STACKABLE_FORMATS = ("coo", "csr", "dia", "ell")

KeyLike = Union[str, Tuple[str, str], DispatchKey]


def as_dispatch_key(k: KeyLike) -> DispatchKey:
    """Normalise a format name / ``(fmt, backend)`` pair / ``DispatchKey``.

    >>> as_dispatch_key("dia")
    DispatchKey(format='dia', backend='plain')
    >>> as_dispatch_key(("ell", "pallas"))
    DispatchKey(format='ell', backend='pallas')
    """
    if isinstance(k, DispatchKey):
        return k
    if isinstance(k, str):
        return DispatchKey(k, "plain")
    fmt, backend = k
    return DispatchKey(fmt, backend)


def _maybe_drop_halo(xr):
    """Fault-injection site "halo": an armed plan may zero the exchanged
    window (a dropped neighbour message) so tests can prove the distributed
    result goes detectably wrong rather than silently so. One ``None`` check
    when no plan is armed."""
    plan = _health.fault_plan()
    if plan is None:
        return xr
    return plan.drop("halo", None, xr)


def _per_part_keys(spec, nparts: int) -> Tuple[DispatchKey, ...]:
    """Broadcast a single choice, or validate a per-part sequence.

    A bare ``"csr"``, a ``DispatchKey``, or a 2-tuple of strings (read as a
    ``(format, backend)`` pair) applies to every part; any other sequence is
    one choice per part and must have length ``nparts``.
    """
    if isinstance(spec, (str, DispatchKey)) or (
            isinstance(spec, tuple) and len(spec) == 2
            and all(isinstance(e, str) for e in spec)):
        return (as_dispatch_key(spec),) * nparts
    keys = tuple(as_dispatch_key(k) for k in spec)
    if len(keys) != nparts:
        raise ValueError(f"need one format choice per part: got {len(keys)} "
                         f"for {nparts} parts")
    return keys


@partial(jax.tree_util.register_dataclass, data_fields=["container"],
         meta_fields=["key", "members"])
@dataclass(frozen=True)
class FormatGroup:
    """Ranks sharing one (format, backend) choice + their stacked container.

    ``container`` leaves have a leading parts axis; parts outside ``members``
    hold an empty (all-padding) matrix, contributing exact zeros.
    """

    key: DispatchKey
    container: Any
    members: Tuple[int, ...]

    def policy(self, base: Optional[ExecutionPolicy]) -> ExecutionPolicy:
        return (base if base is not None else DEFAULT_POLICY).preferring(
            self.key.backend)


def _build_groups(mats: Sequence[sp.spmatrix], keys: Sequence[DispatchKey],
                  dtype) -> Tuple[FormatGroup, ...]:
    """Group per-part matrices by dispatch key and stack each group.

    Groups whose member matrices are all empty are dropped entirely (their
    rows contribute exact zeros) — e.g. the remote groups of a matrix with
    no off-partition entries, which then skips the halo exchange too.
    """
    for key in keys:
        if key.format not in STACKABLE_FORMATS:
            raise ValueError(
                f"distributed containers must be one of {STACKABLE_FORMATS}, "
                f"got {key.format!r} (sell/bsr do not stack across parts)")
    groups: List[FormatGroup] = []
    seen: List[DispatchKey] = []
    for key in keys:
        if key in seen:
            continue
        seen.append(key)
        members = tuple(p for p, k in enumerate(keys)
                        if k == key and mats[p].nnz > 0)
        if not members:
            continue
        sel = [mats[p] if keys[p] == key else sp.csr_matrix(mats[p].shape)
               for p in range(len(mats))]
        groups.append(FormatGroup(key, build_stacked(sel, key.format, dtype),
                                  members))
    return tuple(groups)


@dataclass(frozen=True)
class DistributedOperator:
    """Row-sharded sparse linear operator: ``A @ x`` under ``shard_map``.

    Built with :meth:`build` (or the :func:`distribute` convenience). Like
    ``SparseOperator`` it is a pytree whose leaves are the stacked
    containers, so a jitted solver takes it as an argument
    (``jax.jit(lambda op, b: cg(op, b, ...))(op, b)``); the host-side
    ``source`` matrix does not survive flattening.

    Attributes:
        mesh / axis: the 1-D device axis rows are sharded over.
        shape: global ``(nr, nc)``.
        halo: window half-width of the neighbour exchange, or ``None`` when
            remote columns are gathered with ``all_gather``.
        mode: ``"split"`` (local/remote) or ``"rowblock"`` (exact, see
            module docstring).
        local_groups / remote_groups: :class:`FormatGroup` stacks; remote is
            empty in rowblock mode or when no entries leave the partition.
        choices: per-rank ``(local_key, remote_key)`` dispatch choices.
        base_policy: optional ``ExecutionPolicy`` whose limits every group's
            kernel runs under (the backend preference comes from the group).
    """

    mesh: Mesh
    axis: str
    shape: Tuple[int, int]
    dtype: Any
    halo: Optional[int]
    mode: str
    local_groups: Tuple[FormatGroup, ...]
    remote_groups: Tuple[FormatGroup, ...]
    choices: Tuple[Tuple[DispatchKey, Optional[DispatchKey]], ...]
    base_policy: Optional[ExecutionPolicy] = None
    source: Any = field(default=None, repr=False, compare=False)

    # -- construction -------------------------------------------------------

    @classmethod
    def build(cls, a, mesh: Mesh, axis: str = "data",
              local: KeyLike = "csr", remote: KeyLike = "coo",
              mode: str = "auto", policy: Optional[ExecutionPolicy] = None,
              dtype=jnp.float32) -> "DistributedOperator":
        """Shard ``a`` row-wise over ``mesh[axis]`` with a local/remote split.

        Args:
            a: anything ``as_operator`` accepts — scipy sparse, dense,
                a registered container, or a ``SparseOperator``.
            mesh / axis: 1-D device axis to shard rows (and x) over. Both
                matrix dims must be divisible by ``mesh.shape[axis]``.
            local / remote: per-rank kernel choice for the local and remote
                parts — a format name (backend ``plain``), a
                ``(format, backend)`` pair / ``DispatchKey``, or a sequence
                of one choice per rank (Table III heterogeneous tuning).
            mode: ``"auto" | "halo" | "allgather" | "rowblock"`` (see module
                docstring). ``remote`` is ignored in rowblock mode.
            policy: optional base ``ExecutionPolicy``; each group's backend
                preference is layered on top of it.
            dtype: value dtype of the device containers.

        Returns:
            A ``DistributedOperator`` whose ``op @ x`` takes and returns
            arrays sharded with ``op.sharding()``.
        """
        s = _as_scipy(a).tocsr()
        nparts = int(mesh.shape[axis])
        nr, nc = s.shape
        if nr % nparts or nc % nparts:
            raise ValueError(f"matrix dims {s.shape} must be divisible by "
                             f"the mesh axis {axis!r} of size {nparts} "
                             f"(pad upstream)")
        if mode == "rowblock":
            blocks = split_rowblocks(s, nparts)
            lkeys = _per_part_keys(local, nparts)
            groups = _build_groups(blocks, lkeys, dtype)
            return cls(mesh, axis, (nr, nc), jnp.dtype(dtype), None,
                       "rowblock", groups, (),
                       tuple((k, None) for k in lkeys), policy, s)
        if mode not in ("auto", "halo", "allgather"):
            raise ValueError(f"unknown mode {mode!r}")
        locals_, remotes, halo = split_local_remote(
            s, nparts, halo=None if mode == "allgather" else "auto")
        if mode == "halo" and halo is None:
            raise ValueError("mode='halo': no finite halo covers the remote "
                             "entries; use 'allgather' (or 'auto')")
        lkeys = _per_part_keys(local, nparts)
        rkeys = _per_part_keys(remote, nparts)
        return cls(mesh, axis, (nr, nc), jnp.dtype(dtype), halo, "split",
                   _build_groups(locals_, lkeys, dtype),
                   _build_groups(remotes, rkeys, dtype),
                   tuple(zip(lkeys, rkeys)), policy, s)

    # -- introspection ------------------------------------------------------

    @property
    def nparts(self) -> int:
        return int(self.mesh.shape[self.axis])

    @property
    def format(self) -> str:
        """Summary tag, e.g. ``'dist(dia+coo)'`` — per-rank detail is in
        :meth:`describe`."""
        lf = "|".join(sorted({g.key.format for g in self.local_groups}) or ["-"])
        if self.mode == "rowblock":
            return f"dist[{lf}]"
        rf = "|".join(sorted({g.key.format for g in self.remote_groups}) or ["-"])
        return f"dist({lf}+{rf})"

    @property
    def policy(self) -> Optional[ExecutionPolicy]:
        return self.base_policy

    @property
    def nbytes(self) -> int:
        """Total device bytes of every group's stacked container."""
        return sum(int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
                   for g in self.local_groups + self.remote_groups
                   for l in jax.tree_util.tree_leaves(g.container))

    def describe(self) -> str:
        """Per-rank choices, e.g. ``'p0:dia+coo p1:csr+coo'``."""
        out = []
        for p, (lk, rk) in enumerate(self.choices):
            tag = f"{lk.format}/{lk.backend}"
            if rk is not None:
                tag += f"+{rk.format}/{rk.backend}"
            out.append(f"p{p}:{tag}")
        return " ".join(out)

    def __repr__(self):
        return (f"DistributedOperator(shape={self.shape}, mode={self.mode!r}, "
                f"nparts={self.nparts}, halo={self.halo}, "
                f"format={self.format!r})")

    # -- placement ----------------------------------------------------------

    def sharding(self) -> NamedSharding:
        """The 1-D vector sharding this operator consumes and produces
        (x shards over the column partition, y over the row partition —
        the same ``PartitionSpec`` on this operator's axis)."""
        return NamedSharding(self.mesh, P(self.axis))

    def device_put(self, x) -> jnp.ndarray:
        """Place a host vector with this operator's input sharding."""
        return jax.device_put(jnp.asarray(x, self.dtype), self.sharding())

    # -- application --------------------------------------------------------

    def __matmul__(self, x):
        x = jnp.asarray(x)
        if x.ndim != 1:
            raise ValueError(
                f"DistributedOperator @ ndim={x.ndim}: only SpMV (1-D x) is "
                f"distributed; vmap over columns for SpMM")
        if x.shape[0] != self.shape[1]:
            raise ValueError(f"shape mismatch: {self.shape} @ {x.shape}")
        return self._apply(x, None)

    def matvec(self, x) -> jnp.ndarray:
        """``A @ x`` — sharded in, sharded out."""
        return self @ x

    def masked_matvec(self, x, row_mask) -> jnp.ndarray:
        """``where(row_mask, A @ x, 0)`` — one color of a distributed
        multicolor SymGS sweep. ``row_mask`` is a global ``(nr,)`` bool
        array, sharded like the output rows."""
        return self._apply(jnp.asarray(x), jnp.asarray(row_mask))

    def _apply(self, x, mask):
        spec = P(self.axis)
        lc = tuple(g.container for g in self.local_groups)
        rc = tuple(g.container for g in self.remote_groups)
        if mask is None:
            fn = shard_map(partial(self._shard_fn, None), mesh=self.mesh,
                           in_specs=(spec, spec, spec), out_specs=spec,
                           check_vma=False)
            return fn(lc, rc, x)
        fn = shard_map(self._shard_fn, mesh=self.mesh,
                       in_specs=(spec, spec, spec, spec), out_specs=spec,
                       check_vma=False)
        return fn(mask, lc, rc, x)

    # the per-shard program: local SpMV overlapped with the halo exchange
    def _shard_fn(self, mask, lc, rc, x):
        # 1) issue the gather first: it has no dependency on the local SpMV,
        #    so the collective can overlap with the local compute.
        xr = None
        if self.mode == "rowblock":
            xr = jax.lax.all_gather(x, self.axis, tiled=True)
        elif rc:
            xr = self._exchange(x)
        if xr is not None:
            xr = _maybe_drop_halo(xr)
        # 2) local contribution (each rank's own x shard, or the gathered x
        #    in rowblock mode)
        mr = self.shape[0] // self.nparts
        y = jnp.zeros((mr,), self.dtype)
        xl = xr if self.mode == "rowblock" else x
        for g, c in zip(self.local_groups, lc):
            y = y + self._group_spmv(g, _take_part(c), xl, mask)
        # 3) remote contribution against the exchanged window
        for g, c in zip(self.remote_groups, rc):
            y = y + self._group_spmv(g, _take_part(c), xr, mask)
        return y

    def _group_spmv(self, g: FormatGroup, A, x, mask):
        pol = g.policy(self.base_policy)
        if mask is None:
            return spmv(A, x, policy=pol)
        return masked_spmv(A, x, mask, policy=pol)

    def _exchange(self, x):
        """Gather the remote x entries: nearest-neighbour ``ppermute`` of
        the ``halo`` boundary slices (HPCG's exchange), or ``all_gather``
        when no finite halo covers the remote columns."""
        if self.halo is None:
            return jax.lax.all_gather(x, self.axis, tiled=True)
        h, m, nparts = self.halo, x.shape[0], self.nparts
        if h == 0:
            return x
        if nparts == 1:
            z = jnp.zeros((h,), x.dtype)
            return jnp.concatenate([z, x, z])
        lo = jax.lax.ppermute(  # my window's low side: left neighbour's tail
            x[m - h:], self.axis, [(i, (i + 1) % nparts) for i in range(nparts)])
        hi = jax.lax.ppermute(  # high side: right neighbour's head
            x[:h], self.axis, [(i, (i - 1) % nparts) for i in range(nparts)])
        idx = jax.lax.axis_index(self.axis)
        lo = jnp.where(idx == 0, 0, lo)            # non-periodic boundaries
        hi = jnp.where(idx == nparts - 1, 0, hi)
        return jnp.concatenate([lo, x, hi])

    # -- retargeting --------------------------------------------------------

    def with_policy(self, policy: Optional[ExecutionPolicy]) -> "DistributedOperator":
        """Same containers, different base ``ExecutionPolicy`` limits."""
        return replace(self, base_policy=policy)

    def tune(self, candidates=None, mode: Optional[str] = None,
             **kw) -> "DistributedOperator":
        """Per-partition run-first auto-tune (paper §VII-D, Table III).

        Each rank's local and remote part is tuned *independently* over
        ``candidates`` (default: the plain stackable formats) and the
        operator is rebuilt with the per-rank winners — ranks that pick
        different formats land in different :class:`FormatGroup`s.

        Returns the retuned operator; the timing tables are available via
        :func:`repro.distributed_op.tune_partitions`.

        Raises:
            ValueError: on a ``rowblock``-mode operator — rowblock exists
                for its bit-for-bit accumulation order, which any tuned
                local/remote split would discard; build a split-mode
                operator (``mode="auto"``) to tune instead.
        """
        from .tune import tune_partitions

        if self.mode == "rowblock":
            raise ValueError(
                "refusing to tune a rowblock (exact validation) operator: "
                "the tuned local/remote split changes the per-row "
                "accumulation order and loses the bit-for-bit guarantee; "
                "build with mode='auto' (or call tune_partitions) instead")
        if self.source is None:
            raise ValueError("operator was built without a host-side source "
                             "matrix; re-tune via tune_partitions(s, mesh)")
        op, _ = tune_partitions(
            self.source, self.mesh, self.axis, candidates=candidates,
            mode=mode if mode is not None else
            ("allgather" if self.halo is None else "auto"),
            policy=self.base_policy, dtype=self.dtype, **kw)
        return op


jax.tree_util.register_pytree_node(
    DistributedOperator,
    lambda op: ((op.local_groups, op.remote_groups),
                (op.mesh, op.axis, op.shape, op.dtype, op.halo, op.mode,
                 op.choices, op.base_policy)),
    lambda aux, groups: DistributedOperator(*aux[:6], *groups, *aux[6:]),
)


def distribute(a, mesh: Mesh, axis: str = "data", **kw) -> DistributedOperator:
    """Convenience alias for :meth:`DistributedOperator.build`."""
    return DistributedOperator.build(a, mesh, axis, **kw)
