"""Sparse matrix storage formats as JAX pytrees.

Morpheus's containers (CooMatrix / CsrMatrix / DiaMatrix) map here to frozen
dataclasses registered as pytrees, so a sparse matrix can flow through jit /
shard_map / scan like any other JAX value while its *format* stays static
(a compile-time property, exactly like Morpheus's compile-time dispatch).

All formats carry ``shape`` (static aux data) and expose:
  - ``format``      : static str tag used by the dispatch registry
  - ``nnz``         : stored entries (padded entries included where relevant)
  - ``to_dense()``  : densify (reference semantics for every test oracle)

Container-level index dtype is int32 (the paper uses 32-bit indices on the
FPGA path as well); the *tile-local* column indices inside a container's
:class:`KernelPlan` may be compressed to int16/int8 when the column-tile
width bounds their range (``core.tiling.local_index_dtype``). Value dtype is
any float dtype, fp32 by default; bf16/fp16 storage accumulates in fp32
inside every kernel.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, ClassVar, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Shape = Tuple[int, int]

_REGISTERED_FORMATS: dict = {}


@dataclass(frozen=True)
class KernelPlan:
    """A precomputed Pallas execution layout attached to a container.

    Built host-side at convert time (``core.tiling``), carried as an optional
    ``plan`` leaf on the container so tiled/streamed kernels stay jit-safe:
    ``arrays`` are ordinary pytree leaves (dense per-column-tile index/data
    panels, scalar-prefetch steering arrays), while ``kind`` and the ``meta``
    geometry tuple are static aux data the ``supports(A, policy)`` predicates
    can test under trace.

    Kinds (array/meta layouts are documented on their builders in
    ``core.tiling``): ``"ell-cols"``, ``"dia-cols"``, ``"coo-cols"``,
    ``"scs"`` (the SELL-C-σ stream shared by the csr and sell kernels).
    ``meta[0]`` is always the column-tile width ``ct``.
    """

    kind: str
    arrays: Tuple[Any, ...]
    meta: Tuple[int, ...]

    @property
    def ct(self) -> int:
        return int(self.meta[0])

    @property
    def ntiles(self) -> int:
        return int(self.meta[1])

    def jaxify(self) -> "KernelPlan":
        """Numpy-built arrays moved to device, dtypes preserved — including
        int16/int8 tile-local index arrays from compressed plans."""
        return KernelPlan(self.kind, tuple(jnp.asarray(a) for a in self.arrays),
                          self.meta)

    def index_dtype(self):
        """Dtype of the plan's tile-local column-index array, or None for
        kinds without per-entry indices ("dia-cols")."""
        pos = {"ell-cols": 0, "coo-cols": 1, "scs": 3}.get(self.kind)
        return None if pos is None else jnp.dtype(self.arrays[pos].dtype)


def segment_ids(bounds: jnp.ndarray, n: int) -> jnp.ndarray:
    """Segment of each position ``0..n-1`` given sorted segment starts
    ``bounds`` (``bounds[0] == 0``): ``searchsorted(bounds, arange(n),
    side="right") - 1``, with positions past ``bounds[-1]`` in segment
    ``len(bounds) - 1``. Computed as a scatter of one mark per segment end
    and a running sum: a binary search per position takes seconds on a TPU
    at HPCG's 30M entries, this takes milliseconds."""
    marks = jnp.zeros((n + 1,), jnp.int32).at[bounds[1:]].add(1)
    return jnp.cumsum(marks, dtype=jnp.int32)[:n]


jax.tree_util.register_pytree_node(
    KernelPlan,
    lambda p: (p.arrays, (p.kind, p.meta)),
    lambda aux, leaves: KernelPlan(aux[0], tuple(leaves), aux[1]),
)


def _register(cls):
    """Register a sparse container class as a JAX pytree node."""
    fields = [f.name for f in dataclasses.fields(cls) if f.metadata.get("leaf", True)]
    aux_fields = [f.name for f in dataclasses.fields(cls) if not f.metadata.get("leaf", True)]

    def flatten(obj):
        return tuple(getattr(obj, n) for n in fields), tuple(getattr(obj, n) for n in aux_fields)

    def unflatten(aux, leaves):
        kw = dict(zip(fields, leaves))
        kw.update(dict(zip(aux_fields, aux)))
        return cls(**kw)

    jax.tree_util.register_pytree_node(cls, flatten, unflatten)
    _REGISTERED_FORMATS[cls.format] = cls
    return cls


def format_class(name: str):
    return _REGISTERED_FORMATS[name]


def registered_formats():
    return tuple(sorted(_REGISTERED_FORMATS))


def _aux(**kw):
    return dataclasses.field(metadata={"leaf": False}, **kw)


@_register
@dataclass(frozen=True)
class COO:
    """Coordinate format — Fig. 1b / Algorithm 1 of the paper.

    Entries are kept **row-sorted** (Morpheus sorts before SpMV too; the
    paper's SVE COO kernel exploits exactly this to tree-reduce same-row
    products). ``row``/``col``/``val`` may be padded at the tail with
    (row=nrows, col=0, val=0) sentinels so shapes can be bucketed under jit.

    ``rowptr`` is the CSR row pointer of the sorted entries, built once by
    ``to_coo``: row ``r`` owns entries ``rowptr[r]:rowptr[r + 1]``, and pad
    sentinels lie past ``rowptr[-1]``. With it, and rows long enough, the
    plain SpMV sums each row's products in place instead of scatter-adding
    them (``core.spmv.coo_spmv_plain``). Containers built by hand (or under
    a trace) leave it ``None``; their rows need not even be sorted.
    """

    row: jnp.ndarray  # (nnz,) int32, sorted non-decreasing
    col: jnp.ndarray  # (nnz,) int32
    val: jnp.ndarray  # (nnz,) float
    shape: Shape = _aux()
    plan: Any = None  # optional KernelPlan ("coo-cols" column-tiled stream)
    rowptr: Any = None  # optional (nrows + 1,) int32 row pointer of `row`

    format: ClassVar[str] = "coo"

    @property
    def nnz(self) -> int:
        return int(self.val.shape[0])

    @property
    def dtype(self):
        return self.val.dtype

    def to_dense(self) -> jnp.ndarray:
        nrows, ncols = self.shape
        dense = jnp.zeros((nrows + 1, ncols), self.val.dtype)  # +1 row: pad sentinel bucket
        dense = dense.at[self.row, self.col].add(self.val)
        return dense[:nrows]


@_register
@dataclass(frozen=True)
class CSR:
    """Compressed Sparse Row — Fig. 1c / Algorithm 2."""

    indptr: jnp.ndarray   # (nrows+1,) int32
    indices: jnp.ndarray  # (nnz,) int32 column ids
    data: jnp.ndarray     # (nnz,) float
    shape: Shape = _aux()
    plan: Any = None  # optional KernelPlan ("scs": cached SELL-C-σ view)

    format: ClassVar[str] = "csr"

    @property
    def nnz(self) -> int:
        return int(self.data.shape[0])

    @property
    def dtype(self):
        return self.data.dtype

    def row_ids(self) -> jnp.ndarray:
        """Expand indptr back to per-entry row ids (the COO 'ai' array)."""
        return segment_ids(self.indptr, self.data.shape[0])

    def to_dense(self) -> jnp.ndarray:
        nrows, ncols = self.shape
        dense = jnp.zeros((nrows + 1, ncols), self.data.dtype)
        dense = dense.at[self.row_ids(), self.indices].add(self.data)
        return dense[:nrows]


@_register
@dataclass(frozen=True)
class DIA:
    """Diagonal format — Fig. 1d / Algorithm 3.

    ``data[d, i]`` holds A[i, i + offsets[d]] (row-major diagonal storage,
    the layout the paper's SVE outer-loop vectorisation wants: contiguous in
    the row index for a fixed diagonal).
    """

    offsets: jnp.ndarray  # (ndiags,) int32, sorted
    data: jnp.ndarray     # (ndiags, nrows) float, 0 where out of range
    shape: Shape = _aux()
    plan: Any = None  # optional KernelPlan ("dia-cols" per-tile diagonals)
    #: ``data`` in the resident Pallas kernel's lane-dense layout
    #: (``kernels.dia_spmv.dia_lanes``: rows on the 128 lanes, zero-padded
    #: to whole sub-tiles), built once by ``to_dia`` so no call copies A; a
    #: device reshape of ``data`` is no view (its (8, 128) tiles hold 8
    #: diagonals, the kernel's hold 1024 rows of one). None: laid out per call
    lanes: Any = None
    #: static upper bound on max|offset| (set by ``to_dia``) — lets the
    #: Pallas fit predicate and x padding stay tight *under jit tracing*,
    #: where the offsets array itself is abstract; None = unknown (the
    #: conservative shape-based bound applies)
    extent: Any = _aux(default=None)

    format: ClassVar[str] = "dia"

    @property
    def ndiags(self) -> int:
        return int(self.offsets.shape[0])

    @property
    def nnz(self) -> int:
        return int(self.data.shape[0] * self.data.shape[1])

    @property
    def dtype(self):
        return self.data.dtype

    def to_dense(self) -> jnp.ndarray:
        nrows, ncols = self.shape
        i = jnp.arange(nrows, dtype=jnp.int32)
        dense = jnp.zeros((nrows, ncols), self.data.dtype)

        def body(d, dense):
            k = i + self.offsets[d]
            valid = (k >= 0) & (k < ncols)
            kc = jnp.clip(k, 0, ncols - 1)
            contrib = jnp.where(valid, self.data[d], 0)
            return dense.at[i, kc].add(contrib)

        return jax.lax.fori_loop(0, self.ndiags, body, dense)


@_register
@dataclass(frozen=True)
class ELL:
    """ELLPACK: every row padded to ``width`` entries (col=-1 sentinel).

    The TPU-friendly regularisation of CSR: (nrows, width) tiles map directly
    onto 8x128 VREG lanes; invalid lanes are predicated off with masks, the
    VPU analogue of SVE per-lane predication.
    """

    indices: jnp.ndarray  # (nrows, width) int32, -1 = padding
    data: jnp.ndarray     # (nrows, width) float, 0 at padding
    shape: Shape = _aux()
    plan: Any = None  # optional KernelPlan ("ell-cols" per-tile ELL blocks)

    format: ClassVar[str] = "ell"

    @property
    def width(self) -> int:
        return int(self.indices.shape[1])

    @property
    def nnz(self) -> int:
        return int(self.data.shape[0] * self.data.shape[1])

    @property
    def dtype(self):
        return self.data.dtype

    def to_dense(self) -> jnp.ndarray:
        nrows, ncols = self.shape
        rows = jnp.broadcast_to(jnp.arange(nrows, dtype=jnp.int32)[:, None], self.indices.shape)
        valid = self.indices >= 0
        cols = jnp.where(valid, self.indices, 0)
        vals = jnp.where(valid, self.data, 0)
        dense = jnp.zeros((nrows, ncols), self.data.dtype)
        return dense.at[rows.reshape(-1), cols.reshape(-1)].add(vals.reshape(-1))


@_register
@dataclass(frozen=True)
class SELL:
    """SELL-C-sigma (sliced ELLPACK), C = slice height.

    Rows are permuted by descending nnz within sigma-windows, grouped into
    slices of C rows, and each slice padded to its own max width. Data is
    stored slice-major, flattened: entry (slice s, lane r, j) lives at
    ``sptr[s]*C + j*C + r`` (column-major inside the slice so that the C
    lanes of one j-step are contiguous - the A64FX layout of [37]).
    """

    sptr: jnp.ndarray     # (nslices+1,) int32  per-slice width prefix sum
    indices: jnp.ndarray  # (total,) int32 flattened, -1 = padding
    data: jnp.ndarray     # (total,) float flattened
    perm: jnp.ndarray     # (nrows_padded,) int32 row permutation (padded rows = nrows)
    shape: Shape = _aux()
    C: int = _aux(default=8)
    plan: Any = None  # optional KernelPlan ("scs" stream, built at convert)

    format: ClassVar[str] = "sell"

    @property
    def nslices(self) -> int:
        return int(self.sptr.shape[0]) - 1

    @property
    def nnz(self) -> int:
        return int(self.data.shape[0])

    @property
    def dtype(self):
        return self.data.dtype

    def entry_rows(self) -> jnp.ndarray:
        """Original row id of every flattened entry (padding rows -> nrows)."""
        total = self.data.shape[0]
        e = jnp.arange(total, dtype=jnp.int32)
        base = self.sptr * self.C
        s = segment_ids(base, total)
        lane = (e - base[s]) % self.C
        return self.perm[s * self.C + lane]

    def to_dense(self) -> jnp.ndarray:
        nrows, ncols = self.shape
        rows = self.entry_rows()
        valid = self.indices >= 0
        cols = jnp.where(valid, self.indices, 0)
        vals = jnp.where(valid, self.data, 0)
        dense = jnp.zeros((nrows + 1, ncols), self.data.dtype)
        dense = dense.at[jnp.minimum(rows, nrows), cols].add(vals)
        return dense[:nrows]


@_register
@dataclass(frozen=True)
class BSR:
    """Block CSR with square ``bs x bs`` blocks (MXU-native, bs=128 on TPU).

    ``blocks[k]`` is the dense block at block-row ``brow(k)`` / block-col
    ``bcols[k]``; block rows padded with bcol=-1 zero blocks to ``bwidth``
    blocks per row (ELL-of-blocks), which keeps the Pallas scalar-prefetch
    grid rectangular.
    """

    bcols: jnp.ndarray   # (nbrows, bwidth) int32 block-col ids, -1 = padding
    blocks: jnp.ndarray  # (nbrows, bwidth, bs, bs) float
    shape: Shape = _aux()

    format: ClassVar[str] = "bsr"

    @property
    def bs(self) -> int:
        return int(self.blocks.shape[-1])

    @property
    def bwidth(self) -> int:
        return int(self.bcols.shape[1])

    @property
    def nnz(self) -> int:
        return int(np.prod(self.blocks.shape))

    @property
    def dtype(self):
        return self.blocks.dtype

    def to_dense(self) -> jnp.ndarray:
        nrows, ncols = self.shape
        nbrows, bwidth = self.bcols.shape
        bs = self.bs
        dense = jnp.zeros((nbrows * bs, (ncols + bs - 1) // bs * bs + bs), self.blocks.dtype)

        def body(carry, inp):
            dense = carry
            br = inp
            def inner(j, dense):
                bc = self.bcols[br, j]
                valid = bc >= 0
                col0 = jnp.where(valid, bc, nbrows_cols_pad) * bs
                blk = jnp.where(valid, self.blocks[br, j], 0)
                return jax.lax.dynamic_update_slice(
                    dense, jax.lax.dynamic_slice(dense, (br * bs, col0), (bs, bs)) + blk, (br * bs, col0)
                )
            return jax.lax.fori_loop(0, bwidth, inner, dense), None

        nbrows_cols_pad = (ncols + bs - 1) // bs  # park invalid blocks in the pad column
        dense, _ = jax.lax.scan(body, dense, jnp.arange(nbrows))
        return dense[:nrows, :ncols]


@dataclass(frozen=True)
class Dense:
    """Trivial 'format': the XLA/vendor path (ArmPL analogue in DESIGN.md)."""

    data: jnp.ndarray
    shape: Shape = _aux()

    format: ClassVar[str] = "dense"

    @property
    def nnz(self) -> int:
        return int(np.prod(self.data.shape))

    @property
    def dtype(self):
        return self.data.dtype

    def to_dense(self) -> jnp.ndarray:
        return self.data


jax.tree_util.register_pytree_node(
    Dense, lambda d: ((d.data,), (d.shape,)), lambda aux, leaves: Dense(leaves[0], aux[0])
)
_REGISTERED_FORMATS["dense"] = Dense

AnySparse = Any  # union of the containers above
