"""jit'd wrappers over the Pallas kernels, registered into the structured
dispatch table as the ``pallas`` backend of each format.

Device-fit rules mirror the checks Morpheus's FPGA backend applies
(buffer-size limits, §V of the paper), but they are *declarative* here:
each registration carries a ``supports(A, policy)`` capability predicate
consulted by ``core.spmv`` dispatch, which falls back down the policy's
backend chain (normally to ``plain``) instead of each kernel hiding an
ad-hoc guard.

Every format now has two Pallas strategies and the wrapper picks per call
(``needs_policy=True`` registrations receive the policy):

  - **resident**: x stays in VMEM for the whole kernel; chosen when the
    format's resident footprint fits ``policy.resident_cols()``.
  - **column-tiled**: the container carries a :class:`~repro.core.formats.
    KernelPlan` (built at convert time) whose per-tile arrays stream x
    through VMEM tile by tile — the plan's presence and geometry are static
    aux data, so the predicates stay trace-safe and the kernels jit cleanly.

``csr`` dispatches through its cached SELL-C-σ view (the ``"scs"`` plan) —
the paper's baseline format no longer falls off the Pallas backend.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tiling
from repro.core.formats import BSR, COO, CSR, DIA, ELL, SELL
from repro.core.spmv import register_masked_spmv, register_spmm, register_spmv

from .bsr_spmm import bsr_spmm
from .coo_spmv import coo_spmv, scoo_spmv_tiled
from .dia_spmv import dia_spmv, dia_spmv_lanes, dia_spmv_tiled
from .ell_spmv import ell_spmv, ell_spmv_tiled
from .sell_spmv import scs_spmv_from_plan


# --------------------------------------------------- capability predicates ----


#: Value dtypes the Pallas kernels handle: each one upcasts products to f32
#: before reducing (the storage/accumulation split of the precision lane);
#: f64 never lowers on TPU and is left to the plain/dense backends.
_PALLAS_VALUE_DTYPES = (jnp.float32, jnp.bfloat16, jnp.float16)


def _precision_ok(A, policy) -> bool:
    """The policy's precision knobs are executable on the Pallas backend:
    f32 accumulation (the only mode the kernels implement) over a storage
    dtype they can upcast from. Static metadata only — trace-safe."""
    accum = getattr(policy, "accum_dtype", "float32")
    return (accum == "float32"
            and jnp.dtype(A.dtype) in (jnp.dtype(d) for d in _PALLAS_VALUE_DTYPES))


def _plan_ok(A, policy, kind: str) -> bool:
    """A column-tile plan of ``kind`` whose tile fits the policy's budget.
    Static metadata only — safe under jit tracing."""
    p = A.plan
    return p is not None and p.kind == kind and p.ct <= policy.resident_cols()


def _dia_extent(A: DIA) -> int | None:
    """Static ``max|offset|``: the aux-metadata bound ``to_dia`` records
    (trace-safe — dispatch stays identical inside and outside jit), else
    computed from concrete offsets; ``None`` when neither is available and
    the conservative shape bound applies."""
    if A.extent is not None:
        return int(A.extent)
    offs = A.offsets
    if isinstance(offs, jax.core.Tracer):
        return None
    o = np.asarray(offs)
    return int(np.abs(o).max()) if o.size else 0


def _dia_resident(A: DIA, policy) -> bool:
    # x + the shifted-window padding resident in VMEM; the padding is the
    # actual offset extent when known (wide-but-thin band matrices fit),
    # the worst-case row count when traced
    ext = _dia_extent(A)
    pad = A.shape[0] if ext is None else ext
    return tiling.dia_resident(A.shape[1], pad, policy.resident_cols())


def _dia_ok(A: DIA, policy) -> bool:
    return _precision_ok(A, policy) and (
        _dia_resident(A, policy) or _plan_ok(A, policy, "dia-cols"))


def _ell_resident(A: ELL, policy) -> bool:
    return A.shape[1] <= policy.resident_cols()


def _ell_ok(A: ELL, policy) -> bool:
    return _precision_ok(A, policy) and (
        _ell_resident(A, policy) or _plan_ok(A, policy, "ell-cols"))


def _coo_resident(A: COO, policy) -> bool:
    # full-window mode: one-hot window = all rows; jit-friendly but VMEM-bound
    return (A.shape[0] <= policy.max_onehot_rows
            and A.shape[1] <= policy.resident_cols())


def _coo_ok(A: COO, policy) -> bool:
    return _precision_ok(A, policy) and (
        _coo_resident(A, policy) or _plan_ok(A, policy, "coo-cols"))


def _scs_ok(A, policy) -> bool:
    # sell/csr run the native SELL-C-σ stream cached at convert time; the
    # static plan check replaces the old concrete-arrays-only restriction,
    # so the kernel now runs under jit
    return _precision_ok(A, policy) and _plan_ok(A, policy, "scs")


def pallas_strategy(A, policy) -> str | None:
    """Which Pallas strategy dispatch would run for ``A`` under ``policy``:
    ``"resident"``, ``"tiled"``, or ``None`` (predicate rejects — dispatch
    falls down the chain). The introspection twin of the wrappers below;
    ``benchmarks/spmv_bench.py`` records it per entry."""
    fmt = A.format
    if not _precision_ok(A, policy):
        return None
    if fmt == "dia":
        if _dia_resident(A, policy):
            return "resident"
        return "tiled" if _plan_ok(A, policy, "dia-cols") else None
    if fmt == "ell":
        if _ell_resident(A, policy):
            return "resident"
        return "tiled" if _plan_ok(A, policy, "ell-cols") else None
    if fmt == "coo":
        if _coo_resident(A, policy):
            return "resident"
        return "tiled" if _plan_ok(A, policy, "coo-cols") else None
    if fmt in ("csr", "sell"):
        if not _scs_ok(A, policy):
            return None
        return "tiled" if A.plan.ntiles > 1 else "resident"
    if fmt == "bsr":
        # single strategy: the scalar-prefetched block grid — bwidth is the
        # streaming loop, so there is no column-tiled variant to pick
        return "block"
    return None


# ------------------------------------------------------------ registrations ----


# The needs_policy wrappers branch on pallas_strategy — the same function the
# benchmark trajectory records — so the dispatched strategy and the reported
# one cannot drift apart.


@register_spmv("dia", "pallas", supports=_dia_ok, needs_policy=True)
def dia_spmv_pallas(A: DIA, x, policy):
    if pallas_strategy(A, policy) == "resident":
        kw = dict(extent=_dia_extent(A),
                  vmem_budget_bytes=policy.vmem_budget_bytes)
        if A.lanes is not None:
            return dia_spmv_lanes(A.offsets, A.lanes, x, nrows=A.shape[0], **kw)
        return dia_spmv(A.offsets, A.data, x, **kw)
    offs_t, dat_w = A.plan.arrays
    return dia_spmv_tiled(offs_t, dat_w, x, nrows=A.shape[0], col_tile=A.plan.ct)


@register_spmv("ell", "pallas", supports=_ell_ok, needs_policy=True)
def ell_spmv_pallas(A: ELL, x, policy):
    if pallas_strategy(A, policy) == "resident":
        return ell_spmv(A.indices, A.data, x)
    idx_t, dat_t, prb, pt = A.plan.arrays
    return ell_spmv_tiled(idx_t, dat_t, prb, pt, x, nrows=A.shape[0],
                          col_tile=A.plan.ct)


@register_spmv("coo", "pallas", supports=_coo_ok, needs_policy=True)
def coo_spmv_pallas(A: COO, x, policy):
    if pallas_strategy(A, policy) == "resident":
        return coo_spmv(A.row, A.col, A.val, x, nrows=A.shape[0])
    row, col, val, sid, ctile = A.plan.arrays
    ct, _, slice_rows, tile = (int(v) for v in A.plan.meta)
    return scoo_spmv_tiled(row, col, val, sid, ctile, x, nrows=A.shape[0],
                           col_tile=ct, slice_rows=slice_rows, tile=tile)


@register_spmv("sell", "pallas", supports=_scs_ok)
def sell_spmv_pallas(A: SELL, x):
    """Native SELL-C-σ kernel over the convert-time ``"scs"`` stream (row-
    sorted slices, scalar-prefetched tile/window steering)."""
    return scs_spmv_from_plan(A.plan, x, nrows=A.shape[0])


@register_spmv("csr", "pallas", supports=_scs_ok)
def csr_spmv_pallas(A: CSR, x):
    """CSR runs the same native SELL-C-σ kernel via its cached SCS view —
    convert-time regularisation instead of a rowptr-walk kernel."""
    return scs_spmv_from_plan(A.plan, x, nrows=A.shape[0])


# Row-masked variants (multicolor SymGS colors): the unmasked kernel runs and
# the mask selects its rows, so masked rows are exactly zero whatever x
# holds. Masking the operand instead would make a masked copy of the matrix
# per color; those copies do not depend on x, so XLA hoists them out of a
# solver's loops and keeps every color's copy live at once (several GB at
# HPCG's 104³).


@register_masked_spmv("dia", "pallas", supports=_dia_ok, needs_policy=True)
def dia_masked_spmv_pallas(A: DIA, x, row_mask, policy):
    return jnp.where(row_mask, dia_spmv_pallas(A, x, policy), 0)


@register_masked_spmv("ell", "pallas", supports=_ell_ok, needs_policy=True)
def ell_masked_spmv_pallas(A: ELL, x, row_mask, policy):
    return jnp.where(row_mask, ell_spmv_pallas(A, x, policy), 0)


@register_spmm("bsr", "pallas", supports=_precision_ok)
def bsr_spmm_pallas(A: BSR, X):
    nbcols = -(-A.shape[1] // A.bs)
    Xp = jnp.zeros((nbcols * A.bs, X.shape[1]), X.dtype).at[: X.shape[0]].set(X)
    Y = bsr_spmm(A.bcols, A.blocks, Xp)
    return Y[: A.shape[0]].astype(X.dtype)


@register_spmv("bsr", "pallas", supports=_precision_ok)
def bsr_spmv_pallas(A: BSR, x):
    return bsr_spmm_pallas(A, x[:, None])[:, 0]


@register_masked_spmv("bsr", "pallas", supports=_precision_ok)
def bsr_masked_spmv_pallas(A: BSR, x, row_mask):
    # mask rows on the operand (block-granular predication): zeroed block
    # rows contribute exactly zero, so the block-grid kernel runs unchanged
    nbrows, bs = A.bcols.shape[0], A.bs
    m = jnp.zeros((nbrows * bs,), jnp.bool_).at[: A.shape[0]].set(row_mask)
    blocks = A.blocks * m.reshape(nbrows, 1, bs, 1).astype(A.blocks.dtype)
    return bsr_spmv_pallas(BSR(A.bcols, blocks, A.shape), x)
