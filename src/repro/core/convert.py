"""Format conversions (Morpheus's ``convert`` / copy-constructor machinery).

Conversions are host-side (numpy/scipy) — they play the role of
``armpl_spmat_create_* + armpl_spmv_optimize``: a one-time setup cost that the
registry caches behind a handle (see ``registry.py``), after which the
device-side SpMV runs on the converted container.

Every build goes through :func:`from_dense` or :func:`convert`, each of
which records the host span ``convert`` (attributes ``fmt``, ``nnz``,
``bytes``) and the counters ``convert.calls`` and ``convert.bytes``
(``repro.core.obs``); a conversion inside another counts once.
"""
from __future__ import annotations

from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp

from . import obs, tiling
from .formats import BSR, COO, CSR, DIA, ELL, SELL, Dense

#: ``col_tile`` convert argument: ``None`` = auto (tile only when the column
#: count exceeds the default resident budget), an int = force that tile
#: width, ``False``/``0`` = never build a column-tile plan.
ColTile = Union[None, int, bool]


def _resolve_col_tile(ncols: int, col_tile: ColTile) -> Optional[int]:
    if col_tile is None:
        return tiling.select_col_tile(ncols)
    if not col_tile:  # False / 0: plans disabled (e.g. stacked distributed parts)
        return None
    return int(col_tile)


def col_tile_for_policy(fmt: str, ncols: int, ct: Optional[int]) -> ColTile:
    """Map a policy's ``col_tile(ncols)`` decision onto the converter's
    ``col_tile`` argument, so a build honours *that policy's* budget instead
    of the module default: ``None`` from the policy means "resident here",
    which for csr/sell is a single-tile SCS plan (the resident kernel's
    layout) and for the other formats no tiled plan at all."""
    if ct is not None:
        return ct
    return max(1, ncols) if fmt in ("csr", "sell") else False


def _as_scipy(a) -> sp.csr_matrix:
    if hasattr(a, "container"):  # SparseOperator facade
        a = a.container
    if sp.issparse(a):
        return a.tocsr()
    if hasattr(a, "to_dense"):  # registered sparse container
        s = container_to_scipy(a)  # COO/CSR without densifying
        if not s.data.all():  # drop stored zeros, as the dense round trip would
            s = s.copy()
            s.eliminate_zeros()
        return s
    return sp.csr_matrix(np.asarray(a))


def _as_scipy_sorted(a) -> sp.csr_matrix:
    """Like ``_as_scipy`` but with canonical (sorted) index order, copying
    first when needed — ``tocsr()`` aliases csr inputs, and sorting the
    caller's own matrix in place would be an unadvertised side effect."""
    s = _as_scipy(a)
    if not s.has_sorted_indices:
        s = s.copy()
        s.sort_indices()
    return s


def _recorded(span, out):
    """Count a finished conversion once, at the outermost ``convert`` span."""
    if span and not span.nested:
        nbytes = sum(getattr(x, "nbytes", 0)
                     for x in jax.tree_util.tree_leaves(out))
        span.set(nnz=out.nnz, bytes=nbytes)
        obs.count("convert.calls")
        obs.count("convert.bytes", nbytes)
    return out


def from_dense(a, fmt: str, dtype=jnp.float32, **kw):
    """Build a sparse container of format ``fmt`` from a dense/scipy matrix."""
    builders = {
        "coo": to_coo, "csr": to_csr, "dia": to_dia, "ell": to_ell,
        "sell": to_sell, "bsr": to_bsr, "dense": to_densefmt,
    }
    with obs.span("convert", fmt=fmt) as s:
        return _recorded(s, builders[fmt](a, dtype=dtype, **kw))


def _padded_triplets(c):
    """(row, col, val) host arrays of a DIA/ELL/SELL/BSR container, pad and
    out-of-range slots included (the caller filters them)."""
    if c.format == "dia":
        offsets, data = np.asarray(c.offsets), np.asarray(c.data)
        row = np.broadcast_to(np.arange(data.shape[1]), data.shape)
        return row, row + offsets[:, None].astype(np.int64), data
    if c.format == "ell":
        idx = np.asarray(c.indices)
        return (np.broadcast_to(np.arange(idx.shape[0])[:, None], idx.shape),
                idx, np.asarray(c.data))
    if c.format == "sell":
        base = np.asarray(c.sptr).astype(np.int64) * c.C
        e = np.arange(c.data.shape[0])
        s = np.searchsorted(base, e, side="right") - 1  # slice of each entry
        row = np.asarray(c.perm)[s * c.C + (e - base[s]) % c.C]
        return row, np.asarray(c.indices), np.asarray(c.data)
    if c.format == "bsr":
        bcols, blocks = np.asarray(c.bcols), np.asarray(c.blocks)
        nbrows, bwidth, bs, _ = blocks.shape
        r = np.arange(nbrows)[:, None, None, None] * bs + np.arange(bs)[:, None]
        col = bcols[:, :, None, None].astype(np.int64) * bs + np.arange(bs)
        # pad blocks (bcol=-1) land at negative columns and are filtered out
        col = np.where(bcols[:, :, None, None] >= 0, col, -1)
        return (np.broadcast_to(r, blocks.shape),
                np.broadcast_to(col, blocks.shape), blocks)
    raise TypeError(f"no sparse host view for format {c.format!r}")


def container_to_scipy(c) -> sp.csr_matrix:
    """Registered container -> scipy CSR without densifying (pad sentinels
    and out-of-range slots dropped). Only ``dense`` round-trips its array.

    COO/CSR keep their stored entries; the padded formats drop stored zeros,
    as the dense round trip always did, so a conversion never turns padding
    into structure (e.g. extra DIA diagonals)."""
    nrows, ncols = (int(d) for d in c.shape)
    if c.format == "coo":
        row, col, val = (np.asarray(x) for x in (c.row, c.col, c.val))
        keep = row < nrows  # drop (row=nrows, col=0, val=0) pad sentinels
        return sp.csr_matrix((val[keep], (row[keep], col[keep])), shape=(nrows, ncols))
    if c.format == "csr":
        indptr = np.asarray(c.indptr)
        nnz = int(indptr[-1])  # trailing entries past indptr[-1] are padding
        return sp.csr_matrix((np.asarray(c.data)[:nnz], np.asarray(c.indices)[:nnz],
                              indptr), shape=(nrows, ncols))
    if c.format == "dense":
        return sp.csr_matrix(np.asarray(c.data))
    row, col, val = (np.ravel(a) for a in _padded_triplets(c))
    keep = (row < nrows) & (col >= 0) & (col < ncols) & (val != 0)
    s = sp.csr_matrix((val[keep], (row[keep], col[keep])), shape=(nrows, ncols))
    s.sum_duplicates()  # sorted indices, like the dense round trip
    s.eliminate_zeros()
    return s


def convert(A, fmt: str, **kw):
    """Convert between any two containers (exactness only; COO/CSR sources
    stay sparse on host, the rest round-trip through dense).

    A same-format conversion *with* build options (``width=``, ``col_tile=``,
    ...) is a rebuild, not a no-op — e.g. re-tiling a container for a
    smaller VMEM budget. Rebuilds keep the instance's recoverable build
    parameters (SELL ``C``, ELL ``width``, BSR ``bs``/``bwidth``) unless
    overridden; SELL's ``sigma`` is not stored on the container and resets
    to the builder default."""
    if A.format == fmt:
        if not kw:
            return A
        keep = {"sell": lambda: {"C": A.C},
                "ell": lambda: {"width": A.width},
                "bsr": lambda: {"bs": A.bs, "bwidth": A.bwidth}}.get(fmt)
        if keep is not None:
            kw = {**keep(), **kw}
    with obs.span("convert", fmt=fmt) as s:
        return _recorded(s, from_dense(container_to_scipy(A), fmt,
                                       dtype=A.dtype, **kw))


def to_densefmt(a, dtype=jnp.float32):
    a = np.asarray(a.toarray() if sp.issparse(a) else a)
    return Dense(jnp.asarray(a, dtype), tuple(a.shape))


def to_coo(a, dtype=jnp.float32, pad_to: Optional[int] = None,
           col_tile: ColTile = None, index_dtype="auto"):
    s = _as_scipy(a).tocoo()
    order = np.lexsort((s.col, s.row))  # row-major sort (Morpheus sorts too)
    row, col, val = s.row[order], s.col[order], s.data[order]
    rowptr = np.zeros(s.shape[0] + 1, np.int32)
    np.cumsum(np.bincount(row, minlength=s.shape[0]), out=rowptr[1:])
    ct = _resolve_col_tile(s.shape[1], col_tile)
    plan = None
    if ct is not None:
        plan = tiling.build_coo_col_plan(row, col, val.astype(np.dtype(dtype)),
                                         tuple(s.shape), ct,
                                         index_dtype=index_dtype).jaxify()
    if len(row) == 0:  # degenerate: keep one zero sentinel entry
        row = np.array([s.shape[0]], np.int32)
        col = np.array([0], np.int32)
        val = np.array([0.0], np.float64)
    if pad_to is not None:
        pad = -len(row) % pad_to
        row = np.concatenate([row, np.full(pad, s.shape[0], np.int32)])
        col = np.concatenate([col, np.zeros(pad, np.int32)])
        val = np.concatenate([val, np.zeros(pad, val.dtype)])
    return COO(jnp.asarray(row, jnp.int32), jnp.asarray(col, jnp.int32),
               jnp.asarray(val, dtype), tuple(s.shape), plan, jnp.asarray(rowptr))


def to_csr(a, dtype=jnp.float32, col_tile: ColTile = None, plan: bool = True,
           index_dtype="auto"):
    """CSR container; with ``plan=True`` (default) a cached SELL-C-σ view
    (the ``"scs"`` KernelPlan) rides along so ``csr``×``pallas`` dispatches a
    native kernel, jit-safely, instead of being a dispatch-table hole."""
    s = _as_scipy_sorted(a)
    scs = None
    if plan and col_tile is not False and col_tile != 0:
        ct = _resolve_col_tile(s.shape[1], col_tile)
        scs = tiling.build_scs_plan(s, col_tile=ct, dtype=np.dtype(dtype),
                                    index_dtype=index_dtype).jaxify()
    indices, data = s.indices, s.data
    if len(data) == 0:  # degenerate: one pad entry past indptr[-1] (sentinel row)
        indices = np.array([0], np.int32)
        data = np.array([0.0], np.float64)
    return CSR(jnp.asarray(s.indptr, jnp.int32), jnp.asarray(indices, jnp.int32),
               jnp.asarray(data, dtype), tuple(s.shape), scs)


def to_dia(a, dtype=jnp.float32, col_tile: ColTile = None):
    s = _as_scipy(a).tocoo()
    nrows, ncols = s.shape
    entry_offs = s.col.astype(np.int64) - s.row.astype(np.int64)
    offs = np.unique(entry_offs)
    if len(offs) == 0:
        offs = np.array([0], np.int64)
    data = np.zeros((len(offs), nrows), np.float64)
    # unbuffered add: duplicate entries accumulate, in entry order
    np.add.at(data, (np.searchsorted(offs, entry_offs), s.row), s.data)
    extent = int(np.abs(offs).max())
    # DIA's own residency rule (x plus the band's reach): under the default
    # budget a resident band runs the resident kernel, which reads the
    # lane-dense values made here once; a column-tile plan is built only
    # where x does not fit or a policy asked for one by its tile width
    resident = tiling.dia_resident(ncols, extent, tiling.resident_cols())
    ct = None if col_tile is None and resident else _resolve_col_tile(ncols, col_tile)
    vals = data.astype(np.dtype(dtype))
    plan = lanes = None
    if ct is not None:
        plan = tiling.build_dia_col_plan(offs, vals, (nrows, ncols), ct).jaxify()
    if ct is None or resident:
        from repro.kernels.dia_spmv import dia_lanes

        lanes = jnp.asarray(dia_lanes(vals))
    return DIA(jnp.asarray(offs, jnp.int32), jnp.asarray(vals),
               (nrows, ncols), plan, lanes, extent=extent)


def _row_entry_positions(take: np.ndarray):
    """Vectorised row walk shared by the ELL/SELL builders: for ``take[r]``
    entries taken from each row, (j, k) give every taken entry's within-row
    position and its source row's index in ``take``."""
    total = int(take.sum())
    k = np.repeat(np.arange(len(take)), take)
    j = np.arange(total) - np.repeat(np.cumsum(take) - take, take)
    return j, k


def to_ell(a, dtype=jnp.float32, width: Optional[int] = None,
           col_tile: ColTile = None, index_dtype="auto"):
    s = _as_scipy_sorted(a)
    nrows, ncols = s.shape
    counts = np.diff(s.indptr)
    w = int(width if width is not None else (counts.max() if nrows else 0))
    w = max(w, 1)
    idx = np.full((nrows, w), -1, np.int32)
    dat = np.zeros((nrows, w), np.float64)
    j, k = _row_entry_positions(np.minimum(counts, w))
    src = s.indptr[k] + j
    idx[k, j] = s.indices[src]
    dat[k, j] = s.data[src]
    ct = _resolve_col_tile(ncols, col_tile)
    plan = None
    if ct is not None:
        sp_plan = s
        if len(counts) and counts.max() > w:  # width= truncated rows: the plan
            keep = np.zeros(len(s.data), bool)  # must describe the same matrix
            keep[src] = True
            sp_plan = sp.csr_matrix(
                (s.data[keep], s.indices[keep],
                 np.concatenate([[0], np.cumsum(np.minimum(counts, w))])),
                shape=s.shape)
        plan = tiling.build_ell_col_plan(sp_plan, ct, np.dtype(dtype),
                                         index_dtype=index_dtype).jaxify()
    return ELL(jnp.asarray(idx), jnp.asarray(dat, dtype), (nrows, ncols), plan)


def to_sell(a, dtype=jnp.float32, C: int = 8, sigma: int = 64,
            col_tile: ColTile = None, plan: bool = True, index_dtype="auto"):
    """SELL-C-σ container. With ``plan=True`` (default) the Pallas ``"scs"``
    stream is precomputed here — construction is exactly where the layout is
    concrete, so ``sell``×``pallas`` no longer needs a trace-time rebuild
    (the old ``_sell_concrete`` jit restriction)."""
    s = _as_scipy_sorted(a)
    nrows, ncols = s.shape
    counts = np.diff(s.indptr)
    nrows_pad = -(-max(nrows, 1) // C) * C
    perm = np.full(nrows_pad, nrows, np.int32)  # padding rows point past the end
    rows = np.arange(nrows)
    for w0 in range(0, nrows, sigma):  # sigma-window sort by descending nnz
        win = rows[w0 : w0 + sigma]
        perm[w0 : w0 + len(win)] = win[np.argsort(-counts[win], kind="stable")]
    nslices = nrows_pad // C
    counts_pad = np.concatenate([counts, [0]])  # padding rows contribute 0
    widths = np.maximum(counts_pad[perm].reshape(nslices, C).max(axis=1), 1)
    sptr = np.zeros(nslices + 1, np.int64)
    np.cumsum(widths, out=sptr[1:])
    total = int(sptr[-1]) * C
    idx = np.full(total, -1, np.int32)
    dat = np.zeros(total, np.float64)
    # entry (slice sl, lane, j) of permuted row r lives at (sptr[sl]+j)*C+lane
    real = np.nonzero(perm < nrows)[0]
    rows = perm[real]
    j, k = _row_entry_positions(counts[rows])
    src = s.indptr[rows[k]] + j
    tgt = (sptr[real[k] // C] + j) * C + real[k] % C
    idx[tgt] = s.indices[src]
    dat[tgt] = s.data[src]
    scs = None
    if plan and col_tile is not False and col_tile != 0:
        # the Pallas stream is 128 lanes wide whatever this container's C
        scs = tiling.build_scs_plan(
            s, col_tile=_resolve_col_tile(ncols, col_tile), sigma=sigma,
            dtype=np.dtype(dtype), index_dtype=index_dtype).jaxify()
    return SELL(jnp.asarray(sptr, jnp.int32), jnp.asarray(idx), jnp.asarray(dat, dtype),
                jnp.asarray(perm, jnp.int32), (nrows, ncols), C, scs)


def to_bsr(a, dtype=jnp.float32, bs: int = 32, bwidth: Optional[int] = None,
           block_size=None):
    """Dense/scipy/container -> :class:`BSR` (ELL-of-blocks, ``bcol=-1`` pads).

    ``block_size`` is the preferred spelling of ``bs`` and also accepts
    ``"auto"``: scan the candidate edges (64, 32, 16, 8) and keep the largest
    whose occupied-block fill stays >= 0.5 — the biggest MXU tile that does
    not more than double storage — falling back to the best-fill edge when
    none qualifies (pathologically scattered matrices).
    """
    s = _as_scipy(a)
    nrows, ncols = s.shape
    if block_size is not None:
        if block_size == "auto":
            from .features import block_density

            coo = s.tocoo()
            fills = {cand: block_density(coo.row, coo.col, nrows, ncols, cand)
                     for cand in (64, 32, 16, 8) if cand <= max(nrows, ncols)}
            if not fills:
                fills = {8: 1.0}
            good = [cand for cand, fill in fills.items() if fill >= 0.5]
            bs = max(good) if good else max(fills, key=fills.get)
        else:
            bs = int(block_size)
    nbrows, nbcols = -(-nrows // bs), -(-ncols // bs)
    b = sp.bsr_matrix(s, blocksize=(bs, bs)) if nrows % bs == 0 and ncols % bs == 0 else None
    if b is None:  # pad then re-block
        pad = sp.csr_matrix((nbrows * bs, nbcols * bs), dtype=s.dtype)
        pad[:nrows, :ncols] = s
        b = sp.bsr_matrix(pad, blocksize=(bs, bs))
    counts = np.diff(b.indptr)
    w = int(bwidth if bwidth is not None else max(1, counts.max() if len(counts) else 1))
    bcols = np.full((nbrows, w), -1, np.int32)
    blocks = np.zeros((nbrows, w, bs, bs), np.float64)
    for br in range(nbrows):
        lo, hi = b.indptr[br], min(b.indptr[br + 1], b.indptr[br] + w)
        bcols[br, : hi - lo] = b.indices[lo:hi]
        blocks[br, : hi - lo] = b.data[lo:hi]
    return BSR(jnp.asarray(bcols), jnp.asarray(blocks, dtype), (nrows, ncols))
