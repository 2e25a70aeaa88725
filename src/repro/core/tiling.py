"""Column-tiling model + host-side kernel-plan builders.

The Pallas backend has two execution strategies per format (docs/formats.md,
"Kernel strategy"):

  - resident : x (f32) lives in VMEM for the whole kernel — the fast path for
    matrices whose column count fits the policy's VMEM budget.
  - tiled    : x is partitioned into static column tiles streamed through
    VMEM; the kernel grid gains a trailing (sequential) column-tile dimension
    and partial ``y`` is accumulated across it. Pallas's grid pipeline
    double-buffers the per-step block copies, so the next x tile / data panel
    is in flight while the current one computes.

The tiled strategies need the format's arrays *split by column tile* so each
grid step sees a dense per-tile index block (no in-kernel search for "my
entries"). That split is a one-time host-side cost — the ArmPL
``optimize``-step analogue — done here with numpy and attached to the
container as a :class:`repro.core.formats.KernelPlan` at convert time, which
keeps the Pallas dispatch jit-safe: under trace the plan's arrays are ordinary
pytree leaves and its geometry is static aux data.

This module is import-light on purpose (numpy only + formats): both
``convert`` (build time) and ``operator`` (policy time) consult the same tile
model without an import cycle.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from .formats import KernelPlan

#: Default device-fit limits, shared with ``ExecutionPolicy`` so the policy
#: fields and the convert-time auto-tiling agree on one formula.
DEFAULT_MAX_RESIDENT_COLS = 1 << 20
DEFAULT_VMEM_BUDGET_BYTES = 16 << 20  # one TPU core's VMEM

#: Column-tile geometry caps: at least one 8-lane vector register row, at
#: most 16k columns per tile (a 64 KiB f32 x tile — small against the budget
#: so the double-buffered pipeline always has headroom).
MIN_COL_TILE = 8
MAX_COL_TILE = 1 << 14

#: Index dtypes a plan's tile-local column arrays may use, narrowest first.
#: All signed: -1 is the universal pad sentinel, so an index dtype is feasible
#: for a tile of ``ct`` columns iff it can hold ``ct - 1`` (int8 -> ct <= 128,
#: int16 -> ct <= 32768; anything wider stays int32).
INDEX_DTYPES = ("int8", "int16", "int32")


def index_dtype_fits(index_dtype, col_tile: int) -> bool:
    """True when ``index_dtype`` can hold every tile-local column of a
    ``col_tile``-wide tile (ids in ``[0, col_tile)``) plus the -1 pad."""
    if str(index_dtype) == "auto":
        return True
    dt = np.dtype(index_dtype)
    return dt.kind == "i" and int(np.iinfo(dt).max) >= col_tile - 1


def local_index_dtype(col_tile: int, index_dtype="auto") -> np.dtype:
    """Resolve the plan-local column-index dtype for a ``col_tile``-wide tile.

    ``"auto"`` picks the narrowest signed dtype that holds ``col_tile - 1``
    (the widest tile-local id); an explicit dtype is validated against the
    tile width so a policy can never silently truncate indices.
    """
    if str(index_dtype) != "auto":
        dt = np.dtype(index_dtype)
        if not index_dtype_fits(dt, col_tile):
            raise ValueError(
                f"index dtype {dt} cannot hold tile-local columns of a "
                f"{col_tile}-wide tile")
        return dt
    for name in INDEX_DTYPES:
        if int(np.iinfo(np.dtype(name)).max) >= col_tile - 1:
            return np.dtype(name)
    return np.dtype(np.int32)


def resident_cols(max_resident_cols: int = DEFAULT_MAX_RESIDENT_COLS,
                  vmem_budget_bytes: int = DEFAULT_VMEM_BUDGET_BYTES) -> int:
    """Columns of f32 x that may stay VMEM-resident for a whole kernel.

    The budget model keeps x to a quarter of VMEM (4 bytes/col -> budget/16
    columns): the other three quarters hold the double-buffered data/index
    panels and the y block. The explicit ``max_resident_cols`` cap wins when
    smaller (tests shrink it to force the tiled path on tiny matrices).
    """
    return min(max_resident_cols, vmem_budget_bytes // 16)


def dia_resident(ncols: int, extent: int, resident_cols: int) -> bool:
    """DIA's resident-x fit: x with ``extent`` columns of padding on each
    side (the band's reach) within four times ``resident_cols``."""
    return ncols + 2 * extent <= 4 * resident_cols


def select_col_tile(ncols: int,
                    max_resident_cols: int = DEFAULT_MAX_RESIDENT_COLS,
                    vmem_budget_bytes: int = DEFAULT_VMEM_BUDGET_BYTES,
                    ) -> Optional[int]:
    """Column-tile size for ``ncols``, or ``None`` when x fits resident.

    Tiles take half the resident budget so two (the double buffer) fit where
    one resident x did, rounded down to 8 lanes and capped at
    ``MAX_COL_TILE``.
    """
    res = resident_cols(max_resident_cols, vmem_budget_bytes)
    if ncols <= res:
        return None
    tile = min(res // 2, MAX_COL_TILE)
    return max(MIN_COL_TILE, (tile // 8) * 8)


def _cdiv(a, b):
    """Ceiling division; works elementwise on numpy arrays too."""
    return -(-a // b)


def _cumcount_sorted(group: np.ndarray) -> np.ndarray:
    """Rank of each element within its group, for a non-decreasing group-id
    array (the per-row/per-tile entry position used by every splitter)."""
    n = len(group)
    if n == 0:
        return np.zeros(0, np.int64)
    idx = np.arange(n)
    change = np.r_[True, group[1:] != group[:-1]]
    start = np.maximum.accumulate(np.where(change, idx, 0))
    return idx - start


# ------------------------------------------------------------ ELL splitter ----


def build_ell_col_plan(s, col_tile: int, dtype=np.float32,
                       index_dtype="auto", block_rows: int = 256) -> KernelPlan:
    """Split a (sorted) scipy CSR matrix into per-(row block, column tile)
    ELL panels.

    Only pairs that hold entries get a panel (plus one all-padding panel for
    a row block with none, so its y rows are still written): storage tracks
    the matrix's tile coverage, not ``ntiles * nrows`` — a banded matrix
    touches two or three tiles per row block however many tiles there are.

    Arrays: ``idx_t (P, W, br)`` tile-local columns (-1 pad) in the
    narrowest dtype the tile width allows (see :func:`local_index_dtype`),
    ``dat_t`` alike, ``prb (P,)`` / ``pt (P,)`` int32 each panel's row block
    and column tile, row-block-major; ``W`` is the max per-(row, tile) entry
    count and ``br`` the row-block height (a multiple of 128). Rows run
    along the last axis, the TPU's 128 lanes: a narrow ``W`` there would be
    padded to 128 lanes in device memory. Meta: ``(ct, ntiles, W, br)``.
    """
    nrows, ncols = s.shape
    ntiles = max(1, _cdiv(ncols, col_tile))
    idt = local_index_dtype(col_tile, index_dtype)
    br = min(block_rows, _cdiv(max(nrows, 1), 128) * 128)
    nrb = _cdiv(max(nrows, 1), br)
    counts = np.diff(s.indptr)
    r = np.repeat(np.arange(nrows, dtype=np.int64), counts)
    c = s.indices.astype(np.int64)
    t = c // col_tile
    j = _cumcount_sorted(r * ntiles + t)  # CSR order: sorted by (row, col)
    width = int(j.max()) + 1 if len(j) else 1  # max group size, O(nnz)
    pair = (r // br) * ntiles + t
    present = np.zeros((nrb, ntiles), bool)
    present.reshape(-1)[pair] = True
    present[~present.any(axis=1), 0] = True  # empty row block: one pad panel
    pids = np.flatnonzero(present)  # sorted: row-block-major, tile-minor
    panel = np.zeros(nrb * ntiles, np.int64)
    panel[pids] = np.arange(len(pids))
    idx_t = np.full((len(pids), width, br), -1, idt)
    dat_t = np.zeros((len(pids), width, br), dtype)
    p = panel[pair]
    idx_t[p, j, r % br] = (c - t * col_tile).astype(idt)
    dat_t[p, j, r % br] = s.data
    prb = (pids // ntiles).astype(np.int32)
    pt = (pids % ntiles).astype(np.int32)
    return KernelPlan("ell-cols", (idx_t, dat_t, prb, pt),
                      (col_tile, ntiles, width, br))


# ------------------------------------------------------------ DIA splitter ----


def build_dia_col_plan(offsets: np.ndarray, data: np.ndarray,
                       shape: Tuple[int, int], col_tile: int) -> KernelPlan:
    """Split DIA diagonals by the column tiles they cross.

    A diagonal ``off`` contributes column ``i + off`` at row ``i``; its
    restriction to tile ``t`` is the row range ``[t*ct - off, (t+1)*ct - off)``
    — at most ``ct`` rows, stored as a *window* ``dat_w[t, d, i - (t*ct -
    off)]`` rather than a dense (nrows,) row, so the plan stays O(total
    diagonal coverage) instead of O(ntiles * nrows) per diagonal. Windows
    are pre-masked to the tile's columns: the kernel needs no per-entry tile
    test, and a wrong (clamped) window read can only ever multiply zeros.

    Arrays: ``offs_t (ntiles, max_d)`` int32 and ``dat_w (ntiles, max_d,
    ct)``. Row ``i`` of diagonal ``(t, d)`` lives at window position
    ``i + off - t*ct`` — the same coordinate the haloed x tile uses, so the
    kernel reads both with one clamped dynamic slice.

    DIA carries no per-entry column indices (offsets are scalar-prefetched
    into SMEM and must stay int32), so index compression does not apply —
    DIA participates in the precision lane through its value dtype only.
    """
    nrows, ncols = shape
    ntiles = max(1, _cdiv(ncols, col_tile))
    per_tile: list = [[] for _ in range(ntiles)]
    for d, off in enumerate(np.asarray(offsets, np.int64)):
        lo, hi = max(0, -off), min(nrows, ncols - off)
        if lo >= hi:
            continue
        for t in range((lo + off) // col_tile, (hi - 1 + off) // col_tile + 1):
            i0 = max(lo, t * col_tile - off)
            i1 = min(hi, (t + 1) * col_tile - off)
            if i0 < i1:
                per_tile[t].append((int(off), d, i0, i1))
    max_d = max(1, max((len(p) for p in per_tile), default=1))
    offs_t = np.zeros((ntiles, max_d), np.int32)
    dat_w = np.zeros((ntiles, max_d, col_tile), data.dtype)
    for t, diags in enumerate(per_tile):
        for slot, (off, d, i0, i1) in enumerate(diags):
            offs_t[t, slot] = off
            w0 = t * col_tile - off
            dat_w[t, slot, i0 - w0 : i1 - w0] = data[d, i0:i1]
    return KernelPlan("dia-cols", (offs_t, dat_w), (col_tile, ntiles, max_d))


# ------------------------------------------------------------ COO splitter ----


def build_coo_col_plan(row: np.ndarray, col: np.ndarray, val: np.ndarray,
                       shape: Tuple[int, int], col_tile: int,
                       slice_rows: int = 512, tile: int = 512,
                       index_dtype="auto") -> KernelPlan:
    """Sliced-COO layout bucketed by (row slice, column tile).

    The stream is row-slice-major, column-tile-minor: all of a slice's tiles
    are consecutive, so the kernel's resident y window sees contiguous runs
    and "first block of this slice" remains the init signal. Every slice
    emits at least one (possibly all-padding) block so its y window is
    always written. Pad entries carry ``row = slice_start, col = 0, val = 0``
    — the contribution lands on the window's first row and is exactly zero.

    Arrays: ``row (B*T,)`` global rows (always int32 — they span the whole
    matrix), ``col (B*T,)`` tile-local columns in the narrowest dtype the
    tile width allows, ``val (B*T,)``, ``sid (B,)`` per-block slice id,
    ``ctile (B,)`` per-block column tile.
    """
    nrows, ncols = shape
    ntiles = max(1, _cdiv(ncols, col_tile))
    idt = local_index_dtype(col_tile, index_dtype)
    nsl = max(1, _cdiv(nrows, slice_rows))
    row = np.asarray(row, np.int64)
    keep = row < nrows  # drop (row=nrows,...) pad sentinels
    row, c, v = row[keep], np.asarray(col, np.int64)[keep], np.asarray(val)[keep]
    sl, t = row // slice_rows, c // col_tile
    order = np.lexsort((c, row, t, sl))
    row, c, v, sl, t = row[order], c[order], v[order], sl[order], t[order]

    counts = np.zeros((nsl, ntiles), np.int64)
    np.add.at(counts, (sl, t), 1)
    padded = _cdiv(counts, tile) * tile
    padded[counts.sum(axis=1) == 0, 0] = tile  # empty slice: one zero block
    offsets = np.concatenate([[0], np.cumsum(padded.reshape(-1))])[:-1]
    offsets = offsets.reshape(nsl, ntiles)
    total = int(padded.sum())

    sl_of_group = np.repeat(np.arange(nsl), ntiles)
    row_arr = np.repeat(sl_of_group * slice_rows, padded.reshape(-1)).astype(np.int64)
    col_arr = np.zeros(total, np.int64)
    val_arr = np.zeros(total, v.dtype if len(v) else np.float64)
    rank = _cumcount_sorted(sl * ntiles + t)
    pos = offsets[sl, t] + rank
    row_arr[pos], col_arr[pos], val_arr[pos] = row, c - t * col_tile, v

    blocks = padded.reshape(-1) // tile
    sid = np.repeat(sl_of_group, blocks).astype(np.int32)
    ctile = np.repeat(np.tile(np.arange(ntiles), nsl), blocks).astype(np.int32)
    return KernelPlan(
        "coo-cols",
        (row_arr.astype(np.int32), col_arr.astype(idt), val_arr, sid, ctile),
        (col_tile, ntiles, slice_rows, tile))


# ---------------------------------------------- SELL-C-sigma (SCS) splitter ----


def build_scs_plan(s, col_tile: Optional[int] = None, C: int = 128,
                   sigma: int = 64, slice_window: int = 4,
                   jstep_block: int = 32, dtype=np.float32,
                   index_dtype="auto") -> KernelPlan:
    """SELL-C-σ stream for the native Pallas CSR/SELL kernel.

    Rows are permuted by descending nnz inside σ-windows (Kreutzer et al.'s
    regularisation of CSR for wide SIMD), grouped into slices of C lanes, and
    each slice's entries emitted as *j-steps*: one C-lane vector per within-
    row position. ``C`` defaults to the TPU's 128 lanes: the ``(B*JB, C)``
    arrays are tiled ``(8, 128)`` in device memory, so a narrower C pads
    every panel out to 128 lanes (16x at C = 8). J-steps are bucketed by (slice-window, column tile) —
    window-major, tile-minor — and each bucket padded to ``jstep_block``
    j-steps, so every kernel grid step owns a dense (jstep_block, C) panel,
    its scalar-prefetched ``btile``/``bwin`` steer the x tile + output window
    block specs, and a window change is the y-init signal. Empty windows emit
    one all-padding block so their output rows are still written.

    Arrays: ``btile (B,)``, ``bwin (B,)`` int32 per-block; ``lsl (B*JB,)``
    int32 window-local slice of each j-step; ``idx2/dat2 (B*JB, C)``
    tile-local columns (-1 pad, narrowest dtype the tile width allows) /
    values; ``perm (nrows_pad,)`` the σ-sorted row permutation that
    un-permutes y.
    """
    nrows, ncols = s.shape
    ct = int(col_tile) if col_tile else max(1, ncols)
    ntiles = max(1, _cdiv(max(1, ncols), ct))
    idt = local_index_dtype(ct, index_dtype)
    sw, jb = slice_window, jstep_block
    counts = np.diff(s.indptr)
    nrows_pad = _cdiv(max(nrows, 1), C) * C
    perm = np.full(nrows_pad, nrows, np.int32)
    rows = np.arange(nrows)
    for w0 in range(0, nrows, sigma):
        win = rows[w0:w0 + sigma]
        perm[w0:w0 + len(win)] = win[np.argsort(-counts[win], kind="stable")]
    nslices = nrows_pad // C
    nwin = max(1, _cdiv(nslices, sw))
    nslices_pad = nwin * sw

    pinv = np.zeros(max(nrows, 1), np.int64)
    pinv[perm[perm < nrows]] = np.nonzero(perm < nrows)[0]
    r = np.repeat(np.arange(nrows, dtype=np.int64), counts)
    c = s.indices.astype(np.int64)
    prow = pinv[r]
    sl, lane = prow // C, prow % C
    t = c // ct
    j = _cumcount_sorted(r * ntiles + t)  # within-(row, tile) position

    # per-(slice, tile) width = max over the C lanes of the entry count;
    # j is each entry's within-(row, tile) rank, so the group max of j+1 is
    # exactly the widest lane — O(nnz) scatter into the (nslices, ntiles)
    # grid instead of materialising per-(row, tile) counts
    W = np.zeros((nslices_pad, ntiles), np.int64)
    np.maximum.at(W, (sl, t), j + 1)

    nj = W.reshape(nwin, sw, ntiles).sum(axis=1)           # j-steps per (win, tile)
    nj_pad = _cdiv(nj, jb) * jb
    nj_pad[nj_pad.sum(axis=1) == 0, 0] = jb                # empty window: 1 block
    group_off = np.concatenate([[0], np.cumsum(nj_pad.reshape(-1))])[:-1]
    group_off = group_off.reshape(nwin, ntiles)
    Wr = W.reshape(nwin, sw, ntiles)
    pre = np.cumsum(Wr, axis=1) - Wr                       # within-window prefix
    off_sl_t = (group_off[:, None, :] + pre).reshape(nslices_pad, ntiles)

    total_j = int(nj_pad.sum())
    idx2 = np.full((total_j, C), -1, idt)
    dat2 = np.zeros((total_j, C), dtype)
    jrow = off_sl_t[sl, t] + j
    idx2[jrow, lane] = (c - t * ct).astype(idt)
    dat2[jrow, lane] = s.data

    lsl = np.zeros(total_j, np.int32)
    sl_nz, t_nz = np.nonzero(W)
    lens = W[sl_nz, t_nz]
    starts = off_sl_t[sl_nz, t_nz]
    pos = np.repeat(starts, lens) + _cumcount_sorted(np.repeat(np.arange(len(lens)), lens))
    lsl[pos] = np.repeat(sl_nz % sw, lens).astype(np.int32)

    blocks = nj_pad.reshape(-1) // jb
    bwin = np.repeat(np.repeat(np.arange(nwin), ntiles), blocks).astype(np.int32)
    btile = np.repeat(np.tile(np.arange(ntiles), nwin), blocks).astype(np.int32)
    return KernelPlan("scs", (btile, bwin, lsl, idx2, dat2, perm),
                      (ct, ntiles, C, sw, jb, nwin))
