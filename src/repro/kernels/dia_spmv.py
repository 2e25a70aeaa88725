"""DIA SpMV Pallas kernels — the paper's SVE outer-loop vectorisation on TPU.

Paper (§IV): vectorise the *row* loop (lanes = consecutive rows), iterate
diagonals sequentially, because (i) ``av`` is contiguous along rows for a
fixed diagonal and (ii) no horizontal reduction is needed. That maps 1:1 to
the TPU VPU: a grid over row blocks of ``(8, 128)`` vector registers of
consecutive rows; the diagonal loop's ``x`` access is a *dense shifted
load* — the gather the SVE version needed (``svld1_gather_index``)
disappears entirely because x is pre-padded so every shift is in-bounds
(per-lane predication becomes "pad with zeros"; the zero data entries
contribute nothing).

The shifted load starts at an arbitrary element, which Mosaic cannot slice
directly (dynamic offsets must be tile-aligned). Vectors are therefore laid
out as ``(nchunks, 8, 128)`` register-sized chunks: :func:`_windows` loads
the aligned chunks that cover a run of windows in one slab and shifts them
into place with sublane and lane rotations (``pltpu.roll``), each loaded
chunk serving two neighbouring windows.

Two execution modes:

  - ``dia_spmv``       : resident-x. One grid step per row block of many
    chunks; inside it, sub-tiles of ``SUB`` chunks each build all of their
    diagonals' windows from one slab of x and keep the sum in registers,
    diagonal 0 to ndiags - 1 in f32, so y is stored once and every value of
    A is read from HBM once. The values come in a lane-dense layout
    (:func:`dia_lanes`): ``to_dia`` builds it once beside ``data``
    (``DIA.lanes``), and a container without it is laid out per call. The
    pre/post x padding is sized by the *actual* offset extent
    ``max|offset|`` when given (much tighter than the worst case for
    wide-but-thin band matrices).
  - ``dia_spmv_tiled`` : column-tiled. Diagonals are pre-split per column
    tile (``core.tiling.build_dia_col_plan``) with data pre-masked to the
    rows whose column falls in the tile; each grid step loads one haloed
    x window — streamed/double-buffered by the grid pipeline — and
    accumulates partial y across the sequential column-tile grid axis.
    Window starts are clamped; a clamp can only trigger when the
    (pre-masked) data in that block is all-zero, so it never changes y.

Scalar prefetch: ``offsets`` live in SMEM (PrefetchScalarGridSpec) because
they steer the window *addresses* — the Mosaic-native way to index from data
(same mechanism megablox uses for expert ids).

VMEM budget (resident): x is held once, single-buffered, as
``(ncols + 2*extent)`` f32 plus a spare chunk; the value block
(``ndiags x rows`` in the storage dtype) and the y block (``rows`` f32) are
double-buffered by the grid pipeline. :func:`block_chunks` gives the row
block the largest whole number of sub-tiles that keeps all three plus
``VMEM_RESERVE`` inside the policy's ``vmem_budget_bytes``, and no larger
than an eighth of the rows (``MIN_STEPS``) so the pipeline has blocks to
prefetch behind compute; the kernel's scoped VMEM limit is that budget. At
HPCG's 104³ (27 diagonals, f32) x takes 4.6 MB and a block 48 chunks
(49,152 rows, 5.3 MB of values): 23 steps. A 13³ level is one step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import obs
from repro.core.tiling import DEFAULT_VMEM_BUDGET_BYTES

from .common import LANES, SUBLANES, VREG, interpret_mode, round_up

#: chunks in a sub-tile: the rows whose windows one slab load serves and
#: whose sums stay in registers across the diagonal loop
SUB = 8
#: a row block is at most this share of the rows: the grid pipeline
#: prefetches the next block's values while one computes
MIN_STEPS = 8
#: VMEM left to Mosaic's own scratch (spilled registers)
VMEM_RESERVE = 1 << 20


def lane_rows(nrows: int) -> int:
    """Rows of the lane-dense value layout: ``nrows`` padded up to whole
    sub-tiles, so every sub-tile the kernel runs holds only real data or
    zeros."""
    return round_up(max(nrows, 1), SUB * VREG)


def dia_lanes(data):
    """``(ndiags, nrows)`` diagonal values as the resident kernel reads them:
    ``(ndiags, lane_rows(nrows) // 128, 128)``, zero past ``nrows``. A numpy
    array gives a numpy array (the host build in ``to_dia``); a jax array
    gives a jax array (the per-call layout)."""
    nd, nrows = data.shape
    xp = np if isinstance(data, np.ndarray) else jnp
    out = xp.pad(data, ((0, 0), (0, lane_rows(nrows) - nrows)))
    return out.reshape(nd, -1, LANES)


def _windows(ref, s, n: int, *lead):
    """``(n, 8, 128)``: chunk ``t`` holds flat elements
    ``[s + 1024 t, s + 1024 (t + 1))`` of the chunked vector ``ref[*lead]``
    (shape ``(nchunks, 8, 128)``, row-major).

    Loads the aligned chunks ``s // 1024`` to ``s // 1024 + n`` in one slab,
    then rotates: sublanes by ``a = (s % 1024) // 128`` (row ``r`` of window
    ``t`` takes flat row ``r + a`` of chunk ``t``, from chunk ``t + 1`` once
    ``r + a`` passes 8) and lanes by ``b = s % 128`` (lane ``l`` takes lane
    ``l + b``, from the next flat row once ``l + b`` passes 128).
    """
    c, r = s // VREG, s % VREG
    a, b = r // LANES, r % LANES
    v = ref[(*lead, pl.ds(c, n + 1))]
    row = jax.lax.broadcasted_iota(jnp.int32, (n, SUBLANES, LANES), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (n, SUBLANES, LANES), 2)

    def rows_from(k):  # window row r holds flat row r + k, 0 <= k <= 8
        rolled = pltpu.roll(v, (SUBLANES - k) % SUBLANES, 1)
        return jnp.where(row + k < SUBLANES, rolled[:n], rolled[1:])

    shb = (LANES - b) % LANES
    lo = pltpu.roll(rows_from(a), shb, 2)
    hi = pltpu.roll(rows_from(a + 1), shb, 2)
    return jnp.where(lane + b < LANES, lo, hi)


def _window(ref, s, *lead):
    """The ``(8, 128)`` register holding flat elements ``[s, s + 1024)`` of
    ``ref[*lead]``: one window of :func:`_windows`."""
    return _windows(ref, s, 1, *lead)[0]


def _chunked(v, n: int, start: int = 0):
    """f32 copy of the 1-D (or batched 2-D) ``v`` placed at ``start`` inside
    ``n`` zero elements, reshaped to (…, n // 1024, 8, 128) chunks."""
    lead = v.shape[:-1]
    out = jnp.zeros((*lead, n), jnp.float32)
    out = out.at[..., start:start + v.shape[-1]].set(v.astype(jnp.float32))
    return out.reshape(*lead, n // VREG, SUBLANES, LANES)


def block_chunks(nchunks: int, ndiags: int, itemsize: int, x_chunks: int,
                 vmem_budget_bytes: int) -> int:
    """Chunks in one resident row block: whole sub-tiles, at most an
    ``MIN_STEPS``-th of ``nchunks`` (rounded up), and as many as fit the
    budget beside the resident x (``x_chunks``) with the value and y blocks
    double-buffered; never below one sub-tile."""
    per_chunk = 2 * VREG * (ndiags * itemsize + 4)
    fit = (vmem_budget_bytes - VMEM_RESERVE - x_chunks * VREG * 4) // per_chunk
    want = round_up(-(-nchunks // MIN_STEPS), SUB)
    return max(SUB, min(want, fit // SUB * SUB))


def _kernel(offs_ref, x_ref, data_ref, y_ref, *, ndiags: int, pre: int,
            block: int, nchunks: int):
    i = pl.program_id(0)
    # the last block may hang past the rows: run only its real sub-tiles
    nsub = jnp.minimum(block, nchunks - i * block) // SUB
    rows = SUB * SUBLANES

    def sub_tile(j, carry):
        r0 = (i * block + j * SUB) * VREG
        at = pl.multiple_of(j * rows, rows)

        def diag(d, acc):
            v = data_ref[d, pl.ds(at, rows), :].astype(jnp.float32)
            win = _windows(x_ref, r0 + offs_ref[d] + pre, SUB)
            return acc + v.reshape(SUB, SUBLANES, LANES) * win

        acc = jax.lax.fori_loop(0, ndiags, diag,
                                jnp.zeros((SUB, SUBLANES, LANES), jnp.float32),
                                unroll=min(ndiags, 32))
        y_ref[pl.ds(at, rows), :] = acc.reshape(rows, LANES)
        return carry

    jax.lax.fori_loop(0, nsub, sub_tile, 0)


@functools.partial(jax.jit, static_argnames=("nrows", "extent",
                                             "vmem_budget_bytes", "interpret"))
def dia_spmv_lanes(offsets: jnp.ndarray, lanes: jnp.ndarray, x: jnp.ndarray,
                   nrows: int, extent: int | None = None,
                   vmem_budget_bytes: int = DEFAULT_VMEM_BUDGET_BYTES,
                   interpret: bool | None = None) -> jnp.ndarray:
    """y = A @ x from the lane-dense values ``lanes = dia_lanes(data)``.

    Returns (nrows,). Assumes the values are 0 where a diagonal exits the
    matrix (guaranteed by ``repro.core.convert.to_dia``). ``extent`` is a
    static bound on ``max|offset|``; when given, the x padding shrinks from
    the worst case (every offset possible) to just the band actually used.
    """
    out_dtype = lanes.dtype
    if out_dtype == jnp.float16:  # Mosaic on a v5e loads no f16 vectors
        lanes = lanes.astype(jnp.float32)
    ndiags, nlr, _ = lanes.shape
    ncols = x.shape[0]
    nchunks = nlr // SUBLANES
    lrows = nchunks * VREG
    if lrows != lane_rows(nrows):
        raise ValueError(f"lane-dense values of shape {lanes.shape} do not "
                         f"hold {nrows} rows (dia_lanes lays them out)")

    # x padding so every window a sub-tile reads, rows r0 + off + pre up to
    # r0 + off + pre + (SUB + 1) * 1024, is in bounds: off in [-extent,
    # extent] (worst case nrows / ncols), r0 in [0, lrows - SUB * 1024]
    if extent is None:
        pre, reach = lrows, ncols
    else:
        pre, reach = min(int(extent), lrows), min(int(extent), ncols)
    n = round_up(pre + max(ncols, lrows + reach), VREG) + VREG
    block = block_chunks(nchunks, ndiags, jnp.dtype(lanes.dtype).itemsize,
                         n // VREG, vmem_budget_bytes)
    steps = -(-nchunks // block)
    obs.count("dia_spmv.grid_steps", steps)

    y = pl.pallas_call(
        functools.partial(_kernel, ndiags=ndiags, pre=pre, block=block,
                          nchunks=nchunks),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(steps,),
            in_specs=[
                # x resident: the whole chunked vector, one block, one buffer
                pl.BlockSpec((n // VREG, SUBLANES, LANES),
                             lambda i, offs: (0, 0, 0),
                             pipeline_mode=pl.Buffered(1)),
                # every diagonal's values for this row block
                pl.BlockSpec((ndiags, block * SUBLANES, LANES),
                             lambda i, offs: (0, i, 0)),
            ],
            out_specs=pl.BlockSpec((block * SUBLANES, LANES),
                                   lambda i, offs: (i, 0)),
        ),
        # rows of 128 up to nrows only: y then reshapes to (nrows,) in place
        out_shape=jax.ShapeDtypeStruct((-(-nrows // LANES), LANES), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=int(vmem_budget_bytes)),
        name="dia_spmv",
        interpret=interpret_mode(interpret),
    )(offsets, _chunked(x, n, pre), lanes)
    return y.reshape(-1)[:nrows].astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("extent", "vmem_budget_bytes",
                                             "interpret"))
def dia_spmv(offsets: jnp.ndarray, data: jnp.ndarray, x: jnp.ndarray,
             extent: int | None = None,
             vmem_budget_bytes: int = DEFAULT_VMEM_BUDGET_BYTES,
             interpret: bool | None = None) -> jnp.ndarray:
    """y = A @ x for DIA arrays. data: (ndiags, nrows), x: (ncols,).

    :func:`dia_spmv_lanes` on values laid out in this call (a copy of A):
    containers built by ``to_dia`` carry the layout and skip it.
    """
    return dia_spmv_lanes(offsets, dia_lanes(data), x, nrows=data.shape[1],
                          extent=extent, vmem_budget_bytes=vmem_budget_bytes,
                          interpret=interpret)


def _kernel_tiled(offs_ref, x_ref, dat_ref, y_ref, *, max_d: int,
                  col_tile: int, halo: int):
    i = pl.program_id(0)
    t = pl.program_id(1)
    row0 = i * VREG

    def body(d, acc):
        off = offs_ref[t, d]
        # row i of diagonal (t, d) sits at position i + off - t*ct in BOTH
        # haloed windows (data and x), so one clamped start serves both; the
        # clamp only fires when this (tile, diagonal, row-block) triple has
        # all-zero pre-masked data, and the halo regions are zero-filled
        p = jnp.clip(row0 + off - t * col_tile + halo,
                     0, col_tile + 2 * halo - VREG)
        return acc + _window(dat_ref, p, d) * _window(x_ref, p)

    acc = jax.lax.fori_loop(0, max_d, body,
                            jnp.zeros((SUBLANES, LANES), jnp.float32))

    @pl.when(t == 0)
    def _init():
        y_ref[...] = acc

    @pl.when(t != 0)
    def _acc():
        y_ref[...] += acc


@functools.partial(jax.jit, static_argnames=("nrows", "col_tile", "interpret"))
def dia_spmv_tiled(offs_t: jnp.ndarray, dat_w: jnp.ndarray, x: jnp.ndarray,
                   nrows: int, col_tile: int,
                   interpret: bool | None = None) -> jnp.ndarray:
    """y = A @ x over per-column-tile diagonal windows.

    offs_t: (ntiles, max_d) int32 global offsets (0-padded with zero data),
    dat_w: (ntiles, max_d, ct) per-tile diagonal *windows* (see
    ``build_dia_col_plan``), x: (ncols,). Both the x tile and the data
    windows carry a 1024-row halo of zeros on each side so any diagonal's
    shifted window intersecting the tile stays in-bounds.
    """
    ntiles, max_d, _ = dat_w.shape
    ncols = x.shape[0]
    h = VREG
    nrows_pad = round_up(max(nrows, 1), VREG)
    # haloed window [t*ct - h, t*ct + ct + h), plus one spare chunk
    n = round_up(col_tile + 2 * h, VREG) + VREG
    nch = n // VREG

    dat_c = _chunked(dat_w, n, h)                      # (ntiles, max_d, nch, 8, 128)
    xx = jnp.zeros((h + ntiles * col_tile + n,), x.dtype).at[h : h + ncols].set(x)
    win = (jnp.arange(n, dtype=jnp.int32)[None, :]
           + col_tile * jnp.arange(ntiles, dtype=jnp.int32)[:, None])
    x_c = _chunked(xx[win], n)                         # (ntiles, nch, 8, 128)

    y = pl.pallas_call(
        functools.partial(_kernel_tiled, max_d=max_d, col_tile=col_tile, halo=h),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nrows_pad // VREG, ntiles),
            in_specs=[
                pl.BlockSpec((None, nch, SUBLANES, LANES),
                             lambda i, t, offs: (t, 0, 0, 0)),
                pl.BlockSpec((None, max_d, nch, SUBLANES, LANES),
                             lambda i, t, offs: (t, 0, 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((SUBLANES, LANES), lambda i, t, offs: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((nrows_pad // LANES, LANES), jnp.float32),
        name="dia_spmv_tiled",
        interpret=interpret_mode(interpret),
    )(offs_t, x_c, dat_c)
    return y.reshape(-1)[:nrows].astype(dat_w.dtype)
