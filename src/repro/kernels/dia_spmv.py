"""DIA SpMV Pallas kernels — the paper's SVE outer-loop vectorisation on TPU.

Paper (§IV): vectorise the *row* loop (lanes = consecutive rows), iterate
diagonals sequentially, because (i) ``av`` is contiguous along rows for a
fixed diagonal and (ii) no horizontal reduction is needed. That maps 1:1 to
the TPU VPU: a grid over row-blocks, each block one (8, 128) vector register
of consecutive rows; the diagonal loop is a ``fori_loop`` whose ``x`` access
is a *dense shifted load* — the gather the SVE version needed
(``svld1_gather_index``) disappears entirely because x is pre-padded so every
shift is in-bounds (per-lane predication becomes "pad with zeros"; the zero
data entries contribute nothing).

The shifted load starts at an arbitrary element, which Mosaic cannot slice
directly (dynamic offsets must be tile-aligned). Vectors are therefore laid
out as ``(nchunks, 8, 128)`` register-sized chunks: :func:`_window` loads the
two aligned chunks that cover the window and shifts them into place with
sublane and lane rotations (``pltpu.roll``).

Two execution modes:

  - ``dia_spmv``       : resident-x. The pre/post x padding is sized by the
    *actual* offset extent ``max|offset|`` when given (much tighter than the
    worst-case ``nrows_pad`` pad for wide-but-thin band matrices).
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

VMEM budget: data block ndiags x 1024 rows f32 (27 diagonals: 108 KiB),
x resident = (ncols + 2*extent) x 4 — callers cap ncols via the policy
(ops.py falls back to the tiled plan or plain path); y block 4 KiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import LANES, SUBLANES, VREG, interpret_mode, round_up


def _window(ref, s, *lead):
    """The (8, 128) register holding flat elements ``[s, s + 1024)`` of the
    chunked vector ``ref[*lead]`` (shape ``(nchunks, 8, 128)``, row-major).

    Loads the aligned chunks ``s // 1024`` and the next one, then rotates:
    sublanes by ``a = (s % 1024) // 128`` (row ``r`` of the result takes flat
    row ``r + a``, from the second chunk once ``r + a`` passes 8) and lanes
    by ``b = s % 128`` (lane ``l`` takes lane ``l + b``, from the next flat
    row once ``l + b`` passes 128).
    """
    c, r = s // VREG, s % VREG
    a, b = r // LANES, r % LANES
    v = ref[(*lead, pl.ds(c, 2))]
    v0, v1 = v[0], v[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 1)

    def rows_from(k):  # result row r holds flat row r + k, 0 <= k <= 8
        sh = (SUBLANES - k) % SUBLANES
        return jnp.where(row + k < SUBLANES, pltpu.roll(v0, sh, 0),
                         pltpu.roll(v1, sh, 0))

    shb = (LANES - b) % LANES
    lo = pltpu.roll(rows_from(a), shb, 1)
    hi = pltpu.roll(rows_from(a + 1), shb, 1)
    return jnp.where(lane + b < LANES, lo, hi)


def _chunked(v, n: int, start: int = 0):
    """f32 copy of the 1-D (or batched 2-D) ``v`` placed at ``start`` inside
    ``n`` zero elements, reshaped to (…, n // 1024, 8, 128) chunks."""
    lead = v.shape[:-1]
    out = jnp.zeros((*lead, n), jnp.float32)
    out = out.at[..., start:start + v.shape[-1]].set(v.astype(jnp.float32))
    return out.reshape(*lead, n // VREG, SUBLANES, LANES)


def _kernel(offs_ref, x_ref, data_ref, y_ref, *, ndiags: int, pre: int):
    row0 = pl.program_id(0) * VREG

    def body(d, acc):
        return acc + data_ref[d] * _window(x_ref, row0 + offs_ref[d] + pre)

    y_ref[...] = jax.lax.fori_loop(0, ndiags, body,
                                   jnp.zeros((SUBLANES, LANES), jnp.float32))


@functools.partial(jax.jit, static_argnames=("extent", "interpret"))
def dia_spmv(offsets: jnp.ndarray, data: jnp.ndarray, x: jnp.ndarray,
             extent: int | None = None,
             interpret: bool | None = None) -> jnp.ndarray:
    """y = A @ x for DIA arrays. data: (ndiags, nrows), x: (ncols,).

    Returns (nrows,). Assumes ``data`` is 0 where the diagonal exits the
    matrix (guaranteed by ``repro.core.convert.to_dia``). ``extent`` is a
    static bound on ``max|offset|``; when given, the x padding shrinks from
    the worst case (every offset possible) to just the band actually used.
    """
    ndiags, nrows = data.shape
    ncols = x.shape[0]
    nrows_pad = round_up(max(nrows, 1), VREG)

    # pre/post padding so every shifted window row0+off+pre .. +1024 is
    # in-bounds: off in [-extent, extent] (worst case nrows_pad / ncols),
    # row0 in [0, nrows_pad-1024]; one spare chunk for _window's second load
    if extent is None:
        pre, reach = nrows_pad, ncols
    else:
        pre, reach = min(int(extent), nrows_pad), min(int(extent), ncols)
    n = round_up(pre + max(ncols, nrows_pad + reach), VREG) + VREG

    y = pl.pallas_call(
        functools.partial(_kernel, ndiags=ndiags, pre=pre),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nrows_pad // VREG,),
            in_specs=[
                # x resident: the whole chunked vector, one block
                pl.BlockSpec((n // VREG, SUBLANES, LANES), lambda i, offs: (0, 0, 0)),
                # diag panel: every diagonal's values for this row block
                pl.BlockSpec((ndiags, SUBLANES, LANES), lambda i, offs: (0, i, 0)),
            ],
            out_specs=pl.BlockSpec((SUBLANES, LANES), lambda i, offs: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((nrows_pad // LANES, LANES), jnp.float32),
        name="dia_spmv",
        interpret=interpret_mode(interpret),
    )(offsets, _chunked(x, n, pre), _chunked(data, nrows_pad).reshape(
        ndiags, nrows_pad // LANES, LANES))
    return y.reshape(-1)[:nrows].astype(data.dtype)


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
