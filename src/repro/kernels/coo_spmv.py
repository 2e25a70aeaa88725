"""COO SpMV Pallas kernel — the paper's same-row accumulation, predicated.

Paper (§IV): the SVE COO kernel masks lanes whose ``ai`` equals ``ai(i)``
(``svcmpeq``), tree-reduces their products (``svaddv``) and issues a single
accumulation into ``y`` — i.e. *combine same-row products before writing*.

TPU has no scatter; the vector translation is: for a tile of T (row-sorted)
entries laid along the lanes, form the products p = av * x[aj] and compare
the tile's rows against every row of an output window at once:

    y_window[r] += sum_t [rows[t] == w0 + r] * p[t]     # (RW, T) -> (RW, 1)

The (RW, T) compare *is* the ``svcmpeq`` mask — for every window row at
once — and the lane reduction is the tree reduction, exact in f32. The
window w0 is the tile's slice (rows are sorted, Morpheus guarantees
sortedness before SpMV); cross-tile carries are safe because the TPU grid is
sequential per core, so the read-modify-write on the resident y block never
races. The x gather runs in XLA ahead of the kernel (Mosaic has no gather
from an arbitrary-length vector), so each tile arrives with ``x[aj]``.

Windowing modes (ops.py picks):
  - full  : RW = every row (jit-friendly: no value-dependent shapes) — for
            matrices up to a few thousand rows; the kernel sweeps the window
            in 512-row chunks so the compare mask stays small.
  - tiled : SCOO bucketed per (row slice, column tile)
            (``core.tiling.build_coo_col_plan``), RW = the static slice
            height; blocks are row-slice-major so the resident y window
            sees contiguous runs and "slice changed" is the init signal.
            ``scoo_spmv`` is its one-tile case over a ``build_scoo`` layout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import SUBLANES, interpret_mode, round_up

#: Output rows the full-window kernel compares per step of its chunk loop.
_FULL_CHUNK = 512


def _window_sum(local, prod, rw: int):
    """(rw, 1) per-window-row sums of the (1, T) ``prod`` lanes whose
    window-local row ``local`` matches — the ``svcmpeq`` + ``svaddv`` pair."""
    hit = local == jax.lax.broadcasted_iota(jnp.int32, (rw, local.shape[1]), 0)
    return jnp.sum(jnp.where(hit, prod, 0.0), axis=1, keepdims=True)


def _lanes(v, n: int, tile: int, fill=0):
    """Pad the 1-D ``v`` to ``n`` and view it as (n // tile, 1, tile) tiles."""
    out = jnp.full((n,), fill, v.dtype).at[: v.shape[0]].set(v)
    return out.reshape(n // tile, 1, tile)


def _kernel_full(row_ref, xg_ref, val_ref, y_ref, *, nchunks: int):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    rows = row_ref[...]
    prod = val_ref[...] * xg_ref[...]                  # (1, T)

    def chunk(k, carry):
        w0 = pl.multiple_of(k * _FULL_CHUNK, _FULL_CHUNK)
        y_ref[pl.ds(w0, _FULL_CHUNK), :] += _window_sum(rows - w0, prod, _FULL_CHUNK)
        return carry

    jax.lax.fori_loop(0, nchunks, chunk, 0)


@functools.partial(jax.jit, static_argnames=("nrows", "tile", "interpret"))
def coo_spmv(row: jnp.ndarray, col: jnp.ndarray, val: jnp.ndarray, x: jnp.ndarray,
             nrows: int, tile: int = 512, interpret: bool | None = None) -> jnp.ndarray:
    """Full-window mode. row must be sorted; pad tail rows == nrows are folded
    into a sentinel bucket and dropped."""
    nnz = row.shape[0]
    tile = min(tile, max(8, nnz))
    nnz_pad = round_up(nnz, tile)
    rw = round_up(nrows + 1, _FULL_CHUNK)  # window = all rows + sentinel bucket

    xg = x[col].astype(jnp.float32)
    lane_spec = pl.BlockSpec((None, 1, tile), lambda t: (t, 0, 0))
    y = pl.pallas_call(
        functools.partial(_kernel_full, nchunks=rw // _FULL_CHUNK),
        grid=(nnz_pad // tile,),
        in_specs=[lane_spec, lane_spec, lane_spec],
        out_specs=pl.BlockSpec((rw, 1), lambda t: (0, 0)),   # resident, accumulated
        out_shape=jax.ShapeDtypeStruct((rw, 1), jnp.float32),
        name="coo_spmv",
        interpret=interpret_mode(interpret),
    )(_lanes(row.astype(jnp.int32), nnz_pad, tile, nrows),
      _lanes(xg, nnz_pad, tile), _lanes(val.astype(jnp.float32), nnz_pad, tile))
    return y[:nrows, 0].astype(val.dtype)


def build_scoo(row, col, val, nrows: int, slice_rows: int = 512, tile: int = 512):
    """Host-side SCOO (sliced COO) layout: bucket entries by row-slice and pad
    each slice to a tile multiple, so each kernel tile touches one slice.
    This is the handle/'optimize' step of the workspace path."""
    import numpy as np

    row = np.asarray(row); col = np.asarray(col); val = np.asarray(val)
    keep = row < nrows
    row, col, val = row[keep], col[keep], val[keep]
    nsl = -(-nrows // slice_rows)
    rs, cs, vs, sids = [], [], [], []
    for s in range(nsl):
        m = (row >= s * slice_rows) & (row < (s + 1) * slice_rows)
        r, c, v = row[m], col[m], val[m]
        pad = -len(r) % tile if len(r) else tile
        rs.append(np.concatenate([r, np.full(pad, s * slice_rows, row.dtype)]))
        cs.append(np.concatenate([c, np.zeros(pad, col.dtype)]))
        vs.append(np.concatenate([v, np.zeros(pad, val.dtype)]))
        sids.extend([s] * ((len(r) + pad) // tile))
    return (np.concatenate(rs).astype(np.int32), np.concatenate(cs).astype(np.int32),
            np.concatenate(vs), np.asarray(sids, np.int32))


def scoo_spmv(row, col, val, slice_ids, x, nrows: int, slice_rows: int = 512,
              tile: int = 512, interpret: bool | None = None) -> jnp.ndarray:
    """Sliced mode over a :func:`build_scoo` layout: the tiled kernel with
    one column tile spanning all of x. The contribution of padding entries
    lands on the slice's first row with val=0, so it is harmless."""
    return scoo_spmv_tiled(row, col, val, slice_ids, jnp.zeros_like(slice_ids), x,
                           nrows=nrows, col_tile=x.shape[0],
                           slice_rows=slice_rows, tile=tile, interpret=interpret)


def _kernel_tiled(slice_ids_ref, row_ref, xg_ref, val_ref, y_ref, *, rw: int):
    t = pl.program_id(0)
    w0 = slice_ids_ref[t] * rw
    contrib = _window_sum(row_ref[...] - w0, val_ref[...] * xg_ref[...], rw)

    prev = slice_ids_ref[jnp.maximum(t - 1, 0)]
    fresh = (t == 0) | (prev != slice_ids_ref[t])

    @pl.when(fresh)
    def _init():
        y_ref[...] = contrib

    @pl.when(jnp.logical_not(fresh))
    def _acc():
        y_ref[...] += contrib


@functools.partial(jax.jit, static_argnames=("nrows", "slice_rows", "tile",
                                             "col_tile", "interpret"))
def scoo_spmv_tiled(row, col, val, slice_ids, ctile, x, nrows: int,
                    col_tile: int, slice_rows: int = 512,
                    tile: int = 512, interpret: bool | None = None) -> jnp.ndarray:
    """Column-tiled sliced mode over a ``build_coo_col_plan`` layout.

    ``col`` holds tile-local ids (possibly int16/int8-compressed — the tile
    width bounds their range); ``ctile`` (one per block) names the x tile a
    block's entries index, so the gather reads ``x[ctile * ct + col]``.
    """
    nblocks = slice_ids.shape[0]
    rw = slice_rows
    assert rw % SUBLANES == 0, "slice height must be a multiple of 8 rows"
    nrows_pad = round_up(max(nrows, 1), rw)
    gcol = jnp.repeat(ctile, tile) * col_tile + col.astype(jnp.int32)
    xg = x[jnp.minimum(gcol, x.shape[0] - 1)].astype(jnp.float32)

    lane_spec = pl.BlockSpec((None, 1, tile), lambda t, sid: (t, 0, 0))
    y = pl.pallas_call(
        functools.partial(_kernel_tiled, rw=rw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nblocks,),
            in_specs=[lane_spec, lane_spec, lane_spec],
            out_specs=pl.BlockSpec((rw, 1), lambda t, sid: (sid[t], 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((nrows_pad, 1), jnp.float32),
        name="scoo_spmv_tiled",
        interpret=interpret_mode(interpret),
    )(slice_ids, row.reshape(nblocks, 1, tile), xg.reshape(nblocks, 1, tile),
      val.astype(jnp.float32).reshape(nblocks, 1, tile))
    return y[:nrows, 0].astype(val.dtype)
