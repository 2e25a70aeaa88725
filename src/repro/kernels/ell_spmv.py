"""ELL SpMV Pallas kernels — regularised CSR for 8x128 lanes.

CSR's indptr walk (Algorithm 2) cannot fill TPU lanes; the Morpheus answer on
TPU is to *convert* (CSR -> ELL / SELL) and run a rectangular kernel, the
same move ArmPL's ``optimize`` step makes when it rewrites the matrix into
its internal layout. Each grid step owns a (block_rows x width) tile of
values and reduces its rows; padding lanes carry index -1 and are predicated
off with a mask (SVE ``pg`` analogue).

The x gather runs in XLA ahead of the kernel: Mosaic lowers only
same-shape 2-D gathers inside one register tile, not a gather from an
arbitrary-length x, so the kernel receives ``x[indices]`` already aligned
with the value panel it multiplies.

Rows run along the last (lane) axis of every panel and the entries of a
row down the sublanes, so a narrow ELL width is never padded out to 128
lanes in device memory; each grid step reduces its panel over the sublanes.

Two execution modes:

  - ``ell_spmv``       : the container's (nrows, width) arrays, transposed
    to (width, nrows); one grid step per block of rows.
  - ``ell_spmv_tiled`` : the convert-time column-tile plan
    (``core.tiling.build_ell_col_plan``): one dense (W, block_rows) panel
    per non-empty (row block, column tile) pair with tile-local indices.
    Panels are row-block-major, so the resident (1, block_rows) y block
    sees a contiguous run per row block: "row block changed" initialises,
    otherwise the partial sums accumulate.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import LANES, interpret_mode, round_up


def _gather(x, idx, base=0):
    """f32 ``x[base + idx]`` with the -1 pad lanes zeroed (XLA gather)."""
    valid = idx >= 0
    g = jnp.minimum(base + jnp.where(valid, idx.astype(jnp.int32), 0), x.shape[0] - 1)
    return jnp.where(valid, x[g].astype(jnp.float32), 0.0)


def _kernel(xg_ref, dat_ref, y_ref):
    y_ref[...] = jnp.sum(dat_ref[...] * xg_ref[...], axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def ell_spmv(indices: jnp.ndarray, data: jnp.ndarray, x: jnp.ndarray,
             block_rows: int = 256, interpret: bool | None = None) -> jnp.ndarray:
    """y = A @ x for ELL arrays. indices/data: (nrows, width), x: (ncols,)."""
    nrows, width = indices.shape
    br = min(block_rows, round_up(max(nrows, 1), LANES))
    nrows_pad = round_up(max(nrows, 1), br)

    xg = jnp.zeros((width, nrows_pad), jnp.float32).at[:, :nrows].set(
        _gather(x, indices.T))
    dat = jnp.zeros((width, nrows_pad), jnp.float32).at[:, :nrows].set(
        data.T.astype(jnp.float32))

    y = pl.pallas_call(
        _kernel,
        grid=(nrows_pad // br,),
        in_specs=[
            pl.BlockSpec((width, br), lambda i: (0, i)),
            pl.BlockSpec((width, br), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, br), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, nrows_pad), jnp.float32),
        name="ell_spmv",
        interpret=interpret_mode(interpret),
    )(xg, dat)
    return y[0, :nrows].astype(data.dtype)


def _kernel_tiled(prb_ref, xg_ref, dat_ref, y_ref):
    p = pl.program_id(0)
    acc = jnp.sum(dat_ref[...] * xg_ref[...], axis=0, keepdims=True)
    fresh = (p == 0) | (prb_ref[jnp.maximum(p - 1, 0)] != prb_ref[p])

    @pl.when(fresh)
    def _init():
        y_ref[...] = acc

    @pl.when(jnp.logical_not(fresh))
    def _acc():
        y_ref[...] += acc


@functools.partial(jax.jit, static_argnames=("nrows", "col_tile", "interpret"))
def ell_spmv_tiled(idx_t: jnp.ndarray, dat_t: jnp.ndarray, prb: jnp.ndarray,
                   pt: jnp.ndarray, x: jnp.ndarray, nrows: int, col_tile: int,
                   interpret: bool | None = None) -> jnp.ndarray:
    """y = A @ x over the per-(row block, column tile) ELL panels.

    idx_t/dat_t: (P, W, br) with *tile-local* column ids (-1 pad), prb/pt:
    (P,) each panel's row block and column tile, x: (ncols,). The panel
    grid axis is sequential on TPU: the (1, br) y block stays resident while
    a row block's panels accumulate.
    """
    npanels, width, br = idx_t.shape
    nrb = -(-max(nrows, 1) // br)
    xg = _gather(x, idx_t, pt[:, None, None] * col_tile)

    y = pl.pallas_call(
        _kernel_tiled,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(npanels,),
            in_specs=[
                pl.BlockSpec((None, width, br), lambda p, rb: (p, 0, 0)),
                pl.BlockSpec((None, width, br), lambda p, rb: (p, 0, 0)),
            ],
            out_specs=pl.BlockSpec((None, 1, br), lambda p, rb: (rb[p], 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((nrb, 1, br), jnp.float32),
        name="ell_spmv_tiled",
        interpret=interpret_mode(interpret),
    )(prb, xg, dat_t.astype(jnp.float32))
    return y.reshape(-1)[:nrows].astype(dat_t.dtype)
