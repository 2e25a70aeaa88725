"""BSR SpMM Pallas kernel — block-sparse x dense on the MXU (megablox-style).

The paper's formats assume lane-level gathers; the MXU-native reformulation
is *block* sparsity: 128x128 blocks are exactly one systolic-array tile, and
the per-entry index array collapses to one block-column id per block — small
enough to live in SMEM. The scalar-prefetched ``bcols`` drive the BlockSpec
``index_map`` of X, so the "gather" happens in the memory pipeline (HBM→VMEM
DMA of the right X tile), not in the compute: this is the TPU answer to
SVE's ``svld1_gather_index`` and the same mechanism the megablox MoE kernels
use for expert offsets.

Grid = (nbrows, nftiles, bwidth); w is the innermost axis (``program_id(2)``)
so the y tile is revisited across the w dimension (sequential on TPU ⇒ safe
accumulate); invalid blocks — bcol = -1 padding, or any id outside
[0, nbcols) — are clamped to 0 for the DMA and their contribution masked:
predication at block granularity.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import interpret_mode


def _kernel(bcols_ref, blocks_ref, x_ref, y_ref, *, bwidth: int):
    b = pl.program_id(0)
    w = pl.program_id(2)  # innermost: y tile stays resident across the w loop

    @pl.when(w == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    bc = bcols_ref[b * bwidth + w]
    valid = (bc >= 0).astype(jnp.float32)
    blk = blocks_ref[0, 0].astype(jnp.float32)
    xt = x_ref[...].astype(jnp.float32)
    # f32 in full: the MXU's default is one bf16 pass (~1e-3 relative error)
    y_ref[...] += valid * jnp.dot(blk, xt, preferred_element_type=jnp.float32,
                                  precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("nf_tile", "interpret"))
def bsr_spmm(bcols: jnp.ndarray, blocks: jnp.ndarray, X: jnp.ndarray,
             nf_tile: int = 128, interpret: bool | None = None) -> jnp.ndarray:
    """Y = A @ X. bcols: (nbrows, bwidth) int32 (-1 pad); blocks:
    (nbrows, bwidth, bs, bs); X: (ncols, nf) with ncols >= max(bcols+1)*bs.
    Returns (nbrows*bs, nf) f32."""
    nbrows, bwidth = bcols.shape
    bs = blocks.shape[-1]
    ncols, nf = X.shape
    nbcols = -(-ncols // bs)
    nf_tile = min(nf_tile, nf)
    nf_pad = -(-nf // nf_tile) * nf_tile
    nftiles = nf_pad // nf_tile

    Xp = jnp.zeros((nbcols * bs, nf_pad), X.dtype).at[:ncols, :nf].set(X)
    # Invalidate out-of-range block-column ids on BOTH sides: the prefetched
    # ids drive the X BlockSpec DMA, so an id >= nbcols would stream a tile
    # from past the end of Xp. Map them to the -1 sentinel (masked, DMA
    # clamped to tile 0) rather than clipping to nbcols-1, which would
    # silently accumulate the wrong tile.
    flat = bcols.reshape(-1)
    flat_bcols = jnp.where(flat >= nbcols, -1, jnp.maximum(flat, -1))

    y = pl.pallas_call(
        functools.partial(_kernel, bwidth=bwidth),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nbrows, nftiles, bwidth),
            in_specs=[
                pl.BlockSpec((1, 1, bs, bs), lambda b, f, w, bc: (b, w, 0, 0)),
                # the scalar-prefetch-driven DMA: fetch X block-row bcols[b,w]
                pl.BlockSpec((bs, nf_tile),
                             lambda b, f, w, bc: (jnp.maximum(bc[b * bwidth + w], 0), f)),
            ],
            out_specs=pl.BlockSpec((bs, nf_tile), lambda b, f, w, bc: (b, f)),
        ),
        out_shape=jax.ShapeDtypeStruct((nbrows * bs, nf_pad), jnp.float32),
        name="bsr_spmm",
        interpret=interpret_mode(interpret),
    )(flat_bcols, blocks, Xp)
    return y[:, :nf]
