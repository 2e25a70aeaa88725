"""Native SELL-C-σ SpMV Pallas kernel — the CSR fast path on wide vectors.

Kreutzer et al.'s SELL-C-σ (PAPERS.md) regularises CSR for wide SIMD: rows
are sorted by nnz inside σ-windows and grouped into slices of C lanes, so a
slice's entries form dense C-wide *j-steps* (one vector per within-row
position) with almost no padding. This kernel runs that layout directly:

  - the grid walks blocks of ``jb`` j-steps; each block's (jb, C) index/data
    panels are dense (``core.tiling.build_scs_plan`` pads per bucket);
  - ``btile`` names the column tile each block's tile-local indices point
    into; the x gather ``x[btile * ct + idx]`` runs in XLA ahead of the
    kernel (Mosaic has no gather from an arbitrary-length vector), so every
    block arrives with its (jb, C) panel of x values;
  - the scalar-prefetched ``bwin`` steers the output *block spec*: which
    (sw, C) window of the permuted output the block accumulates into — the
    PrefetchScalarGridSpec mechanism ``dia_spmv`` uses for its offsets;
  - same-window products are combined with a local-slice compare per window
    slice (the COO kernel's ``svcmpeq`` translation, at slice rather than row
    granularity), reduced exactly in f32 on the VPU;
  - blocks are window-major, column-tile-minor, so output windows see
    contiguous runs: "window changed" initialises, otherwise accumulate.
    A resident matrix is simply the ``ntiles == 1`` case of the same
    kernel.

``csr``×``pallas`` dispatches through this kernel via the ``"scs"``
KernelPlan cached on the CSR container at convert time (its SELL-C-σ view),
which is what closes the paper's baseline-format gap in the dispatch table.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import interpret_mode


def _sum_rows(v):
    """(n, C) -> (1, C) by pairwise halving: a fixed association order, so a
    batched (vmapped) call rounds exactly like a single one — a plain
    ``sum(axis=0)`` leaves the order to the compiler, which picks it per
    program."""
    while v.shape[0] > 1:
        h = v.shape[0] // 2
        head = v[:h] + v[h:2 * h]
        v = jnp.concatenate([head, v[2 * h:]], axis=0) if v.shape[0] % 2 else head
    return v


def _kernel(bwin_ref, lsl_ref, xg_ref, dat_ref, y_ref, *, sw: int):
    b = pl.program_id(0)
    prod = dat_ref[...] * xg_ref[...]   # (jb, C); padding lanes carry x = 0
    lsl = lsl_ref[...]                  # (jb, 1) window-local slice per j-step
    contrib = jnp.concatenate(
        [_sum_rows(jnp.where(lsl == s, prod, 0.0)) for s in range(sw)],
        axis=0)                         # (sw, C)

    prev = bwin_ref[jnp.maximum(b - 1, 0)]
    fresh = (b == 0) | (prev != bwin_ref[b])

    @pl.when(fresh)
    def _init():
        y_ref[...] = contrib

    @pl.when(jnp.logical_not(fresh))
    def _acc():
        y_ref[...] += contrib


@functools.partial(jax.jit, static_argnames=("nrows", "col_tile", "C", "sw",
                                             "jb", "nwin", "interpret"))
def scs_spmv(btile, bwin, lsl, idx2, dat2, perm, x, *, nrows: int,
             col_tile: int, C: int, sw: int, jb: int, nwin: int,
             interpret: bool | None = None) -> jnp.ndarray:
    """y = A @ x over a ``build_scs_plan`` SELL-C-σ stream.

    Args:
        btile/bwin: (B,) int32 per-block column tile / output window.
        lsl: (B*jb,) int32 window-local slice id per j-step.
        idx2/dat2: (B*jb, C) tile-local columns (-1 pad) / values.
        perm: (nrows_pad,) σ-sorted row permutation (pad rows = nrows).
        x: (ncols,) dense vector.

    Returns (nrows,) in original row order.
    """
    nblocks = btile.shape[0]
    valid = idx2 >= 0
    gcol = (jnp.repeat(btile, jb)[:, None] * col_tile
            + jnp.where(valid, idx2.astype(jnp.int32), 0))
    xg = jnp.where(valid, x[jnp.minimum(gcol, x.shape[0] - 1)].astype(jnp.float32), 0.0)

    y2 = pl.pallas_call(
        functools.partial(_kernel, sw=sw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nblocks,),
            in_specs=[
                pl.BlockSpec((jb, 1), lambda b, bw: (b, 0)),
                pl.BlockSpec((jb, C), lambda b, bw: (b, 0)),
                pl.BlockSpec((jb, C), lambda b, bw: (b, 0)),
            ],
            out_specs=pl.BlockSpec((None, sw, C), lambda b, bw: (bw[b], 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((nwin, sw, C), jnp.float32),
        name="sell_spmv",
        interpret=interpret_mode(interpret),
    )(bwin, lsl.reshape(-1, 1), xg, dat2.astype(jnp.float32))

    # un-permute: y2.reshape(-1)[p] is the σ-sorted row at position p
    yp = y2.reshape(-1)[: perm.shape[0]]
    y = jnp.zeros((nrows + 1,), jnp.float32).at[jnp.minimum(perm, nrows)].set(yp)
    return y[:nrows].astype(dat2.dtype)


def scs_spmv_from_plan(plan, x, nrows: int, interpret: bool | None = None):
    """Dispatch-table adapter: run :func:`scs_spmv` from a ``"scs"`` plan."""
    btile, bwin, lsl, idx2, dat2, perm = plan.arrays
    ct, _, C, sw, jb, nwin = (int(v) for v in plan.meta)
    return scs_spmv(btile, bwin, lsl, idx2, dat2, perm, x, nrows=nrows,
                    col_tile=ct, C=C, sw=sw, jb=jb, nwin=nwin,
                    interpret=interpret)
