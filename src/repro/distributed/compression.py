"""Gradient compression for the DP all-reduce (QSGD-flavoured int8 with
error feedback) — a distributed-optimization trick for bandwidth-bound pods.

Scheme (per leaf, inside shard_map over the DP axis):
  1. residual-corrected gradient g' = g + err
  2. chunked int8 quantisation (per-chunk absmax scale)
  3. all_to_all the int8 shards (each worker owns 1/DP of the vector)
  4. local dequant + sum -> owned shard (exact f32 accumulation)
  5. all_gather the reduced shards (int8 again, one more quantisation)
  6. new err = g' - dequant(quant(g'))  (error feedback)

Wire bytes ~ 2N int8 vs ~8N for ring-f32-all-reduce: ~4x reduction.
CPU-host validation uses small DP meshes; the collective pattern is the one
a TPU pod runs.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map


def _quant(x: jnp.ndarray, chunk: int = 256) -> Tuple[jnp.ndarray, jnp.ndarray]:
    n = x.shape[0]
    npad = -(-n // chunk) * chunk
    xp = jnp.zeros((npad,), x.dtype).at[:n].set(x).reshape(-1, chunk)
    scale = jnp.max(jnp.abs(xp), axis=1, keepdims=True) / 127.0
    q = jnp.clip(jnp.round(xp / jnp.maximum(scale, 1e-12)), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _dequant(q: jnp.ndarray, scale: jnp.ndarray, n: int) -> jnp.ndarray:
    return (q.astype(jnp.float32) * scale).reshape(-1)[:n]


def int8_psum_mean(x: jnp.ndarray, axis_name: str, nparts: int) -> jnp.ndarray:
    """Mean over `axis_name` with int8 wire format. x: flat (n,) f32 with n
    divisible by nparts (caller pads)."""
    n = x.shape[0]
    shard = n // nparts
    # 1 quantise my full vector, split into worker shards
    q, s = _quant(x)
    chunk = q.shape[1]
    q = q.reshape(nparts, shard // chunk, chunk)
    s = s.reshape(nparts, shard // chunk, 1)
    # 2 all_to_all: I receive everyone's contribution to MY shard
    qt = jax.lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0, tiled=False)
    st = jax.lax.all_to_all(s, axis_name, split_axis=0, concat_axis=0, tiled=False)
    # qt: (nparts, shard//chunk, chunk) = per-source my-shard pieces
    mine = jnp.sum(qt.astype(jnp.float32) * st, axis=0) / nparts   # (shard//chunk, chunk)
    # 3 requantise + all_gather the reduced shards
    q2, s2 = _quant(mine.reshape(-1))
    qg = jax.lax.all_gather(q2, axis_name, tiled=False)            # (nparts, ...)
    sg = jax.lax.all_gather(s2, axis_name, tiled=False)
    out = (qg.astype(jnp.float32) * sg).reshape(-1)[:n]
    return out


class CompressedAllReduce:
    """Mean per-worker gradient vectors over a DP mesh axis with int8 wire
    format + error feedback.

    Inputs are *stacked* per-worker: vec (DP, n) sharded over `axis`; err has
    the same shape. Each worker adds its residual, quantises, participates in
    the all_to_all/all_gather pipeline, and keeps what the wire lost.
    """

    def __init__(self, mesh: Mesh, axis: str = "data", chunk: int = 256):
        self.mesh = mesh
        self.axis = axis
        self.nparts = mesh.shape[axis]
        self.chunk = chunk

    def padded_len(self, n: int) -> int:
        step = self.nparts * self.chunk
        return -(-n // step) * step

    def init_error(self, n: int):
        return jnp.zeros((self.nparts, self.padded_len(n)), jnp.float32)

    def __call__(self, vec_stacked: jnp.ndarray, err_stacked: jnp.ndarray):
        """vec/err: (DP, n_pad) f32 (sharded P(axis)). Returns
        (mean (n_pad,) replicated, new_err (DP, n_pad))."""

        def inner(v, e):
            v = v[0] + e[0]                       # local worker vector
            reduced = int8_psum_mean(v, self.axis, self.nparts)
            q, s = _quant(v, self.chunk)
            sent = _dequant(q, s, v.shape[0])
            return reduced[None], (v - sent)[None]

        fn = shard_map(inner, mesh=self.mesh,
                       in_specs=(P(self.axis), P(self.axis)),
                       out_specs=(P(self.axis), P(self.axis)), check_vma=False)
        red, new_err = fn(vec_stacked, err_stacked)
        return red.mean(axis=0), new_err  # all rows identical; mean collapses
