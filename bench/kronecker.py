"""The Kronecker graph of Graph500 and GAP, made on the device from a seed.

GAP's ``GenerateKronEL``: each edge drawn picks one quadrant of the
adjacency matrix per level with probabilities (A, B, C, 1-A-B-C), one bit
of each endpoint per level; vertex ids are then permuted at random. As GAP
builds its ``kron`` graph, the edges are symmetrised and self-loops and
duplicates dropped.

GAP draws ``edgefactor * 2**scale`` edges, and how many distinct ones that
leaves varies with the seed, which would give each seed its own array sizes
(its own compiled programs, and its own work). So this generator draws 1/8
more and keeps the first ``undirected_edges`` distinct ones in draw order:
the same process, stopped at a fixed count. The result is a scipy CSR
matrix with unit values and sorted rows.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp


def key(seed: int):
    """A JAX key from any whole-number seed (``PRNGKey`` keeps only the low
    32 bits; this keeps them all)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(words)


@partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _edges(k, scale: int, edgefactor: int, initiator, kept: int):
    a, b, c = initiator
    n = 1 << scale
    m = (edgefactor << scale) * 9 // 8  # enough draws for ``kept`` unique edges
    k_edges, k_perm = jax.random.split(k)

    def level(d, st):
        src, dst = st
        u = jax.random.uniform(jax.random.fold_in(k_edges, d), (m,))
        sbit = u >= a + b
        dbit = jnp.where(sbit, u > a + b + c, u > a)
        return (src << 1) | sbit.astype(jnp.int32), (dst << 1) | dbit.astype(jnp.int32)

    zero = jnp.zeros((m,), jnp.int32)
    src, dst = jax.lax.fori_loop(0, scale, level, (zero, zero))
    perm = jax.random.permutation(k_perm, n).astype(jnp.int32)
    src, dst = perm[src], perm[dst]
    lo, hi = jnp.minimum(src, dst), jnp.maximum(src, dst)
    lo = jnp.where(lo == hi, n, lo)  # self-loops sort last and are dropped
    lo, hi, draw = jax.lax.sort((lo, hi, jnp.arange(m, dtype=jnp.int32)), num_keys=3)
    first = jnp.concatenate([jnp.ones((1,), bool),
                             (lo[1:] != lo[:-1]) | (hi[1:] != hi[:-1])]) & (lo < n)
    # each undirected edge at its first draw; keep the first ``kept`` drawn
    _, lo, hi = jax.lax.sort((jnp.where(first, draw, m), lo, hi), num_keys=1)
    lo, hi = lo[:kept], hi[:kept]
    row, col = jax.lax.sort((jnp.concatenate([lo, hi]), jnp.concatenate([hi, lo])),
                            num_keys=2)
    return row, col, jnp.sum(first)


def generate(seed: int, *, scale: int, edgefactor: int, initiator,
             undirected_edges: int) -> sp.csr_matrix:
    """The symmetric, loop-free Kronecker graph of ``2**scale`` vertices with
    exactly ``undirected_edges`` edges (``2 * undirected_edges`` stored)."""
    row, col, unique = jax.device_get(
        _edges(key(seed), int(scale), int(edgefactor),
               tuple(float(p) for p in initiator), int(undirected_edges)))
    if unique < undirected_edges:
        raise ValueError(f"seed {seed} drew {unique} distinct edges, fewer than "
                         f"the {undirected_edges} asked for")
    n = 1 << int(scale)
    indptr = np.zeros(n + 1, np.int32)
    np.cumsum(np.bincount(row, minlength=n), out=indptr[1:])
    return sp.csr_matrix((np.ones(len(col), np.float32), col, indptr),
                         shape=(n, n))
