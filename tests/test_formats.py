"""Format containers: conversion exactness + Plain SpMV vs dense oracle."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import available_impls, convert, from_dense, spmm, spmv
from repro.core import matrices as M

FORMATS = ["coo", "csr", "dia", "ell",
           # sell roundtrips over the whole suite recompile per shape (~8s);
           # the conformance grid + property tests keep fast-lane coverage
           pytest.param("sell", marks=pytest.mark.slow),
           "bsr", "dense"]


@pytest.mark.parametrize("fmt", FORMATS)
def test_to_dense_roundtrip(fmt, suite_small):
    for name, s in suite_small.items():
        A = from_dense(s, fmt)
        np.testing.assert_allclose(np.asarray(A.to_dense()),
                                   s.toarray().astype(np.float32),
                                   rtol=1e-5, atol=1e-5, err_msg=f"{name}/{fmt}")


@pytest.mark.parametrize("fmt", FORMATS)
def test_spmv_plain_matches_dense(fmt, suite_small):
    rng = np.random.default_rng(0)
    for name, s in suite_small.items():
        d = s.toarray().astype(np.float32)
        x = jnp.asarray(rng.standard_normal(d.shape[1]).astype(np.float32))
        y = np.asarray(spmv(from_dense(s, fmt), x, "plain"))
        ref = d @ np.asarray(x)
        scale = np.abs(ref).max() + 1e-9
        np.testing.assert_allclose(y / scale, ref / scale, atol=5e-5,
                                   err_msg=f"{name}/{fmt}")


def test_convert_between_formats():
    s = M.banded(96, 4, seed=1)
    A = from_dense(s, "csr")
    for fmt in ["coo", "csr", "dia", "ell", "sell", "bsr", "dense"]:
        B = convert(A, fmt)
        assert B.format == fmt
        np.testing.assert_allclose(np.asarray(B.to_dense()),
                                   np.asarray(A.to_dense()), rtol=1e-5, atol=1e-5)


def test_spmm_matches_dense():
    rng = np.random.default_rng(1)
    s = M.random_uniform(80, 0.05, seed=2)
    X = rng.standard_normal((80, 7)).astype(np.float32)
    ref = s.toarray() @ X
    for fmt in ["coo", "csr", "bsr", "ell"]:
        Y = np.asarray(spmm(from_dense(s, fmt), jnp.asarray(X)))
        np.testing.assert_allclose(Y, ref, rtol=1e-3, atol=1e-4, err_msg=fmt)


def test_coo_is_row_sorted(suite_small):
    for name, s in suite_small.items():
        A = from_dense(s, "coo")
        rows = np.asarray(A.row)
        assert (np.diff(rows) >= 0).all(), name


def test_sell_perm_is_permutation():
    s = M.powerlaw(100, 6, seed=0)
    A = from_dense(s, "sell")
    perm = np.asarray(A.perm)
    real = perm[perm < 100]
    assert sorted(real.tolist()) == list(range(100))


def test_registered_impls():
    for fmt in ["coo", "dia", "ell"]:
        impls = available_impls(fmt)
        assert "plain" in impls and "pallas" in impls and "dense" in impls, (fmt, impls)


def test_suite_iteration_order_is_pinned(suite_small):
    """``matrices.suite()`` iteration order is an explicit contract (corpus
    and selector accuracy numbers are fractions over suite cells): pin the
    exact small-suite sequence, and require ``suite_names`` to agree with
    what ``suite`` actually yields at every scale."""
    expected = [
        "banded_b3_n64_s0", "banded_b9_n64_s0", "tridiag_n64_s0",
        "random_d01_n64_s0", "random_d05_n64_s0", "powerlaw_n64_s0",
        "block32_n64_s0", "diagnoise_n64_s0",
        "banded_b3_n200_s0", "banded_b9_n200_s0", "tridiag_n200_s0",
        "random_d01_n200_s0", "random_d05_n200_s0", "powerlaw_n200_s0",
        "block32_n200_s0", "diagnoise_n200_s0",
        "fdm27_4x4x4",
    ]
    assert [name for name, _ in M.suite("small")] == expected
    assert M.suite_names("small") == expected
    assert list(suite_small) == expected  # the session fixture too
    # the bench scale agrees with its own declared order without building
    # matrices here (generators stay lazy): first cell + count
    bench = M.suite_names("bench")
    assert bench[0] == "banded_b3_n512_s0"
    assert len(bench) == len(set(bench)) == 8 * 3 * 3 + 2


def test_workspace_caches_handles():
    from repro.core import workspace
    ws = workspace()
    h0, m0 = ws.hits, ws.misses
    s = M.tridiag(64, seed=3)
    x = jnp.ones((64,), jnp.float32)
    y1 = ws.spmv(s, x, "dia", "plain")
    y2 = ws.spmv(s, x, "dia", "plain")
    assert ws.misses == m0 + 1 and ws.hits == h0 + 1
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2))


def _to_dia_data_loop(s):
    """The entry-by-entry DIA build the vectorised ``to_dia`` replaced."""
    coo = s.tocoo()
    offs = np.unique(coo.col.astype(np.int64) - coo.row.astype(np.int64))
    data = np.zeros((len(offs), s.shape[0]), np.float64)
    dmap = {int(o): i for i, o in enumerate(offs)}
    for r, c, v in zip(coo.row, coo.col, coo.data):
        data[dmap[int(c) - int(r)], r] += v
    return offs, data


def _block_random_loop(n, bs, block_density, seed):
    """The element-by-element generator the vectorised one replaced."""
    import scipy.sparse as sp

    rng = np.random.default_rng(seed)
    nb = -(-n // bs)
    mask = rng.random((nb, nb)) < block_density
    mask[np.arange(nb), np.arange(nb)] = True
    rows, cols, vals = [], [], []
    for br, bc in zip(*np.nonzero(mask)):
        blk = rng.standard_normal((bs, bs))
        for i in range(min(bs, n - br * bs)):
            for j in range(min(bs, n - bc * bs)):
                rows.append(br * bs + i), cols.append(bc * bs + j)
                vals.append(blk[i, j])
    return sp.csr_matrix((vals, (rows, cols)), shape=(n, n))


@pytest.mark.parametrize("name", ["fdm27", "duplicates", "rectangular"])
def test_to_dia_matches_loop_reference(name):
    """Same offsets and bit-identical data as the entry loop, including
    duplicate entries (accumulated in entry order)."""
    import scipy.sparse as sp

    if name == "fdm27":
        s = M.fdm27(5, 4, 3)
    elif name == "duplicates":
        s = sp.coo_matrix(([1.0, 2.5, -1.0, 0.25], ([0, 0, 3, 3], [1, 1, 0, 0])),
                          shape=(5, 5))
    else:
        s = M.random_uniform(40, 0.1, seed=3)[:, :25]
    offs, data = _to_dia_data_loop(s)
    A = from_dense(s, "dia", dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(A.offsets), offs)
    np.testing.assert_array_equal(np.asarray(A.data), data.astype(np.float32))


@pytest.mark.parametrize("n,bs,density,seed", [(160, 8, 0.15, 8), (100, 32, 0.3, 3)])
def test_block_random_matches_loop_reference(n, bs, density, seed):
    """Edge blocks clipped, same random stream: the identical matrix."""
    want = _block_random_loop(n, bs, density, seed)
    got = M.block_random(n, bs=bs, block_density=density, seed=seed)
    assert (got != want).nnz == 0
    np.testing.assert_array_equal(got.indices, want.indices)
    np.testing.assert_array_equal(got.data, want.data)


@pytest.mark.parametrize("bounds,n", [
    ([0, 3, 3, 7, 10], 10),       # an empty segment
    ([0, 0, 2, 5], 8),            # empty first segment, padding past the end
    ([0, 4], 4),
    ([0], 3),                     # no segments: every position is padding
])
def test_segment_ids_match_searchsorted(bounds, n):
    """The scatter-and-running-sum segment ids are the binary search's."""
    from repro.core.formats import segment_ids

    b = jnp.asarray(bounds, jnp.int32)
    want = np.searchsorted(np.asarray(bounds), np.arange(n), side="right") - 1
    np.testing.assert_array_equal(np.asarray(segment_ids(b, n)), want)


def _dia_plain_gather(A, x, row_mask=None):
    """The per-entry gather form of the plain DIA lane the slices replaced."""
    import jax

    nrows, ncols = A.shape
    i = jnp.arange(nrows, dtype=jnp.int32)
    mask = True if row_mask is None else row_mask

    def body(d, y):
        k = i + A.offsets[d]
        valid = (k >= 0) & (k < ncols) & mask
        return y + jnp.where(valid, A.data[d] * x[jnp.clip(k, 0, ncols - 1)], 0)

    return jax.lax.fori_loop(0, A.ndiags, body, jnp.zeros((nrows,), jnp.float32))


@pytest.mark.parametrize("shape", [(40, 40), (40, 25), (25, 40)])
def test_dia_plain_slices_match_gather(shape):
    """Reading each diagonal's x window as a slice of the zero-padded x is
    bit-for-bit the entry gather, masked and unmasked, square or not."""
    from repro.core.spmv import masked_spmv

    s = M.random_uniform(max(shape), 0.15, seed=4)[:shape[0], :shape[1]]
    A = from_dense(s, "dia")
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal(shape[1]).astype(np.float32))
    mask = jnp.asarray(rng.random(shape[0]) < 0.5)
    np.testing.assert_array_equal(np.asarray(spmv(A, x, "plain")),
                                  np.asarray(_dia_plain_gather(A, x)))
    np.testing.assert_array_equal(np.asarray(masked_spmv(A, x, mask, "plain")),
                                  np.asarray(_dia_plain_gather(A, x, mask)))


@pytest.mark.parametrize("fmt,kw", [("dia", {}), ("ell", {}), ("sell", {}),
                                    ("bsr", {"block_size": 8}), ("dense", {})])
@pytest.mark.parametrize("name", ["fdm27", "random", "rectangular"])
def test_container_to_scipy_matches_dense_round_trip(fmt, kw, name):
    """The sparse host view (no n x m array) is the dense round trip's CSR:
    same structure, sorted indices, bit-identical values, no stored zeros."""
    import scipy.sparse as sp

    from repro.core.convert import container_to_scipy

    s = {"fdm27": lambda: M.fdm27(6, 5, 4),
         "random": lambda: M.random_uniform(70, 0.1, seed=1),
         "rectangular": lambda: M.random_uniform(90, 0.08, seed=2)[:40]}[name]()
    A = from_dense(s, fmt, **kw)
    got = container_to_scipy(A)
    want = sp.csr_matrix(np.asarray(A.to_dense()))
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_array_equal(got.indptr, want.indptr)
    np.testing.assert_array_equal(got.indices, want.indices)
    np.testing.assert_array_equal(got.data, want.data)
