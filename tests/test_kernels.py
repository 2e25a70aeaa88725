"""Pallas kernel sweeps: shapes x dtypes vs the ref.py pure-jnp oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ExecutionPolicy, SparseOperator, from_dense, obs
from repro.core import matrices as M
from repro.core.tiling import DEFAULT_VMEM_BUDGET_BYTES
from repro.kernels import ops, ref
from repro.kernels import dia_spmv as dia_mod
from repro.kernels.bsr_spmm import bsr_spmm
from repro.kernels.coo_spmv import build_scoo, coo_spmv, scoo_spmv
from repro.kernels.dia_spmv import dia_spmv, dia_spmv_lanes
from repro.kernels.ell_spmv import ell_spmv

SHAPES = [(32, 32), (100, 100), (257, 129), (512, 768)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _mat(n, m, seed, kind="mixed"):
    rng = np.random.default_rng(seed)
    if kind == "banded":
        import scipy.sparse as sp
        d = min(n, m)
        mat = sp.lil_matrix((n, m))
        for off in (-3, -1, 0, 1, 2):
            for i in range(n):
                j = i + off
                if 0 <= j < m:
                    mat[i, j] = rng.standard_normal()
        return mat.tocsr()
    import scipy.sparse as sp
    mat = sp.random(n, m, density=0.05, random_state=rng, format="csr")
    mat.data = rng.standard_normal(len(mat.data))
    return mat


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_dia_kernel_sweep(shape, dtype):
    n, m = shape
    s = _mat(n, m, 0, "banded")
    A = from_dense(s, "dia", dtype=dtype)
    x = jnp.asarray(np.random.default_rng(1).standard_normal(m), dtype)
    got = np.asarray(dia_spmv(A.offsets, A.data, x), np.float32)
    want = np.asarray(ref.dia_spmv_ref(A.offsets, A.data.astype(jnp.float32),
                                       x.astype(jnp.float32), A.shape))
    np.testing.assert_allclose(got, want, **_tol(dtype))


def _band(n, m, offsets, seed):
    """An n x m matrix with random values on the given diagonals."""
    import scipy.sparse as sp
    rng = np.random.default_rng(seed)
    return sp.diags([rng.standard_normal(n) for _ in offsets], list(offsets),
                    shape=(n, m), format="csr")


#: matrices whose resident DIA kernel runs cross its row-block edges: stencils
#: whose rows are (13³, 26³, 52³) and are not (32³, 32 chunks) a multiple of a
#: 1024-row chunk, a band reaching past one 8192-row block of a 3-step grid,
#: rectangles, and bf16 values
DIA_BLOCK_CASES = {
    "stencil13": lambda: (M.fdm27(13, 13, 13), jnp.float32),
    "stencil26": lambda: (M.fdm27(26, 26, 26), jnp.float32),
    "stencil32": lambda: (M.fdm27(32, 32, 32), jnp.float32),
    "stencil52": lambda: (M.fdm27(52, 52, 52), jnp.float32),
    "band_past_block": lambda: (_band(20000, 20000, (-9000, -1, 0, 1, 9000), 14),
                                jnp.float32),
    "rect_tall": lambda: (_band(5000, 3000, (-4500, -7, 0, 2999), 15), jnp.float32),
    "rect_wide": lambda: (_band(3000, 7000, (-2999, 0, 2500, 6500), 16), jnp.float32),
    "stencil26_bf16": lambda: (M.fdm27(26, 26, 26), jnp.bfloat16),
}


@pytest.mark.parametrize("case", sorted(DIA_BLOCK_CASES))
def test_dia_resident_blocks_match_reference(case):
    """The resident DIA kernel against the Algorithm-3 oracle, run every way
    a caller reaches it: from raw values (laid out per call), from the
    container's lane-dense values, with ``extent=None`` under ``jit``,
    through the row-masked wrapper SymGS runs, and vmapped as the SpMM
    lane."""
    s, dtype = DIA_BLOCK_CASES[case]()
    A = from_dense(s, "dia", dtype=dtype)
    assert A.lanes is not None
    n, m = A.shape
    rng = np.random.default_rng(17)
    x = jnp.asarray(rng.standard_normal(m), dtype)
    f32 = lambda v: np.asarray(v, np.float32)
    want = f32(ref.dia_spmv_ref(A.offsets, A.data.astype(jnp.float32),
                                x.astype(jnp.float32), A.shape))
    tol = _tol(dtype)
    np.testing.assert_allclose(f32(dia_spmv(A.offsets, A.data, x, extent=A.extent)),
                               want, **tol)
    np.testing.assert_allclose(
        f32(dia_spmv_lanes(A.offsets, A.lanes, x, nrows=n, extent=A.extent)),
        want, **tol)
    no_extent = jax.jit(lambda o, d, x: dia_spmv(o, d, x))
    np.testing.assert_allclose(f32(no_extent(A.offsets, A.data, x)), want, **tol)

    pol = ExecutionPolicy(backends=("pallas",), allow_fallback=False)
    assert ops.pallas_strategy(A, pol) == "resident"
    mask = rng.random(n) < 0.4
    np.testing.assert_allclose(
        f32(ops.dia_masked_spmv_pallas(A, x, jnp.asarray(mask), pol)),
        np.where(mask, want, 0), **tol)
    X = jnp.stack([x, -2 * x, x * x], axis=1)
    Y = f32(SparseOperator(A, pol) @ X)
    for k in range(X.shape[1]):
        col = f32(ref.dia_spmv_ref(A.offsets, A.data.astype(jnp.float32),
                                   X[:, k].astype(jnp.float32), A.shape))
        np.testing.assert_allclose(Y[:, k], col, **tol)
    if case == "band_past_block":  # the case reaches past a whole row block
        nch = dia_mod.lane_rows(n) // 1024
        block = dia_mod.block_chunks(nch, A.ndiags, 4, nch, DEFAULT_VMEM_BUDGET_BYTES)
        assert A.extent > block * 1024 and nch > block


@pytest.mark.parametrize("grid,steps", [(13, (1, 1)), (52, (4, 12)), (104, (10, 99))])
def test_dia_block_rule_fits_the_budget(grid, steps):
    """The row block comes from the shapes: one step at 13³, tens at 104³,
    and the resident x plus the double-buffered value and y blocks stay
    inside the policy's VMEM budget."""
    n = grid ** 3
    ext = grid * grid + grid + 1
    nch = dia_mod.lane_rows(n) // 1024
    x_chunks = -(-(ext + nch * 1024 + ext) // 1024) + 1
    block = dia_mod.block_chunks(nch, 27, 4, x_chunks, DEFAULT_VMEM_BUDGET_BYTES)
    assert block % dia_mod.SUB == 0
    assert steps[0] <= -(-nch // block) <= steps[1]
    vmem = 4 * 1024 * (x_chunks + 2 * block * (27 + 1))
    assert vmem + dia_mod.VMEM_RESERVE <= DEFAULT_VMEM_BUDGET_BYTES


def test_dia_grid_steps_counter():
    """A recording around the kernel's trace counts its grid steps."""
    s = _band(20000, 20000, (-9000, 0, 9000), 18)
    A = from_dense(s, "dia")
    x = jnp.ones((20000,), jnp.float32)
    with obs.recording() as rec:
        dia_spmv_lanes(A.offsets, A.lanes, x, nrows=20000, extent=A.extent + 1)
    assert rec.counts == {"dia_spmv.grid_steps": 3}


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_ell_kernel_sweep(shape, dtype):
    n, m = shape
    s = _mat(n, m, 2)
    A = from_dense(s, "ell", dtype=dtype)
    x = jnp.asarray(np.random.default_rng(3).standard_normal(m), dtype)
    got = np.asarray(ell_spmv(A.indices, A.data, x), np.float32)
    want = np.asarray(ref.ell_spmv_ref(A.indices, A.data.astype(jnp.float32),
                                       x.astype(jnp.float32)))
    np.testing.assert_allclose(got, want, **_tol(dtype))


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("tile", [64, 512])
def test_coo_kernel_sweep(shape, tile):
    n, m = shape
    s = _mat(n, m, 4)
    A = from_dense(s, "coo")
    x = jnp.asarray(np.random.default_rng(5).standard_normal(m), jnp.float32)
    got = np.asarray(coo_spmv(A.row, A.col, A.val, x, nrows=n, tile=tile))
    want = np.asarray(ref.coo_spmv_ref(A.row, A.col, A.val, x, n))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("slice_rows", [64, 256])
def test_scoo_kernel(slice_rows):
    n = 300
    s = _mat(n, n, 6)
    A = from_dense(s, "coo")
    x = jnp.asarray(np.random.default_rng(7).standard_normal(n), jnp.float32)
    rr, cc, vv, sid = build_scoo(A.row, A.col, A.val, n, slice_rows=slice_rows, tile=128)
    got = np.asarray(scoo_spmv(jnp.asarray(rr), jnp.asarray(cc), jnp.asarray(vv),
                               jnp.asarray(sid), x, nrows=n,
                               slice_rows=slice_rows, tile=128))
    want = np.asarray(ref.coo_spmv_ref(A.row, A.col, A.val, x, n))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def _coo_row_sum_case(case):
    """(scipy matrix, ``to_coo`` options) of one ``coo/plain`` row-sum case."""
    import scipy.sparse as sp
    rng = np.random.default_rng(11)
    if case in ("powerlaw-empty-rows", "first-last-empty"):
        n = 3000
        keep = rng.random(n) > 0.3 if case == "powerlaw-empty-rows" else np.ones(n)
        keep[[0, -1]] = case == "powerlaw-empty-rows"
        s = sp.diags(keep.astype(np.float64)) @ M.powerlaw(n, avg_nnz=12, seed=3)
        s.eliminate_zeros()
        return s.tocsr(), {}
    if case == "one-row":  # 1000 entries in row 7: one run across 8 blocks
        return sp.csr_matrix((rng.standard_normal(1000),
                              (np.full(1000, 7), np.arange(1000))),
                             shape=(40, 1000)), {}
    if case == "pad-to":
        return M.powerlaw(500, avg_nnz=6, seed=4), {"pad_to": 4096}
    if case == "degenerate":  # no entries: one sentinel entry is stored
        return sp.csr_matrix((3, 4)), {}
    # under the threshold: fewer than two entries a row on average
    return sp.random(500, 500, density=0.003, random_state=5, format="csr"), {}


@pytest.mark.parametrize("mode", ["eager", "jit", "vmap"])
@pytest.mark.parametrize("case", ["powerlaw-empty-rows", "first-last-empty",
                                  "one-row", "pad-to", "degenerate",
                                  "under-threshold"])
def test_coo_plain_row_sums(case, mode):
    """``coo/plain`` with its row pointer against scipy in f64 and against
    the scatter-add (the same container without the pointer), eagerly,
    under jit and under vmap (SpMM: every column bit-identical to its own
    call). The in-place row sums are checked on every case, and the lane
    engages them exactly where the rows are long enough; under that it is
    the scatter, bit for bit."""
    import dataclasses

    from repro.core.spmv import (SEGMENTED_MIN_ROW_NNZ, _sorted_row_sums,
                                 coo_spmv_plain)

    s, kw = _coo_row_sum_case(case)
    A = from_dense(s, "coo", **kw)
    scatter = dataclasses.replace(A, rowptr=None)
    nrows, ncols = A.shape
    X = np.random.default_rng(12).standard_normal((ncols, 3)).astype(np.float32)
    if mode == "vmap":
        lane = jax.jit(jax.vmap(coo_spmv_plain, in_axes=(None, 1), out_axes=1))
        Y, Y0 = lane(A, X), lane(scatter, X)
        for j in range(X.shape[1]):
            np.testing.assert_array_equal(Y[:, j], jax.jit(coo_spmv_plain)(A, X[:, j]))
        y, y0 = Y[:, 0], Y0[:, 0]
    else:
        lane = coo_spmv_plain if mode == "eager" else jax.jit(coo_spmv_plain)
        y, y0 = lane(A, X[:, 0]), lane(scatter, X[:, 0])
    x = X[:, 0].astype(np.float64)
    want = s @ x
    # a pairwise sum of a row's products: log2(row length) roundings at most
    bound = 32 * np.finfo(np.float32).eps * (abs(s) @ np.abs(x)) + 1e-30
    sums = _sorted_row_sums(A.row, A.val * X[:, 0][A.col], A.rowptr)
    for got in (y, y0, sums):
        assert np.all(np.abs(np.asarray(got, np.float64) - want) <= bound)
    assert np.all(np.abs(np.asarray(y, np.float64) - np.asarray(y0)) <= bound)
    if A.nnz < SEGMENTED_MIN_ROW_NNZ * nrows:
        np.testing.assert_array_equal(y, y0)


def test_coo_segmented_rows_counter():
    """A recording counts the rows of each traced ``coo/plain`` SpMV that
    sums rows in place: all of a graph-like matrix's, none of HPCG's
    injection transfers (one entry a row, or fewer)."""
    from repro.core.spmv import coo_spmv_plain
    from repro.solvers.mg import injection_operators

    g = from_dense(M.powerlaw(4096, avg_nnz=16, seed=5), "coo")
    with obs.recording() as rec:
        jax.jit(coo_spmv_plain)(g, jnp.ones((4096,), jnp.float32))
    assert rec.counts == {"coo_spmv.segmented_rows": 4096}
    R, P = injection_operators(16, 16, 16)
    for op in (R, P):
        with obs.recording() as rec:
            jax.jit(coo_spmv_plain)(op.container, jnp.ones((op.shape[1],), jnp.float32))
        assert rec.counts == {}


def test_coo_row_pointer_is_validated():
    """The container check refuses a row pointer that does not point into
    the sorted rows; ``to_coo``'s own passes, pad sentinels included."""
    import dataclasses

    from repro.core import SparseInputError, validate_container

    A = from_dense(M.powerlaw(300, avg_nnz=6, seed=2), "coo", pad_to=512)
    validate_container(A)
    for bad in (dataclasses.replace(A, rowptr=A.rowptr.at[5].add(1)),
                dataclasses.replace(A, row=A.row[::-1])):
        with pytest.raises(SparseInputError, match="rowptr"):
            validate_container(bad)


@pytest.mark.parametrize("bs", [8, 32])
@pytest.mark.parametrize("nf", [1, 9, 64])
def test_bsr_spmm_sweep(bs, nf):
    n = 160
    s = M.block_random(n, bs=bs, block_density=0.15, seed=8)
    A = from_dense(s, "bsr", bs=bs)
    X = jnp.asarray(np.random.default_rng(9).standard_normal((A.bcols.shape[0] * bs, nf)),
                    jnp.float32)
    got = np.asarray(bsr_spmm(A.bcols, A.blocks, X))
    want = np.asarray(ref.bsr_spmm_ref(A.bcols, A.blocks, X))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
@pytest.mark.parametrize("bs", [16, 32])
def test_bsr_spmm_matches_dense_oracle(dtype, bs):
    """bsr_spmm against the container's own dense view (not the jnp ref
    kernel): the MXU path must agree with plain A @ X for every storage
    dtype of the precision lane — blocks upcast to f32 inside the kernel,
    so narrow storage costs only the one quantisation at convert time."""
    n = 96
    s = M.block_random(n, bs=bs, block_density=0.2, seed=11)
    A = from_dense(s, "bsr", bs=bs, dtype=dtype)
    X = jnp.asarray(np.random.default_rng(12).standard_normal((n, 7)),
                    jnp.float32)
    Xp = jnp.zeros((A.bcols.shape[0] * bs, 7), jnp.float32).at[:n].set(X)
    got = np.asarray(bsr_spmm(A.bcols, A.blocks, Xp))[:n]
    dense = np.asarray(A.to_dense(), np.float32)  # quantised oracle
    want = dense @ np.asarray(X)
    # the oracle reads the same quantised storage and the kernel upcasts to
    # f32 before the dot, so the tolerance is f32-level for every dtype
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_bsr_spmm_out_of_range_bcol_is_masked():
    """An id >= nbcols must behave exactly like the -1 pad sentinel —
    masked, contributing nothing — not be clipped to the last valid tile
    (regression: the old clamp streamed tile nbcols-1 and silently
    accumulated the wrong X block)."""
    bs, nbcols, nf = 8, 3, 5
    rng = np.random.default_rng(13)
    X = jnp.asarray(rng.standard_normal((nbcols * bs, nf)), jnp.float32)
    blocks = jnp.asarray(rng.standard_normal((2, 2, bs, bs)), jnp.float32)
    poisoned = jnp.asarray([[0, nbcols], [nbcols + 7, 2]], jnp.int32)
    masked = jnp.asarray([[0, -1], [-1, 2]], jnp.int32)
    got = np.asarray(bsr_spmm(poisoned, blocks, X))
    want = np.asarray(bsr_spmm(masked, blocks, X))
    np.testing.assert_array_equal(got, want)
    # and the masked lanes really contribute nothing
    ref_rows = np.asarray(ref.bsr_spmm_ref(masked, blocks, X))
    np.testing.assert_allclose(got, ref_rows, rtol=2e-4, atol=2e-5)


def test_kernels_jit_cacheable():
    """Same shapes => no retrace (the ArmPL-handle analogy: compile once)."""
    s = _mat(128, 128, 10, "banded")
    A = from_dense(s, "dia")
    x = jnp.ones((128,), jnp.float32)
    f = jax.jit(lambda o, d, x: dia_spmv(o, d, x))
    y1 = f(A.offsets, A.data, x)
    y2 = f(A.offsets, A.data, x * 2)
    np.testing.assert_allclose(np.asarray(y2), 2 * np.asarray(y1), rtol=1e-5)


def test_block_sparse_weight_pruning():
    """sparsify: BSR-pruned linear matches the dense masked weight."""
    import jax.numpy as jnp
    from repro.sparsify import bsr_linear, prune_linear_to_bsr
    rng = np.random.default_rng(0)
    w = rng.standard_normal((96, 64)).astype(np.float32)
    A = prune_linear_to_bsr(w, density=0.5, bs=16)
    x = jnp.asarray(rng.standard_normal((4, 96)).astype(np.float32))
    y = np.asarray(bsr_linear(A, x))
    w_masked = np.asarray(A.to_dense()).T[:96, :64]
    np.testing.assert_allclose(y, np.asarray(x) @ w_masked, rtol=1e-3, atol=1e-4)
    # w^T is (64, 96) -> 4 block-rows x 6 block-cols; width can't exceed 6
    assert A.bwidth <= 6
    kept = int((np.asarray(A.bcols) >= 0).sum())
    assert kept <= 24  # never more blocks than exist
