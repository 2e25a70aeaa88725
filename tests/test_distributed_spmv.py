"""Distributed SpMV: core/distributed.py helper coverage (in-process) and
DistributedOperator conformance on a 4-device mesh (subprocess, fake host
devices) — dense-oracle checks across halo modes, heterogeneous per-rank
formats, masked matvec, per-partition tuning, bit-for-bit rowblock
validation, and the 16^3 distributed HPCG acceptance run."""
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from conftest import run_py
from repro.core import matrices as M
from repro.core.convert import to_coo, to_csr, to_dia
from repro.core.distributed import (
    _pad_coo,
    _pad_csr,
    _pad_dia,
    partition_rows,
    split_local_remote,
    split_rowblocks,
)

# ------------------------------------------------- helpers (single device) --


def test_partition_rows_even():
    assert partition_rows(8, 4) == [(0, 2), (2, 4), (4, 6), (6, 8)]
    assert partition_rows(6, 1) == [(0, 6)]
    assert partition_rows(0, 3) == [(0, 0), (0, 0), (0, 0)]


def test_partition_rows_rejects_uneven_when_even():
    with pytest.raises(ValueError, match="divisible"):
        partition_rows(7, 4)
    with pytest.raises(ValueError, match="divisible"):
        partition_rows(2, 4)  # nparts > nrows cannot split evenly


def test_partition_rows_rejects_bad_nparts():
    with pytest.raises(ValueError):
        partition_rows(8, 0)
    with pytest.raises(ValueError):
        partition_rows(8, -1)
    with pytest.raises(ValueError):
        partition_rows(-1, 2)


def test_partition_rows_balanced_uneven():
    """even=False: HPCG-style balanced split, sizes differ by at most one."""
    parts = partition_rows(10, 4, even=False)
    assert parts == [(0, 3), (3, 6), (6, 8), (8, 10)]
    sizes = [r1 - r0 for r0, r1 in parts]
    assert max(sizes) - min(sizes) <= 1 and sum(sizes) == 10


def test_partition_rows_balanced_more_parts_than_rows():
    parts = partition_rows(2, 4, even=False)
    assert parts == [(0, 1), (1, 2), (2, 2), (2, 2)]  # trailing parts empty
    assert parts[-1][0] == parts[-1][1]


def _reassemble(locals_, remotes, halo, shape, nparts):
    """Sum the split parts back into a dense matrix (the oracle identity)."""
    nr, nc = shape
    mr, mc = nr // nparts, nc // nparts
    out = np.zeros(shape)
    for p in range(nparts):
        r0, c0 = p * mr, p * mc
        out[r0:r0 + mr, c0:c0 + mc] += locals_[p].toarray()
        rem = remotes[p].toarray()
        if halo is None:
            out[r0:r0 + mr] += rem
        else:
            w0 = c0 - halo
            for (i, j) in zip(*rem.nonzero()):
                out[r0 + i, w0 + j] += rem[i, j]
    return out


@pytest.mark.parametrize("nparts,halo", [(4, "auto"), (4, None), (2, "auto")])
def test_split_local_remote_reassembles(nparts, halo):
    """local + remote parts must be an exact partition of the matrix."""
    s = M.banded(32, 3, seed=0)
    locals_, remotes, h = split_local_remote(s, nparts, halo=halo)
    if halo is None:
        assert h is None and all(r.shape == (32 // nparts, 32) for r in remotes)
    np.testing.assert_allclose(
        _reassemble(locals_, remotes, h, s.shape, nparts), s.toarray())


def _split_remote_lil(s, nparts, halo):
    """The LIL column-zeroing remote split the entry-wise one replaced."""
    import scipy.sparse as sp

    s = s.tocsr()
    m = s.shape[0] // nparts
    out = []
    for p in range(nparts):
        rem = s[p * m:(p + 1) * m].tolil(copy=True)
        rem[:, p * m:(p + 1) * m] = 0
        rem = rem.tocsr()
        rem.eliminate_zeros()
        if halo is not None:
            win = sp.lil_matrix((m, m + 2 * halo), dtype=s.dtype)
            rc = rem.tocoo()
            win[rc.row, rc.col - (p * m - halo)] = rc.data
            rem = win.tocsr()
        out.append(rem)
    return out


@pytest.mark.parametrize("halo", ["auto", None])
def test_split_local_remote_matches_lil_reference(halo):
    """Same remote matrices, entry for entry and in order, as the LIL split
    (explicit zeros dropped from the remote part in both)."""
    s = M.banded(48, 5, seed=3).tocsr()
    row11 = slice(s.indptr[11], s.indptr[12])
    s.data[row11][s.indices[row11] == 13] = 0.0  # stored zero, remote column
    assert s.nnz == M.banded(48, 5, seed=3).nnz
    _, remotes, h = split_local_remote(s, 4, halo=halo)
    for got, want in zip(remotes, _split_remote_lil(s, 4, h)):
        assert got.shape == want.shape
        np.testing.assert_array_equal(got.indptr, want.indptr)
        np.testing.assert_array_equal(got.indices, want.indices)
        np.testing.assert_array_equal(got.data, want.data)


def test_split_local_remote_halo_covers_banded_reach():
    """A bandwidth-3 matrix needs exactly halo=3 window columns."""
    s = M.banded(24, 3, seed=1)
    locals_, remotes, h = split_local_remote(s, 4)
    assert h == 3
    m = 24 // 4
    assert all(r.shape == (m, m + 2 * h) for r in remotes)
    # own columns are zeroed out of the remote part
    for p, r in enumerate(remotes):
        assert r[:, h:h + m].nnz == 0


def test_split_local_remote_spmv_oracle():
    """y = sum_p (local_p @ x_own + remote_p @ x_window) == A @ x."""
    rng = np.random.default_rng(2)
    s = M.banded(32, 4, seed=2) + M.random_uniform(32, 0.05, seed=3)
    s = sp.csr_matrix(s)
    x = rng.standard_normal(32)
    locals_, remotes, h = split_local_remote(s, 4)
    m = 8
    y = np.zeros(32)
    xp = np.concatenate([np.zeros(h), x, np.zeros(h)]) if h is not None else x
    for p in range(4):
        r0 = p * m
        y[r0:r0 + m] += locals_[p] @ x[r0:r0 + m]
        if h is not None:
            y[r0:r0 + m] += remotes[p] @ xp[r0:r0 + m + 2 * h]
        else:
            y[r0:r0 + m] += remotes[p] @ x
    np.testing.assert_allclose(y, s @ x, rtol=1e-10)


def test_split_local_remote_rectangular():
    """Injection restriction (nc x nf) splits along both axes; the z-major
    numbering makes it rank-aligned -> empty remote parts."""
    f2c = M.coarsen_injection(4, 4, 8)
    nf, nc = 128, len(f2c)
    R = sp.csr_matrix((np.ones(nc), (np.arange(nc), f2c)), shape=(nc, nf))
    locals_, remotes, h = split_local_remote(R, 4)
    assert sum(r.nnz for r in remotes) == 0
    np.testing.assert_allclose(
        _reassemble(locals_, remotes, h, R.shape, 4), R.toarray())


def test_split_rowblocks_exact_partition():
    s = M.banded(24, 2, seed=4)
    blocks = split_rowblocks(s, 4)
    assert all(b.shape == (6, 24) for b in blocks)
    np.testing.assert_allclose(sp.vstack(blocks).toarray(), s.toarray())


@pytest.mark.parametrize("fmt,conv,pad", [
    ("coo", to_coo, _pad_coo), ("csr", to_csr, _pad_csr),
    ("dia", to_dia, _pad_dia)])
def test_padding_round_trip(fmt, conv, pad):
    """_pad_* must be semantically invisible: to_dense is unchanged."""
    s = M.banded(16, 2, seed=5)
    c = conv(s, dtype=jnp.float32)
    grow = {"coo": lambda: c.row.shape[0] + 7,
            "csr": lambda: c.data.shape[0] + 7,
            "dia": lambda: c.offsets.shape[0] + 3}[fmt]()
    padded = pad(c, grow)
    np.testing.assert_allclose(np.asarray(padded.to_dense()),
                               np.asarray(c.to_dense()))
    # and padding to the current size (pad <= 0) is the identity
    assert pad(c, 0) is c


def test_rowblock_operator_refuses_tune():
    """rowblock exists for its bit-for-bit accumulation order; tuning it
    would silently swap in a split operator and lose the guarantee."""
    import jax
    from jax.sharding import Mesh
    from repro.distributed_op import DistributedOperator

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    op = DistributedOperator.build(M.banded(8, 1, seed=0), mesh, "data",
                                   local="csr", mode="rowblock")
    with pytest.raises(ValueError, match="rowblock"):
        op.tune()


def test_distributed_operator_is_a_pytree():
    """Flattened and rebuilt, the operator keeps its layout and applies
    identically, as a jit argument too; the host-side source is dropped."""
    import jax
    from jax.sharding import Mesh
    from repro.distributed_op import DistributedOperator

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    s = M.fdm27(4, 4, 4)
    op = DistributedOperator.build(s, mesh, "data", local="csr", remote="coo")
    leaves, tree = jax.tree.flatten(op)
    op2 = jax.tree.unflatten(tree, leaves)
    assert op2.describe() == op.describe() and op2.halo == op.halo
    assert op2.source is None
    x = op.device_put(np.random.default_rng(0).standard_normal(s.shape[1]))
    y = np.asarray(op @ x)
    np.testing.assert_array_equal(np.asarray(op2 @ x), y)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(lambda A, x: A @ x)(op, x)), y)


# ------------------------------------- DistributedOperator (4 fake devices) --


def test_distributed_operator_conformance_4way():
    """Dense-oracle grid over halo modes, heterogeneous per-rank formats,
    masked matvec, rectangular transfers, bitwise rowblock, and the
    per-partition tuner — one subprocess so jax initialises once."""
    code = """
import jax, numpy as np, jax.numpy as jnp
import scipy.sparse as sp
from jax.sharding import Mesh
from repro.core import matrices as M, as_operator
from repro.distributed_op import DistributedOperator, distribute, tune_partitions

mesh = Mesh(np.array(jax.devices()).reshape(4), ("data",))
s = M.fdm27(4, 4, 8)   # n=128
x = np.random.default_rng(0).standard_normal(128).astype(np.float32)
ref = s.toarray().astype(np.float32) @ x

cases = [
    ("dia", "coo", "auto"),
    ("csr", "csr", "allgather"),
    ("ell", "coo", "halo"),
    ("csr", None, "rowblock"),
    ([("dia", "plain"), ("csr", "plain"), ("ell", "plain"), ("coo", "plain")],
     "coo", "auto"),                      # four format groups, one per rank
]
for lf, rf, mode in cases:
    kw = dict(local=lf, mode=mode)
    if rf is not None:
        kw["remote"] = rf
    op = DistributedOperator.build(s, mesh, "data", **kw)
    y = np.asarray(op @ op.device_put(x))
    err = np.abs(y - ref).max() / np.abs(ref).max()
    assert err < 1e-5, (lf, rf, mode, err)
    if mode in ("auto", "halo"):
        assert op.halo is not None          # ppermute path exercised
mixed = DistributedOperator.build(
    s, mesh, "data",
    local=[("dia", "plain"), ("csr", "plain"), ("ell", "plain"), ("coo", "plain")],
    remote="coo", mode="auto")
assert len(mixed.local_groups) == 4, mixed.describe()

# masked matvec (the SymGS color-sweep primitive)
mask = np.random.default_rng(1).random(128) < 0.5
op = distribute(s, mesh, local="dia", remote="coo", mode="auto")
ym = np.asarray(op.masked_matvec(op.device_put(x),
                                 jax.device_put(jnp.asarray(mask), op.sharding())))
assert np.abs(ym - np.where(mask, ref, 0)).max() < 1e-4

# rectangular restriction: rank-aligned injection -> no remote groups
f2c = M.coarsen_injection(4, 4, 8)
nc = len(f2c)
R = sp.csr_matrix((np.ones(nc), (np.arange(nc), f2c)), shape=(nc, 128))
Rop = DistributedOperator.build(R, mesh, "data", local="csr", mode="auto")
assert not Rop.remote_groups
rc = np.asarray(Rop @ op.device_put(x))
np.testing.assert_allclose(rc, R @ x, rtol=1e-5)

# bit-for-bit: rowblock csr/plain == single-device csr/plain
A1 = as_operator(s, "csr").using("plain")
y1 = np.asarray(A1 @ jnp.asarray(x))
chk = DistributedOperator.build(s, mesh, "data", local="csr", mode="rowblock")
assert np.array_equal(y1, np.asarray(chk @ chk.device_put(x)))

# per-partition tuner returns one choice per rank and a valid operator
opt, table = tune_partitions(s, mesh)
assert len(opt.choices) == 4
assert all((p, "local") in table for p in range(4))
yt = np.asarray(opt @ opt.device_put(x))
assert np.abs(yt - ref).max() / np.abs(ref).max() < 1e-5
print("OK")
"""
    assert "OK" in run_py(code, devices=4)


def test_hpcg_distributed_16cubed_acceptance():
    """The PR acceptance run: on a 4-device mesh, distributed HPCG 16^3 PCG
    reaches rel residual <= 1e-6 and the csr/plain distributed SpMV is
    bit-for-bit identical to the single-device reference."""
    code = """
from repro.apps.hpcg import run_hpcg_distributed
res = run_hpcg_distributed(None, 16, 16, 16, iters=50, tol=1e-6,
                           timed=False, verbose=False)
assert res.bitwise, "distributed csr/plain SpMV != single-device (bitwise)"
assert res.rel_res <= 1e-6, res.rel_res
assert res.valid, (res.rel_err, res.rel_res)
assert res.pcg_iters <= 25, res.pcg_iters
print("OK", res.pcg_iters, res.rel_res)
"""
    assert "OK" in run_py(code, devices=4, timeout=560)


def test_hpcg_distributed_plain_dia_reference():
    """``reference="dia"``: the single-device oracle, the reference split and
    the untuned distributed hierarchy run plain DIA, and the rowblock DIA
    SpMV is bit-for-bit the single-device one."""
    code = """
from repro.apps.hpcg import run_hpcg_distributed
res = run_hpcg_distributed(None, 16, 16, 8, iters=50, timed=False,
                           verbose=False, reference="dia")
assert res.bitwise and res.valid, (res.bitwise, res.valid, res.rel_res)
assert "dist(dia+dia)" in res.mg_levels, res.mg_levels
print("OK")
"""
    assert "OK" in run_py(code, devices=4, timeout=560)


def test_distributed_symgs_matches_single_device():
    """One distributed multicolor SymGS sweep == the single-device sweep."""
    code = """
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import Mesh
from repro.core import matrices as M
from repro.distributed_op import DistributedOperator
from repro.solvers import SymGS

mesh = Mesh(np.array(jax.devices()).reshape(4), ("data",))
s = M.fdm27(4, 4, 4)
n = s.shape[0]
r = np.random.default_rng(0).standard_normal(n).astype(np.float32)
sm = SymGS.build(s, method="multicolor")
y1 = np.asarray(sm(jnp.asarray(r)))

op = DistributedOperator.build(s, mesh, "data", local="csr", remote="csr")
smd = sm.distribute(op)
yd = np.asarray(smd(op.device_put(r)))
assert np.abs(yd - y1).max() < 1e-5, np.abs(yd - y1).max()
print("OK")
"""
    assert "OK" in run_py(code, devices=4)


def test_distributed_symgs_reference_schedule_rejected():
    from repro.solvers import SymGS

    sm = SymGS.build(M.banded(8, 1, seed=0), method="reference")
    with pytest.raises(ValueError, match="multicolor"):
        sm.distribute(None)
