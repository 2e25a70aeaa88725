"""Run-first auto-tuner + HPCG reproduction (paper §VII-B/D)."""
import numpy as np
import pytest

from repro.core import autotune_spmv
from repro.core import matrices as M
from repro.apps.hpcg import cg_solve, run_hpcg

import jax
import jax.numpy as jnp


def test_autotuner_returns_valid_choice():
    res = autotune_spmv(M.banded(256, 3, seed=0), iters=3, warmup=1)
    assert res.table, "empty timing table"
    assert (res.format, res.impl) in res.table
    assert res.time_us == min(res.table.values())
    assert res.matrix.format == res.format


def test_autotuner_structural_guards():
    """Power-law matrices must skip ELL (width blow-up); dense-diagonal
    matrices with many diagonals must skip DIA."""
    res = autotune_spmv(M.powerlaw(256, 6, seed=1), iters=2, warmup=1)
    skipped_fmts = {f for f, _, _ in res.skipped}
    assert "ell" in skipped_fmts
    res2 = autotune_spmv(M.random_uniform(600, 0.5, seed=2), iters=2, warmup=1,
                         dia_max_diags=512)
    skipped2 = {f for f, _, _ in res2.skipped}
    assert "dia" in skipped2


def test_structural_skip_bounds_the_dense_candidate():
    """Densifying is refused once the f32 n x m array passes the byte bound
    (HPCG's 104³ operator would need ~5 TB) and allowed below it."""
    import scipy.sparse as sp
    from repro.core.autotune import structural_skip
    from repro.core.select import DENSE_MAX_BYTES

    small = M.fdm27(4, 4, 4)
    assert structural_skip(small, "dense") is None
    side = int((DENSE_MAX_BYTES // 4) ** 0.5) + 1
    why = structural_skip(sp.eye(side, format="csr"), "dense")
    assert why is not None and why.startswith("dense=")
    assert structural_skip(small, "dense", dense_max_bytes=4 * 64 * 64 - 1)


def test_select_infeasible_agrees_on_the_dense_bound():
    """Pruning and racing refuse the same dense candidates."""
    import scipy.sparse as sp
    from repro.core import select
    from repro.core.autotune import structural_skip
    from repro.core.features import extract_features

    side = int((select.DENSE_MAX_BYTES // 4) ** 0.5) + 1
    for s in (M.fdm27(4, 4, 4), sp.eye(side, format="csr")):
        feats = extract_features(s)
        assert ((select.infeasible(feats, "dense") is None)
                == (structural_skip(s, "dense") is None))


def test_autotune_fails_when_a_candidate_raises(chain_failure_injector,
                                               fresh_health):
    """A candidate kernel that raises fails the tune: it is neither skipped
    nor timed as the plain backend under its own label."""
    from repro.core import DispatchKey
    from repro.core.errors import KernelExecutionError

    bad = DispatchKey("csr", "pallas")
    chain_failure_injector["fail"].add(bad)
    with pytest.raises(KernelExecutionError):
        autotune_spmv(M.banded(64, 2, seed=0), iters=1, warmup=1,
                      candidates=[DispatchKey("csr", "plain"), bad])
    attempts = chain_failure_injector["attempts"]
    assert attempts[-1] == bad  # nothing ran in its place after it failed


def test_autotune_skips_a_refused_backend():
    """A backend whose capability predicate refuses the container is skipped
    as unsupported; before strict racing it was timed as plain."""
    from repro.core import ExecutionPolicy

    res = autotune_spmv(M.banded(64, 2, seed=0), iters=1, warmup=1,
                        candidates=[("csr", "plain"), ("csr", "pallas")],
                        policy=ExecutionPolicy(accum_dtype="float64"))
    assert ("csr", "pallas") not in res.table
    assert ("csr", "pallas", "unsupported") in res.skipped


@pytest.mark.slow
def test_autotuner_prefers_dia_family_for_banded():
    """Fig 3 takeaway: structured/banded matrices leave the CSR default.
    (Timing on CPU; we assert the winner handles the matrix exactly.)"""
    res = autotune_spmv(M.tridiag(2048, seed=3), iters=3, warmup=1)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(2048), jnp.float32)
    from repro.core import spmv
    y = np.asarray(spmv(res.matrix, x, res.impl))
    ref = M.tridiag(2048, seed=3).toarray() @ np.asarray(x)
    np.testing.assert_allclose(y, ref, rtol=1e-4, atol=1e-4)


def test_cg_solves_spd_system():
    s = M.fdm27(4, 4, 4)
    n = s.shape[0]
    b = jnp.asarray(s @ np.ones(n), jnp.float32)
    from repro.core import from_dense, spmv
    A = from_dense(s, "csr")
    x, rs = cg_solve(lambda p: spmv(A, p, "plain"), b, 60)
    np.testing.assert_allclose(np.asarray(x), np.ones(n), atol=1e-3)


@pytest.mark.slow
def test_hpcg_end_to_end():
    res = run_hpcg(6, 6, 6, iters=20, reps=1, verbose=False)
    assert res.valid, res.rel_err
    assert res.ref_time_s > 0 and res.opt_time_s > 0
    assert res.table  # tuner table recorded
    # the tuned configuration can never be slower than what it measured:
    assert res.speedup > 0.5


@pytest.mark.slow
def test_format_distribution_runs():
    from repro.core import optimal_format_distribution
    dist = optimal_format_distribution(
        list(M.suite("small"))[:4], iters=2, warmup=1)
    assert len(dist) == 4
    assert all("/" in v for v in dist.values())
