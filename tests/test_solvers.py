"""Solver-grade tests: SymGS symmetry + schedule equivalence, V-cycle
residual reduction, PCG-vs-CG iteration counts, and the full-HPCG
acceptance run (16^3, rel residual <= 1e-6 in <= 50 iterations, optimised
machinery bit-identical to the reference on csr/plain candidates)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DispatchKey, as_operator
from repro.core import matrices as M
from repro.solvers import (
    SymGS,
    build_mg,
    cg,
    cg_solve,
    greedy_coloring,
    injection_operators,
    pcg_solve,
)

# trimmed tuner candidates: keeps acceptance-test wall time sane while still
# exercising a real multi-format choice
FAST_CANDIDATES = (
    DispatchKey("csr", "plain"), DispatchKey("dia", "plain"),
    DispatchKey("dia", "pallas"), DispatchKey("ell", "plain"),
    DispatchKey("dense", "dense"),
)


def _residual(s, x, b):
    return float(np.linalg.norm(np.asarray(b) - s @ np.asarray(x, np.float64)))


# ------------------------------------------------------------------ SymGS ----

def test_greedy_coloring_is_proper():
    s = M.fdm27(5, 4, 3)
    colors = greedy_coloring(s)
    coo = s.tocoo()
    off = coo.row != coo.col
    assert (colors[coo.row[off]] != colors[coo.col[off]]).all()
    # the 27-point stencil is 8-colorable (2x2x2 parity classes)
    assert colors.max() + 1 == 8


def test_symgs_is_symmetric_operator():
    """M^-1 (sweep from zero) must be symmetric for both schedules — the
    property PCG needs from its preconditioner."""
    s = M.fdm27(3, 3, 3)
    n = s.shape[0]
    eye = np.eye(n, dtype=np.float32)
    for method in ("multicolor", "reference"):
        gs = SymGS.build(s, method=method)
        apply_all = jax.jit(jax.vmap(lambda r: gs(r)))
        Minv = np.asarray(apply_all(jnp.asarray(eye)))
        np.testing.assert_allclose(Minv, Minv.T, rtol=1e-4, atol=1e-6,
                                   err_msg=method)


def test_multicolor_equals_reference_in_color_order():
    """A multicolor sweep IS Gauss-Seidel under the color-sorted row order:
    permuting the system by that order and running the sequential reference
    sweep must give the same iterate."""
    s = M.fdm27(4, 4, 4).tocsr()
    n = s.shape[0]
    colors = greedy_coloring(s)
    perm = np.argsort(colors, kind="stable")
    sp_perm = s[perm][:, perm]
    rng = np.random.default_rng(0)
    r = rng.standard_normal(n).astype(np.float32)
    x0 = rng.standard_normal(n).astype(np.float32)

    mc = SymGS.build(s, method="multicolor")
    ref = SymGS.build(sp_perm, method="reference")
    x_mc = np.asarray(mc.sweep(jnp.asarray(r), jnp.asarray(x0)))
    x_ref = np.asarray(ref.sweep(jnp.asarray(r[perm]), jnp.asarray(x0[perm])))
    np.testing.assert_allclose(x_mc[perm], x_ref, rtol=1e-4, atol=1e-5)


def test_symgs_sweeps_reduce_residual():
    s = M.fdm27(6, 6, 6)
    n = s.shape[0]
    b = jnp.asarray(s @ np.ones(n), jnp.float32)
    for method in ("multicolor", "reference"):
        gs = SymGS.build(s, method=method)
        x = jnp.zeros(n, jnp.float32)
        res = [_residual(s, x, b)]
        for _ in range(4):
            x = gs.sweep(b, x)
            res.append(_residual(s, x, b))
        assert all(res[i + 1] < res[i] for i in range(4)), (method, res)


def test_symgs_retargets_with_operator():
    """with_operator swaps the SpMV backend without changing the math."""
    s = M.fdm27(4, 4, 4)
    b = jnp.asarray(s @ np.ones(s.shape[0]), jnp.float32)
    gs = SymGS.build(s)
    gs_dia = gs.with_operator(as_operator(s, "dia").using("plain"))
    np.testing.assert_allclose(np.asarray(gs(b)), np.asarray(gs_dia(b)),
                               rtol=1e-4, atol=1e-5)


# -------------------------------------------------------------- multigrid ----

def test_injection_operators_are_transposes():
    R, P = injection_operators(4, 4, 4)
    assert R.shape == (8, 64) and P.shape == (64, 8)
    np.testing.assert_array_equal(np.asarray(R.to_dense()).T,
                                  np.asarray(P.to_dense()))
    # injection: exactly one unit entry per coarse point
    assert np.asarray(R.to_dense()).sum() == 8


def test_vcycle_reduces_residual_monotonically():
    nx = ny = nz = 8
    s = M.fdm27(nx, ny, nz)
    n = s.shape[0]
    b = jnp.asarray(s @ np.ones(n), jnp.float32)
    mg = build_mg(nx, ny, nz, depth=3)
    assert mg.depth == 3
    step = jax.jit(lambda x, r: x + mg(r))
    x = jnp.zeros(n, jnp.float32)
    res = [_residual(s, x, b)]
    for _ in range(5):
        r = b - jnp.asarray(s @ np.asarray(x, np.float64), jnp.float32)
        x = step(x, r)
        res.append(_residual(s, x, b))
    assert all(res[i + 1] < res[i] for i in range(5)), res
    assert res[-1] < 5e-2 * res[0]  # and it actually converges


def test_vcycle_is_linear():
    """The V-cycle must be a LINEAR map (fixed sweep counts, no iterate-
    dependent branching) or PCG's theory breaks."""
    vc = build_mg(4, 4, 4, depth=2)
    mg = jax.jit(lambda r: vc(r))
    rng = np.random.default_rng(1)
    r1 = jnp.asarray(rng.standard_normal(64), jnp.float32)
    r2 = jnp.asarray(rng.standard_normal(64), jnp.float32)
    lhs = np.asarray(mg(2.0 * r1 - 3.0 * r2))
    rhs = 2.0 * np.asarray(mg(r1)) - 3.0 * np.asarray(mg(r2))
    np.testing.assert_allclose(lhs, rhs, rtol=1e-3, atol=1e-4)


# --------------------------------------------------------------------- CG ----

def test_cg_tolerance_stopping():
    s = M.fdm27(6, 6, 6)
    n = s.shape[0]
    b = jnp.asarray(s @ np.ones(n), jnp.float32)
    A = as_operator(s, "csr").using("plain")
    info = cg(A, b, tol=1e-6, maxiter=200)
    assert float(info.rel_res) <= 1e-6
    assert 0 < int(info.iters) < 200
    np.testing.assert_allclose(np.asarray(info.x), np.ones(n), atol=1e-3)


def test_pcg_beats_plain_cg_iterations():
    """Satellite criterion: at tol 1e-6, MG-preconditioned CG takes strictly
    fewer iterations than plain CG."""
    nx = ny = nz = 10
    s = M.fdm27(nx, ny, nz)
    n = s.shape[0]
    b = jnp.asarray(s @ np.ones(n), jnp.float32)
    A = as_operator(s, "csr").using("plain")
    mg = build_mg(nx, ny, nz, depth=2)
    plain = cg(A, b, tol=1e-6, maxiter=500)
    pre = cg(A, b, tol=1e-6, maxiter=500, precond=mg)
    assert float(plain.rel_res) <= 1e-6 and float(pre.rel_res) <= 1e-6
    assert int(pre.iters) < int(plain.iters), (int(pre.iters), int(plain.iters))


def test_pcg_solve_matches_cg_solve_unpreconditioned():
    """pcg_solve with no preconditioner degenerates to the classic loop."""
    s = M.fdm27(4, 4, 4)
    n = s.shape[0]
    b = jnp.asarray(s @ np.ones(n), jnp.float32)
    A = as_operator(s, "csr").using("plain")
    x1, _ = cg_solve(lambda p: A @ p, b, 20)
    x2, _ = pcg_solve(lambda p: A @ p, b, 20)
    np.testing.assert_allclose(np.asarray(x1), np.asarray(x2), rtol=1e-5, atol=1e-6)


# ------------------------------------------------------- HPCG acceptance ----

def test_full_hpcg_16cubed_acceptance():
    """The issue's acceptance bar: preconditioned CG on 16^3 reaches rel
    residual <= 1e-6 within 50 iterations, and the optimised (auto-tuned)
    machinery re-run on csr/plain candidates is bit-for-bit the reference."""
    from repro.apps.hpcg import run_hpcg

    res = run_hpcg(16, 16, 16, iters=50, reps=1, verbose=False, timed=False,
                   candidates=FAST_CANDIDATES)
    assert res.precond
    assert res.pcg_iters <= 50, res.pcg_iters
    assert res.rel_res <= 1e-6, res.rel_res
    assert res.bitwise, "optimised pipeline drifted from reference on csr/plain"
    assert res.valid and res.rel_err < 1e-3, (res.valid, res.rel_err)
    assert res.mg_levels  # per-level choices were recorded


@pytest.mark.parametrize("precond", [True, False])
def test_hpcg_solvers_embed_no_matrix_data(precond):
    """The jitted HPCG solvers take the operator and the hierarchy as
    arguments: the lowered program does not grow with the matrix, as it
    would if their arrays were baked in as constants."""
    from repro.apps.hpcg import _solver_pair

    def lowered_text(g):
        A = as_operator(M.fdm27(g, g, g), "csr").using("plain")
        mg = build_mg(g, g, g, depth=2, fmt="csr") if precond else None
        b = jnp.ones((g ** 3,), jnp.float32)
        _, conv = _solver_pair(A, mg, 5, 1e-6)
        n_leaves = len(jax.tree.leaves((A, mg, b)))
        lowered = conv.func.lower(*conv.args, b)
        assert len(jax.tree.leaves(lowered.args_info)) == n_leaves
        return lowered.as_text()

    small, large = lowered_text(8), lowered_text(16)
    assert len(large) < 1.05 * len(small), (len(small), len(large))


def test_vcycle_round_trips_as_a_pytree():
    """A hierarchy flattened and rebuilt applies bit-identically, under jit
    as an argument as well as eagerly."""
    mg = build_mg(8, 8, 8, depth=2, fmt="csr")
    leaves, tree = jax.tree.flatten(mg)
    mg2 = jax.tree.unflatten(tree, leaves)
    assert mg2.describe() == mg.describe() and mg2.pre == mg.pre
    r = jnp.asarray(np.random.default_rng(0).standard_normal(512), jnp.float32)
    y = np.asarray(mg(r))
    np.testing.assert_array_equal(np.asarray(mg2(r)), y)
    np.testing.assert_array_equal(
        np.asarray(jax.jit(lambda m, r: m(r))(mg, r)), y)


def test_hpcg_with_a_plain_dia_reference():
    """``reference="dia"``: the reference solve, the replay and the
    untuned hierarchy run plain DIA, and the replay is still bit-for-bit."""
    from repro.apps.hpcg import run_hpcg

    res = run_hpcg(8, 8, 8, iters=50, verbose=False, timed=False, depth=2,
                   candidates=FAST_CANDIDATES, reference="dia")
    assert res.bitwise and res.valid, (res.bitwise, res.valid, res.rel_res)


def test_retuned_installs_the_finest_operator():
    """``finest=`` takes the place of the finest level's race; the coarser
    levels are still tuned."""
    mg = build_mg(8, 8, 8, depth=2, fmt="csr")
    op = as_operator(M.fdm27(8, 8, 8), "dia").using("plain")
    tuned = mg.retuned((DispatchKey("ell", "plain"),), finest=op)
    assert tuned.levels[0].A is op and tuned.levels[0].smoother.A is op
    assert tuned.levels[1].A.format == "ell"
