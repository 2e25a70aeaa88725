"""Compile rehearsal: every Pallas SpMV kernel lowered natively
(``interpret=False``) for a described TPU v5e chip, at the shapes of the
64³ HPCG stencil.

Interpret-mode tests check what a kernel computes; they cannot see what the
chip's compiler refuses (unaligned dynamic slices, block shapes off the
8x128 tiling, gathers Mosaic cannot lower). These tests compile each entry
point against ``jax.ShapeDtypeStruct``s placed on one described chip, so a
refusal fails here instead of on the chip. Nothing runs: a compile that
passes says nothing about results or speed.

The topology is described inside a module fixture (never at import), so
every test worker collects the same tests and only the worker given this
file loads the TPU compiler.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core import ExecutionPolicy, as_operator
from repro.core import matrices as M
from repro.kernels import ops
from repro.kernels.bsr_spmm import bsr_spmm
from repro.kernels.coo_spmv import coo_spmv, scoo_spmv_tiled
from repro.kernels.dia_spmv import dia_spmv, dia_spmv_tiled
from repro.kernels.ell_spmv import ell_spmv, ell_spmv_tiled
from repro.kernels.sell_spmv import scs_spmv_from_plan

GRID = 64
#: a resident cap below 64³'s column count: every plan-carrying format builds
#: and dispatches its column-tiled plan (DIA's 4x residency rule included)
TILED = ExecutionPolicy(backends=("pallas",), allow_fallback=False,
                        max_resident_cols=1 << 16)
RESIDENT = ExecutionPolicy(backends=("pallas",), allow_fallback=False)
#: COO's full-window kernel holds every row in its window: the largest
#: stencil under the policy's one-hot row cap (8000 rows)
COO_RESIDENT_GRID = 20
#: BSR SpMV/SpMM shapes of a 32768-row, 32-edge, 5%-block-dense matrix
BSR_ROWS, BSR_BS, BSR_WIDTH = 32768, 32, 64
#: PageRank's graph (GAP kron at scale 20): stored entries and rows
GRAPH_NNZ, GRAPH_ROWS = 31_400_000, 1 << 20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache but
    # can never be read back without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def containers():
    """Real 64³ containers (host-built), keyed ``(fmt, strategy)``."""
    s = M.fdm27(GRID, GRID, GRID)
    out = {}
    for fmt in ("dia", "ell", "csr", "sell", "coo"):
        for name, pol in (("resident", RESIDENT), ("tiled", TILED)):
            if (fmt, name) == ("coo", "resident"):
                continue
            A = as_operator(s, fmt, policy=pol).container
            assert ops.pallas_strategy(A, pol) == name, (fmt, name)
            out[fmt, name] = A
    g = COO_RESIDENT_GRID
    out["coo", "resident"] = as_operator(M.fdm27(g, g, g), "coo").container
    return out


def _struct(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the Mosaic kernel is in
    return compiled


def _spmv_case(case, A, x):
    """The kernel entry point of ``case`` applied to container ``A``."""
    n = A.shape[0]
    if case == "dia-resident":
        return dia_spmv(A.offsets, A.data, x, extent=A.extent, interpret=False)
    if case == "dia-tiled":
        offs_t, dat_w = A.plan.arrays
        return dia_spmv_tiled(offs_t, dat_w, x, nrows=n, col_tile=A.plan.ct,
                              interpret=False)
    if case == "ell-resident":
        return ell_spmv(A.indices, A.data, x, interpret=False)
    if case == "ell-tiled":
        idx_t, dat_t, prb, pt = A.plan.arrays
        return ell_spmv_tiled(idx_t, dat_t, prb, pt, x, nrows=n,
                              col_tile=A.plan.ct, interpret=False)
    if case == "coo-resident":
        return coo_spmv(A.row, A.col, A.val, x, nrows=n, interpret=False)
    if case == "coo-tiled":
        row, col, val, sid, ctile = A.plan.arrays
        ct, _, slice_rows, tile = A.plan.meta
        return scoo_spmv_tiled(row, col, val, sid, ctile, x, nrows=n,
                               col_tile=ct, slice_rows=slice_rows, tile=tile,
                               interpret=False)
    # csr and sell share the SELL-C-σ stream kernel
    return scs_spmv_from_plan(A.plan, x, nrows=n, interpret=False)


SPMV_CASES = [("dia", "resident"), ("dia", "tiled"), ("ell", "resident"),
              ("ell", "tiled"), ("coo", "resident"), ("coo", "tiled"),
              ("csr", "resident"), ("csr", "tiled"), ("sell", "tiled")]


@pytest.mark.parametrize("fmt,strategy", SPMV_CASES)
def test_spmv_kernel_compiles_for_v5e(fmt, strategy, containers, one_chip):
    A = containers[fmt, strategy]
    case = f"{fmt}-{strategy}" if fmt in ("dia", "ell", "coo") else "scs"
    x = jax.ShapeDtypeStruct((A.shape[1],), jnp.float32, sharding=one_chip)
    _compile(lambda A, x: _spmv_case(case, A, x), _struct(A, one_chip), x)


@pytest.mark.parametrize("fmt,strategy", [("dia", "resident"), ("dia", "tiled"),
                                          ("ell", "resident"), ("ell", "tiled")])
def test_masked_kernel_compiles_for_v5e(fmt, strategy, containers, one_chip,
                                        monkeypatch):
    """The row-masked lanes exactly as dispatch runs them (output masking
    around the same kernels); the dispatch wrappers decide interpret mode
    from the default backend, so the test reports a TPU."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    A = containers[fmt, strategy]
    pol = RESIDENT if strategy == "resident" else TILED
    masked = {"dia": ops.dia_masked_spmv_pallas, "ell": ops.ell_masked_spmv_pallas}[fmt]
    x = jax.ShapeDtypeStruct((A.shape[1],), jnp.float32, sharding=one_chip)
    m = jax.ShapeDtypeStruct((A.shape[0],), jnp.bool_, sharding=one_chip)
    _compile(lambda A, x, m: masked(A, x, m, pol), _struct(A, one_chip), x, m)


@pytest.mark.parametrize("grid,masked,dtype", [
    (13, False, jnp.float32), (13, True, jnp.float32),
    (104, False, jnp.float32), (104, True, jnp.float32),
    (13, False, jnp.bfloat16), (13, False, jnp.float16)])
def test_dia_resident_blocks_compile_for_v5e(grid, masked, dtype, one_chip,
                                             monkeypatch):
    """The resident DIA kernel at the widths of the HPCG cells' coarsest and
    finest levels, plain and masked, and in the narrow storage dtypes, as
    dispatch runs it on a container's lane-dense values: Mosaic checks the
    row block's VMEM use and the value loads, and the program holds no copy
    of A (its temporaries stay far below the values)."""
    from repro.core.formats import DIA
    from repro.kernels.dia_spmv import lane_rows

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n = grid ** 3
    S = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt,
                                                           sharding=one_chip)
    lanes = S((27, lane_rows(n) // 128, 128), dtype)
    A = DIA(S((27,), jnp.int32), S((27, n), dtype), (n, n), None, lanes,
            extent=grid * grid + grid + 1)
    assert ops.pallas_strategy(A, RESIDENT) == "resident"
    if masked:
        fn = lambda A, x, m: ops.dia_masked_spmv_pallas(A, x, m, RESIDENT)
    else:
        fn = lambda A, x, m: ops.dia_spmv_pallas(A, x, RESIDENT)
    compiled = _compile(fn, A, S((n,)), S((n,), jnp.bool_))
    assert compiled.memory_analysis().temp_size_in_bytes < 27 * n


@pytest.mark.parametrize("fmt", ["csr", "ell"])
def test_hpcg_solver_program_fits_for_v5e(fmt, one_chip, monkeypatch):
    """HPCG's whole convergence solver (PCG around a 4-level V-cycle of
    multicolor SymGS sweeps) on ``fmt``/pallas operators at every level.
    Its temporaries stay below the operators' own bytes: layouts with a
    narrow last axis (padded to 128 lanes in device memory) or masked
    operand copies (hoisted out of the solver loops, one per color) cost
    several times that, and at 104³ more than the chip holds."""
    from repro.solvers import build_mg, cg
    from repro.solvers.mg import MGLevel, VCycle

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    pol = ExecutionPolicy(backends=("pallas",), allow_fallback=False)
    levels = []
    for l in build_mg(GRID, GRID, GRID, depth=4, fmt="csr").levels:
        A = as_operator(l.A, fmt, policy=pol)
        levels.append(MGLevel(l.grid, A, l.smoother.with_operator(A), l.R, l.P))
    mg = VCycle(tuple(levels))
    A = mg.levels[0].A
    b = jax.ShapeDtypeStruct((A.shape[1],), jnp.float32, sharding=one_chip)
    compiled = _compile(
        lambda A, mg, b: cg(lambda p: A @ p, b, tol=1e-6, maxiter=50,
                            precond=mg).x,
        _struct(A, one_chip), _struct(mg, one_chip), b)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < mem.argument_size_in_bytes, (
        mem.temp_size_in_bytes, mem.argument_size_in_bytes)


@pytest.mark.parametrize("nf", [1, 8])
def test_bsr_spmm_compiles_for_v5e(nf, one_chip):
    nbrows = BSR_ROWS // BSR_BS
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    _compile(lambda b, blk, X: bsr_spmm(b, blk, X, interpret=False),
             S((nbrows, BSR_WIDTH), jnp.int32),
             S((nbrows, BSR_WIDTH, BSR_BS, BSR_BS), jnp.float32),
             S((BSR_ROWS, nf), jnp.float32))


def test_coo_plain_row_sums_compile_for_v5e(one_chip):
    """``coo/plain`` with its row pointer at the kron-20 graph's shapes: the
    rows are summed in place (no scatter in the program), one gather of x
    and one of the row ends, and the temporaries stay under 1 GB."""
    import re

    from repro.core.formats import COO
    from repro.core.spmv import coo_spmv_plain

    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    A = COO(S((GRAPH_NNZ,), jnp.int32), S((GRAPH_NNZ,), jnp.int32),
            S((GRAPH_NNZ,), jnp.float32), (GRAPH_ROWS, GRAPH_ROWS), None,
            S((GRAPH_ROWS + 1,), jnp.int32))
    compiled = jax.jit(coo_spmv_plain).lower(A, S((GRAPH_ROWS,), jnp.float32)).compile()
    text = compiled.as_text()
    assert not re.search(r" scatter\(", text)
    assert len(re.findall(r" gather\(", text)) == 2
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


def test_rehearsal_shapes_are_the_stencils(containers):
    """The compiled shapes are the real 64³ operator's, not toy ones."""
    n = GRID ** 3
    assert containers["dia", "resident"].data.shape == (27, n)
    assert containers["ell", "tiled"].plan.ntiles > 1
    assert np.prod(containers["coo", "resident"].shape) == COO_RESIDENT_GRID ** 6
