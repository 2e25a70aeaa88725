"""Morpheus-enabled HPCG (paper §VII-D) in JAX — the full benchmark.

Phases mirror HPCG: (1) problem setup — 27-point stencil on an nx*ny*nz grid
plus the multigrid hierarchy (SymGS smoother, injection restriction,
re-discretised coarse operators); (2) reference run — preconditioned CG with
plain CSR operators at every level (``reference=`` picks another plain
format); (3) optimisation setup — the run-first
auto-tuner picks a (format, backend) *per multigrid level* (Table III style),
and in distributed mode the matrix is physically split into local/remote
parts with independently tuned formats; (4) validation — the optimised
pipeline re-run with the reference candidate must reproduce the
reference solve bit-for-bit (the dispatch machinery adds zero numerical
drift), and the tuned run must agree within tolerance and converge to
``tol`` within ``iters``; (5) timed runs — fixed-iteration PCG so the
SpMV/SymGS op counts are identical across implementations.

``precond=False`` recovers the paper's SpMV-focused slice (plain CG, no
multigrid). ``run_hpcg_distributed`` runs the same five phases on an
N-device mesh: every operator (including each multigrid level and the
SymGS color sweeps) is a ``DistributedOperator`` with halo-exchange SpMV,
and validation additionally demands the distributed reference SpMV be
bit-for-bit identical to the single-device kernel. See ``docs/hpcg.md``.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import DispatchKey, as_operator, autotune_spmv
from repro.core import matrices as M
from repro.core.errors import SolverDivergenceError
from repro.solvers import build_mg, cg, cg_solve, diagnose_cg, pcg_solve  # noqa: F401  (cg_solve re-exported)



@dataclass
class HPCGResult:
    grid: Tuple[int, int, int]
    n: int
    iters: int
    ref_time_s: float
    opt_time_s: float
    speedup: float
    chosen: str
    valid: bool
    rel_err: float
    table: Dict = field(default_factory=dict)
    # full-pipeline extras (defaults keep positional back-compat)
    precond: bool = False
    pcg_iters: int = 0        # iterations the tuned PCG took to reach tol
    rel_res: float = 0.0      # its final ||r||/||b||
    bitwise: bool = True      # optimised machinery on the reference == reference
    mg_levels: str = ""       # per-level (format, backend) choices


def _time(fn, *args, reps=3):
    jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def _guard_phase(info, phase: str, *, tol, maxiter):
    """Fail loudly when a convergence phase went non-finite (a corrupted
    kernel or broken halo exchange must not masquerade as ``valid=False``).
    The conv solvers are jitted, so this runs post-hoc on concrete results;
    a merely *stalled* run stays a validation failure, not an exception."""
    diag = diagnose_cg(info, tol=tol, maxiter=maxiter)
    if not diag.finite:
        raise SolverDivergenceError(
            f"HPCG {phase} phase diverged: non-finite residual after "
            f"{diag.iters} iterations")
    return diag


def _solver_pair(A_op, mg, iters, tol):
    """(timed, convergence) solvers for one operator set: fixed-iteration PCG
    for comparable timing, tolerance-stopping PCG for the convergence run.

    The operator and the hierarchy are arguments of the jitted solvers, not
    closure constants: a closed-over array is embedded in the executable,
    which at HPCG's 104³ grows past 2 GB and compiles for minutes."""
    timed = jax.jit(lambda A, mg, b: pcg_solve(lambda p: A @ p, b, iters,
                                               precond=mg))
    conv = jax.jit(lambda A, mg, b: cg(lambda p: A @ p, b, tol=tol,
                                       maxiter=iters, precond=mg))
    return partial(timed, A_op, mg), partial(conv, A_op, mg)


def run_hpcg(nx=16, ny=16, nz=16, iters=50, reps=3, candidates=None,
             verbose=True, precond=True, tol=1e-6, depth=4,
             timed=True, tune_mode="run", reference="csr") -> HPCGResult:
    """Serial HPCG phases 1-5 (Figure 8a analogue), full pipeline.

    ``timed=False`` runs phases 1-4 only (setup/reference/tune/validate) and
    reports zero times — the convergence-and-validation entry point tests use.

    ``reference`` is the format whose plain (XLA) kernel runs the reference
    solve at every level and the bit-for-bit replay: HPCG's CSR by default.
    On a TPU v5e, plain CSR at 104³ took 504 ms per SpMV in the tuner's
    race (XLA's gather and scatter), and a PCG iteration with a 4-level
    V-cycle runs about 40 of them; ``"dia"`` reads each diagonal's x window
    as one slice and took 2.6 ms.

    ``tune_mode="predict"`` swaps phase 3's run-first races (main operator
    and every multigrid level) for the zero-run feature selector
    (``core/select.py``): setup executes no candidate kernels at all — the
    optimisation-setup fast path for large hierarchies. Validation phases
    are identical either way, so a bad prediction shows up as a failed
    tolerance check, not silent corruption.
    """
    if tune_mode not in ("run", "predict"):
        raise ValueError(f"tune_mode {tune_mode!r}: expected 'run' or 'predict'")
    # Phase 1: problem setup (stencil + multigrid hierarchy)
    A_sp = M.fdm27(nx, ny, nz)
    n = A_sp.shape[0]
    b = jnp.asarray(A_sp @ np.ones(n), jnp.float32)

    # Phase 2: reference run (plain ``reference`` format at every level)
    ref_key = DispatchKey(reference, "plain")
    A_ref = as_operator(A_sp, reference).using("plain")
    mg_ref = build_mg(nx, ny, nz, depth=depth, fmt=reference) if precond else None
    ref_timed, ref_conv = _solver_pair(A_ref, mg_ref, iters, tol)
    ref = ref_conv(b)
    _guard_phase(ref, "reference", tol=tol, maxiter=iters)
    x_ref = ref.x

    # Phase 3: optimisation setup (per-level formats, Table III style).
    # Tuned hierarchies are derived from the reference one — schedules and
    # transfer operators are shared, only the SpMV operators retarget.
    # "run" races candidates (run-first auto-tuner); "predict" asks the
    # zero-run feature selector and never executes a candidate kernel.
    if tune_mode == "predict":
        A_opt = as_operator(A_sp, "csr").tune(candidates=candidates,
                                              mode="predict")
        impl = A_opt.policy.backends[0]
        chosen, tune_table = f"{A_opt.format}/{impl}", {}
    else:
        tune = autotune_spmv(A_sp, candidates=candidates)
        A_opt, impl = tune.operator, tune.impl
        chosen = f"{tune.format}/{impl}"
        tune_table = {f"{f}/{i}": t for (f, i), t in tune.table.items()}
    mg_opt = (mg_ref.retuned(candidates, mode=tune_mode, finest=A_opt)
              if precond else None)
    opt_timed, opt_conv = _solver_pair(A_opt, mg_opt, iters, tol)

    # Phase 4: validation
    #  (a) bit-for-bit: the optimised pipeline, forced onto the reference
    #      candidate, must reproduce the reference run exactly — the
    #      dispatch/tuner machinery itself adds zero numerical drift.
    A_chk = autotune_spmv(A_sp, candidates=(ref_key,)).operator
    mg_chk = mg_ref.retuned((ref_key,), finest=A_chk) if precond else None
    _, chk_conv = _solver_pair(A_chk, mg_chk, iters, tol)
    chk = chk_conv(b)
    bitwise = bool(np.array_equal(np.asarray(chk.x), np.asarray(x_ref))
                   and int(chk.iters) == int(ref.iters))
    #  (b) tolerance: the tuned run must converge and agree with the reference
    opt = opt_conv(b)
    _guard_phase(opt, "optimised", tol=tol, maxiter=iters)
    rel = float(jnp.linalg.norm(opt.x - x_ref)
                / jnp.maximum(jnp.linalg.norm(x_ref), 1e-30))
    valid = bitwise and rel < 1e-3 and float(opt.rel_res) <= tol

    # Phase 5: timed runs (fixed iteration count => identical op mix)
    if timed:
        t_ref = _time(ref_timed, b, reps=reps)
        t_opt = _time(opt_timed, b, reps=reps)
        speedup = t_ref / t_opt
    else:
        t_ref = t_opt = 0.0
        speedup = 0.0

    res = HPCGResult(
        (nx, ny, nz), n, iters, t_ref, t_opt, speedup,
        chosen, valid, rel, tune_table,
        precond=precond, pcg_iters=int(opt.iters), rel_res=float(opt.rel_res),
        bitwise=bitwise, mg_levels=mg_opt.describe() if mg_opt else "")
    if verbose:
        kind = "pcg" if precond else "cg"
        print(f"HPCG {nx}x{ny}x{nz} n={n}: ref({reference}/plain)={t_ref*1e3:.1f}ms "
              f"opt({res.chosen})={t_opt*1e3:.1f}ms speedup={res.speedup:.2f}x "
              f"{kind}_iters={res.pcg_iters} rel_res={res.rel_res:.2e} "
              f"valid={valid} bitwise={bitwise} rel={rel:.2e}")
        if res.mg_levels:
            print(f"  levels: {res.mg_levels}")
    return res


def default_mesh(axis: str = "data"):
    """A 1-D mesh over every visible device (CI: fake host devices via
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``)."""
    from jax.sharding import Mesh

    devs = np.array(jax.devices())
    return Mesh(devs.reshape(devs.size), (axis,))


def run_hpcg_distributed(mesh=None, nx=16, ny=16, nz=16, iters=50, reps=3,
                         candidates=None, verbose=True, precond=True,
                         tol=1e-6, depth=4, timed=True, axis="data",
                         tune_levels=False, reference="csr") -> HPCGResult:
    """Distributed HPCG (Figure 8b/8c analogue) — the full pipeline on an
    N-device mesh.

    Rows (matrix, vectors, multigrid levels) are sharded over ``mesh[axis]``;
    every SpMV is a ``DistributedOperator`` running local-part SpMV
    overlapped with the halo exchange + remote-part SpMV, and CG's dot
    products all-reduce across shards (see ``solvers/cg.py``).

    Phases:
      1. *setup* — stencil + right-hand side + the multigrid hierarchy,
         clamped to :func:`repro.solvers.distributable_depth`.
      2. *reference* — the single-device ``reference``/plain PCG solve
         (the oracle the distributed runs are judged against).
      3. *tune* — :func:`repro.distributed_op.tune_partitions` picks each
         rank's (local, remote) formats (Table III); ``tune_levels=True``
         additionally retunes every multigrid level per-partition.
      4. *validate* — two tiers, mirroring the serial pipeline: (a)
         **bit-for-bit**: the distributed reference SpMV in ``rowblock``
         mode must equal the single-device one exactly — the
         sharding machinery adds zero numerical drift; (b) *tolerance*: the
         tuned distributed PCG must converge to ``tol`` and agree with the
         single-device solution.
      5. *timed* — fixed-iteration distributed PCG, reference split
         (``reference`` local and remote) vs tuned formats, identical op
         mix.

    Args:
        mesh: 1-D mesh (default: every visible device on one ``axis``).
        nx, ny, nz: stencil grid; ``nx*ny*nz`` must be divisible by the
            mesh size.
        iters: fixed iteration count for the timed phase / maxiter for the
            convergence runs.
        reps: timing repetitions.
        candidates: per-partition tuning candidates (DispatchKeys).
        precond: multigrid-preconditioned (the benchmark) vs plain CG.
        tol: convergence target (HPCG: 1e-6).
        depth: max multigrid levels (clamped to what shards evenly).
        timed: ``False`` runs phases 1-4 only (the test entry point).
        tune_levels: per-partition tune of every MG level (slower setup);
            untuned levels run the ``reference`` format.
        reference: plain format of the reference solve, the bit-for-bit
            check and the reference split (see :func:`run_hpcg`).

    Returns:
        :class:`HPCGResult`; ``bitwise`` is tier (a), ``valid`` ands both
        tiers with convergence, ``chosen``/``mg_levels`` describe the
        per-rank and per-level choices.
    """
    from repro.distributed_op import DistributedOperator, tune_partitions
    from repro.solvers import distributable_depth, distribute_vcycle

    if mesh is None:
        mesh = default_mesh(axis)
    nparts = int(mesh.shape[axis])

    # Phase 1: problem setup
    A_sp = M.fdm27(nx, ny, nz)
    n = A_sp.shape[0]
    if n % nparts:
        raise ValueError(f"grid {nx}x{ny}x{nz} ({n} rows) is not divisible "
                         f"by the {nparts}-device mesh")
    b_host = np.asarray(A_sp @ np.ones(n), np.float32)
    depth = distributable_depth(nx, ny, nz, nparts, depth=depth) if precond else 0

    # Phase 2: single-device reference (plain ``reference``, the oracle)
    A_ref = as_operator(A_sp, reference).using("plain")
    mg_ref = build_mg(nx, ny, nz, depth=depth, fmt=reference) if precond else None
    b1 = jnp.asarray(b_host)
    ref = _solver_pair(A_ref, mg_ref, iters, tol)[1](b1)
    x_ref = np.asarray(ref.x)

    # Phase 3: distributed operators — per-partition tune
    D_opt, table = tune_partitions(A_sp, mesh, axis, candidates=candidates)
    mg_dist = distribute_vcycle(mg_ref, mesh, axis, tune=tune_levels,
                                candidates=candidates,
                                fmt=reference) if precond else None
    b_d = D_opt.device_put(b_host)

    # Phase 4a: bit-for-bit — the distributed reference in rowblock (exact)
    # mode must reproduce the single-device reference SpMV bit by bit.
    D_chk = DistributedOperator.build(A_sp, mesh, axis, local=reference,
                                      mode="rowblock")
    y_single = np.asarray(A_ref @ b1)
    y_dist = np.asarray(D_chk @ b_d)
    bitwise = bool(np.array_equal(y_single, y_dist))

    # Phase 4b: tolerance — tuned distributed PCG converges and matches
    opt_timed, opt_conv = _solver_pair(D_opt, mg_dist, iters, tol)
    opt = opt_conv(b_d)
    rel = float(np.linalg.norm(np.asarray(opt.x) - x_ref)
                / max(float(np.linalg.norm(x_ref)), 1e-30))
    valid = bitwise and rel < 1e-3 and float(opt.rel_res) <= tol

    # Phase 5: timed fixed-iteration runs (identical op mix)
    if timed:
        D_ref = DistributedOperator.build(A_sp, mesh, axis, local=reference,
                                          remote=reference, mode="auto")
        ref_timed = _solver_pair(D_ref, mg_dist, iters, tol)[0]
        t_ref = _time(ref_timed, b_d, reps=reps)
        t_opt = _time(opt_timed, b_d, reps=reps)
        speedup = t_ref / t_opt
    else:
        t_ref = t_opt = speedup = 0.0

    flat_table = {f"p{p}/{part}": {f"{f}/{i}": t for (f, i), t in tbl.items()}
                  for (p, part), tbl in table.items()}
    res = HPCGResult(
        (nx, ny, nz), n, iters, t_ref, t_opt, speedup,
        D_opt.describe(), valid, rel, flat_table,
        precond=precond, pcg_iters=int(opt.iters), rel_res=float(opt.rel_res),
        bitwise=bitwise,
        mg_levels=mg_dist.describe() if mg_dist else "")
    if verbose:
        kind = "pcg" if precond else "cg"
        print(f"HPCG-dist {nx}x{ny}x{nz} n={n} parts={nparts}: "
              f"ref={t_ref*1e3:.1f}ms opt={t_opt*1e3:.1f}ms "
              f"speedup={speedup:.2f}x {kind}_iters={res.pcg_iters} "
              f"rel_res={res.rel_res:.2e} valid={valid} bitwise={bitwise} "
              f"rel={rel:.2e}")
        print(f"  per-rank: {res.chosen}")
        if res.mg_levels:
            print(f"  levels: {res.mg_levels}")
    return res
