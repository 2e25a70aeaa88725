#!/usr/bin/env python3
"""Smoke run of the system's main path on a TPU, checked against scipy.

    python chip_smoke.py               # one TPU chip: phases a-d below
    python chip_smoke.py --four-chips  # four chips: the distributed phase only

One process, no subprocesses. Phases:

  a. Refuse to run unless JAX's first device is a TPU (exit 2, naming the
     platform found). Print the device kind and count first.
  b. Every Pallas SpMV kernel natively, with fallback disabled, each result
     compared with scipy in f64:
       - csr, coo, dia, ell, sell on HPCG's 104³ stencil, which selects the
         column-tiled strategies (DIA's residency rule admits 4x the
         columns, so its tiled plan is forced with a smaller resident cap);
       - the same formats on the 64³ stencil, which selects the resident
         strategies (COO's full-window kernel holds every row, so it takes
         the largest stencil under its 8192-row cap, 20³);
       - the row-masked DIA and ELL lanes in both strategies;
       - BSR SpMV and SpMM (k=8) on a 32768-row, 5%-block-dense matrix;
       - the plain (XLA) lanes phase c takes as its oracle: DIA at 104³ and
         64³ with its masked lane, and CSR at 64³, so the reference is
         itself checked against scipy.
  c. ``run_hpcg`` at 104³ with a 4-level multigrid: it must be valid and
     bitwise, and the dispatch health registry must record no failure. The
     reference solve and the bit-for-bit replay run plain DIA: plain CSR,
     HPCG's default reference, took 504 ms per SpMV at 104³ in the tuner's
     race on a v5e (XLA's gather and scatter), and a PCG iteration runs
     about 40 of them. The
     tuner races every candidate with fallback off and raises on any
     failure other than a capability refusal, so a broken kernel fails
     this phase instead of being filed as skipped. HPCG's 50 iterations do
     not reach ``tol=1e-6`` at 104³ with a 4-level hierarchy: the count
     grows with the grid's longest side (10, 19, 27 and 35 iterations at
     16³, 32³, 48³ and 64³ on the CPU, 46 at 104³ on a v5e), so the solves
     may take up to ``HPCG_ITERS``; the count taken is printed.
  d. The last line is one JSON object: ``{"ok": true, "device": {...}}``.

``--four-chips`` runs only ``run_hpcg_distributed`` weak-scaled to
208x208x104 (four 104³ blocks) over a four-device mesh, with the
single-device reference it computes (plain DIA, as in phase c), and the
same health check. Each rank races plain DIA and plain COO on its own
device (``FOUR_CHIP_CANDIDATES``): phase c already races every candidate
on one chip, and this phase checks the sharded path.

Every failed check exits non-zero and prints no result line. Compiles that
take longer than a few seconds are logged as they finish, and a run that
goes quiet for five minutes dumps every thread's stack, so a slow or hung
step names itself.
"""
import argparse
import faulthandler
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: relative error bound (max |y - ref| / max |ref|) of an f32 SpMV
TOL = 1e-5
HPCG_GRID = (104, 104, 104)
RESIDENT_GRID = 64
COO_RESIDENT_GRID = 20
TILED_RESIDENT_COLS = 1 << 17  # DIA at 104³: below its 4x residency rule
BSR_N, BSR_BS, BSR_DENSITY, BSR_K = 32768, 32, 0.05, 8
FOUR_CHIP_GRID = (208, 208, 104)
#: max PCG iterations (the solves stop at tol=1e-6): a ceiling well above
#: the 46 measured at 104³, for the four-chip grid's 208-point side
HPCG_ITERS = 200
#: plain format of the reference solve and the bit-for-bit replay
HPCG_REFERENCE = "dia"
#: per-rank tuning candidates of the four-chip phase: plain DIA, which won
#: HPCG's two finest levels on one chip, and COO for the remote (halo) blocks
FOUR_CHIP_CANDIDATES = (("dia", "plain"), ("coo", "plain"))
#: compiles at least this long are logged as they finish
LOG_COMPILE_S = 5.0


class SmokeFailure(Exception):
    pass


def _rel_err(got, want):
    import numpy as np

    got = np.asarray(got, np.float64)
    if not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def _check(name, got, want, failures, tol=TOL):
    err = _rel_err(got, want)
    ok = err <= tol
    print(f"  {name}: max_rel_err={err:.3e} {'ok' if ok else 'FAIL'}", flush=True)
    if not ok:
        failures.append(name)


def _guarded(label, fn, failures):
    """Run one check; a raise fails that check and the phase goes on, so
    one run reports every broken kernel."""
    try:
        fn()
    except Exception as e:
        print(f"  {label}: {type(e).__name__}: {str(e)[:2000]} FAIL", flush=True)
        failures.append(label)


def _health_failures():
    from repro.core import health

    keys = health.registry().snapshot()["keys"]
    return {k: v for k, v in keys.items() if v["failures"] or v["nonfinite"]}


def phase_kernels(hpcg_grid=HPCG_GRID, resident_grid=RESIDENT_GRID,
                  coo_resident_grid=COO_RESIDENT_GRID,
                  tiled_resident_cols=TILED_RESIDENT_COLS,
                  bsr=(BSR_N, BSR_BS, BSR_DENSITY, BSR_K), seed=0):
    """Phase b. Returns the names of the failed checks."""
    import numpy as np

    from repro.core import ExecutionPolicy, as_operator
    from repro.core import matrices as M
    from repro.kernels.ops import pallas_strategy

    rng = np.random.default_rng(seed)
    failures = []
    stencils = {}

    def stencil(g):
        if g not in stencils:
            stencils[g] = M.fdm27(*g)
        return stencils[g]

    cube = lambda g: (g, g, g)
    small_tiled = ExecutionPolicy(max_resident_cols=tiled_resident_cols)
    # (format, grid, policy, backend, expected Pallas strategy)
    cases = [(fmt, hpcg_grid, None, "pallas", "tiled")
             for fmt in ("csr", "coo", "ell", "sell")]
    cases += [("dia", hpcg_grid, None, "pallas", "resident"),
              ("dia", hpcg_grid, small_tiled, "pallas", "tiled")]
    cases += [(fmt, cube(resident_grid), None, "pallas", "resident")
              for fmt in ("csr", "dia", "ell", "sell")]
    cases += [("coo", cube(coo_resident_grid), None, "pallas", "resident")]
    cases += [("dia", hpcg_grid, None, "plain", None),
              ("dia", cube(resident_grid), None, "plain", None),
              ("csr", cube(resident_grid), None, "plain", None)]

    def run_case(fmt, grid, pol, backend, want_strategy, label):
        s = stencil(grid)
        t0 = time.perf_counter()
        A = as_operator(s, fmt, policy=pol).using(backend, fallback=False)
        if backend == "pallas":
            strategy = pallas_strategy(A.container, A.policy)
            if strategy != want_strategy:
                raise SmokeFailure(
                    f"strategy {strategy!r}, expected {want_strategy!r}")
        x = rng.standard_normal(s.shape[1]).astype(np.float32)
        ref = s @ x.astype(np.float64)
        _check(label, A @ x, ref, failures)
        if fmt in ("dia", "ell"):
            mask = rng.random(s.shape[0]) < 0.5
            _check(f"{label} masked", A.masked_matvec(x, mask),
                   np.where(mask, ref, 0.0), failures)
        print(f"    ({time.perf_counter() - t0:.1f}s incl. convert + compile)",
              flush=True)

    for fmt, grid, pol, backend, want in cases:
        label = f"{fmt} {want or backend} {'x'.join(map(str, grid))}"
        _guarded(label, lambda: run_case(fmt, grid, pol, backend, want, label),
                 failures)

    def run_bsr():
        n, bs, density, k = bsr
        s = M.block_random(n, bs=bs, block_density=density, seed=seed)
        A = as_operator(s, "bsr", block_size=bs).using("pallas", fallback=False)
        x = rng.standard_normal(n).astype(np.float32)
        X = rng.standard_normal((n, k)).astype(np.float32)
        print(f"  bsr: {s.nnz} nnz, {A.nbytes / 2**20:.0f} MiB", flush=True)
        _check(f"bsr block {n} spmv", A @ x, s @ x.astype(np.float64), failures)
        _check(f"bsr block {n} spmm k={k}", A @ X, s @ X.astype(np.float64),
               failures)

    _guarded("bsr block", run_bsr, failures)
    return failures


def phase_hpcg(grid=HPCG_GRID, depth=4, iters=HPCG_ITERS):
    """Phase c. Returns the names of the failed checks."""
    from repro.apps.hpcg import run_hpcg

    t0 = time.perf_counter()
    res = run_hpcg(*grid, iters=iters, depth=depth, timed=False, verbose=False,
                   reference=HPCG_REFERENCE)
    print(f"  run_hpcg {'x'.join(map(str, grid))}: n={res.n} "
          f"pcg_iters={res.pcg_iters} (max {iters}) rel_res={res.rel_res:.3e} "
          f"rel_err={res.rel_err:.3e} valid={res.valid} bitwise={res.bitwise} "
          f"chosen={res.chosen} ({time.perf_counter() - t0:.1f}s)\n"
          f"  levels: {res.mg_levels}", flush=True)
    failures = [name for name, ok in (("hpcg valid", res.valid),
                                      ("hpcg bitwise", res.bitwise)) if not ok]
    return failures


def phase_four_chips(grid=FOUR_CHIP_GRID, depth=4, iters=HPCG_ITERS, nchips=4,
                     candidates=FOUR_CHIP_CANDIDATES):
    """The distributed phase: the HPCG pipeline over an ``nchips`` mesh."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.apps.hpcg import run_hpcg_distributed

    devs = jax.devices()
    if len(devs) < nchips:
        raise SmokeFailure(f"--four-chips needs {nchips} devices, found {len(devs)}")
    mesh = Mesh(np.array(devs[:nchips]), ("data",))
    t0 = time.perf_counter()
    res = run_hpcg_distributed(mesh, *grid, iters=iters, depth=depth,
                               candidates=candidates, timed=False,
                               verbose=False, reference=HPCG_REFERENCE)
    print(f"  run_hpcg_distributed {'x'.join(map(str, grid))} over {nchips}: "
          f"n={res.n} pcg_iters={res.pcg_iters} (max {iters}) "
          f"rel_res={res.rel_res:.3e} "
          f"rel_err={res.rel_err:.3e} valid={res.valid} bitwise={res.bitwise} "
          f"({time.perf_counter() - t0:.1f}s)\n"
          f"  per-rank: {res.chosen}\n  levels: {res.mg_levels}\n"
          f"  tuner (us): {res.table}", flush=True)
    return [name for name, ok in (("distributed valid", res.valid),
                                  ("distributed bitwise", res.bitwise)) if not ok]


def _compile_stats(t_start):
    """Accumulate JAX's compile-time and persistent-cache events; log each
    compile of at least ``LOG_COMPILE_S`` as it finishes."""
    import jax

    stats = {"compile_s": 0.0, "compiles": 0, "cache_hits": 0, "cache_misses": 0}

    def on_duration(event, duration, fun_name="?", **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            stats["compile_s"] += duration
            stats["compiles"] += 1
            if duration >= LOG_COMPILE_S:
                print(f"    [{time.perf_counter() - t_start:7.1f}s] compiled "
                      f"{fun_name} in {duration:.1f}s", flush=True)

    def on_event(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            stats["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            stats["cache_misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return stats


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip distributed HPCG phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    dev = devs[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {dev.platform!r}",
              file=sys.stderr)
        return 2
    try:
        from repro import compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not importable from "
              f"{ROOT}/src: {e}", file=sys.stderr)
        return 2
    print(f"compile cache: {compile_cache.enable()}", flush=True)
    t_start = time.perf_counter()
    stats = _compile_stats(t_start)
    faulthandler.dump_traceback_later(300, repeat=True)

    failures = []
    try:
        if args.four_chips:
            print("phase: four-chip distributed HPCG", flush=True)
            failures += phase_four_chips()
            count = 4
        else:
            print("phase b: Pallas kernels vs scipy", flush=True)
            failures += phase_kernels(seed=args.seed)
            print("phase c: HPCG", flush=True)
            failures += phase_hpcg()
            count = len(devs)
    except Exception as e:  # any raise is a failed phase, reported as such
        import traceback

        traceback.print_exc()
        failures.append(f"{type(e).__name__}: {e}")
    faulthandler.cancel_dump_traceback_later()
    broken = _health_failures()
    if broken:
        failures.append(f"health registry recorded failures: {broken}")
    print(f"compile: {stats['compiles']} programs, {stats['compile_s']:.1f}s "
          f"backend compile, persistent cache {stats['cache_hits']} hits / "
          f"{stats['cache_misses']} misses; wall {time.perf_counter() - t_start:.1f}s",
          flush=True)
    if failures:
        print(f"chip_smoke: FAILED: {failures}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
