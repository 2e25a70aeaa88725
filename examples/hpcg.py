"""End-to-end driver: the Morpheus-enabled HPCG benchmark (paper §VII-D).

  PYTHONPATH=src python examples/hpcg.py [--grid 16] [--iters 50]
  PYTHONPATH=src python examples/hpcg.py --no-precond      # SpMV-only slice
  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
      PYTHONPATH=src python examples/hpcg.py --distributed

Serial: the full pipeline — setup (stencil + multigrid hierarchy), reference
run (csr/plain PCG with SymGS-smoothed V-cycle), optimisation (run-first
auto-tuner picks a format/backend per multigrid level), validation (the
optimised machinery re-run on csr/plain must match the reference bit-for-bit,
the tuned run to tolerance), timed fixed-iteration runs. Distributed: the
same five phases on a mesh over every visible device — rows sharded,
local/remote split with per-rank formats (Table III), ppermute halo
exchange overlapped with the local SpMV, distributed multigrid + SymGS,
and a bit-for-bit single-vs-multi-device SpMV validation. See docs/hpcg.md.
"""
import argparse

import jax

from repro import compile_cache
from repro.apps.hpcg import run_hpcg, run_hpcg_distributed


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--grid", type=int, default=12)
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--depth", type=int, default=4, help="multigrid levels")
    ap.add_argument("--tol", type=float, default=1e-6)
    ap.add_argument("--no-precond", action="store_true",
                    help="disable the multigrid preconditioner (plain CG)")
    ap.add_argument("--distributed", action="store_true")
    args = ap.parse_args()
    compile_cache.enable()

    g = args.grid
    if args.distributed:
        print(f"devices={len(jax.devices())}")
        res = run_hpcg_distributed(None, g, g, g, iters=args.iters,
                                   depth=args.depth, tol=args.tol,
                                   precond=not args.no_precond)
    else:
        res = run_hpcg(g, g, g, iters=args.iters, depth=args.depth,
                       tol=args.tol, precond=not args.no_precond)
    checks = f"bitwise={res.bitwise}, valid={res.valid}"
    print(f"\nphases: setup -> reference -> tune -> validate({checks}) -> timed")
    if res.mg_levels:
        print(f"multigrid levels: {res.mg_levels}")
        print(f"pcg: {res.pcg_iters} iters to rel_res={res.rel_res:.2e}")
    def fmt_entry(v):
        if isinstance(v, str):
            return v
        if isinstance(v, dict):  # distributed: per-rank {fmt/backend: us}
            return " ".join(f"{k}={t:.0f}us" for k, t in sorted(v.items()))
        return f"{v:.1f}us" if v < 1e4 else f"{v/1e3:.1f}ms"

    print("tuner table:")
    for k, v in sorted(res.table.items(), key=lambda kv: str(kv[0])):
        print(f"  {k}: {fmt_entry(v)}")


if __name__ == "__main__":
    main()
