#!/usr/bin/env python3
"""The control of a cell's check: the plain reference in the program's place,
computed below the configuration's precision, must come out not correct.

    python bench/control.py --workload <cell> --seeds 1,2,3 [--dtype bfloat16]

For each seed this builds the cell's problem (the same data a run builds),
solves it with the driver's ``control`` in ``--dtype`` instead of the
program, and applies the cell's own check. It prints one line per seed and,
last, a JSON object with every reading; it exits 1 when any seed's control
passes the check. The benchmark's runs never run it.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None):
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, e.g. 1,2,3")
    ap.add_argument("--dtype", default="bfloat16")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from bench.loader import Bench

    dev = jax.devices()[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind}", flush=True)
    bench = Bench(ROOT)
    spec = bench.cell(args.workload)
    driver = bench.driver(spec["traffic"]["driver"])
    dtype = jnp.dtype(args.dtype)
    readings = []
    for seed in (int(s) for s in args.seeds.split(",")):
        problem = driver.problem(spec["config"], spec["traffic"], seed)
        out = jax.device_get(driver.control(problem, dtype))
        summary = problem.summarize(out)
        checks = problem.check([out])
        correct = not summary["failed"] and all(
            c["value"] <= c["limit"] for c in checks)
        readings.append({"seed": seed, "correct": correct, **summary,
                         "checks": {c["name"]: c["value"] for c in checks}})
        print(f"seed {seed}: {summary} "
              + " ".join(f"{c['name']}={c['value']!r} (limit {c['limit']!r})"
                         for c in checks)
              + f" -> {'correct' if correct else 'not correct'}", flush=True)
    print(json.dumps({"workload": args.workload, "dtype": args.dtype,
                      "platform": dev.platform, "readings": readings}))
    return 1 if any(r["correct"] for r in readings) else 0


if __name__ == "__main__":
    sys.exit(main())
