"""Run every paper-table/figure benchmark. Prints name,us_per_call,derived CSV
and writes the machine-readable SpMV perf trajectory to BENCH_spmv.json at the
repo root (per format x backend x size: median/p10 seconds, GFLOP/s, a
fallback-vs-native flag, and the zero-run selector's predicted
format/backend per matrix with a predicted-vs-measured accuracy summary —
the cross-PR perf + prediction record).

  PYTHONPATH=src python -m benchmarks.run [--scale quick|bench] [--only fig4]
  PYTHONPATH=src python -m benchmarks.run --smoke   # CI: spmv grid only;
      exits non-zero if any expected-native cell silently fell back
  PYTHONPATH=src python -m benchmarks.run --corpus DIR [--accuracy-floor F]
      # Matrix Market corpus sweep: per matrix, the selector's zero-run
      # prediction vs the run-first autotune winner, recorded into the
      # "corpus" section of BENCH_spmv.json; exits non-zero when prediction
      # accuracy falls below the floor (the CI corpus-smoke gate)
  PYTHONPATH=src python -m benchmarks.run --serve [--smoke]
      # serving-layer trajectory: traffic mixes through the ServeEngine ->
      # BENCH_serve.json (latency p50/p99, throughput, warm-pool hit rate);
      # exits non-zero on empty output or a dispatch fallback off a tuned
      # backend (the CI serve-smoke gate)
  PYTHONPATH=src python -m benchmarks.run --precision [--scale quick]
      # compressed-index / mixed-precision sweep: format x {int32,auto}
      # index x {f32,bf16,f16} value variants on the Pallas backend ->
      # "precision" section of BENCH_spmv.json (bytes-per-nnz, measured
      # GFLOP/s vs the roofline-predicted speedup); exits non-zero when a
      # compressed variant falls back while its uncompressed baseline ran
      # natively, or narrower dtypes fail to shrink storage (the CI
      # precision-smoke gate)
  PYTHONPATH=src python -m benchmarks.run --bsr [--smoke]
      # block-sparse sweep: BSR vs CSR/SELL GFLOP/s as intra-block fill
      # varies, with the container-bytes roofline predicting the crossover
      # -> "bsr" section of BENCH_spmv.json; exits non-zero when the fixture
      # block matrix is missing or any bsr x pallas cell silently fell back
      # (the CI bsr-smoke gate)
  PYTHONPATH=src python -m benchmarks.run --chaos [--smoke]
      # fault-injected resilience trajectory: seeded traffic replayed under
      # a recoverable FaultPlan -> BENCH_chaos.json (success rate, degraded
      # share, p99 inflation, breaker recovery time, inactive-hook parity);
      # exits non-zero when success rate < 100%, a quarantined key fails to
      # recover, or the fault hooks are not no-ops when inactive (the CI
      # chaos-smoke gate)
  PYTHONPATH=src python -m benchmarks.run --dynamic [--smoke]
      # dynamic-matrix trajectory: mutation scenarios (FDM assembly,
      # pruning) driven across the drift threshold -> BENCH_dynamic.json;
      # exits non-zero if refresh() never re-selects, re-tunes on the wrong
      # side of the threshold, or a refreshed operator falls back off its
      # predicted backend (the CI dynamic-smoke gate)
"""
import argparse
import importlib
import json
import os
import platform
import sys
import traceback

MODULES = [
    "fig3_format_distribution",
    "fig4_optimized_vs_plain",
    "fig5_formats_vs_csr",
    "fig6_kernel_variants",
    "fig8_hpcg",
    "moe_dispatch",
    "bsr_bench",
    "roofline_table",
    "spmv_bench",
    "serve_bench",
    "dynamic_bench",
    "chaos_bench",
]

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_JSON = os.path.join(REPO_ROOT, "BENCH_spmv.json")
DEFAULT_SERVE_JSON = os.path.join(REPO_ROOT, "BENCH_serve.json")
DEFAULT_DYNAMIC_JSON = os.path.join(REPO_ROOT, "BENCH_dynamic.json")
DEFAULT_CHAOS_JSON = os.path.join(REPO_ROOT, "BENCH_chaos.json")


def _load_doc(path: str) -> dict:
    """Existing BENCH json (so one mode's write keeps the other's section),
    or a fresh doc when missing/corrupt."""
    if os.path.exists(path):
        try:
            with open(path) as f:
                return json.load(f)
        except (OSError, ValueError):
            pass
    return {}


def _write_json(path: str, scale: str, entries) -> None:
    import jax

    from benchmarks.spmv_bench import prediction_summary

    doc = _load_doc(path)  # keep sections other modes recorded (corpus)
    doc.update({
        "schema": 2,
        "scale": scale,
        "jax_backend": jax.default_backend(),
        "interpret": jax.default_backend() != "tpu",
        "python": platform.python_version(),
        "entries": entries,
        "prediction": prediction_summary(entries),
    })
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    acc = doc["prediction"]
    print(f"# wrote {len(entries)} entries to {path} "
          f"(prediction accuracy {acc['accuracy']:.0%} strict, "
          f"{acc['accuracy_near']:.0%} near, {acc['matrices']} matrices)",
          file=sys.stderr)


def _write_serve_json(path: str, doc: dict) -> int:
    """Write the serving trajectory and run the serve-smoke gate; returns
    the number of gate failures."""
    from benchmarks.serve_bench import check

    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    problems = check(doc)
    for p in problems:
        print(f"SERVE: {p}", file=sys.stderr)
    mixes = doc.get("mixes", {})
    print(f"# wrote {len(mixes)} serving mixes to {path} "
          + " ".join(f"{m}:p50={o['latency_p50_s']*1e3:.1f}ms"
                     f"/hit={o['hit_rate']:.0%}" for m, o in mixes.items()),
          file=sys.stderr)
    return len(problems)


def _write_dynamic_json(path: str, doc: dict) -> int:
    """Write the dynamic-matrix trajectory and run the dynamic-smoke gate;
    returns the number of gate failures."""
    from benchmarks.dynamic_bench import check

    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    problems = check(doc)
    for p in problems:
        print(f"DYNAMIC: {p}", file=sys.stderr)
    scen = doc.get("scenarios", {})
    print(f"# wrote {len(scen)} dynamic scenarios to {path} "
          + " ".join(f"{s}:retunes={o['retunes']}/{len(o['steps'])}"
                     f"/final={o['final_key']}" for s, o in scen.items()),
          file=sys.stderr)
    return len(problems)


def _write_chaos_json(path: str, doc: dict) -> int:
    """Write the chaos trajectory and run the chaos-smoke gate; returns
    the number of gate failures."""
    from benchmarks.chaos_bench import check

    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    problems = check(doc)
    for p in problems:
        print(f"CHAOS: {p}", file=sys.stderr)
    mixes = doc.get("mixes", {})
    print(f"# wrote {len(mixes)} chaos mixes to {path} "
          + " ".join(f"{m}:success={o['success_rate']:.0%}"
                     f"/degraded={o['degraded_share']:.0%}"
                     f"/injected={o['injected']}" for m, o in mixes.items()),
          file=sys.stderr)
    return len(problems)


def _write_precision_json(path: str, scale: str, section: dict) -> int:
    """Write the precision sweep into the ``"precision"`` section of the
    SpMV trajectory and run its gate; returns the number of gate failures."""
    from benchmarks.spmv_bench import check_precision

    doc = _load_doc(path)  # keep entries/corpus the other modes recorded
    doc["schema"] = 2
    doc["precision"] = {"scale": scale, **section}
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    problems = check_precision(section)
    for p in problems:
        print(f"PRECISION: {p}", file=sys.stderr)
    recs = section["records"]
    compressed = [r for r in recs if r["variant"] != "int32-f32"]
    print(f"# wrote {len(recs)} precision records to {path} "
          f"({len(compressed)} compressed/narrow variants, "
          f"{sum(r['fallback'] for r in compressed)} fallbacks)",
          file=sys.stderr)
    return len(problems)


def _write_bsr_json(path: str, scale: str, section: dict) -> int:
    """Write the block-sparse sweep into the ``"bsr"`` section of the SpMV
    trajectory and run its gate; returns the number of gate failures."""
    from benchmarks.bsr_bench import check

    doc = _load_doc(path)  # keep entries/corpus/precision sections
    doc["schema"] = 2
    doc["bsr"] = {"scale": scale, **section}
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    problems = check(section)
    for p in problems:
        print(f"BSR: {p}", file=sys.stderr)
    recs = [r for r in section["records"] if "skipped" not in r]
    bsr_pallas = [r for r in recs
                  if r["format"] == "bsr" and r["backend"] == "pallas"]
    print(f"# wrote {len(recs)} bsr-sweep records to {path} "
          f"({len(bsr_pallas)} bsr x pallas cells, "
          f"{sum(r['fallback'] for r in bsr_pallas)} fallbacks)",
          file=sys.stderr)
    return len(problems)


def _check_native(entries) -> int:
    """Expected-native cells that silently fell back (the smoke gate)."""
    bad = [e for e in entries if e["expect_native"] and e["fallback"]]
    for e in bad:
        print(f"FALLBACK: {e['matrix']} {e['format']}x{e['backend']} "
              f"selected={e['selected_backend']}", file=sys.stderr)
    return len(bad)


def run_corpus(corpus_dir: str, json_path: str, iters: int = 5,
               warmup: int = 2) -> dict:
    """Predicted-vs-measured winner per Matrix Market file in ``corpus_dir``.

    Each matrix gets one record: its structural features, the zero-run
    selector's top prediction, the run-first autotune winner and table, and
    whether they agree (strict, and 'near' — predicted cell measured within
    25% of the winner, a statistical tie at CPU timer noise). The summary
    lands in the ``corpus`` section of BENCH_spmv.json, next to (not
    replacing) the synthetic-grid ``entries``.
    """
    from repro.core import autotune_spmv, extract_features, rank_formats
    from repro.io import iter_corpus

    records = []
    n = agree = near = 0
    for name, s in iter_corpus(corpus_dir):
        feats = extract_features(s)
        preds = rank_formats(feats)
        if not preds:
            continue
        top = preds[0]
        res = autotune_spmv(s, iters=iters, warmup=warmup)
        pred_key = (top.key.format, top.key.backend)
        ok = pred_key == (res.format, res.impl)
        t_pred = res.table.get(pred_key)
        ok_near = ok or (t_pred is not None and t_pred <= 1.25 * res.time_us)
        n += 1
        agree += ok
        near += ok_near
        records.append({
            "matrix": name,
            "nrows": feats.nrows, "ncols": feats.ncols, "nnz": feats.nnz,
            "ndiags": feats.ndiags, "band_extent": feats.band_extent,
            "rownnz_max": feats.rownnz_max,
            "predicted_format": top.key.format,
            "predicted_backend": top.key.backend,
            "predicted_est_us": top.est_us,
            "measured_format": res.format,
            "measured_backend": res.impl,
            "measured_us": res.time_us,
            "table": {f"{f}/{i}": t for (f, i), t in res.table.items()},
            "agree": bool(ok), "agree_near": bool(ok_near),
        })
        print(f"corpus/{name},{res.time_us:.2f},"
              f"predicted={top.key.format}/{top.key.backend} "
              f"measured={res.format}/{res.impl} agree={ok}")
    # repo-relative when inside the repo: the committed BENCH_spmv.json must
    # not churn with the machine (CI checkout path vs local clone)
    abs_dir = os.path.abspath(corpus_dir)
    rel = os.path.relpath(abs_dir, REPO_ROOT)
    summary = {
        "dir": rel.replace(os.sep, "/") if not rel.startswith("..") else abs_dir,
        "matrices": n,
        "accuracy": agree / n if n else 0.0,
        "accuracy_near": near / n if n else 0.0,
        "records": records,
    }
    doc = _load_doc(json_path)
    doc["schema"] = 2
    doc["corpus"] = summary
    with open(json_path, "w") as f:
        json.dump(doc, f, indent=1)
    print(f"# corpus: {n} matrices, accuracy {summary['accuracy']:.0%} strict "
          f"/ {summary['accuracy_near']:.0%} near -> {json_path}",
          file=sys.stderr)
    return summary


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="quick", choices=["quick", "bench"])
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", default=DEFAULT_JSON,
                    help="where to write the SpMV trajectory (BENCH_spmv.json)")
    ap.add_argument("--smoke", action="store_true",
                    help="spmv grid only at smoke scale; fail on unexpected "
                         "fallback (the CI benchmark gate)")
    ap.add_argument("--corpus", default=None, metavar="DIR",
                    help="Matrix Market corpus sweep: record the zero-run "
                         "selector's predicted winner vs the run-first "
                         "autotune winner per .mtx file")
    ap.add_argument("--serve", action="store_true",
                    help="serving-layer traffic mixes only -> BENCH_serve."
                         "json; fail on empty output or dispatch fallback "
                         "(the CI serve-smoke gate)")
    ap.add_argument("--serve-json", default=DEFAULT_SERVE_JSON,
                    help="where to write the serving trajectory "
                         "(BENCH_serve.json)")
    ap.add_argument("--chaos", action="store_true",
                    help="fault-injected traffic replays only -> "
                         "BENCH_chaos.json; fail when success rate < 100%%, "
                         "a quarantined backend never recovers, or the "
                         "fault hooks are not inactive no-ops (the CI "
                         "chaos-smoke gate)")
    ap.add_argument("--chaos-json", default=DEFAULT_CHAOS_JSON,
                    help="where to write the chaos trajectory "
                         "(BENCH_chaos.json)")
    ap.add_argument("--dynamic", action="store_true",
                    help="dynamic-matrix mutation scenarios only -> "
                         "BENCH_dynamic.json; fail when refresh() never "
                         "re-selects, re-tunes on the wrong side of the "
                         "threshold, or a refreshed operator falls back "
                         "(the CI dynamic-smoke gate)")
    ap.add_argument("--dynamic-json", default=DEFAULT_DYNAMIC_JSON,
                    help="where to write the dynamic-matrix trajectory "
                         "(BENCH_dynamic.json)")
    ap.add_argument("--bsr", action="store_true",
                    help="block-sparse BSR vs CSR/SELL sweep only -> 'bsr' "
                         "section of BENCH_spmv.json; fail when the fixture "
                         "block matrix is missing or a bsr x pallas cell "
                         "fell back (the CI bsr-smoke gate)")
    ap.add_argument("--precision", action="store_true",
                    help="compressed-index / mixed-precision sweep only -> "
                         "'precision' section of BENCH_spmv.json; fail on "
                         "unexpected compressed-variant fallback or storage "
                         "that does not shrink (the CI precision gate)")
    ap.add_argument("--accuracy-floor", type=float, default=None,
                    help="with --corpus: exit non-zero when 'near' prediction "
                         "accuracy drops below this fraction (CI gate)")
    args = ap.parse_args()
    from repro import compile_cache

    compile_cache.enable()

    if args.corpus:
        summary = run_corpus(args.corpus, args.json)
        if args.accuracy_floor is not None \
                and summary["accuracy_near"] < args.accuracy_floor:
            print(f"FAIL: corpus prediction accuracy "
                  f"{summary['accuracy_near']:.0%} < floor "
                  f"{args.accuracy_floor:.0%}", file=sys.stderr)
            sys.exit(1)
        return

    if args.bsr:
        from benchmarks import bsr_bench

        scale = "smoke" if args.smoke else args.scale
        rows, section = bsr_bench.collect(scale)
        print("name,us_per_call,derived")
        for row in rows:
            print(f"{row['name']},{row['us_per_call']:.2f},{row['derived']}")
        sys.exit(1 if _write_bsr_json(args.json, scale, section) else 0)

    if args.precision:
        from benchmarks import spmv_bench

        scale = "smoke" if args.smoke else args.scale
        rows, section = spmv_bench.collect_precision(scale)
        print("name,us_per_call,derived")
        for row in rows:
            print(f"{row['name']},{row['us_per_call']:.2f},{row['derived']}")
        sys.exit(1 if _write_precision_json(args.json, scale, section) else 0)

    if args.serve:
        from benchmarks import serve_bench

        scale = "smoke" if args.smoke else args.scale
        rows, doc = serve_bench.collect(scale)
        print("name,us_per_call,derived")
        for row in rows:
            print(f"{row['name']},{row['us_per_call']:.2f},{row['derived']}")
        sys.exit(1 if _write_serve_json(args.serve_json, doc) else 0)

    if args.chaos:
        from benchmarks import chaos_bench

        scale = "smoke" if args.smoke else args.scale
        rows, doc = chaos_bench.collect(scale)
        print("name,us_per_call,derived")
        for row in rows:
            print(f"{row['name']},{row['us_per_call']:.2f},{row['derived']}")
        sys.exit(1 if _write_chaos_json(args.chaos_json, doc) else 0)

    if args.dynamic:
        from benchmarks import dynamic_bench

        scale = "smoke" if args.smoke else args.scale
        rows, doc = dynamic_bench.collect(scale)
        print("name,us_per_call,derived")
        for row in rows:
            print(f"{row['name']},{row['us_per_call']:.2f},{row['derived']}")
        sys.exit(1 if _write_dynamic_json(args.dynamic_json, doc) else 0)

    if args.smoke:
        from benchmarks import spmv_bench

        rows, entries = spmv_bench.collect("smoke")
        print("name,us_per_call,derived")
        for row in rows:
            print(f"{row['name']},{row['us_per_call']:.2f},{row['derived']}")
        _write_json(args.json, "smoke", entries)
        sys.exit(1 if _check_native(entries) else 0)

    mods = [m for m in MODULES if args.only is None or args.only in m]
    print("name,us_per_call,derived")
    failed = 0
    entries = None
    serve_doc = None
    dynamic_doc = None
    chaos_doc = None
    for m in mods:
        try:
            mod = importlib.import_module(f"benchmarks.{m}")
            if m == "spmv_bench":
                rows, entries = mod.collect(args.scale)
            elif m == "serve_bench":
                rows, serve_doc = mod.collect(args.scale)
            elif m == "dynamic_bench":
                rows, dynamic_doc = mod.collect(args.scale)
            elif m == "chaos_bench":
                rows, chaos_doc = mod.collect(args.scale)
            else:
                rows = mod.run(args.scale)
            for row in rows:
                print(f"{row['name']},{row['us_per_call']:.2f},{row['derived']}")
        except Exception:
            failed += 1
            print(f"{m},0.00,ERROR", flush=True)
            traceback.print_exc(file=sys.stderr)
    if entries is not None:
        _write_json(args.json, args.scale, entries)
    if serve_doc is not None:
        failed += _write_serve_json(args.serve_json, serve_doc)
    if dynamic_doc is not None:
        failed += _write_dynamic_json(args.dynamic_json, dynamic_doc)
    if chaos_doc is not None:
        failed += _write_chaos_json(args.chaos_json, chaos_doc)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
