"""Benchmark harness entrypoint — one benchmark per paper table/figure.

``python -m benchmarks.run``            full human-readable report
``python -m benchmarks.run --csv``      name,us_per_call,derived CSV rows
``python -m benchmarks.run --fast``     complexity-only (skip training runs)
"""

from __future__ import annotations

import argparse
import sys


def main() -> None:
    sys.path.insert(0, "src")
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--csv", action="store_true")
    ap.add_argument("--fast", action="store_true",
                    help="skip the small training-based quality benchmarks")
    args = ap.parse_args()

    from repro.obs.clock import now
    t0 = now()

    from benchmarks import (appendix_b_prediction, paged_kv_bench,
                            prefill_bench, prefix_cache_bench, pruning_soi,
                            quality_pp, selfspec_bench,
                            serving_trace_bench, soi_lm_bench,
                            table1_pp_soi, table2_fp_soi, table3_resampling,
                            table4_asc)

    # every bench below emits a machine-readable BENCH_*.json trajectory
    # point next to its human-readable report
    table1_pp_soi.run(csv=args.csv)
    table2_fp_soi.run(csv=args.csv)
    table4_asc.run(csv=args.csv, train_quality=not args.fast)
    soi_lm_bench.run(csv=args.csv)
    if not args.fast:
        table3_resampling.run(csv=args.csv)
        quality_pp.run(csv=args.csv)
        pruning_soi.run(csv=args.csv)
        appendix_b_prediction.run(csv=args.csv)
        # serving benches (compile-heavy: skipped under --fast)
        paged_kv_bench.run(csv=args.csv)
        prefill_bench.run(csv=args.csv)
        prefix_cache_bench.run(csv=args.csv)
        selfspec_bench.run(csv=args.csv)
        serving_trace_bench.run(csv=args.csv)

    # roofline summary (from stored dry-run artifacts, if present)
    try:
        from benchmarks import roofline
        rows = roofline.build_table()
        ok = [r for r in rows if r.get("status") == "ok"]
        if ok and not args.csv:
            print(f"\n== Roofline (from {len(ok)} dry-run cells; full table "
                  "in experiments/roofline.md) ==")
            worst = sorted(ok, key=lambda r: r["roofline_fraction"])[:3]
            for r in worst:
                print(f"  worst: {r['arch']} {r['shape']} {r['mesh']} "
                      f"dominant={r['dominant']} "
                      f"frac={r['roofline_fraction']:.2f}")
    except Exception as e:
        print(f"(roofline table unavailable: {e})")

    # trajectory lint: every BENCH_*.json this run left behind must parse
    # as the flat-scalar trajectory schema — a malformed file fails the
    # harness here instead of silently corrupting repro.launch.plan's
    # measured inputs (the same validator gates checked-in files in tier-1)
    from repro.launch.bench import repo_bench_files, validate_bench_file
    errors = []
    for path in repo_bench_files("."):
        errors += validate_bench_file(path)
    if errors:
        print("\nBENCH schema lint FAILED:")
        for e in errors:
            print(f"  {e}")
        raise SystemExit(1)

    if not args.csv:
        print(f"\ntotal benchmark time: {now() - t0:.1f}s")


if __name__ == "__main__":
    main()
