"""Benchmark driver — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. ``--quick`` shrinks workload scales
and MCTS budgets for CI-speed runs; the default configuration is what
bench_output.txt records. ``--json PATH`` additionally writes a machine-
readable summary (rows + per-suite wall time + failures) — CI uploads it as
an artifact. A suite that raises marks the run failed (nonzero exit), so
dispatch-path regressions in smoke-benchmarked suites fail CI.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark names")
    ap.add_argument("--json", default=None,
                    help="write a JSON summary of all rows to this path")
    args = ap.parse_args()
    q = args.quick

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import (ablation, complex_queries, cost_model_bench,
                            optimizers, plan_cache_bench, random_queries,
                            serving_bench, sharded_bench,
                            simplified_analytics)

    suites = {
        "plan_cache": lambda: plan_cache_bench.run(scale=0.3 if q else 0.5),
        # cost-oracle accuracy: predicted vs measured + calibration error;
        # the JSON summary gains a `cost_model` section from this suite
        "cost": lambda: cost_model_bench.run(
            scale=0.3 if q else 0.5, repeats=5 if q else 9,
            queries=cost_model_bench.QUICK_QUERIES if q else None),
        "serving": lambda: serving_bench.run(
            scale=0.08, batch_sizes=(1, 2, 8, 16) if q else (1, 2, 4, 8, 16),
            mix_requests=21 if q else 42, repeats=7 if q else 15),
        # multi-device batch sharding; CI forces 8 fake CPU devices via
        # XLA_FLAGS=--xla_force_host_platform_device_count=8 for this suite
        "sharded": lambda: sharded_bench.run(
            scale=0.08, batch_size=8 if q else 16,
            serve_requests=16 if q else 32, repeats=5 if q else 9),
        "complex_queries": lambda: complex_queries.run(
            scale=0.5 if q else 1.0, iterations=15 if q else 40),
        "ablation": lambda: ablation.run(
            scale=0.5 if q else 1.0, iterations=10 if q else 25),
        "simplified_analytics": lambda: simplified_analytics.run(
            scales=(0.5,) if q else (1.0, 3.0), iterations=8 if q else 18),
        "optimizers": lambda: optimizers.run(
            n_id=8 if q else 24, n_ood=4 if q else 12,
            iterations=6 if q else 15, train_steps=30 if q else 80),
        "random_queries": lambda: random_queries.run(
            n_queries=8 if q else 24, iterations=5 if q else 10),
    }
    only = set(args.only.split(",")) if args.only else None
    if only:
        unknown = only - set(suites)
        if unknown:
            # a typo'd --only must not silently benchmark nothing (CI
            # relies on this run as a regression gate)
            print(f"unknown suite(s): {sorted(unknown)}; "
                  f"available: {sorted(suites)}", file=sys.stderr)
            sys.exit(2)
    summary = {"quick": q, "suites": {}, "rows": [], "failed": []}

    def write_summary():
        # rewritten after every suite so a timeout kill still leaves the
        # partial artifact for diagnosis
        if args.json:
            with open(args.json, "w") as f:
                json.dump(summary, f, indent=2)

    print("name,us_per_call,derived")
    for name, fn in suites.items():
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            for line in fn():
                print(line, flush=True)
                parts = line.split(",", 2)
                summary["rows"].append({
                    "name": parts[0],
                    "us_per_call": float(parts[1]) if len(parts) > 1 else None,
                    "derived": parts[2] if len(parts) > 2 else ""})
            summary["suites"][name] = round(time.time() - t0, 1)
            if name == "cost":
                # oracle-accuracy tracking across PRs (BENCH_*.json)
                summary["cost_model"] = cost_model_bench.LAST_SUMMARY
            print(f"# suite {name} done in {time.time() - t0:.1f}s",
                  file=sys.stderr)
        except Exception:
            summary["failed"].append(name)
            print(f"# suite {name} FAILED", file=sys.stderr)
            traceback.print_exc()
        write_summary()
    if summary["failed"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
