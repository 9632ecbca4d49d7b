"""Benchmark suite orchestrator: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Caches datasets/trained models in
results/bench_cache so repeated runs are fast.

Exit status is the CI contract: non-zero when any sub-benchmark raises
(each failure is also recorded as a ``<tag>/_FAILED`` row and in the
``--json`` summary) or when ``--only`` names an unknown tag — a misspelled
filter must not silently gate on an empty run.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

MODULES = [
    ("table7_decomposer", "benchmarks.bench_decomposer"),
    ("table8_kernel_mape", "benchmarks.bench_kernel_mape"),
    ("fig4_ablation", "benchmarks.bench_ablation"),
    ("fig7_overhead", "benchmarks.bench_overhead"),
    ("fig8_table10_perf_gap", "benchmarks.bench_perf_gap"),
    ("table9_e2e", "benchmarks.bench_e2e"),
    ("sweep", "benchmarks.bench_sweep"),
    ("placement", "benchmarks.bench_placement"),
    ("fleet", "benchmarks.bench_fleet"),
    ("parallelism", "benchmarks.bench_parallelism"),
    ("roofline", "benchmarks.bench_roofline"),
]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", help="comma-separated module tags to run")
    ap.add_argument("--json", help="write a machine-readable run summary here")
    args = ap.parse_args()
    from benchmarks.common import Csv
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    known = {tag for tag, _ in MODULES}
    selected = known
    if args.only:
        selected = set(args.only.split(","))
        unknown = selected - known
        if unknown:
            print(
                f"unknown --only tags: {sorted(unknown)}; known: {sorted(known)}",
                file=sys.stderr,
            )
            return 2

    csv = Csv()
    print("name,us_per_call,derived")
    statuses = {}
    failures = 0
    for tag, modname in MODULES:
        if tag not in selected:
            continue
        t0 = time.time()
        try:
            mod = __import__(modname, fromlist=["run"])
            mod.run(csv)
            statuses[tag] = {"status": "ok", "elapsed_s": time.time() - t0}
            csv.add(f"{tag}/_elapsed_s", 0.0, f"{time.time()-t0:.1f}")
        except Exception as e:  # noqa: BLE001
            failures += 1
            traceback.print_exc()
            statuses[tag] = {
                "status": "failed",
                "elapsed_s": time.time() - t0,
                "error": f"{type(e).__name__}: {e}",
            }
            csv.add(f"{tag}/_FAILED", 0.0, f"{type(e).__name__} (see stderr)")
    if args.json:
        from benchmarks.common import write_bench_json

        write_bench_json(args.json, csv, modules=statuses, failures=failures)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
