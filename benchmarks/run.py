"""Benchmark harness: one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--only fig7,fig12] [--fast]
[--json BENCH.json]`` prints ``bench,metric,value,notes`` CSV rows. ``--fast``
switches bench modules to small-shape quick mode (exported as the
``REPRO_BENCH_FAST=1`` env var) so CI smoke jobs finish in minutes; ``--json``
additionally writes the rows to a machine-readable file for artifact upload,
so the per-PR perf trajectory accumulates.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

BENCHES = [
    ("table1", "benchmarks.bench_table1_cost"),
    ("fig7", "benchmarks.bench_fig7_jct"),
    ("fig8", "benchmarks.bench_fig8_convergence"),
    ("fig9", "benchmarks.bench_fig9_warmstart"),
    ("fig10", "benchmarks.bench_fig10_autoscaling"),
    ("fig11", "benchmarks.bench_fig11_perfmodel"),
    ("fig12", "benchmarks.bench_fig12_hotps"),
    ("fig13", "benchmarks.bench_fig13_straggler"),
    ("fig14", "benchmarks.bench_fig14_cluster"),
    ("fig15", "benchmarks.bench_fig15_jct_cdf"),
    ("chaos", "benchmarks.bench_chaos"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated bench keys (e.g. fig7,fig12)")
    ap.add_argument("--fast", action="store_true",
                    help="small-shape quick mode (sets REPRO_BENCH_FAST=1)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as JSON (CI artifact)")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    if args.fast:
        os.environ["REPRO_BENCH_FAST"] = "1"

    print("bench,metric,value,notes")
    failed = []
    json_rows = []
    for key, module_name in BENCHES:
        if only and key not in only:
            continue
        t0 = time.perf_counter()
        try:
            import importlib
            mod = importlib.import_module(module_name)
            rows = mod.run()
            for name, value, notes in rows:
                print(f"{key},{name},{value:.6g},{notes}")
                json_rows.append({"bench": key, "metric": name,
                                  "value": float(value), "notes": notes})
            elapsed = time.perf_counter() - t0
            print(f"{key},_elapsed_s,{elapsed:.1f},")
            json_rows.append({"bench": key, "metric": "_elapsed_s",
                              "value": elapsed, "notes": ""})
        except Exception as e:
            failed.append(key)
            print(f"{key},_error,nan,{type(e).__name__}: {e}")
            traceback.print_exc(file=sys.stderr)
    if args.json:
        fast = os.environ.get("REPRO_BENCH_FAST", "") == "1"
        with open(args.json, "w") as f:
            json.dump({"fast": fast, "rows": json_rows}, f, indent=2)
    if failed:
        print(f"#FAILED: {','.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
