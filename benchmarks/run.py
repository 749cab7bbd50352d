"""Benchmark orchestrator — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines and writes a consolidated
``BENCH_summary.json`` at the repo root (per-module status, wall time and
returned metrics) so the perf trajectory is machine-readable across PRs
without scraping per-module JSONs.  ``--fast`` trims sweeps (CI); default
runs the full grids.

  PYTHONPATH=src python -m benchmarks.run [--fast] [--only fig4,...]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

REPO_ROOT = os.path.join(os.path.dirname(__file__), "..")
SUMMARY_JSON = os.path.join(REPO_ROOT, "BENCH_summary.json")

MODULES = [
    ("fig4", "benchmarks.fig4_bfp_sweep"),
    ("fig5", "benchmarks.fig5_kv_sweep"),
    ("fig8", "benchmarks.fig8_asym_ablation"),
    ("fig10", "benchmarks.fig10_smoothing"),
    ("table1", "benchmarks.table1_ppl"),
    ("table2", "benchmarks.table2_longtask"),
    ("fig15", "benchmarks.fig15_dataflow"),
    ("fig1618", "benchmarks.fig1618_accelerators"),
    ("fig19", "benchmarks.fig19_seqlen"),
    ("kernels", "benchmarks.kernels_micro"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated benchmark keys")
    args = ap.parse_args()

    only = set(args.only.split(",")) if args.only else None
    failures = []
    # record the filter: a partial --only run must be distinguishable
    # from a full sweep when reading the trajectory file later
    summary = {"meta": {"fast": args.fast,
                        "only": sorted(only) if only else None,
                        "started_unix": int(time.time())},
               "modules": {}}
    print("name,us_per_call,derived")
    for key, modname in MODULES:
        if only and key not in only:
            continue
        t0 = time.time()
        try:
            mod = __import__(modname, fromlist=["main"])
            result = mod.main(fast=args.fast)
            entry = {"status": "ok",
                     "seconds": round(time.time() - t0, 2)}
            if isinstance(result, dict):
                entry["result"] = result
            summary["modules"][key] = entry
            print(f"{key}.TOTAL,{(time.time()-t0)*1e6:.0f},ok")
        except Exception as e:
            traceback.print_exc()
            failures.append((key, repr(e)))
            summary["modules"][key] = {
                "status": "failed", "error": repr(e),
                "seconds": round(time.time() - t0, 2)}
            print(f"{key}.TOTAL,{(time.time()-t0)*1e6:.0f},FAILED:{e!r}")
    with open(SUMMARY_JSON, "w") as f:
        json.dump(summary, f, indent=1)
    print(f"# wrote {os.path.normpath(SUMMARY_JSON)}")
    if failures:
        print(f"# {len(failures)} benchmark(s) failed: "
              f"{[k for k, _ in failures]}", file=sys.stderr)
        sys.exit(1)
    print("# all benchmarks passed")


if __name__ == "__main__":
    main()
