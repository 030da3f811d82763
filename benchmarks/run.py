"""Benchmark harness: one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--full] [--only fig14,...]``
prints ``name,us_per_call,derived`` CSV rows and writes a machine-readable
``BENCH_<label>.json`` artifact (results/bench/ by default) so the perf
trajectory is tracked across PRs — compare against the committed
``BENCH_seed.json`` baseline.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time
import traceback

from .common import RESULTS_DIR

MODULES = [
    "fig13_active_instances",   # Fig. 13: active instances over time
    "fig14_interruptions",      # Fig. 14: interruption counts per policy
    "fig15_durations",          # Fig. 15: interruption durations
    "trace_scale",              # §VII-C/D: trace-scale simulation
    "fig16_correlation",        # Fig. 16: advisor association analysis
    "allocation_throughput",    # §VII-D1: scoring throughput (np/jax/pallas)
    "market_engine",            # PR 2: wave selection + engine end-to-end
    "price_layer",              # PR 5: fused price ticks + batched billing
    "fleet",                    # PR 6: fleet replenish planner + liveness scan
    "serve",                    # PR 10: autoscale tick + request throughput
    "migration",                # PR 3: migration-planner throughput
    "victim_selection",         # beyond-paper: §IX victim selectors
    "cost_analysis",            # beyond-paper: $ cost / waste per policy
    "roofline",                 # §Roofline from dry-run artifacts
]

DEFAULT_JSON_DIR = RESULTS_DIR


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full-scale runs (slower)")
    ap.add_argument("--only", default="",
                    help="comma-separated module subset")
    ap.add_argument("--label", default="",
                    help="artifact label -> BENCH_<label>.json "
                         "(default: quick|full)")
    ap.add_argument("--json-dir", default=DEFAULT_JSON_DIR,
                    help="directory for the JSON artifact")
    args = ap.parse_args(argv)

    label = args.label or ("full" if args.full else "quick")
    selected = args.only.split(",") if args.only else MODULES
    print("name,us_per_call,derived")
    failures = 0
    results = []
    for name in selected:
        t0 = time.time()
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            rows = mod.run(quick=not args.full)
            if rows:
                results.extend(r for r in rows if isinstance(r, dict))
        except Exception:
            failures += 1
            print(f"{name},0,ERROR", flush=True)
            traceback.print_exc(file=sys.stderr)
        print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)

    os.makedirs(args.json_dir, exist_ok=True)
    path = os.path.join(args.json_dir, f"BENCH_{label}.json")
    with open(path, "w") as f:
        json.dump({
            "label": label,
            "mode": "full" if args.full else "quick",
            "modules": selected,
            "failures": failures,
            "results": results,
        }, f, indent=1)
    print(f"# wrote {path}", flush=True)
    return 1 if failures else 0


def main_legacy() -> None:  # kept for the original scaffold entry point
    raise SystemExit(main())


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    raise SystemExit(main())
