"""Benchmark harness — one module per paper table/figure + kernels/roofline.
Prints ``name,us_per_call,derived`` CSV.

  PYTHONPATH=src python -m benchmarks.run            # fast mode (~10 min CPU)
  PYTHONPATH=src python -m benchmarks.run --full     # paper-scale settings
  PYTHONPATH=src python -m benchmarks.run --only fig5,table4
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

BENCHES = [
    ("comm", "benchmarks.comm_cost"),            # Tables 1-2
    ("wire", "benchmarks.wire_bench"),           # measured codec bytes
    ("fig2", "benchmarks.fd_logit"),             # FD logit collapse
    ("fig3", "benchmarks.entropy_bench"),        # entropy traces (Figs 3/9)
    ("fig5", "benchmarks.accuracy_vs_comm"),     # acc vs comm + Table 3
    ("fig6", "benchmarks.temperature"),          # ERA temperature sweep
    ("fig7", "benchmarks.noisy_label"),          # noisy labels
    ("fig8", "benchmarks.noisy_open"),           # noisy open data
    ("table4", "benchmarks.poisoning"),          # model poisoning
    ("ttacc", "benchmarks.time_to_accuracy"),    # sim: acc vs wallclock/bytes
    ("engine", "benchmarks.engine_bench"),       # loop-vs-scan + weighted ERA
    ("serve", "benchmarks.serve_bench"),         # continuous batching + swap
    ("obs", "benchmarks.obs_smoke"),             # traced stack + no-recompile
    ("kernels", "benchmarks.kernels_bench"),     # Pallas kernels
    ("roofline", "benchmarks.roofline_report"),  # dry-run roofline table
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench keys to run")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    from repro.launch import platform
    platform.enable_compile_cache()

    print("name,us_per_call,derived")
    failures = 0
    for key, module_name in BENCHES:
        if only and key not in only:
            continue
        t0 = time.time()
        try:
            mod = __import__(module_name, fromlist=["run"])
            rows = mod.run(fast=not args.full)
            for name, us, derived in rows:
                print(f"{name},{us:.1f},{derived}", flush=True)
            print(f"# {key} done in {time.time()-t0:.0f}s", file=sys.stderr)
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"# {key} FAILED:\n{traceback.format_exc()}",
                  file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
