"""Benchmark harness — one module per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--only <name>]``
prints ``name,us_per_call,derived`` CSV rows (empty us = quality metric).
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from repro.utils.compile_cache import enable_compile_cache

MODULES = [
    ("balance", "benchmarks.bench_balance"),          # Fig. 4
    ("index_build", "benchmarks.bench_index_build"),  # Table 1
    ("recall", "benchmarks.bench_recall"),            # Tables 2/3 + §5.6
    ("drift", "benchmarks.bench_drift"),              # §3.2
    ("merge_sort", "benchmarks.bench_merge_sort"),    # §3.4 / Alg. 1
    ("kernels", "benchmarks.bench_kernels"),          # kernel layer
    ("serving", "benchmarks.bench_serving"),          # §3.4 / Appendix B
    ("freshness", "benchmarks.bench_freshness"),      # §3.1 immediacy
    ("observability", "benchmarks.bench_observability"),  # obs overhead
    ("quality", "benchmarks.bench_quality"),          # probes + SLO loop
    ("federation", "benchmarks.bench_federation"),    # §4 fleet serving
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    enable_compile_cache()
    print("name,us_per_call,derived")
    failures = 0
    for name, modpath in MODULES:
        if args.only and args.only != name:
            continue
        t0 = time.time()
        try:
            mod = __import__(modpath, fromlist=["run"])
            for row in mod.run():
                n, us, derived = row
                us_s = "" if us is None else f"{us:.1f}"
                print(f"{n},{us_s},{derived}", flush=True)
            print(f"# {name} done in {time.time() - t0:.1f}s",
                  file=sys.stderr)
        except Exception:
            failures += 1
            print(f"# {name} FAILED:\n{traceback.format_exc()}",
                  file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
