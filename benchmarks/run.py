"""Benchmark harness: one module per paper table/figure. Prints CSV lines.

  PYTHONPATH=src python -m benchmarks.run [--only table3,fig8,...]
"""
from __future__ import annotations

import argparse
import importlib
import sys
import time
import traceback

MODULES = [
    ("table3", "benchmarks.table3_features"),
    ("table45", "benchmarks.table45_accuracy"),
    ("fig67", "benchmarks.fig67_latency"),
    ("fig8", "benchmarks.fig8_planner"),
    ("fig9", "benchmarks.fig9_resources"),
    ("table78", "benchmarks.table78_usage"),
    ("roofline", "benchmarks.roofline_table"),
    ("perf", "benchmarks.perf_levers"),
    ("kernels", "benchmarks.kernels_bench"),
    ("zoo", "benchmarks.zoo_swap"),
    ("runtime_scale", "benchmarks.runtime_scale"),
    ("serve_async", "benchmarks.serve_async"),
    ("fleet_serve", "benchmarks.fleet_serve"),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    if only:
        valid = {name for name, _ in MODULES}
        unknown = only - valid
        if unknown:
            print(f"error: unknown --only module(s) {sorted(unknown)}; "
                  f"valid names: {sorted(valid)}", file=sys.stderr)
            sys.exit(2)
    from benchmarks.common import use_compile_cache

    use_compile_cache()
    failures = 0
    for name, mod in MODULES:
        if only and name not in only:
            continue
        t0 = time.perf_counter()
        try:
            lines = importlib.import_module(mod).run()
            for line in lines:
                print(line)
            print(f"# {name}: {len(lines)} rows in {time.perf_counter()-t0:.1f}s",
                  flush=True)
        except Exception as e:  # pragma: no cover
            failures += 1
            print(f"# {name}: FAILED {type(e).__name__}: {e}", flush=True)
            traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
