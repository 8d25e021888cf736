"""Benchmark suite entry: harness scenarios + the remaining ad-hoc benches.

    PYTHONPATH=src python -m benchmarks.run [--full]

The four gated cases (overhead, serving, cholesky, lm) run through the
evaluation harness (DESIGN.md §13) — each appends one unified record to
``BENCH_trend.jsonl`` — which is also what finally wires ``bench_serving``
into this suite entry (it previously had no route here at all).  The
exploratory benches without gates (hierarchy, distributed cholesky,
roofline) still run as plain modules.  Every phase runs; the exit code is
nonzero when any of them failed.  For the gated path with baseline
diffing use ``python -m benchmarks.harness check`` directly.

CSV rows: name,us_per_call,derived.
"""

from __future__ import annotations

import argparse
import sys
import traceback


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="larger sizes")
    args = ap.parse_args()
    mode = "full" if args.full else "smoke"
    quick = not args.full

    from benchmarks.harness import REGISTRY, append_trend
    from benchmarks.harness import scenarios  # noqa: F401 — registers

    from . import bench_cholesky_dist, bench_hierarchy, bench_roofline

    print("name,us_per_call,derived")
    failed = []
    for name in sorted(REGISTRY):
        try:
            append_trend(REGISTRY[name].run(mode))
        except Exception as e:  # noqa: BLE001 — run the rest, then fail
            print(f"harness:{name},BENCH_FAILED,{e!r}")
            traceback.print_exc()
            failed.append(f"harness:{name}")
    for mod in (bench_hierarchy, bench_cholesky_dist, bench_roofline):
        try:
            mod.main(quick=quick)
        except Exception as e:  # noqa: BLE001 — run the rest, then fail
            print(f"{mod.__name__},BENCH_FAILED,{e!r}")
            traceback.print_exc()
            failed.append(mod.__name__)
    return 1 if failed else 0


if __name__ == "__main__":
    from repro.compat import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
