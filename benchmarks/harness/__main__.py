"""CLI for the evaluation harness (DESIGN.md §13).

    python -m benchmarks.harness list
    python -m benchmarks.harness run        [--mode smoke|full] [--scenario S]*
    python -m benchmarks.harness check      [--mode ...] [--scenario S]*
                                            [--baseline PATH] [--record PATH]
                                            [--band F] [--report PATH]
                                            [--no-trend]
    python -m benchmarks.harness rebaseline [--mode ...] [--scenario S]*
                                            [--baseline PATH] [--band F]

``check`` runs the selected scenarios (or loads pre-recorded trend lines
via ``--record``, which is how CI's synthetic-regression negative test
feeds a tampered record back through the differ), appends unified records
to ``BENCH_trend.jsonl``, evaluates every declared gate against the
committed ``BENCH_baseline.json``, writes the findings artifact
(``BENCH_report.json``) and exits nonzero on any failing gate.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List

from .baseline import (
    BASELINE_PATH,
    DEFAULT_BAND,
    MissingBaselineError,
    check_result,
    load_baseline,
    save_baseline,
    summarize,
)
from .record import Result, append_trend, read_trend
from .scenario import MODES, REGISTRY

REPORT_PATH = "BENCH_report.json"


def _select(names: List[str]) -> Dict[str, object]:
    # import registers the built-in scenarios
    from . import scenarios  # noqa: F401

    if not names:
        return dict(REGISTRY)
    unknown = [n for n in names if n not in REGISTRY]
    if unknown:
        raise SystemExit(
            f"unknown scenario(s) {unknown}; have {sorted(REGISTRY)}"
        )
    return {n: REGISTRY[n] for n in names}


def _run_scenarios(selected, mode: str, trend: bool) -> List[Result]:
    results = []
    for name, sc in sorted(selected.items()):
        print(f"## harness run: {name} [{mode}]")
        r = sc.run(mode)
        if trend:
            append_trend(r)
        results.append(r)
    return results


def _load_record(path: str, selected, mode: str) -> List[Result]:
    """Results for ``check --record``: the latest trend line per selected
    scenario at the requested mode."""
    latest: Dict[str, Result] = {}
    for r in read_trend(path):
        if r.scenario in selected and r.mode == mode:
            latest[r.scenario] = r
    missing = sorted(set(selected) - set(latest))
    if missing:
        raise SystemExit(
            f"{path}: no {mode!r} record for scenario(s) {missing}"
        )
    return [latest[n] for n in sorted(latest)]


def cmd_list(args) -> int:
    selected = _select(args.scenario)
    for name, sc in sorted(selected.items()):
        kinds = {}
        for g in sc.gates:
            kinds[g.kind] = kinds.get(g.kind, 0) + 1
        gates = ", ".join(f"{v} {k}" for k, v in sorted(kinds.items()))
        print(f"{name:10s} workload={sc.workload:8s} gates: {gates or 'none'}")
    return 0


def cmd_run(args) -> int:
    selected = _select(args.scenario)
    results = _run_scenarios(selected, args.mode, trend=not args.no_trend)
    for r in results:
        print(
            f"# recorded {r.scenario} [{r.mode}]: "
            f"{len(r.metrics)} metrics, {len(r.counters)} counters"
        )
    return 0


def cmd_check(args) -> int:
    selected = _select(args.scenario)
    if args.record:
        results = _load_record(args.record, selected, args.mode)
    else:
        results = _run_scenarios(selected, args.mode, trend=not args.no_trend)

    try:
        baseline = load_baseline(args.baseline)
    except MissingBaselineError as e:
        print(f"harness check: {e}", file=sys.stderr)
        return 2

    findings = []
    for r in results:
        findings.extend(
            check_result(r, baseline, selected[r.scenario].gates,
                         default_band=args.band)
        )
    ok, text = summarize(findings)
    print(text)
    report = {
        "mode": args.mode,
        "ok": ok,
        "scenarios": sorted(r.scenario for r in results),
        "findings": [f.to_dict() for f in findings],
    }
    with open(args.report, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# wrote {args.report}")
    return 0 if ok else 1


def cmd_rebaseline(args) -> int:
    selected = _select(args.scenario)
    results = _run_scenarios(selected, args.mode, trend=True)
    save_baseline(results, path=args.baseline, band_default=args.band)
    print(
        f"# rebaselined {sorted(selected)} [{args.mode}] -> {args.baseline}"
    )
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m benchmarks.harness")
    sub = ap.add_subparsers(dest="cmd", required=True)

    def common(p, band_default=None):
        p.add_argument("--mode", choices=MODES, default="smoke")
        p.add_argument(
            "--scenario", action="append", default=[],
            help="restrict to this scenario (repeatable)",
        )
        p.add_argument("--baseline", default=BASELINE_PATH)
        p.add_argument("--band", type=float, default=band_default)

    p = sub.add_parser("list", help="list registered scenarios")
    p.add_argument("--scenario", action="append", default=[])
    p.set_defaults(fn=cmd_list)

    p = sub.add_parser("run", help="run scenarios, append trend records")
    common(p)
    p.add_argument("--no-trend", action="store_true")
    p.set_defaults(fn=cmd_run)

    p = sub.add_parser(
        "check", help="run (or load --record) and diff against baseline"
    )
    common(p)
    p.add_argument(
        "--record", default=None,
        help="diff pre-recorded trend lines from this file instead of running",
    )
    p.add_argument("--report", default=REPORT_PATH)
    p.add_argument("--no-trend", action="store_true")
    p.set_defaults(fn=cmd_check)

    p = sub.add_parser("rebaseline", help="re-record the baseline (reviewed)")
    common(p, band_default=DEFAULT_BAND)
    p.set_defaults(fn=cmd_rebaseline)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    from repro.compat import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
