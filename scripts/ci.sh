#!/usr/bin/env bash
# Tier-1 gate.  Run from anywhere; cds to the repo root.
#   scripts/ci.sh
# The chip benchmark is separate: BENCHMARK.json + bench/run.py (DESIGN.md §13).
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

echo "== tier-1: pytest =="
# no -x: report every failure; set -e still fails the gate on any red test
python -m pytest -q

echo "== tier-1 under REPRO_VERIFY=1: every drain hazard-checked + plan-proven =="
REPRO_VERIFY=1 python -m pytest -q

echo "== gate: operation-algebra linter over the full registry (DESIGN.md §11) =="
python scripts/lint_ops.py

echo "== gate: ruff check baseline (skipped when ruff is not installed) =="
if command -v ruff >/dev/null 2>&1; then
  ruff check src
else
  echo "ruff not installed; skipping (config: ruff.toml)"
fi
