"""Cells of ``BENCHMARK.json`` cut to a size the CPU runs in seconds.

The sizes and limits here are the tests' own: at n = 64 on the CPU the
program reads a reference gap of at most 0.01, and the control (three bf16
passes) at least 0.18.
"""

from __future__ import annotations

import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

from bench import harness  # noqa: E402

LIMITS = {"ref_gap.cholesky": 0.05}
FACTOR = "chol16k"
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}


def cell(name: str, **mix) -> harness.Cell:
    c = harness.resolve(harness.load_benchmark(), name)
    c.config.update(n=64, tile=16, partitions=[[4, 4]], limits=dict(LIMITS))
    c.mix.update(warmup=1, **mix)
    return c


def run(c: harness.Cell, seed: int = 2**31 + 7, seconds: float = 0.5, trace: bool = False) -> dict:
    return harness.run_cell(c, seed, seconds, trace, t_start=time.perf_counter(),
                            require_tpu=False, peaks=PEAKS)
