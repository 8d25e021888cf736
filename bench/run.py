#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (JAX start, inputs made on the device from the seed, programs loaded
from the compilation cache or compiled, warm-up of the cell's own shapes) is
timed as ``setup_s``; then the window runs for ``--seconds``.  With
``--trace 0`` the result holds the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the result holds its
per-layer metrics, the device's busy time and a breakdown.  After the window
the answers are compared with the plain reference (``bench/reference.py``);
each number compared is printed beside its limit on the last lines of
standard error and under ``checks`` in the result.

The last line of standard output is the result, one JSON object.  Without a
TPU, or with fewer chips than the cell asks for, the run exits nonzero and
prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    harness.setup_process()
    cell = harness.resolve(harness.load_benchmark(), args.workload)
    result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace), t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
