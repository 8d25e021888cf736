#!/usr/bin/env python3
"""Readings of a cell's correctness numbers over many seeds, in one process.

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 --seconds 3 [--control]

Runs the cell as ``bench/run.py`` does, once per seed, sharing one JAX start
and its compiled programs, and prints one JSON line per seed: the numbers
compared with their limits, ``correct``, and the end-to-end metrics.  With
``--control`` the reference computed one precision down takes the program's
place (``bench/control.py``); its readings set the upper end of each limit,
the program's the lower.  The benchmark's own runs never run this.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    harness.setup_process()
    cell = harness.resolve(harness.load_benchmark(), args.workload)
    if args.control:
        from bench.control import controlled

        cell.config = controlled(cell.config)
    seeds = [int(s) for s in args.seeds.split(",")]
    t = T_START
    for seed in seeds:
        res = harness.run_cell(cell, seed, args.seconds, False, t_start=t)
        print(json.dumps({"seed": seed, "control": args.control, **res}), flush=True)
        gc.collect()  # the last seed's inputs and programs, before the next
        t = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
