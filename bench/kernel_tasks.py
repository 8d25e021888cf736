"""Tile tasks by the kernel that runs them: the prefix rule.

``flops.tasks`` names each row of tasks after its kernel, and tasks that a
kernel runs on other operands after the kernel and a qualifier: LU-solve's
substitution tasks ``trsml.rhs``, ``trsmul.rhs``, ``gemmnn.rhs_forward`` and
``gemmnn.rhs_backward`` run on the device as the kernels ``trsml``,
``trsmul`` and ``gemmnn``.  So a row belongs to the kernel named before its
first ``.``.  (``program_trace.kernel_roofline`` keeps only the rows named
exactly after the kernel, which leaves those tasks out.)
"""

from __future__ import annotations

from typing import List, Optional

from bench import flops, program_trace
from bench.readers import _solution_shape


def kernel_of(row: str) -> str:
    """``gemmnn`` for the rows ``gemmnn`` and ``gemmnn.rhs_forward``."""
    return row.split(".", 1)[0]


def kernel_rows(task_list: List[flops.Task], kernel: str) -> List[flops.Task]:
    return [t for t in task_list if kernel_of(t[0]) == kernel]


def kernel_roofline(ctx, kernel: str) -> Optional[float]:
    """The ideal time of the window's tasks that ``kernel`` runs, at the
    chip's peaks, over that kernel's device time, in %."""
    red = program_trace.summary(ctx)["trace"]
    solutions = ctx.counts.get("solutions")
    if red is None or not red["kernel_s"].get(kernel) or not solutions:
        return None
    op, n, b, nrhs = _solution_shape(ctx)
    rows = kernel_rows(flops.tasks(op, n, b, nrhs), kernel)
    ideal, _ = flops.roofline_s(rows, ctx.peaks["bf16_flops"], ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * solutions * ideal / red["kernel_s"][kernel]
