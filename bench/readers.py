"""Computations shared by the per-layer metric readers in ``bench/metrics``.

Each reader takes the run's ``Context`` (``bench/harness.py``) and returns a
number, or ``None`` where the run holds nothing to read.
"""

from __future__ import annotations

from typing import Optional

from bench import flops


def _solution_shape(ctx):
    return ctx.mix["op"], int(ctx.config["n"]), int(ctx.config["tile"]), int(ctx.mix.get("nrhs", 1))


def host_ms_per_call(ctx) -> Optional[float]:
    """Mean host time of an entry call, from its start until it returns
    (before ``block_until_ready``), in ms."""
    d = ctx.spans.durations("entry_call")
    return 1e3 * sum(d) / len(d) if d else None


def idle_share(ctx) -> Optional[float]:
    """1 - (union of device op intervals / traced window), in %."""
    return None if ctx.trace is None else 100.0 * ctx.trace["idle_share"]


def pallas_roofline(ctx) -> Optional[float]:
    """Least time of the window's tile tasks at the chip's peaks, over the
    device time of all Pallas custom calls in the trace, in %."""
    if ctx.trace is None or not ctx.trace["pallas_s"] or not ctx.counts.get("solutions"):
        return None
    op, n, b, nrhs = _solution_shape(ctx)
    ideal, _ = flops.roofline_s(
        flops.tasks(op, n, b, nrhs), ctx.peaks["bf16_flops"], ctx.peaks["hbm_bytes_per_s"]
    )
    return 100.0 * ctx.counts["solutions"] * ideal / ctx.trace["pallas_s"]


def mfu(ctx) -> Optional[float]:
    """Algorithmic flops of the window's solutions over its time and the
    chip's bf16 peak, in %."""
    if not ctx.counts.get("solutions"):
        return None
    op, n, _, nrhs = _solution_shape(ctx)
    rate = ctx.counts["solutions"] * flops.algorithmic_flops(op, n, nrhs) / ctx.window_s
    return 100.0 * rate / ctx.peaks["bf16_flops"]


def compile_s(ctx) -> Optional[float]:
    """Backend compile seconds in set-up, loads from the persistent cache
    included."""
    return ctx.setup.get("compile_s")
