"""Whole solution: algorithmic flops per second over the bf16 peak, %."""

from bench.readers import mfu as read  # noqa: F401
