"""Whole solution: algorithmic flops of a solve (2n^3/3 + 2n^2 nrhs) per
second over the bf16 peak, %."""

from bench.readers import mfu as read  # noqa: F401
