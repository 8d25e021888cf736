"""Pallas tile kernels: the `trsm` kernel's share of its roofline, %."""

from bench.program_trace import kernel_roofline


def read(ctx):
    return kernel_roofline(ctx, "trsm")
