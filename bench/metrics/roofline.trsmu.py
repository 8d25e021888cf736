"""Pallas tile kernels: the `trsmu` kernel's share of its roofline, over every
task it runs (the prefix rule, `bench/kernel_tasks.py`), %."""

from bench.kernel_tasks import kernel_roofline


def read(ctx):
    return kernel_roofline(ctx, "trsmu")
