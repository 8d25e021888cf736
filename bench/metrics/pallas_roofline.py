"""Pallas tile kernels: share of their roofline, all kernels together, %."""

from bench.readers import pallas_roofline as read  # noqa: F401
