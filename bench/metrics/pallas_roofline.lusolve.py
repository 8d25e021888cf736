"""Pallas tile kernels: share of their roofline in a solve cell, all kernels
together, %."""

from bench.readers import pallas_roofline as read  # noqa: F401
