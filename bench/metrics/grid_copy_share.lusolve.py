"""Wave program (grid epoch): device time of the grid copies feeding the
tile kernels in a solve cell, over busy time, %."""

from bench.program_trace import grid_copy_share as read  # noqa: F401
