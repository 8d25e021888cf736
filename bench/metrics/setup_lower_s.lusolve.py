"""WaveProgram compiler: jaxpr trace and MLIR lowering seconds in a solve
cell's set-up, counted on the program's spans."""

from bench.program_trace import setup_lower_s as read  # noqa: F401
