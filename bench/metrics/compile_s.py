"""WaveProgram compiler: compile or cache-load seconds in set-up."""

from bench.readers import compile_s as read  # noqa: F401
