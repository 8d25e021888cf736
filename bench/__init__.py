"""Benchmark of the task runtime on a TPU (see BENCHMARK.json and bench/run.py)."""
