"""CPU tests of the benchmark: no TPU is touched, Pallas runs in interpret mode."""
