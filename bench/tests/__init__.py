"""CPU tests of the benchmark; they never start a TPU backend."""
