"""CPU tests of the benchmark harness (no chip, tiny sizes)."""
