"""Benchmark of the GWLZ compressor on the chip: see run.py and PERF.md."""
