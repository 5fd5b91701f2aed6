"""Least time the chip could take for the window's required decode work, over the window (%)."""
from bench import readers


def read(ctx):
    return readers.mfu(ctx, "decode")
