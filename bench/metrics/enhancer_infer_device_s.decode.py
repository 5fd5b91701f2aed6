"""Device seconds per full decode of the enhancer's per-tile inference program."""
from bench import readers


def read(ctx):
    return readers.program_seconds_per_op(ctx, "decode", "enhancer_inference")
