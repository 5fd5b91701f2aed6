"""Device seconds per ingest of the enhancer's training programs (train step, BN calibration, group gate)."""
from bench import readers


def read(ctx):
    return readers.program_seconds_per_op(ctx, "ingest", "enhancer_training")
