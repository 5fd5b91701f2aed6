"""Share of the traced ingest window in which no operation ran on the device (%)."""
from bench import readers


def read(ctx):
    return readers.idle_share(ctx, "ingest")
