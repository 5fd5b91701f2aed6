"""Share of the traced decode window in which no operation ran on the device (%)."""
from bench import readers


def read(ctx):
    return readers.idle_share(ctx, "decode")
