"""Least time the host's chips together could take for the window's
required ingest work, over the window (%): ``mfu.ingest`` against the
peak of every device the run holds, not of one chip."""
from bench import readers


def read(ctx):
    share = readers.mfu(ctx, "ingest")
    return None if share is None else share / ctx["device"]["count"]
