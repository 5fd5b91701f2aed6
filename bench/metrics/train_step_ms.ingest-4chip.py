"""Host time per enhancer training step over the window's ingests
(``gwlz.train.step`` spans: the step's dispatch, its batch gather and its
loss fetch, which waits for the device), in ms."""
from bench import readers


def read(ctx):
    if not readers.of_kind(ctx, "ingest"):
        return None
    c = ctx["counters"]
    if not c.get("train_steps"):
        return None
    return 1e3 * c["train_step_s"] / c["train_steps"]
