"""Share of enhancer training spent placing the slices and the group state
on the devices and gathering the model back (``gwlz.train.shard`` +
``gwlz.train.gather`` over ``gwlz.train``), in %.  None where training ran
on one device and recorded neither span."""
from bench import readers


def read(ctx):
    if not readers.of_kind(ctx, "ingest"):
        return None
    c = ctx["counters"]
    if c.get("train_mesh_s") is None or not c.get("train_s"):
        return None
    return 100.0 * c["train_mesh_s"] / c["train_s"]
