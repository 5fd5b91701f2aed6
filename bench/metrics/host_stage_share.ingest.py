"""Share of the ingests' wall time the streaming executor's host stage
(container append and commit, plus lane packing where it runs on the host)
spent, summed from ``StreamReport.host_stage_s`` (%)."""
from bench import readers


def read(ctx):
    if not readers.of_kind(ctx, "ingest"):
        return None
    c = ctx["counters"]
    return 100.0 * c["host_stage_s"] / c["op_seconds"]
