"""collector: bus rings -> host memory, per tick (``read_s``: read_latest,
read_latest_into, the assembly window's reads)."""
from vbench import batch_trace


def read(ctx):
    return batch_trace.per_tick(ctx["stage"], lambda t: t["read_s"] * 1e3)
