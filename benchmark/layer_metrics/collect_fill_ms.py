"""collector: samples -> the batch buffer, with its allocation and
zero-padding, per tick (``fill_s``)."""
from vbench import batch_trace


def read(ctx):
    return batch_trace.per_tick(ctx["stage"], lambda t: t["fill_s"] * 1e3)
