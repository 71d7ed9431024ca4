"""drain + emit: the batch's step executable ends on the device (trace) ->
its outputs are on the host (``t_drained``), median over batches: what the
drain thread adds to the device's own time (a late start on the batch, the
fetch)."""
from vbench import batch_trace


def read(ctx):
    return batch_trace.per_step(
        ctx, lambda b, start, end: (b["t_drained"] - end) * 1e3)
