"""drain + emit: batch submitted -> the drain thread holds it (``t_deq``,
stamped before the thread lets the previous batch go), median over
batches."""
from vbench import batch_trace


def read(ctx):
    return batch_trace.per_batch(
        ctx["stage"], lambda b: (b["t_deq"] - b["t_submit"]) * 1e3)
