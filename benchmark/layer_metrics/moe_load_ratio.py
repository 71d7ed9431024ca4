"""expert layer: the busiest held expert's routed pairs over the mean of
the held experts' (``moe_load_max`` / ``moe_load_mean``, the step's own
count over a batch's prefill and decode), median per batch. 1 is an even
load; the grouped matmul's time follows the busiest expert's tiles."""
from vbench import batch_trace


def read(ctx):
    return batch_trace.per_batch(
        ctx["stage"], lambda b: b["moe_load_max"] / b["moe_load_mean"])
