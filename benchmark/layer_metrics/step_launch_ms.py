"""compiled step: the step call begins (``t_step0``) -> the batch's step
executable starts on the device (trace), median over batches. Reported as
it reads: a negative value means the program's stamps and the trace's clock
disagree by that much."""
from vbench import batch_trace


def read(ctx):
    return batch_trace.per_step(
        ctx, lambda b, start, end: (start - b["t_step0"]) * 1e3)
