"""stream head: iterations of the drafted decode loop a round (each runs
the main model on two positions a stream and commits one or two), median
per batch: D where no draft is accepted, D / 2 where all are. None where
the program's batches carry no such field."""
from vbench import batch_trace


def read(ctx):
    return batch_trace.per_batch(ctx["stage"],
                                 lambda b: b["head_decode_iters"])
