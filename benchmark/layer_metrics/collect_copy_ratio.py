"""collector: bytes written into host memory (reads, clip assembly, fill,
padding) per byte of new frame taken off the rings, per tick. A count: it
repeats exactly."""
from vbench import batch_trace


def read(ctx):
    return batch_trace.per_tick(
        ctx["stage"], lambda t: t["bytes_copied"] / t["bytes_read"])
