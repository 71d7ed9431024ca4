"""state pool: the tick thread's time on a batch's slots, reset and index
vectors (``pool_s``: engine/stream_state.py ``plan``), median per batch."""
from vbench import batch_trace


def read(ctx):
    return batch_trace.per_batch(ctx["stage"], lambda b: b["pool_s"] * 1e3)
