"""prefetch: the tick thread's blocked time on the placements of a tick's
batches (``place_wait_s`` summed over them), per tick."""
from vbench import batch_trace


def read(ctx):
    return batch_trace.per_tick(
        ctx["stage"],
        lambda t: sum(b["place_wait_s"] for b in t["batches"]) * 1e3)
