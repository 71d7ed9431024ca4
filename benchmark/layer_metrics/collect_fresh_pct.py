"""collector: share of the bytes it wrote that went into a buffer handed
out once (a new allocation, the bus's per-read destination: first-touched
pages) and not into a pooled buffer reused from tick to tick, per tick."""
from vbench import batch_trace


def read(ctx):
    return batch_trace.per_tick(
        ctx["stage"],
        lambda t: 100.0 * t["bytes_fresh"] / t["bytes_copied"])
