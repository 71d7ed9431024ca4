"""collector: making each stream's clip window one contiguous sample, per
tick (``clip_s``; 0.0 once nothing is assembled)."""
from vbench import batch_trace


def read(ctx):
    return batch_trace.per_tick(ctx["stage"], lambda t: t["clip_s"] * 1e3)
