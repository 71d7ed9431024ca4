"""engine loop: the tick thread's wait BEFORE it reads a round's frames,
per tick (``pace_wait_s``: engine/pacing.py, taken in ``_run`` ahead of
collect() so that the placement ends as the device frees; 0.0 in a tick
where it did not engage, which is every tick of an engine whose host sets
the pace). A program without the field (before ISSUE 33) reads nothing."""
from vbench import batch_trace


def read(ctx):
    return batch_trace.per_tick(
        ctx["stage"], lambda t: t["pace_wait_s"] * 1e3)
