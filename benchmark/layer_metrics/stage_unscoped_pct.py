"""compiled step: the share of the step's device time that no declared
scope claims (``()`` of vbench/stage_trace.py over all the paths, summed
over the reduced ticks): the stage map's coverage, and what a program
loaded from a stale compile cache shows as."""
from vbench import stage_trace


def read(ctx):
    ticks = stage_trace.per_tick(ctx)
    total = sum(s for t in ticks or () for s in t.values())
    if not total:
        return None
    return 100.0 * sum(t.get((), 0.0) for t in ticks) / total
