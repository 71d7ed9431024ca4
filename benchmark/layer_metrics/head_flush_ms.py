"""state pool: device self time of the round's two ends on the pool:
``head_seed`` (the instruction's rows into every slot, a reset stream's
state) and ``head_flush`` (the round's rows committed), per tick
(vbench/stage_trace.py)."""
from vbench import stage_trace


def read(ctx):
    return stage_trace.median_ms(ctx, ("head_seed", "head_flush"))
