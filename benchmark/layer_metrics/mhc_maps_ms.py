"""stream head: device self time of the hyper-connected residual's maps
(``mhc_maps`` of models/xing4.py, in the prefill and in the decode loop
alike), per tick (vbench/stage_trace.py)."""
from vbench import stage_trace


def read(ctx):
    return stage_trace.median_ms(ctx, ("mhc_maps",))
