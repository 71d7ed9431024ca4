"""stream head: device self time of everything under ``head_decode`` (the
decode loop's iterations, sampling and drafts included; divided by
``head_decode_iters`` it is an iteration), per tick
(vbench/stage_trace.py)."""
from vbench import stage_trace


def read(ctx):
    return stage_trace.median_ms(ctx, ("head_decode",))
