"""expert layer: device self time of the prefill's way back from the
sorted held rows to the tokens (``moe_combine`` of models/transformer.py
``TopKMoeMlp``: the kernel of ops/combine_rows.py and the gather of the
rows' weights; not what runs under ``head_decode``), per tick
(vbench/stage_trace.py). None where no operation of the step carries the
scope (a program from before it)."""
from vbench import stage_trace


def read(ctx):
    ticks = stage_trace.per_tick(ctx)
    if not ticks or not any("moe_combine" in path
                            for t in ticks for path in t):
        return None
    return stage_trace.median_ms(ctx, ("moe_combine",),
                                 outside=("head_decode",))
