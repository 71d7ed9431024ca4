"""stream head: device self time of the prefill's state-space mixers
(``head_ssm`` of models/falcon_h1.py, its projections, convolution, gated
norm and ``ssm_scan`` inside it; not what runs under ``head_decode``), per
tick (vbench/stage_trace.py). None where no operation of the step carries
the scope (a head without a mixer, a program from before it)."""
from vbench import stage_trace


def read(ctx):
    ticks = stage_trace.per_tick(ctx)
    if not ticks or not any("head_ssm" in path for t in ticks for path in t):
        return None
    return stage_trace.median_ms(ctx, ("head_ssm",), outside=("head_decode",))
