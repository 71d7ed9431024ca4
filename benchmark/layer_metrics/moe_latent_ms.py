"""expert layer: device self time of the prefill's latent projections
(``moe_latent`` of models/transformer.py ``TopKMoeMlp``: W_down into the
experts' latent and W_up back to the model width; not what runs under
``head_decode``), per tick (vbench/stage_trace.py). None where no
operation of the step carries the scope (an expert layer without a
latent, a program from before it)."""
from vbench import stage_trace


def read(ctx):
    ticks = stage_trace.per_tick(ctx)
    if not ticks or not any("moe_latent" in path
                            for t in ticks for path in t):
        return None
    return stage_trace.median_ms(ctx, ("moe_latent",),
                                 outside=("head_decode",))
