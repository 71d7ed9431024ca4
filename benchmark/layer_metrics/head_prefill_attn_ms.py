"""stream head: device self time of the prefill's attention (``head_attn``
of models/lfm2.py, ``mla_prefill`` of models/mla.py; not what runs under
``head_decode``), per tick (vbench/stage_trace.py)."""
from vbench import stage_trace


def read(ctx):
    return stage_trace.median_ms(ctx, ("head_attn", "mla_prefill"),
                                 outside=("head_decode",))
