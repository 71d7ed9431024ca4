"""expert layer: device self time of the prefill's expert layers
(``head_moe`` with ``moe_route``, ``moe_experts``, ``moe_shared`` inside
it; not what runs under ``head_decode``), per tick
(vbench/stage_trace.py)."""
from vbench import stage_trace


def read(ctx):
    return stage_trace.median_ms(ctx, ("head_moe",),
                                 outside=("head_decode",))
