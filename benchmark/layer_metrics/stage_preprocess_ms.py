"""compiled step: device self time of the preprocess (the scopes
``pre_cast_scale``, ``pre_resize``, ``pre_normalize`` of ops/preprocess.py,
wherever the step runs them: a stream head's inside its prefill loop), per
tick (vbench/stage_trace.py; attribution by a fusion's root)."""
from vbench import stage_trace


def read(ctx):
    return stage_trace.median_ms(
        ctx, ("pre_cast_scale", "pre_resize", "pre_normalize"))
