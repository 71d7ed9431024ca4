"""window pool: device self time of the windowed step's write of the new
frames into the windows and of their ordered copy back (``window_write``,
``window_copy`` in engine/runner.py ``_windowed``), per tick
(vbench/stage_trace.py)."""
from vbench import stage_trace


def read(ctx):
    return stage_trace.median_ms(ctx, ("window_write", "window_copy"))
