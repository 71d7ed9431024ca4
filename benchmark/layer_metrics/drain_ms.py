"""drain + emit: the drain thread starts on a batch -> its last result is
emitted (output fetch, protos, fan-out)."""
from vbench import spans


def read(ctx):
    return spans.median_ms(ctx["stage"], "t_drain0", "t_emitted")
