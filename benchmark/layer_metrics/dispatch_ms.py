"""prefetch: collect() done -> step dispatched; the tick thread's wait for
the batch's H2D placement, then the dispatch call."""
from vbench import spans


def read(ctx):
    return spans.median_ms(ctx["stage"], "t_collect", "t_submit")
