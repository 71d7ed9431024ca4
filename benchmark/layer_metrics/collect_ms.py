"""collector: the earliest frame of a batch was due -> the batch leaves collect().

The wait for the tick that picks the frames up and the assembly copies
(np.stack of each clip window, the copy into the batch)."""
from vbench import spans


def read(ctx):
    return spans.median_ms(ctx["stage"], "pub_s", "t_collect")
