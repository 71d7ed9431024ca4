"""prefetch: the transfer thread's time inside the placement call itself
(``put_call_s`` = ``t_put - t_place0``: ``device_put`` returned, the bytes
may still be crossing), summed over the tick's batches, per tick. With
``t_placed - t_put`` (the wait for the bytes) and ``t_place_got -
t_placed`` (the tick thread's wake-up) it splits a placement's constant. A
program without the stamp reads nothing."""
from vbench import batch_trace


def read(ctx):
    return batch_trace.per_tick(
        ctx["stage"],
        lambda t: sum(b["put_call_s"] for b in t["batches"]) * 1e3)
