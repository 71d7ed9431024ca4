"""prefetch: how long before its tick's collection closed a batch was handed
to the transfer thread, summed over the tick's batches, per tick
(``max(0, t_collect - t_place_q)``: the part of a placement's head start that
lies beside the collector's reads of the groups after it). Read from the two
stamps every batch trace has carried since ISSUE 25, so a program that hands
its batches over after the collect (before ISSUE 35) reads 0.0, and one
without the batch trace nothing."""
from vbench import batch_trace


def read(ctx):
    return batch_trace.per_tick(
        ctx["stage"],
        lambda t: sum(max(0.0, b["t_collect"] - b["t_place_q"])
                      for b in t["batches"]) * 1e3)
