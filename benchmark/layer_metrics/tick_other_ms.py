"""engine loop: the tick thread's time outside the collector's phases, the
placement wait and the step calls, per tick: end of the previous tick's
dispatch -> collect() entry (``pre_collect_s``: cascade, tracker GC,
watchdog, then failover check, ladder, bus enumeration, keep_streams_hot)
plus collect()'s self time (``collect_other_s``)."""
from vbench import batch_trace


def read(ctx):
    return batch_trace.per_tick(
        ctx["stage"],
        lambda t: (t["pre_collect_s"] + t["collect_other_s"]) * 1e3)
