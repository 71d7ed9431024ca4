"""Device time by stage for the per-layer metrics (``stage_*_ms``,
``head_*_ms``, ``mhc_maps_ms``, ``stage_unscoped_pct``).

The program's own reducer does the work (``video_edge_ai_proxy_tpu/obs/
stages.py``: the same code reduces an operator's profile bundle): device
SELF time of the traced window's ``XLA Ops`` events by the scope path the
compiled step gave each instruction, a run of a program at a time. Here the
runs are the batches' step events (``batch_trace.step_events``: a batch and
its ``XLA Modules`` event, paired by ``t_step0``), the map of a run is that
of the ``program`` its batch's trace names, and the maps are what
``InferenceEngine.stop()`` left in ``obs.stages`` under ``stage_trace``.

Attribution is by root: a fusion's whole time goes to the stage of its root
instruction. A program loaded from a compile cache that an older build
wrote carries that build's scope names: ``stage_unscoped_pct`` shows it.

"Per tick" = the paths' seconds summed over the tick's batches whose step
event was found, median over ticks. At most ``TICK_CAP`` ticks are reduced,
those whose step time lies nearest the median (a stream cell's window holds
millions of events). A program without ``obs.stages``, a batch trace
without ``program``, a run without a device trace: every reader returns
None.
"""

from __future__ import annotations

import statistics

from vbench import batch_trace

TICK_CAP = 16
_KEY = "_stage_ticks"


def per_tick(ctx: dict):
    """[{scope path: seconds}], one a reduced tick in tick order; None with
    nothing to read. One reduction a run, kept on ``ctx``."""
    if _KEY not in ctx:
        ctx[_KEY] = _reduce(ctx)
    return ctx[_KEY]


def _reduce(ctx: dict):
    try:
        from video_edge_ai_proxy_tpu.obs import stages
    except ImportError:
        return None
    found = batch_trace.step_events(ctx)
    if not found:
        return None
    todo = [b for b in batch_trace.batches(ctx["stage"])
            if b["batch"] in found and b.get("program")]
    maps = stages.built({b["program"] for b in todo})
    w = ctx["wall_minus_mono"]
    t_end = ctx["t_start"] + ctx["seconds"]
    by_tick = {}
    for b in todo:
        m = maps.get(b["program"])
        start, end = found[b["batch"]]
        # (an event that reaches the window's end was cut there)
        if m is not None and end - w < t_end - 1e-6:
            by_tick.setdefault(b["tick"], []).append(
                (start - w, end - w, m["ops"]))
    if not by_tick:
        return None
    ticks = sorted(by_tick)
    if len(ticks) > TICK_CAP:
        dur = {k: sum(e - s for s, e, _ in by_tick[k]) for k in ticks}
        mid = statistics.median(dur.values())
        ticks = sorted(sorted(ticks, key=lambda k: abs(dur[k] - mid))
                       [:TICK_CAP])
    per_run = iter(stages.run_stage_seconds(
        ctx["trace"]["ops"], [r for k in ticks for r in by_tick[k]]))
    out = []
    for k in ticks:
        acc = {}
        for _ in by_tick[k]:
            for path, seconds in next(per_run).items():
                acc[path] = acc.get(path, 0.0) + seconds
        out.append(acc)
    return out


def median_ms(ctx: dict, names, outside=()):
    """Median over ticks of the device ms under the scopes ``names``: the
    paths that hold one of them and none of ``outside``."""
    ticks = per_tick(ctx)
    if not ticks:
        return None
    names, outside = set(names), set(outside)
    return statistics.median(
        sum(s for path, s in t.items()
            if names.intersection(path) and not outside.intersection(path))
        for t in ticks) * 1e3
