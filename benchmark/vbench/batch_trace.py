"""Arithmetic on the batch trace that the engine's ``stage_records`` carry
(engine/runner.py ``_open_tick`` / ``_dispatch`` / ``_drain_loop``): every
record of a batch holds ``tick`` (the engine's tick number), ``batch`` =
(tick, index of the group in its tick), the tick's collector phases and
byte counts, and the batch's wall stamps from ``t_tick0`` to ``t_drained``.

Batches and ticks are told apart by those identifiers, never by comparing
stamps. A program without the trace (records with no ``batch``) gives empty
lists, and every reader built on them returns None.

"Per tick" = over one tick that dispatched at least one batch, median over
such ticks whose results the window saw.
"""

from __future__ import annotations

import statistics

# a step's device event may read this much before the call that launched
# it (the trace's clock is set to the program's to within a few ms)
CLOCK_SLACK_S = 0.05


def batches(stage: list) -> list:
    """One dict per batch, in (tick, group) order: the trace fields of its
    first record, ``n`` results, ``t_emitted`` of its last result."""
    out = {}
    for s in stage:
        if s.get("batch") is None:
            continue
        key = tuple(s["batch"])
        b = out.get(key)
        if b is None:
            b = out[key] = dict(s, batch=key, n=0)
        b["n"] += 1
        b["t_emitted"] = max(b["t_emitted"], s["t_emitted"])
    return [out[k] for k in sorted(out)]


def ticks(stage: list) -> list:
    """One dict per tick, in order: the tick's fields plus ``batches``."""
    out = {}
    for b in batches(stage):
        t = out.setdefault(b["tick"], dict(b, batches=[]))
        t["batches"].append(b)
    return [out[k] for k in sorted(out)]


def per_tick(stage: list, value):
    """Median over ticks of ``value(tick)``; ticks where it is None (or a
    field is missing) are left out. None with nothing to read."""
    got = []
    for t in ticks(stage):
        try:
            v = value(t)
        except (KeyError, ZeroDivisionError):
            continue
        if v is not None:
            got.append(v)
    return statistics.median(got) if got else None


def per_batch(stage: list, value):
    """Median over batches of ``value(batch)``, as ``per_tick``."""
    got = []
    for b in batches(stage):
        try:
            got.append(value(b))
        except KeyError:
            continue
    return statistics.median(got) if got else None


def step_events(ctx: dict) -> dict:
    """{batch: (start, end)} of each batch's step executable on the device,
    on the stage records' wall clock. The device's step events (``XLA
    Modules`` named in the configuration's ``step_modules``) and the
    batches are both in order, so each batch takes the first event not yet
    taken that starts after its step call (less the clock slack) and
    before its outputs were on the host. Batches whose step call precedes
    the traced window are left out: their event may be cut at its edge.
    Empty without a device trace."""
    t = ctx.get("trace")
    if not t:
        return {}
    names = ctx["cell"]["config"]["step_modules"]
    w = ctx["wall_minus_mono"]
    events = sorted((s + w, s + d + w) for name, s, d in t["module_events"]
                    if name.split("(", 1)[0] in names)
    todo = [b for b in batches(ctx["stage"])
            if "t_step0" in b and b["t_step0"] - w >= ctx["t_start"]]
    out, i = {}, 0
    for b in sorted(todo, key=lambda b: b["t_step0"]):
        while i < len(events) and events[i][0] < b["t_step0"] - CLOCK_SLACK_S:
            i += 1
        if i < len(events) and events[i][0] <= b["t_drained"]:
            out[b["batch"]] = events[i]
            i += 1
    return out


def per_step(ctx: dict, value):
    """Median over the batches whose device event was found of
    ``value(batch, start, end)``; None without a device trace."""
    found = step_events(ctx)
    got = [value(b, *found[b["batch"]]) for b in batches(ctx["stage"])
           if b["batch"] in found]
    return statistics.median(got) if got else None
