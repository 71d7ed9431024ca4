"""Arithmetic on the engine's ``stage_records`` (one record per emitted
result: t_collect, t_submit, t_drain0, t_drained, t_emitted, wall seconds).
Records that share ``t_submit`` are one dispatched batch."""

from __future__ import annotations

import statistics


def batches(stage: list) -> list:
    """One dict per batch: its stamps, its size, its earliest publish."""
    out = {}
    for s in stage:
        b = out.setdefault(s["t_submit"], {
            "t_collect": s["t_collect"], "t_submit": s["t_submit"],
            "t_drain0": s["t_drain0"], "t_drained": s["t_drained"],
            "t_emitted": s["t_emitted"], "bucket": s["bucket"],
            "pub_s": s["ts_pub_ms"] / 1000.0, "n": 0, "devices": []})
        b["n"] += 1
        b["devices"].append(s["device_id"])
        b["t_emitted"] = max(b["t_emitted"], s["t_emitted"])
        b["pub_s"] = min(b["pub_s"], s["ts_pub_ms"] / 1000.0)
    return [out[k] for k in sorted(out)]


def median_ms(stage: list, start: str, end: str):
    """Median over batches of ``end - start`` in ms; None with no batch."""
    spans = [(b[end] - b[start]) * 1000.0 for b in batches(stage)]
    return statistics.median(spans) if spans else None


def rounds(stage: list) -> int:
    """Rounds of the fleet in ``stage``: the most records one camera has."""
    n = {}
    for s in stage:
        n[s["device_id"]] = n.get(s["device_id"], 0) + 1
    return max(n.values(), default=0)


def step_seconds(ctx: dict):
    """Device seconds of the step executables that ran for the batches of
    ``ctx["stage"]``: the traced step events that ended before the last of
    those batches was drained. None without a device trace."""
    t, bs = ctx["trace"], batches(ctx["stage"])
    if t is None or not bs:
        return None
    last = max(b["t_drained"] for b in bs) - ctx["wall_minus_mono"]
    names = ctx["cell"]["config"]["step_modules"]
    return sum(d for name, s, d in t["module_events"]
               if name.split("(", 1)[0] in names and s + d <= last) or None
