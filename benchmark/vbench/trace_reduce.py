"""From a profiler trace to device metrics.

``read_xplane`` pulls the device planes' operation and module events out of
a ``.xplane.pb`` (with nothing but jax); everything after that works on
plain lists, so the reduction is tested on a small recorded trace.

Times inside a trace are nanoseconds from the moment ``start_trace`` was
called (read on the chip: to within a few ms); the harness notes its own
monotonic clock there, which puts the device's events and the engine's
stage records on one clock so that idle gaps can be named by what the host
was doing. Only device planes are read: the harness traces with the host
tracer off.
"""

from __future__ import annotations

import glob
import os

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(log_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return hits[-1]


def read_xplane(path: str) -> dict:
    """{"devices": {plane: {"ops": [(name, start_s, dur_s)], "modules":
    [...], "lines": [names]}}}. An operation's name is cut at " = ": the
    chip's trace names an op by its whole HLO line."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out = {"devices": {}}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            dev = {"ops": [], "modules": [], "lines": []}
            for line in plane.lines:
                dev["lines"].append(line.name)
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                key = "ops" if line.name == OPS_LINE else "modules"
                for ev in line.events:
                    dev[key].append((ev.name.split(" = ", 1)[0],
                                     ev.start_ns * 1e-9,
                                     ev.duration_ns * 1e-9))
            if dev["ops"] or dev["modules"]:
                out["devices"][plane.name] = dev
    return out


def union(intervals: list) -> list:
    """Merged [(start, end)] of possibly overlapping intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(events: list, t0: float, t1: float) -> list:
    """Events cut to the window [t0, t1)."""
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, a, b - a))
    return out


def busy_seconds(ops: list) -> float:
    return sum(e - s for s, e in union([(s, s + d) for _, s, d in ops]))


def gaps(ops: list, t0: float, t1: float) -> list:
    """Idle [(start, end)] inside [t0, t1)."""
    out, cur = [], t0
    for s, e in union([(s, s + d) for _, s, d in ops]):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if t1 > cur:
        out.append((cur, t1))
    return out


def module_seconds(modules: list, prefixes: list) -> tuple:
    """(seconds, runs) of the executables named by ``prefixes`` — a module
    event reads ``jit_raw(1234)``; the name is what precedes the bracket."""
    total, runs = 0.0, 0
    for name, _, d in modules:
        if name.split("(", 1)[0] in prefixes:
            total += d
            runs += 1
    return total, runs


def top_ops(ops: list, n: int = 10) -> list:
    """[[name, seconds]] of the operations that took most device time."""
    acc = {}
    for name, _, d in ops:
        acc[name] = acc.get(name, 0.0) + d
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def name_gaps(idle: list, host_spans: list, default: str,
              n: int = 10) -> list:
    """[[name, seconds]]: idle time by what the host was doing.

    ``host_spans`` are (name, start, end) on the same clock as ``idle``;
    each idle gap is split over the spans it overlaps and what is left goes
    to ``default``. The ``n`` largest totals come back."""
    acc = {}
    for g0, g1 in idle:
        left = g1 - g0
        for name, s, e in host_spans:
            ov = min(g1, e) - max(g0, s)
            if ov > 0:
                acc[name] = acc.get(name, 0.0) + ov
                left -= ov
        if left > 1e-9:
            acc[default] = acc.get(default, 0.0) + left
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]
