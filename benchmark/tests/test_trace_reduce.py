"""The reduction from a trace to device metrics, on a small recorded trace
(``data/trace_small.json``: the device plane's events of one burst of
a 32-camera clip burst on a TPU v5e, cut down) and on hand-made intervals."""

import json
import os

import pytest

from vbench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data", "trace_small.json")


def test_union_busy_and_gaps_by_hand():
    ops = [("a", 1.0, 1.0), ("b", 1.5, 1.0), ("c", 4.0, 0.5)]
    assert tr.union([(s, s + d) for _, s, d in ops]) == [(1.0, 2.5),
                                                         (4.0, 4.5)]
    assert tr.busy_seconds(ops) == pytest.approx(2.0)
    assert tr.gaps(ops, 0.0, 5.0) == [(0.0, 1.0), (2.5, 4.0), (4.5, 5.0)]
    assert tr.clip(ops, 2.0, 4.25) == [("b", 2.0, 0.5), ("c", 4.0, 0.25)]


def test_named_gaps_and_top_ops():
    idle = [(0.0, 1.0), (2.5, 4.0)]
    spans = [("collect", 0.2, 0.9), ("h2d", 0.9, 3.0)]
    named = dict(tr.name_gaps(idle, spans, "waiting for burst"))
    assert named["collect"] == pytest.approx(0.7)
    assert named["h2d"] == pytest.approx(0.1 + 0.5)
    assert named["waiting for burst"] == pytest.approx(0.2 + 1.0)
    top = tr.top_ops([("x", 0, 1.0), ("y", 1, 3.0), ("x", 5, 1.5)], n=1)
    assert top == [["y", 3.0]]


def test_module_seconds_reads_the_name_before_the_bracket():
    mods = [("jit_raw(123)", 0.0, 0.25), ("jit_gather(9)", 1.0, 0.5),
            ("jit_with_stats(77)", 2.0, 0.125)]
    assert tr.module_seconds(mods, ["jit_raw", "jit_with_stats"]) == (
        pytest.approx(0.375), 2)


def test_recorded_trace():
    with open(DATA) as f:
        rec = json.load(f)
    ops = [tuple(e) for e in rec["ops"]]
    mods = [tuple(e) for e in rec["modules"]]
    t0, t1 = rec["window"]
    busy = tr.busy_seconds(tr.clip(ops, t0, t1))
    assert busy == pytest.approx(rec["expect"]["busy_s"], rel=1e-9)
    assert 0.0 < busy < t1 - t0
    s, runs = tr.module_seconds(tr.clip(mods, t0, t1), rec["step_modules"])
    assert runs == rec["expect"]["step_runs"]
    assert s == pytest.approx(rec["expect"]["step_s"], rel=1e-9)
    # ops run inside modules: the step's time bounds the ops' union
    assert busy <= sum(d for _, _, d in tr.clip(mods, t0, t1)) * 1.001
    idle = tr.gaps(tr.clip(ops, t0, t1), t0, t1)
    assert sum(b - a for a, b in idle) == pytest.approx(t1 - t0 - busy)
