"""The readers of the engine's batch trace (``vbench/batch_trace.py`` and
the ten ``layer_metrics`` built on it): on hand-written stage records, on
the recorded trace's module events, and in the CPU rehearsal of a run."""

import json
import os

import pytest

import run as vrun
from test_run_rehearsal import tiny_bench
from vbench import batch_trace, loader

DATA = os.path.join(os.path.dirname(__file__), "data", "trace_small.json")
HOST_METRICS = ("collect_read_ms", "collect_fill_ms", "collect_copy_ratio",
                "collect_fresh_pct", "tick_other_ms",
                "h2d_wait_ms", "drain_wake_ms")
DEVICE_METRICS = ("step_launch_ms", "fetch_lag_ms")
W = 1000.0                      # wall clock minus monotonic, in these records


def tick_fields(tick, read_s, clip_s, fill_s, bytes_read, bytes_copied,
                bytes_fresh, pre_s=0.010, other_s=0.005):
    return {"tick": tick, "read_s": read_s, "read_ahead_s": 0.0,
            "clip_s": clip_s, "fill_s": fill_s, "frames_read": 3,
            "bytes_read": bytes_read, "bytes_copied": bytes_copied,
            "bytes_fresh": bytes_fresh, "pre_collect_s": pre_s,
            "collect_other_s": other_s}


def records(tick, group, n, t_step0, place_wait_s, t_submit, t_deq,
            t_drained):
    """``n`` results of one batch; stamps on the wall clock."""
    return [dict(tick, batch=(tick["tick"], group), bucket=4,
                 device_id=f"cam{tick['tick']}_{group}_{i}", ts_pub_ms=0,
                 place_wait_s=place_wait_s, t_step0=W + t_step0,
                 t_submit=W + t_submit, t_deq=W + t_deq,
                 t_drain0=W + t_drained - 0.002, t_drained=W + t_drained,
                 t_emitted=W + t_drained + 0.001 * (i + 1))
            for i in range(n)]


def hand_stage():
    """Three ticks. Tick 7 has two groups; tick 8's batch was submitted at
    the very float tick 9's was (equal ``t_submit``, different ticks)."""
    t7 = tick_fields(7, 0.100, 0.200, 0.300, 300, 2700, 2600)
    t8 = tick_fields(8, 0.120, 0.220, 0.320, 300, 5100, 5100,
                     pre_s=0.020, other_s=0.010)
    t9 = tick_fields(9, 0.110, 0.000, 0.310, 100, 100, 0,
                     pre_s=0.030, other_s=0.020)
    return (records(t7, 0, 1, 1.000, 0.050, 1.010, 1.011, 1.060)
            + records(t7, 1, 2, 1.100, 0.150, 1.120, 1.124, 1.400)
            + records(t8, 0, 2, 2.000, 0.400, 5.000, 5.002, 5.300)
            + records(t9, 0, 1, 3.000, 0.600, 5.000, 5.008, 5.900))


def ctx_of(stage, trace=None, t_start=0.0):
    return {"stage": stage, "trace": trace, "wall_minus_mono": W,
            "t_start": t_start,
            "cell": {"config": {"step_modules": ["jit_raw",
                                                 "jit_with_stats"]}}}


def read(name, ctx):
    return loader.layer_metric(name).read(ctx)


def test_batches_and_ticks_group_by_identifier():
    stage = hand_stage()
    bs = batch_trace.batches(stage)
    assert [b["batch"] for b in bs] == [(7, 0), (7, 1), (8, 0), (9, 0)]
    assert [b["n"] for b in bs] == [1, 2, 2, 1]
    assert bs[1]["t_emitted"] == W + 1.400 + 0.002    # its last result
    # equal t_submit floats in ticks 8 and 9 stay two batches
    assert bs[2]["t_submit"] == bs[3]["t_submit"]
    ts = batch_trace.ticks(stage)
    assert [(t["tick"], len(t["batches"])) for t in ts] \
        == [(7, 2), (8, 1), (9, 1)]
    # a batch field that arrives as a list (a record through JSON) is one
    # batch with its tuple twin
    stage[1]["batch"] = [7, 1]
    assert len(batch_trace.batches(stage)) == 4


def test_host_readers_on_hand_written_records():
    ctx = ctx_of(hand_stage())
    assert read("collect_read_ms", ctx) == pytest.approx(110.0)
    assert read("collect_fill_ms", ctx) == pytest.approx(310.0)
    assert read("collect_copy_ratio", ctx) == pytest.approx(9.0)  # 9, 17, 1
    assert read("collect_fresh_pct", ctx) == pytest.approx(
        100.0 * 2600 / 2700)                                 # 96.3, 100, 0
    assert read("tick_other_ms", ctx) == pytest.approx(30.0)  # 15, 30, 50
    # summed over the tick's batches: 200 (two groups), 400, 600
    assert read("h2d_wait_ms", ctx) == pytest.approx(400.0)
    assert read("drain_wake_ms", ctx) == pytest.approx(3.0)   # 1, 4, 2, 8


def test_a_phase_that_took_no_time_reads_zero_not_null():
    t = tick_fields(1, 0.1, 0.0, 0.0, 100, 100, 0)
    ctx = ctx_of(records(t, 0, 2, 1.0, 0.0, 1.0, 1.0, 1.1))
    assert read("collect_fill_ms", ctx) == 0.0


@pytest.mark.parametrize("name", HOST_METRICS + DEVICE_METRICS)
def test_a_program_without_the_trace_reads_nothing(name):
    """The parent commit's records (no ``tick``, no ``batch``): every new
    reader returns None and does not raise."""
    old = [{"device_id": "cam0", "ts_pub_ms": 1, "t_collect": W + 1.0,
            "t_submit": W + 1.1, "t_drain0": W + 1.2, "t_drained": W + 1.3,
            "t_emitted": W + 1.4, "bucket": 4}]
    trace = {"module_events": [("jit_raw(1)", 1.15, 0.1)]}
    assert read(name, ctx_of(old, trace)) is None
    assert read(name, ctx_of([], trace)) is None


def test_device_readers_on_the_recorded_trace():
    """``trace_small.json``'s two step events (0.7908 s for 84.5 ms,
    1.4074 s for 11.0 ms) against two batches whose step calls came 2 ms
    and 3 ms before them and whose outputs were on the host 5 ms and 40 ms
    after they ended."""
    with open(DATA) as f:
        rec = json.load(f)
    mods = [tuple(m) for m in rec["modules"]]
    (_, s0, d0), (_, s1, d1) = mods
    t = tick_fields(3, 0.1, 0.1, 0.1, 100, 900, 900)
    stage = (records(t, 0, 2, s0 - 0.002, 0.0, s0 - 0.001, s0, s0 + d0 + 0.005)
             + records(t, 1, 1, s1 - 0.003, 0.0, s1 - 0.001, s1,
                       s1 + d1 + 0.040))
    trace = {"module_events": mods + [("jit_gather(5)", s0 - 0.001, 0.0001)]}
    ctx = ctx_of(stage, trace, t_start=rec["window"][0])
    assert ctx["cell"]["config"]["step_modules"] == rec["step_modules"]
    found = batch_trace.step_events(ctx)
    assert found[(3, 0)] == pytest.approx((W + s0, W + s0 + d0))
    assert found[(3, 1)] == pytest.approx((W + s1, W + s1 + d1))
    assert read("step_launch_ms", ctx) == pytest.approx(2.5, abs=1e-6)
    assert read("fetch_lag_ms", ctx) == pytest.approx(22.5, abs=1e-6)
    # per batch: launch + the step's device time + lag = t_drained - t_step0
    for b in batch_trace.batches(stage):
        start, end = found[b["batch"]]
        assert (start - b["t_step0"]) + (end - start) \
            + (b["t_drained"] - end) \
            == pytest.approx(b["t_drained"] - b["t_step0"])
    # a trace clock 4 ms ahead of the program's reads as a negative launch,
    # reported as it reads
    early = dict(ctx, wall_minus_mono=W - 0.004)
    assert read("step_launch_ms", early) == pytest.approx(-1.5, abs=1e-6)
    # a batch whose step call precedes the traced window is left out (its
    # event may be cut at the window's edge), and without a device trace
    # there is nothing to read
    late = dict(ctx, t_start=s0)
    assert list(batch_trace.step_events(late)) == [(3, 1)]
    for name in DEVICE_METRICS:
        assert read(name, ctx_of(stage, None)) is None


def test_each_batch_takes_its_own_step_event():
    """Two batches of one tick dispatched 1 ms apart, steps back to back on
    the device: the second batch does not take the first one's event."""
    t = tick_fields(5, 0.1, 0.1, 0.1, 100, 900, 900)
    stage = (records(t, 0, 1, 1.000, 0.0, 1.0005, 1.001, 1.060)
             + records(t, 1, 1, 1.001, 0.0, 1.0015, 1.061, 1.115))
    trace = {"module_events": [("jit_with_stats(1)", 1.003, 0.050),
                               ("jit_raw(2)", 1.053, 0.060)]}
    found = batch_trace.step_events(ctx_of(stage, trace))
    assert found[(5, 0)] == pytest.approx((W + 1.003, W + 1.053))
    assert found[(5, 1)] == pytest.approx((W + 1.053, W + 1.113))


def test_rehearsal_prints_the_host_metrics_and_no_device_metric():
    out = vrun.run("tiny.free", 2**31 + 81, 2.0, True, require_chip=False,
                   bench=tiny_bench())
    assert out["correct"] is True, out["checks"]
    assert out["device"]["platform"] == "cpu"
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(HOST_METRICS) <= set(m)
    assert not set(DEVICE_METRICS) & set(m)
    # tag + clip cameras at L = 4 in a four-row bucket: a tag frame goes
    # ring -> pooled slot once (1), a clip frame into its ring's slot and
    # the ring into a pooled row (1 + L), and a batch that is not full
    # writes its padding rows too (at most 4 L more a frame read)
    assert 1.0 <= m["collect_copy_ratio"] <= 1 + 4 + 4 * 4
    assert 0.0 <= m["collect_fresh_pct"] <= 100.0
    for name in ("collect_read_ms", "collect_fill_ms", "tick_other_ms",
                 "h2d_wait_ms", "drain_wake_ms"):
        assert m[name] > 0.0, name
