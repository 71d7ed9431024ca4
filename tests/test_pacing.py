"""Just-in-time reads (ISSUE 33; engine/pacing.py and the tick loop's use
of it): the arithmetic on a fake clock, the wait's ways out on the real
one, and an engine on the CPU whose step is made slow. (That coasted ROI
groups count for nothing in the backlog is checked in tests/test_roi.py,
on its hand-stepped engine.)

CPU backend, tiny models: orderings and counts. The times asserted are
those of a stub (a step whose outputs arrive after a fixed delay), never
a device's.
"""

import threading
import time

import numpy as np
import pytest

from video_edge_ai_proxy_tpu.bus.interface import FrameMeta
from video_edge_ai_proxy_tpu.bus.memory_bus import MemoryFrameBus
from video_edge_ai_proxy_tpu.engine import InferenceEngine
from video_edge_ai_proxy_tpu.engine.pacing import ReadPacer
from video_edge_ai_proxy_tpu.uplink.queue import AnnotationQueue
from video_edge_ai_proxy_tpu.utils.config import EngineConfig

STEP, LEAD = 0.166, 0.138        # clip64_1080p's step and host lead, s
SLACK = ReadPacer.SLACK_S
KEY = ("videomae_b", (1080, 1920), 64)


class _Clock:
    def __init__(self, now=0.0):
        self.now = now

    def __call__(self):
        return self.now


def _timed(pacer, key=KEY, steps=(STEP,) * 5, t=0.0):
    """Run ``steps`` back to back through the pacer, each launched as its
    predecessor drains; returns the time the last one drained."""
    for s in steps:
        token = object()
        pacer.launched(token, key, now=t)
        t += s
        pacer.drained(token, now=t)
    return t


def _device_bound(pacer, t, n=3):
    """``n`` batches in flight, the first launched at ``t`` onto a device
    that just freed; returns when the device is free of them."""
    tokens = [object() for _ in range(n)]
    for i, token in enumerate(tokens):
        pacer.launched(token, KEY, now=t + 0.01 * i)
    return tokens, t + n * STEP


# name -> (set-up(pacer) -> (now, expected read_at or None))
def _case_device_bound(p):
    t = _timed(p)
    p.note_lead(LEAD)
    _, free = _device_bound(p, t)
    return t + 0.03, free - LEAD - SLACK


def _case_host_bound(p):
    t = _timed(p)
    p.note_lead(3.5 * STEP)          # the host's lead is over the backlog
    _, free = _device_bound(p, t)
    return t + 0.03, free - 3.5 * STEP - SLACK


def _case_nothing_in_flight(p):
    t = _timed(p)
    p.note_lead(LEAD)
    return t + 0.03, None


def _case_no_lead_measured_yet(p):
    t = _timed(p)
    _device_bound(p, t)
    return t + 0.03, None


def _case_one_compile_length_step(p):
    t = _timed(p, steps=(STEP, STEP, 40.0, STEP, STEP))
    p.note_lead(LEAD)
    _, free = _device_bound(p, t)
    return t + 0.03, free - LEAD - SLACK


def _case_one_stalled_placement_does_not_move_the_lead(p):
    # a pool buffer's first touch, a stalled host: taken at its word it
    # would put the read so early that the wait never engages, for as
    # many rounds as the history is long
    t = _timed(p)
    for lead in (LEAD, LEAD + 0.01, 0.779, LEAD - 0.005, LEAD):
        p.note_lead(lead)
    _, free = _device_bound(p, t)
    return t + 0.03, free - (LEAD + 0.01) - SLACK


def _case_three_first_touches_at_the_start(p):
    t = _timed(p)
    for lead in (0.51, 0.50, 0.40, LEAD, LEAD + 0.004, LEAD, LEAD - 0.003):
        p.note_lead(lead)
    _, free = _device_bound(p, t)
    return t + 0.03, free - (LEAD + 0.004) - SLACK


def _case_the_lead_is_the_recent_maximum(p):
    t = _timed(p)
    for lead in (LEAD, LEAD + 0.02, LEAD - 0.01):
        p.note_lead(lead)
    _, free = _device_bound(p, t)
    return t + 0.03, free - (LEAD + 0.02) - SLACK


def _case_an_old_lead_ages_out(p):
    t = _timed(p)
    p.note_lead(5.0)                 # a stalled placement, long ago
    for _ in range(ReadPacer.LEAD_HISTORY):
        p.note_lead(LEAD)
    _, free = _device_bound(p, t)
    return t + 0.03, free - LEAD - SLACK


def _case_an_overdue_step_ends_now(p):
    t = _timed(p)
    p.note_lead(LEAD)
    _device_bound(p, t, n=2)
    now = t + 2 * STEP               # the running step should have ended
    return now, now + STEP - LEAD - SLACK


def _case_a_program_never_timed_predicts_nothing(p):
    t = _timed(p)
    p.note_lead(LEAD)
    p.launched(object(), ("another", (360, 640), 64), now=t)
    return t + 0.03, t + 0.03 - LEAD - SLACK


def _case_a_step_launched_onto_an_idle_device(p):
    # launched long after its predecessor drained: its time runs from its
    # own launch, not from the predecessor's end
    t = _timed(p)
    token = object()
    p.launched(token, KEY, now=t + 10.0)
    p.drained(token, now=t + 10.0 + STEP)
    p.note_lead(LEAD)
    _, free = _device_bound(p, t + 20.0)
    return t + 20.0, free - LEAD - SLACK


CASES = {f.__name__[len("_case_"):]: f for f in (
    _case_device_bound, _case_host_bound, _case_nothing_in_flight,
    _case_no_lead_measured_yet, _case_one_compile_length_step,
    _case_one_stalled_placement_does_not_move_the_lead,
    _case_three_first_touches_at_the_start,
    _case_the_lead_is_the_recent_maximum, _case_an_old_lead_ages_out,
    _case_an_overdue_step_ends_now,
    _case_a_program_never_timed_predicts_nothing,
    _case_a_step_launched_onto_an_idle_device)}


class TestArithmetic:
    @pytest.mark.parametrize("case", sorted(CASES))
    def test_the_read_starts_at_free_minus_lead(self, case):
        clock = _Clock()
        pacer = ReadPacer(clock=clock)
        now, want = CASES[case](pacer)
        got = pacer.read_at(now)
        if want is None:
            assert got is None
        else:
            assert got == pytest.approx(want, abs=1e-9)

    @pytest.mark.parametrize("case", [
        "host_bound", "nothing_in_flight", "no_lead_measured_yet",
        "a_program_never_timed_predicts_nothing"])
    def test_where_the_host_sets_the_pace_the_wait_never_engages(self, case):
        clock = _Clock()
        pacer = ReadPacer(clock=clock)
        clock.now, _ = CASES[case](pacer)
        t0 = time.monotonic()
        assert pacer.wait(threading.Event()) == 0.0
        assert time.monotonic() - t0 < 0.05

    def test_a_drained_batch_leaves_the_backlog(self):
        clock = _Clock()
        pacer = ReadPacer(clock=clock)
        t = _timed(pacer)
        pacer.note_lead(LEAD)
        tokens, free = _device_bound(pacer, t)
        pacer.drained(tokens[0], now=t + STEP + 0.004)   # 4 ms late
        assert pacer.in_flight() == 2
        assert pacer.read_at(t + STEP + 0.01) \
            == pytest.approx(free + 0.004 - LEAD - SLACK, abs=1e-9)
        # in order: the last one's arrival takes those ahead of it along
        pacer.forget(tokens[2])
        assert pacer.in_flight() == 0
        pacer.drained(tokens[1], now=t + 9.0)            # no-op by now
        assert pacer.read_at(t + 9.0) is None


def _waiter(pacer, stop):
    out = {}

    def run():
        t0 = time.monotonic()
        out["waited"] = pacer.wait(stop)
        out["took"] = time.monotonic() - t0

    th = threading.Thread(target=run, daemon=True)
    th.start()
    return th, out


class TestTheWaitsWaysOut:
    """On the real clock, with a step of seconds so that a wait that did
    not end early would show."""

    LONG = 5.0

    def _pacer(self):
        pacer = ReadPacer()
        now = time.monotonic()
        _timed(pacer, steps=(self.LONG,) * 3, t=now - 3 * self.LONG)
        pacer.note_lead(0.01)
        token = object()
        pacer.launched(token, KEY)
        return pacer, token

    def test_it_engages_and_ends_at_the_predicted_time(self):
        pacer = ReadPacer()
        now = time.monotonic()
        _timed(pacer, steps=(0.3,) * 3, t=now - 0.9)
        pacer.note_lead(0.1)
        pacer.launched(object(), KEY)
        th, out = _waiter(pacer, threading.Event())
        th.join(timeout=5)
        assert not th.is_alive()
        assert 0.15 <= out["waited"] <= 0.35       # free - lead: ~0.19

    @pytest.mark.parametrize("how", ["drained", "forget"])
    def test_nothing_left_in_flight_ends_it_at_once(self, how):
        pacer, token = self._pacer()
        th, out = _waiter(pacer, threading.Event())
        time.sleep(0.1)
        assert th.is_alive()
        getattr(pacer, how)(token)
        th.join(timeout=5)
        assert not th.is_alive()
        assert 0.05 <= out["waited"] < 1.0 and out["took"] < 1.0

    def test_stop_ends_it_within_a_tenth_of_a_second(self):
        pacer, _ = self._pacer()
        stop = threading.Event()
        th, out = _waiter(pacer, stop)
        time.sleep(0.1)
        assert th.is_alive()
        t_stop = time.monotonic()
        stop.set()
        th.join(timeout=5)
        assert not th.is_alive()
        assert time.monotonic() - t_stop < 0.1

    def test_threads_hammering_it_lose_no_batch(self):
        """More threads than cores launch and drain while one waits: the
        in-flight list comes back empty and every sample is kept whole."""
        import sys

        pacer = ReadPacer()
        pacer.note_lead(0.0)
        stop, errors = threading.Event(), []
        prev = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)

        def churn(k):
            try:
                for i in range(300):
                    token = object()
                    pacer.launched(token, ("m", k % 3))
                    (pacer.drained if i % 2 else pacer.forget)(token)
            except Exception as exc:       # pragma: no cover
                errors.append(exc)

        def wait_loop():
            while not stop.is_set():
                pacer.wait(stop)

        try:
            waiter = threading.Thread(target=wait_loop, daemon=True)
            waiter.start()
            threads = [threading.Thread(target=churn, args=(k,), daemon=True)
                       for k in range(16)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=30)
            assert not any(th.is_alive() for th in threads)
            stop.set()
            waiter.join(timeout=5)
            assert not waiter.is_alive()
        finally:
            sys.setswitchinterval(prev)
        assert not errors
        assert pacer.in_flight() == 0
        assert all(len(h) <= ReadPacer.STEP_HISTORY and min(h) >= 0
                   for h in pacer._steps.values())


# ---- an engine whose step is slow -------------------------------------

H, W = 48, 64
F = H * W * 3
STUB_STEP_S = 0.12


class _Late:
    """A step output that reaches the host at ``ready_at``: ``_emit``'s
    ``np.asarray`` blocks on it as it would on a device array."""

    def __init__(self, value, ready_at):
        self._value, self._ready_at = value, ready_at

    def __array__(self, dtype=None, copy=None):
        delay = self._ready_at - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        return np.asarray(self._value, dtype=dtype)


@pytest.fixture()
def bus():
    b = MemoryFrameBus()
    yield b
    b.close()


def _slow_engine(bus, cams, step_s=STUB_STEP_S, **cfg_kw):
    """Tag cameras on tiny_vit; every step's outputs arrive ``step_s``
    after the stub device (one step at a time) started it."""
    for cam in cams:
        bus.create_stream(cam, F)
    cfg = EngineConfig(
        model="tiny_vit", batch_buckets=(len(cams),), tick_ms=5,
        stage_trace=True, quality=False,
        # the ladder observes and never acts: a CPU tick outlasts its
        # budget whatever the device does
        ladder=True, ladder_escalate_after_s=1e9, **cfg_kw)
    eng = InferenceEngine(
        bus, cfg, annotations=AnnotationQueue(handler=lambda b: True))
    eng.warmup()
    device = {"free": 0.0}
    real_step = eng._step

    def slow_step(*a, **kw):
        fn = real_step(*a, **kw)

        def call(variables, frames, *rest):
            out = fn(variables, frames, *rest)
            start = max(time.monotonic(), device["free"])
            device["free"] = start + step_s
            return {k: _Late(v, device["free"]) for k, v in out.items()}

        return call

    eng._step = slow_step
    return eng


def _publisher(bus, cams, stop, period_s=0.004):
    def run():
        k = 0
        while not stop.is_set():
            k += 1
            for cam in cams:
                meta = FrameMeta(
                    width=W, height=H, channels=3,
                    timestamp_ms=int(time.time() * 1000), is_keyframe=True)
                bus.publish(cam, np.full((H, W, 3), k % 251, np.uint8), meta)
            stop.wait(period_s)

    th = threading.Thread(target=run, daemon=True)
    th.start()
    return th


def _batches(records):
    out = {}
    for r in records:
        out.setdefault(tuple(r["batch"]), r)
    return [out[k] for k in sorted(out)]


class TestADeviceBoundEngine:
    def test_it_reads_just_in_time_and_still_reports_backpressure(self, bus):
        cams = ["tag0", "tag1"]
        eng = _slow_engine(bus, cams)
        depths, blocked = [], []
        observe = eng.ladder.observe

        def spy_observe(**kw):
            depths.append((eng.ticks, kw["queue_depth"]))
            return observe(**kw)

        eng.ladder.observe = spy_observe
        put = eng._drain_q.put

        def spy_put(item, block=True, timeout=None):
            if item is not None and block:   # put_nowait found it full
                blocked.append(eng.ticks)
            return put(item, block, timeout)

        eng._drain_q.put = spy_put
        stop = threading.Event()
        pub = _publisher(bus, cams, stop)
        eng.start()
        try:
            deadline = time.time() + 60
            while len(_batches(eng.stage_records)) < 16 \
                    and time.time() < deadline:
                time.sleep(0.02)
        finally:
            stop.set()
            eng.stop()
            pub.join(timeout=5)
        batches = _batches(eng.stage_records)
        assert len(batches) >= 16, "the slow engine answered too few rounds"
        # the estimate needs a few results; from then on the wait engages
        settled = batches[6:]
        first = settled[0]["tick"]
        assert all(b["pace_wait_s"] > 0 for b in settled)
        # the drain queue never fills: no handoff blocks once paced
        assert [t for t in blocked if t >= first] == []
        for b in settled:
            # read just in time: collect() entry -> step call is the host's
            # lead (ms on this CPU), where an engine running ahead reads
            # frames whole steps before the device can take them ...
            assert b["t_step0"] - b["t_collect0"] < 0.5 * STUB_STEP_S
            # ... and the batch does not wait for the drain thread
            assert b["t_deq"] - b["t_submit"] < 0.5 * STUB_STEP_S
            # nor does the wait cost the device: its outputs still arrive
            # one stub step after the previous batch's (the chip not idled)
        gaps = [b["t_drained"] - a["t_drained"]
                for a, b in zip(settled, settled[1:])]
        assert sorted(gaps)[len(gaps) // 2] < 1.35 * STUB_STEP_S
        # the wait is stamped apart from the tick's other time
        for b in settled:
            assert b["pace_wait_s"] > 0.25 * STUB_STEP_S
            assert b["pre_collect_s"] < 0.5 * STUB_STEP_S
        # rule 4: the ladder hears a full double buffer on such ticks, and
        # the watchdog's episode opens, as with a blocked handoff
        after = [d for t, d in depths if t > first]
        assert after and sum(d == 2 for d in after) >= 0.8 * len(after)
        snap = eng.watchdog.snapshot()
        assert snap["episodes"].get("drain_backpressure", 0) >= 1

    def test_an_engine_whose_device_keeps_up_never_waits(self, bus):
        cams = ["tag0", "tag1"]
        eng = _slow_engine(bus, cams, step_s=0.0)
        stop = threading.Event()
        # a frame every 150 ms: the host, waiting for frames, sets the pace
        pub = _publisher(bus, cams, stop, period_s=0.15)
        eng.start()
        try:
            deadline = time.time() + 60
            while len(_batches(eng.stage_records)) < 10 \
                    and time.time() < deadline:
                time.sleep(0.02)
        finally:
            stop.set()
            eng.stop()
            pub.join(timeout=5)
        batches = _batches(eng.stage_records)
        assert len(batches) >= 10
        assert all(b["pace_wait_s"] == 0.0 for b in batches)
        snap = eng.watchdog.snapshot()
        assert snap["episodes"].get("drain_backpressure", 0) == 0
