"""Device-fault domain tests (engine/fault.py + runner failover, r22):
the FaultLedger conservation/duplicate/rebase accounting, the FaultPlane
watchdog state machine (hard-error attribution, drain-deadline
hysteresis, stall probe resolution), the deterministic ``make_repin``
rendezvous (survivors keep their pins, composition across cascaded
faults), the ``_PrefetchStage``'s bounded queue at shutdown, a live
dp2 -> dp1 engine failover on the CPU twin, the ``/api/v1/faults``
endpoint convention, and the fault=False bit-identical serving pin.

Plane/ledger/repin tests run sleep-free with injected clocks (no jax);
the engine tests follow tests/test_hbm.py's hand-stepped and live-soak
conventions."""

import json
import queue
import threading
import time
import types

import numpy as np
import pytest

from video_edge_ai_proxy_tpu.bus.interface import FrameMeta
from video_edge_ai_proxy_tpu.bus.memory_bus import MemoryFrameBus
from video_edge_ai_proxy_tpu.engine.collector import make_repin, stream_shard
from video_edge_ai_proxy_tpu.engine.fault import FaultLedger, FaultPlane
from video_edge_ai_proxy_tpu.obs.metrics import lint_exposition
from video_edge_ai_proxy_tpu.obs.metrics import registry as metrics_registry
from video_edge_ai_proxy_tpu.uplink.queue import AnnotationQueue
from video_edge_ai_proxy_tpu.utils.config import EngineConfig


class FakeClock:
    def __init__(self, now=1000.0):
        self.now = float(now)

    def __call__(self):
        return self.now

    def advance(self, dt):
        self.now += dt


def _meta(ts=None):
    return FrameMeta(width=64, height=64, channels=3,
                     timestamp_ms=ts or int(time.time() * 1000),
                     is_keyframe=True)


def _blob_frame(delta=0, key=1):
    frame = np.full((64, 64, 3), 114, np.uint8)
    frame[20:40, 20:40] = (64 + delta, 255, key * 32 + 16)
    return frame


# ---------------------------------------------------------------------------
# ledger


class TestFaultLedger:
    def test_balance_zero_when_all_emitted(self):
        led = FaultLedger(clock=FakeClock())
        led.note_dispatched(3)
        for i in range(3):
            led.note_emitted("cam0", (0, 100 + i))
        b = led.balance()
        assert b["dispatched"] == 3 and b["emitted"] == 3
        assert b["lost"] == 0 and b["lost_outside_window"] == 0
        assert b["duplicated"] == 0 and b["rebased"] == 0

    def test_device_fault_drop_outside_window_is_loss(self):
        led = FaultLedger(clock=FakeClock())
        led.note_dispatched(2)
        led.note_dropped(2, "device_fault")     # no window declared
        b = led.balance()
        assert b["lost"] == 0                    # accounted, but...
        assert b["lost_outside_window"] == 2     # ...not excused

    def test_device_fault_drop_inside_window_is_excused(self):
        led = FaultLedger(clock=FakeClock())
        led.note_dispatched(2)
        led.open_window("xla_error")
        led.note_dropped(2, "device_fault")
        led.close_window()
        b = led.balance()
        assert b["lost_outside_window"] == 0
        assert b["dropped"] == {"device_fault": 2}
        assert len(b["windows"]) == 1
        assert b["windows"][0]["reason"] == "xla_error"
        assert b["windows"][0]["closed"] is not None

    def test_unaccounted_residual_is_lost(self):
        led = FaultLedger(clock=FakeClock())
        led.note_dispatched(5)
        for i in range(3):
            led.note_emitted("cam0", (0, i))
        b = led.balance()
        assert b["lost"] == 2
        assert b["lost_outside_window"] == 2

    def test_duplicate_and_rebase_detection(self):
        led = FaultLedger(clock=FakeClock())
        led.note_emitted("cam0", (0, 100))
        led.note_emitted("cam0", (0, 101))
        led.note_emitted("cam1", (0, 101))       # other stream: fine
        assert led.balance()["duplicated"] == 0
        led.note_emitted("cam0", (0, 101))       # same key again
        assert led.balance()["duplicated"] == 1
        led.note_emitted("cam0", (0, 7))         # producer restart
        b = led.balance()
        assert b["rebased"] == 1 and b["duplicated"] == 1

    def test_window_reopen_is_idempotent(self):
        led = FaultLedger(clock=FakeClock())
        led.open_window("xla_error")
        led.open_window("stall")                 # already open: kept
        assert led.window_open
        led.close_window()
        led.close_window()                       # no-op
        assert not led.window_open
        assert len(led.balance()["windows"]) == 1


# ---------------------------------------------------------------------------
# watchdog plane


def make_plane(**kw):
    clock = kw.pop("clock", FakeClock())
    kw.setdefault("shards", 4)
    kw.setdefault("deadline_ms", 100.0)
    kw.setdefault("hysteresis", 2)
    return FaultPlane(clock=clock, **kw), clock


class TestFaultPlane:
    def test_note_error_fault_shard_attribute(self):
        plane, _ = make_plane()
        exc = RuntimeError("device halted")
        exc.fault_shard = 2
        assert plane.note_error(exc, tick=7) == 2
        assert plane.pending() == {2: "xla_error"}
        assert plane.ledger.window_open
        det = [e for e in plane.snapshot()["events"]
               if e["event"] == "detected"]
        assert det and det[0]["shard"] == 2 and det[0]["tick"] == 7

    def test_note_error_device_name_attribution(self):
        plane, _ = make_plane()
        plane.set_shard_devices({0: ["TFRT_CPU_0"], 1: ["TFRT_CPU_1"]})
        exc = RuntimeError("XLA:CPU compile failed on TFRT_CPU_1: dead")
        assert plane.note_error(exc, tick=3) == 1
        assert plane.pending() == {1: "xla_error"}

    def test_note_error_unattributable_returns_none(self):
        plane, _ = make_plane()
        plane.set_shard_devices({0: ["TFRT_CPU_0"]})
        assert plane.note_error(ValueError("plain bug"), tick=1) is None
        assert plane.pending() == {}
        assert not plane.ledger.window_open

    def test_drain_deadline_hysteresis(self):
        plane, _ = make_plane(deadline_ms=100.0, hysteresis=2)
        plane.note_drain(250.0)                  # one overrun: not yet
        assert not plane.stall_suspected()
        plane.note_drain(40.0)                   # on time: counter resets
        plane.note_drain(250.0)
        assert not plane.stall_suspected()
        plane.note_drain(250.0)                  # second consecutive
        assert plane.stall_suspected()

    def test_resolve_stall_marks_pending_and_opens_window(self):
        plane, _ = make_plane()
        plane.note_drain(250.0)
        plane.note_drain(250.0)
        assert plane.stall_suspected()
        assert plane.resolve_stall([3], tick=11) == [3]
        assert plane.pending() == {3: "stall"}
        assert plane.ledger.window_open
        assert not plane.stall_suspected()       # pending suppresses

    def test_resolve_stall_empty_clears_suspicion_without_marking(self):
        plane, _ = make_plane()
        plane.note_drain(250.0)
        plane.note_drain(250.0)
        assert plane.resolve_stall([], tick=11) == []
        assert plane.pending() == {}
        assert not plane.stall_suspected()
        assert not plane.ledger.window_open

    def test_clear_pending_closes_window(self):
        plane, _ = make_plane()
        exc = RuntimeError("x")
        exc.fault_shard = 0
        plane.note_error(exc, tick=1)
        assert plane.ledger.window_open
        plane.clear_pending("no_survivors")
        assert plane.pending() == {}
        assert not plane.ledger.window_open

    def test_note_failover_updates_shards_and_closes(self):
        plane, _ = make_plane(shards=4)
        exc = RuntimeError("x")
        exc.fault_shard = 1
        plane.note_error(exc, tick=5)
        plane.note_failover({
            "tick": 6, "kinds": ["xla_error"], "shards_dead": [1],
            "survivors": 3, "failover_ms": 12.5, "over_budget": False,
            "evacuated": {"quality_thumbs": 8},
            "streams": {"total": 8, "kept": 6, "repinned": 2},
        })
        snap = plane.snapshot()
        assert snap["shards"] == 3 and snap["failovers"] == 1
        assert snap["pending"] == {} and snap["active"] is False
        assert not plane.ledger.window_open
        fo = [e for e in snap["events"] if e["event"] == "failover"]
        assert fo and fo[0]["survivors"] == 3

    def test_snapshot_shape_and_exposition_lint(self):
        plane, _ = make_plane()
        plane.note_dropped(3, "shutdown_drain")
        snap = plane.snapshot()
        assert {"config", "shards", "failovers", "active",
                "stall_suspected", "consecutive_overruns", "pending",
                "events", "ledger"} <= set(snap)
        assert snap["ledger"]["dropped"] == {"shutdown_drain": 3}
        problems = [p for p in lint_exposition(metrics_registry.render())
                    if "vep_fault" in p]
        assert problems == []


# ---------------------------------------------------------------------------
# rendezvous re-pin


class TestMakeRepin:
    def base(self, shards):
        return lambda did: stream_shard(did, shards)

    def test_survivors_keep_their_pins(self):
        base = self.base(4)
        repin = make_repin(base, 4, dead=[1])
        # Old shard s (surviving) -> its index among survivors [0, 2, 3].
        renumber = {0: 0, 2: 1, 3: 2}
        for i in range(32):
            did = f"cam{i}"
            home = base(did) % 4
            if home != 1:
                assert repin(did) == renumber[home]

    def test_dead_streams_land_on_survivors_deterministically(self):
        base = self.base(4)
        repin = make_repin(base, 4, dead=[1])
        again = make_repin(base, 4, dead=[1])
        moved = 0
        for i in range(64):
            did = f"cam{i}"
            if base(did) % 4 == 1:
                moved += 1
                assert 0 <= repin(did) < 3
                assert repin(did) == again(did)    # pure rendezvous
        assert moved > 0

    def test_composition_across_cascaded_faults(self):
        base = self.base(4)
        first = make_repin(base, 4, dead=[1])      # dp4 -> dp3
        second = make_repin(first, 3, dead=[0])    # dp3 -> dp2
        for i in range(64):
            did = f"cam{i}"
            assert 0 <= second(did) < 2
        # A stream that survived BOTH faults still maps through both
        # renumberings to the same physical home: old shard 2 sat at
        # survivor index 1 after fault #1, then index 0 after fault #2.
        keep = [f"cam{i}" for i in range(64)
                if base(f"cam{i}") % 4 == 2]
        assert keep and all(second(d) == 0 for d in keep)


# ---------------------------------------------------------------------------
# the prefetch stage's bounded queue


class TestPrefetchParityAcrossRebuild:
    def _group(self, *, sharded=True, bucket=4):
        return types.SimpleNamespace(
            model="tiny_blob_gauge", src_hw=(64, 64), bucket=bucket,
            rows=((0, 1) if sharded else None),
            frames=np.zeros((bucket, 64, 64, 3), np.uint8))

    def test_a_full_stage_refuses_at_shutdown_and_takes_again_once_drained(
            self):
        from video_edge_ai_proxy_tpu.engine.runner import _PrefetchStage

        stage = _PrefetchStage(lambda f: f, lambda: False)
        stop = threading.Event()
        # Never started, so two submissions sit in the depth-2 queue.
        p0 = stage.submit(self._group(), stop)
        p1 = stage.submit(self._group(), stop)
        assert p0 is not None and p1 is not None and p0 is not p1
        assert not p0.ready.is_set() and p0.placed is None
        # Both slots taken: a third waits, and shutdown ends the wait with
        # no handle (the caller returns the lease).
        stop.set()
        assert stage.submit(self._group(), stop) is None
        # A mesh rebuild waits every handle first — here the queue just
        # drains — and the stage takes the survivor mesh's batches.
        stage._q.get_nowait(), stage._q.get_nowait()
        stop.clear()
        assert stage.submit(self._group(sharded=False), stop) is not None


# ---------------------------------------------------------------------------
# live engine failover (CPU twin)


class TestEngineFailover:
    def test_dp2_hard_fault_fails_over_to_dp1_and_conserves(self):
        """ISSUE r22 acceptance (engine leg): a hard per-shard error on
        a dp=2 mesh detects within 2 ticks, rebuilds over the survivor,
        keeps serving every stream, and the ledger balances to zero
        frames lost or duplicated outside the declared window."""
        from video_edge_ai_proxy_tpu.engine import InferenceEngine

        streams = ["cam0", "cam1", "cam4", "cam5"]
        bus = MemoryFrameBus()
        eng = InferenceEngine(
            bus,
            EngineConfig(model="tiny_blob_gauge", mesh={"dp": 2},
                         batch_buckets=(2, 4), tick_ms=10, prof=False,
                         fault=True),
            annotations=AnnotationQueue(handler=lambda batch: True))
        eng.warmup()
        assert eng.faults is not None and eng.faults.shards == 2
        for sid in streams:
            bus.create_stream(sid, 64 * 64 * 3)
        results_q: queue.Queue = queue.Queue()
        with eng._sub_lock:
            eng._subscribers.append((results_q, None))

        orig_step = eng._step
        inject = {"arm": False, "tick": None}

        def step_with_fault(src_hw, bucket, model=None):
            if inject["arm"]:
                inject["arm"] = False
                inject["tick"] = eng.ticks
                exc = RuntimeError("injected: shard 1 device halted")
                exc.fault_shard = 1
                assert stream_shard(streams[0], 2) in (0, 1)
                raise exc
            return orig_step(src_hw, bucket, model)

        eng._step = step_with_fault

        results = []

        def drain():
            while True:
                try:
                    r = results_q.get_nowait()
                except queue.Empty:
                    return
                if r is not None:
                    results.append((time.monotonic(), r))

        eng.start()
        try:
            deadline = time.monotonic() + 20.0

            def publish_until(cond):
                step = 0
                last_ts = 0
                while not cond() and time.monotonic() < deadline:
                    ts = max(int(time.time() * 1000), last_ts + 1)
                    last_ts = ts
                    for i, sid in enumerate(streams):
                        bus.publish(sid, _blob_frame(key=i + 1),
                                    FrameMeta(width=64, height=64,
                                              channels=3, timestamp_ms=ts,
                                              is_keyframe=True))
                    step += 1
                    time.sleep(0.02)
                    drain()
                assert cond(), "timed out waiting for engine progress"

            publish_until(lambda: len(results) >= 8)   # steady state
            inject["arm"] = True
            publish_until(lambda: eng.faults.failovers >= 1)
            t_failover = time.monotonic()
            # Survivor mesh serves EVERY stream, including the dead
            # shard's evacuated ones.
            publish_until(lambda: {r.device_id for t, r in results
                                   if t > t_failover} == set(streams))
        finally:
            eng.stop()
            bus.close()

        snap = eng.faults.snapshot()
        assert snap["failovers"] == 1 and snap["shards"] == 1
        assert eng._shards == 1
        det = [e for e in snap["events"] if e["event"] == "detected"]
        fo = [e for e in snap["events"] if e["event"] == "failover"]
        assert det[0]["kind"] == "xla_error" and det[0]["shard"] == 1
        assert det[0]["tick"] - inject["tick"] <= 2
        assert fo[0]["shards_dead"] == [1] and fo[0]["survivors"] == 1
        assert not fo[0]["over_budget"]
        ledger = snap["ledger"]
        assert ledger["lost"] == 0
        assert ledger["duplicated"] == 0
        assert ledger["lost_outside_window"] == 0
        assert ledger["dropped"].get("device_fault", 0) > 0
        assert ledger["windows"] and \
            ledger["windows"][0]["closed"] is not None

    def test_fault_disabled_by_default_no_plane(self):
        from video_edge_ai_proxy_tpu.engine import InferenceEngine

        bus = MemoryFrameBus()
        try:
            eng = InferenceEngine(bus, EngineConfig(
                model="tiny_blob_gauge", batch_buckets=(1, 2), tick_ms=5))
            assert eng.faults is None
        finally:
            bus.close()


# ---------------------------------------------------------------------------
# endpoint convention


class _PM:
    def list(self):
        return []


class TestFaultEndpointConvention:
    def test_disabled_fault_answers_400_envelope(self):
        import urllib.error
        import urllib.request

        from video_edge_ai_proxy_tpu.engine import InferenceEngine
        from video_edge_ai_proxy_tpu.serve.rest_api import RestServer

        bus = MemoryFrameBus()
        eng = InferenceEngine(bus, EngineConfig(
            model="tiny_mobilenet_v2", batch_buckets=(1, 2), tick_ms=5))
        assert eng.faults is None                # default off
        srv = RestServer(_PM(), None, host="127.0.0.1", port=0, engine=eng)
        srv.start()
        try:
            base = f"http://127.0.0.1:{srv.bound_port}"
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(base + "/api/v1/faults")
            assert ei.value.code == 400
            body = json.loads(ei.value.read())
            assert set(body) == {"code", "message"}
            assert "engine.fault" in body["message"]
        finally:
            srv.stop()
            bus.close()

    def test_enabled_fault_serves_snapshot_and_stats_embed(self):
        import urllib.request

        from video_edge_ai_proxy_tpu.engine import InferenceEngine
        from video_edge_ai_proxy_tpu.serve.rest_api import RestServer

        bus = MemoryFrameBus()
        eng = InferenceEngine(bus, EngineConfig(
            model="tiny_mobilenet_v2", batch_buckets=(1, 2), tick_ms=5,
            fault=True))
        assert eng.faults is not None
        srv = RestServer(_PM(), None, host="127.0.0.1", port=0, engine=eng)
        srv.start()
        try:
            base = f"http://127.0.0.1:{srv.bound_port}"
            with urllib.request.urlopen(base + "/api/v1/faults") as r:
                body = json.loads(r.read())
            assert {"config", "shards", "failovers", "active",
                    "pending", "events", "ledger"} <= set(body)
            with urllib.request.urlopen(base + "/api/v1/stats") as r:
                stats = json.loads(r.read())
            assert stats["obs"]["faults"]["shards"] == body["shards"]
        finally:
            srv.stop()
            bus.close()


# ---------------------------------------------------------------------------
# fault=False kill-switch pin


class TestFaultChecksumPin:
    def test_fault_off_default_bit_identical(self):
        """The fault domain is watchdog + accounting around the serving
        path: the device outputs an engine emits must fold the SAME
        checksum with fault=True as with the default fault=False (the
        hbm/capacity/roi kill-switch pin, applied to the fault plane)."""
        from video_edge_ai_proxy_tpu.engine.runner import InferenceEngine
        from video_edge_ai_proxy_tpu.replay.checksum import (
            CHECKSUM_MASK,
            device_checksum,
            finalize_checksum,
        )

        def run(fault):
            b = MemoryFrameBus()
            try:
                b.create_stream("cam1", 64 * 64 * 3)
                eng = InferenceEngine(
                    b, EngineConfig(model="tiny_blob_gauge",
                                    batch_buckets=(1, 2, 4), tick_ms=5,
                                    prefetch=False, fault=fault),
                    annotations=AnnotationQueue(handler=lambda batch: True))
                eng.warmup()
                eng._drain_q = queue.Queue(maxsize=8)
                carry = 0
                for f, key in enumerate((1, 3, 5, 7)):
                    b.publish("cam1",
                              _blob_frame(15 if f % 2 == 0 else -15, key),
                              _meta())
                    groups = eng._collector.collect()
                    eng._dispatch(groups, time.perf_counter())
                    inflight = eng._drain_q.get(timeout=10)
                    part = int(np.asarray(
                        device_checksum(inflight.outputs)))
                    carry = (carry + part) & CHECKSUM_MASK
                    eng._emit(inflight)
                    eng._collector.release(inflight.group)
                    eng._drain_q.task_done()
                if fault:
                    assert eng.faults is not None
                    bal = eng.faults.ledger.balance()
                    assert bal["dispatched"] == bal["emitted"] == 4
                    assert bal["lost"] == 0
                else:
                    assert eng.faults is None
                return finalize_checksum(carry)
            finally:
                b.close()

        on, off = run(fault=True), run(fault=False)
        assert on == off
        assert on != 0
