"""The engine's batch trace (ISSUE 25): one measurement per tick and per
batch, taken where the work happens, handed to three sinks — stage records
(``cfg.stage_trace``), ``engine.*`` tracer events, registry counters.

CPU backend, tiny models: counts and orderings only, never a time as a
rate.
"""

import threading
import time

import numpy as np
import pytest

from video_edge_ai_proxy_tpu.bus.interface import FrameMeta
from video_edge_ai_proxy_tpu.bus.memory_bus import MemoryFrameBus
from video_edge_ai_proxy_tpu.engine import Collector, InferenceEngine
from video_edge_ai_proxy_tpu.obs import registry, tracer
from video_edge_ai_proxy_tpu.obs.spans import (
    ENGINE_STREAMS, STAGES, stage_breakdown, to_chrome_trace,
    validate_chrome_trace,
)
from video_edge_ai_proxy_tpu.uplink.queue import AnnotationQueue
from video_edge_ai_proxy_tpu.utils.config import EngineConfig

H, W = 48, 64
F = H * W * 3                     # bytes of one frame
L = 4                             # tiny_videomae's clip length
STAMPS = ("t_collect0", "t_collect", "t_place_q", "t_place0", "t_placed",
          "t_place_got", "t_step0", "t_step1", "t_submit", "t_deq",
          "t_drain0", "t_drained", "t_emitted")
PHASES = ("pre_collect", "pace_wait", "read", "clip", "fill",
          "collect_other", "place_wait", "step_call", "idle")


def _publish(bus, device_id, value=128):
    meta = FrameMeta(width=W, height=H, channels=3,
                     timestamp_ms=int(time.time() * 1000), is_keyframe=True)
    return bus.publish(device_id, np.full((H, W, 3), value, np.uint8), meta)


def _model_of(device_id):
    return ("tiny_videomae", L) if device_id.startswith("clip") \
        else ("tiny_vit", 0)


@pytest.fixture()
def bus():
    b = MemoryFrameBus()
    yield b
    b.close()


@pytest.fixture()
def spans_on():
    prev = (tracer.enabled, tracer.sample_every)
    tracer.clear()
    tracer.configure(enabled=True, sample_every=1)
    yield tracer
    tracer.configure(enabled=prev[0], sample_every=prev[1])
    tracer.clear()


def _phase_seconds():
    fam = {f.name: f for f in registry.families()}
    return {p: fam["vep_tick_phase_seconds_total"].labels(p).value
            for p in PHASES}


def _collect_bytes():
    fam = {f.name: f for f in registry.families()}
    return {k: fam["vep_collect_bytes_total"].labels(k).value
            for k in ("read", "copied", "fresh")}


# Where a clip camera's window lives follows from what the engine is: one
# device keeps it on the device, a mesh (here of one chip) on the host.
HOMES = {"device": {}, "host": {"mesh": {"dp": 1}}}


class _Fleet:
    """An engine serving tag cameras (tiny_vit, read straight into a pooled
    batch row) and clip cameras (tiny_videomae: their new frame likewise,
    into a window on the device, or through their clip rings on the host)
    by resolver.
    ``round()`` publishes one frame from every camera while the collector
    is held, so one tick reads the whole round."""

    def __init__(self, bus, tags=1, clips=2, **cfg_kw):
        self.bus = bus
        self.cams = [f"tag{i}" for i in range(tags)] \
            + [f"clip{i}" for i in range(clips)]
        for cam in self.cams:
            bus.create_stream(cam, F)
        cfg = EngineConfig(model="tiny_vit", batch_buckets=(1, 2, 4),
                           tick_ms=5, stage_trace=True, **cfg_kw)
        self.eng = InferenceEngine(
            bus, cfg, annotations=AnnotationQueue(handler=lambda b: True),
            model_resolver=lambda d: _model_of(d)[0])
        self.eng.warmup()
        self._gate = threading.Lock()
        real = self.eng._collector.collect

        def gated(*a, **kw):
            with self._gate:
                return real(*a, **kw)

        self.eng._collector.collect = gated
        self.rounds = 0

    def round(self):
        with self._gate:
            self.rounds += 1
            for cam in self.cams:
                _publish(self.bus, cam, value=self.rounds)

    def run(self, rounds):
        """Publish ``rounds`` rounds, each after the last was answered;
        returns the stage records once the engine has stopped."""
        self.eng.start()
        try:
            for _ in range(rounds):
                self.round()
                clips_full = self.rounds >= L
                want = sum(1 for c in self.cams
                           if clips_full or c.startswith("tag"))
                deadline = time.time() + 60
                have = len(self.eng.stage_records)
                while len(self.eng.stage_records) < have + want \
                        and time.time() < deadline:
                    time.sleep(0.005)
                assert len(self.eng.stage_records) >= have + want, \
                    "a round went unanswered"
        finally:
            self.eng.stop()
        return list(self.eng.stage_records)


def _by_batch(records):
    out = {}
    for r in records:
        out.setdefault(r["batch"], []).append(r)
    return out


class TestCollectorTrace:
    """Exact counts on a fleet the test controls, collector alone."""

    def _collector(self, bus, **kw):
        return Collector(bus, buckets=(1, 2, 4), model_of=_model_of, **kw)

    @pytest.mark.parametrize("home", ["host", "device"])
    def test_clip_cameras_copy_each_frame_1_plus_L_times(self, bus, home):
        """... with the window on the host; once, with it on the device."""
        n = 2
        for i in range(n):
            bus.create_stream(f"clip{i}", F)
        col = self._collector(bus, device_windows=home == "device")
        for k in range(L + 3):
            for i in range(n):
                _publish(bus, f"clip{i}", value=k)
            groups = col.collect()
            tr = col.last_trace
            assert tr["frames_read"] == n and tr["bytes_read"] == n * F
            if home == "device":
                # the frame is the sample from the first read on: ring of
                # the bus -> a fresh array -> a fresh batch at first
                # sight, then -> the camera's row of a pooled batch
                (g,) = groups
                assert g.window == L and g.frames.shape == (n, H, W, 3)
                assert (g.frames == k).all()
                assert (tr["bytes_copied"], tr["bytes_fresh"]) \
                    == ((2 * n * F,) * 2 if k == 0 else (n * F, 0))
                assert tr["read_s"] > 0 and tr["fill_s"] > 0
                continue
            if k < L - 1:          # windows filling: frames read, no clip
                assert groups == []
                # first sight: ring -> a fresh array -> slot 0 of a new
                # ring; then ring -> the next slot, written for the first
                # time: set-up shows as fresh
                assert tr["bytes_copied"] == tr["bytes_fresh"] \
                    == (2 if k == 0 else 1) * n * F
                continue
            assert len(groups) == 1 and groups[0].frames.shape[:2] == (n, L)
            # ring of the bus -> the slot falling out of the window, the
            # window -> its row of a pooled batch: F + L*F a camera
            assert tr["bytes_copied"] == n * F * (1 + L)
            # the last slot and the two pool buffers are new once each
            assert tr["bytes_fresh"] == {L - 1: n * F * (1 + L),
                                         L: n * F * L}.get(k, 0)
            assert tr["read_s"] > 0 and tr["fill_s"] > 0
            assert tr["read_ahead_s"] == 0.0

    def test_padding_rows_count_as_copied(self, bus):
        cams = [f"clip{i}" for i in range(3)] + ["tag0", "tag1", "tag2"]
        for cam in cams:
            bus.create_stream(cam, F)
        col = self._collector(bus)
        for cam in cams:
            _publish(bus, cam)
        tags, = col.collect()       # first sight: three tags, a fresh batch
        assert tags.bucket == 4
        # the fill writes the fourth row too; each clip seeds its ring
        assert col.last_trace["bytes_copied"] == 3 * F + 4 * F + 3 * 2 * F
        copied = []
        for k in range(1, L + 4):
            # a pooled clip batch: its pad row is written only once a
            # camera that had filled it sits out (from round L + 1 on)
            for cam in cams[:3 if k < L + 1 else 2]:
                _publish(bus, cam, value=k)
            groups = col.collect()
            copied.append(col.last_trace["bytes_copied"])
        assert groups[0].bucket == 2
        buf = groups[0].frames.base
        assert buf.shape[0] == 4 and not buf[2:].any()
        # both pool buffers had held three clips: each zeroes its third
        # row once, in the round that finds it dirty, and never again
        assert copied[-3:] == [2 * F * (1 + L) + L * F,
                               2 * F * (1 + L) + L * F, 2 * F * (1 + L)]

    def test_fast_path_tag_camera_copies_once_into_the_pool(self, bus):
        bus.create_stream("tag0", F)
        col = self._collector(bus)
        _publish(bus, "tag0")
        col.collect()               # first sight: generic path, fresh
        first = col.last_trace
        assert (first["bytes_read"], first["bytes_copied"],
                first["bytes_fresh"]) == (F, 2 * F, 2 * F)
        _publish(bus, "tag0")
        groups = col.collect()      # geometry known: ring -> pooled slot
        tr = col.last_trace
        assert len(groups) == 1
        assert (tr["frames_read"], tr["bytes_read"], tr["bytes_copied"],
                tr["bytes_fresh"]) == (1, F, F, 0)

    def test_an_empty_collect_reads_nothing(self, bus):
        bus.create_stream("tag0", F)
        col = self._collector(bus)
        assert col.collect() == []
        tr = col.last_trace
        assert tr["frames_read"] == tr["bytes_copied"] == 0

    def test_reads_between_ticks_count_in_the_tick_that_dispatches(self, bus):
        bus.create_stream("tag0", F)
        col = self._collector(bus)
        _publish(bus, "tag0")
        col.collect()               # learns the geometry
        col.plan_assembly()
        _publish(bus, "tag0")
        assert col.assemble_step() == 1     # read ahead of the tick
        assert col.last_trace["bytes_read"] == F    # the previous tick's
        groups = col.collect()
        tr = col.last_trace
        assert len(groups) == 1
        assert (tr["frames_read"], tr["bytes_read"], tr["bytes_fresh"]) \
            == (1, F, 0)
        assert 0 < tr["read_ahead_s"] <= tr["read_s"]


class TestStageRecords:
    @pytest.mark.parametrize("prefetch", [True, False])
    def test_every_record_carries_the_ordered_batch_trace(self, bus,
                                                          prefetch):
        records = _Fleet(bus, tags=1, clips=2, prefetch=prefetch).run(L + 2)
        assert records
        for r in records:
            assert isinstance(r["tick"], int)
            assert r["batch"][0] == r["tick"]
            # with the prefetch stage a group is handed to the transfer
            # thread from inside collect(), the moment its last frame is
            # read (ISSUE 35): its placement's stamps start before the
            # collection closes, and the rest of the order holds
            early = prefetch
            order = [k for k in STAMPS if not (early and k == "t_collect")]
            stamps = [r[k] for k in order]
            assert stamps == sorted(stamps), dict(zip(order, stamps))
            if early:
                assert r["t_collect0"] <= r["t_place_q"] <= r["t_collect"] \
                    <= r["t_place_got"]
                assert r["place_ahead_s"] == pytest.approx(
                    r["t_collect"] - r["t_place_q"])
            else:
                assert r["place_ahead_s"] == 0.0
            in_collect = r["read_s"] - r["read_ahead_s"] + r["fill_s"]
            # perf_counter durations against time.time() stamps
            assert in_collect <= r["t_collect"] - r["t_collect0"] + 1e-3
            assert r["collect_other_s"] >= 0 and r["pre_collect_s"] >= 0
            assert r["place_wait_s"] >= 0 and r["step_call_s"] > 0
            # every tick's trace carries the paced wait (0.0 unless the
            # tick was waiting on the previous round's batch when this
            # round was published)
            assert 0.0 <= r["pace_wait_s"] < 1.0
        for recs in _by_batch(records).values():
            first = {k: v for k, v in recs[0].items()
                     if k not in ("device_id", "ts_pub_ms", "t_emitted")}
            for r in recs[1:]:      # one trace a batch, shared
                assert {k: r[k] for k in first} == first

    def test_one_group_a_tick_is_handed_over_as_the_collect_ends(
            self, bus, spans_on):
        """``place_ahead_s`` (ISSUE 35) with one group a tick: the group is
        finished by the collect's last read, so its placement starts the
        few moments the collect takes to close ahead of ``t_collect``, and
        all three sinks say so."""
        fam = {f.name: f for f in registry.families()}
        early = fam["vep_groups_placed_early_total"]
        before = early.value
        records = _Fleet(bus, tags=2, clips=0).run(L + 2)
        batches = _by_batch(records)
        assert len(batches) == L + 2
        for recs in batches.values():
            r = recs[0]
            assert r["batch"][1] == 0
            assert r["place_ahead_s"] >= 0.0
            assert r["place_ahead_s"] == pytest.approx(
                max(0.0, r["t_collect"] - r["t_place_q"]))
            assert r["t_collect0"] <= r["t_place_q"] <= r["t_place0"] \
                <= r["t_placed"] <= r["t_place_got"]
        # every tick that read a frame handed its one group over
        assert early.value - before == L + 2
        placed = {tuple(e["batch"]): e for e in spans_on.events()
                  if e["stream"] == "engine.transfer"}
        for key, recs in batches.items():
            assert placed[key]["ahead_ms"] == pytest.approx(
                recs[0]["place_ahead_s"] * 1e3, abs=1e-3)

    @pytest.mark.parametrize("home", ["device", "host"])
    def test_two_groups_of_one_tick_share_tick_and_differ_in_batch(
            self, bus, home):
        records = _Fleet(bus, tags=1, clips=2, **HOMES[home]).run(L + 2)
        # copies of a clip camera's frame: ring -> pooled row; on the host
        # ring -> its clip ring -> a pooled row
        per_clip = 1 if home == "device" else 1 + L
        by_tick = {}
        for r in records:
            by_tick.setdefault(r["tick"], set()).add(r["batch"])
        double = [t for t, bs in by_tick.items() if len(bs) == 2]
        # rounds L, L+1, L+2: a tag batch and a clip batch in one tick
        assert len(double) == 3
        for t in double:
            assert sorted(b[1] for b in by_tick[t]) == [0, 1]
            recs = [r for r in records if r["tick"] == t]
            assert {r["device_id"] for r in recs} \
                == {"tag0", "clip0", "clip1"}
            # single frames first: the small batch does not wait behind
            # the clip batch's placement
            assert {r["device_id"] for r in recs if r["batch"][1] == 0} \
                == {"tag0"}
            # both batches carry the one tick's collector counts: the tag
            # frame ring -> pooled slot once, each clip frame as above
            for r in recs:
                assert r["bytes_read"] == 3 * F
                assert r["bytes_copied"] == F + 2 * F * per_clip
                assert r["frames_read"] == 3
                # rows written into windows on the device, by batch
                assert r["window_rows"] == (
                    2 if home == "device" and r["batch"][1] == 1 else 0)
                assert r["window_restarts"] == 0
        fresh = [next(r["bytes_fresh"] for r in records if r["tick"] == t)
                 for t in sorted(double)]
        if home == "device":
            assert fresh == [0, 0, 0]    # pooled rows, written before
            return
        # fresh is set-up: the rings' last slots and the first pool buffer
        # in round L, the second pool buffer in round L+1, and nothing
        # after unless the drain thread still held a lease (a third buffer)
        assert fresh[:2] == [2 * F * (1 + L), 2 * F * L]
        assert fresh[2] in (0, 2 * F * L)

    def test_the_paced_wait_is_stamped_apart_from_pre_collect(self, bus,
                                                              spans_on):
        """``pace_wait_s`` (ISSUE 33): the tick thread's wait before the
        read is a phase of its own in all three sinks, and no part of
        ``pre_collect_s`` (so ``tick_other_ms`` reads what it read)."""
        fleet = _Fleet(bus, tags=1, clips=0)
        held = 0.06

        def wait(stop):             # every tick's wait engages
            t0 = time.perf_counter()
            time.sleep(held)
            return time.perf_counter() - t0

        fleet.eng._pacer.wait = wait
        before = _phase_seconds()
        records = fleet.run(4)
        after = _phase_seconds()
        assert len(records) == 4
        for prev, r in zip([None] + records, records):
            assert r["pace_wait_s"] >= held
            # the wait lies between the previous dispatch's end and
            # collect() entry, and is taken out of that span
            assert r["pre_collect_s"] < 0.5 * held
            if prev is not None:
                assert prev["t_submit"] + r["pace_wait_s"] \
                    <= r["t_collect0"] + 1e-3
        # the counter rose by the waits of the ticks that read a frame
        # (an idle tick's wait is idle time)
        assert after["pace_wait"] - before["pace_wait"] == pytest.approx(
            sum(r["pace_wait_s"] for r in records), abs=1e-6)
        assert after["pre_collect"] - before["pre_collect"] < 4 * 0.5 * held
        assert after["idle"] - before["idle"] >= held
        # and the tick track draws it, ending where collect() starts
        events = [e for e in spans_on.events()
                  if e["stream"] == "engine.tick"]
        paced = {e["tick"]: e for e in events if e["stage"] == "pace_wait"}
        assert sorted(paced) == sorted(r["tick"] for r in records)
        for r in records:
            e = paced[r["tick"]]
            assert e["dur_ms"] == pytest.approx(r["pace_wait_s"] * 1e3)
            assert e["ts"] == pytest.approx(r["t_collect0"])
            pre = next(x for x in events if x["stage"] == "pre_collect"
                       and x["tick"] == r["tick"])
            assert pre["ts"] == pytest.approx(e["ts"] - e["dur_ms"] / 1e3)

    def test_equal_floats_never_merge_batches(self, bus):
        """Batches are told apart by identifier: forcing every stamp of a
        kind equal changes nothing about how records group."""
        records = _Fleet(bus, tags=1, clips=2).run(L + 1)
        n_batches = len(_by_batch(records))
        for r in records:
            r["t_submit"] = 1.0
        assert len(_by_batch(records)) == n_batches >= 3


class TestSinksAgree:
    def test_idle_ticks_leave_no_event_and_no_record(self, bus, spans_on):
        fleet = _Fleet(bus, tags=1, clips=0)
        before = _phase_seconds()
        fleet.eng.start()
        try:
            deadline = time.time() + 30
            while fleet.eng.ticks < 20 and time.time() < deadline:
                time.sleep(0.01)
        finally:
            fleet.eng.stop()
        assert fleet.eng.ticks >= 20
        assert not fleet.eng.stage_records
        assert not [s for s in spans_on.streams() if s in ENGINE_STREAMS]
        after = _phase_seconds()
        assert after["idle"] > before["idle"]
        for p in PHASES:
            if p != "idle":
                assert after[p] == before[p], p

    def test_tracks_records_and_counters_tell_one_story(self, bus, spans_on):
        fleet = _Fleet(bus, tags=1, clips=2)
        bytes0, phase0 = _collect_bytes(), _phase_seconds()
        records = fleet.run(L + 2)
        bytes1, phase1 = _collect_bytes(), _phase_seconds()
        events = spans_on.events()
        engine = [e for e in events if e["stream"] in ENGINE_STREAMS]
        assert {e["stream"] for e in engine} == set(ENGINE_STREAMS)
        assert {e["stage"] for e in engine} <= set(STAGES)
        assert all(e["frame"] == e["tick"] for e in engine)
        # every tick that read a frame left one "tick" event; counters rose
        # by the bytes those events name
        ticks = [e for e in engine if e["stage"] == "tick"]
        assert len(ticks) == L + 2
        for kind in ("read", "copied", "fresh"):
            assert bytes1[kind] - bytes0[kind] \
                == sum(e["bytes_" + kind] for e in ticks)
        assert bytes1["read"] - bytes0["read"] == (L + 2) * 3 * F
        for p in PHASES:
            assert phase1[p] >= phase0[p]
        for p in ("read", "fill", "step_call"):
            assert phase1[p] > phase0[p], p
        assert phase1["clip"] == phase0["clip"]     # nothing is assembled
        # the records' batches are the events' batches, but for the clip
        # batches of rounds 1 .. L-1: their frames were written into
        # windows still filling on the device and nothing was computed,
        # so they end on the tick thread, with no record and no drain
        batches = {tuple(e["batch"]) for e in engine if "batch" in e}
        drained = set(_by_batch(records))
        assert drained <= batches and len(batches - drained) == L - 1
        assert sum(e["window_rows"] for e in ticks) == 2 * (L + 2)
        assert sum(e["window_restarts"] for e in ticks) == 0
        for stage in ("place_wait", "step_call"):
            assert {tuple(e["batch"]) for e in engine
                    if e["stage"] == stage} == batches, stage
        for stage in ("place", "drain_wake", "fetch", "emit_batch"):
            assert {tuple(e["batch"]) for e in engine
                    if e["stage"] == stage} == drained, stage
        # the tick's spans nest inside its "tick" event
        for t in ticks:
            inner = [e for e in engine if e["stream"] == "engine.tick"
                     and e["tick"] == t["tick"] and e is not t]
            assert {"pre_collect", "collect_tick"} \
                <= {e["stage"] for e in inner}
            for e in inner:
                assert e["ts"] <= t["ts"] + 1e-3
                assert e["ts"] - e["dur_ms"] / 1e3 \
                    >= t["ts"] - t["dur_ms"] / 1e3 - 1e-3
        # the export draws the three threads beside the cameras, and the
        # per-camera lineage table is what it was
        trace = to_chrome_trace(events)
        assert validate_chrome_trace(trace) == []
        names = {e["args"]["name"] for e in trace["traceEvents"]
                 if e.get("name") == "thread_name"}
        assert {f"stream {s}" for s in ENGINE_STREAMS} <= names
        assert {f"stream {c}" for c in fleet.cams} <= names
        cameras = [e for e in events if e["stream"] not in ENGINE_STREAMS]
        assert stage_breakdown(events) == stage_breakdown(cameras)


STEP_SCOPES = ("pre_cast_scale", "pre_resize", "pre_normalize", "embed",
               "encoder_block", "cls_head", "softmax_topk")


def _served(bus, model, part):
    """Serve ``model``'s tiny twin to two clip cameras for three batches
    each. Returns the stage records that carry ``{part}_blocks_dense``, one
    record a batch of them, and what ``vep_{part}_key_blocks_total`` rose
    by ({kind: n})."""
    cams = ["clip0", "clip1"]
    for cam in cams:
        bus.create_stream(cam, F)
    eng = InferenceEngine(
        bus, EngineConfig(model=model, batch_buckets=(2,), tick_ms=5,
                          stage_trace=True, ladder=False),
        annotations=AnnotationQueue(handler=lambda b: True))
    eng.warmup()
    fam = {f.name: f for f in registry.families()}
    blocks = fam[f"vep_{part}_key_blocks_total"]
    before = {k: blocks.labels(k).value for k in ("live", "dense")}
    eng.start()
    try:
        k, deadline = 0, time.time() + 90
        while len(eng.stage_records) < 3 * len(cams) \
                and time.time() < deadline:
            k += 1
            for cam in cams:
                _publish(bus, cam, value=k % 250)
            time.sleep(0.05)
    finally:
        eng.stop()
    recs = [r for r in eng.stage_records if f"{part}_blocks_dense" in r]
    assert len(recs) >= 3 * len(cams)
    batches = {tuple(r["batch"]): r for r in recs}.values()
    return recs, batches, {k: blocks.labels(k).value - before[k]
                           for k in ("live", "dense")}


def _visited(bus, model, layers):
    """Check what ``model``'s prefill attention visited: the step's count
    of the tiles it visited, and of what a dense pass would visit, on every
    record of the batch and in ``vep_attn_key_blocks_total``. ``layers``:
    the head's attention layers."""
    recs, batches, rose = _served(bus, model, "attn")
    # tiny widths: the new positions one key block and one lane tile of
    # queries, what can be cached one key block: 2 tiles a stream an
    # attention dense, 1 or 2 live
    dense = 2 * layers * 2
    for rec in recs:
        assert rec["attn_blocks_dense"] == dense
        assert dense // 2 <= rec["attn_blocks_live"] <= dense
    # a context holds the instruction at least: its key block is visited
    assert all(r["attn_blocks_live"] == dense for r in batches)
    for kind in ("live", "dense"):
        assert rose[kind] == sum(r[f"attn_blocks_{kind}"] for r in batches)


def test_a_stream_batch_carries_what_its_prefill_attention_visited(bus):
    """A stream head's batch (``tiny_videomae_xing4``: latent attention)."""
    from video_edge_ai_proxy_tpu.models import registry as models

    model = "tiny_videomae_xing4"
    _visited(bus, model, models.get(model).build().cfg.head.num_layers)


def test_a_latent_heads_batch_carries_what_its_decode_attention_fetched(
        bus):
    """``tiny_videomae_dsv2``: its decode attention's count of the key
    blocks it fetched from the pool and of what a dense pass would fetch,
    on every record of the batch and in ``vep_decode_key_blocks_total``.
    Tiny widths: the pool's 160 positions are one key block, which holds
    all 128 cacheable ones: a stream fetches it in each of the 3 decode
    iterations' 3 blocks, as a dense pass would (every context holds the
    instruction)."""
    from video_edge_ai_proxy_tpu.models import registry as models

    model = "tiny_videomae_dsv2"
    c = models.get(model).build().cfg
    recs, batches, rose = _served(bus, model, "decode")
    dense = 2 * c.decode_steps * c.head.num_layers
    for rec in recs:
        assert rec["decode_blocks_dense"] == dense
        assert rec["decode_blocks_live"] == dense
    for kind in ("live", "dense"):
        assert rose[kind] == sum(r[f"decode_blocks_{kind}"] for r in batches)


def test_a_grouped_query_heads_batch_carries_what_it_visited(bus):
    """A grouped-query head's batch (``tiny_videomae_lfm2``: the kernel of
    ``models/gqa.py``) carries the same fields, by the same rule."""
    from video_edge_ai_proxy_tpu.models import registry as models

    model = "tiny_videomae_lfm2"
    _visited(bus, model, models.get(model).build().cfg.head.attn_layers)


@pytest.mark.parametrize("model,shape", [
    ("tiny_vit", (2, H, W, 3)), ("tiny_videomae", (2, L, H, W, 3))])
def test_the_compiled_step_names_its_stages(model, shape):
    """``jax.named_scope`` around the stages of the serving step: the
    names an operator finds in a device trace (PERF.md lists them)."""
    import jax
    import jax.numpy as jnp

    from video_edge_ai_proxy_tpu.engine.runner import build_serving_step
    from video_edge_ai_proxy_tpu.models import registry as models

    spec = models.get(model)
    module, variables = spec.init_params(jax.random.PRNGKey(0))
    lowered = jax.jit(build_serving_step(module, spec)).lower(
        variables, jax.ShapeDtypeStruct(shape, jnp.uint8))
    text = lowered.as_text(debug_info=True)
    for scope in STEP_SCOPES:
        assert f"/{scope}/" in text, scope
