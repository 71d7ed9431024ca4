"""MOSAIC ROI serving tests (engine/runner.py `_RoiGate`/`_roi_transform`,
engine/collector.py `CanvasPacker`, ops/boxes.py `uncrop_boxes`,
obs/perf.py ROI attribution).

The round-trip tests serve the blob gauge (models/blob.py): a detect-
identity instrument that returns the EXACT pixel bbox of color-keyed
blobs, so pack -> detect -> scatter-back is asserted with array equality,
not an IoU tolerance — any coordinate bug in the placement provenance or
the inverse affine shows up as an exact mismatch."""

import queue
import time

import numpy as np
import pytest

from video_edge_ai_proxy_tpu.bus.interface import FrameMeta
from video_edge_ai_proxy_tpu.bus.memory_bus import MemoryFrameBus
from video_edge_ai_proxy_tpu.engine.collector import CanvasPacker, CropPlacement
from video_edge_ai_proxy_tpu.engine.runner import InferenceEngine, _RoiGate
from video_edge_ai_proxy_tpu.models import registry
from video_edge_ai_proxy_tpu.models.blob import BINS, blob_color
from video_edge_ai_proxy_tpu.obs.metrics import Registry, lint_exposition
from video_edge_ai_proxy_tpu.ops.boxes import uncrop_boxes
from video_edge_ai_proxy_tpu.uplink.queue import AnnotationQueue
from video_edge_ai_proxy_tpu.utils.config import EngineConfig


def _meta(w=64, h=64, ts=None):
    return FrameMeta(
        width=w, height=h, channels=3,
        timestamp_ms=ts or int(time.time() * 1000), is_keyframe=True,
    )


def _scene(h=64, w=64, blobs=()):
    """Background-gray frame with color-keyed blobs. ``blobs`` is a list
    of (x0, y0, x1, y1, key); pixels [y0:y1, x0:x1] get blob_color(key),
    so the gauge's anchor ``key`` reports exactly (x0, y0, x1, y1)."""
    frame = np.full((h, w, 3), 114, np.uint8)
    for x0, y0, x1, y1, key in blobs:
        frame[y0:y1, x0:x1] = blob_color(key)
    return frame


@pytest.fixture(scope="module")
def gauge_step():
    """Compiled tiny blob-gauge serving step (one compile per module)."""
    import jax

    from video_edge_ai_proxy_tpu.engine.runner import build_serving_step

    spec = registry.get("tiny_blob_gauge")
    net, variables = spec.init_params(jax.random.PRNGKey(0))
    step = jax.jit(build_serving_step(net, spec))

    def run(frames_u8):
        out = step(variables, np.asarray(frames_u8, np.uint8))
        return {k: np.asarray(v) for k, v in out.items()}

    return run


def _detections(host, i, floor=0.5):
    """(class_id, [x0, y0, x1, y1]) per valid above-floor slot."""
    out = []
    for j in np.nonzero(host["valid"][i])[0]:
        if float(host["scores"][i, j]) < floor:
            continue
        out.append((int(host["classes"][i, j]),
                    [float(v) for v in host["boxes"][i, j]]))
    return out


class TestUncropBoxes:
    def test_identity(self):
        boxes = np.array([[3.0, 4.0, 10.0, 12.0]], np.float32)
        out = uncrop_boxes(boxes, scale=1, dst_origin=(0, 0),
                           src_origin=(0, 0))
        np.testing.assert_array_equal(out, boxes)

    def test_scale_and_origins(self):
        # canvas box [2,3,10,7], crop blitted at dst (1,1) from src
        # (100,50) at stride 2: src = (canvas - dst)*2 + src_origin.
        boxes = np.array([2.0, 3.0, 10.0, 7.0], np.float32)
        out = uncrop_boxes(boxes, scale=2, dst_origin=(1, 1),
                           src_origin=(100, 50))
        np.testing.assert_array_equal(out, [102.0, 54.0, 118.0, 62.0])

    @pytest.mark.parametrize("scale", [1, 2, 4])
    def test_exact_inverse_of_forward_placement(self, scale):
        """Forward placement (decimate by scale, blit at dst) composed
        with uncrop_boxes is the identity on box coordinates."""
        src_origin = (24, 40)
        dst_origin = (5, 9)
        src_box = np.array([32.0, 48.0, 56.0, 64.0], np.float32)
        canvas_box = (src_box
                      - np.array([24, 40, 24, 40], np.float32)) / scale \
            + np.array([5, 9, 5, 9], np.float32)
        out = uncrop_boxes(canvas_box, scale=scale, dst_origin=dst_origin,
                           src_origin=src_origin)
        np.testing.assert_array_equal(out, src_box)

    def test_batched_shape_preserved(self):
        boxes = np.zeros((3, 7, 4), np.float32)
        out = uncrop_boxes(boxes, scale=2, dst_origin=(1, 2),
                           src_origin=(3, 4))
        assert out.shape == (3, 7, 4)


class TestCanvasPacker:
    def _reqs(self, specs, frame_hw=(64, 64)):
        """specs: (device_id, roi) -> packer requests over gray frames."""
        h, w = frame_hw
        return [(did, _meta(w, h), _scene(h, w), roi)
                for did, roi in specs]

    def test_deterministic_byte_identical(self):
        reqs = self._reqs([
            ("camB", (0, 0, 30, 24)),
            ("camA", (10, 10, 28, 25)),
            ("camC", (4, 4, 24, 28)),
        ])
        packer = CanvasPacker(side=64, gap=8, max_canvases=4, min_crop=8)
        c1, p1, o1 = packer.pack(reqs)
        c2, p2, o2 = packer.pack(reqs)
        np.testing.assert_array_equal(c1, c2)
        assert p1 == p2 and o1 == o2

    def test_cells_never_overlap_and_respect_gap(self):
        rng = np.random.default_rng(3)
        specs = []
        for i in range(12):
            x0, y0 = rng.integers(0, 40, 2)
            specs.append((f"c{i:02d}", (x0, y0, x0 + int(rng.integers(8, 24)),
                                        y0 + int(rng.integers(8, 24)))))
        packer = CanvasPacker(side=64, gap=8, max_canvases=8, min_crop=8)
        canvases, placements, overflow = packer.pack(self._reqs(specs))
        assert not overflow
        assert len(placements) == 12
        for a in placements:
            ax0, ay0, ax1, ay1 = a.dst
            assert 0 <= ax0 < ax1 <= 64 and 0 <= ay0 < ay1 <= 64
            for b in placements:
                if a is b or a.canvas != b.canvas:
                    continue
                # Disjoint cells: a detection center can never route to
                # two streams.
                assert (a.dst[2] <= b.dst[0] or b.dst[2] <= a.dst[0]
                        or a.dst[3] <= b.dst[1] or b.dst[3] <= a.dst[1])

    def test_min_crop_inflation(self):
        packer = CanvasPacker(side=64, gap=8, max_canvases=2, min_crop=16)
        _, placements, _ = packer.pack(
            self._reqs([("cam", (30, 30, 33, 32))]))
        (p,) = placements
        assert p.src[2] - p.src[0] == 16 and p.src[3] - p.src[1] == 16
        assert p.scale == 1

    def test_oversize_crop_decimates_power_of_two(self):
        packer = CanvasPacker(side=64, gap=8, max_canvases=2, min_crop=8)
        frame = _scene(128, 128)
        _, placements, _ = packer.pack(
            [("cam", _meta(128, 128), frame, (0, 0, 128, 128))])
        (p,) = placements
        assert p.scale == 2
        assert p.dst == (0, 0, 64, 64)
        assert p.src == (0, 0, 128, 128)

    def test_overflow_lists_unpacked_requests(self):
        # Four 60px crops on one 64px canvas: first fits, rest overflow.
        packer = CanvasPacker(side=64, gap=8, max_canvases=1, min_crop=8)
        reqs = self._reqs([(f"c{i}", (0, 0, 60, 60)) for i in range(4)])
        canvases, placements, overflow = packer.pack(reqs)
        assert canvases.shape[0] == 1
        assert len(placements) == 1
        assert sorted(overflow) == [1, 2, 3]

    def test_area_fraction(self):
        placements = [
            CropPlacement("a", None, 0, (0, 0, 32, 32), (0, 0, 32, 32), 1),
            CropPlacement("b", None, 0, (0, 0, 32, 32), (40, 0, 72, 32), 1),
        ]
        frac = CanvasPacker.area_fraction(placements, 1, 64)
        assert frac == pytest.approx(2 * 32 * 32 / 64 / 64)
        assert CanvasPacker.area_fraction([], 0, 64) == 0.0


class TestPackDetectScatterRoundTrip:
    """Property gate: pack -> blob-gauge detect -> center-point route ->
    uncrop_boxes returns every painted box EXACTLY, including crops at
    canvas edges (letterbox-like 114 background all around) and
    decimated (scale > 1) crops on even-aligned boxes."""

    def _scatter(self, host, placements):
        """Replicates _emit_canvas's routing: center point -> cell ->
        exact inverse affine. Returns {device_id: [(class, box)]} and the
        unrouted count."""
        by_canvas = {}
        for p in placements:
            by_canvas.setdefault(p.canvas, []).append(p)
        routed = {p.device_id: [] for p in placements}
        unrouted = 0
        for ci, cells in by_canvas.items():
            for cid, bx in _detections(host, ci):
                cx = (bx[0] + bx[2]) / 2.0
                cy = (bx[1] + bx[3]) / 2.0
                cell = next((p for p in cells if p.contains(cx, cy)), None)
                if cell is None:
                    unrouted += 1
                    continue
                box = uncrop_boxes(np.asarray(bx, np.float32),
                                   scale=cell.scale,
                                   dst_origin=cell.dst[:2],
                                   src_origin=cell.src[:2])
                routed[cell.device_id].append(
                    (cid, [int(round(v)) for v in box]))
        return routed, unrouted

    def test_multi_stream_exact_boxes(self, gauge_step):
        # One color key per stream; blobs at awkward offsets, one crop
        # landing flush at the canvas origin (edge case: dst (0, 0)).
        blobs = {
            "camA": (24, 20, 36, 30, 1),
            "camB": (8, 40, 28, 56, 2),
            "camC": (30, 6, 44, 18, 4),
        }
        reqs = []
        for did, (x0, y0, x1, y1, key) in sorted(blobs.items()):
            frame = _scene(64, 64, [(x0, y0, x1, y1, key)])
            # Crop = blob rect + context margin, clipped to the frame.
            roi = (max(0, x0 - 3), max(0, y0 - 3),
                   min(64, x1 + 3), min(64, y1 + 3))
            reqs.append((did, _meta(), frame, roi))
        packer = CanvasPacker(side=64, gap=8, max_canvases=4, min_crop=8)
        canvases, placements, overflow = packer.pack(reqs)
        assert not overflow
        host = gauge_step(canvases)
        routed, unrouted = self._scatter(host, placements)
        assert unrouted == 0
        for did, (x0, y0, x1, y1, key) in blobs.items():
            assert routed[did] == [(key, [x0, y0, x1, y1])], did

    def test_blob_touching_crop_edge_stays_exact(self, gauge_step):
        """A box on the crop boundary (zero margin) must come back exact:
        the first/last crop pixels map to the first/last source pixels."""
        frame = _scene(64, 64, [(10, 16, 30, 40, 3)])
        reqs = [("cam", _meta(), frame, (10, 16, 30, 40))]
        packer = CanvasPacker(side=64, gap=8, max_canvases=1, min_crop=8)
        canvases, placements, _ = packer.pack(reqs)
        host = gauge_step(canvases)
        routed, unrouted = self._scatter(host, placements)
        assert unrouted == 0
        assert routed["cam"] == [(3, [10, 16, 30, 40])]

    def test_decimated_crop_round_trips_even_boxes(self, gauge_step):
        """A 128px frame crop on a 64px canvas decimates at stride 2;
        even-aligned blob coordinates survive the stride exactly."""
        frame = _scene(128, 128, [(20, 40, 48, 60, 5)])
        reqs = [("cam", _meta(128, 128), frame, (0, 0, 128, 128))]
        packer = CanvasPacker(side=64, gap=8, max_canvases=1, min_crop=8)
        canvases, placements, _ = packer.pack(reqs)
        assert placements[0].scale == 2
        host = gauge_step(canvases)
        routed, unrouted = self._scatter(host, placements)
        assert unrouted == 0
        assert routed["cam"] == [(5, [20, 40, 48, 60])]


class TestRoiGate:
    class _Tracker:
        def __init__(self, live):
            self.live_tracks = live

    def test_classify_table(self):
        gate = _RoiGate(idle_diff=1e-4, full_interval_ms=1000)
        now = 100.0
        # No gating signal yet (never emitted full): full.
        assert gate.classify("cam", self._Tracker(2), now) == "full"
        gate.note_full("cam", now)
        # Fresh full stamp, no diff signal, no tracker: full.
        assert gate.classify("cam", None, now) == "full"
        # Motionless: idle wins even with live tracks.
        gate.note_diff("cam", 5e-5)
        assert gate.classify("cam", self._Tracker(2), now) == "idle"
        # Motion + live tracks: roi.
        gate.note_diff("cam", 1e-2)
        assert gate.classify("cam", self._Tracker(2), now) == "roi"
        # Motion with nothing to localize it: full.
        assert gate.classify("cam", self._Tracker(0), now) == "full"
        assert gate.classify("cam", None, now) == "full"
        # Refresh cadence expired: full regardless of diff/tracks.
        gate.note_diff("cam", 5e-5)
        assert gate.classify("cam", self._Tracker(2), now + 1.5) == "full"

    def test_dict_protocol_for_engine_gc(self):
        gate = _RoiGate(idle_diff=1e-4, full_interval_ms=1000)
        assert not gate and len(gate) == 0
        gate.note_diff("a", 0.5)
        gate.note_full("b", 1.0)
        assert gate and len(gate) == 2
        assert sorted(gate) == ["a", "b"]
        assert gate.pop("a") is not None
        assert gate.pop("a", "sentinel") == "sentinel"
        assert list(gate) == ["b"]


class _FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class TestPerfRoiAttribution:
    def _perf(self):
        from video_edge_ai_proxy_tpu.obs.perf import PerfTracker

        reg = Registry()
        clk = _FakeClock()
        return reg, clk, PerfTracker(registry=reg, peak_tflops=100.0,
                                     clock=clk)

    def test_canvas_aware_note_batch(self):
        """Packed batches report crop-level occupancy (area fraction),
        not slot occupancy, and the fps window counts served streams,
        not canvases."""
        reg, clk, perf = self._perf()
        perf.note_batch("m", (64, 64), 4, 10.0, 2, streams=9,
                        area_frac=0.42)
        fam = {f.name: f for f in reg.families()}
        assert fam["vep_perf_bucket_occupancy_pct"].labels("m", "4").value \
            == pytest.approx(42.0)
        # Padded-slot accounting still sees 2 canvases in a 4-slot bucket.
        assert fam["vep_perf_padded_slots_total"].labels("m", "4").value == 2
        clk.advance(1.0)
        perf.note_batch("m", (64, 64), 4, 10.0, 2, streams=9,
                        area_frac=0.42)
        # 18 stream results over the 1 s span — canvas count (2) must not
        # deflate the fps evidence.
        assert perf.fps() == pytest.approx(18.0)

    def test_note_batch_without_kwargs_keeps_slot_occupancy(self):
        reg, clk, perf = self._perf()
        perf.note_batch("m", (64, 64), 4, 10.0, 3)
        fam = {f.name: f for f in reg.families()}
        assert fam["vep_perf_bucket_occupancy_pct"].labels("m", "4").value \
            == pytest.approx(75.0)

    def test_roi_counters_and_snapshot_section(self):
        import json

        reg, clk, perf = self._perf()
        assert "roi" not in perf.snapshot()   # quiet until ROI serves
        perf.note_roi_gate(idle=3, roi=2, full=1)
        perf.note_roi_pack(crops=4, canvases=2, area_frac=0.5)
        perf.note_roi_emit(2)
        clk.advance(1.0)
        perf.note_roi_emit(4)     # 6 results over a 1 s span
        perf.note_roi_unrouted()
        fam = {f.name: f for f in reg.families()}
        assert fam["vep_roi_stream_states_total"].labels("idle").value == 3
        assert fam["vep_roi_stream_states_total"].labels("roi").value == 2
        assert fam["vep_roi_stream_states_total"].labels("full").value == 1
        assert fam["vep_roi_crops_total"].value == 4
        assert fam["vep_roi_canvases_total"].value == 2
        assert fam["vep_roi_canvas_occupancy_pct"].value == 50.0
        assert fam["vep_roi_unrouted_total"].value == 1
        snap = perf.snapshot()
        json.dumps(snap)
        roi = snap["roi"]
        assert roi["stream_ticks"] == {"idle": 3, "roi": 2, "full": 1}
        assert roi["gated_stream_pct"] == pytest.approx(83.3)
        assert roi["crops"] == 4 and roi["canvases"] == 2
        assert roi["crops_per_canvas"] == 2.0
        assert roi["canvas_occupancy_pct"] == 50.0
        assert roi["unrouted"] == 1
        assert roi["equivalent_fps"] == pytest.approx(6.0)
        assert lint_exposition(reg.render()) == []


@pytest.fixture()
def bus():
    b = MemoryFrameBus()
    yield b
    b.close()


def _roi_engine(bus, **cfg_kw):
    """Hand-stepped ROI engine on the blob gauge: no threads started,
    the test drives collect -> _roi_transform -> _dispatch -> drain
    itself. The refresh cadence is pushed out so wall-clock time can
    never flip a verdict mid-test; the gate is steered by writing the
    stream's diff/full_at state directly."""
    cfg_kw.setdefault("roi_full_interval_ms", 600_000)
    cfg = EngineConfig(
        model="tiny_blob_gauge", batch_buckets=(1, 2, 4), tick_ms=5,
        prefetch=False, roi=True, roi_canvas=64, roi_min_crop=8, **cfg_kw,
    )
    eng = InferenceEngine(
        bus, cfg, annotations=AnnotationQueue(handler=lambda batch: True))
    eng.warmup()
    # Up to 3 groups (full + canvas + coast) can leave one hand-stepped
    # tick; the real engine overlaps dispatch with the drain thread, but
    # here both run on the test thread, so widen the queue to avoid a
    # self-deadlock on put().
    eng._drain_q = queue.Queue(maxsize=8)
    return eng


def _subscribe(eng):
    q = queue.Queue()
    with eng._sub_lock:
        eng._subscribers.append((q, None))
    return q


def _tick(eng, results_q):
    """One engine tick by hand; returns the InferenceResults it emitted."""
    groups = eng._collector.collect()
    if eng._roi is not None:
        groups = eng._roi_transform(groups)
    eng._dispatch(groups, time.perf_counter())
    while True:
        try:
            inflight = eng._drain_q.get_nowait()
        except queue.Empty:
            break
        try:
            eng._emit(inflight)
        finally:
            eng._collector.release(inflight.group)
            eng._drain_q.task_done()
    out = []
    while True:
        try:
            out.append(results_q.get_nowait())
        except queue.Empty:
            return out


def _only(results):
    assert len(results) == 1, [r.device_id for r in results]
    return results[0]


def _box_tuple(det):
    b = det.box
    return (b.left, b.top, b.left + b.width, b.top + b.height)


class TestRoiEngine:
    BLOB_A = (24, 20, 36, 30)   # xyxy, color key 1
    BLOB_B = (8, 40, 28, 56)    # xyxy, color key 2

    def _publish_scene(self, bus, did, blobs):
        bus.publish(did, _scene(64, 64, blobs), _meta())

    def test_full_roi_idle_transitions_exact_parity(self, bus):
        """One stream through all three verdicts: the packed-path and
        coasted detections must carry the SAME box the classic full
        frame produced (exact, not IoU), routed to the right stream,
        with zero unrouted detections and no synthetic canvas ids ever
        published."""
        import jax

        bus.create_stream("camA", 64 * 64 * 3)
        eng = _roi_engine(bus)
        sub = _subscribe(eng)
        x0, y0, x1, y1 = self.BLOB_A
        blob = [(x0, y0, x1, y1, 1)]
        try:
            # Tick 1 — no gating signal: classic full frame.
            self._publish_scene(bus, "camA", blob)
            r1 = _only(_tick(eng, sub))
            assert r1.device_id == "camA"
            (d1,) = r1.detections
            assert _box_tuple(d1) == self.BLOB_A
            assert d1.class_id == 1 and d1.track_id != ""
            # Full emission stamped the refresh cadence.
            assert eng._roi.state("camA")["full_at"] > 0

            # Tick 2 — motion + live track: crop packed onto a canvas.
            eng._roi.state("camA")["diff"] = 1.0
            self._publish_scene(bus, "camA", blob)
            r2 = _only(_tick(eng, sub))
            assert r2.device_id == "camA"   # never "_canvas0"
            (d2,) = r2.detections
            assert _box_tuple(d2) == self.BLOB_A
            assert d2.class_id == 1
            assert d2.confidence == pytest.approx(
                float(jax.nn.sigmoid(8.0)), rel=1e-4)

            # Tick 3 — motionless: gated idle, tracker-coasted result
            # with one miss of confidence decay, no device work.
            batches_before = eng.batches
            eng._roi.state("camA")["diff"] = 0.0
            self._publish_scene(bus, "camA", blob)
            r3 = _only(_tick(eng, sub))
            assert eng.batches == batches_before   # no device batch ran
            assert r3.device_id == "camA"
            (d3,) = r3.detections
            assert _box_tuple(d3) == self.BLOB_A   # static blob: box holds
            assert d3.track_id == d1.track_id
            assert d3.confidence == pytest.approx(
                float(jax.nn.sigmoid(8.0)) * eng._cfg.roi_coast_decay,
                rel=1e-4)

            snap = eng.perf.snapshot()
            assert snap["roi"]["unrouted"] == 0
            # Tick 1 was an all-full fast-path tick; it still counts.
            assert snap["roi"]["stream_ticks"] == {
                "idle": 1, "roi": 1, "full": 1}
            assert snap["roi"]["crops"] == 1
        finally:
            eng._drain_q.join()

    def test_coasted_groups_add_nothing_to_the_pacers_backlog(self, bus):
        """ISSUE 33: a coasted group rides the drain queue with no device
        work, so the read pacer (engine/pacing.py) never hears of it; a
        batch the device runs is in its backlog until its outputs are on
        the host."""
        bus.create_stream("camA", 64 * 64 * 3)
        eng = _roi_engine(bus)
        sub = _subscribe(eng)
        blob = [self.BLOB_A + (1,)]
        seen = []
        enqueue = eng._enqueue_drain

        def spy(inflight):
            seen.append((inflight.group.coast is not None,
                         eng._pacer.in_flight()))
            enqueue(inflight)

        eng._enqueue_drain = spy
        try:
            self._publish_scene(bus, "camA", blob)
            _only(_tick(eng, sub))              # full frame: device work
            assert seen == [(False, 1)]
            assert eng._pacer.in_flight() == 0  # drained by _emit
            eng._roi.state("camA")["diff"] = 0.0
            self._publish_scene(bus, "camA", blob)
            batches = eng.batches
            _only(_tick(eng, sub))              # gated idle: coasted
            assert eng.batches == batches
            assert seen[1:] == [(True, 0)]
            assert eng._pacer.read_at() is None
        finally:
            eng._drain_q.join()

    def test_two_streams_share_canvas_no_cross_talk(self, bus):
        """Two streams' crops on one shared canvas: each stream gets
        exactly its own blob back (distinct color keys prove routing),
        byte-exact, zero misrouted."""
        for did in ("camA", "camB"):
            bus.create_stream(did, 64 * 64 * 3)
        eng = _roi_engine(bus)
        sub = _subscribe(eng)
        scenes = {"camA": [self.BLOB_A + (1,)], "camB": [self.BLOB_B + (2,)]}
        # Tick 1: both full (primes trackers + cadence stamps).
        for did, blobs in scenes.items():
            self._publish_scene(bus, did, blobs)
        r1 = _tick(eng, sub)
        assert sorted(r.device_id for r in r1) == ["camA", "camB"]
        # Tick 2: both under motion -> both crops pack.
        for did, blobs in scenes.items():
            eng._roi.state(did)["diff"] = 1.0
            self._publish_scene(bus, did, blobs)
        r2 = {r.device_id: r for r in _tick(eng, sub)}
        assert sorted(r2) == ["camA", "camB"]
        (da,) = r2["camA"].detections
        (db,) = r2["camB"].detections
        assert _box_tuple(da) == self.BLOB_A and da.class_id == 1
        assert _box_tuple(db) == self.BLOB_B and db.class_id == 2
        snap = eng.perf.snapshot()
        assert snap["roi"]["unrouted"] == 0
        assert snap["roi"]["crops"] == 2
        assert snap["roi"]["canvases"] == 1   # shared, not one each

    def test_roi_off_is_structurally_inert(self, bus):
        """cfg.roi=False (the kill switch): no gate, no packer, and the
        tick pipeline the classic tests exercise runs exactly as before
        — _roi_transform is never even reachable."""
        cfg = EngineConfig(model="tiny_blob_gauge",
                           batch_buckets=(1, 2, 4), tick_ms=5,
                           prefetch=False)
        eng = InferenceEngine(
            bus, cfg,
            annotations=AnnotationQueue(handler=lambda batch: True))
        eng.warmup()
        assert eng._roi is None
        assert eng._packer is None

    def test_mesh_serving_roi_box_parity_vs_single_chip(self, bus):
        """r17 tentpole leg 3: ROI stays ON under a dp=2 mesh (the old
        auto-disable is gone) and the packed path emits the SAME exact
        boxes the single-chip packed path produces — canvases pack per
        mesh slice, so scatter-back routing is shard-local. cam0 lives
        on shard 0 and cam4 on shard 1 (engine.collector.stream_shard
        crc32 routing)."""
        blobs = {"cam0": self.BLOB_A + (1,), "cam4": self.BLOB_B + (2,)}

        def run(mesh):
            b = MemoryFrameBus()
            try:
                for did in blobs:
                    b.create_stream(did, 64 * 64 * 3)
                eng = _roi_engine(b, **({"mesh": mesh} if mesh else {}))
                if mesh is not None:
                    assert eng._roi is not None     # no auto-disable
                    assert eng._collector._shards == 2
                sub = _subscribe(eng)
                # Tick 1: full (primes trackers + cadence stamps).
                for did, blob in blobs.items():
                    self._publish_scene(b, did, [blob])
                r1 = _tick(eng, sub)
                assert sorted(r.device_id for r in r1) == ["cam0", "cam4"]
                # Tick 2: both under motion -> crops pack per slice.
                for did, blob in blobs.items():
                    eng._roi.state(did)["diff"] = 1.0
                    self._publish_scene(b, did, [blob])
                r2 = {r.device_id: r for r in _tick(eng, sub)}
                assert sorted(r2) == ["cam0", "cam4"]
                snap = eng.perf.snapshot()
                assert snap["roi"]["unrouted"] == 0
                assert snap["roi"]["crops"] == 2
                eng._drain_q.join()
                return {
                    did: [(_box_tuple(d), d.class_id)
                          for d in r2[did].detections]
                    for did in r2
                }
            finally:
                b.close()

        mesh = run({"dp": 2})
        assert mesh["cam0"] == [(self.BLOB_A, 1)]
        assert mesh["cam4"] == [(self.BLOB_B, 2)]
        assert mesh == run(None)                    # single-chip parity

    def test_mesh_roi_crop_blit_reads_global_rows(self, bus):
        """Regression (r17): under the shard-segmented layout with
        UNEQUAL shard occupancy, slot index != batch row — the crop
        blit must read ``group.frames[group.rows[i]]``, not
        ``frames[i]``. cam0 -> shard 0; cam4, cam5 -> shard 1, so the
        batch is [cam0, pad, cam4, cam5] and cam4's slot (1) points at
        shard 0's ZERO PAD row: blitting by slot cuts black pixels and
        the exact-box assert below fails."""
        scenes = {"cam0": self.BLOB_A + (1,), "cam4": self.BLOB_B + (2,),
                  "cam5": (36, 12, 52, 28, 3)}
        for did in scenes:
            bus.create_stream(did, 64 * 64 * 3)
        eng = _roi_engine(bus, mesh={"dp": 2})
        sub = _subscribe(eng)
        for did, blob in scenes.items():
            self._publish_scene(bus, did, [blob])
        r1 = _tick(eng, sub)
        assert sorted(r.device_id for r in r1) == sorted(scenes)
        for did, blob in scenes.items():
            eng._roi.state(did)["diff"] = 1.0
            self._publish_scene(bus, did, [blob])
        # The collected group really is unequally occupied: bucket 4,
        # rows [0, 2, 3] (shard 0 pads its second row).
        groups = eng._collector.collect()
        assert len(groups) == 1 and groups[0].bucket == 4
        assert list(groups[0].rows) == [0, 2, 3]
        groups = eng._roi_transform(groups)
        eng._dispatch(groups, time.perf_counter())
        while True:
            try:
                inflight = eng._drain_q.get_nowait()
            except queue.Empty:
                break
            try:
                eng._emit(inflight)
            finally:
                eng._collector.release(inflight.group)
                eng._drain_q.task_done()
        r2 = {}
        while True:
            try:
                r = sub.get_nowait()
            except queue.Empty:
                break
            r2[r.device_id] = r
        assert sorted(r2) == sorted(scenes)
        for did, blob in scenes.items():
            (det,) = r2[did].detections
            assert _box_tuple(det) == blob[:4], did
            assert det.class_id == blob[4], did
        assert eng.perf.snapshot()["roi"]["unrouted"] == 0
        eng._drain_q.join()

    def test_roi_on_full_path_bit_identical_checksum(self):
        """Detect-less scenes never gate (no tracks -> every verdict is
        full), so an ROI-enabled engine must fold the SAME device-output
        checksum as roi=False over the same frames — the motion gate may
        move work, never results (ISSUE 9 acceptance pin)."""
        from video_edge_ai_proxy_tpu.replay.checksum import (
            CHECKSUM_MASK,
            device_checksum,
            finalize_checksum,
        )

        def run(roi):
            b = MemoryFrameBus()
            try:
                eng = _roi_engine(b) if roi else None
                if eng is None:
                    cfg = EngineConfig(model="tiny_blob_gauge",
                                       batch_buckets=(1, 2, 4), tick_ms=5,
                                       prefetch=False)
                    eng = InferenceEngine(
                        b, cfg,
                        annotations=AnnotationQueue(
                            handler=lambda batch: True))
                    eng.warmup()
                b.create_stream("cam1", 64 * 64 * 3)
                carry = 0
                # Uniform gray ramps: large inter-frame diffs, zero
                # detections — the gate classifies full every tick.
                for value in (15, 60, 105, 150):
                    b.publish("cam1", np.full((64, 64, 3), value, np.uint8),
                              _meta())
                    groups = eng._collector.collect()
                    if eng._roi is not None:
                        groups = eng._roi_transform(groups)
                    eng._dispatch(groups, time.perf_counter())
                    inflight = eng._drain_q.get(timeout=10)
                    part = int(np.asarray(
                        device_checksum(inflight.outputs)))
                    carry = (carry + part) & CHECKSUM_MASK
                    eng._emit(inflight)
                    eng._collector.release(inflight.group)
                    eng._drain_q.task_done()
                return finalize_checksum(carry)
            finally:
                b.close()

        assert run(roi=True) == run(roi=False)
