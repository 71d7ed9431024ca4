"""Observability tests: metrics registry + exposition lint, frame-lineage
spans (sampling, stage breakdown, Chrome trace export), the once-per-
episode watchdog, and the engine satellite regressions (stats() snapshot
isolation, EMA zero-sentinel fix)."""

import dataclasses
import json
import logging
import time

import numpy as np
import pytest

from video_edge_ai_proxy_tpu.obs.metrics import (
    BUCKET_BOUNDS,
    N_BUCKETS,
    Registry,
    bucket_index,
    lint_exposition,
)
from video_edge_ai_proxy_tpu.obs.spans import (
    SpanRecorder,
    stage_breakdown,
    to_chrome_trace,
    validate_chrome_trace,
)
from video_edge_ai_proxy_tpu.obs.watch import Watchdog


class TestBuckets:
    def test_bucket_index_boundaries(self):
        # <= 0 counts in bucket 0 (a 0.0 ms latency is a legitimate
        # observation — the EMA-sentinel bug this layer replaces).
        assert bucket_index(0.0) == 0
        assert bucket_index(-1.0) == 0
        # Exact powers of two land on their own le= bound (value <= le).
        for i, bound in enumerate(BUCKET_BOUNDS):
            assert bucket_index(bound) == i, bound
        # Just above a bound spills to the next bucket; huge -> overflow.
        assert bucket_index(BUCKET_BOUNDS[3] * 1.001) == 4
        assert bucket_index(BUCKET_BOUNDS[-1] * 2) == N_BUCKETS - 1


class TestRegistry:
    def test_counter_gauge_basics(self):
        reg = Registry()
        c = reg.counter("t_frames_total", "frames")
        c.inc()
        c.inc(2.0)
        assert c.value == 3.0
        g = reg.gauge("t_depth", "depth")
        g.set(5.0)
        g.inc()
        g.dec(2.0)
        assert g.value == 4.0
        # get-or-create returns the same family; kind/labels conflict raises
        assert reg.counter("t_frames_total", "frames") is c
        with pytest.raises(ValueError):
            reg.gauge("t_frames_total", "frames")
        with pytest.raises(ValueError):
            reg.counter("t_frames_total", "frames", ("stream",))

    def test_histogram_percentiles_without_samples(self):
        reg = Registry()
        h = reg.histogram("t_lat_ms", "lat").labels()
        assert h.percentile(50) is None
        for v in [1.0] * 50 + [100.0] * 50:
            h.observe(v)
        assert h.count == 100
        assert h.sum == pytest.approx(5050.0)
        # p50 interpolates to the top of the bucket holding 1.0
        assert h.percentile(50) == pytest.approx(1.0)
        # p90 lands inside 100.0's (64, 128] bucket
        assert 64.0 < h.percentile(90) <= 128.0
        snap = h.snapshot()
        assert snap["count"] == 100
        assert snap["avg"] == pytest.approx(50.5)
        # overflow observations clamp to the largest finite bound
        h.observe(1e9)
        assert h.percentile(99.9) == BUCKET_BOUNDS[-1]

    def test_render_lints_clean_and_escapes_labels(self):
        reg = Registry()
        reg.counter("t_esc_total", 'weird "help"\nline', ("stream",)).labels(
            'cam"\\\nx').inc()
        reg.gauge("t_g", "g").set(1.5)
        reg.histogram("t_h_ms", "h", ("model",)).labels("m1").observe(3.0)
        text = reg.render()
        assert lint_exposition(text) == []
        assert r'stream="cam\"\\\nx"' in text
        # snapshot() is JSON-able as-is (artifact embedding)
        json.dumps(reg.snapshot())

    def test_lint_catches_malformed_exposition(self):
        bad = "\n".join([
            "vep_orphan 1",                  # sample with no TYPE
            "# TYPE vep_bogus flavor",       # invalid TYPE token
            "# TYPE vep_dup counter",
            'vep_dup{a="1"} 1',
            'vep_dup{a="1"} 2',              # duplicate sample
            "vep_dup nope",                  # non-numeric value
        ])
        assert lint_exposition(bad) != []

    def test_family_clear_drops_children(self):
        reg = Registry()
        fam = reg.gauge("t_per_worker", "w", ("stream",))
        fam.labels("cam1").set(1)
        assert "cam1" in reg.render()
        fam.clear()
        assert "t_per_worker" not in reg.render()


class TestSpans:
    def test_sampling_deterministic_and_gated(self):
        rec = SpanRecorder(sample_every=4, enabled=True)
        assert [fid for fid in range(12) if rec.sampled(fid)] == [0, 4, 8]
        rec.configure(enabled=False)
        assert not rec.sampled(0)

    def test_ring_bound(self):
        rec = SpanRecorder(enabled=True, sample_every=1, ring=4)
        for i in range(10):
            rec.record("cam1", "collect", i)
        evs = rec.events("cam1")
        assert len(evs) == 4
        assert evs[-1]["frame"] == 9

    def test_stage_breakdown_legs(self):
        # One complete lineage with known leg durations: publish at t0
        # (pub_ms carried by the collect span — the subprocess-worker
        # case), collect +5 ms, submit +2 ms, device 4 ms, emit +0.5 ms.
        rec = SpanRecorder(enabled=True, sample_every=1)
        t0 = 1000.0
        rec.record("cam1", "collect", 7, ts=t0 + 0.005, pub_ms=t0 * 1000.0)
        rec.record("cam1", "submit", 7, ts=t0 + 0.007)
        rec.record("cam1", "device", 7, ts=t0 + 0.011, dur_ms=4.0)
        rec.record("cam1", "emit", 7, ts=t0 + 0.0115)
        br = stage_breakdown(rec.events())
        assert br["ingest_bus"]["avg"] == pytest.approx(5.0, abs=0.01)
        assert br["batch"]["avg"] == pytest.approx(2.0, abs=0.01)
        assert br["device"]["avg"] == pytest.approx(4.0, abs=0.01)
        assert br["emit"]["avg"] == pytest.approx(0.5, abs=0.01)
        assert br["total"]["avg"] == pytest.approx(11.5, abs=0.01)
        assert br["total"]["count"] == 1

    def test_partial_lineage_contributes_partial_legs(self):
        rec = SpanRecorder(enabled=True, sample_every=1)
        rec.record("cam1", "device", 3, ts=2.0, dur_ms=4.0)
        br = stage_breakdown(rec.events())
        assert br["device"]["count"] == 1
        assert br["total"]["count"] == 0

    def test_chrome_trace_export_validates_and_roundtrips(self):
        rec = SpanRecorder(enabled=True, sample_every=1)
        rec.record("cam1", "device", 3, ts=2.0, dur_ms=4.0, bucket=2)
        rec.record("cam1", "emit", 3, ts=2.001)
        obj = to_chrome_trace(rec.events())
        assert validate_chrome_trace(obj) == []
        obj = json.loads(json.dumps(obj))          # JSON-able as-is
        complete = [e for e in obj["traceEvents"] if e.get("ph") == "X"]
        assert len(complete) == 1
        # ph "X" carries start ts (end - dur) in microseconds
        assert complete[0]["dur"] == pytest.approx(4000.0)
        assert complete[0]["ts"] == pytest.approx(2.0e6 - 4000.0)
        assert complete[0]["args"]["bucket"] == 2
        # the validator actually rejects malformed traces
        assert validate_chrome_trace({"traceEvents": [{"ph": "X"}]}) != []
        assert validate_chrome_trace([]) != []


class TestWatchdog:
    def test_once_per_episode(self, caplog):
        wd = Watchdog()
        with caplog.at_level(logging.INFO, logger="vep.obs.watch"):
            assert wd.check("depth", 5, above=2) is True   # opens: WARNING
            assert wd.check("depth", 9, above=2) is True   # silent
            assert wd.check("depth", 1, above=2) is False  # closes: INFO
            assert wd.check("depth", 7, above=2) is True   # new episode
        warns = [r for r in caplog.records if r.levelno == logging.WARNING]
        infos = [r for r in caplog.records if r.levelno == logging.INFO]
        assert len(warns) == 2
        assert len(infos) == 1
        snap = wd.snapshot()
        assert snap["episodes"]["depth"] == 2
        assert snap["active"]["depth"]["peak"] == 7

    def test_below_direction_and_validation(self):
        wd = Watchdog()
        with pytest.raises(ValueError):
            wd.check("x", 1.0)
        with pytest.raises(ValueError):
            wd.check("x", 1.0, above=1.0, below=2.0)
        assert wd.check("occupancy", 10.0, below=25.0) is True
        assert wd.check("occupancy", 50.0, below=25.0) is False
        assert wd.snapshot()["episodes"]["occupancy"] == 1
        assert wd.active() == {}


# ---------------------------------------------------------------------------
# Engine satellite regressions (need the tiny models / CPU backend)
# ---------------------------------------------------------------------------


def _meta(w=32, h=32):
    from video_edge_ai_proxy_tpu.bus.interface import FrameMeta

    return FrameMeta(
        width=w, height=h, channels=3,
        timestamp_ms=int(time.time() * 1000), is_keyframe=True,
    )


def _publish(bus, device_id, w=32, h=32, value=128):
    frame = np.full((h, w, 3), value, np.uint8)
    return bus.publish(device_id, frame, _meta(w, h))


@pytest.fixture()
def bus():
    from video_edge_ai_proxy_tpu.bus.memory_bus import MemoryFrameBus

    b = MemoryFrameBus()
    yield b
    b.close()


def _engine(bus, model="tiny_mobilenet_v2"):
    from video_edge_ai_proxy_tpu.engine import InferenceEngine
    from video_edge_ai_proxy_tpu.uplink.queue import AnnotationQueue
    from video_edge_ai_proxy_tpu.utils.config import EngineConfig

    cfg = EngineConfig(model=model, batch_buckets=(1, 2, 4), tick_ms=5)
    eng = InferenceEngine(
        bus, cfg, annotations=AnnotationQueue(handler=lambda batch: True))
    eng.warmup()
    return eng


class TestEngineObsSatellites:
    def test_ema_zero_first_latency_does_not_reseed(self):
        """Regression: the old ``ema == 0.0`` sentinel re-seeded the EMA
        forever for a stream whose first latency measured a legitimate
        0.0 ms; the explicit flag blends from the second sample on."""
        from video_edge_ai_proxy_tpu.engine.runner import StreamStats

        st = StreamStats()
        st.note_latency(0.0)
        assert st.ema_initialized
        assert st.ema_latency_ms == 0.0
        st.note_latency(10.0)
        assert st.ema_latency_ms == pytest.approx(1.0)   # sentinel gave 10.0
        st.note_latency(10.0)
        assert st.ema_latency_ms == pytest.approx(1.9)

    def test_stats_returns_immutable_snapshots(self, bus):
        """Regression: stats() used to hand out the LIVE StreamStats
        objects the drain thread mutates — callers could read torn state
        or mutate engine internals through them."""
        from video_edge_ai_proxy_tpu.engine.runner import StreamStatsView

        bus.create_stream("cam1", 32 * 32 * 3)
        eng = _engine(bus)
        eng.start()
        try:
            deadline = time.time() + 30
            while not eng.stats().get("cam1") and time.time() < deadline:
                _publish(bus, "cam1")
                time.sleep(0.05)
        finally:
            eng.stop()
        view = eng.stats()["cam1"]
        assert isinstance(view, StreamStatsView)
        with pytest.raises(dataclasses.FrozenInstanceError):
            view.frames = 999
        # later engine-side mutation must not leak into an existing view
        live = eng._stats["cam1"]
        before = view.frames
        live.frames += 100
        assert view.frames == before
        assert eng.stats()["cam1"].frames == live.frames

    def test_engine_populates_registry_and_renders_clean(self, bus):
        from video_edge_ai_proxy_tpu.obs import registry

        bus.create_stream("cam1", 32 * 32 * 3)
        eng = _engine(bus)
        eng.start()
        try:
            deadline = time.time() + 30
            while not eng.stats().get("cam1") and time.time() < deadline:
                _publish(bus, "cam1")
                time.sleep(0.05)
        finally:
            eng.stop()
        fam = {f.name: f for f in registry.families()}
        assert fam["vep_engine_ticks_total"].value >= 1
        assert fam["vep_stream_frames_total"].labels("cam1").value >= 1
        assert fam["vep_stream_latency_ms"].labels("cam1").count >= 1
        text = registry.render()
        assert 'vep_stream_frames_total{stream="cam1"}' in text
        assert lint_exposition(text) == []

    def test_collector_counts_superseded_frames(self, bus):
        """Two frames published before one collect: latest wins, the
        cursor jump is accounted as a skipped frame."""
        from video_edge_ai_proxy_tpu.engine.collector import Collector
        from video_edge_ai_proxy_tpu.obs import registry

        fam = registry.counter(
            "vep_frames_skipped_total",
            "Frames superseded before read (latest-wins drops)", ("stream",))
        base = fam.labels("skipcam").value
        bus.create_stream("skipcam", 32 * 32 * 3)
        col = Collector(bus, buckets=(1, 2, 4))
        _publish(bus, "skipcam", value=1)
        col.collect()                      # seeds the cursor at seq 1
        for v in (2, 3, 4):
            _publish(bus, "skipcam", value=v)
        groups = col.collect()
        assert groups and groups[0].frames[0, 0, 0, 0] == 4
        assert fam.labels("skipcam").value == base + 2

    def test_engine_emits_sampled_lineage_spans(self, bus):
        """With tracing on and sample_every=1, a served frame leaves
        collect/submit/device/emit spans that fold into a breakdown."""
        from video_edge_ai_proxy_tpu.obs import tracer

        bus.create_stream("cam1", 32 * 32 * 3)
        eng = _engine(bus)
        prev = (tracer.enabled, tracer.sample_every)
        tracer.configure(enabled=True, sample_every=1)
        tracer.clear()
        eng.start()
        try:
            deadline = time.time() + 30
            while not eng.stats().get("cam1") and time.time() < deadline:
                _publish(bus, "cam1")
                time.sleep(0.05)
        finally:
            eng.stop()
            tracer.configure(enabled=prev[0], sample_every=prev[1])
        events = tracer.events("cam1")
        stages = {ev["stage"] for ev in events}
        assert {"collect", "submit", "device", "emit"} <= stages
        br = stage_breakdown(events)
        assert br["total"]["count"] >= 1
        assert br["device"]["count"] >= 1
        obj = to_chrome_trace(events)
        assert validate_chrome_trace(obj) == []
        tracer.clear()


# ---------------------------------------------------------------------------
# r9: device-performance attribution (obs/perf.py)
# ---------------------------------------------------------------------------


class _FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class TestPerfTracker:
    def test_compile_and_batch_attribution(self):
        from video_edge_ai_proxy_tpu.obs.perf import PerfTracker

        reg = Registry()
        clk = _FakeClock()
        perf = PerfTracker(registry=reg, peak_tflops=100.0, clock=clk)
        perf.note_compile("m", (96, 128), 4, 1.5, cost={"flops": 5e9})
        fam = {f.name: f for f in reg.families()}
        assert fam["vep_compile_seconds"].labels("m", "96x128", "4").count \
            == 1
        assert fam["vep_compile_programs_total"].labels(
            "m", "96x128", "4").value == 1
        for _ in range(20):
            clk.advance(0.01)
            perf.note_batch("m", (96, 128), 4, 10.0, 3)
        # 5 GFLOP / 10 ms = 0.5 TFLOP/s = 0.5% of the 100 TF peak.
        assert fam["vep_perf_mfu_pct"].labels("m", "4").value \
            == pytest.approx(0.5)
        assert fam["vep_perf_padded_slots_total"].labels("m", "4").value \
            == 20
        assert fam["vep_perf_batch_slots_total"].labels("m", "4").value \
            == 80
        assert fam["vep_perf_bucket_occupancy_pct"].labels("m", "4").value \
            == pytest.approx(75.0)
        assert perf.fps() > 0
        snap = perf.snapshot()
        json.dumps(snap)          # artifact sections must be JSON-able
        assert snap["compiles"][0]["programs"] == 1
        b = snap["buckets"][0]
        assert b["padded_slots"] == 20 and b["frames"] == 60
        assert b["mfu_pct"] == pytest.approx(0.5)
        assert lint_exposition(reg.render()) == []

    @pytest.mark.parametrize("kind,peak", [
        ("TPU v5 lite", 197.0), ("cpu", None), ("TPU v9 imaginary", None)])
    def test_mfu_needs_a_peaks_table_row_for_the_device(self, kind, peak):
        """One table keyed by device_kind: a device in it gets MFU against
        ITS peak; a device not in it gets achieved TFLOP/s and NO MFU —
        gauge unset, JSON null — never another chip's peak."""
        from video_edge_ai_proxy_tpu.obs.perf import PerfTracker

        reg = Registry()
        perf = PerfTracker(registry=reg, clock=_FakeClock())
        perf.set_device_kind(kind)
        perf.note_compile("m", (96, 128), 4, 1.5, cost={"flops": 1.97e12})
        perf.note_batch("m", (96, 128), 4, 10.0, 4)
        snap = perf.snapshot()
        text = reg.render()
        assert snap["peak_tflops"] == peak
        assert "vep_perf_achieved_tflops" in text
        if peak is None:
            assert snap["buckets"][0]["mfu_pct"] is None
            assert "vep_perf_mfu_pct" not in text
            assert "vep_perf_peak_tflops" not in text
        else:
            # 1.97 TFLOP in 10 ms = 197 TFLOP/s = 100% of a v5e
            assert snap["buckets"][0]["mfu_pct"] == pytest.approx(100.0)
            assert "vep_perf_mfu_pct" in text
        assert lint_exposition(text) == []

    def test_cost_summary_tolerates_api_shapes(self):
        from video_edge_ai_proxy_tpu.obs.perf import cost_summary

        class C:
            def __init__(self, rv):
                self.rv = rv

            def cost_analysis(self):
                if isinstance(self.rv, Exception):
                    raise self.rv
                return self.rv

        assert cost_summary(C({"flops": 2.0}))["flops"] == 2.0
        assert cost_summary(C([{"flops": 3.0}]))["flops"] == 3.0
        assert cost_summary(C([])) == {}
        assert cost_summary(C(None)) == {}
        assert cost_summary(C(RuntimeError("unsupported"))) == {}

    def test_mfu_pct_degenerate_inputs(self):
        from video_edge_ai_proxy_tpu.obs.perf import mfu_pct

        assert mfu_pct(0.0, 10.0, 100.0) is None
        assert mfu_pct(1e9, 0.0, 100.0) is None
        assert mfu_pct(1e9, 10.0, 0.0) is None
        # 1 TFLOP in 10 ms = 100 TF/s = 100% of a 100 TF peak.
        assert mfu_pct(1e12, 10.0, 100.0) == pytest.approx(100.0)


# ---------------------------------------------------------------------------
# r9: SLO burn-rate engine (obs/slo.py) under fake clocks
# ---------------------------------------------------------------------------


def _slo(clk, reg, *, objective=0.99, fire=10.0, warmup=0.0,
         fast=300.0, slow=3600.0):
    from video_edge_ai_proxy_tpu.obs.slo import BurnRateSLO, SLOSpec

    return BurnRateSLO(
        SLOSpec(name="t", objective=objective, fire_burn_rate=fire,
                warmup_s=warmup, fast_window_s=fast, slow_window_s=slow),
        clock=clk, registry=reg)


class TestSLOBurnRate:
    def test_fast_burn_fires_and_counts_one_episode(self):
        clk = _FakeClock()
        slo = _slo(clk, Registry())
        # 50% bad for 10 minutes: burn 0.5/0.01 = 50 on BOTH windows.
        for _ in range(60):
            clk.advance(10.0)
            slo.record(good=1, bad=1)
        state = slo.evaluate()
        assert state["burn"]["fast"] == pytest.approx(50.0)
        assert state["firing"] and state["episodes"] == 1
        # staying in burn does not open a second episode
        clk.advance(10.0)
        slo.record(good=1, bad=1)
        assert slo.evaluate()["episodes"] == 1

    def test_slow_burn_holds_fire(self):
        """A short spike trips the fast window only — no page (the whole
        point of requiring BOTH windows)."""
        clk = _FakeClock()
        slo = _slo(clk, Registry())
        # 55 minutes of clean traffic, then 4 minutes of 100% bad.
        for _ in range(330):
            clk.advance(10.0)
            slo.record(good=10)
        for _ in range(24):
            clk.advance(10.0)
            slo.record(bad=10)
        state = slo.evaluate()
        assert state["burn"]["fast"] > 10.0       # fast window saturated
        assert state["burn"]["slow"] < 10.0       # diluted by the hour
        assert not state["firing"]

    def test_recovery_closes_episode_on_fast_window(self):
        clk = _FakeClock()
        slo = _slo(clk, Registry())
        wd = Watchdog()
        for _ in range(60):
            clk.advance(10.0)
            slo.record(bad=1)
        assert slo.evaluate(wd)["firing"]
        assert "slo_burn:t" in wd.snapshot()["active"]
        # 6 minutes of clean traffic pushes the bad burst out of the
        # fast window; the slow window still remembers it.
        for _ in range(36):
            clk.advance(10.0)
            slo.record(good=1)
        state = slo.evaluate(wd)
        assert not state["firing"]
        assert state["burn"]["slow"] > 10.0
        assert state["episodes"] == 1
        assert "slo_burn:t" not in wd.snapshot()["active"]
        assert wd.snapshot()["episodes"]["slo_burn:t"] == 1

    def test_warmup_gates_firing(self):
        clk = _FakeClock()
        slo = _slo(clk, Registry(), warmup=120.0)
        for _ in range(6):
            clk.advance(10.0)
            slo.record(bad=5)
        assert not slo.evaluate()["firing"]       # 60 s < 120 s warmup
        for _ in range(7):
            clk.advance(10.0)
            slo.record(bad=5)
        assert slo.evaluate()["firing"]

    def test_empty_windows_report_none(self):
        clk = _FakeClock()
        slo = _slo(clk, Registry())
        state = slo.evaluate()
        assert state["burn"] == {"fast": None, "slow": None}
        assert not state["firing"]

    def test_engine_aggregates_and_snapshots(self):
        from video_edge_ai_proxy_tpu.obs.slo import SLOEngine, default_slos

        clk = _FakeClock()
        reg = Registry()
        eng = SLOEngine(default_slos(warmup_s=0.0), clock=clk,
                        registry=reg)
        assert eng.names() == ["aggregate_fps", "detect_latency_p50",
                               "stream_availability"]
        for _ in range(60):
            clk.advance(10.0)
            eng.record("aggregate_fps", bad=1)
            eng.record("detect_latency_p50", good=1)
        out = eng.evaluate()
        assert out["burning"]
        assert out["slos"]["aggregate_fps"]["firing"]
        assert not out["slos"]["detect_latency_p50"]["firing"]
        snap = eng.snapshot()
        json.dumps(snap)
        assert snap["burning"] and "aggregate_fps" in snap["slos"]
        assert lint_exposition(reg.render()) == []


# ---------------------------------------------------------------------------
# r9: engine integration — live attribution, REST surfaces, hot-path bound
# ---------------------------------------------------------------------------


class TestEnginePerfSLO:
    def _serve_one(self, bus, eng, device_id="cam1"):
        bus.create_stream(device_id, 32 * 32 * 3)
        eng.start()
        try:
            deadline = time.time() + 30
            while not eng.stats().get(device_id) and time.time() < deadline:
                _publish(bus, device_id)
                time.sleep(0.05)
        finally:
            eng.stop()
        assert eng.stats().get(device_id), "engine never served a frame"

    def test_engine_attributes_compile_and_batches(self, bus):
        from video_edge_ai_proxy_tpu.obs import registry

        eng = _engine(bus)
        self._serve_one(bus, eng)
        snap = eng.perf.snapshot()
        # The one serving program this run compiled is attributed with a
        # positive wall time; on the CPU backend XLA cost analysis also
        # yields FLOPs, so achieved TFLOP/s is live — but the CPU has no
        # row in the peaks table, so no MFU is published for it.
        assert snap["compiles"], "no compile recorded at the miss site"
        rec = snap["compiles"][0]
        assert rec["programs"] >= 1 and rec["compile_s"] > 0
        assert rec["geometry"] == "32x32"
        assert snap["buckets"] and snap["buckets"][0]["device_ms_ema"] > 0
        assert snap["fps"] > 0
        fam = {f.name: f for f in registry.families()}
        geo = (rec["model"], rec["geometry"], str(rec["bucket"]))
        assert fam["vep_compile_seconds"].labels(*geo).count >= 1
        text = registry.render()
        assert "vep_compile_seconds" in text
        assert "vep_perf_padded_slots_total" in text
        assert "vep_perf_achieved_tflops" in text
        assert snap["peak_tflops"] is None
        assert snap["buckets"][0]["mfu_pct"] is None
        assert snap["aot_fallbacks"] == 0
        assert lint_exposition(text) == []

    def test_stats_view_carries_device_attribution(self, bus):
        eng = _engine(bus)
        self._serve_one(bus, eng)
        view = eng.stats()["cam1"]
        assert view.bucket == view.last_batch >= 1
        assert view.padded_slots >= 0
        assert view.device_ms_ema > 0
        d = dataclasses.asdict(view)     # the /api/v1/stats wire shape
        assert {"bucket", "padded_slots", "device_ms_ema"} <= set(d)

    def test_rest_slo_endpoint_and_metrics_golden(self, bus):
        """Full REST surface over a served engine: /api/v1/slo returns
        per-SLO burn + episode state, /api/v1/stats carries the perf/slo
        obs sections and the new stream fields, and the complete
        /metrics exposition (engine + perf + slo families) lints clean."""
        import urllib.request

        from video_edge_ai_proxy_tpu.serve.rest_api import RestServer

        class _PM:
            def list(self):
                return []

        eng = _engine(bus)
        self._serve_one(bus, eng)
        srv = RestServer(_PM(), None, host="127.0.0.1", port=0, engine=eng)
        srv.start()
        try:
            rest = f"http://127.0.0.1:{srv.bound_port}"
            with urllib.request.urlopen(rest + "/api/v1/slo") as r:
                slo = json.loads(r.read())
            assert set(slo) == {"burning", "slos"}
            for state in slo["slos"].values():
                assert {"burn", "firing", "episodes", "objective",
                        "fire_burn_rate"} <= set(state)
            assert {"detect_latency_p50", "aggregate_fps",
                    "stream_availability"} == set(slo["slos"])
            with urllib.request.urlopen(rest + "/api/v1/stats") as r:
                stats = json.loads(r.read())
            cam = stats["engine"]["streams"]["cam1"]
            assert {"bucket", "padded_slots", "device_ms_ema"} <= set(cam)
            assert stats["obs"]["perf"]["compiles"]
            assert "slos" in stats["obs"]["slo"]
            with urllib.request.urlopen(rest + "/metrics") as r:
                text = r.read().decode()
            for fam in ("vep_perf_achieved_tflops",
                        "vep_perf_padded_slots_total",
                        "vep_compile_seconds", "vep_slo_burn_rate",
                        "vep_slo_firing"):
                assert fam in text, f"{fam} missing from /metrics"
            assert lint_exposition(text) == []
        finally:
            srv.stop()

    def test_slo_disabled_engine(self, bus):
        """engine.slo=False: no SLO objects, no ladder input, and the
        REST endpoint answers 400 instead of crashing."""
        from video_edge_ai_proxy_tpu.engine import InferenceEngine
        from video_edge_ai_proxy_tpu.utils.config import EngineConfig

        eng = InferenceEngine(bus, EngineConfig(
            model="tiny_mobilenet_v2", batch_buckets=(1, 2), tick_ms=5,
            slo=False))
        assert eng.slo is None
        from video_edge_ai_proxy_tpu.serve.rest_api import RestServer

        class _PM:
            def list(self):
                return []

        srv = RestServer(_PM(), None, host="127.0.0.1", port=0, engine=eng)
        srv.start()
        try:
            import urllib.error
            import urllib.request

            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.bound_port}/api/v1/slo")
            assert ei.value.code == 400
        finally:
            srv.stop()


class TestHotPathAllocationBound:
    def test_perf_slo_instrumentation_fixed_allocation(self):
        """r9 guard: with tracing off, the per-tick perf/SLO work
        (note_batch + SLO record + throttled evaluate) holds a FIXED
        memory footprint — automated successor to the r7 'within noise'
        one-off measurement. Warm 2k iterations populate every cache and
        ring; the next 2k must not grow traced allocations beyond a
        small bound."""
        import tracemalloc

        from video_edge_ai_proxy_tpu.obs.perf import PerfTracker
        from video_edge_ai_proxy_tpu.obs.slo import SLOEngine, default_slos

        reg = Registry()
        clk = _FakeClock()
        perf = PerfTracker(registry=reg, clock=clk)
        perf.note_compile("m", (96, 128), 4, 0.5, cost={"flops": 1e9})
        slo = SLOEngine(default_slos(warmup_s=0.0), clock=clk,
                        registry=reg)

        def tick():
            clk.advance(0.01)
            perf.note_batch("m", (96, 128), 4, 7.5, 3)
            slo.record("detect_latency_p50", good=1.0)
            slo.record("aggregate_fps", bad=1.0)
            slo.record("stream_availability", good=1.0)

        for _ in range(2000):
            tick()
        slo.evaluate()
        tracemalloc.start()
        try:
            base, _ = tracemalloc.get_traced_memory()
            for i in range(2000):
                tick()
                if i % 100 == 0:
                    slo.evaluate()
            now, _ = tracemalloc.get_traced_memory()
        finally:
            tracemalloc.stop()
        growth = now - base
        assert growth < 64 * 1024, (
            f"perf/SLO hot path grew {growth} B over 2000 ticks — "
            "per-tick allocations are no longer bounded")
