import os
import time

import numpy as np

from video_edge_ai_proxy_tpu.bus import MemoryFrameBus
from video_edge_ai_proxy_tpu.ingest import (
    GopSegment,
    IngestWorker,
    SegmentArchiver,
    SyntheticSource,
    WorkerConfig,
    open_source,
)


def unpaced(url_extra: str = "") -> str:
    return "test://pattern?w=64&h=48&fps=30&gop=5&pace=0" + url_extra


class TestSyntheticSource:
    def test_grab_retrieve(self):
        src = open_source(unpaced("&frames=12"))
        assert isinstance(src, SyntheticSource)
        src.open()
        packets, frames = [], []
        while (pkt := src.grab()) is not None:
            packets.append(pkt)
            frames.append(src.retrieve())
        assert len(packets) == 12
        assert [p.is_keyframe for p in packets[:6]] == [
            True, False, False, False, False, True,
        ]
        assert frames[0].shape == (48, 64, 3) and frames[0].dtype == np.uint8
        # Deterministic but moving content.
        assert not np.array_equal(frames[0], frames[1])

    def test_pts_monotonic(self):
        src = SyntheticSource(unpaced("&frames=5"))
        src.open()
        pts = [src.grab().pts for _ in range(5)]
        assert pts == sorted(pts) and len(set(pts)) == 5


def run_worker(bus, *, frames=20, query=False, keyframe_only=False):
    cfg = WorkerConfig(
        rtsp_endpoint=unpaced(f"&frames={frames}"),
        device_id="cam1",
        bus_backend="memory",
        max_frames=frames,
    )
    worker = IngestWorker(cfg, bus=bus)
    if query:
        bus.touch_query("cam1")
    if keyframe_only:
        bus.set_keyframe_only("cam1", True)
    worker.run()
    return worker


class TestDecodeGating:
    """Reference lazy-decode semantics (rtsp_to_rtmp.py:141-153,
    read_image.py:70-80): keyframes always; the rest only on fresh query."""

    def test_idle_decodes_keyframes_only(self):
        bus = MemoryFrameBus()
        w = run_worker(bus, frames=20)
        assert w._keyframes == 4  # gop=5 over 20 frames
        assert w._decoded == w._keyframes

    def test_fresh_query_decodes_everything(self):
        bus = MemoryFrameBus()
        w = run_worker(bus, frames=20, query=True)
        assert w._decoded == 20

    def test_keyframe_only_mode_wins_over_query(self):
        bus = MemoryFrameBus()
        w = run_worker(bus, frames=20, query=True, keyframe_only=True)
        assert w._decoded == w._keyframes

    def test_stale_query_back_to_keyframes(self):
        bus = MemoryFrameBus()
        bus.touch_query("cam1", now_ms=int(time.time() * 1000) - 60_000)
        w = run_worker(bus, frames=20)
        assert w._decoded == w._keyframes

    def test_published_frames_on_bus(self):
        bus = MemoryFrameBus()
        run_worker(bus, frames=20, query=True)
        frame = bus.read_latest("cam1")
        assert frame is not None
        assert frame.data.shape == (48, 64, 3)
        assert frame.meta.packet == 19

    def test_status_heartbeat(self):
        bus = MemoryFrameBus()
        run_worker(bus, frames=20)
        import json

        hb = json.loads(bus.kv_get("stream_status_cam1"))
        assert hb["packets"] == 20 and hb["pid"] > 0


class TestArchiver:
    def test_segment_naming_contract(self, tmp_path):
        # "<start_ts_ms>_<duration_ms>" naming (reference archive.py:75).
        arch = SegmentArchiver(str(tmp_path))
        arch.start()
        frames = [np.zeros((32, 32, 3), np.uint8) for _ in range(5)]
        arch.submit(GopSegment("camA", 1000, 1500, 30.0, frames))
        arch.stop()
        files = list((tmp_path / "camA").iterdir())
        assert len(files) == 1
        assert files[0].name.startswith("1000_500.")

    def test_duration_fallback_from_fps(self, tmp_path):
        # Zero timestamp span -> frames/fps fallback (reference
        # archive.py:45-72 dts-span fallback).
        seg = GopSegment("c", 0, 0, 10.0, [np.zeros((8, 8, 3), np.uint8)] * 20)
        assert seg.duration_ms == 2000

    def test_worker_archives_gops(self, tmp_path):
        bus = MemoryFrameBus()
        cfg = WorkerConfig(
            rtsp_endpoint=unpaced("&frames=20"),
            device_id="cam1",
            bus_backend="memory",
            disk_buffer_path=str(tmp_path),
            max_frames=20,
        )
        w = IngestWorker(cfg, bus=bus)
        w.run()
        # Archiving forces full decode.
        assert w._decoded == 20
        segs = list((tmp_path / "cam1").iterdir())
        assert len(segs) >= 3  # 4 keyframes -> 3 closed GOPs


class TestPassthrough:
    def test_writer_flushes_gop_on_activation(self, tmp_path):
        from video_edge_ai_proxy_tpu.ingest.passthrough import PassthroughWriter

        sink = str(tmp_path / "out" / "relay.mp4")
        w = PassthroughWriter(sink, fps=10.0)
        frames = [np.full((32, 32, 3), i, np.uint8) for i in range(6)]
        w.buffer(frames[0], True)       # GOP head
        for f in frames[1:3]:
            w.buffer(f, False)
        w.set_active(True)              # must flush the 3 buffered frames
        assert w.written == 3
        for f in frames[3:]:
            w.relay(f)
        w.set_active(False)
        assert w.written == 6
        assert os.path.getsize(sink) > 0

    def test_keyframe_resets_buffer(self):
        from video_edge_ai_proxy_tpu.ingest.passthrough import PassthroughWriter

        w = PassthroughWriter("/tmp/never-opened.mp4")
        for i in range(5):
            w.buffer(np.zeros((8, 8, 3), np.uint8), i % 2 == 0)
        assert len(w._gop) == 1 + (5 - 1) % 2  # last keyframe + trailing

    def test_worker_relays_when_proxy_flag_set(self, tmp_path):
        bus = MemoryFrameBus()
        sink = str(tmp_path / "relay.mp4")
        cfg = WorkerConfig(
            device_id="cam1",
            rtsp_endpoint="test://pattern?w=32&h=32&fps=30&gop=5",
            rtmp_endpoint=sink,
            max_frames=25,
        )
        bus.set_proxy_rtmp("cam1", True)   # toggle already on at start
        worker = IngestWorker(cfg, bus=bus)
        worker.run()
        assert worker._passthrough is not None
        assert worker._passthrough.written > 0
        assert os.path.exists(sink) and os.path.getsize(sink) > 0
        bus.close()


def test_worker_entry_is_jax_free():
    """The chip belongs to ONE process — the server, whose engine holds
    it. Ingest workers are separate processes, N per server: one that
    imported jax on a TPU host would reach for the chip and fail or hang.
    Import the worker entry (and open the synthetic source the smokes
    use) in a fresh interpreter and check jax never loaded."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "import video_edge_ai_proxy_tpu.ingest.worker as w\n"
        "from video_edge_ai_proxy_tpu.ingest import open_source\n"
        "src = open_source('test://pattern?w=64&h=48&fps=30&pace=0&frames=2')\n"
        "src.open(); assert src.grab() is not None\n"
        "assert callable(w.main)\n"
        "bad = [m for m in ('jax', 'jaxlib', 'flax') if m in sys.modules]\n"
        "assert not bad, bad\n"
    )
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=root, capture_output=True,
        text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
