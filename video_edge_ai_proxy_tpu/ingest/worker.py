"""Per-camera ingest worker.

One worker process per camera — the reference runs one Docker container per
camera with three threads (demux -> decode -> archive,
``python/rtsp_to_rtmp.py:207-253``). Here the demux/decode pair collapses into
one capture loop (grab -> gated retrieve; the two-phase laziness lives in the
source, see ``sources.py``) and the archiver remains its own thread fed by a
queue — same pipeline shape, minus the cross-thread handshake the reference
got wrong (its ``query_timestamp`` global never crossed modules, SURVEY.md
§3.2; ours is an explicit read of the shared-memory control KV each packet,
exactly as the reference *intended* with its per-packet Redis HGETALL,
``rtsp_to_rtmp.py:117``).

Decode gating (reference semantics, ``rtsp_to_rtmp.py:141-153``,
``read_image.py:70-80``):
- keyframes always decode;
- non-keyframes decode only when a client queried within ``active_window``
  seconds (default 10, reference ``rtsp_to_rtmp.py:144-145``);
- keyframe-only mode (per-device KV flag) restricts decode to keyframes;
- with a packet source (the default), archive and RTMP pass-through consume
  *compressed* packets (stream copy, ``python/archive.py:75-100``,
  ``rtsp_to_rtmp.py:163-182``) and never touch the decode gate; on the
  OpenCV fallback they consume decoded frames and therefore force decode
  while enabled.

Failure semantics (reference ``rtsp_to_rtmp.py:61-79,186-187``): initial
connect failure exits nonzero so the supervisor restarts the worker
(restart-policy-always parity); mid-stream EOF loops forever re-opening the
source.
"""

from __future__ import annotations

import argparse
import json
import os
import threading
import time
from dataclasses import dataclass
from typing import Optional

from ..bus import FrameBus, FrameMeta, RingSlotTooSmall, open_bus
from ..obs import registry as obs_registry, trace_id_for, tracer
from ..utils.logging import get_logger, set_log_context
from .archive import GopSegment, PacketGopSegment, SegmentArchiver
from .sources import VideoSource, open_source

log = get_logger("ingest.worker")

# Heartbeats older than this are stale: a crashed worker must not report
# healthy off its last write. Single bar shared by every consumer
# (ListStreams, Info) via parse_fresh_status.
STATUS_FRESH_MS = 5000


def parse_fresh_status(raw, now_ms: int) -> dict:
    """Worker heartbeat JSON -> dict if parseable and fresh, else {}."""
    import json as _json

    if not raw:
        return {}
    try:
        hb = _json.loads(raw)
    except ValueError:
        return {}
    # Valid JSON that isn't an object ('null', a number, a list — corrupt
    # write or another app's key in a shared Redis db) must degrade to {},
    # not AttributeError every consumer.
    if not isinstance(hb, dict):
        return {}
    return hb if now_ms - hb.get("ts_ms", 0) < STATUS_FRESH_MS else {}


KEY_STATUS_PREFIX = "stream_status_"   # worker heartbeat (new; the reference
                                       # derives health from Docker inspect,
                                       # rtsp_process_manager.go:283-335)
RECONNECT_DELAY_S = 1.0
STATUS_INTERVAL_S = 1.0


@dataclass
class WorkerConfig:
    rtsp_endpoint: str
    device_id: str
    rtmp_endpoint: str = ""
    in_memory_buffer: int = 1
    disk_buffer_path: str = ""
    active_window_s: float = 10.0
    shm_dir: str = "/dev/shm/vep_tpu"
    bus_backend: str = "shm"
    redis_addr: str = "127.0.0.1:6379"
    redis_password: str = ""
    redis_db: int = 0
    max_frames: int = 0  # 0 = endless; tests set a bound
    # Flight recorder (replay/recorder.py): non-empty = write
    # <trace_dir>/<device_id>.vtrace capturing every published frame
    # (packet timing + pixels, or the pattern seed for synthetic sources)
    # for deterministic replay via replay://.
    trace_dir: str = ""

    @classmethod
    def from_env(cls) -> "WorkerConfig":
        """Env-var contract parity with the reference's server->worker
        interface (``services/rtsp_process_manager.go:96-104``,
        ``python/start.sh:8-12``)."""
        env = os.environ
        return cls(
            rtsp_endpoint=env.get("rtsp_endpoint", ""),
            device_id=env.get("device_id", ""),
            rtmp_endpoint=env.get("rtmp_endpoint", ""),
            in_memory_buffer=int(env.get("in_memory_buffer", "1") or 1),
            disk_buffer_path=env.get("disk_buffer_path", ""),
            shm_dir=env.get("vep_shm_dir", "/dev/shm/vep_tpu"),
            bus_backend=env.get("vep_bus_backend", "shm"),
            redis_addr=env.get("vep_redis_addr", "127.0.0.1:6379"),
            redis_password=env.get("vep_redis_password", ""),
            redis_db=int(env.get("vep_redis_db", "0") or 0),
            max_frames=int(env.get("vep_max_frames", "0") or 0),
            trace_dir=env.get("vep_trace_dir", ""),
        )


class IngestWorker:
    def __init__(
        self,
        cfg: WorkerConfig,
        bus: Optional[FrameBus] = None,
        source: Optional[VideoSource] = None,
    ):
        self.cfg = cfg
        self._owns_bus = bus is None
        self.bus = bus or open_bus(
            cfg.bus_backend, cfg.shm_dir, cfg.redis_addr,
            cfg.redis_password, cfg.redis_db,
        )
        try:
            self.source = source or open_source(cfg.rtsp_endpoint)
        except Exception:
            if self._owns_bus:
                self.bus.close()  # don't leak the live socket/mappings
            raise
        self._stop = threading.Event()
        self._packets = 0
        self._keyframes = 0
        self._decoded = 0
        self._published = 0
        self._last_status = 0.0
        self._fps_window: list[float] = []
        self._archiver: Optional[SegmentArchiver] = None
        self._gop_frames: list = []
        self._gop_start_ms = 0
        self._passthrough = None  # built in run() once source fps is known
        # Packet mode: source exposes compressed payloads, so archive and
        # pass-through are stream copies that never touch the decode gate.
        self._packet_mode = bool(getattr(self.source, "supports_packets", False))
        self._gop_packets: list = []
        self._gop_bytes = 0
        self._gop_info = None  # StreamInfo captured at GOP open
        self._gop_audio_info = None  # audio StreamInfo captured at GOP open
        self._audio_packets = 0
        self._recorder = None  # flight recorder (cfg.trace_dir), built in run()
        # Unified metrics: per-process registry (subprocess workers report
        # the same numbers through the status heartbeat; in-process workers
        # — replay cameras, tests — land directly in the scraped registry).
        dev = (cfg.device_id,)
        self._m_packets = obs_registry.counter(
            "vep_ingest_packets_total", "Video packets demuxed", ("stream",)
        ).labels(*dev)
        self._m_decoded = obs_registry.counter(
            "vep_ingest_decoded_total", "Frames decoded", ("stream",)
        ).labels(*dev)
        self._m_published = obs_registry.counter(
            "vep_ingest_published_total", "Frames published to the bus",
            ("stream",),
        ).labels(*dev)
        self._m_corrupt = obs_registry.counter(
            "vep_ingest_corrupt_total", "Corrupt packets flagged by demux",
            ("stream",),
        ).labels(*dev)
        self._m_reconnects = obs_registry.counter(
            "vep_ingest_reconnects_total", "Mid-stream EOF reconnect loops",
            ("stream",),
        ).labels(*dev)
        # Subprocess workers inherit tracing intent via env (the parent's
        # obs.tracer object does not cross the fork/exec boundary).
        if os.environ.get("VEP_OBS_TRACE"):
            tracer.configure(
                enabled=True,
                sample_every=int(
                    os.environ.get("VEP_OBS_SAMPLE_EVERY") or 16
                ),
            )

    # -- control-plane reads (per packet; shm KV, nanosecond-cheap) --

    def _client_active(self, now_ms: int) -> bool:
        last = self.bus.last_query_ms(self.cfg.device_id)
        return last is not None and (now_ms - last) < self.cfg.active_window_s * 1000

    def _should_decode(self, is_keyframe: bool, now_ms: int) -> bool:
        if not self._packet_mode:
            # OpenCV fallback: archive/relay consume decoded frames, so
            # they pin decoding on. Packet mode stream-copies instead.
            if self._archiver is not None:
                return True
            if self._passthrough is not None and self._passthrough.active:
                return True
        if is_keyframe:
            return True
        if self.bus.keyframe_only(self.cfg.device_id):
            return False
        return self._client_active(now_ms)

    # -- status heartbeat --

    def _publish_status(self, now: float, error: str = "", force: bool = False) -> None:
        if now - self._last_status < STATUS_INTERVAL_S and not (error or force):
            return
        self._last_status = now
        window = [t for t in self._fps_window if now - t < 5.0]
        self._fps_window = window
        status = {
            "pid": os.getpid(),
            "running": not self._stop.is_set(),
            "packets": self._packets,
            "audio_packets": self._audio_packets,
            "keyframes": self._keyframes,
            "decoded": self._decoded,
            "published": self._published,
            "fps": round(len(window) / 5.0, 2),
            "width": self.source.width,
            "height": self.source.height,
            # packet|opencv|synthetic — which media path this camera is
            # really on (opencv fabricates keyframes/pts; fleets need to
            # SEE that, VERDICT r2 weak #6).
            "source": getattr(self.source, "kind", ""),
            "error": error,
            "ts_ms": int(time.time() * 1000),  # epoch: readers check staleness
        }
        self.bus.kv_set(
            KEY_STATUS_PREFIX + self.cfg.device_id,
            json.dumps(status, separators=(",", ":")),
        )

    # -- archive plumbing --

    def _archive_frame(self, frame, meta: FrameMeta) -> None:
        if self._archiver is None or self._packet_mode:
            return
        if meta.is_keyframe and self._gop_frames:
            # Keyframe closes the previous GOP -> hand to archiver thread
            # (reference rtsp_to_rtmp.py:97-110).
            self._archiver.submit(
                GopSegment(
                    device_id=self.cfg.device_id,
                    start_ts_ms=self._gop_start_ms,
                    end_ts_ms=meta.timestamp_ms,
                    fps=self.source.fps or 30.0,
                    frames=self._gop_frames,
                )
            )
            self._gop_frames = []
        if meta.is_keyframe or self._gop_frames:
            if not self._gop_frames:
                self._gop_start_ms = meta.timestamp_ms
            self._gop_frames.append(frame)

    # Cap on a single buffered GOP (a camera that stops emitting keyframes
    # must not grow the buffer until OOM). On overflow the buffered prefix
    # — which starts at a keyframe, so it is decodable — is submitted as a
    # segment, and the GOP's remaining packets are skipped until the next
    # keyframe (the empty-buffer guard below does that naturally).
    MAX_GOP_BYTES = 64 << 20

    def _flush_gop_tail(self) -> None:
        """Submit the buffered (keyframe-headed, keyframe-unclosed) GOP —
        at EOF/reconnect/shutdown. Mixing packets from two demuxer
        instances in one segment would rebase across unrelated clocks."""
        if self._archiver is not None and self._gop_packets:
            self._archiver.submit(
                PacketGopSegment(
                    device_id=self.cfg.device_id,
                    start_ts_ms=self._gop_start_ms,
                    info=self._gop_info,
                    packets=self._gop_packets,
                    audio_info=self._gop_audio_info,
                )
            )
        self._gop_packets = []

    def _archive_packet(self, pkt, is_keyframe: bool, now_ms: int) -> None:
        """Compressed-GOP archiving (packet mode): a VIDEO keyframe closes
        the previous GOP and opens a new one — same grouping as the
        reference's demux loop (rtsp_to_rtmp.py:97-110), but with real
        packets. Audio packets (camera mic) interleave into whatever GOP
        is open (``is_keyframe=False`` for them: AAC KEY flags are not GOP
        heads) and mux into the segment's audio track
        (reference archive.py:78-96)."""
        if self._archiver is None:
            return
        if self._gop_packets and (
            is_keyframe
            or self._gop_bytes + len(pkt.data) > self.MAX_GOP_BYTES
        ):
            self._flush_gop_tail()
        if is_keyframe or self._gop_packets:
            if not self._gop_packets:
                self._gop_start_ms = now_ms
                self._gop_bytes = 0
                # Captured at GOP open: the source may be closed (EOF) or
                # re-opened with new params by the time the GOP is flushed.
                self._gop_info = self.source.stream_info
                self._gop_audio_info = getattr(
                    self.source, "audio_info", None)
            self._gop_packets.append(pkt)
            self._gop_bytes += len(pkt.data)

    # -- RTMP pass-through (reference §3.4: toggle + buffered-GOP flush) --

    def _maybe_passthrough(self) -> None:
        if self._passthrough is None:
            return
        self._passthrough.set_active(self.bus.proxy_rtmp(self.cfg.device_id))

    # -- main loop --

    def run(self) -> None:
        cfg = self.cfg
        set_log_context(stream=cfg.device_id)
        try:
            self.source.open()
        except ConnectionError as exc:
            # Exit hard: supervisor restart-policy takes over (reference
            # rtsp_to_rtmp.py:76-78 + RestartPolicy always).
            log.error("initial connect failed for %s: %s", cfg.device_id, exc)
            self._publish_status(time.monotonic(), error=str(exc))
            raise SystemExit(2)

        frame_bytes = max(
            self.source.width * self.source.height * 3, 1920 * 1080 * 3
        )
        self.bus.create_stream(
            cfg.device_id, frame_bytes, slots=max(2, cfg.in_memory_buffer + 1)
        )
        if cfg.trace_dir:
            # Flight recorder (replay/): one trace per camera, opened once
            # geometry is known. Lazy import keeps live-camera workers free
            # of the replay plane.
            from ..replay.recorder import TraceRecorder

            os.makedirs(cfg.trace_dir, exist_ok=True)
            self._recorder = TraceRecorder(
                os.path.join(cfg.trace_dir, f"{cfg.device_id}.vtrace"))
            self._recorder.record_stream(
                cfg.device_id,
                width=self.source.width, height=self.source.height,
                fps=self.source.fps, gop=getattr(self.source, "gop", 0),
                kind=getattr(self.source, "kind", ""),
            )
        if cfg.disk_buffer_path:
            self._archiver = SegmentArchiver(cfg.disk_buffer_path)
            self._archiver.start()
        if cfg.rtmp_endpoint:
            if self._packet_mode:
                from .passthrough import PacketPassthroughWriter

                self._passthrough = PacketPassthroughWriter(
                    cfg.rtmp_endpoint, self.source.stream_info,
                    audio_info=getattr(self.source, "audio_info", None),
                )
            else:
                from .passthrough import PassthroughWriter

                self._passthrough = PassthroughWriter(
                    cfg.rtmp_endpoint, fps=self.source.fps or 30.0
                )
        log.info(
            "ingest worker up: device=%s source=%s %dx%d@%.1ffps",
            cfg.device_id, cfg.rtsp_endpoint,
            self.source.width, self.source.height, self.source.fps,
        )

        try:
            while not self._stop.is_set():
                pkt = self.source.grab()
                if pkt is None:
                    if cfg.max_frames and self._packets >= cfg.max_frames:
                        break
                    # Mid-stream EOF: wait for the camera to come back,
                    # forever (reference rtsp_to_rtmp.py:186-187).
                    log.warning(
                        "stream %s EOF/gone; reconnecting in %.0fs",
                        cfg.device_id, RECONNECT_DELAY_S,
                    )
                    self._m_reconnects.inc()
                    # The buffered GOP is a valid keyframe-headed prefix of
                    # the dying stream; archive it now — the re-opened
                    # demuxer has a fresh clock (and possibly fresh codec
                    # params) that must not be mixed into this segment.
                    self._flush_gop_tail()
                    self.source.close()
                    if self._stop.wait(RECONNECT_DELAY_S):
                        break
                    try:
                        self.source.open()
                        if self._packet_mode and self._passthrough is not None:
                            # Fresh demuxer: new clock, possibly new codec
                            # params. Stale GOP buffer and mux must go; an
                            # operator-requested relay resumes on the new
                            # stream's next keyframe.
                            self._passthrough.reset(
                                self.source.stream_info,
                                getattr(self.source, "audio_info", None),
                            )
                    except ConnectionError:
                        pass
                    continue

                if getattr(pkt, "is_audio", False):
                    # Camera-mic packet: carry through to the stream-copy
                    # consumers (archive audio track + RTMP relay —
                    # reference rtsp_to_rtmp.py:170-180, archive.py:78-96)
                    # and nothing else: no decode, no frame publish, no
                    # keyframe/fps accounting.
                    self._audio_packets += 1
                    self._maybe_passthrough()
                    if self._packet_mode and (
                        self._archiver is not None
                        or self._passthrough is not None
                    ):
                        full = self.source.packet_with_data()
                        if self._passthrough is not None:
                            self._passthrough.feed(full)
                        self._archive_packet(
                            full, False, pkt.timestamp_ms)
                    self._publish_status(time.monotonic())
                    if cfg.max_frames and self._packets >= cfg.max_frames:
                        break
                    continue

                self._packets += 1
                self._m_packets.inc()
                # Log correlation (utils/logging.py): every record logged
                # while this packet is handled — decode, archive, publish,
                # ring growth — carries stream=<id> seq=<packet>. The
                # worker thread is dedicated to this stream, so the
                # context is overwritten per packet, never reset.
                set_log_context(stream=cfg.device_id, seq=pkt.packet)
                if pkt.is_corrupt:
                    self._m_corrupt.inc()
                if pkt.is_keyframe:
                    self._keyframes += 1
                now_ms = pkt.timestamp_ms
                self._maybe_passthrough()

                if self._packet_mode and (
                    self._archiver is not None or self._passthrough is not None
                ):
                    # Compressed consumers ride the demux path: one payload
                    # memcpy, zero codec work, decode gate untouched.
                    full = self.source.packet_with_data()
                    if self._passthrough is not None:
                        self._passthrough.feed(full)
                    self._archive_packet(full, pkt.is_keyframe, now_ms)

                if self._should_decode(pkt.is_keyframe, now_ms):
                    frame = self.source.retrieve()
                    if frame is None:
                        continue
                    self._decoded += 1
                    self._m_decoded.inc()
                    frame_type = (
                        getattr(self.source, "last_frame_type", "")
                        or ("I" if pkt.is_keyframe else "P")
                    )
                    # Under decoder delay the frame lags the grabbed packet;
                    # publish the FRAME's presentation time (reference fills
                    # VideoFrame from the frame, read_image.py:99-117).
                    frame_pts = getattr(self.source, "last_frame_pts", None)
                    if frame_pts is None:
                        frame_pts = pkt.pts
                    meta = FrameMeta(
                        width=frame.shape[1],
                        height=frame.shape[0],
                        channels=frame.shape[2] if frame.ndim == 3 else 1,
                        timestamp_ms=now_ms,
                        # VideoFrame proto pts/dts are int64; a source
                        # that supplied none (AV_NOPTS -> None) ships 0,
                        # matching libav's own "unknown" downgrade.
                        pts=frame_pts if frame_pts is not None else 0,
                        dts=pkt.dts if pkt.dts is not None else 0,
                        packet=pkt.packet,
                        keyframe_cnt=self._keyframes,
                        is_keyframe=pkt.is_keyframe,
                        is_corrupt=pkt.is_corrupt,
                        frame_type=frame_type,
                        time_base=pkt.time_base,
                        # Cross-process lineage origin: deterministic id
                        # (replay-stable) stamped once here and carried by
                        # the bus + echoed in every serve response.
                        trace_id=trace_id_for(cfg.device_id, pkt.packet),
                    )
                    try:
                        self.bus.publish(cfg.device_id, frame, meta)
                    except RingSlotTooSmall:
                        # The source under-reported its
                        # resolution at open (OpenCV backends may say 0x0) or
                        # the camera switched to a larger mode mid-stream.
                        # The worker owns the ring, so grow it in place
                        # rather than dying into a restart loop that would
                        # re-create the same undersized ring.
                        log.warning(
                            "ring slot too small for %s (%d B); recreating",
                            cfg.device_id, frame.nbytes,
                        )
                        self.bus.create_stream(
                            cfg.device_id, frame.nbytes,
                            slots=max(2, cfg.in_memory_buffer + 1),
                        )
                        self.bus.publish(cfg.device_id, frame, meta)
                    self._published += 1
                    self._m_published.inc()
                    if tracer.sampled(meta.packet):
                        # Lineage origin: frame id (the packet number) is
                        # stamped here and flows unchanged to result emit.
                        tracer.record(cfg.device_id, "publish", meta.packet,
                                      trace_id=meta.trace_id)
                    if self._recorder is not None:
                        # Record what was published: synthetic frames are
                        # fully determined by (w, h, n), so the trace keeps
                        # the seed, not the pixels.
                        synth = None
                        if getattr(self.source, "kind", "") == "synthetic":
                            synth = {"w": frame.shape[1],
                                     "h": frame.shape[0], "n": pkt.packet}
                        self._recorder.record_frame(
                            cfg.device_id, frame, meta, synth=synth)
                    self._fps_window.append(time.monotonic())
                    self._archive_frame(frame, meta)
                    if self._passthrough is not None and not self._packet_mode:
                        self._passthrough.buffer(frame, meta.is_keyframe)
                        self._passthrough.relay(frame)

                self._publish_status(time.monotonic())
                if cfg.max_frames and self._packets >= cfg.max_frames:
                    break
        finally:
            # Every teardown step runs even when an earlier one raises (a
            # dead bus makes the status publish the likeliest raiser; it
            # must not cost the trailing-GOP flush or leak the demuxer).
            def _safe(what, fn):
                try:
                    fn()
                except Exception:
                    log.exception("worker teardown: %s failed", what)

            _safe("status", lambda: self._publish_status(
                time.monotonic(), force=True))
            if self._archiver is not None:
                # Flush the trailing (keyframe-unclosed) GOP — dropping it
                # would lose the tail (the reference loses it; deliberate
                # divergence).
                _safe("gop flush", self._flush_gop_tail)
                _safe("archiver", self._archiver.stop)
            if self._passthrough is not None:
                _safe("passthrough", self._passthrough.close)
            if self._recorder is not None:
                _safe("trace recorder", self._recorder.close)
            _safe("source", self.source.close)
            log.info(
                "ingest worker down: device=%s packets=%d decoded=%d",
                cfg.device_id, self._packets, self._decoded,
            )
            if self._owns_bus:
                # A redis-backed bus holds a live socket; injected buses
                # (tests, embedded use) belong to the caller.
                _safe("bus", self.bus.close)

    def stop(self) -> None:
        self._stop.set()


def main(argv: Optional[list[str]] = None) -> None:
    """CLI entrypoint; flags mirror the reference's ``start.sh:27-43`` argv
    translation, and every flag falls back to the env-var contract."""
    env_cfg = WorkerConfig.from_env()
    p = argparse.ArgumentParser(description="per-camera ingest worker")
    p.add_argument("--rtsp", default=env_cfg.rtsp_endpoint)
    p.add_argument("--device_id", default=env_cfg.device_id)
    p.add_argument("--rtmp", default=env_cfg.rtmp_endpoint)
    p.add_argument("--memory_buffer", type=int, default=env_cfg.in_memory_buffer)
    p.add_argument("--disk_buffer_path", default=env_cfg.disk_buffer_path)
    p.add_argument("--shm_dir", default=env_cfg.shm_dir)
    p.add_argument("--bus_backend", default=env_cfg.bus_backend)
    p.add_argument("--redis_addr", default=env_cfg.redis_addr)
    # No --redis_password flag: argv is world-readable via /proc; the
    # credential travels ONLY through the env contract (vep_redis_password),
    # like the reference's env-var spawn interface.
    p.add_argument("--redis_db", type=int, default=env_cfg.redis_db)
    p.add_argument("--max_frames", type=int, default=env_cfg.max_frames)
    p.add_argument("--trace_dir", default=env_cfg.trace_dir,
                   help="flight-recorder output dir (replay/)")
    args = p.parse_args(argv)
    if not args.rtsp or not args.device_id:
        p.error("--rtsp and --device_id are required (or env contract)")
    cfg = WorkerConfig(
        rtsp_endpoint=args.rtsp,
        device_id=args.device_id,
        rtmp_endpoint=args.rtmp,
        in_memory_buffer=args.memory_buffer,
        disk_buffer_path=args.disk_buffer_path,
        shm_dir=args.shm_dir,
        bus_backend=args.bus_backend,
        redis_addr=args.redis_addr,
        redis_password=env_cfg.redis_password,  # env-only (see above)
        redis_db=args.redis_db,
        max_frames=args.max_frames,
        trace_dir=args.trace_dir,
    )
    worker = IngestWorker(cfg)

    import signal

    def _sig(_s, _f):
        worker.stop()

    signal.signal(signal.SIGTERM, _sig)
    signal.signal(signal.SIGINT, _sig)
    worker.run()


if __name__ == "__main__":
    main()
