"""Live device-performance attribution: compile cost, padding waste, MFU.

The reference proxy has no notion of device efficiency at all — its per-
stream in/out frame counters (reference grpcapi.go:141 stats loop) say
*whether* frames flow, never *how well the accelerator is used*. On a TPU
the three quantities that decide "as fast as the hardware allows" are
(a) what each compiled program costs (XLA cost analysis: FLOPs/bytes),
(b) how long the device actually spends per batch, and (c) how many batch
slots carry zero-padding instead of real frames (``pad_to_bucket``,
engine/collector.py:45). Until r9 those existed only offline
(tools/profile_mfu.py artifacts like ``MFU_vit_r05.json``); this module
is the *live* counterpart feeding the r7 registry (obs/metrics.py) so
``/metrics`` and ``/api/v1/stats`` show, per model+bucket: device ms,
achieved TFLOPs vs the device's peak, and % slots wasted to padding
(MOSAIC / arxiv 2305.03222: spatial multiplexing lives or dies on
continuous accelerator-utilization accounting).

Design notes:

- **jax-free at import.** ``cost_summary`` takes an already-compiled XLA
  executable object duck-typed (``.cost_analysis()``), so the control
  plane imports this without initializing a backend (CLAUDE.md rule).
- **Fixed-allocation hot path.** ``note_batch`` runs per device batch on
  the drain thread: child metric handles and EMA cells are cached per
  (model, bucket) key — after the first batch of a key, the call makes no
  new long-lived objects (guarded by the tier-1 allocation-bound test in
  tests/test_obs.py).
- **Live MFU is a proxy, not a profile.** ``device_ms`` as measured by
  the engine (runner.py `_emit`) runs from submit to host fetch and so
  includes drain-queue wait; the gauge trends with true MFU but is not a
  tracing profile. Its denominator comes from the peaks table below by
  ``device_kind``; a device without a row exports no MFU at all.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Deque, Dict, Optional, Tuple

from . import metrics

# Dense bf16 peak of ONE chip in TFLOP/s, keyed by the ``device_kind`` jax
# reports. Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s
# bf16 per chip; jax names the chip "TPU v5 lite"). The one table for the
# live gauges, bench.py and tools/profile_mfu.py: a device that is not in
# it has no MFU — unset in the server, an error in a bench script — never
# another chip's peak.
PEAK_TFLOPS_BY_DEVICE_KIND = {
    "TPU v5 lite": 197.0,
    "TPU v5e": 197.0,
}


def peak_tflops_for(device_kind: str) -> Optional[float]:
    """Table lookup; None for a device without a published row."""
    return PEAK_TFLOPS_BY_DEVICE_KIND.get(device_kind)


def require_peak_tflops(device_kind: str) -> float:
    """The bench scripts' form of the lookup: no row, no number."""
    peak = peak_tflops_for(device_kind)
    if peak is None:
        raise SystemExit(
            f"no peak TFLOP/s known for device_kind {device_kind!r} "
            f"(obs/perf.py PEAK_TFLOPS_BY_DEVICE_KIND has "
            f"{sorted(PEAK_TFLOPS_BY_DEVICE_KIND)}); an MFU against "
            "another chip's peak is not reported")
    return peak


def cost_summary(compiled) -> dict:
    """FLOPs/bytes from an XLA compiled executable's ``cost_analysis()``.

    Same shape-tolerance as tools/profile_mfu.py: jax versions return a
    dict, a list of dicts, or raise on backends without cost analysis —
    normalize all of that to a plain {"flops": .., "bytes_accessed": ..}
    dict, empty when unavailable (callers treat missing FLOPs as
    "MFU unknown", never as an error).
    """
    try:
        cost = compiled.cost_analysis()
    except Exception:
        return {}
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    if not isinstance(cost, dict):
        return {}
    out: dict = {}
    flops = float(cost.get("flops", 0.0) or 0.0)
    nbytes = float(cost.get("bytes accessed", 0.0) or 0.0)
    if flops > 0.0:
        out["flops"] = flops
    if nbytes > 0.0:
        out["bytes_accessed"] = nbytes
    return out


def memory_summary(compiled) -> dict:
    """Device-memory footprint from an XLA compiled executable's
    ``memory_analysis()`` — the byte-side sibling of :func:`cost_summary`
    feeding the r21 HBM plane (obs/hbm.py).

    Duck-typed with the same tolerance: backends without memory analysis
    (or older jax returning None) normalize to ``{}`` — callers treat a
    missing footprint as "memory unknown", never as an error. Keys when
    available: ``argument_bytes``, ``output_bytes``, ``temp_bytes``,
    ``code_bytes`` (generated executable), ``alias_bytes`` (donated-
    argument aliasing — bytes the output shares with donated inputs).
    """
    try:
        mem = compiled.memory_analysis()
    except Exception:
        return {}
    if mem is None:
        return {}
    out: dict = {}
    for key, attr in (
        ("argument_bytes", "argument_size_in_bytes"),
        ("output_bytes", "output_size_in_bytes"),
        ("temp_bytes", "temp_size_in_bytes"),
        ("code_bytes", "generated_code_size_in_bytes"),
        ("alias_bytes", "alias_size_in_bytes"),
    ):
        try:
            val = getattr(mem, attr)
        except Exception:
            continue
        if val is None:
            continue
        try:
            out[key] = int(val)
        except (TypeError, ValueError):
            continue
    return out


def mfu_pct(flops: float, device_ms: float,
            peak_tflops: Optional[float]) -> Optional[float]:
    """Model FLOPs utilization: achieved FLOP/s over peak, percent.
    None when any input is unknown/degenerate rather than a fake 0."""
    if flops <= 0.0 or device_ms <= 0.0 or (peak_tflops or 0.0) <= 0.0:
        return None
    achieved = flops / (device_ms * 1e-3)
    return 100.0 * achieved / (peak_tflops * 1e12)


class _RateWindow:
    """Sliding-window event rate over a bounded deque of (t, n) samples.

    Memory is bounded by ``maxlen``; expired entries are popped on every
    add, so steady state neither grows nor shrinks — the allocation-bound
    test measures across this. One sample per device batch (not per
    frame), so 4096 slots cover >40 s even at 100 batches/s.
    """

    __slots__ = ("_window_s", "_samples", "_total")

    def __init__(self, window_s: float = 10.0, maxlen: int = 4096):
        self._window_s = float(window_s)
        self._samples: Deque[Tuple[float, float]] = collections.deque(
            maxlen=maxlen)
        self._total = 0.0

    def add(self, n: float, now: float) -> None:
        if len(self._samples) == self._samples.maxlen:
            self._total -= self._samples[0][1]   # about to be evicted
        self._samples.append((now, float(n)))
        self._total += n
        self._expire(now)

    def _expire(self, now: float) -> None:
        cutoff = now - self._window_s
        s = self._samples
        while s and s[0][0] < cutoff:
            self._total -= s.popleft()[1]

    def rate(self, now: float) -> float:
        """Events/second over the window (0.0 when empty)."""
        self._expire(now)
        if not self._samples:
            return 0.0
        span = max(now - self._samples[0][0], 1e-6)
        # Use the real elapsed span, capped at the window, so the rate is
        # meaningful immediately after start instead of diluted by the
        # not-yet-elapsed window remainder.
        return self._total / min(max(span, 0.5), self._window_s)


class _H2DCell:
    """Per-(model, bucket) host->device transfer accounting: pre-resolved
    counter children + running totals, same fixed-allocation discipline
    as :class:`_BatchCell` (``note_h2d`` runs once per dispatched batch
    — on the engine tick thread, which with the prefetch stage enabled
    just relays the numbers the transfer thread measured)."""

    __slots__ = ("bytes_child", "seconds_child", "hidden_child", "bytes",
                 "seconds", "hidden_s", "batches", "slots")

    def __init__(self, bytes_child, seconds_child, hidden_child):
        self.bytes_child = bytes_child
        self.seconds_child = seconds_child
        self.hidden_child = hidden_child
        self.bytes = 0
        self.seconds = 0.0
        self.hidden_s = 0.0
        self.batches = 0
        self.slots = 0


class _ShardCell:
    """Per-(model, bucket, shard) mesh-serving attribution: pre-resolved
    counter children + running totals (same fixed-allocation discipline
    as :class:`_BatchCell`; these are NEW label families so the existing
    aggregate series keep their label tuples)."""

    __slots__ = ("frames_child", "busy_child", "frames", "busy_ms")

    def __init__(self, frames_child, busy_child):
        self.frames_child = frames_child
        self.busy_child = busy_child
        self.frames = 0
        self.busy_ms = 0.0


class _BatchCell:
    """Per-(model, geometry, bucket) hot-path state: pre-resolved metric
    children + EMA accumulator, so ``note_batch`` is lookups and float
    math after the first batch of a key."""

    __slots__ = ("device", "padded", "slots", "occupancy", "mfu", "tflops",
                 "ema_ms", "ema_init", "frames", "padded_total")

    def __init__(self, device, padded, slots, occupancy, mfu, tflops):
        self.device = device
        self.padded = padded
        self.slots = slots
        self.occupancy = occupancy
        self.mfu = mfu
        self.tflops = tflops
        self.ema_ms = 0.0
        self.ema_init = False
        self.frames = 0
        self.padded_total = 0


class PerfTracker:
    """Per-engine device-performance attribution feeding the registry.

    ``note_compile`` runs at every step-cache miss (engine/runner.py
    ``_step``): compile wall time + XLA cost analysis keyed by
    (model, geometry, bucket). ``note_batch`` runs per drained device
    batch: device-time histogram, padded-slot waste, occupancy, and the
    derived live MFU / achieved-TFLOPs / aggregate-fps gauges
    (``vep_perf_*`` + ``vep_compile_*`` families).
    """

    def __init__(self, *, peak_tflops: Optional[float] = None,
                 registry: Optional[metrics.Registry] = None,
                 clock=time.monotonic, fps_window_s: float = 10.0):
        reg = registry if registry is not None else metrics.registry
        # None until the device is known (``set_device_kind`` at engine
        # warmup) and for a device outside the peaks table: MFU stays
        # unset then, while achieved TFLOP/s is still reported.
        self.peak_tflops: Optional[float] = None
        self._clock = clock
        self._lock = threading.Lock()
        # (model, geometry, bucket) -> compile record
        self._compiles: Dict[Tuple[str, str, int], dict] = {}
        self._aot_fallbacks = 0
        # (model, geometry, bucket) -> hot-path cell
        self._cells: Dict[Tuple[str, str, int], _BatchCell] = {}
        # (model, bucket) -> H2D transfer cell
        self._h2d: Dict[Tuple[str, int], _H2DCell] = {}
        # (model, bucket, shard) -> mesh-serving shard cell
        self._shard_cells: Dict[Tuple[str, int, str], _ShardCell] = {}
        self._fps = _RateWindow(window_s=fps_window_s)

        self._m_compile_s = reg.histogram(
            "vep_compile_seconds",
            "XLA compile wall time per step-cache miss",
            ("model", "geometry", "bucket"))
        self._m_compile_programs = reg.counter(
            "vep_compile_programs_total",
            "Compiled serving programs per (model, geometry, bucket)",
            ("model", "geometry", "bucket"))
        self._m_program_gflop = reg.gauge(
            "vep_compile_program_gflop",
            "FLOPs per program execution from XLA cost analysis (GFLOP)",
            ("model", "geometry", "bucket"))
        self._m_device = reg.histogram(
            "vep_perf_device_ms",
            "Device batch time per bucket (submit->drained; includes "
            "drain-queue wait)", ("model", "bucket"))
        self._m_padded = reg.counter(
            "vep_perf_padded_slots_total",
            "Batch slots filled with padding, not frames (pad_to_bucket "
            "waste)", ("model", "bucket"))
        self._m_slots = reg.counter(
            "vep_perf_batch_slots_total",
            "Total batch slots dispatched (real frames + padding)",
            ("model", "bucket"))
        self._m_occupancy = reg.gauge(
            "vep_perf_bucket_occupancy_pct",
            "Real frames over bucket size, last batch",
            ("model", "bucket"))
        self._m_mfu = reg.gauge(
            "vep_perf_mfu_pct",
            "Live model-FLOPs utilization vs peak_tflops (EMA device "
            "time; proxy, see obs/perf.py)", ("model", "bucket"))
        self._m_tflops = reg.gauge(
            "vep_perf_achieved_tflops",
            "Achieved TFLOP/s per batch (EMA device time)",
            ("model", "bucket"))
        self._m_peak = reg.gauge(
            "vep_perf_peak_tflops",
            "Device peak TFLOP/s used for MFU (peaks table, by "
            "device_kind)")
        self._set_peak(None if peak_tflops is None else float(peak_tflops))
        self._m_fps = reg.gauge(
            "vep_perf_fps",
            "Aggregate emitted frames/second (sliding window)")
        # Mesh-native serving (ISSUE 17): per-shard attribution rides NEW
        # counter families keyed by shard, so every pre-existing series
        # above keeps its exact label tuple (exposition-lint stability).
        self._m_shard_frames = reg.counter(
            "vep_perf_shard_frames_total",
            "Real frames served per dp mesh shard",
            ("model", "bucket", "shard"))
        self._m_shard_busy = reg.counter(
            "vep_perf_shard_busy_ms_total",
            "Device batch milliseconds attributed per dp mesh shard "
            "(data-parallel replication: every chip runs the full "
            "program wall time)", ("model", "bucket", "shard"))
        self._m_h2d_bytes = reg.counter(
            "vep_h2d_bytes",
            "Host->device bytes shipped per dispatched batch (uint8 "
            "frames incl. bucket padding, plus aux tensors such as the "
            "int32 thumbnail slot-index vector)", ("model", "bucket"))
        self._m_h2d_seconds = reg.counter(
            "vep_h2d_seconds",
            "Wall seconds of async device_put transfer per batch, timed "
            "on the prefetch transfer thread (copy start to "
            "block_until_ready)", ("model", "bucket"))
        self._m_h2d_hidden = reg.counter(
            "vep_h2d_hidden_seconds",
            "Share of H2D transfer wall seconds that overlapped in-flight "
            "device compute or dispatch work (prefetch stage)",
            ("model", "bucket"))
        # ROI serving attribution (MOSAIC, engine/runner.py cfg.roi):
        # per-tick gate split, packer output, scatter-back routing
        # failures, and the projected full-frame-equivalent fps — the
        # rate of per-stream results served through the ROI plane
        # (coasted + packed + full), i.e. what the fleet would have cost
        # in full frames.
        self._m_roi_states = reg.counter(
            "vep_roi_stream_states_total",
            "Motion-gate verdicts per detect stream per tick",
            ("state",))
        self._m_roi_crops = reg.counter(
            "vep_roi_crops_total",
            "Crops packed onto shared canvases").labels()
        self._m_roi_canvases = reg.counter(
            "vep_roi_canvases_total",
            "Shared canvases dispatched").labels()
        self._m_roi_occupancy = reg.gauge(
            "vep_roi_canvas_occupancy_pct",
            "Crop-pixel share of the packed canvas plane, last "
            "batch").labels()
        self._m_roi_unrouted = reg.counter(
            "vep_roi_unrouted_total",
            "Canvas detections that landed outside every crop cell "
            "(dropped in scatter-back)").labels()
        self._m_roi_fps = reg.gauge(
            "vep_roi_equivalent_fps",
            "Per-stream results served through the ROI plane per second "
            "(full-frame-equivalent fps, sliding window)").labels()
        self._roi_fps = _RateWindow(window_s=fps_window_s)
        self._roi = {"idle": 0, "roi": 0, "full": 0, "crops": 0,
                     "canvases": 0, "unrouted": 0, "area_frac": None}
        # Temporal cascade attribution (temporal/scheduler.py, engine
        # cfg.cascade): detect runs every tick, the temporal head at
        # cadence 1/N — the cadence gauge (head batches over cascade
        # ticks) is the live form of the smoke artifact's
        # cascade_head_cadence gate.
        self._m_cascade_ticks = reg.counter(
            "vep_cascade_ticks_total",
            "Engine ticks observed by the cascade scheduler").labels()
        self._m_cascade_head = reg.counter(
            "vep_cascade_head_batches_total",
            "Temporal-head batches dispatched (cadence ticks with due "
            "tracks)").labels()
        self._m_cascade_events = reg.counter(
            "vep_cascade_events_total",
            "Track event transitions fired by the hysteresis machine",
            ("kind",))
        self._m_cascade_tracks = reg.gauge(
            "vep_cascade_tracks",
            "Track slots live in the device-resident state pool").labels()
        self._m_cascade_cadence = reg.gauge(
            "vep_cascade_head_cadence",
            "Cascade ticks per temporal-head batch (target: "
            "cascade_every_n)").labels()
        self._cascade = {"ticks": 0, "head_batches": 0, "head_slots": 0,
                         "events": {}, "tracks": 0, "high_water": 0}

    # -- compile-time attribution ----------------------------------------

    def set_device_kind(self, device_kind: str) -> None:
        """Resolve the MFU denominator from the peaks table once the
        backend is up. An unknown device leaves MFU unset."""
        self._set_peak(peak_tflops_for(device_kind))

    def _set_peak(self, peak: Optional[float]) -> None:
        self.peak_tflops = peak
        if peak is not None:
            self._m_peak.set(peak)

    @staticmethod
    def _geometry(src_hw: Tuple[int, int]) -> str:
        return f"{src_hw[0]}x{src_hw[1]}"

    def note_compile(self, model: str, src_hw: Tuple[int, int], bucket: int,
                     seconds: float, *, compiled=None,
                     cost: Optional[dict] = None) -> None:
        """Record one step-cache-miss compile. ``compiled`` (an XLA
        executable) or a pre-extracted ``cost`` dict supplies FLOPs."""
        if cost is None:
            cost = cost_summary(compiled) if compiled is not None else {}
        geometry = self._geometry(src_hw)
        key = (model, geometry, bucket)
        with self._lock:
            rec = self._compiles.get(key)
            if rec is None:
                rec = {"model": model, "geometry": geometry,
                       "bucket": bucket, "programs": 0,
                       "compile_s": 0.0, "flops": 0.0,
                       "bytes_accessed": 0.0}
                self._compiles[key] = rec
            rec["programs"] += 1
            rec["compile_s"] += float(seconds)
            if cost.get("flops"):
                rec["flops"] = cost["flops"]
            if cost.get("bytes_accessed"):
                rec["bytes_accessed"] = cost["bytes_accessed"]
        b = str(bucket)
        self._m_compile_s.labels(model, geometry, b).observe(float(seconds))
        self._m_compile_programs.labels(model, geometry, b).inc()
        if cost.get("flops"):
            self._m_program_gflop.labels(model, geometry, b).set(
                cost["flops"] / 1e9)

    def note_aot_fallback(self) -> None:
        """One program's AOT executable rejected its arguments (avals
        drift) and now runs through plain jit (engine/runner.py
        ``_TimedStep``, which logs which). Zero on a healthy member."""
        with self._lock:
            self._aot_fallbacks += 1

    # -- tick-time attribution -------------------------------------------

    def note_batch(self, model: str, src_hw: Tuple[int, int], bucket: int,
                   device_ms: float, frames: int, *,
                   streams: Optional[int] = None,
                   area_frac: Optional[float] = None,
                   shard_frames: Optional[Dict[str, int]] = None) -> None:
        """Record one drained device batch: ``frames`` real frames in a
        ``bucket``-slot program that ran for ``device_ms``.

        Canvas-aware accounting (MOSAIC packed batches): ``frames`` is
        then the canvas count, ``streams`` the number of source streams
        whose crops rode the batch (feeds the fps window — results
        emitted, not canvases), and ``area_frac`` the crop-pixel share
        of the canvas plane. With ``area_frac`` the occupancy gauge
        reports crop-level occupancy — a half-empty canvas must NOT read
        as one fully-occupied slot.

        Mesh-native serving: ``shard_frames`` maps dp shard label ->
        real frames that shard contributed to this batch; each listed
        shard is charged the FULL ``device_ms`` (replicated program —
        every chip is busy for the whole batch wall time)."""
        geometry = self._geometry(src_hw)
        key = (model, geometry, bucket)
        cell = self._cells.get(key)
        if cell is None:
            cell = self._make_cell(key)
        padded = bucket - frames
        cell.device.observe(device_ms)
        if padded > 0:
            cell.padded.inc(padded)
        cell.slots.inc(bucket)
        if area_frac is not None:
            cell.occupancy.set(100.0 * area_frac)
        else:
            cell.occupancy.set(100.0 * frames / bucket if bucket else 0.0)
        if cell.ema_init:
            cell.ema_ms = 0.9 * cell.ema_ms + 0.1 * device_ms
        else:
            cell.ema_ms = device_ms
            cell.ema_init = True
        cell.frames += frames
        cell.padded_total += max(padded, 0)
        rec = self._compiles.get(key)
        flops = rec["flops"] if rec is not None else 0.0
        util = mfu_pct(flops, cell.ema_ms, self.peak_tflops)
        if flops > 0.0 and cell.ema_ms > 0.0:
            cell.tflops.set(flops / (cell.ema_ms * 1e-3) / 1e12)
        if util is not None:
            if cell.mfu is None:
                cell.mfu = self._m_mfu.labels(model, str(bucket))
            cell.mfu.set(util)
        if shard_frames:
            for shard, n in shard_frames.items():
                skey = (model, bucket, str(shard))
                scell = self._shard_cells.get(skey)
                if scell is None:
                    scell = self._make_shard_cell(skey)
                scell.frames_child.inc(int(n))
                scell.busy_child.inc(device_ms)
                scell.frames += int(n)
                scell.busy_ms += float(device_ms)
        now = self._clock()
        self._fps.add(streams if streams is not None else frames, now)
        self._m_fps.set(self._fps.rate(now))

    def note_h2d(self, model: str, bucket: int, nbytes: int,
                 seconds: float, *, hidden_s: float = 0.0) -> None:
        """Record one host->device batch placement: ``nbytes`` on the wire
        (the full padded uint8 batch plus aux tensors such as the int32
        thumbnail slot-index vector) taking ``seconds`` of transfer wall
        time. With the prefetch stage enabled this is a real async
        ``device_put`` timed on the dedicated transfer thread (copy start
        to ``block_until_ready``); ``hidden_s`` is the portion of that
        window which overlapped in-flight device compute or dispatch work
        on the tick thread — the evidence behind ``h2d_hidden_pct``.
        Without prefetch it degrades to the legacy synchronous placement
        timing with ``hidden_s`` = 0. Called once per dispatched batch,
        same fixed-allocation cell discipline as ``note_batch`` — the
        direct measurement behind ROADMAP item 5's bytes-per-frame gate."""
        key = (model, bucket)
        cell = self._h2d.get(key)
        if cell is None:
            cell = self._make_h2d_cell(key)
        cell.bytes_child.inc(nbytes)
        cell.seconds_child.inc(seconds)
        if hidden_s > 0.0:
            cell.hidden_child.inc(hidden_s)
            cell.hidden_s += float(hidden_s)
        cell.bytes += int(nbytes)
        cell.seconds += float(seconds)
        cell.batches += 1
        cell.slots += int(bucket)

    # -- ROI serving attribution (cfg.roi, engine/runner.py) --------------

    def note_roi_gate(self, idle: int, roi: int, full: int) -> None:
        """One tick's motion-gate split over detect streams."""
        if idle:
            self._m_roi_states.labels("idle").inc(idle)
        if roi:
            self._m_roi_states.labels("roi").inc(roi)
        if full:
            self._m_roi_states.labels("full").inc(full)
        with self._lock:
            self._roi["idle"] += idle
            self._roi["roi"] += roi
            self._roi["full"] += full

    def note_roi_pack(self, crops: int, canvases: int,
                      area_frac: float) -> None:
        """One packed canvas batch leaving the packer."""
        self._m_roi_crops.inc(crops)
        self._m_roi_canvases.inc(canvases)
        self._m_roi_occupancy.set(100.0 * area_frac)
        with self._lock:
            self._roi["crops"] += crops
            self._roi["canvases"] += canvases
            self._roi["area_frac"] = area_frac

    def note_roi_emit(self, streams: int) -> None:
        """Per-stream results served through the ROI plane (coasted,
        packed, or full-frame-while-gating) — the full-frame-equivalent
        fps evidence (ISSUE 9 acceptance)."""
        now = self._clock()
        self._roi_fps.add(streams, now)
        self._m_roi_fps.set(self._roi_fps.rate(now))

    def note_roi_unrouted(self, n: int = 1) -> None:
        self._m_roi_unrouted.inc(n)
        with self._lock:
            self._roi["unrouted"] += n

    def roi_equivalent_fps(self) -> float:
        return self._roi_fps.rate(self._clock())

    # -- temporal cascade attribution (cfg.cascade, temporal/) -------------

    def note_cascade_tick(self) -> None:
        """One engine tick seen by the cascade scheduler (fires whether
        or not this tick is a head-cadence tick)."""
        self._m_cascade_ticks.inc()
        with self._lock:
            self._cascade["ticks"] += 1
            self._set_cascade_cadence_locked()

    def note_cascade_head(self, slots: int) -> None:
        """One temporal-head batch dispatched with ``slots`` live track
        slots (device time/H2D ride note_batch/note_h2d under the
        ``cascade/<model>`` key, same as every other program)."""
        self._m_cascade_head.inc()
        with self._lock:
            self._cascade["head_batches"] += 1
            self._cascade["head_slots"] += int(slots)
            self._set_cascade_cadence_locked()

    def note_cascade_event(self, kind: str) -> None:
        """One hysteresis transition ("enter"/"exit") fired for a track."""
        self._m_cascade_events.labels(kind).inc()
        with self._lock:
            ev = self._cascade["events"]
            ev[kind] = ev.get(kind, 0) + 1

    def note_cascade_slots(self, in_use: int, high_water: int) -> None:
        """State-pool occupancy after a cascade tick (slot-conservation
        evidence: in_use tracks live tracks, high_water stays bounded
        across churn)."""
        self._m_cascade_tracks.set(float(in_use))
        with self._lock:
            self._cascade["tracks"] = int(in_use)
            self._cascade["high_water"] = max(
                self._cascade["high_water"], int(high_water))

    def _set_cascade_cadence_locked(self) -> None:
        c = self._cascade
        if c["head_batches"]:
            self._m_cascade_cadence.set(c["ticks"] / c["head_batches"])

    def _make_h2d_cell(self, key: Tuple[str, int]) -> _H2DCell:
        model, bucket = key
        b = str(bucket)
        cell = _H2DCell(
            bytes_child=self._m_h2d_bytes.labels(model, b),
            seconds_child=self._m_h2d_seconds.labels(model, b),
            hidden_child=self._m_h2d_hidden.labels(model, b),
        )
        with self._lock:
            return self._h2d.setdefault(key, cell)

    def _make_shard_cell(self, key: Tuple[str, int, str]) -> _ShardCell:
        model, bucket, shard = key
        cell = _ShardCell(
            frames_child=self._m_shard_frames.labels(
                model, str(bucket), shard),
            busy_child=self._m_shard_busy.labels(model, str(bucket), shard),
        )
        with self._lock:
            return self._shard_cells.setdefault(key, cell)

    def _make_cell(self, key: Tuple[str, str, int]) -> _BatchCell:
        model, _geometry, bucket = key
        b = str(bucket)
        cell = _BatchCell(
            device=self._m_device.labels(model, b),
            padded=self._m_padded.labels(model, b),
            slots=self._m_slots.labels(model, b),
            occupancy=self._m_occupancy.labels(model, b),
            # Bound on the first batch with a known MFU, so a device
            # outside the peaks table exports no vep_perf_mfu_pct sample.
            mfu=None,
            tflops=self._m_tflops.labels(model, b),
        )
        with self._lock:
            return self._cells.setdefault(key, cell)

    def fps(self) -> float:
        """Aggregate emitted frames/second over the sliding window."""
        return self._fps.rate(self._clock())

    # -- snapshots --------------------------------------------------------

    def snapshot(self) -> dict:
        """JSON-able attribution summary for /api/v1/stats and the soak
        artifact's "perf" section."""
        with self._lock:
            compiles = [dict(rec) for rec in self._compiles.values()]
            aot_fallbacks = self._aot_fallbacks
            buckets = []
            for (model, geometry, bucket), cell in sorted(
                    self._cells.items()):
                rec = self._compiles.get((model, geometry, bucket))
                flops = rec["flops"] if rec is not None else 0.0
                util = mfu_pct(flops, cell.ema_ms, self.peak_tflops)
                slots = cell.frames + cell.padded_total
                buckets.append({
                    "model": model, "geometry": geometry, "bucket": bucket,
                    "device_ms_ema": round(cell.ema_ms, 3),
                    "frames": cell.frames,
                    "padded_slots": cell.padded_total,
                    "padded_pct": round(100.0 * cell.padded_total / slots,
                                        2) if slots else 0.0,
                    "mfu_pct": round(util, 3) if util is not None else None,
                })
            shards = [
                {"model": model, "bucket": bucket, "shard": shard,
                 "frames": scell.frames,
                 "busy_ms": round(scell.busy_ms, 3)}
                for (model, bucket, shard), scell in sorted(
                    self._shard_cells.items())
            ]
            h2d = []
            h2d_seconds = 0.0
            h2d_hidden = 0.0
            for (model, bucket), cell in sorted(self._h2d.items()):
                h2d_seconds += cell.seconds
                h2d_hidden += cell.hidden_s
                h2d.append({
                    "model": model, "bucket": bucket,
                    "bytes": cell.bytes,
                    "seconds": round(cell.seconds, 6),
                    "hidden_seconds": round(cell.hidden_s, 6),
                    "hidden_pct": (round(100.0 * cell.hidden_s
                                         / cell.seconds, 1)
                                   if cell.seconds > 0 else None),
                    "batches": cell.batches,
                    "bytes_per_frame": (cell.bytes // cell.slots
                                        if cell.slots else None),
                    "mbps": (round(cell.bytes / 1e6 / cell.seconds, 1)
                             if cell.seconds > 0 else None),
                })
        out = {
            "peak_tflops": self.peak_tflops,
            "fps": round(self.fps(), 1),
            "compiles": sorted(
                compiles, key=lambda r: (r["model"], r["geometry"],
                                         r["bucket"])),
            "aot_fallbacks": aot_fallbacks,
            "buckets": buckets,
            "h2d": h2d,
            "h2d_hidden_pct": (round(100.0 * h2d_hidden / h2d_seconds, 1)
                               if h2d_seconds > 0 else None),
        }
        if shards:
            out["shards"] = shards
        with self._lock:
            roi = dict(self._roi)
        gated = roi["idle"] + roi["roi"] + roi["full"]
        if gated or roi["canvases"]:
            out["roi"] = {
                "stream_ticks": {"idle": roi["idle"], "roi": roi["roi"],
                                 "full": roi["full"]},
                "gated_stream_pct": round(
                    100.0 * (roi["idle"] + roi["roi"]) / gated, 1)
                if gated else 0.0,
                "crops": roi["crops"],
                "canvases": roi["canvases"],
                "crops_per_canvas": round(
                    roi["crops"] / roi["canvases"], 2)
                if roi["canvases"] else None,
                "canvas_occupancy_pct": round(
                    100.0 * roi["area_frac"], 1)
                if roi["area_frac"] is not None else None,
                "unrouted": roi["unrouted"],
                "equivalent_fps": round(self.roi_equivalent_fps(), 1),
            }
        with self._lock:
            casc = dict(self._cascade)
            casc["events"] = dict(casc["events"])
        if casc["ticks"] or casc["head_batches"]:
            out["cascade"] = {
                "ticks": casc["ticks"],
                "head_batches": casc["head_batches"],
                "head_cadence": round(
                    casc["ticks"] / casc["head_batches"], 2)
                if casc["head_batches"] else None,
                "slots_per_head": round(
                    casc["head_slots"] / casc["head_batches"], 2)
                if casc["head_batches"] else None,
                "events": casc["events"],
                "tracks": casc["tracks"],
                "slot_high_water": casc["high_water"],
            }
        return out
