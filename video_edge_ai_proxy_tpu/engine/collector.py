"""Batch collector: N camera streams → padded device batches per tick.

This is the fan-in point (SURVEY.md §2.3 P3): where the reference left each
ML client to read one Redis stream at a time
(`/root/reference/server/grpcapi/grpc_api.go:187-229`), the collector walks
every active ring each tick, takes the newest unseen frame per stream
(latest-wins, depth-1 semantics preserved), groups frames by source
geometry, and pads each group to a bucketed batch size so XLA sees a small
closed set of shapes (SURVEY.md §7 hard part 1 — no recompilation storms).

Video models get clip assembly: a per-stream sliding window of the last
``clip_len`` frames (the temporal axis is just a leading axis, SURVEY.md
§5.7). The window is a ring of ``clip_len`` frame slots allocated once per
stream (``_ClipRing``): the bus writes each new frame over the one falling
out of the window, and the ring is copied, oldest frame first, into a row
of the same pooled batch buffers single-frame streams are read into.

Where a window lives. A Collector built with ``device_windows`` (the
one-device engine builds its own so, because it owns a window pool:
engine/stream_state.py ``ClipWindowPool``) keeps NO window: a clip stream
is read like a single-frame stream, its new frame straight into its row of
a pooled ``[bucket, H, W, C]`` buffer (copy count 1), and the group says
``window = clip_len``: the windowed step writes the frame into the
stream's window on the device and reads the window there. That holds for
the fast path and for first sight and drift alike (the frame is the whole
sample the host ships). Without the argument (the mesh engine, whose rows
must stay re-pinnable between shards, and callers that consume groups
themselves) the window is the host ring above and a group carries whole
clips. The argument may be a predicate over the model a stream runs: the
engine keeps the ``stream`` kind's windows on the host
(``InferenceEngine._window_on_device``). Which home a window has follows
from what the engine observes (one device or a mesh, the step's kind),
never from a model's name or a setting.

When a device window restarts. Every frame read for such a stream
(``_note_read``: its ``collect`` span) has to reach the stream's window,
in read order, or the window starts anew: the engine restarts it for a
batch it sheds or drops after the read and for a step that raised; the
collector reports the reads it could not hand on (a frame that is not
[H, W, C]) through ``take_window_breaks``. A stream with no new frame is
not in the batch and its window is untouched.
"""

from __future__ import annotations

import threading
import time
import zlib
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from ..bus.interface import Frame, FrameBus, FrameMeta
from ..obs import registry as obs_registry, tracer
from ..obs.spans import trace_id_of


def stream_shard(device_id: str, shards: int) -> int:
    """Stable stream -> mesh-shard assignment (dp-sharded serving).

    crc32 is platform- and run-stable, so a stream always lands on the
    same chip: its ROI tracker state, thumbnail slot and cascade clips
    live in that shard's pools and never migrate mid-flight. The engine
    and the collector must agree on this mapping — it is THE routing
    function for mesh-native serving."""
    if shards <= 1:
        return 0
    return zlib.crc32(device_id.encode("utf-8")) % shards


def make_repin(base_shard_of, shards: int, dead):
    """Deterministic rendezvous re-pin for survivor-mesh failover
    (device-fault domain, r22).

    ``base_shard_of`` is the routing function that was live when the
    fault hit (``stream_shard`` bound to the old shard count, or a
    previous failover's repin — composition handles cascaded faults);
    ``shards`` its shard count; ``dead`` the faulted shard indices.
    Survivor shards keep their old index order in the rebuilt mesh
    (new shard i == i-th surviving old shard, same physical device), so:

    - a stream whose home shard survives maps to that shard's new index
      — it stays on the SAME device, state intact, which is what makes
      failover a re-pin and not a full crc32 reshuffle (surviving
      shards keep >= 90% of their pins by construction: they keep all
      of them);
    - a stream whose home shard died re-pins by highest-random-weight
      (rendezvous) hashing over the survivors — deterministic,
      uniformly spread, and stable under further shard loss (only
      streams of the newly dead shard move again)."""
    dead = frozenset(int(s) for s in dead)
    survivors = [s for s in range(int(shards)) if s not in dead]
    if not survivors:
        raise ValueError("no surviving shards to re-pin onto")
    new_index = {s: i for i, s in enumerate(survivors)}

    def repin(device_id: str) -> int:
        home = base_shard_of(device_id)
        idx = new_index.get(home)
        if idx is not None:
            return idx
        best = max(
            survivors,
            key=lambda t: zlib.crc32(f"{device_id}@{t}".encode("utf-8")),
        )
        return new_index[best]

    return repin


def _new_trace() -> dict:
    """One collect() call's measurement, accumulated over cameras and
    groups and named by purpose, not by today's numpy call:

    - ``read_s``: bus ring -> host memory (``read_latest``,
      ``read_latest_into``, the window's ``assemble_step``);
      ``read_ahead_s`` is the part of it spent between ticks, before
      collect() was entered.
    - ``fill_s``: samples -> the batch buffer (a clip ring -> its row),
      with the buffer's allocation and zero-padding.
    - ``frames_read`` / ``bytes_read``: new frames taken off the rings.
    - ``bytes_copied``: every byte written into host memory (reads, fill,
      padding); ``bytes_fresh``: those written into a buffer that is
      handed out once (a new allocation, the bus's per-read destination)
      and so first-touched, as against a pooled buffer reused from tick
      to tick. A clip ring's slot and a clip batch's pool buffer count
      fresh the first time they are written: set-up, not steady state.
    """
    return {"read_s": 0.0, "read_ahead_s": 0.0, "fill_s": 0.0,
            "frames_read": 0, "bytes_read": 0, "bytes_copied": 0,
            "bytes_fresh": 0}


@dataclass
class BatchGroup:
    """One shape-homogeneous device batch (before padding)."""

    src_hw: tuple            # (H, W) of the source frames
    device_ids: List[str]
    frames: np.ndarray       # [N, H, W, C] u8, or [N, T, H, W, C] for clips
    metas: List[FrameMeta]
    bucket: int = 0          # padded batch size chosen by pad_to_bucket
    model: str = ""          # registry model these streams run (engine key)
    window: int = 0          # clip_len when ``frames`` are single frames
                             # bound for each stream's window on the device
                             # (Collector(device_windows=True)); 0 = whole
                             # samples
    lease: Optional[tuple] = None  # (pool shape, buf idx) when the frames
                                   # view a pooled buffer under strict
                                   # leasing (Collector.release returns it)
    # MOSAIC lineage (cfg.roi, engine/runner.py). ``crops``: this group's
    # frames are packed shared canvases, one CropPlacement per blitted
    # crop — the provenance the scatter-back path needs to route canvas
    # detections to their source streams. ``coast``: no device work at
    # all; list of (device_id, meta, detections) for gated-idle streams
    # whose tracker-coasted results ride the drain queue so per-stream
    # emit ordering is preserved. Both None on the classic full-frame
    # path — which is exactly what keeps roi=False bit-identical.
    crops: Optional[list] = None
    coast: Optional[list] = None
    # Mesh-sharded layout (Collector(shards=S)): ``rows[j]`` is the frame
    # row of ``device_ids[j]`` in the shard-segmented batch — shard s owns
    # rows [s*bucket/S, (s+1)*bucket/S), each segment zero-padded
    # independently so one ``dp``-sharded device_put gives every chip
    # exactly its own streams' frames. None = dense identity layout (row
    # j == device_ids[j]), the single-chip path, bit-identical to pre-
    # shard behavior.
    rows: Optional[List[int]] = None

    @property
    def padded_slots(self) -> int:
        """Batch slots carrying zero-padding instead of real frames — the
        per-batch waste obs/perf.py attributes (pad_to_bucket and the
        pooled fast paths both pad up to ``bucket``)."""
        return max(0, self.bucket - len(self.device_ids))

    @property
    def nbytes(self) -> int:
        """Bytes of frame plane this batch ships host->device when
        dispatched: the whole padded uint8 plane, padding slots included
        (they cross the PCIe/ICI link like real frames). Aux tensors that
        ride along per dispatch (e.g. the int32 thumbnail slot-index
        vector for 3-arg quality steps) are accounted at the dispatch
        site in engine/runner.py, which adds them to this figure before
        feeding the vep_h2d_* accounting in obs/perf.py — the evidence
        gate for ROADMAP item 5's uint8-shipping / double-buffered H2D
        work."""
        return int(self.frames.nbytes)


def pad_to_bucket(group: BatchGroup, buckets: Sequence[int]) -> BatchGroup:
    """Zero-pad the batch dim to the smallest bucket >= N. Oversized batches
    are the caller's job (Collector.collect chunks to max bucket)."""
    n = group.frames.shape[0]
    bucket = next((b for b in sorted(buckets) if b >= n), None)
    if bucket is None:
        raise ValueError(f"batch {n} exceeds max bucket {max(buckets)}")
    if bucket != n:
        pad = np.zeros((bucket - n,) + group.frames.shape[1:], group.frames.dtype)
        group.frames = np.concatenate([group.frames, pad], axis=0)
    group.bucket = bucket
    return group


class _ClipRing:
    """One clip camera's window: ``clip_len`` frame slots allocated once
    and written in place. ``pos`` is the slot the next frame goes to; once
    the ring is full that is the slot of the frame falling out of the
    window, so the window in time order is ``buf[pos:]`` then
    ``buf[:pos]``. Only Collector touches it, on the engine thread, and no
    BatchGroup ever views it: a batch in flight holds a copy."""

    __slots__ = ("buf", "pos", "count")

    def __init__(self, clip_len: int, geom: tuple):
        self.buf = np.zeros((clip_len,) + tuple(geom), np.uint8)
        self.pos = 0
        self.count = 0

    @property
    def full(self) -> bool:
        return self.count == len(self.buf)

    def advance(self) -> None:
        """The slot at ``pos`` now holds the newest frame."""
        self.pos = (self.pos + 1) % len(self.buf)
        self.count = min(self.count + 1, len(self.buf))

    def copy_to(self, row: np.ndarray) -> None:
        """A full window into ``row`` [clip_len, H, W, C], oldest first —
        byte for byte the last ``clip_len`` frames stacked."""
        k = len(self.buf) - self.pos
        row[:k] = self.buf[self.pos:]
        row[k:] = self.buf[:self.pos]


@dataclass(frozen=True)
class CropPlacement:
    """Provenance for one crop blitted onto a shared canvas (MOSAIC).

    The forward placement is a pure integer affine — source rect ``src``
    decimated by ``scale`` (source px per canvas px, power of two) and
    blitted with its top-left corner at ``dst``'s origin — so the
    scatter-back inverse (ops/boxes.py ``uncrop_boxes``) is exact:
    ``src_px = (canvas_px - dst_origin) * scale + src_origin``.
    """

    device_id: str
    meta: FrameMeta          # the source frame's meta (timestamps, packet)
    canvas: int              # slot index within the canvas batch
    src: tuple               # (x0, y0, x1, y1) source-frame px (ints)
    dst: tuple               # (x0, y0, x1, y1) canvas px (ints)
    scale: int               # source px per canvas px (>= 1, power of 2)

    def contains(self, x: float, y: float) -> bool:
        """Does a canvas-coordinate point land in this crop's cell? Used
        by the scatter-back router: one cell per detection center, cells
        never overlap (the packer keeps a gap between them)."""
        return (self.dst[0] <= x < self.dst[2]
                and self.dst[1] <= y < self.dst[3])


class CanvasPacker:
    """Deterministic shelf packer: many streams' active crops → a small
    set of static-shape shared canvases (MOSAIC, arxiv 2305.03222).

    Geometry is the bucket: every canvas is ``side``×``side`` uint8, so
    the packed batch reuses the engine's existing (geometry, bucket) step
    cache — XLA still sees a small closed shape set, no new programs
    beyond the one canvas geometry. Packing is deterministic (sort by
    scaled height/width then stream id, first-fit shelves) so replaying
    the same crops yields byte-identical canvases — the property the
    replay-checksum harness leans on.

    Crops larger than a canvas are decimated by the smallest power-of-two
    stride that fits; power-of-two strided views keep the inverse
    transform exact (no fractional resampling) and the blit a cheap numpy
    strided copy. A ``gap`` of background pixels separates cells so a
    detection can never straddle two streams' crops; background is 114
    gray, matching ``preprocess_letterbox``'s pad value so cell borders
    look like letterbox padding to the detector.
    """

    def __init__(self, side: int = 640, gap: int = 8,
                 max_canvases: int = 8, min_crop: int = 16):
        self.side = int(side)
        self.gap = int(gap)
        self.max_canvases = int(max_canvases)
        self.min_crop = int(min_crop)

    def _fit_scale(self, w: int, h: int) -> int:
        scale = 1
        while (w + scale - 1) // scale > self.side \
                or (h + scale - 1) // scale > self.side:
            scale *= 2
        return scale

    def pack(self, requests: Sequence[tuple]):
        """``requests``: (device_id, meta, frame [H,W,3] u8, roi xyxy).

        Returns (canvases [K, side, side, 3] u8, placements, overflow):
        ``placements`` one CropPlacement per packed crop, ``overflow``
        the request indices that did not fit within ``max_canvases``
        (the engine falls those streams back to the full-frame path).
        """
        side, gap = self.side, self.gap
        prepared = []   # (sh, sw, scale, rect, req_index)
        overflow: List[int] = []
        for ri, (device_id, _meta, frame, roi) in enumerate(requests):
            fh, fw = frame.shape[0], frame.shape[1]
            x0 = max(0, min(int(roi[0]), fw - 1))
            y0 = max(0, min(int(roi[1]), fh - 1))
            x1 = max(x0 + 1, min(int(round(roi[2])), fw))
            y1 = max(y0 + 1, min(int(round(roi[3])), fh))
            # Tiny ROIs inflate to min_crop: the detector needs context
            # and the NMS floor behaves badly on few-pixel cells.
            if x1 - x0 < self.min_crop:
                x1 = min(fw, x0 + self.min_crop)
                x0 = max(0, x1 - self.min_crop)
            if y1 - y0 < self.min_crop:
                y1 = min(fh, y0 + self.min_crop)
                y0 = max(0, y1 - self.min_crop)
            scale = self._fit_scale(x1 - x0, y1 - y0)
            sw = (x1 - x0 + scale - 1) // scale
            sh = (y1 - y0 + scale - 1) // scale
            prepared.append((sh, sw, scale, (x0, y0, x1, y1), ri))
        # Deterministic shelf order: tallest first, then widest, then
        # stream id — identical input always packs identically.
        prepared.sort(key=lambda p: (-p[0], -p[1],
                                     requests[p[4]][0], p[4]))
        placements: List[CropPlacement] = []
        slots = []   # per-canvas shelf cursors: [x, y, shelf_h]
        blits = []   # (canvas, dst, rect, scale, req_index)
        for sh, sw, scale, rect, ri in prepared:
            placed = False
            for ci, cur in enumerate(slots):
                x, y, shelf_h = cur
                if x + sw > side:                     # next shelf
                    x, y, shelf_h = 0, y + shelf_h + gap, 0
                if x + sw <= side and y + sh <= side:
                    blits.append((ci, (x, y, x + sw, y + sh),
                                  rect, scale, ri))
                    slots[ci] = [x + sw + gap, y, max(shelf_h, sh)]
                    placed = True
                    break
            if not placed:
                if len(slots) < self.max_canvases:
                    ci = len(slots)
                    slots.append([sw + gap, 0, sh])
                    blits.append((ci, (0, 0, sw, sh), rect, scale, ri))
                else:
                    overflow.append(ri)
        canvases = np.full((len(slots), side, side, 3), 114, np.uint8)
        for ci, dst, rect, scale, ri in blits:
            device_id, meta, frame, _roi = requests[ri]
            x0, y0, x1, y1 = rect
            view = frame[y0:y1:scale, x0:x1:scale]
            canvases[ci, dst[1]:dst[3], dst[0]:dst[2]] = view
            placements.append(CropPlacement(
                device_id=device_id, meta=meta, canvas=ci,
                src=rect, dst=dst, scale=scale,
            ))
        return canvases, placements, overflow

    @staticmethod
    def area_fraction(placements: Sequence[CropPlacement],
                      n_canvases: int, side: int) -> float:
        """Crop-pixel share of the canvas batch — the crop-level
        occupancy obs/perf.py reports for packed batches (a canvas is
        NOT one fully-occupied slot)."""
        if not n_canvases:
            return 0.0
        used = sum((p.dst[2] - p.dst[0]) * (p.dst[3] - p.dst[1])
                   for p in placements)
        return used / float(n_canvases * side * side)


class Collector:
    """Tracks per-stream cursors and assembles per-tick batches."""

    def __init__(
        self,
        bus: FrameBus,
        *,
        buckets: Sequence[int] = (1, 2, 4, 8, 16, 32),
        clip_len: int = 0,
        active_window_s: float = 10.0,
        model_of: Optional[callable] = None,   # device_id -> (model, clip_len)
        default_model: str = "",
        interest_of: Optional[callable] = None,  # device_id -> bool
        strict_lease: bool = False,
        shards: int = 1,
        device_windows=False,       # bool, or model name -> bool
    ):
        self._bus = bus
        self._buckets = tuple(sorted(buckets))
        # Mesh-sharded batch layout (engine.mesh, dp axis): every batch is
        # segmented into ``shards`` equal row ranges, streams are routed to
        # their stream_shard() segment, and each segment pads
        # independently — the frames a dp-sharded device_put lands on chip
        # s are exactly shard s's streams. Buckets must split evenly;
        # non-divisible ones are dropped here (the engine pre-filters to
        # the same set). shards=1 keeps every path bit-identical.
        self._shards = max(1, int(shards))
        if self._shards > 1:
            sharded = tuple(b for b in self._buckets if b % self._shards == 0)
            if not sharded:
                import logging

                logging.getLogger("vep.engine.collector").warning(
                    "no bucket in %s divides into %d shards; serving "
                    "unsharded", self._buckets, self._shards)
                self._shards = 1
            else:
                self._buckets = sharded
        # Stream -> shard routing override (device-fault failover,
        # ``repin``): None = the stable crc32 ``stream_shard`` map.
        self._shard_fn = None
        # Clip windows live on the device (module docstring): the caller
        # owns a window pool and runs the windowed step; it may say so a
        # model. Dense layout only.
        if self._shards > 1 or not device_windows:
            self._device_windows = None
        elif callable(device_windows):
            self._device_windows = device_windows
        else:
            self._device_windows = lambda model: True
        # streams whose read frame could not be handed on (take_window_breaks)
        self._window_breaks: List[str] = []
        # Degradation-ladder bucket cap (resilience/ladder.py rung 2):
        # None = full bucket list; an int hides buckets above it so new
        # batches compile/run at the next-smaller device program.
        self._bucket_cap: Optional[int] = None
        self._clip_len = clip_len
        self._active_window_s = active_window_s
        self._model_of = model_of
        self._default_model = default_model
        # Inference gating (SURVEY §2.3 P6, device half): ``interest_of``
        # answers "does anything consume results for this stream right
        # now" (uplink configured / live subscriber). A stream whose
        # interest lapses keeps inferring for ``active_window_s`` (linger
        # prevents batch-membership thrash on reconnecting clients), then
        # drops out of the device batch AND out of keep_streams_hot — so
        # the worker's lazy-decode valve actually closes.
        self._interest_of = interest_of
        self._last_interest: Dict[str, float] = {}
        self._cursors: Dict[str, int] = {}
        self._clips: Dict[str, _ClipRing] = {}
        self._geom: Dict[str, tuple] = {}   # last-seen (h, w, c) per stream
        # shape -> {"bufs": [arr], "prev": set, "cur": [idx], "leased":
        # [idx in lease order]} (_pooled / release)
        self._pool: Dict[tuple, dict] = {}
        # strict_lease (the engine's mode): a buffer backing an emitted
        # BatchGroup stays off-limits until Collector.release(group) —
        # required once dispatched batches outlive the tick that built
        # them (the engine's event-driven drain queue). Off (default):
        # the epoch heuristic alone bounds reuse to one emitting tick,
        # which is enough for callers that consume groups synchronously.
        self._strict_lease = strict_lease
        self._pool_lock = threading.Lock()  # release() runs on the drain
                                            # thread, _pooled on the engine
        # Incremental assembly window (assemble_until): frames are copied
        # into their pooled batch slots AS THEY ARRIVE between ticks, so
        # collect() at the tick boundary only finalizes. None = no window
        # active (plain collect path).
        self._window: Optional[dict] = None
        self._only: Optional[set] = None   # restrict to these ids (None = all)
        # What one collect() cost, by purpose (see _new_trace). Reads that
        # assemble_step makes between ticks land in the accumulator of the
        # collect() that dispatches them; collect() hands it out as
        # ``last_trace`` and starts the next. Engine thread only.
        self._acc = _new_trace()
        self.last_trace = _new_trace()
        # Latest-wins supersessions are BY DESIGN, but invisible drops are
        # not: a cursor that jumps k>1 sequence numbers means k-1 frames
        # were published and never read (camera outrunning the tick rate).
        self._m_skipped = obs_registry.counter(
            "vep_frames_skipped_total",
            "Frames superseded before read (latest-wins drops)",
            ("stream",),
        )

    def set_bucket_cap(self, cap: Optional[int]) -> None:
        """Cap the effective bucket list (degradation-ladder rung 2,
        resilience/ladder.py): ``cap=8`` hides buckets above 8 so new
        batches run the smaller, already-compiled device program; ``None``
        restores the full list. In-flight groups and the assembly
        window's existing allocations are untouched — the cap applies
        from the next planning/collect pass."""
        self._bucket_cap = cap

    def _effective_buckets(self) -> tuple:
        cap = self._bucket_cap
        if cap is None:
            return self._buckets
        eff = tuple(b for b in self._buckets if b <= cap)
        return eff or self._buckets[:1]

    def _rebase_if_restarted(self, device_id: str) -> bool:
        """A producer that recreates its ring (stop/start stream re-add,
        worker crash-restart) restarts sequence numbering below our
        cursor, so ``read_latest*(min_seq=cursor)`` would treat every
        frame on the new ring as already-seen until its seq caught up —
        seconds of invisibly dropped frames at low fps. A head strictly
        below the cursor is impossible on a monotonic ring, so it is an
        unambiguous restart signal: drop the cursor (callers retry the
        read in the same pass). ``head()`` None (backend without cheap
        heads) keeps the old behavior. Returns True when rebased."""
        cursor = self._cursors.get(device_id, 0)
        if cursor:
            head = self._bus.head(device_id)
            if head is not None and head < cursor:
                self._cursors.pop(device_id, None)
                return True
        return False

    def _note_read(self, device_id: str, seq: int, meta) -> None:
        """Every cursor advance funnels here: counts latest-wins skips and
        stamps the frame's ``collect`` lineage span. ``pub_ms`` rides the
        span because the publish span usually lives in a worker
        subprocess — the ingest->collect leg must be computable from the
        engine side alone."""
        prev = self._cursors.get(device_id, 0)
        if prev and seq > prev + 1:
            self._m_skipped.labels(device_id).inc(seq - prev - 1)
        self._cursors[device_id] = seq
        if meta is not None and tracer.sampled(meta.packet):
            tracer.record(
                device_id, "collect", meta.packet, pub_ms=meta.timestamp_ms,
                trace_id=trace_id_of(meta, device_id),
            )

    def _read_into(self, device_id: str, dst: np.ndarray, min_seq: int,
                   pooled: bool = True):
        """``bus.read_latest_into``, timed and counted into the trace."""
        acc = self._acc
        t = time.perf_counter()
        res = self._bus.read_latest_into(device_id, dst, min_seq=min_seq)
        acc["read_s"] += time.perf_counter() - t
        if res is not None:
            drifted = isinstance(res, Frame)   # the bus allocated instead
            self._count_frame(res.data.nbytes if drifted else dst.nbytes,
                              fresh=drifted or not pooled)
        return res

    def _read(self, device_id: str, min_seq: int) -> Optional[Frame]:
        """``bus.read_latest`` (a fresh array per frame), timed and
        counted into the trace."""
        acc = self._acc
        t = time.perf_counter()
        frame = self._bus.read_latest(device_id, min_seq=min_seq)
        acc["read_s"] += time.perf_counter() - t
        if frame is not None:
            self._count_frame(frame.data.nbytes, fresh=True)
        return frame

    def _count_frame(self, nbytes: int, fresh: bool) -> None:
        """One new frame off a ring, copied once into host memory."""
        acc = self._acc
        acc["frames_read"] += 1
        acc["bytes_read"] += int(nbytes)
        acc["bytes_copied"] += int(nbytes)
        if fresh:
            acc["bytes_fresh"] += int(nbytes)

    def _note_fill(self, t0: float, nbytes: int, fresh: bool) -> None:
        """Close a fill span opened at perf_counter ``t0``."""
        acc = self._acc
        acc["fill_s"] += time.perf_counter() - t0
        acc["bytes_copied"] += int(nbytes)
        if fresh:
            acc["bytes_fresh"] += int(nbytes)

    def _seed_ring(self, device_id: str, clip_len: int,
                   frame: Frame) -> Optional[_ClipRing]:
        """Start the stream's clip window anew from ``frame`` (first sight
        of the stream, or its geometry drifted): a ring at the frame's
        geometry with the frame in slot 0. A frame that is not [H, W, C]
        starts nothing and leaves the stream without a window."""
        if frame.data.ndim != 3:
            self._clips.pop(device_id, None)
            return None
        ring = self._clips[device_id] = _ClipRing(clip_len, frame.data.shape)
        t0 = time.perf_counter()
        ring.buf[0] = frame.data
        self._note_fill(t0, frame.data.nbytes, fresh=True)
        ring.advance()
        return ring

    def _take(self, device_id: str, model: str, clip_len: int,
              dst: np.ndarray, warm: bool, spill: List[tuple],
              window: int = 0):
        """One planned stream's newest unseen frame -> its batch row
        ``dst``. A single-frame stream is read by the bus straight into
        ``dst``, and so is a clip stream whose window is on the device
        (``window`` its length, ``clip_len`` 0: the frame is the sample);
        a clip stream with a host window into the slot of its ring that
        falls out of the window, and the full window is then copied into
        ``dst``, oldest frame first. ``warm``: ``dst`` is memory the
        collector has written before. Returns the frame's meta once
        ``dst`` holds the stream's sample, else None: no new frame (the
        window stays as it was: the slot offered to the bus is rewritten
        whole before it is served again), a window still filling, or a
        frame of another geometry, which goes to ``spill`` where it is a
        whole sample and starts a clip stream's window anew."""
        ring = None
        slot, slot_warm = dst, warm
        if clip_len:
            ring = self._clips.get(device_id)
            if ring is None or ring.buf.shape != dst.shape:
                # No window, or one of another length or geometry (the
                # stream was re-added with another model): never inherit.
                ring = self._clips[device_id] = _ClipRing(
                    clip_len, dst.shape[1:])
            slot, slot_warm = ring.buf[ring.pos], ring.full
        res = self._read_into(
            device_id, slot, self._cursors.get(device_id, 0), slot_warm)
        if res is None and self._rebase_if_restarted(device_id):
            res = self._read_into(device_id, slot, 0, slot_warm)
        if res is None:
            return None
        if isinstance(res, Frame):   # geometry drifted
            self._note_read(device_id, res.seq, res.meta)
            sample = res.data
            if sample.ndim == 3:     # corrupt 1-D frames must not poison
                # the geometry cache (the generic path guards the same)
                self._geom[device_id] = sample.shape
            if clip_len:
                ring = self._seed_ring(device_id, clip_len, res)
                if ring is None or not ring.full:
                    return None
                sample = ring.buf
            if window and sample.ndim != 3:
                self._window_breaks.append(device_id)
                return None
            spill.append((device_id, model, sample, res.meta, window))
            return None
        seq, meta = res
        self._note_read(device_id, seq, meta)
        if ring is not None:
            ring.advance()
            if not ring.full:
                return None
            t0 = time.perf_counter()
            ring.copy_to(dst)
            self._note_fill(t0, dst.nbytes, fresh=not warm)
        return meta

    def _device_window(self, model: str, clip_len: int) -> int:
        """``clip_len`` when this model's windows live on the device (the
        group then carries single frames and says ``window``), else 0."""
        if clip_len and self._device_windows is not None \
                and self._device_windows(model):
            return clip_len
        return 0

    def _stream_model(self, device_id: str):
        """(model name, clip_len) for one stream — per-stream override via
        the resolver (StreamProcess.inference_model), else engine default."""
        if self._model_of is not None:
            resolved = self._model_of(device_id)
            if resolved:
                return resolved
        return self._default_model, self._clip_len

    def restrict(self, device_ids: Optional[Sequence[str]]) -> None:
        self._only = set(device_ids) if device_ids else None

    def active_streams(self) -> List[str]:
        ids = self._bus.streams()
        if self._only is not None:
            ids = [d for d in ids if d in self._only]
        return sorted(ids)

    def _gated(self, device_id: str) -> bool:
        """True when this stream must NOT be inferred this tick: the
        operator switched it off (``inference_model: "none"``) or nothing
        consumes its results and the ``active_window_s`` linger expired."""
        model, _ = self._stream_model(device_id)
        if model == "none":
            return True
        if self._interest_of is None:
            return False
        now = time.monotonic()
        if self._interest_of(device_id):
            self._last_interest[device_id] = now
            return False
        last = self._last_interest.get(device_id)
        return last is None or now - last >= self._active_window_s

    def partition(self) -> tuple:
        """ONE bus enumeration -> (present, inferred): every listed
        stream, and the subset the engine will infer this tick. The
        engine's tick calls this once and threads the lists through
        keep_streams_hot / collect / its GC — on the Redis backend each
        enumeration is a SCAN and each gating check runs the model
        resolver, so repeating them per call triples control-plane
        traffic to a shared production server."""
        present = self.active_streams()
        return present, [d for d in present if not self._gated(d)]

    def inference_streams(self) -> List[str]:
        """Streams the engine will actually infer this tick."""
        return self.partition()[1]

    def keep_streams_hot(
        self, now_ms: Optional[int] = None,
        device_ids: Optional[Sequence[str]] = None,
    ) -> List[str]:
        """The engine is a frame consumer like any gRPC client: touching
        ``last_query`` keeps the ingest workers' lazy-decode gate open
        (reference semantics, ``python/rtsp_to_rtmp.py:144-145``) — but
        ONLY for streams it will actually infer. Touching a gated stream
        would hold every idle camera's decode valve open from inside the
        engine, defeating the lazy-decode CPU saving (round-2 verdict
        missing #4). ``device_ids``: a precomputed inferred set (from
        ``partition``); None re-enumerates."""
        ids = list(device_ids) if device_ids is not None \
            else self.inference_streams()
        for device_id in ids:
            self._bus.touch_query(device_id, now_ms)
        return ids

    # Failsafe: a caller that leases (collect() under strict_lease) but
    # never releases would grow a shape's pool without bound; past this
    # many live buffers per shape new handouts become one-off non-pooled
    # allocations (idx None — never tracked, never reused). The engine's
    # drain queue is depth-2, so steady state is 3-4; hitting the cap
    # means a leak and is logged.
    MAX_POOL_BUFFERS = 8

    def _begin_tick(self) -> None:
        """Start a new pool rotation epoch (called at collect() entry).
        Buffers backing the previous EMITTING tick's groups stay
        off-limits — the engine's double-buffered dispatch may still be
        reading them — and the new tick's handouts accumulate so no two
        same-shape groups within one tick can share a buffer. Idle ticks
        (cur drained by _unrotate) keep the existing protection window:
        consumers hold frames from the last tick that emitted, however
        long ago that was."""
        with self._pool_lock:
            for slot in self._pool.values():
                if slot["cur"]:
                    slot["prev"] = set(slot["cur"])
                    slot["cur"] = []

    def _pooled(self, shape: tuple):
        """Pooled batch buffer per shape -> (array, pool index). Reuse
        keeps the pages warm — a write into first-touched pages measured
        ~1 GB/s on the chip's host against 11.4 GB/s into a reused buffer
        (PERF.md section 6, PR 24). Every call
        within one tick gets a DISTINCT buffer (3 models on same-geometry
        cameras build 3+ same-shape groups per tick), nothing handed out
        the previous tick is reused, and under strict_lease nothing
        leased to an in-flight batch is reused until release(). The pool
        grows to the observed high-water mark — steady state 2 buffers
        for the common synchronous one-group case."""
        t0 = time.perf_counter()
        try:
            return self._pooled_locked(shape)
        finally:
            self._acc["fill_s"] += time.perf_counter() - t0

    def _pooled_locked(self, shape: tuple):
        with self._pool_lock:
            slot = self._pool.get(shape)
            if slot is None:
                slot = {"bufs": [], "prev": set(), "cur": [], "leased": [],
                        "fill": {}}
                self._pool[shape] = slot
            busy = set(slot["prev"])
            busy.update(slot["cur"])
            busy.update(slot["leased"])
            idx = next(
                (i for i in range(len(slot["bufs"])) if i not in busy), None
            )
            if idx is None:
                if len(slot["bufs"]) >= self.MAX_POOL_BUFFERS \
                        and slot["leased"]:
                    # Failsafe: leak containment. Stealing the oldest lease
                    # here would hand the SAME pages to a new batch while an
                    # in-flight dispatch may still be reading them (torn
                    # frames). A one-off non-pooled buffer costs the page
                    # faults the pool exists to avoid, but only on the
                    # already-broken leak path — correctness over speed.
                    import logging

                    logging.getLogger("vep.engine.collector").warning(
                        "batch pool for shape %s hit %d buffers; handing "
                        "out a one-off non-pooled buffer (a consumer is "
                        "not calling Collector.release)", shape,
                        self.MAX_POOL_BUFFERS,
                    )
                    return np.zeros(shape, np.uint8), None
                slot["bufs"].append(np.zeros(shape, np.uint8))
                idx = len(slot["bufs"]) - 1
            slot["cur"].append(idx)
            return slot["bufs"][idx], idx

    def pool_nbytes(self) -> int:
        """Total bytes held by the pooled batch buffers across shapes and
        by the clip rings — the obs/hbm.py ``register_pool`` tap for the
        collector's host staging memory (the canvas/batch buffers the
        device step reads from, and the windows clip batches are copied
        from). Sums live ``.nbytes`` under the pool lock so the figure
        is exact against the constituent arrays at any instant."""
        with self._pool_lock:
            pooled = sum(buf.nbytes for slot in self._pool.values()
                         for buf in slot["bufs"])
        # list(): the engine thread adds and drops rings meanwhile
        return pooled + sum(r.buf.nbytes for r in list(self._clips.values()))

    def _row_warm(self, shape: tuple, idx, clip_len: int) -> bool:
        """Is a row of this pool buffer memory the collector has written
        before? A single-frame row counts so whenever it is pooled (the
        count PR 25 fixed); a clip row once its buffer has been through a
        tick: ``fill`` gets the buffer's entry at its first zero-padding.
        A one-off failsafe buffer (``idx`` None) never is."""
        if idx is None or not clip_len:
            return idx is not None
        with self._pool_lock:
            return idx in self._pool[shape]["fill"]

    def _unrotate(self, shape: tuple) -> None:
        """No group was emitted from the last-handed-out buffer (every
        read came back empty): hand it back so idle ticks do not grow the
        pool or burn the one-tick safety margin for consumers still
        holding the previous tick's frames."""
        with self._pool_lock:
            slot = self._pool[shape]
            if slot["cur"]:
                slot["cur"].pop()

    def _lease(self, group: BatchGroup, shape: tuple, idx) -> None:
        """Under strict leasing, tie the group to its pooled buffer: the
        pool will not reuse it until release(group). ``idx`` None = the
        failsafe handed out a one-off non-pooled buffer — nothing to
        lease, release(group) stays a no-op."""
        if not self._strict_lease or idx is None:
            return
        with self._pool_lock:
            self._pool[shape]["leased"].append(idx)
            group.lease = (shape, idx)

    def release(self, group: BatchGroup) -> None:
        """Return a strict-leased group's buffer to the pool (called by
        the engine's drain thread once the batch is emitted — i.e. once
        nothing can still be reading the host frames). No-op for
        generic-path groups (fresh allocations: first sight, drift) and
        non-strict mode."""
        if group.lease is None:
            return
        shape, idx = group.lease
        group.lease = None
        with self._pool_lock:
            slot = self._pool.get(shape)
            if slot is not None:
                try:
                    slot["leased"].remove(idx)
                except ValueError:
                    pass   # double release / unknown lease: stay robust

    def _zero_pad_rows(self, buf: np.ndarray, shape: tuple, idx,
                       n: int, touched: int) -> None:
        """Zero only the pooled buffer rows that may actually be dirty,
        instead of memset-ing the full pad tail every tick: the pool
        tracks a per-buffer dirty high-water mark ("fill"), so a steady
        16-stream batch re-zeroes nothing and the ~100 MB/tick frame
        plane is touched exactly once (the bus copy). ``touched`` is the
        caller's per-tick attempt high-water — one past the highest slot
        any read_latest_into call targeted, including calls that did NOT
        join the batch: a drifted/raced read may leave a partial write in
        its target slot before the geometry check fails (bus/shm_bus.py
        seqlock reader copies before validating). Invariant after this
        call: rows >= n of ``buf`` are zero and fill[idx] == n. ``idx``
        None = one-off failsafe buffer, freshly np.zeros — nothing to
        do."""
        if idx is None:
            return
        touched = min(max(touched, n), buf.shape[0])
        with self._pool_lock:
            slot = self._pool.get(shape)
            if slot is None:                 # defensive: shape evicted
                dirty = buf.shape[0]
            else:
                fill = slot["fill"]
                # Fresh pool buffers are np.zeros => default high-water 0.
                dirty = max(fill.get(idx, 0), touched)
                fill[idx] = n
        if dirty > n:
            t0 = time.perf_counter()
            buf[n:dirty] = 0
            self._note_fill(t0, (dirty - n) * buf[0].nbytes, fresh=False)

    def _zero_pad_rows_sharded(self, buf: np.ndarray, shape: tuple, idx,
                               real: set, bucket: int, touched: int) -> None:
        """Shard-layout twin of _zero_pad_rows: padding is interleaved
        (each shard's segment pads independently), so instead of one
        contiguous tail the dirty rows are "every row in the dirty extent
        not carrying a real frame". Restores the pool invariant rows >=
        ``bucket`` are zero (fill[idx] == bucket) plus the sharded one:
        interior pad rows inside the view are zero."""
        touched = min(max(touched, bucket), buf.shape[0])
        dirty = touched
        if idx is not None:
            with self._pool_lock:
                slot = self._pool.get(shape)
                if slot is None:             # defensive: shape evicted
                    dirty = buf.shape[0]
                else:
                    fill = slot["fill"]
                    dirty = max(fill.get(idx, 0), touched)
                    fill[idx] = bucket
        t0 = time.perf_counter()
        zeroed = 0
        for r in range(dirty):
            if r not in real:
                buf[r] = 0
                zeroed += 1
        self._note_fill(t0, zeroed * buf[0].nbytes, fresh=idx is None)

    def _finish_sharded(self, buf: np.ndarray, shape: tuple, idx,
                        per: List[list], seg_src: int, bucket: int,
                        touched: int, *, src_hw: tuple,
                        model: str) -> BatchGroup:
        """Compact per-shard rows from allocation spacing (``seg_src``
        rows per shard) down to the final bucket's spacing, zero the
        dirty pad rows, and build the shard-segmented BatchGroup.
        ``per[s]`` is shard s's (device_id, meta) list in read order.
        Compaction is overlap-safe: with seg <= seg_src the destination
        row s*seg+i never exceeds the source row s*seg_src+i, and
        ascending (s, i) order means every source is read before any
        later destination could land on it."""
        seg = bucket // self._shards
        ids: List[str] = []
        metas: List[FrameMeta] = []
        rows: List[int] = []
        real: set = set()
        t0 = time.perf_counter()
        moved = 0
        for s, entries in enumerate(per):
            for i, (device_id, meta) in enumerate(entries):
                old = s * seg_src + i
                new = s * seg + i
                if new != old:
                    buf[new] = buf[old]
                    moved += 1
                ids.append(device_id)
                metas.append(meta)
                rows.append(new)
                real.add(new)
        self._note_fill(t0, moved * buf[0].nbytes, fresh=idx is None)
        self._zero_pad_rows_sharded(buf, shape, idx, real, bucket, touched)
        group = BatchGroup(
            src_hw=src_hw, device_ids=ids, frames=buf[:bucket],
            metas=metas, bucket=bucket, model=model, rows=rows,
        )
        self._lease(group, shape, idx)
        return group

    def _by_shard(self, devs: Sequence) -> List[list]:
        """Partition a stream list (or (device_id, ...) tuple list) into
        per-shard lists, preserving order within each shard."""
        out: List[list] = [[] for _ in range(self._shards)]
        fn = self._shard_fn
        for item in devs:
            did = item if isinstance(item, str) else item[0]
            s = stream_shard(did, self._shards) if fn is None else fn(did)
            out[s % self._shards].append(item)
        return out

    def repin(self, *, shards: int, shard_of,
              buckets: Optional[Sequence[int]] = None) -> None:
        """Survivor-mesh failover re-pin (device-fault domain, r22): swap
        the routing function and shard count in one tick-thread call.
        ``shard_of`` is a ``make_repin`` closure (or any stream -> shard
        map the engine installs — engine and collector MUST share it,
        same invariant as ``stream_shard``). The live assembly window is
        invalidated: its slot plan was laid out under the old routing and
        would land frames in segments the new mesh does not own; the
        frames are still on their rings and next tick's plan re-reads
        them (latest-wins, nothing lost). ``buckets`` replaces the bucket
        list (the survivor dp count divides a different subset); buckets
        not divisible by the new shard count are dropped, engine
        pre-filter convention."""
        self._window = None
        self._shards = max(1, int(shards))
        self._shard_fn = shard_of if self._shards > 1 else None
        if buckets is not None:
            sharded = tuple(sorted(
                b for b in buckets if b % self._shards == 0))
            if sharded:
                self._buckets = sharded

    # -- incremental batch assembly (between ticks) --

    def assemble_until(
        self, deadline: float, device_ids: Optional[Sequence[str]] = None,
        stop_event=None,
    ) -> None:
        """Overlap batch assembly with frame arrival (VERDICT r4 next
        #1b): instead of sleeping out the tick remainder and memcpy-ing
        every stream's frame at collect() time — which put the whole
        ~100 MB/tick frame plane between a camera's publish and its
        dispatch (pub_to_collect p50 3x the memcpy floor) — plan the next
        tick's batches now and copy each frame into its pooled slot the
        moment its producer publishes. The bus doorbell (futex on shm,
        condition on memory) wakes the sweep per publish with zero idle
        CPU; backends without a doorbell (Redis: every poll is a network
        round trip) sleep to the deadline and keep the collect-time path.

        Runs on the engine thread between ticks; ``deadline`` is
        time.monotonic-based; ``device_ids`` is the inferred set from
        partition() (a stream gated after planning still emits one last
        result at finalize — gating is linger-tolerant by design)."""
        remaining = deadline - time.monotonic()
        if not getattr(self._bus, "doorbell", False):
            if remaining > 0:
                if stop_event is not None:
                    stop_event.wait(remaining)
                else:
                    time.sleep(remaining)
            return
        if remaining <= 0:
            return
        self.plan_assembly(device_ids)
        token = self._bus.doorbell_token()
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return
            if stop_event is not None and stop_event.is_set():
                return
            token = self._bus.doorbell_wait(token, min(remaining, 0.1))
            self.assemble_step()

    def plan_assembly(
        self, device_ids: Optional[Sequence[str]] = None
    ) -> None:
        """Lay out next tick's fast-path batches: (model, geometry)
        grouping and bucket chunking identical to collect()'s, with a
        pooled buffer acquired per group. Single-frame streams only: a
        clip stream's batch row is copied from its ring at collect() (or,
        with its window on the device, read there: every frame read has
        to reach the window, and a second publish between ticks would
        overwrite one that was), and a stream of unknown geometry takes
        collect()'s generic path and joins the window next tick."""
        if device_ids is None:
            device_ids = self.inference_streams()
        buckets = self._effective_buckets()
        max_bucket = buckets[-1]
        fast_plan: Dict[tuple, list] = {}
        for device_id in device_ids:
            model, clip_len = self._stream_model(device_id)
            geom = self._geom.get(device_id)
            if not clip_len and geom is not None:
                fast_plan.setdefault((model, geom), []).append(device_id)
        groups: Dict[tuple, dict] = {}
        of: Dict[str, tuple] = {}
        shard_of: Dict[str, int] = {}
        for (model, geom), devs in sorted(fast_plan.items()):
            if self._shards > 1:
                # Shard-segmented window groups: chunk capacity is per
                # shard (a chunk fills when its fullest SHARD fills), and
                # a stream's slot is pinned inside its shard's segment.
                cap = max_bucket // self._shards
                by_shard = self._by_shard(devs)
                n_chunks = max(((len(l) + cap - 1) // cap
                                for l in by_shard if l), default=0)
                for ci in range(n_chunks):
                    chunk = [l[ci * cap:(ci + 1) * cap] for l in by_shard]
                    need = max(len(l) for l in chunk)
                    alloc = next(b for b in buckets
                                 if b // self._shards >= need)
                    shape = (alloc,) + geom
                    buf, bidx = self._pooled(shape)
                    key = (model, geom, ci)
                    groups[key] = {
                        "model": model, "geom": geom, "shape": shape,
                        "buf": buf, "idx": bidx,
                        "per": [[] for _ in range(self._shards)],
                        "entry": {}, "slot": {},
                        "seg": alloc // self._shards,
                        "hw": 0,   # attempt high-water
                    }
                    for s, shard_devs in enumerate(chunk):
                        for device_id in shard_devs:
                            of[device_id] = key
                            shard_of[device_id] = s
                continue
            for ci, start in enumerate(range(0, len(devs), max_bucket)):
                chunk = devs[start:start + max_bucket]
                alloc = next(b for b in buckets if b >= len(chunk))
                shape = (alloc,) + geom
                buf, bidx = self._pooled(shape)
                key = (model, geom, ci)
                groups[key] = {
                    "model": model, "geom": geom, "shape": shape,
                    "buf": buf, "idx": bidx,
                    "ids": [], "metas": [], "slot": {},
                    "hw": 0,   # attempt high-water for _zero_pad_rows
                }
                for device_id in chunk:
                    of[device_id] = key
        self._window = {"groups": groups, "of": of, "spill": [],
                        "shard": shard_of}

    def assemble_step(self) -> int:
        """One pass over the planned streams: copy any newly published
        frame straight into its group's next free slot (latest-wins: a
        second publish within the window overwrites the stream's slot).
        Returns how many frames were copied."""
        win = self._window
        if win is None:
            return 0
        got = 0
        drifted: List[str] = []
        for device_id, key in win["of"].items():
            cursor = self._cursors.get(device_id, 0)
            head = self._bus.head(device_id)
            if head is not None and head < cursor:
                # Ring recreated under us — see _rebase_if_restarted.
                self._cursors.pop(device_id, None)
                cursor = 0
            if head is not None and head <= cursor:
                continue   # idle ring: one cheap load, no read setup
            g = win["groups"][key]
            slot = g["slot"].get(device_id)
            sharded = "per" in g
            if sharded:
                s = win["shard"][device_id]
                t = slot if slot is not None \
                    else g["seg"] * s + len(g["per"][s])
            else:
                t = slot if slot is not None else len(g["ids"])
            g["hw"] = max(g["hw"], t + 1)   # slot t may get partial bytes
            res = self._read_into(device_id, g["buf"][t], cursor,
                                  pooled=g["idx"] is not None)
            if res is None:
                continue
            if isinstance(res, Frame):   # geometry drifted mid-window
                self._note_read(device_id, res.seq, res.meta)
                if res.data.ndim == 3:
                    self._geom[device_id] = res.data.shape
                win["spill"].append(
                    (device_id, g["model"], res.data, res.meta, 0))
                drifted.append(device_id)
                continue
            seq, meta = res
            self._note_read(device_id, seq, meta)
            if sharded:
                if slot is None:
                    g["slot"][device_id] = t
                    g["entry"][device_id] = (s, len(g["per"][s]))
                    g["per"][s].append((device_id, meta))
                else:
                    es, ei = g["entry"][device_id]
                    g["per"][es][ei] = (device_id, meta)
            elif slot is None:
                g["slot"][device_id] = len(g["ids"])
                g["ids"].append(device_id)
                g["metas"].append(meta)
            else:
                g["metas"][slot] = meta
            got += 1
        for device_id in drifted:
            del win["of"][device_id]
        return got

    def collect(
        self, device_ids: Optional[Sequence[str]] = None,
        sink: Optional[Callable[[BatchGroup], None]] = None,
    ) -> List[BatchGroup]:
        """One tick: newest unseen frame per stream -> (model, shape)-
        grouped, bucket-padded batches (clips for video models).
        ``device_ids``: precomputed inferred set (from ``partition``);
        None re-enumerates. ``sink``, where given, is called with each
        group the moment it is finished (its last frame read, its pad rows
        zeroed, its buffer leased), in the order of the returned list and
        before the next group's first frame is read: the engine starts the
        group's placement there, beside the reads that follow. The groups,
        their buffers and leases, ``last_trace`` and every byte count are
        the same with and without one.

        Hot path: a stream whose geometry is known from a previous tick
        is planned under (model, geometry, clip_len) and served from
        pooled batch buffers (``_take``). A window of one frame is read
        by the bus DIRECTLY into its batch row (`read_latest_into`) —
        ring to device batch in one memory pass, and so is the new frame
        of a window that lives on the device (``device_windows``: the
        group says ``window``); a window of ``clip_len`` frames on the
        host goes through the stream's clip ring: the new frame over the
        oldest, then the ring into the row. First sight of a stream and
        geometry drift take the generic frame path and join the hot path
        next tick."""
        if device_ids is None:
            device_ids = self.inference_streams()
        acc = self._acc
        acc["read_ahead_s"] = acc["read_s"]   # assemble_step, between ticks
        self._begin_tick()
        buckets = self._effective_buckets()
        max_bucket = buckets[-1]

        groups: List[BatchGroup] = []

        def done(group: BatchGroup) -> None:
            # the one place a finished group leaves the collector
            groups.append(group)
            if sink is not None:
                sink(group)

        spill: List[tuple] = []             # geometry drifted mid-plan
        win_planned: set = set()
        win = self._window
        if win is not None:
            # Finalize the assembly window: one catch-up sweep for frames
            # published since the last doorbell wake, then emit the
            # incrementally filled batches as-is — their copies already
            # happened, overlapped with arrival.
            self.assemble_step()
            self._window = None
            win_planned = set(win["of"])
            spill.extend(win["spill"])
            for key, g in sorted(win["groups"].items()):
                if "per" in g:   # shard-segmented window group
                    counts = [len(p) for p in g["per"]]
                    if not any(counts):
                        continue   # idle; buffer ages out via epochs
                    bucket = next(b for b in self._buckets
                                  if b // self._shards >= max(counts))
                    done(self._finish_sharded(
                        g["buf"], g["shape"], g["idx"], g["per"],
                        g["seg"], bucket, g["hw"],
                        src_hw=g["geom"][:2], model=g["model"]))
                    continue
                n = len(g["ids"])
                if n == 0:
                    continue   # idle group; its buffer ages out via epochs
                # Full bucket list, NOT the capped one: the window buffer
                # was allocated before a cap could land, and its alloc is
                # always a member of the full list >= n.
                bucket = next(b for b in self._buckets if b >= n)
                self._zero_pad_rows(g["buf"], g["shape"], g["idx"], n,
                                    g["hw"])
                view = g["buf"][:bucket]
                group = BatchGroup(
                    src_hw=g["geom"][:2], device_ids=g["ids"],
                    frames=view, metas=g["metas"], bucket=bucket,
                    model=g["model"],
                )
                self._lease(group, g["shape"], g["idx"])
                done(group)

        # (model, (h, w, c), clip_len) -> [ids]: one plan for every stream
        # whose geometry is known; the window length is the model spec's.
        fast_plan: Dict[tuple, list] = {}
        slow_ids: List[str] = []
        for device_id in device_ids:
            if device_id in win_planned:
                continue   # already served (or known idle) via the window
            model, clip_len = self._stream_model(device_id)
            geom = self._geom.get(device_id)
            if geom is None:
                slow_ids.append(device_id)
            else:
                fast_plan.setdefault(
                    (model, geom, clip_len), []).append(device_id)

        # Single frames first, then windows by length (the order groups had
        # when clips still took the generic path): a small batch's results
        # do not wait behind the placement of a clip batch.
        for (model, geom, clip_len), devs in sorted(
                fast_plan.items(), key=lambda kv: (kv[0][2],) + kv[0][:2]):
            if self._shards > 1:
                self._collect_fast_sharded(
                    model, geom, clip_len, devs, buckets, done, spill)
                continue
            # a window on the device: the sample the host ships is a frame
            window = self._device_window(model, clip_len)
            if window:
                clip_len = 0
            sample = ((clip_len,) + geom) if clip_len else geom
            for start in range(0, len(devs), max_bucket):
                chunk = devs[start:start + max_bucket]
                alloc = next(b for b in buckets if b >= len(chunk))
                shape = (alloc,) + sample
                batch, bidx = self._pooled(shape)
                warm = self._row_warm(shape, bidx, clip_len)
                ids: List[str] = []
                metas: List[FrameMeta] = []
                hw = 0   # attempt high-water for _zero_pad_rows
                for device_id in chunk:
                    if not clip_len:   # the bus writes into the row itself
                        hw = max(hw, len(ids) + 1)
                    meta = self._take(device_id, model, clip_len,
                                      batch[len(ids)], warm, spill, window)
                    if meta is not None:
                        ids.append(device_id)
                        metas.append(meta)
                n = len(ids)
                if not n:
                    if bidx is not None:
                        # One-off failsafe buffers never entered "cur";
                        # unrotating would pop a legitimate same-tick entry.
                        self._unrotate(shape)
                    continue
                bucket = next(b for b in buckets if b >= n)
                self._zero_pad_rows(batch, shape, bidx, n, hw)
                view = batch[:bucket]
                group = BatchGroup(
                    src_hw=geom[:2], device_ids=ids, frames=view,
                    metas=metas, bucket=bucket, model=model, window=window,
                )
                self._lease(group, shape, bidx)
                done(group)

        # Generic path: first sight (geometry unknown) and drift.
        first_sight: List[tuple] = []
        for device_id in slow_ids:
            frame = self._read(device_id, self._cursors.get(device_id, 0))
            if frame is None and self._rebase_if_restarted(device_id):
                frame = self._read(device_id, 0)
            if frame is None:
                continue
            self._note_read(device_id, frame.seq, frame.meta)
            model, clip_len = self._stream_model(device_id)
            if frame.data.ndim == 3:
                self._geom[device_id] = frame.data.shape
            sample = frame.data
            window = self._device_window(model, clip_len)
            if window:
                # the frame is the sample: the window it opens is on the
                # device, as every later frame's
                if sample.ndim != 3:
                    self._window_breaks.append(device_id)
                    continue
            elif clip_len:
                ring = self._seed_ring(device_id, clip_len, frame)
                if ring is None or not ring.full:
                    continue
                sample = ring.buf   # a window of one frame: full at once
            first_sight.append((device_id, model, sample, frame.meta, window))
        # (model, sample shape, device window length) -> items
        by_key: Dict[tuple, list] = {}
        for device_id, model, sample, meta, window in first_sight + spill:
            by_key.setdefault((model, sample.shape, window), []).append(
                (device_id, sample, meta)
            )
        for (model, shape, window), items in sorted(by_key.items()):
            hw = shape[:-1][-2:]    # of [H, W, C] or [clip_len, H, W, C]
            self._collect_generic(model, hw, items, buckets, done, window)
        self.last_trace, self._acc = acc, _new_trace()
        return groups

    def _collect_fast_sharded(self, model: str, geom: tuple, clip_len: int,
                              devs: Sequence[str], buckets: tuple,
                              done: Callable[[BatchGroup], None],
                              spill: List[tuple]) -> None:
        """Shard-segmented fast path: one (model, geometry, clip_len)
        stream set -> pooled, bucket-padded, shard-segmented batches.
        Streams land (``_take``, as in the dense layout) in their shard's
        segment at allocation spacing; the final bucket is the smallest
        whose PER-SHARD segment covers the fullest shard, then
        _finish_sharded compacts the segments down."""
        S = self._shards
        sample = ((clip_len,) + geom) if clip_len else geom
        max_bucket = buckets[-1]
        cap = max_bucket // S        # per-shard chunk capacity
        by_shard = self._by_shard(devs)
        n_chunks = max(((len(l) + cap - 1) // cap for l in by_shard if l),
                       default=0)
        for c in range(n_chunks):
            chunk = [l[c * cap:(c + 1) * cap] for l in by_shard]
            need = max(len(l) for l in chunk)
            alloc = next(b for b in buckets if b // S >= need)
            shape = (alloc,) + sample
            batch, bidx = self._pooled(shape)
            warm = self._row_warm(shape, bidx, clip_len)
            seg_a = alloc // S
            per: List[list] = [[] for _ in range(S)]
            touched = 0   # attempt high-water (one past highest row hit)
            for s, shard_devs in enumerate(chunk):
                for device_id in shard_devs:
                    t = s * seg_a + len(per[s])
                    if not clip_len:   # the bus writes into the row itself
                        touched = max(touched, t + 1)
                    meta = self._take(device_id, model, clip_len, batch[t],
                                      warm, spill)
                    if meta is not None:
                        per[s].append((device_id, meta))
            counts = [len(p) for p in per]
            if not any(counts):
                if bidx is not None:
                    self._unrotate(shape)
                continue
            bucket = next(b for b in buckets if b // S >= max(counts))
            done(self._finish_sharded(
                batch, shape, bidx, per, seg_a, bucket, touched,
                src_hw=geom[:2], model=model))

    def _collect_generic(self, model: str, hw: tuple,
                         items: Sequence[tuple], buckets: tuple,
                         done: Callable[[BatchGroup], None],
                         window: int = 0) -> None:
        """Generic path (first sight, drift): whole samples, each already
        in an array of its own, into a fresh zeroed buffer at final-bucket
        spacing — no compaction needed, pad rows already zero. One shard
        is the dense identity layout (``rows`` None)."""
        S = self._shards
        cap = buckets[-1] // S
        by_shard = self._by_shard(items)
        n_chunks = max(((len(l) + cap - 1) // cap for l in by_shard if l),
                       default=0)
        for c in range(n_chunks):
            chunk = [l[c * cap:(c + 1) * cap] for l in by_shard]
            need = max(len(l) for l in chunk)
            bucket = next(b for b in buckets if b // S >= need)
            seg = bucket // S
            first = next(l[0] for l in chunk if l)
            t0 = time.perf_counter()
            batch = np.zeros((bucket,) + first[1].shape, first[1].dtype)
            ids: List[str] = []
            metas: List[FrameMeta] = []
            rows: List[int] = []
            for s, shard_items in enumerate(chunk):
                for i, (device_id, arr, meta) in enumerate(shard_items):
                    batch[s * seg + i] = arr
                    ids.append(device_id)
                    metas.append(meta)
                    rows.append(s * seg + i)
            self._note_fill(t0, batch.nbytes, fresh=True)
            done(BatchGroup(
                src_hw=hw, device_ids=ids, frames=batch, metas=metas,
                bucket=bucket, model=model, rows=rows if S > 1 else None,
                window=window,
            ))

    def take_window_breaks(self) -> List[str]:
        """Streams with a device window whose read frame could not be
        handed on since the last call (not [H, W, C]): the caller starts
        their windows anew."""
        if not self._window_breaks:
            return []
        out, self._window_breaks = self._window_breaks, []
        return out

    def drop_stream(self, device_id: str) -> None:
        self._cursors.pop(device_id, None)
        self._clips.pop(device_id, None)
        self._geom.pop(device_id, None)
        self._last_interest.pop(device_id, None)
