"""TPU inference engine: the plane the reference doesn't have.

The reference ships raw BGR24 frames to external CPU clients and calls it a
day (`/root/reference/README.md:5-27`); results only re-enter the system if
the client pushes `Annotate` events. This engine closes that loop on-device
(BASELINE.json north star): collector output crosses PCIe as uint8, and one
jitted program per (bucket, source-geometry) does preprocess → forward →
postprocess (Pallas NMS for detectors) on the TPU. Results fan out to

- gRPC `Inference` subscribers (serve/grpc_api.py), and
- the annotation uplink queue, as the same `AnnotateRequest` protos an
  external ML client would have sent — so the reference's cloud pipeline
  (`examples/annotation.py` shape) keeps working with zero client code.

Latency pipeline: JAX dispatch is async — the engine thread submits each
tick's batches and hands them to a dedicated drain thread that blocks on
the device outputs and emits the moment the device finishes (event-driven
drain). H2D/compute for tick N+1 overlaps D2H/postprocess for tick N
(double buffering, SURVEY.md §7 hard part 2) WITHOUT parking results
until the next tick boundary — the r4-measured full-tick drain deferral
(~tick_ms of p50) is gone. Where the device is slower than the tick rate
the engine thread waits BEFORE it reads (engine/pacing.py: a round's
frames are read so that their placement ends as the device frees, and
that wait is the backpressure the ladder hears); the depth-2 drain
queue, beyond which the engine thread blocks, is the hard limit behind
it. Collector buffers
backing in-flight batches are strict-leased and released by the drain
thread after emit, so a deep pipeline can never alias host frames.
"""

from __future__ import annotations

import os
import queue
import tempfile
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from ..bus.interface import FrameBus, FrameMeta
from ..obs import registry as obs_registry, tracer
from ..obs.spans import trace_id_of
from ..obs.perf import PerfTracker
from ..obs import stages
from ..obs.prof import Profiler
from ..obs.slo import SLOEngine, default_slos
from ..obs.watch import Watchdog
from ..ops.nms import batched_nms
from ..ops.preprocess import (
    frame_quality_stats, preprocess_classify, preprocess_clip,
    preprocess_letterbox, preprocess_letterbox_fused, unletterbox_boxes,
)
from ..proto import pb
from ..resilience.ladder import RUNGS, DegradationLadder
from ..utils.config import EngineConfig
from ..utils.logging import get_logger, reset_log_context, set_log_context
from .classes import class_name
from .collector import BatchGroup, CanvasPacker, Collector, pad_to_bucket
from .pacing import ReadPacer
from .stream_state import (
    ClipWindowPool, StreamStatePool, _ShardedThumbPool, _ThumbPool)

log = get_logger("engine.runner")

TOP_K_CLASSES = 5


def _rebox(template, values):
    """Re-attach flax AxisMetadata boxes (logical sharding names) from
    ``template`` onto the raw arrays in ``values`` — the inverse of
    ``parallel.sharding.unbox`` for checkpoint restore."""
    import flax.linen as nn
    import jax

    return jax.tree_util.tree_map(
        lambda box, val: box.replace_boxed(val)
        if isinstance(box, nn.meta.AxisMetadata) else val,
        template, values,
        is_leaf=lambda x: isinstance(x, nn.meta.AxisMetadata),
    )


def build_serving_step(model, spec, *, quality_thumb: int = 0, mesh=None,
                       window: bool = False):
    """The per-tick device program for one model kind: uint8 frames in,
    postprocessed results out. SINGLE source of truth — the engine compiles
    it per (geometry, bucket), bench.py times it, __graft_entry__ exposes
    it, so all three always run the identical program.

    With ``quality_thumb`` > 0 (engine.quality_thumb config) the returned
    step takes an optional third argument — the previous tick's [N, th, tw]
    f32 luma thumbnails (omitted → zeros, so two-arg callers still work) —
    and its output gains ``quality_stats`` / ``quality_thumbs``
    (ops/preprocess.py frame_quality_stats), so per-frame health statistics
    ride the existing result transfer. The default two-argument signature
    is byte-identical to before, which keeps bench.py, __graft_entry__ and
    the replay goldens pinning the same program; ``device_checksum`` keys
    off the detect/embed/classify signature keys and ignores the extras.
    Clip-input specs (5-d frames) never carry stats — their streams get
    detections-only verdicts (obs/quality.py).

    ``mesh`` (engine.mesh serving): on a dp-only mesh the step runs as a
    ``shard_map`` over ``dp`` — every slice executes the single-chip
    program on its own rows (rows are independent, params replicated),
    bit-identical to one chip. Not a style choice: the TPU compiler
    refuses to partition a Mosaic kernel automatically ("wrap the call in
    a shard_map"), so the jit-partitioned form of the detect step, Pallas
    NMS inside, does not compile for a real mesh. A mesh that also shards
    the model (tp/fsdp/sp/ep > 1) keeps the compiler's partitioning: it
    serves the transformer families, which carry no such kernel below
    ``FLASH_THRESHOLD_T`` tokens.

    Step kind ``stream`` (models/lfm2.py, models/xing4.py): a step with
    state in and state out,
    ``stream_step(variables, frames, state, idx, pos0, reset, rounds)``.
    ``state`` is the model's ``StreamStatePool`` buffers by name (the kinds
    its ``empty_state`` declares and the pool's ``tokens`` [slots, ...];
    the caller donates them), ``idx``
    [bucket] the slot of each batch row, ``pos0`` where its visual tokens
    start, ``reset`` which rows start a new context, ``rounds`` the rounds
    since each row's reset (engine/stream_state.py ``plan``). Encoder,
    connector, prefill and the D decode steps are ONE program a batch; the
    output carries ``state`` (the same buffers, rewritten in place), which
    the dispatcher takes back before the drain fetches the rest. One chip
    only: experts over a real ``ep`` mesh are PERF.md's open question.

    ``window`` (the one-device engine's fast path, for a clip-taking
    spec): the step's windowed form, ``_windowed``. Each stream's
    ``clip_len``-frame window is device state (engine/stream_state.py
    ``ClipWindowPool``): the batch carries ONE new frame a row, the step
    writes it into the window and reads the window back in time order,
    and the body below runs unchanged on what it read. Without it the
    clip step takes whole windows, ``raw(variables, clips_u8)``, as
    bench.py, __graft_entry__, the replay goldens and mesh serving do."""
    import jax

    if window:
        if mesh is not None or not spec.clip_len:
            raise ValueError(
                f"model {spec.name!r}: a device window serves clips on one "
                "chip")
        return _windowed(build_serving_step(
            model, spec, quality_thumb=quality_thumb))
    if mesh is not None and all(
            n == 1 for a, n in mesh.shape.items() if a != "dp"):
        return _dp_sharded(
            build_serving_step(model, spec, quality_thumb=quality_thumb),
            mesh)
    size = spec.input_size

    if spec.kind == "stream":
        if mesh is not None:
            raise ValueError(
                f"model {spec.name!r}: a stream head serves on one chip")
        return _build_stream_step(model, size)
    if spec.kind == "detect":
        # Stem-variant dispatch (round 15): an s2d-stem model gets the
        # fused letterbox+normalize+s2d megakernel — the 1080p uint8
        # plane is read exactly once and the stem consumes the folded
        # 320²x12 plane directly. The classic path below stays
        # byte-identical (replay checksums pin it bit-for-bit).
        fused = getattr(getattr(model, "cfg", None), "stem", "classic") == "s2d"

        def raw(variables, frames_u8):
            if fused:
                x, lb = preprocess_letterbox_fused(frames_u8, size)
            else:
                x, lb = preprocess_letterbox(frames_u8, size)
            # decode="serving" (models/yolov8.py): class reduction happens
            # in logit space inside the model; sigmoid is monotone, so
            # applying it to the per-anchor winners here gives the same
            # scores as decode=True's full sigmoid at a fraction of the
            # elementwise work.
            boxes, max_logit, cls_ids = model.apply(
                variables, x, decode="serving"
            )
            b, s, c, valid = batched_nms(
                boxes, jax.nn.sigmoid(max_logit), cls_ids
            )
            b = unletterbox_boxes(b, lb)
            return {"boxes": b, "scores": s, "classes": c, "valid": valid}
    elif spec.kind == "embed":
        def raw(variables, frames_u8):
            x = preprocess_classify(frames_u8, (size, size))
            emb = model.apply(variables, x, features_only=True)
            return {"embedding": emb}
    else:  # classify | video
        pre = preprocess_clip if spec.clip_len else preprocess_classify

        def raw(variables, frames_u8):
            import jax.numpy as jnp

            x = pre(frames_u8, (size, size))
            logits = model.apply(variables, x)
            with jax.named_scope("softmax_topk"):
                probs = jax.nn.softmax(logits, axis=-1)
                top_p, top_i = jax.lax.top_k(
                    probs, min(TOP_K_CLASSES, probs.shape[-1])
                )
                return {"top_probs": top_p,
                        "top_ids": top_i.astype(jnp.int32)}

    if not quality_thumb or spec.clip_len:
        return raw

    thumb_hw = (quality_thumb, quality_thumb)

    def with_stats(variables, frames_u8, prev_thumbs=None, _raw=raw):
        import jax.numpy as jnp

        out = dict(_raw(variables, frames_u8))
        if prev_thumbs is None:
            # Two-arg call (existing callers, warm-start): diff against a
            # zero thumbnail; the host tracker discards the first diff
            # sample anyway (obs/quality.py first-sample rule).
            prev_thumbs = jnp.zeros(
                (frames_u8.shape[0],) + thumb_hw, jnp.float32
            )
        stats, thumbs = frame_quality_stats(frames_u8, prev_thumbs, thumb_hw)
        out["quality_stats"] = stats
        out["quality_thumbs"] = thumbs
        return out

    return with_stats


def _build_stream_step(model, size: int):
    """The ``stream`` step kind (``build_serving_step``)."""
    import jax.numpy as jnp

    steps = model.cfg.decode_steps

    def stream_step(variables, frames_u8, state, idx, pos0, reset, rounds):
        take = lambda a: jnp.take(a, idx, axis=0, mode="clip")  # noqa: E731
        # the model's kinds of state go in and come back by name (each
        # read and written by slot inside the round); the token history is
        # the pool's own
        out = model.serve_round(
            variables, frames_u8,
            {k: v for k, v in state.items() if k != "tokens"},
            idx, pos0, reset,
            preprocess=lambda clips: preprocess_clip(
                clips, (size, size), out_dtype=model.dtype))
        # the ids decoded since the reset, this round's last
        history = jnp.where(reset[:, None], -1, take(state["tokens"]))
        at = jnp.arange(history.shape[1])[None] // steps
        history = jnp.where(
            at == rounds[:, None],
            jnp.tile(out["tokens"], (1, history.shape[1] // steps)), history)
        out["state"]["tokens"] = state["tokens"].at[idx].set(
            history, mode="drop")
        out["history"] = history
        out["rounds"] = rounds + 1
        out["positions"] = pos0 + model.cfg.round_positions
        return out

    return stream_step


def window_write(window, frames_u8, idx, pos):
    """Row i's frame into ``window[idx[i], pos[i]]``; a padded row carries
    ``idx = slots`` and is dropped (as ``StreamStatePool.plan`` pads).
    The windowed step's first half, and a program of its own for a batch
    whose windows are all still filling: its frames are written and
    nothing is computed (``InferenceEngine._dispatch``). The caller
    donates ``window``: same shape and dtype out, rewritten in place."""
    import jax

    with jax.named_scope("window_write"):
        return window.at[idx, pos].set(frames_u8, mode="drop")


def _windowed(step):
    """The windowed form of a clip-taking step (``build_serving_step``):
    ``(variables, frames_u8 [bucket, H, W, C], window [slots, clip_len, H,
    W, C], idx, pos, *rest)`` -> the step's outputs + ``window``.

    One program: the new frames are written (``window_write``), every
    row's window is read back oldest frame first (the slot after the one
    just written holds the oldest) by a loop of whole-frame copies into
    one [bucket, clip_len, ...] temporary, so the pool itself is never
    shifted, and the unchanged ``step(variables, clips_u8, *rest)`` runs
    on that. The caller donates ``window``; it comes back the same shape
    and dtype, so it is rewritten in place. The jitted program keeps the
    step's name (a device trace finds it where it found the step)."""
    import jax
    import jax.numpy as jnp

    def windowed(variables, frames_u8, window, idx, pos, *rest):
        slots, clip_len = window.shape[:2]
        frame = window.shape[2:]
        window = window_write(window, frames_u8, idx, pos)
        with jax.named_scope("window_copy"):
            order = (pos[:, None] + 1 + jnp.arange(clip_len)) % clip_len
            src = idx[:, None] * clip_len + order  # a padded row is clipped
            src = jnp.minimum(src, slots * clip_len - 1).reshape(-1)
            flat = window.reshape((slots * clip_len,) + frame)
            zero = (0,) * len(frame)

            def copy(i, clips):
                one = jax.lax.dynamic_slice(flat, (src[i],) + zero,
                                            (1,) + frame)
                return jax.lax.dynamic_update_slice(clips, one, (i,) + zero)

            clips = jax.lax.fori_loop(
                0, src.shape[0], copy,
                jnp.zeros((src.shape[0],) + frame, window.dtype))
        out = dict(step(variables, clips.reshape(
            (idx.shape[0], clip_len) + frame), *rest))
        out["window"] = window
        return out

    windowed.__name__ = step.__name__
    windowed.__qualname__ = step.__qualname__
    return windowed


def _dp_sharded(step, mesh):
    """``step(variables, frames[, prev_thumbs])`` as a shard_map over a
    dp-only mesh: batch-leading arguments and every output split over
    dp, variables replicated. Fully manual — leaving the size-1 axes to
    the compiler changes its fusion choices and costs the bit-identity
    with the single-chip program (1 ulp in the scores, measured)."""
    import jax
    from jax.sharding import PartitionSpec as P

    def sharded(variables, frames_u8, *rest):
        return jax.shard_map(
            step, mesh=mesh,
            in_specs=(P(), P("dp")) + (P("dp"),) * len(rest),
            out_specs=P("dp"), check_vma=False,
        )(variables, frames_u8, *rest)

    return sharded


_RUNG_IDX = {r: i for i, r in enumerate(RUNGS)}

# Once-per-process memo for _note_feature_disabled: engine restarts within
# one process (tests, soak harnesses) would otherwise re-log every
# construction, and dashboards only need the gauge, not the log scrape.
_FEATURES_NOTED: set = set()


def _note_feature_disabled(feature: str, reason: str) -> None:
    """Surface an auto-disabled engine feature as a gauge
    (``vep_engine_feature_disabled{feature,reason}`` == 1) plus ONE
    process-lifetime log line — fleet dashboards watch the metric, not
    per-startup warnings."""
    obs_registry.gauge(
        "vep_engine_feature_disabled",
        "1 when an engine feature auto-disabled itself (see reason label)",
        ("feature", "reason"),
    ).labels(feature, reason).set(1.0)
    key = (feature, reason)
    if key not in _FEATURES_NOTED:
        _FEATURES_NOTED.add(key)
        log.info("%s: disabled (%s); vep_engine_feature_disabled gauge set",
                 feature, reason)


def admitted_streams(
    inferred: Sequence[str], deprioritized: Sequence[str] = (),
) -> List[str]:
    """Degradation-ladder rung 3 (admission_pause): admit a deterministic
    half of the streams — the first half of the sorted id list, so the
    SAME streams stay admitted across ticks (stable batches, no
    membership thrash) and recovery resumes the rest. One stream never
    pauses (shedding the whole fleet is an outage, not a degradation).

    ``deprioritized`` streams (quality-unhealthy: black/frozen per
    obs/quality.py — their frames carry no recoverable signal) sort
    BEHIND every healthy stream, making them the first-shed candidates;
    with no deprioritized set the result is byte-identical to before."""
    dep = set(deprioritized)
    ids = sorted(inferred, key=lambda d: (d in dep, d))
    if len(ids) <= 1:
        return ids
    return sorted(ids[: (len(ids) + 1) // 2])


def shed_stale(group: BatchGroup, now_ms: float, max_staleness_ms: float,
               buckets: Sequence[int], shards: int = 1):
    """Degradation-ladder rung 1: drop frames older than the staleness
    bound from a collected group BEFORE dispatch (oldest-first by
    construction — only stale rows leave). Fresh rows compact in place
    within the pooled buffer view (the lease is untouched) and the view
    re-slices to the smallest covering bucket. Returns ``(group, shed)``;
    group is None when every row was stale (caller releases the lease).
    Frames without a publish timestamp are treated as fresh. Shed rows
    close their lineage with a terminal ``dropped`` span (r14 bugfix:
    the per-stream ring used to keep the span open forever, so trace
    export and stage_breakdown undercounted drops)."""
    keep = [
        i for i, m in enumerate(group.metas)
        if not m.timestamp_ms or now_ms - m.timestamp_ms <= max_staleness_ms
    ]
    shed = len(group.metas) - len(keep)
    if shed == 0:
        return group, 0
    if tracer.enabled:
        kept = set(keep)
        for i, m in enumerate(group.metas):
            if i not in kept and tracer.sampled(m.packet):
                tracer.record(
                    group.device_ids[i], "dropped", m.packet,
                    reason="stale_shed", trace_id=trace_id_of(
                        m, group.device_ids[i]))
    if not keep:
        return None, shed
    if group.rows is not None and shards > 1:
        return _compact_sharded(group, keep, buckets, shards), shed
    for new_i, old_i in enumerate(keep):
        if new_i != old_i:
            group.frames[new_i] = group.frames[old_i]
    group.device_ids = [group.device_ids[i] for i in keep]
    group.metas = [group.metas[i] for i in keep]
    n = len(keep)
    bucket = next(b for b in sorted(buckets) if b >= n)
    view = group.frames[:bucket]
    if bucket != n:
        view[n:] = 0
    group.frames = view
    group.bucket = bucket
    return group, shed


def _compact_sharded(group: BatchGroup, keep: List[int],
                     buckets: Sequence[int], shards: int) -> BatchGroup:
    """Keep-list compaction for shard-segmented groups (r17), shared by
    rung-1 stale shedding and the ROI full-row path: surviving rows
    compact WITHIN their shard's segment (a row must never migrate to
    another chip's slice), and the group re-slices to the smallest
    bucket whose per-shard segment covers the fullest shard. Compaction
    runs low-to-high global row, so every move reads an untouched
    source (same in-place discipline as the identity-layout path)."""
    seg_src = group.bucket // shards
    per: Dict[int, List[int]] = {}
    for i in keep:
        per.setdefault(group.rows[i] // seg_src, []).append(i)
    k_max = max(len(v) for v in per.values())
    bucket = next(
        b for b in sorted(buckets)
        if b % shards == 0 and b // shards >= k_max
    )
    seg = bucket // shards
    moves = []       # (dst_row, slot i) sorted by source row below
    for s, slots in per.items():
        for j, i in enumerate(slots):
            moves.append((s * seg + j, i))
    # seg <= seg_src, so dst <= src slotwise within a shard and shards
    # only move down: processing in ascending source-row order never
    # overwrites a pending source.
    moves.sort(key=lambda m: group.rows[m[1]])
    occupied = set()
    for dst, i in moves:
        src = group.rows[i]
        if dst != src:
            group.frames[dst] = group.frames[src]
        occupied.add(dst)
    group.device_ids = [group.device_ids[i] for _, i in moves]
    group.metas = [group.metas[i] for _, i in moves]
    group.rows = [dst for dst, _ in moves]
    view = group.frames[:bucket]
    for r in range(bucket):
        if r not in occupied:
            view[r] = 0
    group.frames = view
    group.bucket = bucket
    return group


@dataclass
class StreamStats:
    frames: int = 0
    last_latency_ms: float = 0.0
    ema_latency_ms: float = 0.0
    last_batch: int = 0
    # Per-stream device attribution (r9): padding waste and device time
    # of the batches that served this stream, so /api/v1/stats can say
    # which streams ride under-filled (expensive) buckets.
    padded_slots: int = 0          # zero-padded slots in the last batch
    device_ms_ema: float = 0.0
    device_ms_initialized: bool = False
    # Monotonic time of the last emitted result — the availability-SLO
    # signal (obs/slo.py): an inferred stream that stops emitting goes
    # "unavailable" after slo_availability_window_s.
    last_emit_mono: float = 0.0
    # A first frame CAN legitimately measure 0.0 ms (synthetic sources
    # stamp publish-time wall clock; sub-ms emit rounds to 0) — the seed
    # flag, not the value, decides whether the EMA re-seeds.
    ema_initialized: bool = False

    def note_latency(self, latency_ms: float) -> None:
        self.last_latency_ms = latency_ms
        if self.ema_initialized:
            self.ema_latency_ms = (
                0.9 * self.ema_latency_ms + 0.1 * latency_ms)
        else:
            self.ema_latency_ms = latency_ms
            self.ema_initialized = True

    def note_device(self, device_ms: float, padded_slots: int) -> None:
        self.padded_slots = padded_slots
        if self.device_ms_initialized:
            self.device_ms_ema = 0.9 * self.device_ms_ema + 0.1 * device_ms
        else:
            self.device_ms_ema = device_ms
            self.device_ms_initialized = True


@dataclass(frozen=True)
class StreamStatsView:
    """Immutable point-in-time copy handed out by `stats()`. The live
    `StreamStats` objects are mutated by the drain thread; sharing them
    with API handlers let a caller read torn (or worse, mutate engine)
    state."""

    frames: int = 0
    last_latency_ms: float = 0.0
    ema_latency_ms: float = 0.0
    last_batch: int = 0
    # r9 per-stream device attribution. `bucket` is the padded size of
    # the last batch that served the stream (same number last_batch has
    # always carried, named for the API surface the ISSUE specifies).
    bucket: int = 0
    padded_slots: int = 0
    device_ms_ema: float = 0.0


@dataclass
class _Inflight:
    """A dispatched (not yet drained) device batch."""

    group: BatchGroup
    outputs: Any              # tree of jax.Arrays (async)
    t_submit: float
    # The batch's trace: the tick's measurement (tick number, collector
    # phases and byte counts, ``t_collect``) plus this batch's stamps,
    # filled in as the batch moves through the transfer, tick and drain
    # threads (see InferenceEngine._dispatch). One dict per batch, shared
    # by the three sinks: stage records, ``engine.*`` tracer events,
    # registry counters. None for coast groups (no device work).
    tr: Optional[dict] = None
    # Indices into ``group.device_ids`` that are emitted; None = all. A
    # row whose device window is still filling was written and computed,
    # and owes no result before its ``clip_len``-th read.
    emit: Optional[List[int]] = None


# vep_tick_phase_seconds_total{phase=...}: what the tick thread did with a
# tick that read at least one frame, plus "idle" (ticks that found nothing,
# and the between-tick wait for frames).
_TICK_PHASES = ("pre_collect", "pace_wait", "read", "fill", "collect_other",
                "place_wait", "pool", "state_wait", "step_call", "idle")


class _TimedStep:
    """Callable wrapper around a jitted serving step that AOT-compiles on
    first call, timing the compile wall-clock and capturing XLA cost
    analysis (FLOPs/bytes) into the engine's :class:`PerfTracker` — the
    per-cache-miss attribution behind the ``vep_compile_*`` families.

    One fallback only: when the AOT executable rejects its inputs before
    running them (avals or shardings drifted, e.g. params re-placed onto
    a mesh — jax raises ``TypeError``/``ValueError`` from its argument
    check, nothing was donated or executed), the wrapper logs it, counts
    it in ``PerfTracker`` (``aot_fallbacks``) and from then on calls the
    plain jitted function, where jax's own cache handles compilation. A
    compiler refusal or a runtime error from the device is NOT retried:
    it propagates to the dispatch site and the fault plane
    (engine/fault.py) as it is. Harness wrappers that decorate
    ``InferenceEngine._step`` (replay/harness.py device-stall fault)
    keep working: ``_step`` still returns a plain callable.
    """

    __slots__ = ("_jit", "_aot", "_perf", "_model", "_src_hw", "_bucket",
                 "_on_success", "_on_compiled", "program", "__weakref__")

    def __init__(self, jit_fn, perf: PerfTracker, model: str,
                 src_hw: tuple, bucket: int, on_first_success=None,
                 on_compiled=None):
        self._jit = jit_fn
        self._aot = None          # None = not compiled; False = jit path
        self._perf = perf
        self._model = model
        self._src_hw = src_hw
        self._bucket = bucket
        # The program's id: what a batch trace names (``_dispatch``) and
        # obs/stages.py keys the program's stage map by.
        self.program = f"{model}/{src_hw[0]}x{src_hw[1]}/{bucket}"
        # Fired once, after the first call that compiled AND executed
        # without raising — the AOT manifest record hook. Keyed on
        # success so a program whose compile reliably fails is never
        # recorded (and re-failed) on every future spawn's boot.
        self._on_success = on_first_success
        # Fired once with the AOT executable right after note_compile —
        # the r21 HBM plane's memory_analysis() tap. Never fires on the
        # jit fallback (no executable handle to analyze there).
        self._on_compiled = on_compiled

    def __call__(self, variables, *args):
        out = self._invoke(variables, *args)
        if self._on_success is not None:
            cb, self._on_success = self._on_success, None
            cb()
        return out

    @property
    def compiled(self):
        """The AOT executable, or None before the first call / after an
        avals-drift fallback."""
        return self._aot or None

    def _invoke(self, variables, *args):
        if self._aot is None:
            t0 = time.perf_counter()
            compiled = self._jit.lower(variables, *args).compile()
            self._perf.note_compile(
                self._model, self._src_hw, self._bucket,
                time.perf_counter() - t0, compiled=compiled)
            if self._on_compiled is not None:
                cb, self._on_compiled = self._on_compiled, None
                cb(compiled)
            self._aot = compiled
            # one dict entry: the map itself is built when asked for
            stages.register(self.program, self)
        if self._aot is not False:
            try:
                return self._aot(variables, *args)
            except (TypeError, ValueError) as exc:
                log.warning(
                    "AOT executable for %s %sx%s bucket=%d rejected its "
                    "arguments; serving this program through jit from "
                    "now on: %s", self._model, self._src_hw[0],
                    self._src_hw[1], self._bucket, exc)
                self._perf.note_aot_fallback()
                self._aot = False
        return self._jit(variables, *args)


def _build_cascade_head(model, score_w, score_b):
    """Temporal-head program body (CASCADE): uint8 clips -> VideoMAE
    logits + pooled clip features + logistic anomaly score, one fused
    program per (model, geometry, bucket) in the engine step cache.

    Features, per clip slot: [0] temporal diff energy — mean absolute
    luma difference between consecutive frames ([0,1] scale; exactly 0
    for a pixel-static track, the zero-false-positive anchor), [1] clip
    luma variance, [2] the head's max softmax probability. The logistic
    ``sigmoid(w . f + b)`` is the flagship event model; the VideoMAE
    logits ride the event payload for downstream consumers. f32 feature
    math and softmax (CLAUDE.md numerics convention — the VideoMAE
    encoder itself computes in bf16 internally)."""
    import jax
    import jax.numpy as jnp

    w = jnp.asarray((tuple(score_w) + (0.0, 0.0, 0.0))[:3], jnp.float32)
    b = jnp.float32(score_b)

    def head(variables, clips):
        x = clips.astype(jnp.float32) / 255.0
        logits = model.apply(variables, x, train=False).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        luma = x.mean(axis=-1)
        diff_energy = jnp.abs(luma[:, 1:] - luma[:, :-1]).mean(
            axis=(1, 2, 3))
        luma_var = jnp.var(luma, axis=(1, 2, 3))
        top_prob = probs.max(axis=-1)
        feats = jnp.stack([diff_energy, luma_var, top_prob], axis=-1)
        score = jax.nn.sigmoid(feats @ w + b)
        return {"event_score": score, "features": feats, "logits": logits}

    return head


class _Prefetched:
    """Handle for one batch placement in flight on the transfer thread."""

    __slots__ = ("group", "ready", "placed", "error", "transfer_s",
                 "overlapped_s", "t_q", "t0", "t_put", "t1")

    def __init__(self, group: BatchGroup):
        self.group = group
        self.ready = threading.Event()
        self.placed = None
        self.error: Optional[BaseException] = None
        self.transfer_s = 0.0
        self.overlapped_s = 0.0   # transfer wall time with >=1 batch in flight
        # wall stamps for the batch trace: handed to the stage, picked up
        # by the transfer thread, the placement call returned (the bytes
        # may still be crossing), block_until_ready returned
        self.t_q = time.time()
        self.t0 = self.t_put = self.t1 = 0.0


class _PrefetchStage:
    """Dedicated H2D transfer stage (ROADMAP item 5 tentpole): a
    depth-2 in-queue — the per-(model, geometry, bucket) double-buffered
    input slots — feeding one transfer thread that places each collected
    batch with a real async ``jax.device_put``. The copy of batch t+1
    runs while the tick thread dispatches batch t and the device
    computes it, instead of serializing inside the dispatch loop.
    It is fed from two places on the tick thread: the collector's sink
    (``InferenceEngine._early_placements``), which hands over a tick's
    first DEPTH groups each the moment its last frame is read, so that a
    placement runs beside the reads of the groups after it; and the
    dispatch loop, which submits whatever the sink did not (every group,
    on a tick without a sink).
    ``block_until_ready`` on the placed array bounds the transfer window
    AND guarantees the pooled host buffer is no longer being read when
    the handle resolves — the lease-return failure path relies on that.

    At most DEPTH placements are ever outstanding; the HBM itself
    returns to the allocator when the dispatched step is done with its
    frames argument — under a mesh XLA may take it earlier, as a donated
    buffer (see ``_step`` for why only there).
    """

    DEPTH = 2

    def __init__(self, place_fn, busy_fn):
        self._place = place_fn       # host frames -> device array
        self._busy = busy_fn         # True when >=1 dispatched batch in flight
        self._q: "queue.Queue[Optional[_Prefetched]]" = queue.Queue(
            maxsize=self.DEPTH)
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        self._thread = threading.Thread(
            target=self._loop, name="tpu-engine-xfer", daemon=True)
        self._thread.start()

    def alive(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def stop(self) -> None:
        if self._thread is None:
            return
        try:
            self._q.put(None, timeout=5)
        except queue.Full:
            log.warning("transfer queue full at stop; abandoning thread")
        self._thread.join(timeout=10)

    def nbytes(self) -> int:
        """Device bytes currently parked in the prefetch stage: placed-
        and-undispatched batches sitting in the depth-2 in-queue — the
        obs/hbm.py ``register_pool`` tap for the double-buffered input
        slots. Snapshots the queue under its own mutex (the stdlib-
        sanctioned way to size a live Queue); handles not yet placed (or
        errored) count 0. Metadata reads only."""
        with self._q.mutex:
            pending = list(self._q.queue)
        total = 0
        for pre in pending:
            placed = getattr(pre, "placed", None)
            if placed is None:
                continue
            parts = placed if isinstance(placed, (list, tuple)) else (placed,)
            for part in parts:
                total += int(getattr(part, "nbytes", 0) or 0)
        return total

    def submit(self, group: BatchGroup, stop_event) -> Optional[_Prefetched]:
        """Queue a placement; blocks (in interruptible slices) while both
        slots are occupied — same bounded-pipeline stance as the drain
        queue. Returns None on shutdown (caller returns the lease)."""
        pre = _Prefetched(group)
        while not stop_event.is_set():
            try:
                self._q.put(pre, timeout=0.1)
                return pre
            except queue.Full:
                continue
        return None

    def _loop(self) -> None:
        while True:
            pre = self._q.get()
            if pre is None:
                return
            pre.t0 = time.time()
            busy = self._busy()
            t0 = time.perf_counter()
            try:
                placed = self._place(pre.group.frames)
                pre.t_put = time.time()
                if hasattr(placed, "block_until_ready"):
                    placed.block_until_ready()
                pre.placed = placed
            except BaseException as exc:   # surfaced on the tick thread
                pre.error = exc
            pre.transfer_s = time.perf_counter() - t0
            pre.t1 = time.time()
            if busy or self._busy():
                # Device work was in flight while this copy ran: the
                # whole window was hidden behind compute.
                pre.overlapped_s = pre.transfer_s
            pre.ready.set()


def _group_slots(group: BatchGroup) -> int:
    """Stream slots a batch group will emit when healthy — the unit the
    FaultLedger (engine/fault.py) conserves. Coast groups emit one
    result per coast entry, canvas groups one per distinct crop stream
    (``_emit_canvas`` seeds its results dict from crop device_ids), and
    classic groups one per occupied slot."""
    if group.coast:
        return len(group.coast)
    if group.crops:
        return len({c.device_id for c in group.crops})
    return len(group.device_ids)


class _RoiGate(dict):
    """Per-stream motion-gate state for MOSAIC ROI serving (cfg.roi):
    ``device_id -> {"diff", "full_at"}``.

    Classification inputs are both *feedback* signals: the previous
    tick's device thumbnail diff energy (ops/preprocess.py
    frame_quality_stats, observed host-side in ``_emit``) and the
    stream's IoUTracker state (updated in ``_emit`` from the previous
    detections). The verdict per detect stream per tick:

    - ``full``  — refresh cadence due, or no gating signal yet, or
      motion with no tracks to localize it: run the classic full frame
      (also the only slots that refresh quality stats, so the diff
      signal can never starve itself).
    - ``idle``  — diff energy below ``roi_idle_diff``: no device work;
      the tracker coasts one frame (misses age so stale tracks expire)
      and its predicted boxes emit with decayed confidence.
    - ``roi``   — motion with live tracks: crops around the predicted
      track boxes join the shared canvases.

    A dict, so the engine's debounced stream GC treats it exactly like
    the tracker / thumbnail state maps. All access runs under the
    engine's ``_state_lock`` (tick-thread classify + GC, drain-thread
    feedback).
    """

    def __init__(self, idle_diff: float, full_interval_ms: float):
        super().__init__()
        self.idle_diff = float(idle_diff)
        self.full_interval_s = full_interval_ms / 1000.0

    def state(self, device_id: str) -> dict:
        return self.setdefault(device_id, {"diff": None, "full_at": 0.0})

    def note_diff(self, device_id: str, diff: float) -> None:
        self.state(device_id)["diff"] = float(diff)

    def note_full(self, device_id: str, now: float) -> None:
        self.state(device_id)["full_at"] = now

    def classify(self, device_id: str, tracker, now: float) -> str:
        st = self.state(device_id)
        if not st["full_at"] \
                or now - st["full_at"] >= self.full_interval_s:
            return "full"
        if st["diff"] is not None and st["diff"] < self.idle_diff:
            return "idle"
        if tracker is not None and tracker.live_tracks:
            return "roi"
        return "full"


class InferenceEngine:
    """Owns the model, the compiled step cache, and the engine thread."""

    # Tracker GC debounce: longer than any worker-restart ring re-create
    # gap, far shorter than "stream is really gone" timescales.
    _TRACKER_GC_GRACE_S = 10.0

    # Per-stream model failure breaker: first retry after this long,
    # doubling per consecutive failure up to the cap. Class attributes so
    # tests can shrink them without monkeypatching module globals.
    BAD_MODEL_BACKOFF_S = 30.0
    BAD_MODEL_BACKOFF_MAX_S = 600.0

    def __init__(
        self,
        bus: FrameBus,
        cfg: Optional[EngineConfig] = None,
        *,
        annotations=None,                    # AnnotationQueue or None
        spec=None,                           # ModelSpec override (tests)
        model_resolver=None,                 # device_id -> model name or ""
        annotation_policy_resolver=None,     # device_id -> policy or ""
        archiver=None,                       # .submit(GopSegment) duck type
        journal=None,                        # shared DecisionJournal or None
    ):
        self._bus = bus
        self._cfg = cfg or EngineConfig()
        self._journal_arg = journal
        self._annotations = annotations
        # Cascade event archive sink (ingest/archive.py SegmentArchiver
        # duck type): "enter" events submit the track's recent tile
        # history as a clip segment. None = no archive taps.
        self._archiver = archiver
        self._spec = spec
        self._model = None
        self._variables = None
        self._mesh = None
        # Per-stream model selection (StreamProcess.inference_model): other
        # registry models load lazily on first use; name -> (spec, model,
        # variables). The default model also lives here under its name.
        self._model_resolver = model_resolver
        self._ann_policy_resolver = annotation_policy_resolver
        self._models: Dict[str, tuple] = {}
        # Per-model failure circuit breaker: name -> {"failures", "retry_at"
        # (monotonic), "error"}. Entries half-open after an exponential
        # backoff so a transient init failure (OOM while another model
        # loads) does not disable the model until process restart; a model
        # that keeps failing backs off harder instead of starving every
        # healthy stream with multi-second re-init attempts per tick.
        self._bad_models: Dict[str, dict] = {}
        self._conf_threshold = 0.0   # calibrated at warmup from ckpt meta
        self._step_cache: Dict[tuple, Any] = {}
        # AOT prewarm cache (r19, engine/aot_cache.py): when enabled the
        # cache dir carries a prewarm manifest alongside the XLA payload;
        # _prewarm_required/_done back /api/v1/stats "prewarm" (the
        # fleet tier's "warming" member state — scraped-alive but not
        # yet holding its program set; obs/fleet.py).
        self._aot_dir = (
            (self._cfg.aot_cache_dir or "")
            if getattr(self._cfg, "aot_cache", False) else ""
        )
        self._prewarm_required = len(self._cfg.prewarm)
        self._prewarm_done = 0
        # With the AOT cache on, the true program set is unknown until
        # start() unions the manifest in — and REST binds before start(),
        # so a scrape during warmup must read "warming" even when
        # cfg.prewarm is empty (the harness's spawn path boots with no
        # --prewarm flags). Without the cache the config list IS the set.
        self._prewarm_started = not self._aot_dir
        self._collector: Optional[Collector] = None
        self._subscribers: List[tuple] = []   # (queue, device_id filter set|None)
        self._sub_lock = threading.Lock()
        # Set by stop() BEFORE the subscriber end-sentinels go out: a
        # wedged drain thread that wakes up later must not emit results
        # after a subscriber already saw its None (ADVICE r5 #5).
        self._fanout_closed = False
        self._stats: Dict[str, StreamStats] = {}
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # Event-driven drain: the engine thread queues dispatched batches;
        # the drain thread blocks on device outputs and emits immediately.
        # Depth 2 = classic double buffering; a full queue back-pressures
        # the tick loop instead of growing the in-flight set unboundedly.
        self._drain_q: "queue.Queue[Optional[_Inflight]]" = queue.Queue(
            maxsize=2
        )
        self._drain_thread: Optional[threading.Thread] = None
        # _emit mutates tracker/annotation state from the drain thread
        # while the tick loop GCs the same dicts — one lock covers both.
        self._state_lock = threading.Lock()
        self.ticks = 0
        self.batches = 0
        self.last_tick_monotonic = 0.0
        self._trackers: Dict[str, Any] = {}      # device_id -> IoUTracker
        self._tracker_absent: Dict[str, float] = {}  # id -> absent-since
        # Annotation emit policy state: device_id -> {"sig": {key: conf},
        # "last_ms": int} (cfg.annotation_emit; GC'd with the trackers).
        self._ann_state: Dict[str, dict] = {}
        # Every per-stream container, in the order the tick loop's GC pops
        # a departed stream from them (``_run``): these two, the thumbnails,
        # the ROI gate and its journaled mode, the cascade, ``_window_home``,
        # then each head and window pool as it is built. The HBM ledger sums
        # the pools among them by their ``ledger`` name.
        self._per_stream: List[Any] = [self._trackers, self._ann_state]
        self._ann_policy_warned: set = set()  # (device_id, bad policy)
        self.annotations_suppressed = 0
        # Results dropped on slow subscribers (queue full in _publish):
        # total + per-stream, surfaced in /metrics and /api/v1/stats so a
        # client that cannot keep up is visible, not silently starved
        # (annotation suppression already has this treatment).
        self.subscriber_drops = 0
        self.subscriber_drops_by_stream: Dict[str, int] = {}
        # stage_trace: one dict per emitted result carrying its batch's
        # trace (wall s stamps, phase seconds, byte counts), bounded deque.
        # Readers: benchmark/vbench/spans.py and batch_trace.py (the
        # per-layer metrics), replay/harness.py (the occupancy timeline).
        import collections

        self.stage_records: collections.deque = collections.deque(
            maxlen=4096
        )
        self._probe_cache: tuple = (0.0, None)   # (monotonic, ok | None)
        self._probe_thread: Optional[threading.Thread] = None
        self._probe_spawn_lock = threading.Lock()
        self._probe_fn = None                    # jitted once, reused
        # Unified metrics (obs/metrics.py): handles held here so hot-path
        # observations skip the registry name lookup. Unlabeled families
        # bind their singleton child eagerly — the sample then renders (as
        # 0) from the first scrape, not from the first event. The registry
        # is process-global — /metrics renders these directly.
        # Control-plane decision journal (obs/journal.py, r23): built
        # FIRST so every plane below can record causally-linked audit
        # events. cfg.journal=False leaves it None — no hooks anywhere,
        # /api/v1/journal answers 400, replay bit-identical (test-pinned
        # kill switch, fault convention). A journal passed to the ctor
        # (the head process sharing one journal with router/supervisor)
        # wins over building a fresh one.
        self.journal = None
        if self._cfg.journal:
            if self._journal_arg is not None:
                self.journal = self._journal_arg
            else:
                from ..obs.journal import DecisionJournal

                self.journal = DecisionJournal(self._cfg.journal_capacity)
        self.watchdog = Watchdog(journal=self.journal)
        self._m_ticks = obs_registry.counter(
            "vep_engine_ticks_total", "Engine ticks completed").labels()
        self._m_batches = obs_registry.counter(
            "vep_engine_batches_total", "Device batches dispatched").labels()
        self._m_frames = obs_registry.counter(
            "vep_stream_frames_total", "Inference results per stream",
            ("stream",))
        self._m_latency = obs_registry.histogram(
            "vep_stream_latency_ms",
            "End-to-end frame latency, bus publish to result emit (ms)",
            ("stream",))
        self._m_device = obs_registry.histogram(
            "vep_device_batch_ms",
            "Batch submit to host fetch complete (ms)", ("model",))
        self._m_occupancy = obs_registry.histogram(
            "vep_batch_occupancy_pct",
            "Real frames per padded batch slot (percent)").labels()
        self._m_cache_miss = obs_registry.counter(
            "vep_step_cache_misses_total",
            "Serving-step cache misses (each triggers an XLA compile)"
        ).labels()
        self._m_cache_hit = obs_registry.counter(
            "vep_step_cache_hits_total", "Serving-step cache hits").labels()
        self._m_drain_depth = obs_registry.gauge(
            "vep_drain_queue_depth",
            "Dispatched batches waiting on the device drain thread").labels()
        self._m_sub_drops = obs_registry.counter(
            "vep_stream_subscriber_dropped_total",
            "Results dropped on slow subscribers per stream", ("stream",))
        self._m_late = obs_registry.counter(
            "vep_frames_late_total",
            "Results slower end-to-end than engine.obs_late_ms",
            ("stream",))
        # Where the tick thread's time and the collector's bytes go, fed
        # once per tick that read a frame from the same measurement the
        # stage records and the engine.* tracer events carry (_close_tick).
        phase = obs_registry.counter(
            "vep_tick_phase_seconds_total",
            "Engine tick thread seconds by phase", ("phase",))
        self._m_phase = {p: phase.labels(p) for p in _TICK_PHASES}
        cbytes = obs_registry.counter(
            "vep_collect_bytes_total",
            "Collector bytes: read off the rings, copied into host memory, "
            "copied into first-touched (unpooled) buffers", ("kind",))
        self._m_cbytes = {k: cbytes.labels(k)
                          for k in ("read", "copied", "fresh")}
        # Stream heads (step kind "stream"): tokens through the head,
        # context resets, routed (token, expert) pairs the held experts took.
        head_tok = obs_registry.counter(
            "vep_head_tokens_total",
            "Tokens through a stream head", ("kind",))
        self._m_head_tokens = {k: head_tok.labels(k)
                               for k in ("prefill", "decode")}
        self._m_head_resets = obs_registry.counter(
            "vep_head_resets_total",
            "Stream-head contexts reset (full, first context over, new "
            "stream)").labels()
        self._m_moe_pairs = obs_registry.counter(
            "vep_moe_pairs_total",
            "Routed (token, expert) pairs computed by the held experts"
        ).labels()
        self._m_moe_routed = obs_registry.counter(
            "vep_moe_pairs_routed_total",
            "Routed (token, expert) pairs a head's routers chose over all "
            "experts, held here or not (heads that count them)").labels()
        self._m_moe_group_hits = obs_registry.counter(
            "vep_moe_group_hits_total",
            "Tokens, a routed layer, whose kept expert groups include a "
            "group held here (group-limited routers)").labels()
        attn = obs_registry.counter(
            "vep_attn_key_blocks_total",
            "Tiles (a key block against 128 queries) a head's prefill "
            "attention visited (live) and what a pass over every key it "
            "could hold would visit (dense)", ("kind",))
        decode = obs_registry.counter(
            "vep_decode_key_blocks_total",
            "Key blocks a head's decode attention fetched from its cache "
            "(live) and what a pass over every cacheable position would "
            "fetch (dense)", ("kind",))
        self._m_key_blocks = {
            part: {k: c.labels(k) for k in ("live", "dense")}
            for part, c in (("attn", attn), ("decode", decode))}
        mtp = obs_registry.counter(
            "vep_mtp_drafts_total",
            "Drafts of a stream head's prediction module, verified by the "
            "decode loop and accepted", ("outcome",))
        self._m_mtp = {k: mtp.labels(k) for k in ("drafted", "accepted")}
        # model name -> StreamStatePool (engine/stream_state.py), built
        # when a stream-head model is first dispatched or prewarmed
        self._head_pools: Dict[str, Any] = {}
        # Clip windows (engine/stream_state.py ClipWindowPool): model name
        # -> the pool of its streams' windows on the device, built like the
        # head pools; stream -> the model whose pool holds its window. Only
        # the one-device engine has them, and not for every kind
        # (_window_on_device, which its collector asks too).
        self._device_windows = False    # set by warmup: no mesh
        self._window_pools: Dict[str, Any] = {}
        self._window_home: Dict[str, str] = {}
        win_rows = obs_registry.counter(
            "vep_clip_window_rows_total",
            "Clip samples dispatched, by where the stream's window lives",
            ("home",))
        self._m_window_rows = {h: win_rows.labels(h)
                               for h in ("device", "host")}
        self._m_placed_early = obs_registry.counter(
            "vep_groups_placed_early_total",
            "Batch groups handed to the transfer thread by the collector, "
            "before their tick's collection closed")
        self._m_window_restarts = obs_registry.counter(
            "vep_clip_window_restarts_total",
            "Device clip windows started anew because a read frame did not "
            "reach them", ("reason",))
        self._window_restarts = 0   # since the last batch trace took them
        self._tick_mark = time.perf_counter()   # end of the last closed tick
        self._assemble_s = 0.0   # assemble_until seconds since that mark
        # Recompile-storm detection state (tick loop only).
        self._miss_seen = 0.0
        self._miss_streak = 0
        # Overload degradation ladder (resilience/ladder.py): observed
        # once per tick with drain-queue depth + previous tick duration;
        # the returned rung gates shedding / bucket cap / admission.
        # Shares the engine watchdog so a degraded excursion logs once.
        self.ladder: Optional[DegradationLadder] = None
        if self._cfg.ladder:
            self.ladder = DegradationLadder(
                escalate_after_s=self._cfg.ladder_escalate_after_s,
                recover_after_s=self._cfg.ladder_recover_after_s,
                watchdog=self.watchdog,
                journal=self.journal,
            )
        self.shed_frames = 0
        # r23 journal edge state: open shed-excursion event seq + its
        # accumulated frame count, and the last journaled ROI mode per
        # stream (transitions are journaled on the edge, not per tick).
        self._shed_seq: Optional[int] = None
        self._shed_excursion_frames = 0
        self._roi_mode: Dict[str, str] = {}
        self._m_shed = obs_registry.counter(
            "vep_ladder_shed_frames_total",
            "Frames shed by the degradation ladder (stale at dispatch)",
        ).labels()
        self._last_tick_dur_s = 0.0
        # Backpressure discriminator for the prefetch pipeline (tick
        # thread only): with cfg.prefetch the depth-2 drain queue is
        # legitimately FULL in healthy saturated serving (that is the
        # double buffer doing its job), so raw qsize no longer means
        # "device behind". The signal that still does is the tick
        # thread having had to BLOCK handing a batch to the drain
        # thread (_enqueue_drain found the queue full) — a device that
        # keeps up absorbs the handoff without blocking. Since the tick
        # thread reads just in time (engine/pacing.py) that wait is taken
        # BEFORE the read wherever the device's backlog can be predicted,
        # and a tick that waited and then read frames is the same
        # condition: it raises the same flag, and the queue blocks only
        # when the prediction was off.
        self._drain_blocked = False
        self._bp_depth = 0
        self._pacer = ReadPacer()
        self._pace_s = 0.0        # paced wait since the last _close_tick
        # Live device-performance attribution (obs/perf.py): compile
        # cost per (model, geometry, bucket) fed from _step misses,
        # per-batch device time / padding waste / MFU fed from _emit.
        self.perf = PerfTracker()
        # SLO burn-rate engine (obs/slo.py): per-frame latency events
        # from _emit, per-tick fps + availability samples from the tick
        # loop; evaluated at most every slo_eval_interval_s. The
        # aggregate burn verdict feeds the ladder as extra pressure
        # (cfg.slo_ladder).
        self.slo: Optional[SLOEngine] = None
        self._slo_latency = self._slo_fps = self._slo_avail = None
        self._slo_burning = False
        self._slo_episodes = 0
        self._slo_next_eval = 0.0
        if self._cfg.slo:
            self.slo = SLOEngine(
                default_slos(
                    latency_ms=self._cfg.slo_latency_ms,
                    target_fps=self._cfg.slo_target_fps,
                    warmup_s=self._cfg.slo_warmup_s,
                ),
                watchdog=self.watchdog,
                journal=self.journal,
            )
            self._slo_latency = self.slo.get("detect_latency_p50")
            self._slo_fps = self.slo.get("aggregate_fps")
            self._slo_avail = self.slo.get("stream_availability")
        # Triggered device profiling (obs/prof.py): bounded jax.profiler
        # captures on demand (REST/gRPC) or fired once per SLO episode /
        # ladder escalation from _watch_tick. cfg.prof=False disables the
        # subsystem entirely (the REST endpoint answers 400).
        self.prof: Optional[Profiler] = None
        if self._cfg.prof:
            self.prof = Profiler(
                self._cfg.prof_dir
                or os.path.join(tempfile.gettempdir(), "vep_prof"),
                retention_bytes=self._cfg.prof_retention_bytes,
                trigger=self._cfg.prof_trigger,
                trigger_ms=self._cfg.prof_trigger_ms,
                trigger_min_interval_s=(
                    self._cfg.prof_trigger_min_interval_s),
                max_ms=self._cfg.prof_max_ms,
                tracer=tracer,
                journal=self.journal,
                snapshot_fn=self._prof_snapshot,
            )
        # Output-quality observability (obs/quality.py): host verdict
        # state machines + drift scores fed from _emit; the device side
        # (frame statistics folded into the serving step) additionally
        # needs per-stream thumbnail state — per mesh shard under
        # engine.mesh (r17), one pool on the single chip otherwise.
        # cfg.quality=False disables the whole plane (the REST endpoint
        # answers 400, same kill-switch convention as slo/prof).
        self.quality = None
        self.canary = None
        self._canary_thread: Optional[threading.Thread] = None
        # Device-resident thumbnail pool (dict-like: stream -> pool row).
        # Under a mesh, warmup swaps in the sharded twin once the mesh
        # exists (_ShardedThumbPool: one _ThumbPool per dp slice).
        self._thumbs = _ThumbPool(self._cfg.quality_thumb)
        self._per_stream.append(self._thumbs)
        self._quality_device = False
        # Data-parallel serving state (r17 tentpole leg 1): shard count
        # and the stream->shard map, set by warmup once the mesh shape
        # is known. 1/None = single-chip layout everywhere.
        self._shards = 1
        self._shard_of = None
        # Spatially-multiplexed ROI serving (MOSAIC, ROADMAP item 1):
        # motion gate state + shelf packer, built at warmup (the packer
        # needs the effective bucket list). cfg.roi=False leaves both
        # None — every batch then takes the classic full-frame path
        # bit-identically (test-pinned kill switch). Under engine.mesh
        # (r17) canvases pack per mesh slice, so the scatter-back
        # routing table stays shard-local and ROI serving runs on-mesh.
        self._roi: Optional[_RoiGate] = None
        self._packer: Optional[CanvasPacker] = None
        if self._cfg.roi:
            self._roi = _RoiGate(
                self._cfg.roi_idle_diff, self._cfg.roi_full_interval_ms)
            self._per_stream += [self._roi, self._roi_mode]
        # Temporal cascade serving (CASCADE, ROADMAP item 2): tracker-
        # keyed device clip rings + cadence-1/N temporal head
        # (temporal/scheduler.py). cascade=False leaves it None — every
        # batch takes today's stateless path bit-identically (test-
        # pinned kill switch, roi=False convention). Under engine.mesh
        # the scheduler swaps its pool for the sharded twin
        # (configure_mesh in warmup) so clip state lives per chip.
        self._cascade = None
        if self._cfg.cascade:
            from ..temporal import CascadeScheduler

            self._cascade = CascadeScheduler(
                model=self._cfg.cascade_model,
                every_n=self._cfg.cascade_every_n,
                crop=self._cfg.cascade_crop,
                clip_len=self._cfg.cascade_clip_len,
                threshold=self._cfg.cascade_threshold,
                enter_n=self._cfg.cascade_enter_n,
                exit_n=self._cfg.cascade_exit_n,
                ttl_ticks=self._cfg.cascade_track_ttl_ticks,
                perf=self.perf,
            )
            self._cascade.head = self._cascade_head
            self._per_stream.append(self._cascade)
        self._per_stream.append(self._window_home)
        # Capacity attribution plane (obs/capacity.py): the per-stream
        # device-time ledger + headroom forecast fed from the same
        # _emit measurements obs/perf.py aggregates, evaluated off the
        # tick (throttled). cfg.capacity=False leaves it None — no tap
        # anywhere in the emit path, /api/v1/capacity answers 400, and
        # serving stays bit-identical (test-pinned kill switch, same
        # convention as roi/cascade).
        self.capacity = None
        if self._cfg.capacity:
            from ..obs.capacity import CapacityTracker

            self.capacity = CapacityTracker(
                tick_ms=self._cfg.tick_ms,
                fast_window_s=self._cfg.capacity_fast_window_s,
                slow_window_s=self._cfg.capacity_slow_window_s,
                util_objective=self._cfg.capacity_util_objective,
                eval_interval_s=self._cfg.capacity_eval_interval_s,
            )
        # H2D prefetch stage (cfg.prefetch): placement of collected
        # batches moves off the tick thread onto a dedicated transfer
        # thread, double-buffered at depth 2 to match the drain pipeline.
        # "busy" (the hidden-transfer attribution signal) keys off the
        # drain queue's unfinished-task count: put in _enqueue_drain,
        # task_done after _emit — exactly the submitted-but-not-yet-
        # drained window during which device compute is in flight.
        self._xfer: Optional[_PrefetchStage] = None
        if self._cfg.prefetch:
            self._xfer = _PrefetchStage(
                self._place_device,
                lambda: self._drain_q.unfinished_tasks > 0,
            )
        if self._cfg.quality:
            from ..obs.quality import QualityTracker

            self.quality = QualityTracker(
                black_luma=self._cfg.quality_black_luma,
                black_var=self._cfg.quality_black_var,
                freeze_diff=self._cfg.quality_freeze_diff,
                enter_s=self._cfg.quality_enter_s,
                exit_s=self._cfg.quality_exit_s,
                flatline_s=self._cfg.quality_flatline_s,
                window_s=self._cfg.quality_window_s,
                drift_threshold=self._cfg.quality_drift_threshold,
                on_transition=self._on_quality_transition,
            )
            # r17: device frame statistics run under the mesh too — the
            # thumbnail pool shards per dp slice (warmup).
            self._quality_device = self._cfg.quality_thumb > 0
        # HBM attribution plane (obs/hbm.py, r21): the memory mirror of
        # the capacity plane — compiled-program footprints tapped at the
        # same _TimedStep cache-miss site obs/perf.py uses, plus live
        # byte ledgers for every device/host pool the engine owns. The
        # register_pool callables close over self attributes, so the
        # warmup swaps to sharded twins (and the collector being built
        # later) stay tracked with no re-registration. cfg.hbm=False
        # leaves it None — no compile tap, no pool callables,
        # /api/v1/hbm answers 400, serving bit-identical (test-pinned
        # kill switch, capacity convention).
        self.hbm = None
        if self._cfg.hbm:
            from ..obs.hbm import HbmTracker

            self.hbm = HbmTracker(
                budget_bytes=self._cfg.hbm_budget_bytes,
                fast_window_s=self._cfg.hbm_fast_window_s,
                slow_window_s=self._cfg.hbm_slow_window_s,
                util_objective=self._cfg.hbm_util_objective,
                eval_interval_s=self._cfg.hbm_eval_interval_s,
                pressure_horizon_s=self._cfg.hbm_pressure_horizon_s,
            )
            for ledger in (_ThumbPool.ledger, StreamStatePool.ledger,
                           ClipWindowPool.ledger):
                self.hbm.register_pool(
                    ledger, lambda name=ledger: self._ledger_nbytes(name))
            self.hbm.register_pool(
                "track_state",
                lambda: self._cascade.pool_nbytes()
                if self._cascade is not None else 0)
            self.hbm.register_pool(
                "prefetch",
                lambda: self._xfer.nbytes() if self._xfer is not None else 0)
            self.hbm.register_pool(
                "collector_host",
                lambda: self._collector.pool_nbytes()
                if self._collector is not None else 0)
        # Device-fault domain (engine/fault.py, r22): per-dispatch
        # deadline/error watchdog + FaultLedger conservation proof +
        # bounded-time survivor-mesh failover. cfg.fault=False leaves it
        # None — no tap in the dispatch/drain paths, /api/v1/faults
        # answers 400, serving bit-identical (test-pinned kill switch,
        # capacity/hbm convention).
        self.faults = None
        if self._cfg.fault:
            from .fault import FaultPlane

            self.faults = FaultPlane(
                deadline_ms=self._cfg.fault_dispatch_deadline_ms,
                hysteresis=self._cfg.fault_hysteresis,
                failover_budget_ms=self._cfg.fault_failover_budget_ms,
                probe_timeout_ms=self._cfg.fault_probe_timeout_ms,
                journal=self.journal,
            )

    def _ledger_nbytes(self, ledger: str):
        """Device bytes of the pools filed under one name of the HBM
        ledger: their sum, or the one sharded pool's ``{shard: bytes}``
        as it is (the thumbnails under a mesh). list(): the ledger reads
        while the tick thread may be building a pool."""
        held = [p.nbytes() for p in list(self._per_stream)
                if getattr(p, "ledger", None) == ledger]
        return held[0] if len(held) == 1 else sum(held)

    def _swap_thumbs(self, pool) -> None:
        """The thumbnail pool's sharded twin takes its place (warmup under
        a mesh, a survivor-mesh failover), in the GC's list too."""
        self._per_stream[self._per_stream.index(self._thumbs)] = pool
        self._thumbs = pool

    @property
    def cascade(self):
        """The cascade scheduler, or None when cfg.cascade is off (the
        REST endpoint keys its 400 on this, r9 convention)."""
        return self._cascade

    # -- lifecycle --

    def warmup(self) -> None:
        """Build model + params and compile nothing yet (steps compile per
        observed shape; call `compile_for` to prewarm a given geometry)."""
        import jax

        from ..models import registry

        cache_dir = self._aot_dir or self._cfg.compile_cache_dir
        if cache_dir:
            # Persistent XLA compile cache: a restarted server re-loads
            # compiled programs instead of paying tens of seconds per
            # (geometry, bucket) again (SURVEY.md §5.4). With the AOT
            # prewarm cache (r19) the manifest lives in aot_cache_dir and
            # the payload goes where utils/compile_cache.py's one rule
            # puts it: JAX_COMPILATION_CACHE_DIR when set, else here.
            from ..utils import compile_cache

            compile_cache.configure(cache_dir)
        if self._spec is None:
            self._spec = registry.get(self._cfg.model)
        # Detect-family variant axes (round 15): cfg.stem / int8_act
        # rewrite the spec's build BEFORE init so the whole lifecycle
        # (checkpoint templates, prewarm, serving steps) sees one model.
        self._spec = self._variant_spec(self._spec)
        self._model, self._variables = self._spec.init_params(
            jax.random.PRNGKey(0)
        )
        # Calibrated per-checkpoint serving threshold (selftrain loop
        # writes it into checkpoint metadata): detections below it never
        # leave the engine. 0.0 = no calibration -> NMS's own floor only.
        self._conf_threshold = 0.0
        ckpt = self._cfg.checkpoint_path
        if ckpt:
            from ..parallel.sharding import unbox
            from ..utils.checkpoint import load_msgpack_with_meta

            if os.path.exists(ckpt):
                # Checkpoints are UNBOXED raw trees (the canonical format
                # tools/import_weights.py writes and save_checkpoint
                # mirrors); restore against an unboxed template, then
                # re-box so ViT-family logical sharding names survive for
                # mesh serving.
                from ..models.import_weights import pad_stem_on_load

                raw, meta = load_msgpack_with_meta(
                    ckpt, jax.tree.map(np.asarray, unbox(self._variables))
                )
                # Pre-stem_pad_c checkpoints: zero-pad the stem kernel
                # (config-gated — never fires for the s2d stem, whose
                # extra input planes carry real pixels).
                raw = pad_stem_on_load(
                    raw, unbox(self._variables), self._model
                )
                # Host tree for now: placement happens ONCE below (mesh
                # sharding or single-chip put). An eager device_put here
                # would materialize the full tree on one chip first —
                # exactly what sharded serving of big models must avoid.
                self._variables = _rebox(self._variables, raw)
                log.info("loaded engine params from %s", ckpt)
                thr = (meta or {}).get("conf_threshold")
                if thr is not None:
                    self._conf_threshold = float(thr)
                    log.info(
                        "serving at calibrated conf_threshold=%.3f "
                        "(checkpoint metadata)", self._conf_threshold,
                    )
            else:
                log.warning("checkpoint %s missing; using random init", ckpt)
        self._variables = self._maybe_calibrate(
            self._spec, self._model, self._variables
        )
        self._variables = self._maybe_quantize(self._variables)
        if self._spec.prepare is not None:
            self._variables = self._spec.prepare(self._model, self._variables)
        buckets = tuple(self._cfg.batch_buckets)
        if self._cfg.mesh:
            # Multi-chip serving: batch axis sharded over dp; params
            # placed by _place_variables (replicated for dp-only meshes
            # and conv trees, SHARDED per logical axis names when the
            # mesh has tp/fsdp/sp/ep — big/long-context transformers).
            # Buckets must divide evenly across dp so every chip gets
            # identical static shapes.
            from ..parallel import factor_mesh, make_mesh

            if isinstance(self._cfg.mesh, str):
                if self._cfg.mesh != "auto":
                    raise ValueError(
                        f"engine.mesh: unknown value {self._cfg.mesh!r} — "
                        "use 'auto', an axis dict like {'dp': 4}, or empty "
                        "for single-chip"
                    )
                # Serving profile: every visible device on the batch axis.
                self._mesh = factor_mesh(prefer=("dp",))
            else:
                n_need = 1
                for v in self._cfg.mesh.values():
                    n_need *= v
                self._mesh = make_mesh(
                    **self._cfg.mesh, devices=jax.devices()[:n_need]
                )
            dp = self._mesh.shape["dp"]
            buckets = tuple(b for b in buckets if b % dp == 0) or (dp,)
            self._variables = self._place_variables(self._variables)
            self._model = self._maybe_seq_parallel(self._model)
            # r17 mesh-native serving: everything downstream of the
            # collector addresses batches in the shard-segmented row
            # layout (shard s owns rows [s*seg, (s+1)*seg)). The stream
            # -> shard map is the collector's stable crc32 hash so a
            # stream's ROI/cascade/thumbnail state lives where its
            # frames land, tick after tick.
            from .collector import stream_shard

            self._shards = dp
            self._shard_of = lambda did: stream_shard(did, dp)
            if self._quality_device:
                self._swap_thumbs(_ShardedThumbPool(
                    self._cfg.quality_thumb, mesh=self._mesh, shards=dp,
                    shard_of=self._shard_of,
                ))
            if self._cascade is not None:
                self._cascade.configure_mesh(
                    mesh=self._mesh, shards=dp, shard_of=self._shard_of,
                )
            if self.faults is not None:
                # Shard -> device-name strings for XLA-error attribution
                # (a raw device error names the chip, not the shard).
                from ..temporal.state_pool import shard_devices

                self.faults.configure(shards=dp, shard_devices={
                    s: [str(d)]
                    for s, d in enumerate(shard_devices(self._mesh, dp))
                })
            log.info(
                "engine mesh: %s (buckets -> %s)",
                dict(zip(self._mesh.axis_names, self._mesh.devices.shape)),
                buckets,
            )
        else:
            # Single chip: a checkpoint-loaded tree is host numpy at this
            # point — place it once so the serving step isn't re-shipping
            # params every tick. (No-op for random-init device arrays.)
            self._variables = jax.device_put(self._variables)
        self._models[self._spec.name] = (self._spec, self._model, self._variables)
        self._buckets = buckets   # effective (mesh-filtered) buckets
        self._device_windows = self._mesh is None
        if self._roi is not None:
            # Canvas count per tick can never exceed the largest batch
            # bucket (the packed group must still pad to a known bucket).
            self._packer = CanvasPacker(
                side=self._cfg.roi_canvas,
                gap=self._cfg.roi_gap,
                max_canvases=min(self._cfg.roi_max_canvases,
                                 max(buckets)),
                min_crop=self._cfg.roi_min_crop,
            )
        self._collector = Collector(
            self._bus,
            buckets=buckets,
            clip_len=self._spec.clip_len,
            active_window_s=self._cfg.active_window_s,
            model_of=self._stream_model,
            default_model=self._spec.name,
            interest_of=self._stream_interest,
            # In-flight batches outlive the tick that built them (drain
            # queue); pooled buffers must stay valid until the drain
            # thread releases them.
            strict_lease=True,
            # r17: per-shard batch slices — the collector emits groups in
            # the shard-segmented row layout (group.rows set) so each dp
            # slice receives exactly its streams' frames.
            shards=self._shards,
            # One device: a clip stream's window is device state (the
            # window pools below), the host ships single frames and the
            # step is the windowed form. A mesh keeps host rings (its rows
            # must stay re-pinnable between shards), and so does the
            # stream kind: _window_on_device.
            device_windows=self._window_on_device,
        )
        device = jax.devices()[0]
        # MFU denominator from the peaks table (obs/perf.py): a device
        # without a row — the CPU twin — publishes no MFU.
        self.perf.set_device_kind(device.device_kind)
        if self.hbm is not None and not self._cfg.hbm_budget_bytes:
            # Resolve the real device budget now that the backend is up.
            # A TPU reports bytes_limit and a TPU that does not is an
            # error, not a reason to forecast against a made-up budget;
            # the synthetic default is for the CPU twin only (no memory
            # stats there), where it keeps forecasts meaningful in tests.
            stats = device.memory_stats() or {}
            limit = int(stats.get("bytes_limit", 0) or 0)
            if limit > 0:
                self.hbm.set_budget(limit)
            elif device.platform == "tpu":
                raise RuntimeError(
                    f"{device} reports no bytes_limit in memory_stats() "
                    f"({sorted(stats)}); set engine.hbm_budget_bytes or "
                    "turn engine.hbm off")
        log.info(
            "engine ready: model=%s kind=%s input=%d backend=%s",
            self._spec.name, self._spec.kind, self._spec.input_size,
            jax.default_backend(),
        )

    def _variant_spec(self, spec):
        """Apply the engine's detect-family variant axes — ``cfg.stem``
        ("s2d": space-to-depth stem + fused preprocess) and
        ``cfg.quantize="int8_act"`` (int8 activation convs) — by rewriting
        the spec's build to clone the model with the overridden config.
        Classic/fp configs pass through untouched (the spec object is the
        SAME one, so replay checksums and step-cache identity are
        unchanged). Models whose config lacks the fields (e.g. the
        BlobGauge diagnostic) serve unmodified with a warning."""
        if spec.kind != "detect":
            return spec
        import dataclasses

        stem = getattr(self._cfg, "stem", "classic") or "classic"
        if stem not in ("classic", "s2d"):
            raise ValueError(
                f"engine.stem={stem!r} unsupported ('classic' or 's2d')"
            )
        overrides = {}
        if stem != "classic":
            overrides["stem"] = stem
        if self._cfg.quantize == "int8_act":
            overrides["act_int8"] = True
        if not overrides:
            return spec
        cfg = getattr(spec.build(), "cfg", None)
        try:
            fields = {f.name for f in dataclasses.fields(cfg)}
        except TypeError:
            fields = set()
        missing = sorted(set(overrides) - fields)
        if missing:
            log.warning(
                "model '%s' config has no %s field(s); serving the stock "
                "variant", spec.name, "/".join(missing),
            )
            return spec

        def build(_base=spec.build, _ov=dict(overrides)):
            m = _base()
            return m.clone(cfg=dataclasses.replace(m.cfg, **_ov))

        return dataclasses.replace(spec, build=build)

    def _maybe_calibrate(self, spec, model, variables):
        """cfg.quantize="int8_act": one-shot activation-range calibration
        (models/quantize.py calibrate_serving) over deterministic synthetic
        frames at engine boot. The pass runs the FP forward — it only
        observes per-conv max-abs input ranges into the "quant" collection
        the int8 serving graph then consumes. Deployments wanting
        data-matched ranges re-calibrate offline (tools/bench_levers.py
        calibrates on its own frame set and accuracy-gates the result)."""
        if self._cfg.quantize != "int8_act":
            return variables
        if spec.kind != "detect" or not getattr(
            getattr(model, "cfg", None), "act_int8", False
        ):
            return variables
        from ..models.quantize import calibrate_serving

        rng = np.random.default_rng(0)
        s = spec.input_size
        batches = [
            rng.integers(0, 256, (2, s, s, 3), np.uint8) for _ in range(2)
        ]
        variables = calibrate_serving(model, spec, dict(variables), batches)
        log.info(
            "engine activations calibrated for int8 serving "
            "(%d synthetic batches at %d²)", len(batches), s,
        )
        return variables

    def _maybe_quantize(self, variables):
        """cfg.quantize="int8": weight-only PTQ (models/quantize.py) — int8
        device/checkpoint residency, dequantize fused into the jitted step.
        No calibration data needed, so it is safe at engine boot.
        cfg.quantize="int8_act" keeps the same int8 weight residency and
        additionally runs calibrated int8 activation convs (the model was
        built with act_int8=True by _variant_spec; calibration happened in
        _maybe_calibrate)."""
        if not self._cfg.quantize:
            return variables
        if self._cfg.quantize not in ("int8", "int8_act"):
            raise ValueError(
                f"engine.quantize={self._cfg.quantize!r} unsupported "
                "(only 'int8' weight-only and 'int8_act' calibrated "
                "activation quantization exist)"
            )
        from ..models.quantize import quantize_tree, quantized_nbytes, tree_nbytes

        before = tree_nbytes(variables)
        qt = quantize_tree(variables)
        log.info(
            "engine params quantized int8 (%s): %.1f MB -> %.1f MB",
            "weight-only" if self._cfg.quantize == "int8" else
            "weights + calibrated activations",
            before / 1e6, quantized_nbytes(qt) / 1e6,
        )
        return qt

    def _maybe_seq_parallel(self, model):
        """Long-context serving: when the mesh carries a sequence axis
        (sp > 1), transformer-family models re-instantiate with the
        ring-attention ``attn_fn`` so the [T, T] attention tiles shard
        over sp instead of materializing per chip — the serving-side
        twin of parallel.with_ring_attention (params are unchanged;
        attn_fn is not a parameter). Conv models pass through."""
        if self._mesh is None or self._mesh.shape.get("sp", 1) <= 1:
            return model
        import dataclasses

        if not any(f.name == "attn_fn" for f in dataclasses.fields(model)):
            return model
        from ..parallel import with_ring_attention

        log.info("serving with ring attention over sp=%d",
                 self._mesh.shape["sp"])
        return with_ring_attention(
            type(model), model.cfg, self._mesh, dtype=model.dtype
        )

    def _place_variables(self, variables):
        """Put a model's variables onto the serving mesh. With model
        axes configured (tp/fsdp/sp/ep > 1) and full-precision weights,
        transformer params shard per their flax logical axis names
        ("embed"/"qkv"/"mlp"/"expert"; conv trees carry none and
        replicate) — big/long-context models (ViT-B, VideoMAE-64) fit and
        serve across chips with XLA inserting the collectives
        (scaling-book recipe, parallel/sharding.py rules). dp-only meshes
        and int8 weight trees (already tiny) replicate. ONE decision for
        the default model and every per-stream extra."""
        import jax

        from ..parallel import replicated

        model_axes = any(
            self._mesh.shape.get(a, 1) > 1 for a in ("tp", "fsdp", "sp", "ep")
        )
        if model_axes and not self._cfg.quantize:
            from ..parallel.sharding import place_params

            return place_params(self._mesh, variables)
        return jax.device_put(variables, replicated(self._mesh))

    def _ensure_model(self, name: str):
        """(spec, model, variables) for a registry model, lazily built.
        Only the default model reads cfg.checkpoint_path; per-stream extras
        start from init (their checkpoints belong to a later config)."""
        entry = self._models.get(name)
        if entry is None:
            import jax

            from ..models import registry

            spec = self._variant_spec(registry.get(name))
            model, variables = spec.init_params(jax.random.PRNGKey(0))
            variables = self._maybe_calibrate(spec, model, variables)
            variables = self._maybe_quantize(variables)
            if spec.prepare is not None:
                variables = spec.prepare(model, variables)
            if self._mesh is not None:
                variables = self._place_variables(variables)
                model = self._maybe_seq_parallel(model)
            entry = (spec, model, variables)
            self._models[name] = entry
            log.info("engine loaded extra model '%s' (kind=%s)", name, spec.kind)
        return entry

    def _stream_model(self, device_id: str):
        """Collector resolver: (model name, clip_len) or None for default."""
        if self._model_resolver is None:
            return None
        name = self._model_resolver(device_id)
        if name == "none":
            # Operator switched inference off for this stream
            # (StreamProcess.inference_model: "none"); the collector gates
            # it out of batches and keep_streams_hot.
            return "none", 0
        if not name or name == self._spec.name:
            return None
        bad = self._bad_models.get(name)
        if bad is not None and time.monotonic() < bad["retry_at"]:
            return None
        try:
            spec, _, _ = self._ensure_model(name)
        except Exception as exc:
            # Unknown name OR a model that fails to build (OOM, bug): either
            # way confine the damage to this stream's model choice — a
            # per-tick re-attempt of a failing multi-second init would
            # starve every healthy stream. The breaker half-opens after an
            # exponential backoff (next attempt is the probe) rather than
            # disabling the model until restart.
            failures = (bad["failures"] if bad else 0) + 1
            backoff = min(
                self.BAD_MODEL_BACKOFF_S * (2 ** (failures - 1)),
                self.BAD_MODEL_BACKOFF_MAX_S,
            )
            self._bad_models[name] = {
                "failures": failures,
                "retry_at": time.monotonic() + backoff,
                "error": f"{type(exc).__name__}: {exc}",
            }
            log.exception(
                "stream %s model '%s' unavailable (failure %d); using "
                "default, retrying in %.0fs",
                device_id, name, failures, backoff,
            )
            return None
        if bad is not None:
            self._bad_models.pop(name, None)
            log.info("model '%s' recovered after %d failure(s)",
                     name, bad["failures"])
        return name, spec.clip_len

    # -- profiling (SURVEY.md §5.1: the reference has no tracing at all) --

    def _prof_snapshot(self) -> dict:
        """Engine state frozen into every capture bundle (obs/prof.py):
        the perf/SLO numbers that were true while the trace ran."""
        snap = {
            "ticks": self.ticks,
            "batches": self.batches,
            "perf": self.perf.snapshot(),
        }
        if self.slo is not None:
            snap["slo"] = self.slo.snapshot()
        if self.ladder is not None:
            snap["rung"] = self.ladder.rung
        return snap

    def start_profile(self, log_dir: str) -> None:
        """Begin an unbounded jax.profiler trace.

        Deprecated: thin delegate kept for signature compatibility; the
        capture path lives in obs/prof.py (``self.prof``), which shares
        one busy flag with the bounded ``/api/v1/profile?ms=N`` captures
        and the burn triggers. Prefer ``self.prof.capture(ms)``.
        """
        if self.prof is None:
            raise RuntimeError("profiling disabled (engine.prof=False)")
        self.prof.start(log_dir)

    def stop_profile(self) -> None:
        """Stop the trace begun by :meth:`start_profile` (deprecated
        delegate; see start_profile)."""
        if self.prof is None:
            raise RuntimeError("profiling disabled (engine.prof=False)")
        self.prof.stop()

    def save_checkpoint(self, path: Optional[str] = None) -> str:
        """Persist current params (msgpack, atomic)."""
        import jax

        from ..utils.checkpoint import save_msgpack

        if self._variables is None:
            raise RuntimeError(
                "save_checkpoint before warmup would overwrite the "
                "checkpoint with empty params; call warmup() first"
            )
        path = path or self._cfg.checkpoint_path
        if not path:
            raise ValueError("no checkpoint path configured")
        variables = self._variables
        if self._cfg.quantize:
            # Checkpoints stay full-precision (the canonical format every
            # load path expects); quantization re-applies at next warmup.
            # The exact pre-quantization weights are gone, so this write is
            # LOSSY relative to whatever the engine originally loaded —
            # overwriting a trained f32 checkpoint bakes in up to
            # absmax/254 per-element error. Warn, don't silently clobber.
            from ..models.quantize import dequantize_tree

            log.warning(
                "save_checkpoint from a quantized engine writes int8-"
                "roundtripped weights (lossy vs the originally loaded "
                "params); keep a copy of the source checkpoint"
            )
            variables = dequantize_tree(variables)
        # Unboxed raw trees on disk — one canonical format shared with
        # tools/import_weights.py (see the load path in warmup).
        from ..parallel.sharding import unbox

        save_msgpack(path, jax.tree.map(np.asarray, unbox(variables)))
        return path

    def start(self) -> None:
        if self._model is None:
            self.warmup()
        entries = [list(g) for g in self._cfg.prewarm]
        if self._aot_dir:
            # AOT prewarm cache (r19): union the manifest's recorded
            # program set into the configured prewarm list — every
            # compile below is then a persistent-cache hit on a member
            # sharing the dir, so a spawned member holds its programs
            # within one scrape interval. A mismatched/absent manifest
            # is just an empty union (clean compile).
            from . import aot_cache

            def _ekey(e):
                try:
                    return (int(e[0]), int(e[1]), int(e[2]),
                            str(e[3]) if len(e) >= 4 and e[3] else "")
                except (TypeError, ValueError, IndexError):
                    return None

            seen = {k for k in (_ekey(e) for e in entries) if k}
            programs = aot_cache.load_manifest(self._aot_dir) or []
            # r17: replay only programs recorded under THIS mesh spec —
            # a stale single-chip manifest on a mesh boot (or vice
            # versa) contributes nothing and degrades to clean compile.
            for entry in aot_cache.prewarm_entries(programs,
                                                   mesh=self._mesh):
                key = _ekey(entry)
                if key is not None and key not in seen:
                    seen.add(key)
                    entries.append(entry)
            if programs:
                log.info(
                    "AOT prewarm manifest: %d recorded programs, "
                    "%d total prewarm entries", len(programs), len(entries),
                )
        # Prewarm progress backs the fleet tier's "warming" state: a
        # member is scraped-alive but must not take migrated traffic (or
        # be retired) until complete. Skipped/failed entries still count
        # as done — log-and-continue must not wedge a member in warming.
        self._prewarm_required = len(entries)
        self._prewarm_done = 0
        self._prewarm_started = True   # the entry list is now final
        self._size_windows(entries)
        for geom in entries:
            # Log-and-continue like every other per-item path here: a bad
            # prewarm entry must not abort server boot, and buckets must be
            # ones the collector can actually dispatch (post mesh filter).
            try:
                # [h, w, bucket], [h, w, bucket, model] or
                # [h, w, bucket, model, stem]: the optional 4th element
                # prewarms a non-default model's program; the optional 5th
                # pins the stem variant the entry was written for (config
                # files survive engine.stem flips — a mismatched entry is
                # skipped below instead of compiling a program the engine
                # can never serve, its params being the other variant's).
                model = None
                if len(geom) >= 4:
                    model = str(geom[3])
                stem = str(geom[4]) if len(geom) >= 5 else None
                h, w, bucket = (int(v) for v in geom[:3])
                if bucket not in self._buckets:
                    log.warning(
                        "prewarm bucket %d not in effective buckets %s; "
                        "skipping", bucket, self._buckets,
                    )
                    continue
                log.info("prewarming program for %dx%d bucket=%d model=%s",
                         h, w, bucket, model or self._spec.name)
                self.compile_for((h, w), bucket, model, stem=stem)
            except Exception:
                log.exception("prewarm entry %r failed; continuing", geom)
            finally:
                self._prewarm_done += 1
        if self._xfer is not None:
            self._xfer.start()
        self._drain_thread = threading.Thread(
            target=self._drain_loop, name="tpu-engine-drain", daemon=True
        )
        self._drain_thread.start()
        self._thread = threading.Thread(
            target=self._run, name="tpu-engine", daemon=True
        )
        self._thread.start()
        if self.quality is not None and self._cfg.quality_canary:
            try:
                self._start_canary()
            except Exception:
                log.exception(
                    "canary start failed; integrity loop disabled")

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
        if self._canary_thread is not None:
            self._canary_thread.join(timeout=10)
        if self._xfer is not None:
            # After the tick thread: nothing submits anymore, and any
            # handle the tick thread abandoned mid-wait has resolved.
            self._xfer.stop()
        if self._drain_thread is not None:
            # Sentinel AFTER the tick loop stops producing: everything
            # queued before it still drains (no result is dropped on a
            # clean stop), then the drain thread exits. Bounded put: a
            # wedged device keeps the depth-2 queue full with the drain
            # thread stuck inside a fetch — shutdown must not block
            # forever on the sentinel (the daemon thread is abandoned
            # after the bounded join, like every other stop step here).
            try:
                self._drain_q.put(None, timeout=10)
            except queue.Full:
                log.warning(
                    "drain queue full at stop (wedged device fetch?); "
                    "abandoning drain thread"
                )
            self._drain_thread.join(timeout=10)
        with self._sub_lock:
            # Close the fan-out before the end-sentinels: an abandoned
            # (wedged) drain thread that later finishes its fetch would
            # otherwise _publish into queues whose consumers already saw
            # None — a post-sentinel result a client can never attribute.
            self._fanout_closed = True
            for q, _ in self._subscribers:
                q.put(None)
            self._subscribers.clear()
        if self._cfg.stage_trace:
            # The stage maps (obs/stages.py) of the programs the stage
            # records name, left behind as plain dicts: whoever reads the
            # records reads them after this engine, and its executables,
            # are gone (benchmark/run.py needs the HBM back first).
            try:
                stages.built({r["program"] for r in self.stage_records
                              if r.get("program")})
            except Exception:
                log.exception("stage maps not built")

    # -- output-quality plane (obs/quality.py) --

    def _start_canary(self) -> None:
        """Arm the canary integrity loop: an engine-owned publisher
        replays the committed golden trace (cfg.quality_canary) into the
        bus at low cadence under cfg.quality_canary_stream, and the drain
        thread folds each emitted slot's host checksum into the
        CanaryChecker, which compares once per trace loop. The canary
        rides the normal serving path end to end — bus, collector,
        device program, NMS, drain — so a silent numerics regression
        anywhere on that path moves the fold and fires the
        ``canary_integrity`` SLO + watchdog."""
        from ..obs.quality import CanaryChecker
        from ..obs.slo import BurnRateSLO, integrity_slo
        from ..replay.player import TracePlayer

        player = TracePlayer(self._cfg.quality_canary)
        if not player.devices:
            raise ValueError(
                f"canary trace {self._cfg.quality_canary!r} has no streams")
        events = player.frame_events(player.devices[0])
        if not events:
            raise ValueError(
                f"canary trace {self._cfg.quality_canary!r} has no frames")
        slo = None
        if self.slo is not None:
            slo = self.slo.add(BurnRateSLO(
                integrity_slo(warmup_s=self._cfg.slo_warmup_s)))
        self.canary = CanaryChecker(
            loop_len=len(events),
            stream=self._cfg.quality_canary_stream,
            golden=self._cfg.quality_canary_golden or None,
            watchdog=self.watchdog,
            slo=slo,
        )
        self._canary_thread = threading.Thread(
            target=self._canary_loop, args=(events,),
            name="tpu-engine-canary", daemon=True,
        )
        self._canary_thread.start()

    def _canary_loop(self, events: list) -> None:
        """Low-cadence golden-replay publisher (dedicated thread). Frames
        re-enter through the public bus API like any camera's; publish
        failures (bus flap, ring full) are logged once per failure run
        and otherwise skipped — the checker voids incomplete cycles, so
        dropped canary frames can never manufacture a false mismatch."""
        from ..replay.player import meta_for
        from ..replay.trace import decode_frame

        name = self._cfg.quality_canary_stream
        period = 1.0 / max(self._cfg.quality_canary_fps, 0.1)
        frame0 = decode_frame(events[0])
        i = 0
        alive = False
        warned = False
        while not self._stop.wait(period):
            ev = events[i % len(events)]
            i += 1
            try:
                if not alive:
                    self._bus.create_stream(name, frame0.nbytes)
                    alive = True
                frame = decode_frame(ev)
                meta = meta_for(
                    ev, frame, timestamp_ms=int(time.time() * 1000))
                self._bus.publish(name, frame, meta)
                warned = False
            except Exception as exc:
                alive = False
                if not warned:
                    log.warning("canary publish failed: %s", exc)
                    warned = True

    def _on_quality_transition(self, stream: str, old: str,
                               new: str) -> None:
        """Verdict transitions become uplink alert events on the same
        AnnotateRequest channel the reference's cloud consumes
        (examples/annotation.py shape): type="quality", the verdict as
        object_type — black/frozen/flatline onsets AND recoveries reach
        the cloud side without anything scraping /metrics."""
        if self._annotations is None:
            return
        req = pb.AnnotateRequest(
            device_name=stream,
            type="quality",
            start_timestamp=int(time.time() * 1000),
            object_type=new,
            confidence=1.0,
            ml_model="obs.quality",
            ml_model_version=old,
        )
        try:
            self._annotations.publish(req.SerializeToString())
        except Exception:
            log.exception("quality alert publish failed")

    # -- results fan-out --

    def _stream_interest(self, device_id: str) -> bool:
        """Does anything consume inference results for this stream right
        now? The annotation uplink is standing interest (the engine is its
        producer, feeding the cloud the reference's clients fed,
        examples/annotation.py); otherwise a live subscriber must cover
        the stream. With neither, inferring would compute results nobody
        reads — the collector gates the stream out (SURVEY §2.3 P6).
        The canary stream's consumer is the integrity checker itself:
        always of interest while the loop is armed, or its golden-replay
        frames would never reach the device on a quiet engine."""
        if self.canary is not None and device_id == self.canary.stream:
            return True
        if self._annotations is not None:
            return True
        with self._sub_lock:
            return any(
                ids is None or device_id in ids
                for _, ids in self._subscribers
            )

    def subscribe(self, device_ids=None, context=None, timeout: float = 0.5):
        """Blocking iterator of pb.InferenceResult for gRPC serving."""
        q: queue.Queue = queue.Queue(maxsize=256)
        ids = set(device_ids) if device_ids else None
        with self._sub_lock:
            self._subscribers.append((q, ids))
        try:
            while not self._stop.is_set():
                if context is not None and not context.is_active():
                    return
                try:
                    item = q.get(timeout=timeout)
                except queue.Empty:
                    continue
                if item is None:
                    return
                yield item
        finally:
            with self._sub_lock:
                self._subscribers = [
                    (sq, si) for sq, si in self._subscribers if sq is not q
                ]

    def stats(self) -> Dict[str, StreamStatsView]:
        # Snapshot copies, never the live objects: the drain thread keeps
        # mutating StreamStats after this returns, and handing out live
        # references let API callers observe mid-update state (or corrupt
        # engine accounting by writing through them).
        return {
            device_id: StreamStatsView(
                frames=st.frames,
                last_latency_ms=st.last_latency_ms,
                ema_latency_ms=st.ema_latency_ms,
                last_batch=st.last_batch,
                bucket=st.last_batch,
                padded_slots=st.padded_slots,
                device_ms_ema=st.device_ms_ema,
            )
            for device_id, st in list(self._stats.items())
        }

    def prewarm_status(self) -> Dict[str, Any]:
        """Prewarm progress for /api/v1/stats (r19): the fleet tier
        derives the "warming" member state from ``complete`` — a
        spawned member is scraped-alive the moment REST binds but must
        not take migrated traffic until its program set compiled. A
        member with nothing to prewarm is complete from boot — UNLESS
        the AOT cache is on: then the program set is the manifest union
        computed inside start(), after the (potentially long) warmup, so
        "complete" holds False until that list exists (or 0>=0 during
        warmup would let the router place onto a mid-ramp member)."""
        required = self._prewarm_required
        done = self._prewarm_done
        return {
            "required": required,
            "done": done,
            "complete": self._prewarm_started and done >= required,
            "aot_cache": bool(self._aot_dir),
        }

    def _run_probe(self) -> None:
        """Device round-trip on a dedicated thread; writes the cache when
        (if) the runtime answers."""
        try:
            import jax
            import jax.numpy as jnp

            if self._probe_fn is None:
                self._probe_fn = jax.jit(jnp.add)
            ok = int(self._probe_fn(jnp.int32(1), jnp.int32(1))) == 2
        except Exception:
            log.exception("device health probe failed")
            ok = False
        self._probe_cache = (time.monotonic(), ok)

    def health(self, probe_ttl_s: float = 5.0,
               probe_wait_s: float = 2.0) -> dict:
        """TPU-side health (SURVEY.md §5.3 — the rebuild adds device
        liveness and compile-cache warmth on top of the reference's
        container-level health): engine-thread liveness, last-tick age, a
        round-trip device probe, and how many programs are compiled.

        The probe (a tiny jitted add) runs on ONE dedicated thread and its
        result is cached ``probe_ttl_s`` — a wedged runtime must neither
        leak a new blocked thread per poll nor hang the caller, so polls
        wait at most ``probe_wait_s`` and a probe that cannot answer by
        then reports ``device_ok=False`` until it does.

        ``stale`` compares the last completed tick against
        cfg.health_stale_after_s, which must stay larger than any
        legitimate in-tick XLA compile (first frame of a new geometry
        compiles inside the tick; see cfg.prewarm to move that to boot) —
        it flags a wedged loop, not a busy one.
        """
        import jax

        tick_alive = self._thread is not None and self._thread.is_alive()
        drain_alive = (
            self._drain_thread is not None and self._drain_thread.is_alive()
        )
        # Every stage of the pipeline must live: a dead drain thread backs
        # the queue up and silently stops every emission even while ticks
        # keep completing; a dead transfer thread starves every dispatch
        # at the placement pop the same way.
        xfer_alive = self._xfer is None or self._xfer.alive()
        alive = tick_alive and drain_alive and xfer_alive
        now = time.monotonic()
        age = (now - self.last_tick_monotonic) if self.last_tick_monotonic else None
        with self._probe_spawn_lock:
            # Check-then-spawn under a lock, inputs re-read inside it:
            # concurrent /healthz polls must not each start a probe thread
            # (one would become untracked), and a poll that waited on the
            # lock must see the probe the winner's thread just completed.
            now = time.monotonic()
            ts, ok = self._probe_cache
            if (ok is None or now - ts > probe_ttl_s) and (
                self._probe_thread is None or not self._probe_thread.is_alive()
            ):
                self._probe_thread = threading.Thread(
                    target=self._run_probe, name="tpu-health-probe", daemon=True
                )
                self._probe_thread.start()
        if self._probe_thread is not None and self._probe_thread.is_alive():
            self._probe_thread.join(timeout=probe_wait_s)
        _, ok = self._probe_cache
        if self._probe_thread is not None and self._probe_thread.is_alive():
            # Probe outstanding past its wait budget: the runtime is not
            # answering. A stale cached success must not mask that — report
            # unhealthy until the probe actually returns.
            ok = False
        stale_after = self._cfg.health_stale_after_s
        stale = age is not None and age > stale_after
        # Per-stream models currently tripped by the failure breaker:
        # operators see WHY a stream silently serves the default model and
        # when the next half-open retry is due. Informational — does not
        # flip `healthy` (the default model still serves every stream).
        disabled = {
            name: {
                "failures": bad["failures"],
                "retry_in_s": round(max(0.0, bad["retry_at"] - now), 1),
                "error": bad["error"],
            }
            for name, bad in list(self._bad_models.items())
        }
        return {
            "disabled_models": disabled,
            "healthy": bool(alive and ok and not stale),
            "engine_thread_alive": tick_alive,
            "drain_thread_alive": drain_alive,
            "transfer_thread_alive": (
                self._xfer.alive() if self._xfer is not None else None),
            "tick_age_s": round(age, 3) if age is not None else None,
            "tick_stale": stale,
            "device_ok": bool(ok),
            "backend": jax.default_backend(),
            "devices": len(jax.devices()),
            "programs_compiled": len(self._step_cache),
            "model": self._spec.name if self._spec else None,
            "ticks": self.ticks,
        }

    # -- compiled step construction --

    def compiled_programs(self) -> Dict[tuple, Any]:
        """``(model, stem, src_hw, bucket)`` -> the AOT executable of every
        serving step compiled so far (None for one that fell back to
        jit): what ``chip_smoke.py`` reads to see which kernels the
        served program really contains. A windowed clip step's key has a
        fifth element, its window buffer's slots."""
        return {key: fn.compiled
                for key, fn in list(self._step_cache.items())}

    def compile_for(self, src_hw: tuple, bucket: int,
                    model: Optional[str] = None, *,
                    stem: Optional[str] = None) -> None:
        """Prewarm the program for one (source geometry, bucket) — of
        the default model, or of any registry model a stream resolves to
        (``model``; 4-element cfg.prewarm entries). Multi-family fleets
        otherwise pay each extra model's compile stall on its first
        mid-soak frame (the stall r11's harness worked around by
        prewarming downshift buckets for the default model only).

        ``stem`` pins the stem variant a prewarm entry expects
        (5-element cfg.prewarm entries): the engine's stem is a warmup
        decision — params are folded/initialized for exactly one
        variant — so an entry written for the OTHER variant is skipped
        with a warning rather than compiled into an unservable program."""
        effective = getattr(self._cfg, "stem", "classic") or "classic"
        if stem is not None and stem != effective:
            log.warning(
                "prewarm entry pinned stem=%r but engine serves stem=%r; "
                "skipping %sx%s bucket=%d",
                stem, effective, src_hw[0], src_hw[1], bucket,
            )
            return
        spec, _, variables = self._ensure_model(model or self._spec.name)
        if self._window_on_device(spec.name):
            self._compile_windowed(tuple(src_hw), bucket, spec, variables)
            return
        shape = (bucket,) + (
            (spec.clip_len,) if spec.clip_len else ()
        ) + tuple(src_hw) + (3,)
        args = [self._place(np.zeros(shape, np.uint8))]
        if self._head_pool(spec.name) is not None:
            # every row padded: the state is gathered clipped, nothing is
            # scattered, and the pool's shapes are the serving ones
            pool = self._head_pool(spec.name)
            pool.ensure(bucket)
            plan = pool.plan((), bucket)
            out = self._step(src_hw, bucket, model)(
                variables, args[0], pool.state, plan["idx"], plan["pos0"],
                plan["reset"], plan["rounds"])
            pool.state = out["state"]
            return
        if self._quality_device and not spec.clip_len:
            side = self._cfg.quality_thumb
            thumbs = np.zeros((bucket, side, side), np.float32)
            # Under a mesh the serving thumbnails arrive dp-sharded (the
            # sharded pool's gather); prewarm with the same sharding or
            # the first real batch would compile a second program.
            args.append(self._place(thumbs) if self._mesh is not None
                        else thumbs)
        self._step(src_hw, bucket, model)(variables, *args)

    def _size_windows(self, entries) -> None:
        """Before a start's prewarm list is compiled: every windowed
        model's buffer gets room for the largest bucket its entries name,
        so each of its programs is keyed by the capacity it is served at
        (entry by entry a list of three buckets would leave three programs
        nobody runs). An entry ``compile_for`` will refuse is passed over
        here too."""
        for geom in entries:
            try:
                h, w, bucket = (int(v) for v in geom[:3])
                name = str(geom[3]) if len(geom) >= 4 and geom[3] \
                    else self._spec.name
                if bucket in self._buckets and self._window_on_device(name):
                    self._window_pool(name).ensure((h, w, 3), bucket)
            except Exception:
                continue    # compile_for logs it

    def _compile_windowed(self, src_hw: tuple, bucket: int, spec,
                          variables) -> None:
        """``compile_for`` for a model whose clip windows live on the
        device: the windowed step, the form this engine serves, on a batch
        of single frames with every row padded (nothing is written) and
        the window buffer at its serving shape, kept for the fleet; then
        the write alone, for rounds in which every window still fills
        (``_dispatch``). Neither result is waited for."""
        geom = src_hw + (3,)
        wpool = self._window_pool(spec.name)
        wpool.ensure(geom, bucket)
        slots = wpool.capacity(geom)
        plan = wpool.plan((), geom, bucket)
        frames = self._place(np.zeros((bucket,) + geom, np.uint8))
        try:
            out = self._step(src_hw, bucket, spec.name, window=slots)(
                variables, frames, wpool.window(geom), plan["idx"],
                plan["pos"])
            wpool.put(geom, self._window_writer(
                src_hw, bucket, spec.name, slots)(
                    out["window"], frames, plan["idx"], plan["pos"]))
        except Exception:
            wpool.lost(geom, "step_error")      # donated
            raise

    def _head_pool(self, model: str):
        """The state pool of a stream-head model (one a model); None for a
        model whose step carries no head state."""
        pool = self._head_pools.get(model)
        if pool is None:
            spec, mod, _ = self._ensure_model(model)
            if spec.kind not in StreamStatePool.kinds:
                return None
            pool = self._head_pools[model] = StreamStatePool(
                mod, grow=max(self._buckets or (1,)),
                note_round=self._note_head_round)
            self._per_stream.append(pool)
        return pool

    def _note_head_round(self, prefill: int, decode: int,
                         resets: int) -> None:
        self._m_head_tokens["prefill"].inc(prefill)
        self._m_head_tokens["decode"].inc(decode)
        self._m_head_resets.inc(resets)

    def _window_on_device(self, model: str) -> bool:
        """Where a model's clip window lives, from what the engine
        observes: on the device for a clip-taking step on one device, on
        the host (``collector._ClipRing``) over a mesh, and for the
        ``stream`` kind. That kind's round is its second-long step, so a
        window on the device buys its latency nothing, and its set-up read
        longer with one (PERF.md 6, PR 31); the windowed step and the
        dispatcher take it as they take the others."""
        if not self._device_windows:
            return False
        spec = self._ensure_model(model or self._spec.name)[0]
        return bool(spec.clip_len) and spec.kind != "stream"

    def _window_pool(self, model: str):
        """The clip-window pool of a clip-taking model (one a model)."""
        pool = self._window_pools.get(model)
        if pool is None:
            spec, _, _ = self._ensure_model(model)

            def program(geom, bucket, slots, write_only):
                # called as the step is: the windowed step, or
                # window_write alone (no output but the window)
                src_hw = tuple(geom[:2])
                if not write_only:
                    return self._step(src_hw, bucket, model, window=slots)
                write = self._window_writer(src_hw, bucket, model, slots)

                def alone(variables, frames, window, idx, pos):
                    return {ClipWindowPool.key: write(window, frames, idx,
                                                      pos)}

                alone.program = getattr(write, "program", None)
                return alone

            pool = self._window_pools[model] = ClipWindowPool(
                spec.clip_len, self._buckets or (1,),
                note_restart=self._note_window_restart, program=program)
            self._per_stream.append(pool)
        return pool

    def _carried(self, model: str, window: bool, canvas: bool = False):
        """The states the step of (model, window) carries from round to
        round, in the order of their arguments after ``frames``: ``_step``
        reckons the donated positions from them, ``_dispatch`` plans,
        calls and commits them. From what the engine observes: single
        frames bound for device windows, the model's spec, and the quality
        plane's device side for a step of single frames (never a canvas
        group's: its synthetic _canvas<i> ids must not claim thumbnail
        rows, and a canvas "frame" has no per-stream diff meaning anyway;
        full-frame refreshes keep the signal)."""
        spec = self._ensure_model(model)[0]
        states = [self._window_pool(model)] if window else []
        head = self._head_pool(model)
        if head is not None:
            states.append(head)
        if self._quality_device and not spec.clip_len and not canvas:
            states.append(self._thumbs)
        return states

    def _leave_windows(self, device_ids, name: Optional[str]) -> None:
        """These streams are served under model ``name`` now (None: one
        whose windows live on the host): each leaves the device window it
        had under another model, counted where that held a frame."""
        if name is None and not self._window_home:
            return
        for did in device_ids:
            was = self._window_home.get(did, name)
            if was != name and was in self._window_pools:
                if self._window_pools[was].held(did):
                    self._note_window_restart("model", 1)
                self._window_pools[was].pop(did)
            if name is None:
                self._window_home.pop(did, None)
            else:
                self._window_home[did] = name

    def _window_writer(self, src_hw: tuple, bucket: int, model: str,
                       slots: int):
        """``window_write`` compiled for (model, geometry, bucket, window
        slots): what a batch runs whose windows are all still filling.
        Cached and timed beside the steps, under a six-element key."""
        key = (model, getattr(self._cfg, "stem", "classic"), src_hw, bucket,
               slots, "write")
        fn = self._step_cache.get(key)
        if fn is None:
            import jax

            # its compile is counted under a name of its own: the step's
            # record carries the FLOPs the live MFU is reckoned from
            fn = self._step_cache[key] = _TimedStep(
                jax.jit(window_write, donate_argnums=(0,)), self.perf,
                f"{model}/window_write", src_hw, bucket)
        return fn

    def _note_window_restart(self, reason: str, n: int) -> None:
        self._m_window_restarts.labels(reason).inc(n)
        self._window_restarts += n

    def _restart_windows(self, groups, reason: str) -> None:
        """Frames of these groups were read and will not reach their
        streams' device windows: the windows start anew."""
        for g in groups:
            if g.window:
                pool = self._window_pools.get(g.model or self._spec.name)
                if pool is not None:
                    pool.restart(g.device_ids, reason)

    def _place(self, frames: np.ndarray):
        """Shard the batch dim over dp when serving on a mesh; pass through
        numpy (implicit single-device transfer) otherwise. Tick-thread
        fallback path — with cfg.prefetch the transfer thread uses
        `_place_device` instead, which always performs the real copy."""
        if self._mesh is None:
            return frames
        from ..parallel import batch_sharding, shard_put

        return shard_put(frames, batch_sharding(self._mesh, frames.ndim))

    def _place_device(self, frames: np.ndarray):
        """Real async H2D placement for the prefetch stage: single-chip
        batches device_put explicitly (the legacy passthrough deferred
        the copy into the step call, serializing it on the tick thread),
        mesh batches shard over dp via ``shard_put`` — one async
        ``device_put`` per mesh slice, issued back-to-back so the S
        copies overlap instead of staging through a single host->chip0
        transfer (r17 tentpole leg 2)."""
        if self._mesh is None:
            import jax

            return jax.device_put(frames)
        from ..parallel import batch_sharding, shard_put

        return shard_put(frames, batch_sharding(self._mesh, frames.ndim))

    def _step(self, src_hw: tuple, bucket: int, model: Optional[str] = None,
              window: int = 0):
        """The compiled step of (model, geometry, bucket). ``window``: the
        slots of the model's device window buffer at this geometry; the
        step is then the windowed form (``_windowed``), one program a
        capacity, under a five-element key."""
        model = model or self._spec.name
        # The key carries the stem-variant axis (round 15): cfg.stem picks
        # a different compiled program (fused vs classic preprocess, 2x2
        # vs 3x3 stem) for the SAME model name — recording it keys every
        # cached program by what it actually computes, so introspection
        # and any future runtime stem flip can never alias the variants.
        key = (model, getattr(self._cfg, "stem", "classic"), src_hw, bucket)
        if window:
            key += (window,)
        fn = self._step_cache.get(key)
        if fn is not None:
            self._m_cache_hit.inc()
        else:
            self._m_cache_miss.inc()
            import jax

            spec, mod, _ = self._ensure_model(model)
            raw = build_serving_step(
                mod, spec,
                quality_thumb=(self._cfg.quality_thumb
                               if self._quality_device else 0),
                mesh=self._mesh, window=bool(window),
            )
            if self._cfg.quantize:
                from ..models.quantize import dequantize_tree

                base = raw

                def raw(qv, *args, _base=base):
                    # Dequantize inside the program: XLA fuses int8*scale
                    # into each weight's first consumer, HBM stays int8.
                    return _base(dequantize_tree(qv), *args)
            # Donate the frames slot (argnum 1) so XLA may reuse the input
            # HBM allocation — aliasing only, numerics (and the replay
            # goldens) are untouched. The thumbnail argument is never
            # donated: its buffer is a gather view of the device-resident
            # pool. "auto" donates only where the donation can be taken:
            # a TPU program partitioned over a mesh hands the slot to XLA
            # as a buffer donor. On ONE chip jit can alias a donated input
            # only to an output of its shape and dtype, the step has no
            # uint8 frame-plane output, and the donation is dropped with
            # a warning at every compile — as on the CPU backend.
            donate = ()
            if self._cfg.donate_frames == "on" or (
                    self._cfg.donate_frames == "auto"
                    and jax.default_backend() == "tpu"
                    and self._mesh is not None and self._mesh.size > 1):
                donate = (1,)
            # what each carried state lets the step rewrite in place, at
            # its place after (variables, frames)
            at = 2
            for state in self._carried(model, bool(window)):
                donate += tuple(at + i for i in state.donated)
                at += state.step_args
            # Compile attribution (obs/perf.py): the wrapper AOT-compiles
            # on first call, recording wall time + XLA cost analysis per
            # (model, geometry, bucket) — this is the only cache-miss
            # site, so every compile in the process is accounted.
            record = None
            if self._aot_dir:
                # Every serving step lands in the prewarm manifest (this
                # is the only miss site, so the recorded set IS the
                # program set a member must hold) — but only once its
                # FIRST call compiles and executes successfully, or a
                # reliably-failing (geometry, bucket, model) would be
                # replayed (and re-fail) on every future spawn's boot.
                # record_program is internally best-effort (never raises).
                from . import aot_cache

                def record(_dir=self._aot_dir, _model=model,
                           _stem=getattr(self._cfg, "stem", "classic"),
                           _hw=src_hw, _bucket=bucket,
                           _mesh=self._mesh):
                    aot_cache.record_program(
                        _dir, model=_model, stem=_stem,
                        src_hw=_hw, bucket=_bucket, mesh=_mesh)

            fn = _TimedStep(jax.jit(raw, donate_argnums=donate),
                            self.perf, model, src_hw, bucket,
                            on_first_success=record,
                            on_compiled=self._hbm_compile_tap(
                                model, src_hw, bucket))
            self._step_cache[key] = fn
        return fn

    def _hbm_compile_tap(self, model: str, src_hw: tuple, bucket: int):
        """``on_compiled`` callback for a :class:`_TimedStep`: records
        the program's ``memory_analysis()`` footprint (argument/output/
        temp/code bytes, donated aliasing credited) into the HBM plane
        under its (model, stem, geometry, bucket, mesh) key. None when
        cfg.hbm is off — the wrapper then carries no callback at all,
        keeping the kill-switch path bit-identical and free."""
        if self.hbm is None:
            return None
        stem = getattr(self._cfg, "stem", "classic")
        mesh = f"dp{self._shards}" if self._mesh is not None else ""

        def tap(compiled, _model=model, _hw=src_hw, _bucket=bucket,
                _stem=stem, _mesh=mesh):
            from ..obs.perf import memory_summary

            try:
                self.hbm.note_program(
                    _model, _hw, _bucket, memory_summary(compiled),
                    stem=_stem, mesh=_mesh)
            except Exception:     # footprint attribution must never
                log.debug(        # take down the drain thread
                    "hbm compile tap failed", exc_info=True)

        return tap

    # -- engine loop --

    def _run(self) -> None:
        tick_s = self._cfg.tick_ms / 1000.0
        inferred: List[str] = []
        self._tick_mark = time.perf_counter()
        self._assemble_s = self._pace_s = 0.0
        while not self._stop.is_set():
            t0 = time.monotonic()
            # The loop must outlive any single bad batch: a dead engine
            # thread would leave subscribers blocked forever (same
            # log-and-keep-going stance as the reference's worker loops,
            # rtsp_to_rtmp.py:186-187).
            try:
                # Device-fault failover (engine/fault.py, r22): shards
                # marked pending by the dispatch error path or the stall
                # probe fail over HERE, at the top of the tick — the one
                # point where this thread owns every mesh-coupled
                # structure and no dispatch is mid-flight on it.
                if self.faults is not None and self.faults.pending():
                    self._execute_failover()
                # Degradation ladder: one observe per tick (queue depth +
                # last tick's duration vs budget); the rung gates the
                # stages below. Closed-ladder overhead is one comparison.
                # Effective backpressure depth for this tick: raw drain
                # qsize without prefetch; with prefetch, a full queue
                # counts only when the tick thread actually blocked on
                # the handoff since the last observation (see
                # _drain_blocked above). A paced read is that block taken
                # early: the queue it kept from filling counts as full.
                depth = self._drain_q.qsize()
                if self._drain_blocked:
                    depth = max(depth, self._drain_q.maxsize)
                elif self._xfer is not None:
                    depth = min(depth, 1)
                self._drain_blocked = False
                self._bp_depth = depth
                rung = "normal"
                if self.ladder is not None:
                    rung = self.ladder.observe(
                        queue_depth=depth,
                        tick_lag_s=self._last_tick_dur_s,
                        tick_budget_s=tick_s,
                        # SLO-level pressure: a sustained multi-window
                        # budget burn (obs/slo.py) starts shedding before
                        # queues physically back up.
                        slo_burning=(self._slo_burning
                                     and self._cfg.slo_ladder),
                        # Memory-level pressure (r21, obs/hbm.py): shed/
                        # stretch BEFORE the allocator OOMs — a byte
                        # forecast inside the horizon is as real as a
                        # queue backing up. One cached-dict read.
                        hbm_pressure=(self.hbm is not None
                                      and self.hbm.pressure()),
                    )
                    self._apply_rung_cap(rung)
                if self._cascade is not None:
                    # Cadence stretch under pressure (r23): shed
                    # temporal-head FLOPs while the ladder is degraded.
                    # ``inferred`` still holds last tick's stream list —
                    # exactly the streams whose cadence is changing.
                    self._apply_cascade_stretch(rung, inferred)
                if rung == "normal" and self._shed_seq is not None:
                    # Shed excursion closes when the ladder recovers
                    # (edge-triggered journaling, never per-tick).
                    self._close_shed_excursion()
                # One bus enumeration per tick, threaded everywhere.
                present, inferred = self._collector.partition()
                if rung == "admission_pause":
                    # Rung 3: only the admitted half competes for device
                    # slots; the paused half's workers stop decoding too
                    # (keep_streams_hot skips them). Quality-unhealthy
                    # streams (black/frozen — frames with no recoverable
                    # signal) are the first-shed candidates; the canary
                    # is never shed (shedding the integrity probe during
                    # degradation is when its signal matters most).
                    dep: frozenset = frozenset()
                    if (self.quality is not None
                            and self._cfg.quality_ladder):
                        dep = self.quality.unhealthy()
                    canary = (self.canary.stream
                              if self.canary is not None else None)
                    if canary is not None:
                        dep = dep - {canary}
                    admitted = admitted_streams(inferred, dep)
                    if (canary is not None and canary in inferred
                            and canary not in admitted):
                        admitted.append(canary)
                    inferred = admitted
                self._collector.keep_streams_hot(device_ids=inferred)
                # Read just in time (engine/pacing.py): where the device
                # is behind, wait HERE, before the frames are read, until
                # their placement would end as the device frees. A plain
                # wait: nothing is read ahead during it. It never engages
                # where the host sets the pace.
                paced_s = self._pacer.wait(self._stop)
                self._pace_s += paced_s
                t_collect0, pc_collect0 = time.time(), time.perf_counter()
                # A group's placement starts when its last frame is read,
                # beside the reads of the groups after it -- where what
                # collect() finishes is what _dispatch will place.
                sink, handed, handles = self._early_placements(rung)
                try:
                    groups = self._collector.collect(
                        device_ids=inferred, sink=sink)
                except Exception:
                    # frames may have been read and never made a group
                    for wpool in self._window_pools.values():
                        wpool.restart(list(wpool), "collect_error")
                    # the groups that were made hold leases, and the first
                    # of them are on the transfer thread already
                    if self.faults is not None:
                        for g in handed:
                            self.faults.ledger.note_dispatched(
                                _group_slots(g))
                    self._drop_groups(handed, handles, "collect_error")
                    raise
                if paced_s and groups:
                    # frames that had to wait for the device: the signal a
                    # blocked handoff gives (a tick that waited and found
                    # nothing to read kept nobody waiting)
                    self._drain_blocked = True
                if rung != "normal" and groups:
                    # Rung 1+: stale frames leave before they cost device
                    # time (shed oldest-first with a staleness bound).
                    groups = self._shed_stale_groups(groups)
                if self._roi is not None and groups:
                    # MOSAIC: motion-gate detect streams, pack active
                    # crops onto shared canvases, coast gated-idle
                    # streams (ROADMAP item 1).
                    groups = self._roi_transform(groups)
                # t_collect closes the collection: collect() itself plus,
                # under pressure or cfg.roi, the two group transforms above.
                tick = self._open_tick(t_collect0, pc_collect0)
                batches = self._dispatch(groups, tick["t_collect"], tick,
                                         handles)
                if batches:
                    # the host's lead: collect() entry -> the tick's first
                    # step call, less its wait for a predecessor step
                    self._pacer.note_lead(
                        batches[0]["t_step0"] - t_collect0
                        - batches[0].get("state_wait_s", 0.0))
                self._close_tick(tick, batches)
                if self._cascade is not None:
                    # CASCADE: scatter harvested track tiles, run the
                    # temporal head on cadence ticks, fan out events
                    # (uplink / archive / metrics / spans). A pure tap —
                    # the detect path above never branches on it.
                    self._cascade_tick()
                # Scope per-stream tracker state to streams that still
                # exist: a long-lived engine with churning device_ids must
                # not accumulate IoUTracker entries forever. Absence is
                # debounced (grace period) because a restarting worker
                # re-creates its ring unlink-then-create — one sample in
                # that window must not reset the stream's track-id
                # numbering (invariant in _assign_tracks).
                if any(self._per_stream):
                    now = time.monotonic()
                    # GC keys on bus PRESENCE, not on inference_streams():
                    # a live stream gated >grace (inference_model toggled
                    # to "none") must keep its tracker, or re-enabling
                    # would restart track-id numbering and reuse ids
                    # already uplinked for other objects.
                    present = set(present)
                    with self._state_lock:
                        for d in set().union(*self._per_stream):
                            if d in present:
                                self._tracker_absent.pop(d, None)
                                continue
                            since = self._tracker_absent.setdefault(d, now)
                            if now - since > self._TRACKER_GC_GRACE_S:
                                # Every kind of per-stream state rides the
                                # same debounced GC: a worker-restart ring
                                # gap must not reset it, but a re-added
                                # stream must not diff against a months-old
                                # signature. It starts over: thumbnail and
                                # verdict machine restart cleanly, the
                                # first frame re-gates to full, cascade
                                # event machines clear without firing,
                                # head and window slots free.
                                for container in self._per_stream:
                                    container.pop(d, None)
                                if self.quality is not None:
                                    self.quality.forget(d)
                                del self._tracker_absent[d]
            except Exception:
                if self._stop.is_set():
                    # Shutdown races (e.g. a prefetched placement abandoned
                    # mid-dispatch) are expected here — not an error.
                    log.info("engine tick aborted by shutdown")
                else:
                    log.exception("engine tick failed; continuing")
            self.ticks += 1
            self._m_ticks.inc()
            self.last_tick_monotonic = time.monotonic()
            # Tick staleness signal for the ladder: how long the work
            # phase (partition/collect/dispatch) ran, excluding the
            # assembly window that absorbs the remaining budget.
            self._last_tick_dur_s = self.last_tick_monotonic - t0
            self._watch_tick(tick_s, inferred)
            pc_assemble0 = time.perf_counter()
            try:
                # Tick remainder = incremental assembly: copy next tick's
                # frames into their batch slots as they arrive (doorbell-
                # woken) instead of sleeping then doing the whole frame
                # plane at collect() time. Falls back to a plain wait on
                # doorbell-less buses.
                self._collector.assemble_until(
                    t0 + tick_s, device_ids=inferred,
                    stop_event=self._stop,
                )
            except Exception:
                log.exception("window assembly failed; continuing")
                elapsed = time.monotonic() - t0
                if elapsed < tick_s:
                    self._stop.wait(tick_s - elapsed)
            self._assemble_s += time.perf_counter() - pc_assemble0

    def _early_placements(self, rung: str) -> tuple:
        """``(sink, groups, handles)`` for one tick's ``collect()``: the
        sink hands each group the collector finishes to the transfer
        thread at once (what the head of ``_dispatch`` would do for it
        after the collect), keeping the groups seen and the handles made,
        in order. At most ``_PrefetchStage.DEPTH`` placements start ahead
        of the dispatch, so no more batches are parked on the device than
        ``_dispatch`` itself parks; it submits the rest.

        There is a sink only where a group that ``collect()`` finished is
        certainly the group ``_dispatch`` will place: the prefetch stage
        exists, the ladder is at ``normal`` (else ``_shed_stale_groups``
        may drop or rebuild groups) and there is no ROI gate (else
        ``_roi_transform`` replaces them). Otherwise it is None and the
        tick places after the collect."""
        handed: List[BatchGroup] = []
        handles: List[Optional[_Prefetched]] = []
        if self._xfer is None or rung != "normal" or self._roi is not None:
            return None, handed, handles

        def sink(group: BatchGroup) -> None:
            handed.append(group)
            if len(handles) < _PrefetchStage.DEPTH:
                handles.append(self._xfer.submit(group, self._stop))
                self._m_placed_early.inc()

        return sink, handed, handles

    def _drop_groups(self, groups: Sequence[BatchGroup],
                     handles: Sequence[Optional[_Prefetched]],
                     reason: str) -> None:
        """Groups whose frames were read and that will not reach the drain
        thread: return each lease, count its slots dropped, leave the
        frames' ``dropped`` spans. ``handles[i]``, where there is one, is
        the placement of ``groups[i]`` on the transfer thread: its lease
        goes back only once the handle resolves -- the copy may still be
        reading the pooled host buffer."""
        for i, group in enumerate(groups):
            if i < len(handles) and handles[i] is not None:
                # Bounded: block_until_ready in the transfer loop keeps
                # this short.
                handles[i].ready.wait(timeout=5.0)
            self._collector.release(group)
            if self.faults is not None:
                self.faults.note_dropped(_group_slots(group), reason)
            if tracer.enabled:
                for did, m in zip(group.device_ids, group.metas):
                    if tracer.sampled(m.packet):
                        tracer.record(
                            did, "dropped", m.packet, reason=reason,
                            trace_id=trace_id_of(m, did),
                        )

    def _open_tick(self, t_collect0: float, pc_collect0: float) -> dict:
        """The tick's trace, measured once, on the tick thread, right after
        the collection: tick number, wall stamps, and the collector's
        phases and byte counts of this collect() (``Collector.last_trace``).
        Every batch of the tick carries a copy (``_dispatch``)."""
        pc_collect = time.perf_counter()
        tick = dict(self._collector.last_trace)
        in_collect = tick["read_s"] - tick["read_ahead_s"] + tick["fill_s"]
        tick.update(
            tick=self.ticks,
            # end of the previous tick's dispatch -> collect() entry, less
            # the assembly window (its reads are in read_s, its waiting is
            # idle) and the paced wait, stamped apart: tail of the last
            # tick, head of this one
            pre_collect_s=max(
                0.0, pc_collect0 - self._tick_mark - self._assemble_s
                - self._pace_s),
            pace_wait_s=self._pace_s,
            t_collect0=t_collect0, t_collect=time.time(),
            collect_other_s=max(0.0, pc_collect - pc_collect0 - in_collect),
        )
        return tick

    def _close_tick(self, tick: dict, batches: List[dict]) -> None:
        """Feed the tick's measurement to the counters and, when the tick
        is sampled, to the ``engine.tick`` tracer track. A tick that read
        no frame leaves no event; its whole time is idle."""
        now = time.perf_counter()
        whole_s = now - self._tick_mark
        idle_s = max(0.0, self._assemble_s - tick["read_ahead_s"])
        self._tick_mark, self._assemble_s, self._pace_s = now, 0.0, 0.0
        if not tick["frames_read"] and not batches:
            self._m_phase["idle"].inc(whole_s)
            return
        place_wait_s = sum(b["place_wait_s"] for b in batches)
        step_call_s = sum(b["step_call_s"] for b in batches)
        pool_s = sum(b.get("pool_s", 0.0) for b in batches)
        state_wait_s = sum(b.get("state_wait_s", 0.0) for b in batches)
        for phase, seconds in (
                ("pre_collect", tick["pre_collect_s"]),
                ("pace_wait", tick["pace_wait_s"]),
                ("read", tick["read_s"]), ("fill", tick["fill_s"]),
                ("collect_other", tick["collect_other_s"]),
                ("place_wait", place_wait_s), ("pool", pool_s),
                ("state_wait", state_wait_s), ("step_call", step_call_s),
                ("idle", idle_s)):
            self._m_phase[phase].inc(seconds)
        for kind in ("read", "copied", "fresh"):
            self._m_cbytes[kind].inc(tick["bytes_" + kind])
        n = tick["tick"]
        if not tracer.sampled(n):
            return
        t_end = time.time()
        tracer.record(
            "engine.tick", "tick", n, ts=t_end, dur_ms=whole_s * 1e3,
            tick=n, batches=len(batches), frames_read=tick["frames_read"],
            bytes_read=tick["bytes_read"],
            bytes_copied=tick["bytes_copied"],
            bytes_fresh=tick["bytes_fresh"],
            window_rows=sum(b.get("window_rows", 0) for b in batches),
            window_restarts=sum(b.get("window_restarts", 0)
                                for b in batches))
        tracer.record(
            "engine.tick", "pre_collect", n,
            ts=tick["t_collect0"] - tick["pace_wait_s"],
            dur_ms=tick["pre_collect_s"] * 1e3, tick=n)
        if tick["pace_wait_s"]:
            tracer.record(
                "engine.tick", "pace_wait", n, ts=tick["t_collect0"],
                dur_ms=tick["pace_wait_s"] * 1e3, tick=n)
        tracer.record(
            "engine.tick", "collect_tick", n, ts=tick["t_collect"],
            dur_ms=(tick["t_collect"] - tick["t_collect0"]) * 1e3, tick=n,
            read_ms=round(tick["read_s"] * 1e3, 3),
            fill_ms=round(tick["fill_s"] * 1e3, 3))
        for b in batches:
            extra = {"tick": n, "batch": list(b["batch"])}
            tracer.record(
                "engine.tick", "place_wait", n, ts=b["t_place_got"],
                dur_ms=b["place_wait_s"] * 1e3, **extra)
            if "pool_s" in b:
                tracer.record(
                    "engine.tick", "pool", n,
                    ts=b["t_step0"] - b["state_wait_s"],
                    dur_ms=b["pool_s"] * 1e3,
                    prefill_tokens=b["head_prefill_tokens"],
                    decode_steps=b["head_decode_steps"],
                    ctx_mean=round(b["head_ctx_mean"], 1),
                    resets=b["head_resets"], **extra)
                tracer.record(
                    "engine.tick", "state_wait", n, ts=b["t_step0"],
                    dur_ms=b["state_wait_s"] * 1e3, **extra)
            tracer.record(
                "engine.tick", "step_call", n, ts=b["t_step1"],
                dur_ms=b["step_call_s"] * 1e3, program=b["program"],
                **extra)

    @staticmethod
    def _trace_batch(tr: dict) -> None:
        """The batch's transfer- and drain-thread spans as complete events
        on their own tracer tracks (called by the drain thread once the
        batch is emitted, for sampled ticks)."""
        n = tr["tick"]
        extra = {"tick": n, "batch": list(tr["batch"])}
        tracer.record(
            "engine.transfer", "place", n, ts=tr["t_placed"],
            dur_ms=(tr["t_placed"] - tr["t_place0"]) * 1e3,
            queued_ms=round((tr["t_place0"] - tr["t_place_q"]) * 1e3, 3),
            put_call_ms=round(tr["put_call_s"] * 1e3, 3),
            ahead_ms=round(tr["place_ahead_s"] * 1e3, 3), **extra)
        tracer.record(
            "engine.drain", "drain_wake", n, ts=tr["t_deq"],
            dur_ms=(tr["t_deq"] - tr["t_submit"]) * 1e3, **extra)
        tracer.record(
            "engine.drain", "fetch", n, ts=tr["t_drained"],
            dur_ms=(tr["t_drained"] - tr["t_drain0"]) * 1e3, **extra)
        t_end = time.time()
        tracer.record(
            "engine.drain", "emit_batch", n, ts=t_end,
            dur_ms=(t_end - tr["t_drained"]) * 1e3, **extra)

    def _probe_shards(self) -> List[int]:
        """Default stall probe (engine/fault.py): one tiny H2D+D2H
        round-trip per shard lead device, each bounded by
        ``fault_probe_timeout_ms``. A wedged chip cannot answer — its
        worker thread stays stuck in the fetch (daemon, abandoned) and
        the shard reports faulted. Probes run concurrently so the whole
        sweep is one timeout, not shards-many. ``faults.probe_fn``
        (tests, the chaos soak) replaces this wholesale."""
        import jax

        from ..temporal.state_pool import shard_devices

        timeout_s = self.faults.probe_timeout_ms / 1000.0
        leads = shard_devices(self._mesh, self._shards)
        done = [threading.Event() for _ in leads]

        def roundtrip(dev, ev):
            try:
                x = jax.device_put(np.ones((8,), np.float32), dev)
                if float(np.asarray(x).sum()) == 8.0:
                    ev.set()
            except Exception:
                log.debug("shard probe failed", exc_info=True)

        threads = [
            threading.Thread(target=roundtrip, args=(dev, ev), daemon=True)
            for dev, ev in zip(leads, done)
        ]
        for t in threads:
            t.start()
        deadline = time.monotonic() + timeout_s
        bad: List[int] = []
        for s, ev in enumerate(done):
            if not ev.wait(max(0.0, deadline - time.monotonic())):
                bad.append(s)
        return bad

    def _execute_failover(self) -> None:
        """Survivor-mesh failover (tentpole, engine/fault.py): executed
        at the top of the tick, the one point where this thread owns
        every mesh-coupled structure and nothing is mid-dispatch.
        Bounded end to end by ``fault_failover_budget_ms`` (best-effort:
        each leg is bounded, an over-budget run completes and is
        reported as such rather than abandoned half-swapped).

        Order matters: (1) flush the drain pipeline so no in-flight
        batch still references the old mesh's arrays; (2) rebuild the
        mesh over the survivors IN OLD ORDER — surviving shards keep
        their physical device, which is what lets ``make_repin`` keep
        their stream pins (>= 90% gate holds by construction); (3)
        re-place params, counted-reset the sharded carry state
        (thumbnails, cascade tracks — a dead chip's rows are gone;
        state rebuilds from the stream in ticks, and the ledger records
        the reset instead of pretending), re-pin the collector; (4)
        record + prewarm the survivor-mesh program variants so the AOT
        manifest warms the NEXT failover too."""
        t0 = time.monotonic()
        budget_s = self.faults.failover_budget_ms / 1000.0
        pending = self.faults.pending()
        if self._mesh is None:
            log.error("fault pending with no mesh; clearing: %s", pending)
            self.faults.clear_pending("no_mesh")
            return
        if any(self._mesh.shape.get(a, 1) > 1
               for a in ("fsdp", "sp", "tp", "ep", "pp")):
            # Model-sharded meshes cannot lose a chip without losing
            # parameter shards — failover is a dp-replication feature.
            log.error(
                "device fault on a model-sharded mesh %s; survivor "
                "failover requires dp-only replication — not failing over",
                dict(self._mesh.shape),
            )
            self.faults.clear_pending("unsupported_mesh")
            return
        devs = list(np.asarray(self._mesh.devices).reshape(-1))
        dead = sorted(s for s in pending if 0 <= int(s) < len(devs))
        if not dead:
            log.error("pending fault shards %s out of range; clearing",
                      pending)
            self.faults.clear_pending("unattributed")
            return
        survivors = [d for s, d in enumerate(devs) if s not in set(dead)]
        if not survivors:
            log.error("all %d shards faulted; no survivor mesh — engine "
                      "keeps the old mesh and the faults stay visible in "
                      "/api/v1/faults", len(devs))
            self.faults.clear_pending("no_survivors")
            return
        kinds = sorted(set(pending.values()))
        log.warning(
            "FAILOVER: shards %s faulted (%s); rebuilding dp%d -> dp%d",
            dead, ",".join(kinds), len(devs), len(survivors),
        )
        # (1) Bounded drain flush: in-flight batches hold old-mesh
        # arrays (and pooled-buffer leases). Half the budget at most —
        # a wedged chip's fetch never finishes, and its batch is the
        # drain thread's to drop (drain_error, counted).
        flush_deadline = t0 + budget_s / 2.0
        while self._drain_q.unfinished_tasks \
                and time.monotonic() < flush_deadline \
                and not self._stop.is_set():
            time.sleep(0.01)
        if self._drain_q.unfinished_tasks:
            log.warning(
                "drain pipeline did not flush within %.0f ms; proceeding "
                "(stuck batches drop as drain_error)",
                budget_s * 500.0,
            )
        from ..parallel import make_mesh
        from ..temporal.state_pool import shard_devices
        from .collector import make_repin

        old_shards = self._shards
        old_shard_of = self._shard_of
        old_keys = list(self._step_cache.keys())
        # Stream census BEFORE the swap: pin = home shard's device under
        # the old routing, kept = that device survived (same stream ->
        # same chip after the swap, by survivor ordering).
        streams = list(self._collector.inference_streams())
        kept = sum(1 for did in streams
                   if old_shard_of(did) % old_shards not in set(dead))
        new_shards = len(survivors)
        new_mesh = make_mesh(dp=new_shards, devices=survivors)
        repin = make_repin(old_shard_of, old_shards, dead)
        new_buckets = tuple(
            b for b in self._cfg.batch_buckets if b % new_shards == 0
        ) or (new_shards,)
        # (2) The swap. Step cache first: every cached program was
        # compiled for the old mesh's sharding.
        self._step_cache.clear()
        self._mesh = new_mesh
        self._shards = new_shards
        self._shard_of = repin
        self._buckets = new_buckets
        # (3) Params back onto the survivor mesh. dp-only means fully
        # replicated — every survivor holds a complete copy, so
        # re-placement never needs the dead chip's buffers.
        for name in list(self._models):
            spec, mod, variables = self._models[name]
            try:
                variables = self._place_variables(variables)
            except Exception:
                log.exception(
                    "re-placing model '%s' on the survivor mesh failed; "
                    "keeping old placement (XLA will re-shard lazily)",
                    name,
                )
            self._models[name] = (spec, mod, variables)
            if self._spec is not None and name == self._spec.name:
                self._variables = variables
        evacuated: Dict[str, int] = {}
        if isinstance(self._thumbs, _ShardedThumbPool):
            evacuated["quality_thumbs"] = len(self._thumbs)
            self._swap_thumbs(_ShardedThumbPool(
                self._cfg.quality_thumb, mesh=new_mesh, shards=new_shards,
                shard_of=repin,
            ))
        if self._cascade is not None:
            try:
                evacuated.update(self._cascade.repin_mesh(
                    mesh=new_mesh, shards=new_shards, shard_of=repin,
                ))
            except Exception:
                log.exception("cascade re-pin failed; state dropped")
        self._collector.repin(
            shards=new_shards, shard_of=repin, buckets=new_buckets,
        )
        self.faults.configure(shards=new_shards, shard_devices={
            s: [str(d)]
            for s, d in enumerate(shard_devices(new_mesh, new_shards))
        })
        # (4) AOT: stamp the survivor-mesh variants of every program the
        # old mesh served into the manifest, then prewarm whatever the
        # manifest already holds for THIS mesh spec (a previous failover
        # to the same survivor count recorded them — warm hit).
        aot = {"recorded": 0, "prewarmed": 0}
        if self._aot_dir:
            from . import aot_cache

            seen = set()
            for (model, stem, hw, _bucket) in old_keys:
                for b in new_buckets:
                    if (model, stem, hw, b) in seen:
                        continue
                    seen.add((model, stem, hw, b))
                    aot_cache.record_program(
                        self._aot_dir, model=model, stem=stem,
                        src_hw=hw, bucket=b, mesh=new_mesh,
                    )
                    aot["recorded"] += 1
            programs = aot_cache.load_manifest(self._aot_dir) or []
            for entry in aot_cache.prewarm_entries(programs,
                                                   mesh=new_mesh):
                try:
                    h, w, bucket = (int(v) for v in entry[:3])
                    if bucket not in self._buckets:
                        continue
                    self.compile_for(
                        (h, w), bucket, str(entry[3]) or None,
                        stem=str(entry[4]) if entry[4] else None,
                    )
                    aot["prewarmed"] += 1
                except Exception:
                    log.exception("survivor prewarm %r failed; continuing",
                                  entry)
        failover_ms = (time.monotonic() - t0) * 1000.0
        event = {
            "ts": time.time(),
            "tick": self.ticks,
            "kinds": kinds,
            "shards_dead": dead,
            "survivors": new_shards,
            "failover_ms": failover_ms,
            "over_budget": failover_ms > self.faults.failover_budget_ms,
            "evacuated": evacuated,
            "streams": {
                "total": len(streams),
                "kept": kept,
                "repinned": len(streams) - kept,
            },
            "aot": aot,
        }
        self.faults.note_failover(event)
        log.warning(
            "FAILOVER complete in %.0f ms: dp%d over %s; %d/%d stream "
            "pins kept, evacuated=%s, aot=%s",
            failover_ms, new_shards, [str(d) for d in survivors],
            kept, len(streams), evacuated, aot,
        )

    def _dispatch(self, groups: List[BatchGroup], t_collect: float,
                  tick: Optional[dict] = None,
                  handles: Sequence[Optional[_Prefetched]] = ()
                  ) -> List[dict]:
        """Dispatch one tick's collected groups to the device; returns
        the traces of the batches handed to the drain thread.

        ``tick`` is the tick's trace (``_open_tick``); each device batch
        gets a copy with its own stamps added: ``batch`` = (tick, index of
        the group), ``t_place_q``/``t_place0``/``t_placed`` (handed to the
        transfer thread, picked up, placed), ``put_call_s`` (of
        ``t_place0 -> t_placed``, the part inside the placement call
        itself; the rest is the wait for the bytes), ``place_ahead_s`` (how
        long before ``t_collect`` it was handed over: 0.0 unless the
        collector's sink did it), ``place_wait_s`` (this
        thread's blocked time on the placement, ending at
        ``t_place_got``), what each carried state stamps (a stream head:
        ``head_*`` and ``pool_s``, its plan; a device window:
        ``window_rows``, rows of this batch written into their streams'
        windows), ``state_wait_s`` (blocked on the predecessor step, whose
        state this one takes), ``program`` (the id of the program the step
        call runs, ``"{model}/{H}x{W}/{bucket}"``: what pairs the batch's
        device event with that program's stage map, obs/stages.py),
        ``t_step0``/``t_step1`` and ``step_call_s``
        (around the step call; a compile shows here), ``t_submit`` and
        ``window_restarts`` (device windows started anew since the last
        batch's trace). The drain thread adds ``t_deq``, ``t_drain0``,
        ``t_drained``.

        One call sequence for every batch: the states its step carries
        from round to round (``_carried``; engine/stream_state.py) each
        plan it (``carry``: slots, index vectors, host bookkeeping), the
        step runs on their arguments with their buffers donated, and each
        takes its buffer back before the drain fetches the rest; the next
        step takes that handle at once (jax orders the two, nothing waits
        here). A group of single frames bound for device windows
        (``group.window``, from a Collector told ``device_windows``) runs
        the program its window names: the windowed step, rows whose window
        is still filling computed and not emitted, or, with no full window
        at all, the write alone (``window_write``), which ends at the
        dispatch. A failure after the frames were read restarts the
        windows involved.

        ``handles`` are the placements the collector's sink started while
        the tick was still reading (``_early_placements``), for
        ``groups[:len(handles)]`` in order; the loop submits only what is
        missing. What must precede a step (window breaks, the fault
        ledger's count, the carried states' plans, the wait for a
        predecessor step) is here, before the step call; none of it has to
        precede a placement, which only reads the leased host buffer.

        With cfg.prefetch the placement of group g+1 (and g+2) runs on
        the transfer thread while this thread dispatches group g and the
        device computes earlier batches — H2D accounting (ROADMAP item 5
        evidence) then times the REAL async device_put on the transfer
        thread, and splits off the hidden share: the copy wall time that
        overlapped in-flight device work, plus whatever share this
        thread did not have to wait out at the pop. Without prefetch the
        legacy synchronous path remains (mesh: real device_put; single
        device: numpy handoff whose transfer hides inside the async
        dispatch) — either way bytes-per-frame stays exact.

        A dispatch failure aborts the tick; every group not yet handed
        to the drain thread (this one AND the ones after it, including
        batches still in flight on the transfer thread) must return its
        lease, or a persistently failing model leaks one pooled buffer
        per tick until the pool failsafe churns. Prefetched leases are
        returned only after their transfer handle resolves — the copy
        may still be reading the pooled host buffer (``_drop_groups``;
        ``_run`` does the same for the groups a ``collect()`` that raised
        had already finished).
        """
        trace_on = tracer.enabled
        if tick is None:   # called outside the tick loop (tests, smokes)
            tick = {"tick": self.ticks, "t_collect": t_collect}
        batches: List[dict] = []
        for did in self._collector.take_window_breaks():
            wpool = self._window_pools.get(self._window_home.get(did))
            if wpool is not None:
                wpool.restart([did], "corrupt")
        if self.faults is not None:
            # FaultLedger conservation: every stream slot entering the
            # device pipeline is counted in here and counted out in the
            # emit paths (or as a reasoned drop) — the balance the
            # failover gates check.
            for g in groups:
                self.faults.ledger.note_dispatched(_group_slots(g))
        if self._roi is not None and groups:
            # Tracker-coasted groups (gated-idle streams): no device
            # work, but they ride the drain queue so per-stream emit
            # ordering against earlier in-flight batches is preserved.
            rest = []
            for g in groups:
                if g.coast is not None:
                    self._enqueue_drain(_Inflight(g, None, time.time()))
                else:
                    rest.append(g)
            groups = rest
        handles = list(handles)

        def _top_up(upto: int) -> None:
            while len(handles) < min(len(groups), upto):
                handles.append(
                    self._xfer.submit(groups[len(handles)], self._stop)
                )
        if self._xfer is not None and groups:
            _top_up(_PrefetchStage.DEPTH)
        for gi, group in enumerate(groups):
            tr = dict(tick, batch=(tick["tick"], gi))
            emit = None
            try:
                # (a windowed step is keyed by its buffer's slots: its
                # window's carry names the program, below)
                step = None if group.window else self._step(
                    group.src_hw, group.bucket, group.model)
                name = group.model or self._spec.name
                _, _, variables = self._ensure_model(name)
                if self._xfer is not None:
                    _top_up(gi + 1 + _PrefetchStage.DEPTH)
                    pre = handles[gi]
                    if pre is None:   # shutdown aborted the submission
                        raise RuntimeError(
                            "engine stopping; prefetch submission aborted")
                    t_wait = time.perf_counter()
                    while not pre.ready.wait(timeout=0.1):
                        if self._stop.is_set():
                            raise RuntimeError(
                                "engine stopping; prefetched placement "
                                "abandoned")
                    wait_s = time.perf_counter() - t_wait
                    tr.update(t_place_q=pre.t_q, t_place0=pre.t0,
                              t_placed=pre.t1,
                              put_call_s=max(0.0, pre.t_put - pre.t0),
                              place_ahead_s=max(
                                  0.0, tick["t_collect"] - pre.t_q))
                    if pre.error is not None:
                        raise pre.error
                    placed = pre.placed
                    h2d_s = pre.transfer_s
                    # Hidden share: fully overlapped when device work was
                    # in flight during the copy; otherwise the part this
                    # thread did not spend blocked on the handle (it was
                    # dispatching earlier groups meanwhile).
                    hidden_s = max(pre.overlapped_s,
                                   max(0.0, pre.transfer_s - wait_s))
                else:
                    tr["t_place_q"] = tr["t_place0"] = time.time()
                    tr["place_ahead_s"] = 0.0
                    t_h2d = time.perf_counter()
                    placed = self._place(group.frames)
                    wait_s = h2d_s = time.perf_counter() - t_h2d
                    tr["t_placed"] = time.time()
                    tr["put_call_s"] = h2d_s    # the call is all of it
                    hidden_s = 0.0
                tr["place_wait_s"] = wait_s
                tr["t_place_got"] = time.time()
                ids, rows = group.device_ids, group.rows
                if group.frames.ndim == 5:      # whole windows, host rings
                    self._m_window_rows["host"].inc(len(ids))
                if group.window or group.frames.ndim == 5:
                    self._leave_windows(ids, name if group.window else None)
                # What the step carries from round to round, planned in the
                # order of its arguments: host bookkeeping advances here.
                carried: List[Any] = []
                tr["window_rows"] = 0
                for state in self._carried(name, bool(group.window),
                                           canvas=group.crops is not None):
                    carry = state.carry(ids, group.bucket, rows,
                                        group.frames.shape[1:])
                    carried.append(carry)
                    tr.update(carry.trace)
                    step = carry.step or step
                    if carry.emit is not None:
                        emit = carry.emit
                        rows = [j if rows is None else rows[j] for j in emit]
                        ids = [ids[j] for j in emit]
                        if not ids:
                            # no row is owed a result: what follows sits
                            # the round out (the program is the first's)
                            break
                self._m_window_rows["device"].inc(tr["window_rows"])
                tr["window_restarts"] = self._window_restarts
                self._window_restarts = 0
                self.perf.note_h2d(
                    name, group.bucket,
                    group.nbytes + sum(c.aux_nbytes for c in carried),
                    h2d_s, hidden_s=hidden_s,
                )
                # a state that serialises its steps (a stream head waits
                # for its predecessor): stamped apart, no part of the call
                pc_wait0 = time.perf_counter()
                for carry in carried:
                    if carry.wait is not None:
                        carry.wait()
                        tr["state_wait_s"] = time.perf_counter() - pc_wait0
                tr["program"] = getattr(step, "program", None)
                tr["t_step0"], pc_step0 = time.time(), time.perf_counter()
                try:
                    ran = step(variables, placed,
                               *(a for c in carried for a in c.args()))
                except Exception:
                    # donated buffers are gone and the host's bookkeeping
                    # ran ahead of the device: each state starts anew
                    for carry in carried:
                        carry.lost()
                    raise
                outputs = dict(ran) if carried else ran
                for carry in carried:
                    carry.commit(outputs)
                if group.crops is not None and isinstance(outputs, dict):
                    # Quality-carrying steps still compute stats for
                    # the canvas batch (same compiled program); they
                    # are meaningless per-stream — drop them before
                    # _emit's D2H fetch.
                    outputs = dict(outputs)
                    outputs.pop("quality_stats", None)
                    outputs.pop("quality_thumbs", None)
                tr["step_call_s"] = time.perf_counter() - pc_step0
                tr["t_step1"] = time.time()
            except Exception as exc:
                shard = None
                if self.faults is not None:
                    # Classify before the lease sweep: an XLA error that
                    # names a shard's device (or carries fault_shard)
                    # arms the failover the next tick picks up, and the
                    # dropped slots below are attributed to it.
                    shard = self.faults.note_error(exc, self.ticks)
                reason = ("device_fault" if shard is not None
                          else "dispatch_error")
                # read, and now never to reach their windows
                self._restart_windows(groups[gi:], "dropped")
                self._drop_groups(groups[gi:], handles[gi:], reason)
                raise
            self.batches += 1
            self._m_batches.inc()
            self._m_occupancy.observe(
                100.0 * len(group.device_ids) / group.bucket
            )
            t_submit = tr["t_submit"] = time.time()
            if carried and not outputs:
                # a round that only wrote its frames into their windows:
                # nothing to fetch or emit, the batch ends here. The
                # placement is done with the host buffer (without the
                # prefetch stage the call itself may still be reading
                # it); no row owes a result before its clip_len-th read.
                batches.append(tr)
                if self._xfer is None:
                    import jax

                    jax.block_until_ready(ran)
                self._collector.release(group)
                if self.faults is not None:
                    self.faults.note_dropped(
                        len(group.device_ids), "window_filling")
                continue
            if trace_on:
                for did, meta in zip(group.device_ids, group.metas):
                    if tracer.sampled(meta.packet):
                        tracer.record(
                            did, "submit", meta.packet,
                            ts=t_submit, bucket=group.bucket,
                            trace_id=trace_id_of(meta, did),
                        )
            batches.append(tr)
            inflight = _Inflight(group, outputs, t_submit, tr, emit)
            self._pacer.launched(inflight, (name, group.src_hw, group.bucket))
            self._enqueue_drain(inflight)
        return batches

    def _apply_rung_cap(self, rung: str) -> None:
        """bucket_downshift and above: hide the largest batch bucket so
        new batches run the next-smaller (cheaper, typically
        already-compiled) device program; below it the cap clears.
        Keyed on the rung NAME, not a raw index — r16 inserted
        shed_to_fleet between shed and bucket_downshift, and a
        fleet-shedding engine must NOT also be shrinking its programs
        (horizontal re-placement engages before vertical degradation)."""
        cap = None
        if _RUNG_IDX[rung] >= _RUNG_IDX["bucket_downshift"] \
                and len(self._buckets) > 1:
            cap = self._buckets[-2]
        self._collector.set_bucket_cap(cap)

    def _shed_stale_groups(self, groups: List[BatchGroup]) -> List[BatchGroup]:
        """Apply rung 1 shedding to this tick's groups (see shed_stale);
        fully-stale groups return their pooled-buffer lease here."""
        now_ms = time.time() * 1000.0
        out: List[BatchGroup] = []
        tick_shed = 0
        for group in groups:
            before = list(group.device_ids) if group.window else ()
            kept, shed = shed_stale(
                group, now_ms, self._cfg.shed_staleness_ms, self._buckets,
                shards=self._shards,
            )
            if shed and before:
                # read, shed before their windows saw them: those start anew
                left = set(kept.device_ids) if kept is not None else ()
                wpool = self._window_pools.get(
                    group.model or self._spec.name)
                if wpool is not None:
                    wpool.restart(
                        [d for d in before if d not in left], "shed")
            if shed:
                self.shed_frames += shed
                tick_shed += shed
                self._m_shed.inc(shed)
            if kept is None:
                self._collector.release(group)
            else:
                out.append(kept)
        # r23 journal: one shed excursion event per degraded episode
        # (opened on the first frame actually dropped, closed when the
        # ladder recovers in _run), caused by the ladder transition that
        # engaged shedding — never one event per tick.
        if tick_shed and self.journal is not None:
            if self._shed_seq is None:
                self._shed_seq = self.journal.record(
                    "engine", "shed_open",
                    subject=("engine", "dispatch"),
                    trigger={"frames": tick_shed,
                             "staleness_ms": self._cfg.shed_staleness_ms},
                    cause=(self.ladder.last_transition_seq
                           if self.ladder is not None else None))
                self._shed_excursion_frames = 0
            self._shed_excursion_frames += tick_shed
        return out

    def _close_shed_excursion(self) -> None:
        """Close the open shed excursion (ladder back at normal)."""
        if self.journal is not None and self._shed_seq is not None:
            self.journal.record(
                "engine", "shed_close", subject=("engine", "dispatch"),
                trigger={"frames": self._shed_excursion_frames},
                cause=self._shed_seq)
        self._shed_seq = None
        self._shed_excursion_frames = 0

    def _apply_cascade_stretch(self, rung: str, streams) -> None:
        """Cascade cadence stretch (r23): while the degradation ladder
        sits at shed or deeper, the temporal head dispatches every
        ``every_n * cascade_stretch_factor`` ticks instead of every
        ``every_n`` — head FLOPs shed before streams do. Journaled on
        the EDGE only (engage/release), with a per-stream event so
        ``/api/v1/why?stream=S`` resolves the stream's cadence back
        through the ladder transition to the SLO burn that drove it."""
        factor = (self._cfg.cascade_stretch_factor
                  if rung != "normal" else 1)
        if not self._cascade.set_stretch(factor):
            return
        action = ("cascade_stretch" if factor > 1
                  else "cascade_unstretch")
        seq = None
        if self.journal is not None:
            cause = (self.ladder.last_transition_seq
                     if self.ladder is not None else None)
            trigger = {"rung": rung, "factor": factor,
                       "every_n": self._cascade.every_n}
            seq = self.journal.record(
                "engine", action, subject=("cascade", "head"),
                trigger=trigger, cause=cause)
            for sid in sorted(set(streams or [])):
                self.journal.record(
                    "engine", action, subject=("stream", str(sid)),
                    trigger=dict(trigger), cause=cause)
        log.info("cascade cadence %s: every_n %d x%d (rung %s)",
                 "stretched" if factor > 1 else "restored",
                 self._cascade.every_n, factor, rung,
                 extra={"vep_actor": "engine",
                        "vep_subject": "cascade:head",
                        "vep_journal_seq": seq})

    # -- MOSAIC ROI serving (cfg.roi; ROADMAP item 1) --

    def _roi_transform(self, groups: List[BatchGroup]) -> List[BatchGroup]:
        """Motion-gate each detect group's rows and rewrite the tick's
        work: ``full`` rows stay classic full frames (compacted in place,
        shed_stale discipline — the lease rides with them), ``roi`` rows
        become crops shelf-packed onto shared canvases (one synthetic
        canvas group per tick, lease-free copies), ``idle`` rows become a
        tracker-coasted group with no device work at all.

        Ordering matters twice: crops blit (copy) out of the pooled
        buffer BEFORE full rows compact (compaction moves rows upward
        within the same view), and classification runs under
        ``_state_lock`` because the drain thread feeds the gate (diff
        energy, full-frame stamps) and trackers concurrently. Groups
        that are not full-frame detect batches (clip inputs, embed/
        classify models, already-transformed groups) pass through
        untouched — with cfg.roi=False this method is never called and
        the classic path is bit-identical (test-pinned)."""
        out: List[BatchGroup] = []
        for group in groups:
            model = group.model or self._spec.name
            entry = self._models.get(model)
            spec = entry[0] if entry is not None else None
            if (spec is None or spec.kind != "detect"
                    or group.frames.ndim != 4
                    or group.crops is not None or group.coast is not None):
                out.append(group)
                continue
            now = time.monotonic()
            full_rows: List[int] = []
            coast: List[tuple] = []
            reqs: List[tuple] = []    # CanvasPacker requests
            req_row: List[int] = []   # request index -> group row
            roi_edges: List[tuple] = []   # r23 journal: mode transitions
            with self._state_lock:
                for i, device_id in enumerate(group.device_ids):
                    t_entry = self._trackers.get(device_id)
                    tracker = (
                        t_entry[1]
                        if t_entry is not None and t_entry[0] == spec.name
                        else None
                    )
                    verdict = self._roi.classify(device_id, tracker, now)
                    if (self.journal is not None
                            and self._roi_mode.get(device_id) != verdict):
                        roi_edges.append(
                            (device_id,
                             self._roi_mode.get(device_id), verdict))
                        self._roi_mode[device_id] = verdict
                    if verdict == "idle":
                        coast.append((
                            device_id, group.metas[i],
                            self._coasted_detections(tracker, spec),
                        ))
                        continue
                    rects = (self._track_rois(tracker)
                             if verdict == "roi" else [])
                    if rects:
                        # Frames live at the GLOBAL row under the
                        # shard-segmented layout, not the slot index —
                        # blitting by slot cuts another stream's pixels
                        # whenever per-shard occupancy is unequal.
                        fr = (group.rows[i] if group.rows is not None
                              else i)
                        for rect in rects:
                            reqs.append((device_id, group.metas[i],
                                         group.frames[fr], rect))
                            req_row.append(i)
                    else:
                        full_rows.append(i)
            # Journal ROI gate transitions outside the state lock —
            # edge-triggered (a stream flipping full/roi/idle), so gate
            # steady state records nothing.
            for device_id, prev, verdict in roi_edges:
                self.journal.record(
                    "engine", "roi_mode",
                    subject=("stream", str(device_id)),
                    trigger={"mode": verdict, "prev": prev or "none"})
            if not coast and not reqs:
                # Everything full: the group passes through untouched.
                # Still count the verdicts — synchronized refresh ticks
                # (streams primed together expire together) would
                # otherwise vanish from gated_stream_pct.
                self.perf.note_roi_gate(0, 0, len(group.device_ids))
                out.append(group)
                continue
            placements: list = []
            cgroup: Optional[BatchGroup] = None
            n_used = 0
            if reqs and self._shards > 1 and group.rows is not None:
                # r17 mesh serving: canvases pack per mesh slice so the
                # scatter-back routing table stays shard-local.
                cgroup, placements, full_rows, n_used = (
                    self._pack_canvases_sharded(group, reqs, req_row,
                                                full_rows))
            elif reqs:
                canvases, placements, overflow = self._packer.pack(reqs)
                if overflow:
                    # Crops that did not fit fall back to the full-frame
                    # path. ALL of a spilled stream's placements leave
                    # the routing table too — a stream must never emit
                    # twice in one tick, so its already-placed crops'
                    # canvas detections drop as unrouted (rare, counted).
                    spill = {reqs[ri][0] for ri in overflow}
                    placements = [p for p in placements
                                  if p.device_id not in spill]
                    spill_rows = sorted(
                        {req_row[ri] for ri in range(len(reqs))
                         if reqs[ri][0] in spill})
                    full_rows = sorted(set(full_rows) | set(spill_rows))
            self.perf.note_roi_gate(
                len(coast), len({p.device_id for p in placements}),
                len(full_rows))
            if placements and cgroup is None:
                side = self._packer.side
                n_used = 1 + max(p.canvas for p in placements)
                metas = []
                for ci in range(n_used):
                    pts = [p.meta.timestamp_ms or 0
                           for p in placements if p.canvas == ci]
                    # Latency accounting for the canvas batch follows its
                    # oldest member; per-stream latency uses each crop's
                    # own meta at scatter-back.
                    metas.append(FrameMeta(
                        width=side, height=side, channels=3,
                        timestamp_ms=min(pts) if pts else 0,
                    ))
                cgroup = pad_to_bucket(BatchGroup(
                    src_hw=(side, side),
                    device_ids=[f"_canvas{ci}" for ci in range(n_used)],
                    frames=canvases[:n_used],
                    metas=metas,
                    model=group.model,
                    crops=placements,
                ), self._buckets)
            if cgroup is not None:
                out.append(cgroup)
                self.perf.note_roi_pack(
                    len(placements), n_used,
                    CanvasPacker.area_fraction(placements, n_used,
                                               self._packer.side))
            if coast:
                out.append(BatchGroup(
                    src_hw=group.src_hw,
                    device_ids=[c[0] for c in coast],
                    frames=np.empty((0,) + group.frames.shape[1:],
                                    group.frames.dtype),
                    metas=[c[1] for c in coast],
                    bucket=0,
                    model=group.model,
                    coast=coast,
                ))
            if full_rows and group.rows is not None and self._shards > 1:
                out.append(_compact_sharded(
                    group, full_rows, self._buckets, self._shards))
            elif full_rows:
                for new_i, old_i in enumerate(full_rows):
                    if new_i != old_i:
                        group.frames[new_i] = group.frames[old_i]
                group.device_ids = [group.device_ids[i] for i in full_rows]
                group.metas = [group.metas[i] for i in full_rows]
                n = len(full_rows)
                bucket = next(b for b in sorted(self._buckets) if b >= n)
                view = group.frames[:bucket]
                if bucket != n:
                    view[n:] = 0
                group.frames = view
                group.bucket = bucket
                out.append(group)
            else:
                # No full rows survive: the pooled buffer goes back now
                # (canvases and coast groups hold copies, not views).
                self._collector.release(group)
        return out

    def _pack_canvases_sharded(self, group: BatchGroup, reqs, req_row,
                               full_rows):
        """MOSAIC packing under mesh serving (r17 tentpole leg 3): each
        dp shard's crops shelf-pack onto that shard's OWN canvases, and
        the canvas batch is emitted in the shard-segmented row layout —
        a canvas only ever carries crops of streams its chip serves, so
        the scatter-back routing table is shard-local by construction
        (the single-chip assumption the old auto-disable guarded).

        Returns ``(canvas group or None, kept placements, updated
        full_rows, used canvas rows)``. Spilled streams fall back to the
        full-frame path exactly like the single-chip branch; if no
        bucket segment can hold a shard's canvas count, the whole
        request set falls back (rare — counted as full rows)."""
        import dataclasses

        S = self._shards
        by_shard: Dict[int, List[int]] = {}
        for ri, req in enumerate(reqs):
            by_shard.setdefault(self._shard_of(req[0]) % S, []).append(ri)
        packed: Dict[int, tuple] = {}
        spill: set = set()
        for s, ris in sorted(by_shard.items()):
            canvases, placements, overflow = self._packer.pack(
                [reqs[ri] for ri in ris])
            if overflow:
                spill |= {reqs[ris[oi]][0] for oi in overflow}
            packed[s] = (canvases, placements)
        if spill:
            spill_rows = {req_row[ri] for ri in range(len(reqs))
                          if reqs[ri][0] in spill}
            full_rows = sorted(set(full_rows) | spill_rows)
        n_by_shard: Dict[int, int] = {}
        for s, (canvases, placements) in packed.items():
            kept = [p for p in placements if p.device_id not in spill]
            packed[s] = (canvases, kept)
            n_by_shard[s] = (1 + max(p.canvas for p in kept)) if kept else 0
        k_max = max(n_by_shard.values(), default=0)
        if k_max == 0:
            return None, [], full_rows, 0
        bucket = next(
            (b for b in sorted(self._buckets)
             if b % S == 0 and b // S >= k_max), None)
        if bucket is None:
            rows_all = {req_row[ri] for ri in range(len(reqs))}
            return None, [], sorted(set(full_rows) | rows_all), 0
        seg = bucket // S
        side = self._packer.side
        frames = np.zeros((bucket, side, side, 3), np.uint8)
        rows: List[int] = []
        device_ids: List[str] = []
        metas: List[FrameMeta] = []
        out_placements: list = []
        for s, (canvases, kept) in sorted(packed.items()):
            if not kept:
                continue
            n_used = n_by_shard[s]
            frames[s * seg:s * seg + n_used] = canvases[:n_used]
            for ci in range(n_used):
                r = s * seg + ci
                pts = [p.meta.timestamp_ms or 0
                       for p in kept if p.canvas == ci]
                rows.append(r)
                device_ids.append(f"_canvas{r}")
                metas.append(FrameMeta(
                    width=side, height=side, channels=3,
                    timestamp_ms=min(pts) if pts else 0,
                ))
            # Placement canvas indices become GLOBAL batch rows so the
            # scatter-back router addresses host outputs directly.
            out_placements.extend(
                dataclasses.replace(p, canvas=s * seg + p.canvas)
                for p in kept)
        cgroup = BatchGroup(
            src_hw=(side, side),
            device_ids=device_ids,
            frames=frames,
            metas=metas,
            bucket=bucket,
            model=group.model,
            crops=out_placements,
            rows=rows,
        )
        return cgroup, out_placements, full_rows, len(rows)

    def _coasted_detections(self, tracker, spec) -> List[pb.Detection]:
        """Gated-idle emission: advance the stream's tracker one frame
        with no detections (misses age, so stale tracks still expire
        while the stream is gated) and render the surviving predicted
        boxes as detections with geometrically decayed confidence.
        Caller holds ``_state_lock``."""
        if tracker is None:
            return []
        tracker.update([], [])
        decay = self._cfg.roi_coast_decay
        floor = self._cfg.roi_coast_floor
        n_classes = self._num_classes(spec)
        out: List[pb.Detection] = []
        for t in tracker.tracks():
            conf = t["confidence"] * decay ** max(t["misses"], 1)
            if conf < floor:
                continue
            x1, y1, x2, y2 = (int(round(v)) for v in t["box"])
            det = pb.Detection(
                box=pb.BoundingBox(left=x1, top=y1,
                                   width=x2 - x1, height=y2 - y1),
                confidence=float(conf),
                class_id=t["class_id"],
                class_name=class_name(t["class_id"], n_classes),
            )
            det.track_id = str(t["track_id"])
            out.append(det)
        return out

    def _track_rois(self, tracker) -> List[tuple]:
        """Candidate crop rectangles for a tracked stream: predicted
        track boxes inflated by cfg.roi_margin (context for the detector
        + slack for motion since the prediction), then overlapping
        rects merged to a common hull — one object must never appear in
        two crops of the same stream (double detection after
        scatter-back). Caller holds ``_state_lock``."""
        if tracker is None:
            return []
        margin = self._cfg.roi_margin
        rects: List[list] = []
        for t in tracker.tracks():
            x1, y1, x2, y2 = t["box"]
            mw = (x2 - x1) * margin
            mh = (y2 - y1) * margin
            rects.append([x1 - mw, y1 - mh, x2 + mw, y2 + mh])
        merged = True
        while merged:
            merged = False
            folded: List[list] = []
            for r in rects:
                for o in folded:
                    if (r[0] < o[2] and o[0] < r[2]
                            and r[1] < o[3] and o[1] < r[3]):
                        o[0] = min(o[0], r[0])
                        o[1] = min(o[1], r[1])
                        o[2] = max(o[2], r[2])
                        o[3] = max(o[3], r[3])
                        merged = True
                        break
                else:
                    folded.append(list(r))
            rects = folded
        return [tuple(r) for r in rects]

    def _watch_tick(self, tick_s: float,
                    inferred: Sequence[str] = ()) -> None:
        """Per-tick watermark checks (obs/watch.py): each warns once per
        episode, so a stalled device or recompile storm surfaces as ONE
        log line, not one per tick. Also feeds the per-tick SLO samples
        (fps, availability) and runs the throttled SLO evaluation."""
        self._m_drain_depth.set(self._drain_q.qsize())   # raw, for dashboards
        self.watchdog.check(
            # Effective depth (computed in _run): prefetch keeps the
            # queue full by design, so only a blocked handoff counts.
            "drain_backpressure", self._bp_depth, above=1,
            detail="device slower than the tick loop (double buffer full)",
        )
        # Recompile storm: a step-cache miss on N consecutive ticks means
        # shapes are churning faster than the cache warms (the exact
        # pathology bucketed batching exists to prevent).
        misses = self._m_cache_miss.value
        self._miss_streak = (
            self._miss_streak + 1 if misses > self._miss_seen else 0
        )
        self._miss_seen = misses
        self.watchdog.check(
            "recompile_storm", self._miss_streak, above=2,
            detail="step-cache miss on 3+ consecutive ticks (shape churn)",
        )
        if self.slo is not None:
            self._slo_tick(inferred)
        if self.prof is not None:
            # Burn-triggered profiling (obs/prof.py): fires at most one
            # bounded capture per new SLO episode / ladder escalation,
            # rate-limited, on its own thread. Idle cost: integer
            # compares under a lock.
            rung_idx = (self.ladder.rung_index
                        if self.ladder is not None else 0)
            self.prof.poll(
                episodes=self._slo_episodes,
                rung=rung_idx,
                context={
                    "slo_episode": self._slo_episodes or None,
                    "slo_burning": self._slo_burning,
                    "rung": RUNGS[rung_idx],
                },
            )
        if self.capacity is not None:
            # Throttled internally to capacity_eval_interval_s — per-tick
            # cost between refreshes is one clock read and a compare.
            self.capacity.evaluate()
        if self.hbm is not None:
            # Same stance for the byte ledger: the registered pool
            # callables are metadata reads, and between refreshes the
            # per-tick cost is one clock read and a compare.
            self.hbm.evaluate()
        if self.faults is not None and self.faults.stall_suspected():
            # Stall attribution (tick thread — the drain thread only
            # raised the suspicion): probe each shard's lead device with
            # a bounded round-trip; shards that fail become pending and
            # fail over at the top of the next tick. An unattributed
            # stall (every probe passes — a slow host, not a dead chip)
            # resolves the suspicion without a failover.
            try:
                probe = self.faults.probe_fn or self._probe_shards
                bad = probe()
            except Exception:
                log.exception("shard fault probe failed; unattributed")
                bad = []
            marked = self.faults.resolve_stall(bad, self.ticks)
            if marked:
                log.warning(
                    "device stall attributed to shard(s) %s; failover "
                    "pending", marked,
                )
            else:
                log.warning(
                    "dispatch deadline overruns resolved unattributed "
                    "(all shard probes healthy)")

    def _slo_tick(self, inferred: Sequence[str]) -> None:
        """Per-tick SLO sampling + throttled evaluation (obs/slo.py).

        Only sampled while streams are inferred: an idle engine (no
        cameras) has no fps/availability objective to miss, so it must
        never build ladder pressure. Recording is ring index math;
        the window-scan evaluation runs at most once per
        slo_eval_interval_s so the tick loop never pays it per tick.
        """
        now = time.monotonic()
        if inferred:
            if self._cfg.slo_target_fps > 0:
                good = self.perf.fps() >= self._cfg.slo_target_fps
                self._slo_fps.record(good=1.0 if good else 0.0,
                                     bad=0.0 if good else 1.0)
            window = self._cfg.slo_availability_window_s
            for device_id in inferred:
                st = self._stats.get(device_id)
                if st is None or not st.last_emit_mono:
                    continue   # never served yet: boot grace, not an SLI
                ok = now - st.last_emit_mono <= window
                self._slo_avail.record(good=1.0 if ok else 0.0,
                                       bad=0.0 if ok else 1.0)
        if now >= self._slo_next_eval:
            self._slo_next_eval = now + self._cfg.slo_eval_interval_s
            verdict = self.slo.evaluate()
            self._slo_burning = verdict["burning"]
            # Cumulative episode count across all SLOs: the prof trigger
            # watermark (one capture per newly-opened episode).
            self._slo_episodes = sum(
                s["episodes"] for s in verdict["slos"].values()
            )

    def _enqueue_drain(self, inflight: _Inflight) -> None:
        """Hand a dispatched batch to the drain thread. Blocks (in short
        interruptible slices) when the pipeline is 2 deep — backpressure,
        not unbounded in-flight growth. On shutdown while full, the
        batch's result is dropped but its buffer lease is returned."""
        try:
            self._drain_q.put_nowait(inflight)
            return
        except queue.Full:
            # The ladder/watchdog backpressure signal under prefetch:
            # the device did NOT absorb the pipeline this tick.
            self._drain_blocked = True
        while not self._stop.is_set():
            try:
                self._drain_q.put(inflight, timeout=0.1)
                return
            except queue.Full:
                continue
        if tracer.enabled:
            for did, m in zip(inflight.group.device_ids,
                              inflight.group.metas):
                if tracer.sampled(m.packet):
                    tracer.record(did, "dropped", m.packet,
                                  reason="shutdown_drain",
                                  trace_id=trace_id_of(m, did))
        if self.faults is not None:
            self.faults.note_dropped(
                _group_slots(inflight.group), "shutdown_drain")
        self._pacer.forget(inflight)
        self._collector.release(inflight.group)

    def _drain_loop(self) -> None:
        """Event-driven drain (VERDICT r4 next #1): block on the oldest
        in-flight batch's device outputs and emit the moment they are
        ready, instead of parking finished results until the next tick
        boundary (which taxed every result a full tick_ms by design)."""
        while True:
            nxt = self._drain_q.get()
            # t_deq: this thread holds the new batch. The previous one is
            # let go only on the line after (its host batch is freed here,
            # on this thread), so that wake-up and release read apart:
            # t_submit -> t_deq is the wake-up, t_deq -> t_drain0 the rest.
            t_deq = time.time()
            inflight = nxt
            if inflight is None:
                self._drain_q.task_done()
                return
            tr = inflight.tr
            if tr is not None:
                tr["t_deq"] = t_deq
            try:
                self._emit(inflight)
                if tr is not None and tracer.sampled(tr["tick"]):
                    self._trace_batch(tr)
            except Exception:
                log.exception("drain failed; continuing")
                if self.faults is not None:
                    # Conservative: a partial emission still counts the
                    # whole group dropped — the ledger's lost figure can
                    # only understate health, never hide a loss.
                    self.faults.note_dropped(
                        _group_slots(inflight.group), "drain_error")
            finally:
                self._pacer.forget(inflight)   # a fetch that failed
                self._collector.release(inflight.group)
                # Closes the in-flight window the prefetch stage's
                # "busy" signal (hidden-transfer attribution) reads.
                self._drain_q.task_done()

    # -- result emission --

    def _emit(self, inflight: _Inflight) -> None:
        group = inflight.group
        spec = self._models[group.model or self._spec.name][0]
        if group.coast is not None:
            # MOSAIC gated-idle group: no device outputs at all; emit
            # the tracker-coasted detections computed at gate time.
            self._emit_coast(inflight, spec)
            return
        t_drain0 = time.time()
        host = {k: np.asarray(v) for k, v in inflight.outputs.items()}  # D2H
        t_drained = time.time()
        self._pacer.drained(inflight)
        inflight.tr.update(t_drain0=t_drain0, t_drained=t_drained)
        inflight.tr.setdefault("t_deq", t_drain0)   # _emit called directly
        if "moe_load" in host:
            # stream head: the step's own count of the routed pairs each
            # held expert took (prefill and decode of this batch)
            load = host.pop("moe_load")
            inflight.tr.update(moe_pairs_local=int(load.sum()),
                               moe_load_max=int(load.max()),
                               moe_load_mean=float(load.mean()))
            self._m_moe_pairs.inc(int(load.sum()))
        if "moe_pair_rows" in host:
            # the rows its expert layers moved (the capacity rung each
            # call's held-pair count chose) and the pairs they were routed
            moved, routed = (int(v) for v in host.pop("moe_pair_rows"))
            inflight.tr.update(moe_pair_rows=moved, moe_pairs_routed=routed)
        if "moe_pairs_total" in host:
            # a head that shards its experts by group: the pairs its
            # routers chose over ALL experts, and the tokens whose kept
            # groups include one held here
            routed = int(host.pop("moe_pairs_total"))
            hits = int(host.pop("moe_group_hits"))
            inflight.tr.update(moe_pairs_total=routed, moe_group_hits=hits)
            self._m_moe_routed.inc(routed)
            self._m_moe_group_hits.inc(hits)
        for part in ("attn", "decode"):
            # a head whose prefill attention skips the key blocks past a
            # stream's context and above the causal diagonal; one whose
            # decode attention fetches only those its context reaches
            if f"{part}_blocks_live" in host:
                for kind in ("live", "dense"):
                    n = int(host.pop(f"{part}_blocks_{kind}"))
                    inflight.tr[f"{part}_blocks_{kind}"] = n
                    self._m_key_blocks[part][kind].inc(n)
        if "decode_iters" in host:
            inflight.tr["head_decode_iters"] = int(host.pop("decode_iters"))
        if "mtp_drafted" in host:
            # a head whose prediction module drafts: the drafts the batch's
            # streams verified and accepted
            rows = (list(group.rows) if group.rows is not None
                    else list(range(len(group.device_ids))))
            drafted = int(host.pop("mtp_drafted")[rows].sum())
            accepted = int(host["mtp_accepted"][rows].sum())
            inflight.tr.update(mtp_drafted=drafted, mtp_accepted=accepted)
            self._m_mtp["drafted"].inc(drafted)
            self._m_mtp["accepted"].inc(accepted)
        # submit -> outputs on the host: drain-queue wait + device + fetch
        device_ms = (t_drained - inflight.t_submit) * 1000.0
        if self.faults is not None:
            # Stall watchdog signal (engine/fault.py): submit-to-drained
            # wall time against fault_dispatch_deadline_ms with
            # hysteresis — a wedged chip shows up here first, as the
            # drain future that stops resolving on time.
            self.faults.note_drain(device_ms)
        self._m_device.labels(group.model or self._spec.name).observe(
            device_ms
        )
        # r17 per-shard attribution: under the shard-segmented layout
        # each mesh slice was busy for the WHOLE dispatch (the chips run
        # the same program in lockstep), so every shard that carried
        # frames is charged the full device_ms — per-chip measured and
        # attributed time then agree by construction and conservation
        # holds per shard as well as in aggregate.
        shard_frames = shard_streams = None
        if group.rows is not None and self._shards > 1:
            seg = max(1, group.bucket // self._shards)
            shard_frames = {}
            shard_streams = {}
            for j in range(len(group.device_ids)):
                s = str(group.rows[j] // seg)
                shard_frames[s] = shard_frames.get(s, 0) + 1
                shard_streams.setdefault(s, []).append(group.device_ids[j])
        if group.crops is not None:
            # MOSAIC canvas batch: the fps window counts the STREAMS the
            # canvases served, and occupancy is the crop-pixel area
            # share (a canvas is not one fully-occupied batch slot).
            streams = len({p.device_id for p in group.crops})
            self.perf.note_batch(
                group.model or self._spec.name, group.src_hw, group.bucket,
                device_ms, len(group.device_ids), streams=streams,
                area_frac=CanvasPacker.area_fraction(
                    group.crops, len(group.device_ids), group.src_hw[0]),
                shard_frames=shard_frames,
            )
            if self.capacity is not None:
                # Ledger attribution by packed canvas share: each
                # stream's weight is its crops' blitted canvas-pixel
                # area, so a stream with two big tracks carries more of
                # the batch's cost than a one-sliver neighbor.
                areas: Dict[str, int] = {}
                for p in group.crops:
                    a = ((p.dst[2] - p.dst[0]) * (p.dst[3] - p.dst[1]))
                    areas[p.device_id] = areas.get(p.device_id, 0) + a
                crop_shards = None
                if shard_streams is not None:
                    crop_shards = {}
                    for did in areas:
                        s = str(self._shard_of(did) % self._shards)
                        crop_shards.setdefault(s, []).append(did)
                self.capacity.note_batch(
                    group.model or self._spec.name, group.src_hw,
                    group.bucket, device_ms, list(areas),
                    weights=list(areas.values()), kind="roi",
                    shard_streams=crop_shards,
                )
            self._emit_canvas(inflight, host, spec, device_ms, t_drained)
            return
        # Per-bucket device attribution (obs/perf.py): device-time
        # histogram, padded-slot waste, occupancy, live MFU/fps gauges.
        self.perf.note_batch(
            group.model or self._spec.name, group.src_hw, group.bucket,
            device_ms, len(group.device_ids),
            shard_frames=shard_frames,
        )
        if self.capacity is not None:
            # Ledger attribution by slot occupancy: the bucket's cost
            # (padding included — padded slots are real device time the
            # occupants caused) splits equally across the real frames.
            self.capacity.note_batch(
                group.model or self._spec.name, group.src_hw,
                group.bucket, device_ms, group.device_ids,
                shard_streams=shard_streams,
            )
        slo_latency = (
            self._slo_latency
            if self.slo is not None and spec.kind == "detect" else None
        )
        now_ms = int(t_drained * 1000)
        if self._roi is not None and spec.kind == "detect" \
                and group.frames.ndim == 4:
            # Classic full-frame detect emission while ROI serving is
            # on: stamp the refresh cadence (gate feedback) and count
            # the streams toward the equivalent-fps window.
            now_mono = time.monotonic()
            with self._state_lock:
                for device_id in group.device_ids:
                    self._roi.note_full(device_id, now_mono)
            self.perf.note_roi_emit(len(group.device_ids))
        emit = range(len(group.device_ids)) if inflight.emit is None \
            else inflight.emit
        if self.faults is not None and len(emit) < len(group.device_ids):
            # rows whose device window is still filling: computed, owed
            # no result yet
            self.faults.note_dropped(
                len(group.device_ids) - len(emit), "window_filling")
        for i in emit:
            device_id = group.device_ids[i]
            meta = group.metas[i]
            # Shard-segmented layout (r17): slot i's device outputs (and
            # its leased frame) live at batch row rows[i]; identity on
            # the single-chip path.
            row = group.rows[i] if group.rows is not None else i
            # Structured log correlation: every record logged while this
            # slot emits (tracker, annotate, publish, quality) carries
            # stream=<id> seq=<packet> (utils/logging.py injector).
            ctx = set_log_context(stream=device_id, seq=meta.packet)
            try:
                self._emit_slot(
                    inflight, host, row, device_id, meta, spec, now_ms,
                    device_ms, slo_latency, t_drained,
                )
            finally:
                reset_log_context(ctx)

    def _emit_slot(self, inflight, host, row, device_id, meta, spec, now_ms,
                   device_ms, slo_latency, t_drained) -> None:
        group = inflight.group
        detections = self._to_detections(host, row, spec)
        if self._cfg.track and spec.kind == "detect":
            # Unconditionally — empty frames MUST reach the tracker so
            # misses accumulate and stale tracks expire; skipping them
            # would freeze old tracks and hand their ids to the next
            # object that appears nearby.
            self._assign_tracks(device_id, spec.name, detections)
            if (self._cascade is not None and group.frames.ndim == 4
                    and group.crops is None):
                # CASCADE harvest: letterbox each tracked detection's
                # crop into its device clip ring (scattered next tick).
                # Classic full-frame slots only — frames[row] is the
                # leased host buffer, valid until _emit returns; canvas
                # and clip slots have no per-stream full frame here.
                try:
                    self._cascade.harvest(
                        device_id, group.frames[row], detections, meta)
                except Exception:
                    log.exception("cascade harvest failed; continuing")
        if self.quality is not None:
            self._observe_quality(host, row, device_id, meta, detections)
        latency = max(0.0, now_ms - meta.timestamp_ms) if meta.timestamp_ms else 0.0
        result = pb.InferenceResult(
            device_id=device_id,
            timestamp=meta.timestamp_ms,
            model=spec.name,
            model_version="0",
            detections=detections,
            latency_ms=latency,
            batch_size=group.bucket,
            frame_packet=meta.packet,
            # Trace-context echo: clients join their receive event to the
            # frame's cross-process lineage on this id (0 = unstamped).
            trace_id=meta.trace_id,
            parent_span=meta.parent_span,
        )
        if spec.kind == "stream":
            self._fill_head(result.head, host, row)
        if self.faults is not None:
            # (packet, timestamp_ms): monotone per stream even for
            # producers that never stamp packet ids (ledger dup/rebase
            # detection, engine/fault.py).
            self.faults.ledger.note_emitted(
                device_id, (meta.packet, meta.timestamp_ms))
        self._publish(result)
        if self._cfg.stage_trace:
            # the batch's whole trace (tick, batch, stamps, the tick's
            # collector phases and byte counts) plus this result's own
            self.stage_records.append(dict(
                inflight.tr,
                device_id=device_id,
                ts_pub_ms=meta.timestamp_ms,
                t_emitted=time.time(),
                bucket=group.bucket,
            ))
        self._annotate(device_id, meta, detections, spec)
        st = self._stats.setdefault(device_id, StreamStats())
        st.frames += 1
        st.note_latency(latency)
        st.last_batch = group.bucket
        st.note_device(device_ms, group.padded_slots)
        st.last_emit_mono = time.monotonic()
        if slo_latency is not None and meta.timestamp_ms:
            # p50 detect-latency SLI: one good/bad event per emitted
            # detect frame (objective 0.5 == the p50 target).
            ok = latency <= self._cfg.slo_latency_ms
            slo_latency.record(good=1.0 if ok else 0.0,
                               bad=0.0 if ok else 1.0)
        self._m_frames.labels(device_id).inc()
        self._m_latency.labels(device_id).observe(latency)
        if latency > self._cfg.obs_late_ms:
            self._m_late.labels(device_id).inc()
        if tracer.sampled(meta.packet):
            tid = trace_id_of(meta, device_id)
            tracer.record(
                device_id, "device", meta.packet, ts=t_drained,
                dur_ms=device_ms, bucket=group.bucket, trace_id=tid,
            )
            tracer.record(device_id, "emit", meta.packet, trace_id=tid)

    def _emit_coast(self, inflight: _Inflight, spec) -> None:
        """Emit a gated-idle (MOSAIC ``coast``) group: detections were
        computed at gate time on the tick thread (tracker coasting); this
        just fans them out with the same per-stream semantics as
        ``_emit_slot``. Rides the drain queue so coasted results never
        overtake an earlier in-flight device batch for the same stream."""
        group = inflight.group
        now_ms = int(time.time() * 1000)
        slo_latency = (
            self._slo_latency
            if self.slo is not None and spec.kind == "detect" else None
        )
        for device_id, meta, detections in group.coast:
            ctx = set_log_context(stream=device_id, seq=meta.packet)
            try:
                self._emit_stream_result(
                    inflight, device_id, meta, detections, spec, now_ms,
                    0.0, slo_latency, coasted=True,
                )
            finally:
                reset_log_context(ctx)
        self.perf.note_roi_emit(len(group.coast))
        if self.capacity is not None:
            # Zero-cost occupants: a coasting stream must read as
            # costing 0 ms in the ledger, not as missing from it.
            self.capacity.note_coast(
                [device_id for device_id, _, _ in group.coast])

    def _emit_canvas(self, inflight: _Inflight, host: dict, spec,
                     device_ms: float, t_drained: float) -> None:
        """MOSAIC scatter-back: route each canvas detection to its crop
        by center point (cells never overlap — the packer keeps a
        background gap), map it through the exact per-crop inverse
        affine (ops/boxes.py ``uncrop_boxes``), clip to the crop's
        source rect, and emit per source stream. A detection whose
        center lands in no cell (gap/background artifact, or a spilled
        stream's cell that left the routing table) is counted and
        dropped — it must never reach the wrong stream."""
        from ..ops.boxes import uncrop_boxes

        group = inflight.group
        now_ms = int(t_drained * 1000)
        slo_latency = (
            self._slo_latency
            if self.slo is not None and spec.kind == "detect" else None
        )
        by_canvas: Dict[int, list] = {}
        results: Dict[str, tuple] = {}   # device_id -> (meta, [Detection])
        for p in group.crops:
            by_canvas.setdefault(p.canvas, []).append(p)
            results.setdefault(p.device_id, (p.meta, []))
        thr = (
            self._conf_threshold
            if self._spec is not None and spec.name == self._spec.name
            else 0.0
        )
        n_classes = self._num_classes(spec)
        # Shard-segmented canvas batches (r17): placement .canvas already
        # names the GLOBAL batch row, so host outputs index directly;
        # identity range on the single-chip path.
        canvas_rows = (group.rows if group.rows is not None
                       else range(len(group.device_ids)))
        for ci in canvas_rows:
            cells = by_canvas.get(ci)
            if not cells:
                continue
            for j in np.nonzero(host["valid"][ci])[0]:
                score = float(host["scores"][ci, j])
                if score < thr:
                    continue
                bx = [float(v) for v in host["boxes"][ci, j]]
                cx = (bx[0] + bx[2]) / 2.0
                cy = (bx[1] + bx[3]) / 2.0
                cell = next(
                    (p for p in cells if p.contains(cx, cy)), None)
                if cell is None:
                    self.perf.note_roi_unrouted()
                    continue
                box = uncrop_boxes(
                    np.asarray(bx, np.float32), scale=cell.scale,
                    dst_origin=cell.dst[:2], src_origin=cell.src[:2],
                )
                x1 = max(cell.src[0], min(float(box[0]), cell.src[2]))
                y1 = max(cell.src[1], min(float(box[1]), cell.src[3]))
                x2 = max(cell.src[0], min(float(box[2]), cell.src[2]))
                y2 = max(cell.src[1], min(float(box[3]), cell.src[3]))
                ix1, iy1 = int(round(x1)), int(round(y1))
                ix2, iy2 = int(round(x2)), int(round(y2))
                cid = int(host["classes"][ci, j])
                results[cell.device_id][1].append(pb.Detection(
                    box=pb.BoundingBox(left=ix1, top=iy1,
                                       width=ix2 - ix1, height=iy2 - iy1),
                    confidence=score,
                    class_id=cid,
                    class_name=class_name(cid, n_classes),
                ))
        for device_id, (meta, detections) in results.items():
            ctx = set_log_context(stream=device_id, seq=meta.packet)
            try:
                self._emit_stream_result(
                    inflight, device_id, meta, detections, spec, now_ms,
                    device_ms, slo_latency,
                )
            finally:
                reset_log_context(ctx)
        self.perf.note_roi_emit(len(results))

    def _emit_stream_result(self, inflight, device_id, meta, detections,
                            spec, now_ms, device_ms, slo_latency,
                            coasted: bool = False) -> None:
        """ROI-path twin of ``_emit_slot``'s tail: tracker association,
        quality detections-only observation (canvas slots carry no
        per-stream frame statistics), publish, annotate, stats, SLO.
        Kept separate so the classic full-frame path stays byte-for-byte
        untouched with roi off. Coasted results skip tracker association
        (the gate already advanced the tracker and the detections ARE
        its tracks) and device-time attribution (no device work ran)."""
        group = inflight.group
        if self._cfg.track and spec.kind == "detect" and not coasted:
            self._assign_tracks(device_id, spec.name, detections)
        if self.quality is not None:
            self.quality.observe(
                device_id,
                classes=[d.class_id for d in detections],
                scores=[d.confidence for d in detections],
            )
        latency = max(0.0, now_ms - meta.timestamp_ms) if meta.timestamp_ms else 0.0
        result = pb.InferenceResult(
            device_id=device_id,
            timestamp=meta.timestamp_ms,
            model=spec.name,
            model_version="0",
            detections=detections,
            latency_ms=latency,
            batch_size=group.bucket,
            frame_packet=meta.packet,
            trace_id=meta.trace_id,
            parent_span=meta.parent_span,
        )
        if self.faults is not None:
            # (packet, timestamp_ms): monotone per stream even for
            # producers that never stamp packet ids (ledger dup/rebase
            # detection, engine/fault.py).
            self.faults.ledger.note_emitted(
                device_id, (meta.packet, meta.timestamp_ms))
        self._publish(result)
        self._annotate(device_id, meta, detections, spec)
        st = self._stats.setdefault(device_id, StreamStats())
        st.frames += 1
        st.note_latency(latency)
        st.last_batch = group.bucket
        if not coasted:
            st.note_device(device_ms, group.padded_slots)
        st.last_emit_mono = time.monotonic()
        if slo_latency is not None and meta.timestamp_ms:
            ok = latency <= self._cfg.slo_latency_ms
            slo_latency.record(good=1.0 if ok else 0.0,
                               bad=0.0 if ok else 1.0)
        self._m_frames.labels(device_id).inc()
        self._m_latency.labels(device_id).observe(latency)
        if latency > self._cfg.obs_late_ms:
            self._m_late.labels(device_id).inc()

    def _observe_quality(self, host: dict, i: int, device_id: str,
                         meta: FrameMeta, detections) -> None:
        """Fold one emitted slot into the quality plane: the device
        frame statistics (when the step carried them — mesh/clip paths
        don't), the detection set for flatline + drift scoring, and —
        for the canary stream — the host-side content checksum into the
        integrity checker (replay/checksum.py host_slot_checksum)."""
        kwargs = {}
        qs = host.get("quality_stats")
        if qs is not None:
            kwargs = {
                "luma_mean": float(qs[i, 0]),
                "luma_var": float(qs[i, 1]),
                "diff_energy": float(qs[i, 2]),
            }
            if self._roi is not None:
                # MOSAIC gate feedback: the next tick classifies this
                # stream against the diff energy just fetched (only
                # full-frame slots carry stats, so the refresh cadence
                # keeps the signal alive).
                with self._state_lock:
                    self._roi.note_diff(device_id, float(qs[i, 2]))
        self.quality.observe(
            device_id,
            classes=[d.class_id for d in detections],
            scores=[d.confidence for d in detections],
            **kwargs,
        )
        if (self.canary is not None and device_id == self.canary.stream
                and "boxes" in host):
            from ..replay.checksum import host_slot_checksum

            self.canary.note(meta.packet, host_slot_checksum(host, i))

    def _assign_tracks(self, device_id: str, model: str, detections) -> None:
        """Per-stream SORT-style association (engine/tracker.py): fills
        Detection.track_id, which `_annotate` forwards as the reference's
        AnnotateRequest.object_tracking_id — the field the reference leaves
        to external ML clients. The tracker resets when the stream's model
        changes: class_ids from different models are different label
        vocabularies, so tracks must never continue across a switch."""
        from .tracker import IoUTracker

        with self._state_lock:
            entry = self._trackers.get(device_id)
            if entry is None or entry[0] != model:
                # Ids stay unique within the stream across resets: the
                # fresh tracker continues numbering where the old one
                # stopped.
                first = entry[1].next_id if entry else 1
                entry = (model, IoUTracker(next_id=first))
                self._trackers[device_id] = entry
            tracker = entry[1]
            boxes = [
                (d.box.left, d.box.top, d.box.left + d.box.width,
                 d.box.top + d.box.height)
                for d in detections
            ]
            # Scores ride along so ROI coasting can decay from the last
            # matched confidence (state-only: emitted bytes unchanged).
            ids = tracker.update(
                boxes, [d.class_id for d in detections],
                scores=[d.confidence for d in detections],
            )
        for det, tid in zip(detections, ids):
            det.track_id = tid

    # -- temporal cascade (CASCADE, temporal/scheduler.py) -----------------

    def _cascade_head(self, pool, slot_idx, time_idx, n_real):
        """Temporal-head dispatch for the cascade scheduler: device-side
        time-ordered clip gather from the state pool, then one bucketed
        program (VideoMAE head + logistic anomaly scorer) cached in the
        engine step cache under its own ``cascade:`` model key. Returns
        (host outputs, device_ms). The pool array itself never crosses
        to the host — only the small outputs dict does; the two int32
        index vectors are the aux H2D traffic (``vep_h2d_*``)."""
        import jax

        name = self._cfg.cascade_model
        spec, model, variables = self._ensure_model(name)
        bucket = int(slot_idx.shape[0])
        side = pool.side
        label = f"cascade:{name}"
        key = (label, getattr(self._cfg, "stem", "classic"),
               (side, side), bucket)
        fn = self._step_cache.get(key)
        if fn is None:
            self._m_cache_miss.inc()
            fn = _TimedStep(
                jax.jit(_build_cascade_head(
                    model, self._cfg.cascade_score_w,
                    self._cfg.cascade_score_b)),
                self.perf, label, (side, side), bucket,
                on_compiled=self._hbm_compile_tap(
                    label, (side, side), bucket))
            self._step_cache[key] = fn
        else:
            self._m_cache_hit.inc()
        t0 = time.perf_counter()
        clips = pool.gather(slot_idx, time_idx)
        outputs = fn(variables, clips)
        host = {k: np.asarray(v) for k, v in outputs.items()}
        device_ms = (time.perf_counter() - t0) * 1000.0
        self.perf.note_h2d(
            f"cascade/{name}", bucket,
            int(slot_idx.nbytes + time_idx.nbytes), 0.0)
        self.perf.note_batch(
            f"cascade/{name}", (side, side), bucket, device_ms, n_real,
            streams=0,  # head passes are not emitted frames: keep the
                        # aggregate-fps window honest (quality pattern)
        )
        return host, device_ms

    def _cascade_tick(self) -> None:
        """Drive one scheduler tick and fan its outcome out: lineage
        spans for sampled due tracks (the ``temporal`` stage joining
        detect→track→temporal→emit) and per-event uplink / archive /
        metrics emission. Never raises — the detect path must not feel
        a cascade failure."""
        try:
            res = self._cascade.tick()
        except Exception:
            log.exception("cascade tick failed; continuing")
            return
        if self.capacity is not None and res.head_ms is not None:
            # Ledger attribution for the 1/N-cadence temporal head: the
            # dispatch's measured time splits equally across the due
            # tracks' streams (raw cost in the ledger; cadence-amortized
            # per-tick figure via amortize_n — a head pass every N ticks
            # is 1/N of its cost per tick at steady state).
            side = self._cascade.side
            streams = [stream for stream, _ in res.head_tracks]
            shard_streams = None
            if self._shards > 1 and self._shard_of is not None and streams:
                shard_streams = {}
                for stream in set(streams):
                    s = str(self._shard_of(stream) % self._shards)
                    shard_streams.setdefault(s, []).append(stream)
            self.capacity.note_batch(
                f"cascade/{self._cfg.cascade_model}", (side, side),
                len(res.head_tracks) or 1, res.head_ms,
                streams,
                kind="cascade",
                amortize_n=self._cfg.cascade_every_n,
                shard_streams=shard_streams,
            )
        if tracer.enabled and res.head_ms is not None:
            t_now = time.time()
            for stream, meta in res.head_tracks:
                if meta is None or not tracer.sampled(meta.packet):
                    continue
                tracer.record(
                    stream, "temporal", meta.packet, ts=t_now,
                    dur_ms=res.head_ms,
                    trace_id=trace_id_of(meta, stream),
                )
        for ev in res.events:
            self._cascade_emit_event(ev)

    def _cascade_emit_event(self, ev: dict) -> None:
        """One cascade event out three planes, each failing
        independently: ``vep_cascade_events_total`` metrics, an
        Annotate-shaped record on the existing uplink batch path
        (type="cascade", retry+breaker+spool downstream), and — on
        "enter" — the track's recent tile history into the archive sink
        as a clip segment."""
        kind = ev["kind"]
        self.perf.note_cascade_event(kind)
        meta = ev.get("meta")
        now_ms = int(time.time() * 1000)
        ts = (meta.timestamp_ms
              if meta is not None and getattr(meta, "timestamp_ms", 0)
              else now_ms)
        seq = None
        if self.journal is not None:
            # Hysteresis already edge-triggers enter/exit — each is a
            # decision event with the score that crossed the threshold.
            seq = self.journal.record(
                "engine", f"cascade_{kind}",
                subject=("stream", str(ev["stream"])),
                trigger={"track": str(ev["track_id"]),
                         "score": round(float(ev["score"]), 4),
                         "tick": int(ev["tick"])})
        log.info(
            "cascade %s stream=%s track=%s score=%.3f tick=%d",
            kind, ev["stream"], ev["track_id"], ev["score"], ev["tick"],
            extra={"vep_actor": "engine",
                   "vep_subject": f"stream:{ev['stream']}",
                   "vep_journal_seq": seq},
        )
        if self._annotations is not None:
            try:
                req = pb.AnnotateRequest(
                    device_name=ev["stream"],
                    type="cascade",
                    start_timestamp=ts,
                    object_type=f"anomaly_{kind}",
                    object_tracking_id=str(ev["track_id"]),
                    confidence=float(ev["score"]),
                    ml_model="temporal.cascade",
                    ml_model_version=self._cfg.cascade_model,
                    width=(getattr(meta, "width", 0)
                           if meta is not None else 0),
                    height=(getattr(meta, "height", 0)
                            if meta is not None else 0),
                )
                self._annotations.publish(req.SerializeToString())
            except Exception:
                log.exception("cascade uplink publish failed")
        history = ev.get("history")
        if kind == "enter" and self._archiver is not None and history:
            try:
                from ..ingest.archive import GopSegment

                fps = max(1.0, 1000.0 / max(self._cfg.tick_ms, 1))
                dur_ms = int(len(history) * 1000.0 / fps)
                self._archiver.submit(GopSegment(
                    device_id=f"cascade_{ev['stream']}",
                    start_ts_ms=ts - dur_ms,
                    end_ts_ms=ts,
                    fps=fps,
                    frames=list(history),
                ))
            except Exception:
                log.exception("cascade archive trigger failed")

    def _to_detections(self, host: dict, i: int, spec=None) -> List[pb.Detection]:
        spec = spec or self._spec
        out: List[pb.Detection] = []
        if spec.kind == "detect":
            valid = host["valid"][i]
            # The calibrated operating point rides the DEFAULT model's
            # checkpoint; per-stream extra models start from init and
            # keep the NMS floor.
            thr = (
                self._conf_threshold
                if self._spec is not None and spec.name == self._spec.name
                else 0.0
            )
            for j in np.nonzero(valid)[0]:
                if float(host["scores"][i, j]) < thr:
                    continue
                # BoundingBox carries int32 pixel coords (proto parity with
                # the reference's AnnotateRequest consumers).
                x1, y1, x2, y2 = (int(round(float(v))) for v in host["boxes"][i, j])
                cid = int(host["classes"][i, j])
                out.append(pb.Detection(
                    box=pb.BoundingBox(left=x1, top=y1, width=x2 - x1, height=y2 - y1),
                    confidence=float(host["scores"][i, j]),
                    class_id=cid,
                    class_name=class_name(cid, self._num_classes(spec)),
                ))
        elif spec.kind == "stream":
            # this round's tokens as (id, probability of the greedy pick);
            # the whole answer is the result's ``head``
            for p, cid in zip(host["top_probs"][i, :, 0], host["tokens"][i]):
                out.append(pb.Detection(confidence=float(p),
                                        class_id=int(cid)))
        elif spec.kind == "embed":
            out.append(pb.Detection(
                confidence=1.0, class_id=-1,
                embedding=[float(v) for v in host["embedding"][i]],
            ))
        else:
            for p, cid in zip(host["top_probs"][i], host["top_ids"][i]):
                out.append(pb.Detection(
                    confidence=float(p), class_id=int(cid),
                    class_name=class_name(int(cid), self._num_classes(spec)),
                ))
        return out

    @staticmethod
    def _fill_head(head, host: dict, i: int) -> None:
        """A stream head's answer for batch row ``i``: the ids decoded
        since the stream's reset (this round's last), the top-5 of each of
        this round's steps, where the state stands, and of a drafting head
        the drafts accepted and the round's first draft."""
        head.token_ids.extend(int(t) for t in host["history"][i] if t >= 0)
        for ids, probs in zip(host["top_ids"][i], host["top_probs"][i]):
            head.steps.add(token_ids=[int(t) for t in ids],
                           probs=[float(p) for p in probs])
        head.rounds_since_reset = int(host["rounds"][i])
        head.positions = int(host["positions"][i])
        if "mtp_accepted" in host:
            head.accepted = int(host["mtp_accepted"][i])
            head.first_draft.token_ids.extend(
                int(t) for t in host["draft_ids"][i])
            head.first_draft.probs.extend(
                float(p) for p in host["draft_probs"][i])

    def _num_classes(self, spec=None) -> int:
        spec = spec or self._spec
        model = self._models[spec.name][1] if spec.name in self._models else self._model
        cfg = getattr(model, "cfg", None)
        return getattr(cfg, "num_classes", 0) if cfg is not None else 0

    def _publish(self, result: pb.InferenceResult) -> None:
        with self._sub_lock:
            if self._fanout_closed:
                return
            subs = list(self._subscribers)
        for q, ids in subs:
            if ids is not None and result.device_id not in ids:
                continue
            try:
                q.put_nowait(result)
            except queue.Full:
                # Slow subscriber: latest-wins spirit, drop — but count it
                # (engine thread is the only writer; plain increments).
                self.subscriber_drops += 1
                self.subscriber_drops_by_stream[result.device_id] = (
                    self.subscriber_drops_by_stream.get(result.device_id, 0) + 1
                )
                self._m_sub_drops.labels(result.device_id).inc()

    def _annotate(
        self, device_id: str, meta: FrameMeta, detections: Sequence[pb.Detection],
        spec=None,
    ) -> None:
        spec = spec or self._spec
        if self._annotations is None:
            return
        eligible = [
            det for det in detections
            if det.confidence > 0.0 and (det.class_id >= 0 or det.embedding)
        ]
        if not self._should_annotate(device_id, meta, eligible):
            self.annotations_suppressed += len(eligible)
            return
        for det in eligible:
            req = pb.AnnotateRequest(
                device_name=device_id,
                type="detection" if spec.kind == "detect" else spec.kind,
                start_timestamp=meta.timestamp_ms or int(time.time() * 1000),
                object_type=det.class_name,
                object_tracking_id=det.track_id,
                confidence=det.confidence,
                object_bouding_box=det.box if det.HasField("box") else None,
                # Re-ID feature vectors ride the proto's embedding field
                # (AnnotateRequest.object_signature, video_streaming.proto:26)
                object_signature=list(det.embedding),
                ml_model=spec.name,
                ml_model_version="0",
                width=meta.width,
                height=meta.height,
                is_keyframe=meta.is_keyframe,
            )
            self._annotations.publish(req.SerializeToString())

    def _should_annotate(self, device_id, meta, eligible) -> bool:
        """Per-stream annotation emit policy (cfg.annotation_emit or the
        StreamProcess.annotation_policy override). The reference never
        rate-limits because its CLIENTS choose what to annotate
        (examples/annotation.py); the engine is a firehose and must not
        outrun the uplink drain budget (VERDICT r2 weak #3)."""
        policy = ""
        if self._ann_policy_resolver is not None:
            policy = self._ann_policy_resolver(device_id) or ""
        policy = policy or self._cfg.annotation_emit
        if policy == "all":
            return True
        if policy == "keyframe":
            return bool(meta.is_keyframe)
        if policy not in ("min_interval", "on_change"):
            if (device_id, policy) not in self._ann_policy_warned:
                self._ann_policy_warned.add((device_id, policy))
                log.warning(
                    "unknown annotation policy %r for %s; emitting all",
                    policy, device_id,
                )
            return True
        # The whole policy-state read/update runs under _state_lock: the
        # engine-thread GC deletes _ann_state entries for dropped streams
        # under the same lock, and a setdefault-then-mutate-unlocked here
        # would keep writing an orphaned dict (state silently lost, a
        # re-added stream's first frames mis-gated).
        with self._state_lock:
            st = self._ann_state.setdefault(device_id, {})
            if policy == "min_interval":
                if not eligible:
                    # Nothing to emit: must NOT consume the interval slot, or
                    # sparse scenes (mostly empty frames) would starve real
                    # detections quasi-indefinitely.
                    return True
                now = meta.timestamp_ms or int(time.time() * 1000)
                last = st.get("last_ms")
                if last is not None and now - last < \
                        self._cfg.annotation_min_interval_ms:
                    return False
                st["last_ms"] = now
                return True
            # on_change: the tracked object set changed, or some object's
            # confidence moved more than the configured delta. Track ids when
            # the tracker runs, per-class max-confidence otherwise.
            cur: Dict[str, float] = {}
            for det in eligible:
                key = det.track_id or f"class{det.class_id}"
                cur[key] = max(cur.get(key, 0.0), det.confidence)
            prev = st.get("sig")
            delta = self._cfg.annotation_confidence_delta
            changed = prev is None or set(cur) != set(prev) or any(
                abs(cur[k] - prev[k]) > delta for k in cur
            )
            if changed:
                st["sig"] = cur
            return changed and bool(eligible)
