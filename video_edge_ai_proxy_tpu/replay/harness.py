"""Replay-driven soak + determinism harness (ISSUE r6 tentpole part 3).

Four entry points, all consumed by ``tools/soak_replay.py``:

- :func:`lockstep_checksum` — deterministic replay of a trace through the
  real pipeline stages (bus -> collector -> serving step), folding the
  shared content checksum (replay/checksum.py) over every output. No wall
  clock, no threads: every frame is delivered exactly once, so two runs
  of the same trace are bit-identical — THE record->replay determinism
  claim, and the host for the seeded-numerics-fault test.
- :func:`run_fleet_soak` — in-process fleet soak: N replay-driven cameras
  (6 detect + 5 embed + 5 classify by default) on the in-proc bus, one
  InferenceEngine with per-stream model routing, the REAL annotation
  uplink handler (retry + breaker + dead-letter spool) over a flaky fake
  cloud, a scripted FaultPlan (camera kill/re-add, frame gaps, bus
  stall/flap, slow subscriber, uplink down, device stall), recording
  per-family latency percentiles, bucket_fill over time, step-cache
  stability, cross-family result misrouting, and a "resilience" section
  (ladder transitions, breaker states, annotation conservation).
- :func:`run_e2e` — the FULL single-process pipeline: a real Server
  (subprocess ingest worker reading ``replay://``, bus, collector,
  engine, gRPC serve) with a client measuring publish->receive latency —
  the first true single-path e2e percentile artifact (``E2E_r06.json``).
- :func:`run_fleet_obs` — r14 fleet telemetry soak: N member Server
  SUBPROCESSES (``--fleet N``), a FleetAggregator scraping them, gRPC
  clients recording the trace_id echo, and hard gates on merged-page
  lint, member presence, cross-process trace stitching and counter
  conservation (``FLEETOBS_r01.json``).

jax/server imports live inside functions: this module is imported by the
tools layer before the backend is chosen.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Optional

import numpy as np

from .checksum import (
    CHECKSUM_MASK,
    device_checksum,
    finalize_checksum,
    zero_class_prior,
)
from .faults import QUALITY_KINDS, FaultPlan
from .player import TracePlayer, meta_for
from .recorder import record_synthetic_trace
from .trace import decode_frame

# The north-star fleet split per backend: real models on the chip, the
# structurally-identical tiny twins on the CPU backend (same serving
# families, same orchestration load, laptop-sized programs).
FLEET_TPU = {"yolov8n": 6, "resnet50": 5, "vit_b16": 5}
FLEET_CPU = {"tiny_yolov8": 6, "tiny_resnet": 5, "tiny_vit": 5}


def default_fleet(backend: str) -> dict:
    return dict(FLEET_TPU) if backend == "tpu" else dict(FLEET_CPU)


def _pct(values, points=(50, 90, 95, 99)) -> Optional[dict]:
    if not values:
        return None
    arr = np.asarray(values, dtype=np.float64)
    out = {f"p{p}": round(float(np.percentile(arr, p)), 2) for p in points}
    out["n"] = len(values)
    return out


# ---------------------------------------------------------------------------
# Lockstep determinism replay
# ---------------------------------------------------------------------------


def lockstep_checksum(
    trace_path: str, *, model: str = "tiny_yolov8",
    device_id: Optional[str] = None, limit: int = 0,
    perturb=None, zero_prior: bool = True, mesh=None, shards: int = 0,
) -> dict:
    """Replay a trace deterministically through bus -> collector ->
    serving step and fold the content checksum over every emitted batch.

    Frames go through the REAL pipeline stages (publish, cursor tracking,
    pooled-buffer assembly, bucket padding) one publish per collect so
    latest-wins can never drop a frame — replay order is trace order and
    the fold is exact, not racy. ``perturb(variables) -> variables`` is
    the seeded-fault hook (tests perturb one weight and the checksum must
    move). ``mesh`` (r17) places every batch dp-sharded through the
    mesh-serving H2D path (parallel.shard_put) instead of a plain
    transfer — at dp=1 the checksum must stay bit-identical to the
    single-chip golden, the smoke gate pinning mesh-native serving to
    the exact same numerics. ``shards`` (default: the mesh's dp, else 1)
    is the collector's shard-segmented batch layout; given WITHOUT a mesh,
    one device runs each shard's rows as a batch of their own, in turn —
    what the chips of a dp mesh do at once, at the same per-chip batch
    shape. That is the run a dp=4 mesh of real chips is compared with:
    on a TPU the compiler tiles a convolution by its batch size, so one
    [4-row] program and four [1-row] programs need not agree to the bit
    (on v5e they do not: near-tied random-weight scores reorder in NMS),
    while the same program on four chips must. Returns {"checksum",
    "frames", "batches", "model"}.
    """
    import jax
    import jax.numpy as jnp

    from ..bus.memory_bus import MemoryFrameBus
    from ..engine.collector import Collector
    from ..engine.runner import build_serving_step
    from ..models import registry

    spec = registry.get(model)
    net, variables = spec.init_params(jax.random.PRNGKey(0))
    if zero_prior and spec.kind == "detect":
        variables = zero_class_prior(variables)
    if perturb is not None:
        variables = perturb(variables)
    if mesh is not None:
        from ..parallel import replicated

        variables = jax.device_put(variables, replicated(mesh))
    serving_step = build_serving_step(net, spec, mesh=mesh)
    step = jax.jit(lambda v, u8: device_checksum(serving_step(v, u8)))

    player = TracePlayer(trace_path)
    bus = MemoryFrameBus()
    if not shards:
        shards = mesh.shape["dp"] if mesh is not None else 1
    col = Collector(
        bus, buckets=(1, 2, 4, 8, 16), default_model=spec.name,
        clip_len=spec.clip_len, shards=shards,
    )
    created: set[str] = set()
    carry = 0
    frames = 0
    batches = 0
    try:
        for dev, frame, meta in player.iter_frames(device_id):
            if limit and frames >= limit:
                break
            if dev not in created:
                bus.create_stream(dev, frame.nbytes)
                created.add(dev)
            bus.publish(dev, frame, meta)
            frames += 1
            for group in col.collect():
                batches += 1
                if mesh is not None:
                    from ..parallel import batch_sharding, shard_put

                    pieces = [shard_put(
                        np.ascontiguousarray(group.frames),
                        batch_sharding(mesh, group.frames.ndim))]
                else:
                    pieces = [jnp.asarray(seg) for seg in
                              np.split(group.frames, shards)]
                for placed in pieces:
                    part = int(np.asarray(step(variables, placed)))
                    carry = (carry + part) & CHECKSUM_MASK
    finally:
        bus.close()
    return {
        "checksum": finalize_checksum(carry),
        "frames": frames,
        "batches": batches,
        "model": spec.name,
    }


# ---------------------------------------------------------------------------
# In-process fleet soak
# ---------------------------------------------------------------------------


class StallBus:
    """FrameBus proxy whose publish path can be stalled for a window —
    the ``bus_stall`` fault (a wedged shm writer / slow Redis) — or made
    to fail fast for a window — the ``bus_flap`` fault (a flapping link:
    publishes raise ``ConnectionError`` instead of blocking). Everything
    else delegates."""

    def __init__(self, bus):
        self._bus = bus
        self._stall_until = 0.0
        self._flap_until = 0.0

    def __getattr__(self, name):
        return getattr(self._bus, name)

    def stall_for(self, duration_s: float) -> None:
        self._stall_until = time.monotonic() + duration_s

    def flap_for(self, duration_s: float) -> None:
        self._flap_until = time.monotonic() + duration_s

    def publish(self, device_id, frame, meta):
        while time.monotonic() < self._stall_until:
            time.sleep(0.01)
        if time.monotonic() < self._flap_until:
            raise ConnectionError("bus_flap (scripted fault)")
        return self._bus.publish(device_id, frame, meta)


class _FlakyCloud:
    """CloudClient stand-in for the soak's annotation uplink: delivery is
    an in-memory count, and the ``uplink_down`` fault makes every post
    raise ``URLError`` for a window — the transport-failure class the
    real handler retries, breaks on, and spools through. Exactly-once by
    construction (a post either raises before counting or delivers), so
    the artifact's conservation check is exact."""

    def __init__(self):
        self.down_until = 0.0
        self.posts = 0
        self.post_failures = 0
        self.delivered = 0

    def post_annotations(self, url, annotations, deadline=None):
        import urllib.error

        self.posts += 1
        if time.monotonic() < self.down_until:
            self.post_failures += 1
            raise urllib.error.URLError("uplink_down (scripted fault)")
        self.delivered += len(annotations)
        return b"{}"


class _ReplayCamera(threading.Thread):
    """One replay-driven camera: publishes its trace stream at recorded
    cadence (looping past the end), honoring kill/gap fault flags."""

    def __init__(self, bus, device_id: str, events: list, stop: threading.Event):
        super().__init__(name=f"replay-cam-{device_id}", daemon=True)
        self.bus = bus
        self.device_id = device_id
        self.events = events
        self.stop_ev = stop
        self.killed = threading.Event()
        self.gap_until = 0.0
        # Output-quality faults (ISSUE r10): while black_until is open the
        # camera publishes all-zero frames (lens cap / dead sensor); while
        # frozen_until is open it republishes the window's first frame (a
        # wedged decoder). Both keep the publish cadence — the stream
        # stays live, only its CONTENT degrades, which is exactly the
        # failure class obs/quality.py exists to see.
        self.black_until = 0.0
        self.frozen_until = 0.0
        self._frozen_frame = None
        self.published = 0
        self.suppressed = 0

    def run(self) -> None:
        ev0 = self.events[0]
        base = ev0["t_ms"]
        span = self.events[-1]["t_ms"] - base + (
            self.events[1]["t_ms"] - base if len(self.events) > 1 else 33.0)
        shape = ev0.get("shape") or [ev0["synth"]["h"], ev0["synth"]["w"], 3]
        self.bus.create_stream(self.device_id, shape[0] * shape[1] * shape[2])
        alive = True
        t0 = time.monotonic()
        i = 0
        while not self.stop_ev.is_set():
            ev = self.events[i % len(self.events)]
            due = t0 + ((ev["t_ms"] - base)
                        + (i // len(self.events)) * span) / 1000.0
            delay = due - time.monotonic()
            if delay > 0 and self.stop_ev.wait(delay):
                break
            i += 1
            if self.killed.is_set():
                alive = False
                self.suppressed += 1
                continue
            if not alive:
                # Re-added after a kill: the stream was dropped from the
                # bus; re-create it (a restarted worker does the same).
                self.bus.create_stream(
                    self.device_id, shape[0] * shape[1] * shape[2])
                alive = True
            if time.monotonic() < self.gap_until:
                self.suppressed += 1
                continue
            frame = decode_frame(ev)
            now_mono = time.monotonic()
            if now_mono < self.black_until:
                frame = np.zeros_like(frame)
            elif now_mono < self.frozen_until:
                if self._frozen_frame is None:
                    self._frozen_frame = frame
                frame = self._frozen_frame
            else:
                self._frozen_frame = None
            meta = meta_for(ev, frame, timestamp_ms=int(time.time() * 1000))
            try:
                self.bus.publish(self.device_id, frame, meta)
            except ConnectionError:
                # bus_flap: the link dropped the publish but the stream
                # itself is intact — count suppressed and keep the
                # cursor (re-creating the stream would reset its seq and
                # confuse the collector for no reason).
                self.suppressed += 1
                continue
            except ValueError:
                # Raced a camera_kill's drop_stream: treat as suppressed
                # and re-create on the next live frame.
                alive = False
                self.suppressed += 1
                continue
            self.published += 1


def run_fleet_soak(
    *, duration_s: float = 120.0, fleet: Optional[dict] = None,
    src_hw: tuple = (96, 128), fps: float = 30.0, tick_ms: int = 10,
    trace_path: Optional[str] = None, fault_plan: Optional[FaultPlan] = None,
    warmup_timeout_s: float = 1800.0, sample_every_s: float = 2.0,
    timeline_bin_s: float = 10.0, trace_sample_every: int = 4,
    profile_on_burn: bool = False, prof_dir: Optional[str] = None,
    quality_kinds: tuple = (), engine_overrides: Optional[dict] = None,
) -> dict:
    """The >=120 s chaos soak. Returns the artifact's "soak" section.

    ``profile_on_burn`` arms the r10 trigger path (obs/prof.py): the
    engine fires a bounded jax.profiler capture when an SLO episode
    opens or the ladder escalates, at soak-scale settings (200 ms
    captures, 5 s rate limit — a 20 s smoke must be able to catch its
    own excursion). The bundle manifests land in the artifact's "prof"
    section; tools/soak_replay.py --profile-on-burn hard-gates on them.

    ``quality_kinds`` (ISSUE r11) schedules output-quality faults
    (replay/faults.py QUALITY_KINDS: black_frame on the first camera,
    frozen_frame on the second, a global score_drift) and arms the full
    quality plane at soak scale: tight verdict hysteresis (0.6 s), a
    recorded canary golden-replay trace wired into the live engine
    (adopt-first-cycle golden), the detect class prior zeroed so the
    fleet produces real detections (bench.py's measured-regime
    transform — a random-init detector would otherwise emit nothing
    and neither drift nor the canary fold would have signal). The
    artifact gains a "quality" section: per-fault detection latency
    (first matching verdict transition / canary integrity episode after
    injection, in seconds and engine ticks) and the false-positive
    count over everything outside the fault windows. Without quality
    faults the tracker still runs (engine default) — the plain soak
    doubles as the zero-false-positive clean window.
    """
    import shutil
    import tempfile

    import jax

    from ..bus.memory_bus import MemoryFrameBus
    from ..engine import InferenceEngine
    from ..models import registry
    from ..obs import registry as obs_registry, tracer
    from ..obs.spans import ENGINE_STREAMS, stage_breakdown
    from ..resilience import CircuitBreaker, DeadLetterSpool, RetryPolicy
    from ..uplink.cloud import make_batch_handler
    from ..uplink.queue import AnnotationQueue
    from ..utils.config import EngineConfig

    backend = jax.default_backend()
    fleet = fleet or default_fleet(backend)
    h, w = src_hw

    assignment = {}
    i = 0
    for name, count in fleet.items():
        for _ in range(count):
            assignment[f"fleet{i:02d}"] = name
            i += 1
    family_of = {name: registry.get(name).kind for name in fleet}

    # Deterministic traffic: one synthetic trace shared by every camera
    # (replay-driven, not freerunning RNG — the soak's inputs are a file).
    if trace_path is None:
        trace_path = os.path.join(
            "/tmp", f"vep_soak_trace_{os.getpid()}.vtrace")
        record_synthetic_trace(
            trace_path, sorted(assignment), width=w, height=h, fps=fps,
            gop=30, frames=max(60, int(min(duration_s, 30.0) * fps)))
    player = TracePlayer(trace_path)

    # Frame lineage across the soak: cameras publish in-process, so the
    # collect span's pub_ms carries the ingest leg; engine spans complete
    # the chain. Restore the prior tracer config on exit — the soak runs
    # inside the test/tool process alongside other obs users.
    prev_trace = (tracer.enabled, tracer.sample_every)
    tracer.configure(enabled=True, sample_every=max(1, trace_sample_every))

    inner_bus = MemoryFrameBus()
    bus = StallBus(inner_bus)
    default_model = next(iter(fleet))

    # Annotation uplink under test: the REAL batch handler (retry +
    # breaker + dead-letter spool, uplink/cloud.py) over a flaky fake
    # transport. Timings are soak-scale (tens of ms) so the uplink_down
    # window exercises the whole ladder: retries, breaker open, spool,
    # drain-on-recovery — within one smoke run.
    ann_cloud = _FlakyCloud()
    spool_dir = tempfile.mkdtemp(prefix="vep_soak_spool_")
    ann_spool = DeadLetterSpool(spool_dir, max_bytes=8 << 20)
    ann_handler = make_batch_handler(
        None, "soak://annotate", client=ann_cloud, spool=ann_spool,
        retry=RetryPolicy(max_attempts=2, base_s=0.01, cap_s=0.05),
        breaker=CircuitBreaker(
            "uplink_soak", failure_threshold=2, recovery_timeout_s=0.5),
        post_deadline_s=5.0,
    )
    ann_q = AnnotationQueue(
        ann_handler, max_batch_size=299, poll_duration_ms=100,
        unacked_limit=100_000, requeue_interval_s=0.5,
    )
    ann_q.start()

    if profile_on_burn and prof_dir is None:
        prof_dir = tempfile.mkdtemp(prefix="vep_soak_prof_")
    has_quality = bool(quality_kinds)
    qcfg = {}
    if has_quality:
        # Soak-scale quality knobs: verdicts must enter/exit within a
        # 20 s smoke, and the drift window must roll several times. The
        # canary trace shares the fleet geometry so its batches slot
        # into already-compiled programs (and already-warm buckets).
        canary_trace = os.path.join(
            "/tmp", f"vep_canary_{os.getpid()}.vtrace")
        record_synthetic_trace(
            canary_trace, ["_canary"], width=w, height=h, fps=fps,
            gop=6, frames=6)
        qcfg = dict(
            quality_enter_s=0.6,
            quality_exit_s=0.6,
            quality_window_s=2.0,
            quality_canary=canary_trace,
            # Slow deliberately: the canary is an integrity probe, not a
            # throughput probe. Injected faster than the loaded engine's
            # effective tick, frames overwrite in the collector slot and
            # every cycle voids (a dropped packet makes the checksum
            # meaningless, so the checker refuses to judge it). 2 fps
            # over a 6-frame loop = one integrity verdict every 3 s,
            # which even the saturated CPU soak serves losslessly.
            quality_canary_fps=2.0,
        )
    eng_cfg = EngineConfig(
            model=default_model, tick_ms=tick_ms, stage_trace=True,
            batch_buckets=(1, 2, 4, 8, 16), track=False,
            annotation_emit="all",   # firehose: conservation needs volume
            # Profiling is opt-in for the soak: a capture pauses ~200 ms
            # of wall inside the measured window, so only the
            # --profile-on-burn legs pay it. Soak-scale trigger knobs:
            # small capture, short rate limit, and an SLO warmup shorter
            # than the smoke duration so episode triggers can fire too.
            prof=profile_on_burn,
            prof_dir=prof_dir or "",
            # The replay soak forks nothing, so the fork hazard behind
            # the EngineConfig prof_trigger=False default does not
            # apply here — arm the trigger path explicitly.
            prof_trigger=profile_on_burn,
            prof_trigger_ms=200,
            prof_trigger_min_interval_s=5.0,
            slo_warmup_s=(
                10.0 if (profile_on_burn or has_quality) else 60.0),
            **qcfg,
    )
    if engine_overrides:
        # Engine-config passthrough (r17): cascade-enabled soak members
        # (track=True + cascade=True + a tiny head model) ride the same
        # harness without a parameter per knob; replace() keeps override
        # keys validated against the dataclass fields.
        import dataclasses as _dc

        eng_cfg = _dc.replace(eng_cfg, **engine_overrides)
    eng = InferenceEngine(
        bus,
        eng_cfg,
        model_resolver=lambda d: assignment.get(d, ""),
        annotations=ann_q,
    )

    # device_stall fault: while the window is open every serving-step
    # call eats ~50 ms of fake device time. Per-call (not one long
    # block) so consecutive over-budget ticks build the SUSTAINED
    # pressure the ladder's escalate hysteresis requires.
    # score_drift fault: while its window is open every detect batch's
    # post-NMS scores are scaled ×0.75 — a SILENT numerics regression
    # (boxes intact, counts intact, just confidences off), the failure
    # class only the canary checksum + drift scorer can see.
    stall = {"until": 0.0}
    drift = {"until": 0.0}
    _orig_step = eng._step

    def _stalled_step(src_hw, bucket, model=None):
        fn = _orig_step(src_hw, bucket, model)

        def slow(*a, **k):
            if time.monotonic() < stall["until"]:
                time.sleep(0.05)
            out = fn(*a, **k)
            if time.monotonic() < drift["until"] and "scores" in out:
                out = dict(out)
                out["scores"] = out["scores"] * 0.75
            return out

        return slow

    eng._step = _stalled_step
    eng.warmup()
    if has_quality:
        # Measured-regime transform (replay/checksum.py zero_class_prior,
        # the bench.py idiom): random-init detect scores sit at ~1e-5,
        # below the NMS floor — zero detections means no drift signal
        # and an all-zero canary fold. Zeroing the class-prior biases
        # saturates the candidate sets so scores/classes carry real,
        # content-dependent numerics for the canary to pin.
        entry = eng._models.get(default_model)
        if entry is not None and entry[0].kind == "detect":
            spec0, mod0, vars0 = entry
            vars0 = zero_class_prior(vars0)
            eng._models[default_model] = (spec0, mod0, vars0)
            eng._variables = vars0
    eng.start()

    stop = threading.Event()
    cams = {
        d: _ReplayCamera(bus, d, player.frame_events(d), stop)
        for d in sorted(assignment)
    }

    # Result sink: one subscriber over all streams. latencies per family,
    # misrouting check, pausable for the slow_subscriber fault.
    lat_by_family: dict[str, list] = {k: [] for k in set(family_of.values())}
    lat_lock = threading.Lock()
    misrouted: list = []
    results = {"n": 0}
    slow_until = [0.0]
    measuring = threading.Event()

    def sink() -> None:
        for res in eng.subscribe(timeout=0.5):
            while time.monotonic() < slow_until[0] and not stop.is_set():
                time.sleep(0.05)   # slow subscriber: stop draining
            if stop.is_set():
                break
            expected = assignment.get(res.device_id)
            if expected is not None and res.model != expected:
                misrouted.append((res.device_id, res.model, expected))
            if not measuring.is_set():
                continue
            results["n"] += 1
            fam = family_of.get(res.model)
            if fam is not None:
                with lat_lock:
                    lat_by_family[fam].append(res.latency_ms)

    sink_thread = threading.Thread(target=sink, name="soak-sink", daemon=True)
    sink_thread.start()

    # Warmup: first frame per camera, wait for every (model, bucket)
    # program to compile before the measured window (bench_fleet idiom).
    for d, cam in cams.items():
        ev = cam.events[0]
        frame = decode_frame(ev)
        inner_bus.create_stream(d, frame.nbytes)
        inner_bus.publish(
            d, frame, meta_for(ev, frame, timestamp_ms=int(time.time() * 1000)))
    warm_deadline = time.monotonic() + warmup_timeout_s
    while time.monotonic() < warm_deadline:
        if len(eng.stats()) >= len(assignment):
            break
        time.sleep(1.0)
    warmup_s = warmup_timeout_s - (warm_deadline - time.monotonic())
    # Prewarm every bucket the degradation ladder can downshift to. The
    # warmup traffic only compiles each model's nominal bucket; the first
    # downshift then pays a mid-soak CPU compile that stalls the tick
    # loop for seconds — blanking quality sampling exactly when the
    # overload (and the scripted faults) hit. Compile them all now, in
    # the window the measurement already excludes.
    model_counts: dict = {}
    for mname in assignment.values():
        model_counts[mname] = model_counts.get(mname, 0) + 1
    for mname, count in model_counts.items():
        spec_m, _, vars_m = eng._ensure_model(mname)
        if spec_m.clip_len:
            continue
        for b in eng._cfg.batch_buckets:
            args = [np.zeros((b, h, w, 3), np.uint8)]
            if eng._quality_device:
                side = eng._cfg.quality_thumb
                args.append(np.zeros((b, side, side), np.float32))
            eng._step((h, w), b, mname)(vars_m, *args)
            if b >= count:
                break
    eng.stage_records.clear()
    # The measured window starts clean: warmup compiles would otherwise
    # register as recompile-storm episodes and skew the span breakdown.
    tracer.clear()
    eng.watchdog.reset()
    if eng.quality is not None:
        # Warmup frames (one per camera, then silence) would otherwise
        # seep into the measured window as flatline/freeze priors. The
        # canary is NOT reset: the golden it adopted from warmup cycles
        # is exactly the reference the measured window checks against.
        eng.quality.reset()

    if fault_plan is not None:
        events = list(fault_plan.events)
    elif has_quality:
        # Quality smoke runs without the churn script: camera kills and
        # bus stalls would starve the very streams whose verdicts the
        # detection-latency gate is timing.
        events = []
    else:
        events = list(
            FaultPlan.default_churn(sorted(assignment), duration_s).events)
    if has_quality:
        events += FaultPlan.quality(
            duration_s, sorted(assignment), quality_kinds).events
    plan = FaultPlan(events)
    plan.reset()

    measuring.set()
    for cam in cams.values():
        cam.start()

    t0 = time.monotonic()
    t0_wall = time.time()   # stage_records carry wall-clock stamps
    faults_applied = []
    step_cache_samples = []
    timeline: dict[int, dict] = {}
    seen_batches: set = set()
    next_sample = 0.0

    def drain_stage_records() -> None:
        while True:
            try:
                r = eng.stage_records.popleft()
            except IndexError:
                break
            b = int(max(0.0, r["t_emitted"] - t0_wall) // timeline_bin_s)
            slot = timeline.setdefault(b, {"real": 0, "padded": 0})
            slot["real"] += 1
            # one batch contributes its bucket once
            if r["batch"] not in seen_batches:
                seen_batches.add(r["batch"])
                slot["padded"] += r["bucket"]

    while True:
        now_s = time.monotonic() - t0
        if now_s >= duration_s:
            break
        for ev in plan.pop_due(now_s):
            faults_applied.append({
                "at_s": round(now_s, 2), "kind": ev.kind,
                "device_id": ev.device_id, "duration_s": ev.duration_s,
            })
            if ev.kind == "camera_kill":
                cams[ev.device_id].killed.set()
                bus.drop_stream(ev.device_id)
            elif ev.kind == "camera_restore":
                cams[ev.device_id].killed.clear()
            elif ev.kind == "frame_gap":
                cams[ev.device_id].gap_until = \
                    time.monotonic() + ev.duration_s
            elif ev.kind == "bus_stall":
                bus.stall_for(ev.duration_s)
            elif ev.kind == "slow_subscriber":
                slow_until[0] = time.monotonic() + ev.duration_s
            elif ev.kind == "uplink_down":
                ann_cloud.down_until = time.monotonic() + ev.duration_s
            elif ev.kind == "bus_flap":
                bus.flap_for(ev.duration_s)
            elif ev.kind == "device_stall":
                stall["until"] = time.monotonic() + ev.duration_s
            elif ev.kind == "black_frame":
                cams[ev.device_id].black_until = \
                    time.monotonic() + ev.duration_s
            elif ev.kind == "frozen_frame":
                cams[ev.device_id].frozen_until = \
                    time.monotonic() + ev.duration_s
            elif ev.kind == "score_drift":
                drift["until"] = time.monotonic() + ev.duration_s
        if now_s >= next_sample:
            step_cache_samples.append(
                {"t_s": round(now_s, 1), "programs": len(eng._step_cache)})
            drain_stage_records()
            next_sample = now_s + sample_every_s
        time.sleep(0.25)

    measuring.clear()
    stop.set()
    for cam in cams.values():
        cam.join(timeout=5)
    drain_stage_records()
    stats = eng.stats()
    subscriber_drops = eng.subscriber_drops
    programs_final = len(eng._step_cache)
    ticks = eng.ticks
    span_events = tracer.events()
    obs_section = {
        "metrics": obs_registry.snapshot(),
        "watch": eng.watchdog.snapshot(),
        "stage_breakdown": stage_breakdown(span_events),
        "trace": {
            "sample_every": tracer.sample_every,
            "events": len(span_events),
            "streams": len([s for s in tracer.streams()
                            if s not in ENGINE_STREAMS]),   # cameras
        },
        "quality": eng.quality.snapshot() if eng.quality is not None
        else None,
    }
    canary_snapshot = eng.canary.snapshot() if eng.canary is not None \
        else None
    tracer.configure(enabled=prev_trace[0], sample_every=prev_trace[1])
    ladder_snapshot = eng.ladder.snapshot() if eng.ladder is not None else None
    shed_frames = eng.shed_frames
    # r9 attribution snapshots, captured live like the ladder's: compile
    # cost + device-time/padding/MFU per bucket, and per-SLO burn state
    # (a >=2x-warmup soak may legitimately fire the fps objective on the
    # CPU backend — the artifact records it; the chaos gates don't care).
    perf_section = eng.perf.snapshot()
    slo_section = eng.slo.snapshot() if eng.slo is not None else None
    # r10: let an in-flight burn-triggered capture finish flushing its
    # bundle, then freeze the manifest list into the artifact.
    prof_section = None
    if eng.prof is not None:
        eng.prof.join_trigger()
        prof_section = eng.prof.snapshot()
    eng.stop()
    sink_thread.join(timeout=5)
    inner_bus.close()

    # Final uplink drain: uplink healthy again, every queued batch and
    # every spooled batch must make it out — the "zero lost annotations"
    # claim is this loop terminating with both depths at zero.
    ann_cloud.down_until = 0.0
    drain_deadline = time.monotonic() + 30.0
    while ann_q.depth() > 0 and time.monotonic() < drain_deadline:
        ann_q.requeue_rejected()
        if ann_q.drain_once() == 0:
            time.sleep(0.05)
    while ann_spool.pending() > 0 and time.monotonic() < drain_deadline:
        ann_handler([])   # empty batch = pure spool drain through cloud.py
    ann_q.stop()
    spool_snapshot = ann_spool.snapshot()
    shutil.rmtree(spool_dir, ignore_errors=True)
    if has_quality:
        try:
            os.unlink(canary_trace)
        except OSError:
            pass
    # Conservation: everything the engine enqueued was delivered exactly
    # once, minus only explicit spool evictions (bounded spool) — no
    # silent loss anywhere in queue -> handler -> spool -> drain.
    conserved = (
        ann_cloud.delivered + spool_snapshot["dropped_events"]
        == ann_q.published
    )
    resilience_section = {
        "ladder": ladder_snapshot,
        "shed_frames": shed_frames,
        "uplink": {
            "published": ann_q.published,
            "acked": ann_q.acked,
            "queue_dropped": ann_q.dropped,
            "rejected_batches": ann_q.rejected_batches,
            "posts": ann_cloud.posts,
            "post_failures": ann_cloud.post_failures,
            "delivered_events": ann_cloud.delivered,
            "final_queue_depth": ann_q.depth(),
            "breaker": ann_handler.breaker.snapshot(),
            "spool": spool_snapshot,
            "conserved": conserved,
        },
    }

    # Quality-fault attribution (ISSUE r10): for each injected quality
    # fault, find the verdict transition (or canary mismatch) that
    # answers it, and time it in ticks. Transitions carry the tracker's
    # monotonic clock, faults_applied carries offsets from t0 — same
    # clock, so the subtraction is exact. Any non-ok transition outside
    # every expected window is a false positive (the clean remainder of
    # the soak doubles as the zero-false-positive window).
    quality_section = None
    if has_quality and obs_section["quality"] is not None:
        qsnap = obs_section["quality"]
        enter_s = qcfg["quality_enter_s"]
        exit_s = qcfg["quality_exit_s"]
        verdict_for = {"black_frame": "black", "frozen_frame": "frozen"}
        expected: dict[str, list] = {}
        fault_reports = []
        episodes = obs_section["watch"].get("episodes", {})
        canary_episodes = episodes.get("canary_integrity", 0)
        for f in faults_applied:
            if f["kind"] not in verdict_for and f["kind"] != "score_drift":
                continue
            fault_mono = t0 + f["at_s"]
            report = dict(f)
            if f["kind"] == "score_drift":
                # Untimestamped by design (cycle accounting, not wall
                # time): detection = the canary mismatched and opened a
                # watchdog episode while the window was live.
                mism = (canary_snapshot or {}).get("mismatch_cycles", 0)
                report["detected"] = bool(mism and canary_episodes)
                report["mismatch_cycles"] = mism
                report["latency_s"] = None
                report["latency_ticks"] = None
            else:
                want = verdict_for[f["kind"]]
                trans = qsnap["streams"].get(
                    f["device_id"], {}).get("transitions", [])
                hit = next(
                    (t for t, v in trans
                     if v == want and t >= fault_mono - 0.5), None)
                report["detected"] = hit is not None
                report["latency_s"] = (
                    round(hit - fault_mono, 3) if hit is not None else None)
                report["latency_ticks"] = (
                    int(round((hit - fault_mono) / (tick_ms / 1000.0)))
                    if hit is not None else None)
                expected.setdefault(f["device_id"], []).append(
                    (fault_mono - 0.5,
                     fault_mono + f["duration_s"] + enter_s + exit_s + 3.0))
            fault_reports.append(report)
        false_positives = []
        for name, st in qsnap["streams"].items():
            for t, v in st["transitions"]:
                if v == "ok":
                    continue
                if any(lo <= t <= hi for lo, hi in expected.get(name, ())):
                    continue
                false_positives.append(
                    {"stream": name, "verdict": v,
                     "at_s": round(t - t0, 2)})
        quality_section = {
            "faults": fault_reports,
            "false_positives": false_positives,
            "canary": canary_snapshot,
            "canary_watchdog_episodes": canary_episodes,
            "tick_ms": tick_ms,
        }

    bucket_fill_timeline = [
        {
            "t_s": int(b * timeline_bin_s),
            "real": slot["real"],
            "padded": slot["padded"],
            "fill": round(slot["real"] / slot["padded"], 3)
            if slot["padded"] else None,
        }
        for b, slot in sorted(timeline.items())
    ]
    # Stable = the program set stopped growing before the soak ended
    # (churn-induced compiles allowed mid-run; unbounded growth is the
    # recompilation-storm failure this pins).
    step_cache_samples.append(
        {"t_s": round(duration_s, 1), "programs": programs_final})
    tail = [s["programs"] for s in step_cache_samples[-5:]]
    with lat_lock:
        per_family = {
            fam: _pct(vals) for fam, vals in sorted(lat_by_family.items())
        }
    return {
        "backend": backend,
        "duration_s": duration_s,
        "fleet": fleet,
        "streams": len(assignment),
        "src_hw": [h, w],
        "trace": os.path.basename(trace_path),
        "warmup_s": round(warmup_s, 1),
        "ticks": ticks,
        "results_measured": results["n"],
        "per_family_latency_ms": per_family,
        "bucket_fill_timeline": bucket_fill_timeline,
        "step_cache": {
            "samples": step_cache_samples,
            "final": programs_final,
            "stable": len(set(tail)) <= 1 if tail else False,
        },
        "misrouted_results": len(misrouted),
        "misrouted_examples": misrouted[:5],
        "subscriber_drops": subscriber_drops,
        "published": {d: c.published for d, c in cams.items()},
        "suppressed": {d: c.suppressed for d, c in cams.items()},
        "streams_with_results": len(stats),
        "faults_applied": faults_applied,
        "obs": obs_section,
        "resilience": resilience_section,
        "perf": perf_section,
        "slo": slo_section,
        "prof": prof_section,
        "quality": quality_section,
    }


# ---------------------------------------------------------------------------
# Full single-process pipeline e2e
# ---------------------------------------------------------------------------


def run_e2e(
    *, duration_s: float = 30.0, warmup_s: float = 8.0,
    width: int = 128, height: int = 96, fps: float = 30.0,
    model: str = "tiny_yolov8", workdir: Optional[str] = None,
) -> dict:
    """Replay a trace through the FULL pipeline — subprocess ingest worker
    (``replay://`` source) -> shm bus -> collector -> engine -> gRPC serve
    -> client — and record publish->receive latency percentiles: the <40 ms
    p50 SLA observed as ONE number on ONE pipeline run (VERDICT r5 missing
    #3). Returns the E2E_r06.json payload."""
    import shutil
    import tempfile

    import grpc

    from ..obs import registry as obs_registry, tracer
    from ..obs.spans import stage_breakdown
    from ..proto import pb, pb_grpc
    from ..serve.models import StreamProcess
    from ..serve.server import Server
    from ..utils.config import Config

    import jax

    backend = jax.default_backend()
    tmp = workdir or tempfile.mkdtemp(prefix="vep_e2e_")
    trace_path = os.path.join(tmp, "e2e.vtrace")
    record_synthetic_trace(
        trace_path, ["e2e0"], width=width, height=height, fps=fps, gop=30,
        frames=max(90, int(fps * 10)))

    cfg = Config()
    cfg.bus.shm_dir = os.path.join("/dev/shm", f"vep_e2e_{os.getpid()}")
    cfg.annotation.endpoint = "http://127.0.0.1:1/annotate"   # no egress
    cfg.engine.model = model
    cfg.engine.track = False
    # Server.__init__ reconfigures the global tracer from cfg.obs — the
    # e2e artifact carries the stage-segmented breakdown (ingest leg via
    # pub_ms on collect spans; the publish span lives in the subprocess
    # worker's rings, not ours).
    cfg.obs.trace = True
    cfg.obs.sample_every = 4
    srv = Server(cfg, data_dir=tmp, grpc_port=0, rest_port=0,
                 enable_engine=True)
    srv.start()
    lat: list[float] = []
    lat_all: list[float] = []
    lat_lock = threading.Lock()
    stop = threading.Event()
    measure_after = [float("inf")]

    def client() -> None:
        channel = grpc.insecure_channel(f"127.0.0.1:{srv.bound_grpc_port}")
        stub = pb_grpc.ImageStub(channel)
        while not stop.is_set():
            try:
                for res in stub.Inference(pb.InferenceRequest(), timeout=5):
                    if stop.is_set():
                        break
                    if not res.timestamp:
                        continue
                    sample = time.time() * 1000 - res.timestamp
                    with lat_lock:
                        lat_all.append(sample)
                        if time.monotonic() >= measure_after[0]:
                            lat.append(sample)
            except grpc.RpcError:
                if not stop.is_set():
                    time.sleep(0.5)
        channel.close()

    t = threading.Thread(target=client, daemon=True)
    t.start()
    try:
        srv.process_manager.start(StreamProcess(
            name="e2e0",
            rtsp_endpoint=f"replay://{trace_path}?device=e2e0&pace=1&loop=1",
        ))
        # Warmup covers worker boot + first-geometry compile; then measure.
        time.sleep(warmup_s)
        tracer.clear()   # measured-window spans only
        measure_after[0] = time.monotonic()
        time.sleep(duration_s)
    finally:
        stop.set()
        t.join(timeout=10)
        span_events = tracer.events()
        obs_section = {
            "metrics": obs_registry.snapshot(),
            "watch": srv.engine.watchdog.snapshot()
            if srv.engine is not None else None,
            "stage_breakdown": stage_breakdown(span_events),
            "trace": {
                "sample_every": tracer.sample_every,
                "events": len(span_events),
            },
            "perf": srv.engine.perf.snapshot()
            if srv.engine is not None else None,
            "slo": srv.engine.slo.snapshot()
            if srv.engine is not None and srv.engine.slo is not None
            else None,
        }
        tracer.configure(enabled=False)
        srv.stop()
        shutil.rmtree(cfg.bus.shm_dir, ignore_errors=True)
        if workdir is None:
            shutil.rmtree(tmp, ignore_errors=True)
    with lat_lock:
        measured = list(lat)
        total = len(lat_all)
    return {
        "metric": f"e2e_single_path_latency_{model}_{backend}",
        "pipeline": "replay://(worker subprocess) -> shm bus -> collector "
                    "-> engine -> gRPC Inference stream -> client",
        "backend": backend,
        "model": model,
        "src_hw": [height, width],
        "fps": fps,
        "duration_s": duration_s,
        "warmup_s": warmup_s,
        "results_total": total,
        "results_measured": len(measured),
        "latency_ms": _pct(measured),
        "unit": "ms publish->client-receive",
        "obs": obs_section,
    }

def _fleet_member_main(argv=None) -> None:
    """Entry for ONE fleet-soak member subprocess (``python -m
    video_edge_ai_proxy_tpu.replay.harness --instance m0 ...``), spawned
    by :func:`run_fleet_obs` / :func:`run_router_soak`. Protocol over
    stdout (JSON lines; server logs go to stderr): ``{"ready": ...,
    "rest_port", "grpc_port"}`` after boot, ``{"quiesced": ...}`` after
    the replay stream stopped and drained (counters static — the
    parent's conservation-scrape window), then the member blocks on
    stdin until the parent releases it, dumps its span rings to
    ``--spans-out`` and exits.

    ``--serve-only`` (r16, router soak): boot NO stream of its own — the
    fleet router places streams over REST — and run a stdin command loop
    instead of the timed window: ``burn`` forces the engine's SLO-burn
    verdict on (deterministic ladder pressure; pair with ``--slo-off``
    so the real SLO engine never recomputes it), ``calm`` clears it,
    ``exit`` releases the member. Each command is acked with a JSON
    line.

    A member has its own engine, so N of them cannot share one chip:
    every spawner starts members with ``JAX_PLATFORMS=cpu`` in the
    environment, and these multi-member legs are CPU count checks."""
    import argparse
    import json
    import shutil
    import sys

    ap = argparse.ArgumentParser()
    ap.add_argument("--instance", required=True)
    ap.add_argument("--workdir", required=True)
    ap.add_argument("--trace", default="",
                    help="replay trace for the self-started stream "
                         "(ignored with --serve-only)")
    ap.add_argument("--device", default="",
                    help="self-started stream name (ignored with "
                         "--serve-only)")
    ap.add_argument("--model", default="tiny_yolov8")
    ap.add_argument("--duration", type=float, default=12.0)
    ap.add_argument("--warmup", type=float, default=8.0,
                    help="extra replay seconds before the measured window "
                         "(covers worker boot + first-geometry compile)")
    ap.add_argument("--spans-out", required=True)
    ap.add_argument("--serve-only", action="store_true")
    ap.add_argument("--slo-off", action="store_true",
                    help="disable the SLO engine so the burn flag is "
                         "script-controlled, not recomputed per window")
    ap.add_argument("--ladder-escalate", type=float, default=None,
                    help="override engine.ladder_escalate_after_s (the "
                         "router soak spaces rungs so migration lands "
                         "between shed_to_fleet and bucket_downshift)")
    ap.add_argument("--shed-staleness-ms", type=float, default=None,
                    help="override engine.shed_staleness_ms (the router "
                         "soak sets it high so the shed rung itself "
                         "drops nothing and the conservation ledger "
                         "stays attributable to migration alone)")
    ap.add_argument("--batch-bucket", type=int, default=0,
                    help="pin a single collector batch bucket so a "
                         "migrated stream joining mid-soak never "
                         "triggers a new device program (compile would "
                         "drop frames via latest-frame-wins)")
    ap.add_argument("--ladder-slo-only", action="store_true",
                    help="neuter the ladder's physical pressure inputs "
                         "(queue depth / tick lag) so the injected SLO "
                         "burn is the ONLY rung driver — on the CPU "
                         "backend an inference tick takes ~20x the 10ms "
                         "tick budget, which would walk every member's "
                         "ladder and make the router soak ping-pong")
    ap.add_argument("--trace-every", type=int, default=None,
                    help="override obs.sample_every (the router soak "
                         "traces every frame so short post-migration "
                         "residence still yields a stitchable chain)")
    ap.add_argument("--prewarm", action="append", default=[],
                    metavar="HxWxB[:model]",
                    help="compile this program during boot (repeatable); "
                         "soak members prewarm every geometry they will "
                         "serve so no in-soak compile ever overwrites an "
                         "uncollected frame (latest-frame-wins) and the "
                         "conservation ledger holds from the FIRST frame")
    ap.add_argument("--aot-cache", default="",
                    help="shared persistent AOT cache dir (r19, "
                         "engine/aot_cache.py): sets engine.aot_cache + "
                         "aot_cache_dir; a member sharing a populated dir "
                         "prewarms via persistent-cache hits and the "
                         "manifest supplies the program set when no "
                         "--prewarm flags are given (the spawned-member "
                         "path)")
    ap.add_argument("--capacity", action="store_true",
                    help="enable the r18 capacity attribution plane "
                         "(headroom + saturation forecast) — the "
                         "autoscale soak's supervisor steers on it")
    ap.add_argument("--capacity-fast-window", type=float, default=None,
                    help="override engine.capacity_fast_window_s (soaks "
                         "run minutes, not hours: the fast burn window "
                         "must fit inside the soak's ramp)")
    args = ap.parse_args(argv)
    if not args.serve_only and (not args.trace or not args.device):
        ap.error("--trace/--device required without --serve-only")
    from ..obs import tracer
    from ..serve.models import StreamProcess
    from ..serve.server import Server
    from ..utils.config import Config

    cfg = Config()
    cfg.bus.shm_dir = os.path.join("/dev/shm", f"vep_fleet_{os.getpid()}")
    cfg.annotation.endpoint = "http://127.0.0.1:1/annotate"   # no egress
    cfg.engine.model = args.model
    cfg.engine.track = False
    cfg.obs.trace = True
    cfg.obs.sample_every = 4
    cfg.obs.instance = args.instance   # const instance label on /metrics
    if args.slo_off:
        cfg.engine.slo = False
    if args.ladder_escalate is not None:
        cfg.engine.ladder_escalate_after_s = args.ladder_escalate
    if args.shed_staleness_ms is not None:
        cfg.engine.shed_staleness_ms = args.shed_staleness_ms
    if args.batch_bucket:
        cfg.engine.batch_buckets = (args.batch_bucket,)
    if args.trace_every is not None:
        cfg.obs.sample_every = args.trace_every
    if args.prewarm:
        entries = []
        for spec in args.prewarm:
            geom, _, mdl = spec.partition(":")
            h, w, b = (int(v) for v in geom.split("x"))
            entries.append([h, w, b, mdl] if mdl else [h, w, b])
        cfg.engine.prewarm = entries
    if args.aot_cache:
        cfg.engine.aot_cache = True
        cfg.engine.aot_cache_dir = args.aot_cache
    if args.capacity:
        cfg.engine.capacity = True
    if args.capacity_fast_window is not None:
        cfg.engine.capacity_fast_window_s = args.capacity_fast_window
    srv = Server(cfg, data_dir=args.workdir, grpc_port=0, rest_port=0,
                 enable_engine=True)
    srv.start()
    if args.ladder_slo_only and srv.engine is not None \
            and srv.engine.ladder is not None:
        # Physical pressure (drain depth / tick lag vs the 10ms budget)
        # is unavoidable on the CPU backend; push both thresholds out of
        # reach so observe()'s slo_burning input is the only escalation
        # driver and the soak's rung walk is script-controlled.
        srv.engine.ladder.depth_threshold = 10**9
        srv.engine.ladder.lag_factor = 10**9
    print(json.dumps({
        "ready": True, "instance": args.instance,
        "rest_port": srv._rest.bound_port,
        "grpc_port": srv.bound_grpc_port,
    }), flush=True)
    try:
        if args.serve_only:
            # Router-soak mode: the router owns placement; this process
            # only answers burn/calm/exit (ack each so the parent can
            # sequence without sleeps).
            for line in sys.stdin:
                cmd = line.strip()
                if cmd == "burn":
                    if srv.engine is not None:
                        srv.engine._slo_burning = True
                elif cmd == "calm":
                    if srv.engine is not None:
                        srv.engine._slo_burning = False
                elif cmd == "exit":
                    print(json.dumps({"ack": "exit",
                                      "instance": args.instance}),
                          flush=True)
                    break
                else:
                    continue
                print(json.dumps({"ack": cmd, "instance": args.instance}),
                      flush=True)
        else:
            srv.process_manager.start(StreamProcess(
                name=args.device,
                rtsp_endpoint=(
                    f"replay://{args.trace}?device={args.device}"
                    "&pace=1&loop=1"
                ),
            ))
            time.sleep(args.warmup + args.duration)
            srv.process_manager.stop(args.device)
            time.sleep(1.0)   # engine drain: counters static after this
            print(json.dumps({"quiesced": True, "instance": args.instance}),
                  flush=True)
            sys.stdin.readline()   # parent finished conservation scrapes
    finally:
        events = tracer.events()
        with open(args.spans_out, "w") as f:
            json.dump({"events": events}, f)
        tracer.configure(enabled=False)
        srv.stop()
        shutil.rmtree(cfg.bus.shm_dir, ignore_errors=True)


def run_fleet_obs(
    *, n_members: int = 3, duration_s: float = 12.0, warmup_s: float = 8.0,
    width: int = 128, height: int = 96, fps: float = 30.0,
    model: str = "tiny_yolov8", workdir: Optional[str] = None,
) -> dict:
    """r14 fleet telemetry soak: N REAL server processes (each with its
    own subprocess ingest worker, shm bus, engine, gRPC + REST), one
    FleetAggregator scraping them, and one gRPC client per member
    recording the ``InferenceResult.trace_id`` echo. Produces the
    ``FLEETOBS_r01.json`` payload with the four hard gates:

    - ``merged_lint_clean`` — the aggregator's single Prometheus page
      passes ``metrics.lint_exposition``;
    - ``all_members_present`` — every member alive + fresh in the ranked
      health view at quiesce;
    - ``stitched_traces`` >= 1 — at least one trace_id stamped in a
      member's WORKER process (nonzero on the wire) observed through the
      engine's collect/device/emit spans AND received by the client —
      the full worker -> bus -> engine -> client lineage;
    - ``counters_conserved`` — after quiesce, every merged counter
      equals the sum of the members' individually-scraped values.

    CPU-only by construction: the gates are counts, and N member
    processes each with its own engine cannot share one chip — every
    member is started with ``JAX_PLATFORMS=cpu``.
    """
    import json as _json
    import shutil
    import subprocess
    import sys
    import tempfile
    import urllib.request

    import grpc

    from ..obs.fleet import FleetAggregator, _strip_label, parse_exposition
    from ..obs.metrics import lint_exposition
    from ..obs.spans import to_chrome_trace, validate_chrome_trace
    from ..proto import pb, pb_grpc

    tmp = workdir or tempfile.mkdtemp(prefix="vep_fleetobs_")
    procs: list = []
    spans_paths: list = []
    try:
        for i in range(n_members):
            device = f"fleet{i}"
            trace_path = os.path.join(tmp, f"{device}.vtrace")
            record_synthetic_trace(
                trace_path, [device], width=width, height=height, fps=fps,
                gop=30, frames=max(90, int(fps * 10)))
            spans_out = os.path.join(tmp, f"m{i}_spans.json")
            spans_paths.append(spans_out)
            member_dir = os.path.join(tmp, f"m{i}")
            os.makedirs(member_dir, exist_ok=True)
            cmd = [
                sys.executable, "-m",
                "video_edge_ai_proxy_tpu.replay.harness",
                "--instance", f"m{i}", "--workdir", member_dir,
                "--trace", trace_path, "--device", device,
                "--model", model, "--duration", str(duration_s),
                "--warmup", str(warmup_s), "--spans-out", spans_out,
            ]
            env = dict(os.environ, JAX_PLATFORMS="cpu")
            procs.append(subprocess.Popen(
                cmd, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                stderr=open(os.path.join(tmp, f"m{i}.stderr"), "w"),
                env=env, text=True))

        def read_msg(proc, key, timeout_s=120.0):
            """Next stdout JSON line carrying ``key`` (skips log noise);
            SystemExit with the member's stderr tail on death/timeout."""
            deadline = time.monotonic() + timeout_s
            while time.monotonic() < deadline:
                line = proc.stdout.readline()
                if not line:
                    raise SystemExit(
                        f"fleet member died (rc={proc.poll()}); see "
                        f"{tmp}/m*.stderr")
                try:
                    msg = _json.loads(line)
                except ValueError:
                    continue
                if key in msg:
                    return msg
            raise SystemExit(f"fleet member: no {key!r} within {timeout_s}s")

        boots = [read_msg(p, "ready") for p in procs]
        rest_ports = [b["rest_port"] for b in boots]
        grpc_ports = [b["grpc_port"] for b in boots]

        agg = FleetAggregator(
            [f"m{i}=http://127.0.0.1:{rest_ports[i]}"
             for i in range(n_members)],
            scrape_interval_s=1.0)
        agg.start()

        client_tids: list = [set() for _ in range(n_members)]
        results_count = [0] * n_members
        stop = threading.Event()

        def client(i: int) -> None:
            channel = grpc.insecure_channel(f"127.0.0.1:{grpc_ports[i]}")
            stub = pb_grpc.ImageStub(channel)
            while not stop.is_set():
                try:
                    for res in stub.Inference(
                            pb.InferenceRequest(), timeout=5):
                        if stop.is_set():
                            break
                        results_count[i] += 1
                        if res.trace_id:
                            client_tids[i].add(res.trace_id)
                except grpc.RpcError:
                    if not stop.is_set():
                        time.sleep(0.5)
            channel.close()

        threads = [threading.Thread(target=client, args=(i,), daemon=True)
                   for i in range(n_members)]
        for t in threads:
            t.start()

        for p in procs:
            read_msg(p, "quiesced", timeout_s=warmup_s + duration_s + 120.0)
        stop.set()
        for t in threads:
            t.join(timeout=10)

        # Conservation window: streams are stopped and drained, but a
        # few heartbeat counters (engine tick loop) keep moving. Bracket
        # the aggregator's scrape with two direct member scrapes and
        # gate ONLY the families that were provably static across the
        # whole window (frame/result counters are; tick counters
        # self-exclude) — merged value must equal the member-wise sum.
        def scrape_pages():
            pages = []
            for port in rest_ports:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/metrics", timeout=5) as r:
                    pages.append(r.read().decode())
            return pages

        def counter_sums(pages):
            out: dict = {}
            for page in pages:
                for fam in parse_exposition(page):
                    if fam["kind"] != "counter":
                        continue
                    for _name, labels, value in fam["samples"]:
                        key = (fam["name"],
                               _strip_label(labels, "instance"))
                        out[key] = out.get(key, 0.0) + value
            return out

        pages_before = scrape_pages()
        agg.scrape_once()
        pages_after = scrape_pages()
        member_lint = [lint_exposition(p) for p in pages_after]
        before = counter_sums(pages_before)
        after = counter_sums(pages_after)
        static_keys = sorted(
            k for k, v in before.items() if after.get(k) == v)
        merged_counters = agg.fleet_stats()["counters"]
        mismatches = []
        for fam_name, labels in static_keys:
            want = before[(fam_name, labels)]
            got = merged_counters.get(fam_name, {}).get(
                labels, {}).get("value")
            if got is None or abs(got - want) > 1e-6:
                mismatches.append({
                    "family": fam_name, "labels": labels,
                    "member_sum": want, "merged": got})

        merged_text = agg.merged_exposition()
        lint_errors = lint_exposition(merged_text)
        health = agg.health()
        all_present = (
            len(health) == n_members
            and all(h["up"] and not h["stale"] for h in health))

        # Release members -> they dump spans and exit.
        for p in procs:
            try:
                p.stdin.write("exit\n")
                p.stdin.flush()
                p.stdin.close()
            except (BrokenPipeError, OSError):
                pass
        for p in procs:
            p.wait(timeout=60)
        agg.stop()

        member_spans = []
        for path in spans_paths:
            with open(path) as f:
                member_spans.append(_json.load(f).get("events", []))

        # One fleet timeline: per-member pid namespaces (the same merge
        # tools/obs_export.py --merge --member performs).
        merged_events: list = []
        for i, evs in enumerate(member_spans):
            merged_events.extend(to_chrome_trace(
                evs, pid=i + 1, process_name=f"m{i}")["traceEvents"])
        fleet_trace = {"traceEvents": merged_events,
                       "displayTimeUnit": "ms"}
        trace_problems = validate_chrome_trace(fleet_trace)

        # Cross-process stitching: the trace_id was minted in the ingest
        # WORKER process (FrameMeta on the shm bus), observed by the
        # engine's spans, and echoed to the gRPC client.
        stitched = []
        for i, evs in enumerate(member_spans):
            stages_by_tid: dict = {}
            for ev in evs:
                tid = ev.get("trace_id")
                if tid:
                    stages_by_tid.setdefault(tid, set()).add(ev["stage"])
            for tid, stages in sorted(stages_by_tid.items()):
                if ({"collect", "device", "emit"} <= stages
                        and tid in client_tids[i]):
                    stitched.append({
                        "member": f"m{i}", "trace_id": tid,
                        "stages": sorted(stages)})

        return {
            "metric": f"fleet_obs_{n_members}x_{model}",
            "pipeline": (
                f"{n_members}x [replay worker -> shm bus -> engine -> "
                "gRPC/REST] -> FleetAggregator + per-member clients"),
            "members": n_members,
            "duration_s": duration_s,
            "model": model,
            "fps": fps,
            "gates": {
                "merged_lint_clean": not lint_errors,
                "member_lint_clean": all(not e for e in member_lint),
                "all_members_present": all_present,
                "stitched_traces": len(stitched),
                "counters_conserved": bool(static_keys) and not mismatches,
                "fleet_trace_valid": not trace_problems,
            },
            "lint_errors": lint_errors[:10],
            "counters_gated": len(static_keys),
            "counter_mismatches": mismatches[:10],
            "trace_problems": trace_problems[:10],
            "health": health,
            "stitched_example": stitched[0] if stitched else None,
            "client_results": results_count,
            "client_trace_ids": [len(s) for s in client_tids],
            "merged_exposition_lines": len(merged_text.splitlines()),
            "merged_counter_families": len(merged_counters),
            "fleet_trace_events": len(merged_events),
            "span_events_per_member": [len(s) for s in member_spans],
        }
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()   # by PID via Popen handle — never pkill
        if workdir is None:
            shutil.rmtree(tmp, ignore_errors=True)


def run_router_soak(
    *, n_members: int = 3, streams_per_member: int = 2,
    width: int = 128, height: int = 96, fps: float = 2.0,
    model: str = "tiny_yolov8", scrape_interval_s: float = 1.0,
    ladder_escalate_s: float = 8.0, workdir: Optional[str] = None,
) -> dict:
    """r16 fleet-router soak: N REAL serve-only server processes, one
    :class:`~..serve.router.StreamRouter` placing ``n_members *
    streams_per_member`` replay streams across them, then two fault
    legs with hard gates (the ``ROUTER_r01.json`` payload):

    - **burn leg** — force the SLO-burn verdict on one member
      (stdin ``burn``; the member runs ``--slo-off`` so nothing
      recomputes the flag). Its ladder walks shed → shed_to_fleet; the
      router sees the rung and gracefully migrates the member's streams
      (drain→cutover→resume at the replay cursor). Gate: at migration
      completion the member's ladder shows ``shed_to_fleet >= 1`` and
      ``bucket_downshift == 0`` transitions — horizontal re-placement
      engaged BEFORE the local ladder shrank device programs.
    - **kill leg** — SIGKILL one member. Gate: every one of its streams
      is re-placed with detection-to-resumed latency within one scrape
      interval (detection itself is bounded by the scrape cadence; the
      wall-clock kill→resumed bound is ``scrape_interval + 1s``).

    Cross-cutting gates: the frame-conservation ledger balances for
    EVERY stream (packet ids gap-free from the very FIRST delivery,
    zero duplicates — exactly-once across the handoffs; members prewarm
    their one device program at boot, so there is no compile ramp to
    excuse and no post-warmup ledger reset); every completed migration has a
    stitched worker→bus→engine→client lineage (span chain
    collect+device+emit for a trace id the destination's gRPC client
    also received — and the source's too on the graceful leg); and the
    router's ``vep_router_*`` exposition is ``lint_exposition``-clean.

    Determinism levers: members pin ONE batch bucket and prewarm its
    program at boot (any in-soak compile — first frame or migrated
    stream joining — would drop frames via latest-frame-wins and
    corrupt the ledger), shed staleness is set
    above the soak length (the shed rung itself drops nothing),
    ``ladder_escalate_s`` spaces the rungs so migration has a full
    window between shed_to_fleet and bucket_downshift, ``fps`` sits
    well below the CPU backend's per-member tick rate (latest-frame-wins
    never overwrites an uncollected frame, so steady state is lossless
    and the ledger attributes any gap to migration), and members run
    ``--ladder-slo-only`` (physical tick-lag pressure is unavoidable on
    CPU and would walk EVERY member's ladder — the injected burn must be
    the only rung driver or the fleet ping-pongs).

    CPU-only by construction (members start with ``JAX_PLATFORMS=cpu``):
    the gates are counts, and N engines cannot share one chip.
    """
    import json as _json
    import shutil
    import subprocess
    import sys
    import tempfile
    import urllib.request

    import grpc

    from ..obs import registry as obs_registry
    from ..obs.metrics import lint_exposition
    from ..proto import pb, pb_grpc
    from ..serve.router import StreamRouter

    tmp = workdir or tempfile.mkdtemp(prefix="vep_router_")
    member_names = [f"m{i}" for i in range(n_members)]
    bucket = 1
    while bucket < n_members * streams_per_member + 2:
        bucket *= 2
    procs: list = []
    spans_paths: list = []
    router: Optional[StreamRouter] = None
    stop = threading.Event()
    threads: list = []
    try:
        for i, mname in enumerate(member_names):
            spans_out = os.path.join(tmp, f"{mname}_spans.json")
            spans_paths.append(spans_out)
            member_dir = os.path.join(tmp, mname)
            os.makedirs(member_dir, exist_ok=True)
            cmd = [
                sys.executable, "-m",
                "video_edge_ai_proxy_tpu.replay.harness",
                "--instance", mname, "--workdir", member_dir,
                "--model", model, "--spans-out", spans_out,
                "--serve-only", "--slo-off", "--ladder-slo-only",
                "--ladder-escalate", str(ladder_escalate_s),
                "--shed-staleness-ms", "60000",
                "--batch-bucket", str(bucket),
                "--trace-every", "1",
                # The member's ONE device program compiles during boot
                # (before the ready line), not on the first delivered
                # frame: the compile ramp used to overwrite ~20 frames
                # per stream (latest-frame-wins) and forced a post-warmup
                # ledger reset — prewarmed, conservation holds from the
                # very first frame (r19; see MigrationLedger docstring).
                "--prewarm", f"{height}x{width}x{bucket}",
            ]
            env = dict(os.environ, JAX_PLATFORMS="cpu")
            procs.append(subprocess.Popen(
                cmd, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                stderr=open(os.path.join(tmp, f"{mname}.stderr"), "w"),
                env=env, text=True))

        def read_msg(proc, key, timeout_s=240.0):
            deadline = time.monotonic() + timeout_s
            while time.monotonic() < deadline:
                line = proc.stdout.readline()
                if not line:
                    raise SystemExit(
                        f"router-soak member died (rc={proc.poll()}); "
                        f"see {tmp}/m*.stderr")
                try:
                    msg = _json.loads(line)
                except ValueError:
                    continue
                if key in msg:
                    return msg
            raise SystemExit(f"router-soak member: no {key!r} within "
                             f"{timeout_s}s")

        def send_cmd(idx: int, cmd: str, ack: bool = True):
            procs[idx].stdin.write(cmd + "\n")
            procs[idx].stdin.flush()
            if ack:
                read_msg(procs[idx], "ack", timeout_s=30.0)

        boots = [read_msg(p, "ready") for p in procs]
        rest_ports = [b["rest_port"] for b in boots]
        grpc_ports = [b["grpc_port"] for b in boots]

        router = StreamRouter(
            [f"{m}=http://127.0.0.1:{rest_ports[i]}"
             for i, m in enumerate(member_names)],
            scrape_interval_s=scrape_interval_s,
            max_moves_per_pass=n_members * streams_per_member,
            # Drain poll/settle must cover a full CPU inference tick
            # (~0.2-0.4s): a frame collected just before the stop lands
            # on the src's counter up to one tick AFTER it first reads
            # static, and a cursor read inside that window would resume
            # the dst on an already-delivered packet (duplicate).
            drain_timeout_s=5.0, drain_poll_s=0.5)
        router.run_pass()                       # first health view
        attach_errors = {k: v for k, v in router.attach().items() if v}

        # Balanced initial placement by CONSTRUCTION of the names: walk
        # candidate stream names and keep the first streams_per_member
        # that consistent-hash onto each member — every member compiles
        # its (single) device program during warmup, so neither fault
        # leg's destination ever compiles on a migrated stream's frames.
        per_member: dict = {m: [] for m in member_names}
        cand = 0
        while any(len(v) < streams_per_member for v in per_member.values()):
            name = f"cam{cand:03d}"
            cand += 1
            owner = router.ring.place(name)
            if owner and len(per_member[owner]) < streams_per_member:
                per_member[owner].append(name)
            if cand > 10_000:
                raise SystemExit("hash search failed to balance placement")
        stream_names = [n for m in member_names for n in per_member[m]]
        # One long trace per stream: frames must OUTLAST the soak
        # (loop/EOF-restart would re-deliver packet ids and fake a
        # conservation violation).
        for name in stream_names:
            record_synthetic_trace(
                os.path.join(tmp, f"{name}.vtrace"), [name],
                width=width, height=height, fps=fps, gop=30,
                frames=int(fps * 240))

        # Per-member result consumers feed the router's conservation
        # ledger: (stream, member, packet, trace_id) for every delivered
        # InferenceResult — the client side of the lineage chain.
        tids: dict = {m: {} for m in member_names}

        def client(i: int) -> None:
            mname = member_names[i]
            channel = grpc.insecure_channel(f"127.0.0.1:{grpc_ports[i]}")
            stub = pb_grpc.ImageStub(channel)
            while not stop.is_set():
                try:
                    # NO deadline: a deadline-kicked re-subscribe loop
                    # would miss the results emitted during each gap and
                    # fake conservation-ledger losses. Streams keep
                    # flowing until shutdown, so the stop flag is always
                    # reached; a dead member raises instead.
                    for res in stub.Inference(pb.InferenceRequest()):
                        if stop.is_set():
                            break
                        if not res.device_id:
                            continue
                        router.ledger.note_delivery(
                            res.device_id, mname, res.frame_packet,
                            res.trace_id)
                        if res.trace_id:
                            tids[mname].setdefault(
                                res.device_id, set()).add(res.trace_id)
                except grpc.RpcError:
                    if not stop.is_set():
                        time.sleep(0.25)
            channel.close()

        threads = [threading.Thread(target=client, args=(i,), daemon=True)
                   for i in range(n_members)]
        for t in threads:
            t.start()

        for name in stream_names:
            placed = router.add_stream(
                name,
                f"replay://{tmp}/{name}.vtrace?device={name}&pace=1&loop=0",
                priority=stream_names.index(name))
            assert placed in per_member and name in per_member[placed]

        # Warmup: every stream delivering (worker boot + the one compile
        # per member), then let the pipeline settle.
        deadline = time.monotonic() + 180.0
        while time.monotonic() < deadline:
            if all(router.ledger.next_cursor(n) is not None
                   for n in stream_names):
                break
            time.sleep(0.25)
        else:
            raise SystemExit(
                "warmup: not every stream delivered results; see "
                f"{tmp}/m*.stderr")
        time.sleep(2.0)                         # pipeline settles
        router.start()                          # background control loop

        # ---- burn leg: m0 burns; ladder must hand off BEFORE downshift.
        burn_member = member_names[0]
        burn_streams = list(per_member[burn_member])
        send_cmd(0, "burn")
        t_burn = time.monotonic()
        deadline = t_burn + 2 * ladder_escalate_s + 3 * scrape_interval_s \
            + 10.0
        while time.monotonic() < deadline:
            if not router.streams_on(burn_member):
                break
            time.sleep(0.05)
        burn_evacuated = not router.streams_on(burn_member)
        t_burn_done = time.monotonic()
        # Ladder state AT migration completion — then calm immediately,
        # before idle burn pressure walks the member any further.
        with urllib.request.urlopen(
                f"http://127.0.0.1:{rest_ports[0]}/api/v1/router",
                timeout=5) as r:
            burn_ladder = _json.loads(r.read())
        send_cmd(0, "calm")
        burn_transitions = burn_ladder.get("transitions", {})
        # Wait out the ladder's recovery walk (one rung per
        # recover_after_s): while the burn member still reports
        # shed_to_fleet or above, the router would immediately re-shed
        # any stream the kill leg evacuates onto it.
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{rest_ports[0]}/api/v1/router",
                    timeout=5) as r:
                if _json.loads(r.read()).get("rung") in ("normal", "shed"):
                    break
            time.sleep(0.25)
        time.sleep(3.0)                         # resumed streams deliver

        # ---- kill leg: SIGKILL the last member; the router must
        # re-place its streams within one scrape interval of detection.
        kill_idx = n_members - 1
        kill_member = member_names[kill_idx]
        kill_streams = list(router.streams_on(kill_member))
        procs[kill_idx].kill()   # by PID via Popen handle — never pkill
        procs[kill_idx].wait(timeout=10)
        t_kill = time.monotonic()
        deadline = t_kill + 3 * scrape_interval_s + 10.0
        while time.monotonic() < deadline:
            if not router.streams_on(kill_member):
                break
            time.sleep(0.02)
        kill_wall_s = time.monotonic() - t_kill
        kill_evacuated = not router.streams_on(kill_member)
        time.sleep(4.0)                         # resumed streams deliver

        router.stop()
        migrations = list(router.ledger.migrations)
        kill_migs = [m for m in migrations if m["reason"] == "member_dead"]
        burn_migs = [m for m in migrations
                     if m["src"] == burn_member and m["ok"]]
        kill_detect_s = max(
            (m["replace_s"] for m in kill_migs if m.get("ok")),
            default=None)

        stop.set()
        for t in threads:
            t.join(timeout=10)
        balance = router.ledger.balance()

        # Release survivors -> span dumps; the killed member left none.
        for i, p in enumerate(procs):
            if i == kill_idx:
                continue
            try:
                send_cmd(i, "exit", ack=False)
                p.stdin.close()
            except (BrokenPipeError, OSError):
                pass
        for i, p in enumerate(procs):
            if i != kill_idx:
                p.wait(timeout=60)

        member_spans: dict = {}
        for mname, path in zip(member_names, spans_paths):
            if not os.path.exists(path):
                member_spans[mname] = []
                continue
            with open(path) as f:
                member_spans[mname] = _json.load(f).get("events", [])

        def stitched(mname: str, stream: str) -> bool:
            """A trace id with the full collect+device+emit span chain on
            ``mname`` that ``mname``'s gRPC client also delivered for
            ``stream`` — worker->bus->engine->client, one id."""
            stages_by_tid: dict = {}
            for ev in member_spans.get(mname, []):
                tid = ev.get("trace_id")
                if tid:
                    stages_by_tid.setdefault(tid, set()).add(ev["stage"])
            want = tids.get(mname, {}).get(stream, set())
            return any({"collect", "device", "emit"} <= stages
                       and tid in want
                       for tid, stages in stages_by_tid.items())

        lineage = []
        for m in migrations:
            if not m.get("ok"):
                continue
            row = {"stream": m["stream"], "src": m["src"],
                   "dst": m["dst"], "reason": m["reason"],
                   "dst_stitched": stitched(m["dst"], m["stream"])}
            if (not row["dst_stitched"] and m["dst"] == kill_member
                    and not member_spans.get(kill_member)):
                # A burn-leg migration may land on the member the kill
                # leg later SIGKILLs — the kill forfeits its span dump,
                # so the on-wire trace ids its gRPC client DID deliver
                # for the stream are the surviving lineage evidence.
                row["dst_stitched"] = bool(
                    tids.get(kill_member, {}).get(m["stream"]))
                row["dst_evidence"] = \
                    "client-delivered trace ids (span dump lost to kill)"
            if m["src"] != kill_member:
                row["src_stitched"] = stitched(m["src"], m["stream"])
            lineage.append(row)
        lineage_ok = bool(lineage) and all(
            r["dst_stitched"] and r.get("src_stitched", True)
            for r in lineage)

        exposition = obs_registry.render()
        lint_errors = lint_exposition(exposition)
        router_families = sorted({
            line.split()[2] for line in exposition.splitlines()
            if line.startswith("# TYPE vep_router_")})

        gates = {
            "attach_clean": not attach_errors,
            "burn_streams_evacuated": burn_evacuated and bool(burn_migs),
            "burn_shed_to_fleet_before_downshift": (
                burn_transitions.get("shed_to_fleet", 0) >= 1
                and burn_transitions.get("bucket_downshift", 0) == 0),
            "kill_streams_replaced": (
                kill_evacuated and bool(kill_streams)
                and all(m.get("ok") for m in kill_migs)),
            "kill_replace_within_scrape": (
                kill_detect_s is not None
                and kill_detect_s <= scrape_interval_s),
            "kill_replace_wall_bounded": (
                kill_wall_s <= scrape_interval_s + 1.0),
            "ledger_balanced": balance["balanced"],
            "migrated_lineage_stitched": lineage_ok,
            "router_metrics_lint_clean": (
                not lint_errors and len(router_families) >= 6),
        }
        return {
            "metric": f"fleet_router_{n_members}x{streams_per_member}_"
                      f"{model}",
            "pipeline": (
                f"{n_members}x serve-only member <- StreamRouter "
                "(consistent hash + burn/kill migration) <- per-member "
                "gRPC clients -> conservation ledger"),
            "members": n_members,
            "streams": len(stream_names),
            "fps": fps,
            "model": model,
            "scrape_interval_s": scrape_interval_s,
            "ladder_escalate_s": ladder_escalate_s,
            "gates": gates,
            "placement": per_member,
            "burn": {
                "member": burn_member,
                "streams": burn_streams,
                "migrate_s": round(t_burn_done - t_burn, 3),
                "transitions_at_migration": burn_transitions,
                "ladder": burn_ladder,
                "migrations": burn_migs,
            },
            "kill": {
                "member": kill_member,
                "streams": kill_streams,
                "replace_detect_s": kill_detect_s,
                "replace_wall_s": round(kill_wall_s, 3),
                "migrations": kill_migs,
            },
            "ledger": {
                "balanced": balance["balanced"],
                "lost": balance["lost"],
                "duplicated": balance["duplicated"],
                "streams": balance["streams"],
            },
            "lineage": lineage,
            "lint_errors": lint_errors[:10],
            "router_families": router_families,
            "router_snapshot": router.snapshot(),
        }
    finally:
        stop.set()
        if router is not None:
            router.stop()
        for p in procs:
            if p.poll() is None:
                p.kill()   # by PID via Popen handle — never pkill
        if workdir is None:
            shutil.rmtree(tmp, ignore_errors=True)


class LoadShape:
    """Production-shaped churn schedule for the autoscale soak (r19).

    Four shapes the reference deployments actually see, folded into one
    deterministic timetable (no RNG — reruns hit identical schedules):

    - **diurnal ramp** — ``ramp_streams`` cameras connect one every
      ``ramp_interval_s`` on top of the ``base_streams`` steady tenants:
      the morning build-up whose utilization *slope* the r18 capacity
      forecast extrapolates into ``time_to_saturation_s`` — the signal
      the supervisor must act on BEFORE saturation, not after. The ramp
      deliberately outlasts a spawned member's boot, so the arrivals
      still connecting when the fresh member comes up land on it (the
      headroom-tiered admission prefers the emptiest member) — scale-out
      absorbs the tail of the very build-up that triggered it.
    - **connect/disconnect storm** — ``storm_streams`` cameras connect
      within seconds (an NVR rebooting, a site coming back from a
      network partition) and later disconnect just as fast. The storm
      lands AFTER the ramp so a forecast-driven scale-out has already
      added capacity when it hits.
    - **hot-spot camera** — the first base stream runs ``hot_fps``
      against everyone else's ``base_fps``: one member always carries
      visibly more load than its peers, so placement/retire decisions
      ride on real per-member skew, not uniform load.
    - **mixed model tenants** — stream specs rotate through ``models``
      (``""`` = the member default), so members serve multiple device
      programs and the AOT prewarm manifest has to carry the full
      program SET, not one geometry.

    ``specs()`` lists every stream (name, fps, model, phase);
    ``events()`` is the sorted ``{"t", "op", "stream"}`` timetable
    relative to the soak's post-warmup t=0 (base connects at t<=0 run
    before the supervisor starts).
    """

    def __init__(
        self, *, base_streams: int = 3, ramp_streams: int = 6,
        ramp_start_s: float = 2.0, ramp_interval_s: float = 4.0,
        storm_streams: int = 6, storm_start_s: float = 28.0,
        storm_spacing_s: float = 0.4, storm_hold_s: float = 18.0,
        drain_interval_s: float = 0.8,
        base_fps: float = 0.5, hot_fps: float = 1.5,
        models: tuple = ("", "tiny_mobilenet_v2"),
    ):
        if base_streams < 1 or storm_streams < 1:
            raise ValueError("need at least one base and one storm stream")
        if storm_start_s <= ramp_start_s + ramp_streams * ramp_interval_s:
            raise ValueError(
                "storm must start after the ramp finishes (the shape's "
                "point is that forecast-driven scale-out lands first)")
        self.base_streams = int(base_streams)
        self.ramp_streams = int(ramp_streams)
        self.ramp_start_s = float(ramp_start_s)
        self.ramp_interval_s = float(ramp_interval_s)
        self.storm_streams = int(storm_streams)
        self.storm_start_s = float(storm_start_s)
        self.storm_spacing_s = float(storm_spacing_s)
        self.storm_hold_s = float(storm_hold_s)
        self.drain_interval_s = float(drain_interval_s)
        self.base_fps = float(base_fps)
        self.hot_fps = float(hot_fps)
        self.models = tuple(models)

    def specs(self) -> list:
        out = []
        tenant = 0
        for phase, count, prefix in (
                ("base", self.base_streams, "base"),
                ("ramp", self.ramp_streams, "ramp"),
                ("storm", self.storm_streams, "storm")):
            for i in range(count):
                hot = phase == "base" and i == 0
                out.append({
                    "stream": f"{prefix}{i:03d}",
                    "phase": phase,
                    "hot": hot,
                    "fps": self.hot_fps if hot else self.base_fps,
                    "model": self.models[tenant % len(self.models)],
                })
                tenant += 1
        return out

    def events(self) -> list:
        ev = []
        for spec in self.specs():
            name, phase = spec["stream"], spec["phase"]
            i = int(name[-3:])
            if phase == "base":
                ev.append({"t": 0.0, "op": "connect", "stream": name})
            elif phase == "ramp":
                t_on = self.ramp_start_s + i * self.ramp_interval_s
                ev.append({"t": t_on, "op": "connect", "stream": name})
                # Ramp sheds after the storm has fully drained: the
                # surplus the retire leg waits on is sustained, not a
                # lull between waves.
                t_off = (self.storm_start_s + self.storm_hold_s
                         + self.storm_streams * self.drain_interval_s
                         + 1.0 + i * self.drain_interval_s)
                ev.append({"t": t_off, "op": "disconnect", "stream": name})
            else:
                t_on = self.storm_start_s + i * self.storm_spacing_s
                ev.append({"t": t_on, "op": "connect", "stream": name})
                t_off = (self.storm_start_s + self.storm_hold_s
                         + i * self.drain_interval_s)
                ev.append({"t": t_off, "op": "disconnect", "stream": name})
        ev.sort(key=lambda e: (e["t"], e["stream"], e["op"]))
        return ev

    @property
    def duration_s(self) -> float:
        return max(e["t"] for e in self.events())


def run_autoscale_soak(
    *, width: int = 128, height: int = 96, model: str = "tiny_yolov8",
    scrape_interval_s: float = 1.0,
    capacity_scrape_interval_s: float = 30.0,
    decision_interval_s: float = 1.0, spawn_horizon_s: float = 1800.0,
    surplus_headroom: float = 0.3, surplus_hold_s: float = 8.0,
    spawn_cooldown_s: float = 12.0, retire_cooldown_s: float = 60.0,
    capacity_fast_window_s: float = 5.0,
    storm_admission_bound_s: float = 12.0,
    shape: Optional[LoadShape] = None,
    workdir: Optional[str] = None,
) -> dict:
    """r19 autoscale soak: a :class:`~..serve.supervisor.FleetSupervisor`
    with a REAL subprocess spawner over a :class:`LoadShape` churn
    schedule — the ``AUTOSCALE_r01.json`` payload.

    Two members boot sequentially against a shared persistent AOT cache
    dir (m0 cold — it POPULATES the cache and the prewarm manifest; m1's
    identical prewarm set is already a persistent-cache hit). The
    supervisor's spawned member boots with NO ``--prewarm`` flags at
    all: its program set comes purely from the manifest, every compile a
    cache hit — the spawn path the r19 cache exists for.

    Gates:

    - ``scale_out_on_forecast`` / ``scale_out_beats_burn`` — the one
      spawn is reason ``saturation_forecast`` (the ramp's utilization
      slope crossed the horizon) and landed while fleet ``min_headroom``
      was still positive: capacity arrived BEFORE the burn, not after.
    - ``spawn_prewarm_from_manifest`` — the spawned member's
      ``/api/v1/stats`` prewarm block shows the manifest supplied (and
      it completed) every recorded program with the cache enabled.
    - ``spawn_first_frame_within_scrape`` — Popen→first-served-frame on
      the spawned member lands inside ONE capacity-forecast scrape
      interval (``capacity_scrape_interval_s``, the O(10 s) cadence a
      production fleet scrapes capacity at — distinct from the router's
      1 s liveness scrape): the member is serving before the forecast
      plane would even re-sample.
    - ``storm_admission_bounded`` — connect→first-frame p99 across the
      storm stays under ``storm_admission_bound_s``.
    - ``retire_on_surplus`` / ``no_flap`` — after the storm and ramp
      drain, sustained surplus retires exactly one member (drained via
      the r16 lineage-verified ``scale_in`` migration) and the member
      set neither re-spawns on the drain's utilization echo nor
      oscillates: one spawn, one retire, back at ``min_members``.
    - ``ledger_balanced`` — zero frames lost, zero duplicated across
      admission, storm churn, scale-out and the retire drain. Members
      prewarm every program they serve, so conservation holds from the
      very first frame of every stream with NO warmup exclusion.
    - ``supervisor_metrics_lint_clean`` — ``vep_supervisor_*`` is
      ``lint_exposition``-clean.

    Determinism levers carry over from :func:`run_router_soak` (pinned
    single bucket, prewarmed programs, ``--slo-off --ladder-slo-only``,
    shed staleness above the soak length, fps under the CPU tick rate);
    new here: ``capacity_fast_window_s`` shrinks the burn window to fit
    the soak's ramp, the supervisor's symmetric spawn cooldown outlasts
    it so the retire drain's slope echo cannot re-spawn, and
    ``retire_cooldown_s`` outlasts the whole churn schedule — the CPU
    twin's utilization never dents headroom, so the surplus BAR is held
    throughout and the cooldown is what makes "sustained surplus" mean
    "after the storm and ramp drained" instead of "the first quiet
    10 s" (on the real chip the bar itself does this work).

    CPU-only by construction (members start with ``JAX_PLATFORMS=cpu``):
    the gates are counts and host wall-clock, and N engines cannot share
    one chip.
    """
    import json as _json
    import itertools
    import shutil
    import subprocess
    import sys
    import tempfile
    import urllib.request

    import grpc

    from ..obs import registry as obs_registry
    from ..obs.metrics import lint_exposition
    from ..proto import pb, pb_grpc
    from ..serve.router import StreamRouter
    from ..serve.supervisor import FleetSupervisor

    shape = shape or LoadShape()
    tmp = workdir or tempfile.mkdtemp(prefix="vep_autoscale_")
    aot_dir = os.path.join(tmp, "aot_cache")
    bucket = 8
    specs = {s["stream"]: s for s in shape.specs()}
    tenant_models = sorted({s["model"] for s in shape.specs()
                            if s["model"]})

    stop = threading.Event()
    rx_lock = threading.Lock()
    first_rx: dict = {}          # stream -> monotonic of first delivery
    member_first_rx: dict = {}   # member -> monotonic of first frame served
    t_admit: dict = {}           # stream -> monotonic at admit()
    procs_by_name: dict = {}
    boots: dict = {}             # member -> {"boot_s", rest/grpc ports}
    spawn_info: dict = {}
    retire_info: dict = {}
    failures: list = []
    threads: list = []
    router: Optional[StreamRouter] = None
    sup: Optional[FleetSupervisor] = None

    def read_msg(proc, key, timeout_s=300.0):
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            line = proc.stdout.readline()
            if not line:
                raise SystemExit(
                    f"autoscale member died (rc={proc.poll()}); "
                    f"see {tmp}/*.stderr")
            try:
                msg = _json.loads(line)
            except ValueError:
                continue
            if key in msg:
                return msg
        raise SystemExit(f"autoscale member: no {key!r} within {timeout_s}s")

    def _boot_member(mname: str, *, prewarm: bool):
        """Popen → ready line; returns (base_url, grpc_port). With
        ``prewarm=False`` the member gets NO --prewarm flags: its
        program set must come from the shared AOT cache's manifest."""
        member_dir = os.path.join(tmp, mname)
        os.makedirs(member_dir, exist_ok=True)
        cmd = [
            sys.executable, "-m",
            "video_edge_ai_proxy_tpu.replay.harness",
            "--instance", mname, "--workdir", member_dir,
            "--model", model,
            "--spans-out", os.path.join(tmp, f"{mname}_spans.json"),
            "--serve-only", "--slo-off", "--ladder-slo-only",
            "--shed-staleness-ms", "600000",
            "--batch-bucket", str(bucket),
            "--capacity",
            "--capacity-fast-window", str(capacity_fast_window_s),
            "--aot-cache", aot_dir,
        ]
        if prewarm:
            cmd += ["--prewarm", f"{height}x{width}x{bucket}"]
            for mdl in tenant_models:
                cmd += ["--prewarm", f"{height}x{width}x{bucket}:{mdl}"]
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        t0 = time.monotonic()
        proc = subprocess.Popen(
            cmd, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=open(os.path.join(tmp, f"{mname}.stderr"), "w"),
            env=env, text=True)
        procs_by_name[mname] = proc
        msg = read_msg(proc, "ready")
        boots[mname] = {
            "boot_s": round(time.monotonic() - t0, 3),
            "rest_port": msg["rest_port"], "grpc_port": msg["grpc_port"],
            "prewarm_flags": prewarm,
        }
        return f"http://127.0.0.1:{msg['rest_port']}", msg["grpc_port"]

    def _start_client(mname: str, grpc_port: int) -> None:
        def _client():
            channel = grpc.insecure_channel(f"127.0.0.1:{grpc_port}")
            stub = pb_grpc.ImageStub(channel)
            while not stop.is_set():
                try:
                    for res in stub.Inference(pb.InferenceRequest()):
                        if stop.is_set():
                            break
                        if not res.device_id:
                            continue
                        now = time.monotonic()
                        router.ledger.note_delivery(
                            res.device_id, mname, res.frame_packet,
                            res.trace_id)
                        with rx_lock:
                            first_rx.setdefault(res.device_id, now)
                            member_first_rx.setdefault(mname, now)
                except grpc.RpcError:
                    if not stop.is_set():
                        time.sleep(0.25)
            channel.close()
        t = threading.Thread(target=_client, daemon=True,
                             name=f"autoscale-client-{mname}")
        threads.append(t)
        t.start()

    def _send_exit(mname: str) -> None:
        proc = procs_by_name.get(mname)
        if proc is None or proc.poll() is not None:
            return
        try:
            proc.stdin.write("exit\n")
            proc.stdin.flush()
            proc.stdin.close()
        except (BrokenPipeError, OSError):
            pass
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()   # by PID via Popen handle — never pkill

    try:
        for spec in shape.specs():
            record_synthetic_trace(
                os.path.join(tmp, f"{spec['stream']}.vtrace"),
                [spec["stream"]], width=width, height=height,
                fps=spec["fps"], gop=30, frames=int(spec["fps"] * 240))

        # m0 boots COLD (populates the persistent cache + manifest), m1
        # boots against the populated dir — sequentially, so m1's boot
        # time already shows the cache-hit delta.
        urls = {}
        for mname in ("m0", "m1"):
            urls[mname], _ = _boot_member(mname, prewarm=True)

        router = StreamRouter(
            [f"{m}={urls[m]}" for m in ("m0", "m1")],
            scrape_interval_s=scrape_interval_s,
            max_moves_per_pass=16,
            drain_timeout_s=5.0, drain_poll_s=0.5)
        router.run_pass()
        attach_errors = {k: v for k, v in router.attach().items() if v}
        for mname in ("m0", "m1"):
            _start_client(mname, boots[mname]["grpc_port"])
        router.start()

        admit_seq = itertools.count()

        def _admit(name: str) -> None:
            url = (f"replay://{tmp}/{name}.vtrace?device={name}"
                   "&pace=1&loop=0")
            t_admit[name] = time.monotonic()
            try:
                router.admit(name, url, priority=next(admit_seq),
                             inference_model=specs[name]["model"])
            except Exception as exc:  # noqa: BLE001 — gate, don't abort
                failures.append(f"admit {name}: {type(exc).__name__}: "
                                f"{exc}")

        events = shape.events()
        for ev in [e for e in events if e["t"] <= 0.0]:
            _admit(ev["stream"])
        base_names = [s["stream"] for s in shape.specs()
                      if s["phase"] == "base"]
        deadline = time.monotonic() + 180.0
        while time.monotonic() < deadline:
            with rx_lock:
                if all(n in first_rx for n in base_names):
                    break
            time.sleep(0.25)
        else:
            raise SystemExit("warmup: base streams never all delivered; "
                             f"see {tmp}/*.stderr")
        # Let the connect transient leave the fast burn window: the
        # supervisor must see the RAMP's slope, not the base warmup's.
        time.sleep(2.0 * capacity_fast_window_s)

        spawn_seq = itertools.count()

        def spawner():
            mname = f"a{next(spawn_seq)}"
            t0 = time.monotonic()
            url, grpc_port = _boot_member(mname, prewarm=False)
            _start_client(mname, grpc_port)
            # The manifest-driven prewarm block, captured at ready: the
            # spawned member must hold every recorded program with the
            # cache on — nothing left to compile on first dispatch.
            prewarm = None
            try:
                with urllib.request.urlopen(
                        f"{url}/api/v1/stats", timeout=5) as r:
                    prewarm = _json.loads(r.read())["engine"]["prewarm"]
            except Exception:  # noqa: BLE001 — gate reads None
                pass
            spawn_info[mname] = {
                "t_spawn": t0,
                "boot_s": round(time.monotonic() - t0, 3),
                "prewarm": prewarm,
            }
            return mname, url

        def retirer(mname: str) -> None:
            retire_info[mname] = {"t_retire": time.monotonic()}
            _send_exit(mname)

        sup = FleetSupervisor(
            router, spawner=spawner, retirer=retirer,
            min_members=2, max_members=3,
            decision_interval_s=decision_interval_s,
            spawn_horizon_s=spawn_horizon_s,
            surplus_headroom=surplus_headroom,
            surplus_hold_s=surplus_hold_s,
            spawn_cooldown_s=spawn_cooldown_s,
            retire_cooldown_s=retire_cooldown_s)
        sup.start()

        t0 = time.monotonic()
        for ev in [e for e in events if e["t"] > 0.0]:
            wait = t0 + ev["t"] - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            if ev["op"] == "connect":
                _admit(ev["stream"])
            else:
                router.remove_stream(ev["stream"])

        # The retire leg: sustained surplus after the drain.
        deadline = time.monotonic() + surplus_hold_s \
            + retire_cooldown_s + 60.0
        while time.monotonic() < deadline:
            if any(e["action"] == "retire" for e in list(sup.events)):
                break
            time.sleep(0.25)
        # Post-retire observation: long enough for a flap to show.
        time.sleep(max(4.0, 3.0 * decision_interval_s))
        sup.stop()
        sup_snapshot = sup.snapshot()
        router.stop()

        stop.set()
        for t in threads:
            t.join(timeout=10)
        balance = router.ledger.balance()

        spawns = [e for e in sup.events if e["action"] == "spawn"]
        retires = [e for e in sup.events if e["action"] == "retire"]
        spawned = spawns[0]["member"] if spawns else None
        spawn_first_frame_s = None
        if spawned and spawned in spawn_info:
            with rx_lock:
                served = member_first_rx.get(spawned)
            if served is not None:
                spawn_first_frame_s = round(
                    served - spawn_info[spawned]["t_spawn"], 3)
        spawn_prewarm = (spawn_info.get(spawned, {}).get("prewarm")
                        if spawned else None)

        storm_names = [s["stream"] for s in shape.specs()
                       if s["phase"] == "storm"]
        with rx_lock:
            storm_lat = sorted(
                round(first_rx[n] - t_admit[n], 3) for n in storm_names
                if n in first_rx and n in t_admit)
        storm_p99 = (storm_lat[max(0, min(len(storm_lat) - 1,
                     int(round(0.99 * (len(storm_lat) - 1)))))]
                     if storm_lat else None)

        exposition = obs_registry.render()
        lint_errors = lint_exposition(exposition)
        sup_families = sorted({
            line.split()[2] for line in exposition.splitlines()
            if line.startswith("# TYPE vep_supervisor_")})

        gates = {
            "attach_clean": not attach_errors,
            "scale_out_on_forecast": bool(spawns) and
                spawns[0]["reason"] == "saturation_forecast",
            "scale_out_beats_burn": bool(spawns) and
                (spawns[0].get("min_headroom") or 0.0) > 0.0,
            "spawn_prewarm_from_manifest": bool(
                spawn_prewarm and spawn_prewarm.get("aot_cache")
                and spawn_prewarm.get("complete")
                and spawn_prewarm.get("required", 0) >= 1
                + len(tenant_models)),
            "spawn_first_frame_within_scrape": (
                spawn_first_frame_s is not None
                and spawn_first_frame_s <= capacity_scrape_interval_s),
            "storm_admission_bounded": (
                len(storm_lat) == len(storm_names)
                and storm_p99 <= storm_admission_bound_s),
            "retire_on_surplus": bool(retires),
            "no_flap": (len(spawns) == 1 and len(retires) == 1
                        and len(router.clients) == 2),
            "ledger_balanced": balance["balanced"],
            "no_admission_errors": not failures,
            "supervisor_metrics_lint_clean": (
                not lint_errors and len(sup_families) >= 6),
        }
        return {
            "metric": f"autoscale_{shape.base_streams}b{shape.ramp_streams}"
                      f"r{shape.storm_streams}s_{model}",
            "pipeline": (
                "2 cold/warm members + FleetSupervisor (subprocess "
                "spawner, shared AOT prewarm cache) <- LoadShape "
                "ramp/storm/hot-spot/mixed-tenant churn <- per-member "
                "gRPC clients -> conservation ledger"),
            "model": model,
            "shape": {
                "base": shape.base_streams, "ramp": shape.ramp_streams,
                "storm": shape.storm_streams,
                "base_fps": shape.base_fps, "hot_fps": shape.hot_fps,
                "models": list(shape.models),
                "duration_s": shape.duration_s,
            },
            "config": {
                "scrape_interval_s": scrape_interval_s,
                "capacity_scrape_interval_s": capacity_scrape_interval_s,
                "decision_interval_s": decision_interval_s,
                "spawn_horizon_s": spawn_horizon_s,
                "surplus_headroom": surplus_headroom,
                "surplus_hold_s": surplus_hold_s,
                "capacity_fast_window_s": capacity_fast_window_s,
                "storm_admission_bound_s": storm_admission_bound_s,
                "bucket": bucket,
            },
            "gates": gates,
            "boots": boots,
            "spawn": {
                "member": spawned,
                "event": spawns[0] if spawns else None,
                "boot_s": spawn_info.get(spawned, {}).get("boot_s")
                if spawned else None,
                "first_frame_s": spawn_first_frame_s,
                "prewarm": spawn_prewarm,
            },
            "storm": {
                "streams": len(storm_names),
                "admitted_first_frame_s": storm_lat,
                "p99_s": storm_p99,
            },
            "retire": {
                "member": retires[0]["member"] if retires else None,
                "event": retires[0] if retires else None,
            },
            "ledger": {
                "balanced": balance["balanced"],
                "lost": balance["lost"],
                "duplicated": balance["duplicated"],
                "streams": balance["streams"],
            },
            "failures": failures,
            "lint_errors": lint_errors[:10],
            "supervisor_families": sup_families,
            "supervisor_snapshot": sup_snapshot,
        }
    finally:
        stop.set()
        if sup is not None:
            sup.stop()
        if router is not None:
            router.stop()
        for mname in list(procs_by_name):
            _send_exit(mname)
        for proc in procs_by_name.values():
            if proc.poll() is None:
                proc.kill()   # by PID via Popen handle — never pkill
        if workdir is None:
            shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    _fleet_member_main()
