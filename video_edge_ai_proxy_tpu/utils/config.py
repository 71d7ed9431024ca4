"""Framework configuration.

Schema parity with the reference's YAML config (``server/globals/config.go:28-64``
and documented defaults in ``server/main.go:50-88``): the reference has
``redis``/``annotation``/``api``/``buffer`` sub-configs; we keep the same
capability surface but rename ``redis`` -> ``bus`` (the frame bus here is a
native shared-memory ring, not Redis) and add an ``engine`` sub-config for the
TPU inference plane, which has no counterpart in the reference (it ships frames
to external CPU clients instead).

Precedence matches the reference (``server/main.go:50-88``): config file if
present, else compiled-in defaults; selected fields are force-overridden (the
reference pins the REST port to 8080 at ``server/main.go:82``).
"""

from __future__ import annotations

import dataclasses
import os
from dataclasses import dataclass, field
from typing import Any, Optional

import yaml

DEFAULT_CONFIG_PATH = "/data/chrysalis/conf.yaml"


@dataclass
class BusConfig:
    """Frame-bus connection (reference ``RedisSubconfig``, ``config.go:28-35``)."""

    backend: str = "shm"  # "shm" (native ring) | "redis" (reference-wire
    #                        interop) | "memory" (in-proc, tests)
    # Directory holding the shared-memory segments (one per camera + control KV).
    shm_dir: str = "/dev/shm/vep_tpu"
    # Redis server for backend "redis" (reference ``RedisSubconfig``
    # connection/database/password, ``config.go:28-35``).
    redis_addr: str = "127.0.0.1:6379"
    redis_password: str = ""
    redis_db: int = 0
    # Ring capacity per camera in frames; reference default is 1 in-memory frame
    # (``server/main.go:74``, latest-frame-wins semantics).
    ring_slots: int = 4


@dataclass
class AnnotationConfig:
    """Annotation uplink batching (reference ``AnnotationSubconfig``,
    ``config.go:37-46``; defaults from ``server/main.go:59-64``)."""

    endpoint: str = "https://event.chryscloud.com/api/v1/annotate"
    unacked_limit: int = 1000
    poll_duration_ms: int = 300
    max_batch_size: int = 299
    # Dead-letter spool for batches that exhaust uplink retries
    # (resilience/spool.py): "" = <data_dir>/annotation_spool.
    spool_dir: str = ""
    spool_max_bytes: int = 64 << 20


@dataclass
class ApiConfig:
    """Cloud REST endpoint (reference ``ApiSubconfig``, ``config.go:48-52``)."""

    endpoint: str = "https://api.chryscloud.com"


@dataclass
class BufferConfig:
    """Frame buffering (reference ``BufferSubconfig``, ``config.go:54-64``)."""

    in_memory: int = 1
    on_disk: bool = False
    on_disk_folder: str = "/data/chrysalis/archive"
    on_disk_clean_older_than: str = "5m"
    on_disk_schedule: str = "@every 5m"


@dataclass
class EngineConfig:
    """TPU inference plane (new; no reference counterpart — see SURVEY.md §7)."""

    model: str = "yolov8n"
    # Bucketed batch sizes to avoid XLA recompilation storms when streams
    # come and go (SURVEY.md §7 hard part 1).
    # 64 included: XLA's schedule at bs64 is ~3x better per frame than bs16
    # on v5e (measured), so large camera fleets get the good bucket.
    batch_buckets: tuple[int, ...] = (1, 2, 4, 8, 16, 32, 64)
    # Collector tick deadline: stack whatever arrived, pad to bucket, go.
    tick_ms: int = 10
    # Seconds of client inactivity after which a stream drops out of the
    # device batch (mirrors the reference's 10 s decode gate,
    # ``python/rtsp_to_rtmp.py:144-145``).
    active_window_s: float = 10.0
    dtype: str = "bfloat16"
    # Mesh shape for multi-chip serving; empty = single chip. The string
    # "auto" serves data-parallel over every visible device (dp-heavy
    # factoring — a fleet operator needs no hand-written shape).
    mesh: "dict[str, int] | str" = field(default_factory=dict)
    # msgpack params checkpoint; empty = random init (no pretrained weights
    # are bundled). Loaded at warmup so restart = load + compile cache.
    checkpoint_path: str = ""
    # Persistent XLA compile cache (SURVEY.md §5.4: "warmup = load +
    # compile-cache"): big serving programs take tens of seconds to
    # minutes to compile; with a cache dir a restarted server skips
    # recompiling every (geometry, bucket) program it has seen. "" = off
    # (jax default); "auto" = the server resolves <data_dir>/compile_cache.
    # Where JAX_COMPILATION_CACHE_DIR is set the cache stays there and
    # this directory is not used (utils/compile_cache.py).
    compile_cache_dir: str = ""
    # Geometries to compile at boot instead of on first frame: list of
    # [height, width, bucket] or [height, width, bucket, model] (the
    # 4-element form prewarms a non-default registry model's program —
    # multi-family fleets otherwise hit the compile stall mid-soak on the
    # first frame of each extra model). Big programs (e.g. ViT at bucket
    # 32) can take minutes to compile; prewarming moves that cost out of
    # the hot path.
    prewarm: list = field(default_factory=list)
    # H2D prefetch stage (ROADMAP item 5): batch placement runs as a real
    # async jax.device_put on a dedicated transfer thread so the copy of
    # batch t+1 overlaps device compute for batch t (double-buffered: at
    # most 2 placements outstanding, matching the depth-2 drain
    # pipeline). False = legacy synchronous placement on the tick thread.
    prefetch: bool = True
    # Donate the frames argument to the compiled step (jax donate_argnums)
    # so XLA may reuse the input HBM slot. "auto" = donate where the
    # donation can be taken: a TPU program partitioned over engine.mesh
    # (XLA buffer donor). On one chip, and on the CPU test backend, jit
    # can only drop it with a warning (no output shares the uint8 frame
    # plane's shape), so "auto" does not ask. "on"/"off" force.
    donate_frames: str = "auto"
    # /healthz flags the engine loop wedged when no tick completed for this
    # long. Must exceed the longest legitimate in-tick XLA compile (first
    # frame of a new geometry compiles inside the tick) or a k8s liveness
    # probe would restart the pod mid-warmup in a loop.
    health_stale_after_s: float = 300.0
    # Annotation emit policy. At north-star rates (16 streams x 30 fps x
    # a few detections) one AnnotateRequest per detection per frame
    # outruns the uplink drain budget (299 per 300 ms, reference
    # main.go:59-64) and sheds on the floor; the reference never hits
    # this because CLIENTS choose what to annotate (examples/
    # annotation.py). Policies: "all" (reference-client firehose),
    # "keyframe" (GOP heads only), "on_change" (default: emit when the
    # tracked object set changes or a confidence moves more than
    # annotation_confidence_delta), "min_interval" (at most one frame's
    # annotations per annotation_min_interval_ms). Per-stream override:
    # StreamProcess.annotation_policy.
    annotation_emit: str = "on_change"
    annotation_min_interval_ms: int = 1000
    annotation_confidence_delta: float = 0.15
    # "int8" = weight-only post-training quantization of serving params
    # (models/quantize.py): int8 device/HBM residency (checkpoints stay
    # full precision on disk), bf16 compute,
    # dequantize fused in-graph. "" = full precision.
    # "int8_act" (round 15, detect family only) = the above PLUS int8
    # activation compute: a calibration pass over synthetic frames at
    # warmup observes per-conv input ranges, then every conv except the
    # stem and head out-convs runs int8 x int8 on the MXU
    # (models/common.py _Int8Conv). Accuracy-gated by the tolerance
    # committed in tools/bench_levers.py.
    quantize: str = ""
    # Detect-family stem variant (round 15). "classic" (default) = stock
    # stride-2 3x3 stem, replay checksums bit-identical to prior rounds.
    # "s2d" = space-to-depth: the fused letterbox+s2d preprocess
    # (ops/preprocess.py preprocess_letterbox_fused) reads the 1080p
    # plane once and feeds a 320²x12 plane to a stride-1 2x2 stem;
    # classic checkpoints fold in losslessly at load
    # (models/import_weights.py s2d_fold_kernel). Non-detect models
    # ignore this.
    stem: str = "classic"
    # Fill Detection.track_id / AnnotateRequest.object_tracking_id with a
    # per-stream SORT-style tracker (engine/tracker.py). Host-side numpy on
    # NMS output — negligible next to a device batch.
    track: bool = True
    # One record per emitted result, carrying its batch's trace (tick and
    # batch number, wall stamps from tick start to emit, the collector's
    # phase seconds and byte counts), appended to engine.stage_records,
    # bounded. Off in production; benchmark/vbench/spans.py and
    # batch_trace.py (the per-layer metrics) and replay/harness.py read it.
    stage_trace: bool = False
    # End-to-end latency (bus publish -> result emit) above this increments
    # vep_frames_late_total for the stream (obs/watch.py episode checks key
    # off the same number).
    obs_late_ms: float = 1000.0
    # Overload degradation ladder (resilience/ladder.py): normal -> shed
    # stale frames -> cap the batch bucket one size down -> pause
    # admission for half the streams. Driven by drain-queue depth and
    # tick staleness; escalates after ladder_escalate_after_s of
    # continuous pressure, recovers one rung per ladder_recover_after_s
    # pressure-free. False = never degrade (old behavior: latency grows).
    ladder: bool = True
    ladder_escalate_after_s: float = 0.5
    ladder_recover_after_s: float = 2.0
    # Rung 1 (shed): frames older than this at dispatch are dropped
    # oldest-first instead of occupying device batch slots.
    shed_staleness_ms: float = 500.0
    # Live SLOs (obs/slo.py): p50 detect latency, aggregate fps, stream
    # availability, each evaluated as multi-window burn rate (fast 5 m /
    # slow 1 h). slo_warmup_s gates firing until that much wall time has
    # been observed (also keeps short CPU test runs from tripping the
    # fps objective, unreachable off-chip). slo_ladder feeds sustained
    # burn into the degradation ladder as extra pressure.
    slo: bool = True
    slo_latency_ms: float = 40.0
    slo_target_fps: float = 1000.0
    slo_warmup_s: float = 60.0
    slo_availability_window_s: float = 5.0
    slo_eval_interval_s: float = 1.0
    slo_ladder: bool = True
    # Triggered device profiling (obs/prof.py): duration-bounded
    # jax.profiler captures on demand (/api/v1/profile?ms=N, gRPC admin
    # mirror) and fired automatically once per SLO episode / ladder
    # escalation, written as self-contained bundles (device trace +
    # lineage-span window + perf/SLO snapshot) into a byte-bounded
    # retention ring. prof=False disables the subsystem and the REST
    # endpoint answers 400 (same kill-switch convention as slo above).
    prof: bool = True
    prof_dir: str = ""                 # "" = <tempdir>/vep_prof (server
                                       # wires <data_dir>/prof instead)
    # Trigger-driven capture is OPT-IN: the serving process forks camera
    # workers (process manager restarts, soak chaos), and jax's profiler
    # segfaults when a trace overlaps a fork (observed: tools/soak.py
    # chaos run, SIGSEGV the tick a ladder escalation fired a capture).
    # Arm it where the engine runs fork-free (replay soaks via
    # --profile-on-burn) or the operator isolates the engine process.
    prof_trigger: bool = False         # auto-capture on burn/escalation
    prof_trigger_ms: int = 500         # duration of triggered captures
    prof_trigger_min_interval_s: float = 60.0  # rate limit between them
    prof_retention_bytes: int = 256 << 20      # ring bound, oldest evicted
    prof_max_ms: int = 10_000          # cap on ?ms= (400 above this)
    # Output-quality observability (obs/quality.py): device-computed
    # per-frame luma mean/variance + inter-frame diff energy folded into
    # the serving step (ops/preprocess.py frame_quality_stats; under
    # engine.mesh the thumbnail carry state is dp-sharded per slice —
    # engine/stream_state.py _ShardedThumbPool — so quality rides the
    # mesh path too), host black/frozen/flatline verdict state machines
    # with time hysteresis, detection drift scoring, and the degradation
    # ladder's first-shed set. quality=False disables the subsystem and
    # /api/v1/quality answers 400 (same kill-switch convention as
    # slo/prof above).
    quality: bool = True
    quality_thumb: int = 32            # luma thumbnail side (device state)
    quality_black_luma: float = 0.04   # black: thumb luma mean below this
    quality_black_var: float = 5e-4    #   ... AND luma variance below this
    quality_freeze_diff: float = 1e-6  # frozen: inter-frame MSE below this
    quality_enter_s: float = 2.0       # condition must hold this long
    quality_exit_s: float = 2.0        # all-clear must hold this long
    quality_flatline_s: float = 10.0   # zero detections for this long
    quality_window_s: float = 5.0      # drift scoring window
    quality_drift_threshold: float = 0.35
    quality_ladder: bool = True        # black/frozen streams shed first
    # Spatially-multiplexed ROI serving (MOSAIC, arxiv 2305.03222;
    # ROADMAP item 1). Each tick, detect streams are motion-gated from
    # the previous tick's device thumbnail diff energy (quality plane)
    # plus IoUTracker state: "idle" streams (no motion, no live tracks
    # past coasting) skip device work entirely and emit tracker-coasted
    # results; "tracked" streams contribute crops around their predicted
    # track boxes, shelf-packed with crops from other streams onto a few
    # shared side×side canvases (engine/collector.py CanvasPacker) that
    # run through the SAME (geometry, bucket) step cache; "active"
    # streams (fresh motion / refresh cadence due / no diff signal yet)
    # run the classic full frame. Detections scatter back from canvas to
    # per-stream frame coordinates via exact per-crop inverse affines
    # (ops/boxes.py uncrop_boxes). roi=False (default) is the kill
    # switch: every batch takes today's full-frame path bit-identically
    # (test-pinned).
    roi: bool = False
    roi_canvas: int = 640              # shared canvas side (geometry)
    roi_gap: int = 8                   # background px between packed crops
    roi_max_canvases: int = 8          # per tick; overflow crops go full
    roi_margin: float = 0.25           # track-box inflation for crops
    roi_min_crop: int = 32             # minimum crop side before packing
    # Streams whose thumbnail diff energy (inter-frame MSE of [0,1] luma)
    # stays below this are motionless; with no live tracks they gate to
    # idle, with tracks they serve from crops only. ~50x the freeze
    # detector's quality_freeze_diff floor: "no scene change worth a
    # full frame", not "pixel-identical".
    roi_idle_diff: float = 5e-5
    # Full-frame refresh cadence per stream: catches objects appearing
    # outside every tracked ROI and refreshes the diff-energy signal
    # (quality stats only ride full-frame slots — crops would alias the
    # thumbnail). Also the bound on how stale a gated stream's scene
    # model can get.
    roi_full_interval_ms: int = 1000
    # Coasted-emission confidence decay per missed frame; a coasted track
    # below roi_coast_floor stops being emitted (the track itself still
    # expires via IoUTracker.max_misses).
    roi_coast_decay: float = 0.9
    roi_coast_floor: float = 0.1
    # Canary integrity loop: a golden trace (recorder.py) replayed into
    # the live engine at low cadence by an engine-owned publisher; each
    # completed loop's host result checksums fold and compare against the
    # golden (0 = adopt the first complete cycle), feeding the
    # canary_integrity SLO + watchdog. "" = no canary.
    quality_canary: str = ""           # trace path ("" = off)
    quality_canary_stream: str = "_canary"
    quality_canary_fps: float = 2.0
    quality_canary_golden: int = 0     # committed fold; 0 = record-only
    # Temporal cascade serving (CASCADE, temporal/): the detect megastep
    # runs every tick unchanged; tracked detections' crops accumulate in
    # a device-resident per-track clip ring and the temporal head
    # (cascade_model + a logistic anomaly scorer over pooled clip
    # features) runs every cascade_every_n ticks as its own bucketed
    # program. Requires track=True (state is keyed by track id).
    # cascade=False (default) is the kill switch: every batch takes
    # today's stateless path bit-identically (test-pinned, same
    # convention as roi=False / stem="classic").
    cascade: bool = False
    cascade_every_n: int = 4           # temporal-head cadence (ticks)
    cascade_model: str = "videomae_b"  # registry video model for the head
    cascade_crop: int = 0              # track tile side; 0 = model input
    cascade_clip_len: int = 0          # ring depth; 0 = model clip_len
    # Event hysteresis (temporal/events.py): score >= threshold for
    # enter_n consecutive head passes fires "enter"; < threshold for
    # exit_n fires "exit". Counts, not seconds — observations are
    # cadence-quantized.
    cascade_threshold: float = 0.5
    cascade_enter_n: int = 2
    cascade_exit_n: int = 2
    # Logistic scorer over pooled clip features [temporal diff energy
    # (mean |luma diff| between consecutive frames), clip luma variance,
    # max head softmax prob]: score = sigmoid(w . f + b). Defaults make
    # a pixel-static clip score sigmoid(b) ~= 0.018 and saturate on
    # appearance change; the VideoMAE logits ride the event payload.
    cascade_score_w: tuple = (2000.0, 0.0, 0.0)
    cascade_score_b: float = -4.0
    # Ticks without a harvested detection before a track's device slot
    # frees (IoUTracker coasts max_misses=30 frames first, so this fires
    # only after the tracker itself dropped the track).
    cascade_track_ttl_ticks: int = 60
    # Capacity attribution plane (obs/capacity.py): per-stream
    # device-time ledger (every measured batch amortized back to its
    # occupant streams, conservation-gated), per-(model, geometry,
    # bucket) utilization rings with an EWMA-slope time_to_saturation_s
    # forecast, and SRE-style fast/slow capacity burn rates — the
    # headroom signal obs/fleet.py merges and StreamRouter.admit()
    # consults. capacity=False (default) is the kill switch: no tap in
    # the emit path, /api/v1/capacity answers 400, and serving stays
    # bit-identical (test-pinned, roi=False / cascade=False convention).
    capacity: bool = False
    capacity_fast_window_s: float = 60.0     # fast burn window
    capacity_slow_window_s: float = 1800.0   # slow burn window (30 m)
    # Sustainable tick-budget utilization: burn rate = utilization over
    # this; burning when BOTH windows exceed 1.0 (SRE multi-window).
    capacity_util_objective: float = 0.8
    capacity_eval_interval_s: float = 1.0    # forecast refresh throttle
    # HBM attribution plane (obs/hbm.py, r21): the memory mirror of the
    # capacity plane — per-(model, stem, geometry, bucket, mesh) compiled
    # program footprints (memory_analysis() at the step-cache-miss site,
    # donated aliasing credited), live register_pool byte ledgers for
    # thumb/track-state/prefetch/collector pools, and an EWMA byte-slope
    # time_to_oom_s forecast against the device budget that feeds the
    # resilience ladder, StreamRouter._pick_admission, and the
    # supervisor. hbm=False (default) is the kill switch: no compile tap,
    # no pool callables, /api/v1/hbm answers 400, serving bit-identical
    # (test-pinned, capacity=False convention).
    hbm: bool = False
    # 0 = auto: device.memory_stats()["bytes_limit"] on the real TPU,
    # obs/hbm.py DEFAULT_SYNTHETIC_BUDGET_BYTES (4 GiB) on the CPU twin
    # which reports no memory stats. Nonzero pins a synthetic budget
    # (tests/soaks shrink it to make the forecast bite).
    hbm_budget_bytes: int = 0
    hbm_fast_window_s: float = 60.0          # fast high-water window
    hbm_slow_window_s: float = 1800.0        # slow high-water window
    # Sustainable HBM utilization: burn = window-peak util over this.
    # Higher than the capacity objective (0.8) — memory is a level, and
    # a level parked at 85% is fine where a rate at 85% is not.
    hbm_util_objective: float = 0.9
    hbm_eval_interval_s: float = 1.0         # forecast refresh throttle
    # OOM forecast inside this horizon => pressure() true => the engine
    # feeds hbm_pressure into the resilience ladder (shed before the
    # allocator fails, not after).
    hbm_pressure_horizon_s: float = 120.0
    # Persistent AOT prewarm cache (r19, engine/aot_cache.py).
    # compile_cache_dir above makes a RESTART cheap; this makes a fresh
    # SPAWN cheap: the cache dir carries a versioned prewarm manifest
    # recording every (model, stem, geometry, bucket) serving step this
    # member (or any sibling sharing the dir) ever compiled, and a
    # booting engine replays the whole set before taking traffic — each
    # a persistent-cache hit, so spawn→first-served-frame fits inside
    # one router scrape interval (ROADMAP item 4). aot_cache=False
    # (default) is the kill switch: no manifest read/write, no extra
    # compile-cache wiring, serving bit-identical (test-pinned).
    aot_cache: bool = False
    # "" with aot_cache=True -> the server resolves <data_dir>/aot_cache
    # (shared across members via a common data volume); also becomes the
    # XLA persistent cache dir for this member (overrides
    # compile_cache_dir so manifest and payload travel together) unless
    # JAX_COMPILATION_CACHE_DIR places the payload elsewhere.
    aot_cache_dir: str = ""
    # Device-fault domain (engine/fault.py, r22): per-dispatch deadline/
    # error watchdog over the dp-sharded megastep — a shard whose program
    # raises (XLA error) or whose drain fetch overruns
    # fault_dispatch_deadline_ms for fault_hysteresis consecutive batches
    # is declared faulted, and the engine executes a bounded-time
    # failover: survivor mesh rebuild, AOT-warm recompile, deterministic
    # rendezvous stream re-pin, counted-reset state evacuation — all
    # proven frame-conserving by the FaultLedger (/api/v1/faults).
    # fault=False (default) is the kill switch: no watchdog, no ledger
    # taps, /api/v1/faults answers 400, serving bit-identical
    # (test-pinned).
    fault: bool = False
    # Drain fetch (submit -> host numpy) slower than this is one deadline
    # overrun; fault_hysteresis consecutive overruns open a stall
    # suspicion (then the per-shard probe attributes it, or not).
    fault_dispatch_deadline_ms: float = 5000.0
    fault_hysteresis: int = 2
    # Wall-clock budget for one failover (mesh rebuild through first
    # survivor program recorded); overruns are surfaced, not aborted —
    # half a failover is strictly worse than a slow one.
    fault_failover_budget_ms: float = 30000.0
    # Per-shard health probe (stall attribution): a tiny device
    # round-trip per shard lead device, failed/overrun => faulted.
    fault_probe_timeout_ms: float = 2000.0
    # Control-plane decision journal (obs/journal.py, r23): bounded ring
    # of causally-linked audit events from every autonomous loop
    # (ladder, shed, cascade stretch, failover, router, supervisor),
    # served at /api/v1/journal + /api/v1/why. Default ON — recording is
    # a pure side effect off the per-frame path; journal=False is the
    # kill switch: no hooks, /api/v1/journal answers 400, replay
    # bit-identical (test-pinned, fault=False convention).
    journal: bool = True
    journal_capacity: int = 4096       # ring slots (events retained)
    # Cascade cadence stretch under pressure (r23): while the
    # degradation ladder sits at shed or deeper, the temporal head's
    # dispatch cadence multiplies by this factor (every_n * stretch
    # ticks), shedding head FLOPs before streams are shed to the fleet.
    # Factor 1 disables the mechanism; stretch only ever engages on a
    # rung transition, so rung=normal serving is bit-identical.
    cascade_stretch_factor: int = 2


@dataclass
class ObsConfig:
    """Observability plane (obs/): frame-lineage tracing knobs. Metrics
    (obs/metrics.py) are always on — one counter add per event; tracing is
    opt-in because span dicts allocate."""

    trace: bool = False       # record sampled per-frame lineage spans
    sample_every: int = 16    # trace 1-in-N frames (deterministic, by
                              # packet id, so spans join into lineages)
    trace_ring: int = 1024    # span events buffered per stream
    # Fleet telemetry plane (r14). instance: this member's identity; when
    # nonempty it is rendered as a constant label on every /metrics
    # sample (Registry.set_const_labels) so merged expositions stay
    # attributable. fleet_members: "name=http://host:port" specs; when
    # nonempty this process also runs a FleetAggregator and serves
    # /api/v1/fleet/stats + /api/v1/fleet/metrics.
    instance: str = ""
    fleet_members: tuple = ()
    fleet_scrape_s: float = 2.0
    fleet_stale_s: float = 0.0   # 0 -> one scrape interval


@dataclass
class RouterConfig:
    """Fleet router tier (r16, serve/router.py): consistent-hash stream
    placement across engine members + burn-driven live migration. Only
    the dedicated router process reads this block (``python -m
    video_edge_ai_proxy_tpu.serve.router``); engine members need nothing
    beyond their normal REST surface — the router attaches to them."""

    members: tuple = ()             # "name=http://host:port" specs
    port: int = 9091                # router admin plane (/metrics, stats)
    scrape_interval_s: float = 1.0  # health poll + decision-pass cadence;
                                    # bounds re-placement latency
    vnodes: int = 64                # virtual nodes per member at weight 1
    max_moves_per_pass: int = 2     # graceful-migration budget per pass
                                    # (dead-member failover is unbounded)
    min_healthy_age_s: float = 0.0  # keep a freshly-healthy member out of
                                    # the ring until its verdict has aged
    drain_timeout_s: float = 8.0    # max wait for the source engine to
                                    # flush a stream before cutover
    ema_alpha: float = 0.4          # health-score smoothing (obs/fleet.py)
    healthy_above: float = 0.7      # hysteresis band: healthy at/above
    unhealthy_below: float = 0.4    # ... unhealthy at/below; hold between


@dataclass
class SupervisorConfig:
    """Autoscaling supervisor (r19, serve/supervisor.py): closes the
    loop from the r18 capacity forecast to member lifecycle. Watches the
    router's merged fleet health, spawns a member when the fleet-wide
    ``time_to_saturation_s`` forecast crosses the horizon, retires the
    emptiest member (drained through the r16 lineage-verified migration)
    after a sustained headroom surplus, and holds min/max bounds with
    cooldown hysteresis so a connect/disconnect storm cannot flap the
    fleet. enabled=True in server mode (serve/server.py) runs the loop
    in-process over ``router.members`` — advisory (no spawner is
    configurable from YAML; decisions surface in /api/v1/supervisor and
    the vep_supervisor_* families for the deployment system to act on;
    the acting mode lives in the autoscale harness). enabled=False
    (default) is the kill switch: no decision thread,
    /api/v1/supervisor answers 400 (r9 convention)."""

    enabled: bool = False
    min_members: int = 1
    max_members: int = 4
    decision_interval_s: float = 2.0  # forecast poll + decision cadence
    # Scale out when the merged fleet forecast says saturation lands
    # within this many seconds (the rung ABOVE shed_to_fleet: shedding
    # moves load across members, this adds a member).
    spawn_horizon_s: float = 120.0
    # Scale in only after min(headroom) across members has stayed above
    # surplus_headroom for surplus_hold_s straight (sustained surplus,
    # not a lull between storm waves).
    surplus_headroom: float = 0.6
    surplus_hold_s: float = 30.0
    spawn_cooldown_s: float = 10.0    # min gap between spawns
    retire_cooldown_s: float = 30.0   # min gap between retires (and
                                      # after any spawn — no flap)


@dataclass
class RunnerConfig:
    """Worker isolation runner (SURVEY.md §7.5 "subprocess first, Docker
    optional"). "subprocess": RLIMIT_AS + niceness containment (default).
    "container": one container per camera via the docker/podman CLI with
    the reference's HostConfig vocabulary — cgroup CPU weight, kernel
    memory limits, runtime log rotation, restart-always
    (rtsp_process_manager.go:70-115; serve/container.py)."""

    kind: str = "subprocess"     # subprocess | container
    image: str = "vep-tpu-worker"  # worker image (container kind)
    binary: str = "docker"       # docker | podman
    memory_mb: int = 2048        # cgroup memory limit per camera
    cpu_shares: int = 1024       # reference CPUShares parity (:78)
    network: str = "host"        # host: shm bus + loopback Redis work


@dataclass
class Config:
    version: str = "0.1.0"
    title: str = "video-edge-ai-proxy-tpu"
    description: str = "TPU-native video edge AI proxy"
    mode: str = "release"
    port: int = 8080
    grpc_port: int = 50001
    # Worker re-adoption across server restarts (reference parity: camera
    # containers keep running under dockerd through a control-plane restart
    # and are re-attached on boot, rtsp_process_manager.go:191-233). True:
    # workers log to <data_dir>/worker_logs, survive server death, and
    # resume() re-adopts them; false: workers pipe to the server, die with
    # it, resume = respawn.
    worker_adoption: bool = True
    bus: BusConfig = field(default_factory=BusConfig)
    runner: RunnerConfig = field(default_factory=RunnerConfig)
    annotation: AnnotationConfig = field(default_factory=AnnotationConfig)
    api: ApiConfig = field(default_factory=ApiConfig)
    buffer: BufferConfig = field(default_factory=BufferConfig)
    engine: EngineConfig = field(default_factory=EngineConfig)
    obs: ObsConfig = field(default_factory=ObsConfig)
    router: RouterConfig = field(default_factory=RouterConfig)
    supervisor: SupervisorConfig = field(default_factory=SupervisorConfig)


def _merge(dc: Any, data: dict[str, Any]) -> Any:
    """Overlay a dict onto a dataclass, recursing into nested dataclasses."""
    kwargs: dict[str, Any] = {}
    for f in dataclasses.fields(dc):
        if f.name not in data:
            continue
        cur = getattr(dc, f.name)
        val = data[f.name]
        if dataclasses.is_dataclass(cur) and isinstance(val, dict):
            kwargs[f.name] = _merge(cur, val)
        elif isinstance(cur, tuple) and isinstance(val, list):
            kwargs[f.name] = tuple(val)
        else:
            kwargs[f.name] = val
    return dataclasses.replace(dc, **kwargs)


def load_config(path: Optional[str] = None) -> Config:
    """Load config: explicit path > $VEP_TPU_CONF > default path > defaults.

    Like the reference (``server/main.go:50-88``), a missing file is not an
    error — compiled-in defaults are used, and the REST port is pinned.
    """
    cfg = Config()
    candidate = path or os.environ.get("VEP_TPU_CONF") or DEFAULT_CONFIG_PATH
    if candidate and os.path.isfile(candidate):
        with open(candidate, "r", encoding="utf-8") as fh:
            data = yaml.safe_load(fh) or {}
        if not isinstance(data, dict):
            raise ValueError(f"config root must be a mapping: {candidate}")
        cfg = _merge(cfg, data)
    return cfg
