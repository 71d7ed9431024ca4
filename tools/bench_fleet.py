"""Multi-model fleet serving benchmark (VERDICT r3 next #3).

The realistic fleet shape per-stream model overrides exist for: one engine,
16 cameras split across heterogeneous models (detection + re-ID embedding +
tagging). The reference got this shape for free — every gRPC client brought
its own model (`/root/reference/server/grpcapi/grpc_api.go:133-235`); the
consolidated on-TPU engine must show it doesn't regress it.

Two legs, both recorded:

A. Device capacity (bench.py methodology): per-model scan-folded serving
   step at the fleet's bucket split -> device ms per tick = sum over
   models; fleet aggregate fps vs the single-model number at the same
   total stream count. The step alone, host dispatch amortized out.

B. The real engine loop (functional + host orchestration): 16 synthetic
   cameras on the in-proc bus, per-stream model resolver, stage_trace on.
   Reports programs compiled (step-cache pressure), per-group
   collect->submit p50 (orchestration overhead), bucket padding waste,
   and the frames the loop served per wall second.

Both legs time the device: run it on the TPU. The record stamps
``backend`` and ``device_kind``; a CPU run is a rehearsal of the control
flow and its numbers are not device metrics.

    python tools/bench_fleet.py --record FLEET.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The fleet split: model -> number of streams. 16 total = the north-star
# stream count, split across the three serving families.
DEFAULT_FLEET = {"yolov8n": 6, "resnet50": 5, "vit_b16": 5}


def _buckets_for(n: int, buckets=(1, 2, 4, 8, 16)) -> list:
    """How the collector actually packs n same-geometry streams: full
    max-bucket chunks, then the tail padded to the smallest bucket that
    fits (collector.py pad_to_bucket semantics)."""
    out = []
    remaining = n
    mx = max(buckets)
    while remaining >= mx:
        out.append(mx)
        remaining -= mx
    if remaining:
        out.append(next(b for b in sorted(buckets) if b >= remaining))
    return out


def device_leg(fleet: dict, src_hw, iters: int) -> dict:
    import jax
    import jax.numpy as jnp

    from bench import timed_best
    from video_edge_ai_proxy_tpu.engine.runner import build_serving_step
    from video_edge_ai_proxy_tpu.models import registry

    rng = np.random.default_rng(0)
    per_model = {}
    total_ms = 0.0
    for name, streams in fleet.items():
        spec = registry.get(name)
        model, variables = spec.init_params(jax.random.PRNGKey(0))
        step = build_serving_step(model, spec)
        buckets = _buckets_for(streams)
        model_ms = 0.0
        bucket_ms = {}
        for bucket in sorted(set(buckets)):
            if spec.clip_len:
                shape = (bucket, spec.clip_len) + tuple(src_hw) + (3,)
            else:
                shape = (bucket,) + tuple(src_hw) + (3,)
            base_dev = jax.device_put(
                rng.integers(0, 256, shape, dtype=np.uint8))
            # Params go in as an ARGUMENT, not a closure: closed-over
            # trees bake into the program as constants (ViT-B/16 f32 is
            # ~344 MB of them, compiled and cached with the program).
            v_dev = jax.device_put(variables)

            @jax.jit
            def megastep(v, base_u8, _step=step):
                def body(carry, i):
                    out = _step(v, base_u8 + i.astype(jnp.uint8))
                    leaf = out.get("valid",
                                   next(iter(out.values())))
                    return carry + jnp.sum(leaf).astype(jnp.float32), None

                total, _ = jax.lax.scan(
                    body, jnp.zeros((), jnp.float32), jnp.arange(iters))
                return total

            np.asarray(megastep(v_dev, base_dev))     # compile + warm
            elapsed, _ = timed_best(
                lambda m=megastep, v=v_dev, b=base_dev: m(v, b))
            bucket_ms[bucket] = elapsed / iters * 1000.0
        for bucket in buckets:
            model_ms += bucket_ms[bucket]
        per_model[name] = {
            "streams": streams,
            "groups": buckets,
            "bucket_ms": {str(k): round(v, 3) for k, v in bucket_ms.items()},
            "tick_device_ms": round(model_ms, 3),
        }
        total_ms += model_ms
    n_streams = sum(fleet.values())
    return {
        "per_model": per_model,
        "tick_device_ms_total": round(total_ms, 3),
        "fleet_fps": round(n_streams / (total_ms / 1000.0), 1),
    }


def single_model_leg(model: str, n_streams: int, src_hw, iters: int) -> dict:
    out = device_leg({model: n_streams}, src_hw, iters)
    return {
        "model": model,
        "tick_device_ms": out["tick_device_ms_total"],
        "fps": out["fleet_fps"],
    }


def engine_leg(fleet: dict, src_hw, duration_s: float, tick_ms: int) -> dict:
    import threading

    from video_edge_ai_proxy_tpu.bus import FrameMeta
    from video_edge_ai_proxy_tpu.bus.memory_bus import MemoryFrameBus
    from video_edge_ai_proxy_tpu.engine import InferenceEngine
    from video_edge_ai_proxy_tpu.uplink.queue import AnnotationQueue
    from video_edge_ai_proxy_tpu.utils.config import EngineConfig

    h, w = src_hw
    assignment = {}
    i = 0
    for name, count in fleet.items():
        for _ in range(count):
            assignment[f"fleet{i:02d}"] = name
            i += 1
    default_model = next(iter(fleet))
    bus = MemoryFrameBus()
    eng = InferenceEngine(
        bus,
        EngineConfig(model=default_model, tick_ms=tick_ms, stage_trace=True,
                     batch_buckets=(1, 2, 4, 8, 16), track=False),
        annotations=AnnotationQueue(handler=lambda batch: True),
        model_resolver=lambda d: assignment.get(d, ""),
    )
    eng.warmup()
    eng.start()
    frames = {d: np.random.default_rng(j).integers(
        0, 256, (h, w, 3), np.uint8)
        for j, d in enumerate(assignment)}
    for d in assignment:
        bus.create_stream(d, h * w * 3)
        bus.publish(d, frames[d], FrameMeta(
            width=w, height=h, channels=3,
            timestamp_ms=int(time.time() * 1000), is_keyframe=True))
    # wait out compiles: every (model, bucket) program builds on first use
    deadline = time.monotonic() + 1800
    results_seen = 0
    while time.monotonic() < deadline:
        stats = eng.stats()
        results_seen = sum(s.frames for s in stats.values())
        if len(stats) >= len(assignment):
            break
        time.sleep(1.0)
    eng.stage_records.clear()
    t0 = time.monotonic()
    ticks0, batches0 = eng.ticks, eng.batches
    stop = threading.Event()

    def cameras():
        while not stop.is_set():
            ts = int(time.time() * 1000)
            for d in assignment:
                bus.publish(d, frames[d], FrameMeta(
                    width=w, height=h, channels=3,
                    timestamp_ms=ts, is_keyframe=True))
            stop.wait(1.0 / 30.0)

    cam = threading.Thread(target=cameras, daemon=True)
    cam.start()
    time.sleep(duration_s)
    stop.set()
    cam.join(timeout=2)
    wall = time.monotonic() - t0
    records = list(eng.stage_records)
    stats = eng.stats()
    frames_served = sum(s.frames for s in stats.values())
    programs = len(eng._step_cache)
    real = len(records)   # one record per REAL frame (pad rows emit none)
    collect_to_submit = [
        (r["t_submit"] - r["t_collect"]) * 1000 for r in records
        if r["t_collect"]]
    eng.stop()
    bus.close()
    groups = {}
    for r in records:
        groups.setdefault(r["batch"], r["bucket"])   # (tick, group)
    padded_frames = sum(groups.values())
    return {
        "streams": len(assignment),
        "programs_compiled": programs,
        "ticks": eng.ticks - ticks0,
        "batches": eng.batches - batches0,
        "frames_served": frames_served,
        "engine_loop_fps": round(frames_served / wall, 1),
        "bucket_fill": round(real / padded_frames, 3) if padded_frames else None,
        "collect_to_submit_ms_p50": round(
            float(np.percentile(collect_to_submit, 50)), 3)
        if collect_to_submit else None,
        "collect_to_submit_ms_p95": round(
            float(np.percentile(collect_to_submit, 95)), 3)
        if collect_to_submit else None,
        "streams_with_results": len(stats),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--height", type=int, default=1080)
    ap.add_argument("--width", type=int, default=1920)
    ap.add_argument("--iters", type=int, default=100)
    ap.add_argument("--duration", type=float, default=20.0)
    ap.add_argument("--tick-ms", type=int, default=10)
    ap.add_argument("--skip-engine-leg", action="store_true")
    ap.add_argument("--record", default="")
    args = ap.parse_args(argv)

    import jax

    from video_edge_ai_proxy_tpu.utils import compile_cache

    # Persistent XLA cache: a rerun re-loads every (model, bucket)
    # program instead of compiling it again.
    compile_cache.configure(compile_cache.checkout_dir())

    src_hw = (args.height, args.width)
    record = {
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "fleet": DEFAULT_FLEET,
        "src_hw": list(src_hw),
    }
    print("leg A: single-model reference (16 x yolov8n) ...", flush=True)
    record["single_model"] = single_model_leg(
        "yolov8n", sum(DEFAULT_FLEET.values()), src_hw, args.iters)
    print(json.dumps(record["single_model"]), flush=True)
    print("leg A: multi-model fleet ...", flush=True)
    record["multi_model_device"] = device_leg(
        DEFAULT_FLEET, src_hw, args.iters)
    print(json.dumps(record["multi_model_device"]), flush=True)
    if not args.skip_engine_leg:
        print("leg B: engine loop ...", flush=True)
        record["engine_loop"] = engine_leg(
            DEFAULT_FLEET, src_hw, args.duration, args.tick_ms)
        print(json.dumps(record["engine_loop"]), flush=True)

    if args.record:
        with open(args.record, "w") as f:
            json.dump(record, f, indent=2)
            f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
