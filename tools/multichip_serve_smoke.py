"""Mesh-native serving smoke: prove the dp-replicated megastep serves a
real fleet — collector -> per-shard H2D prefetch -> sharded dispatch ->
emit — with ROI packing and the temporal cascade ON, and that going
multi-chip changed the capacity curve, not the answers.

Two legs on the CPU twin (8 virtual devices via
``--xla_force_host_platform_device_count``):

1. **Lockstep parity** — the committed 240-frame synthetic trace
   checksum (``soak:lockstep:tiny_yolov8:cpu:240f``) pinned in a
   1-device subprocess (the golden's canonical config — the
   8-virtual-device XLA flag changes CPU codegen, so the pre-PR anchor
   must replay without it), then the same trace replayed in-process
   once single-chip and once through the mesh H2D path on a dp=1 mesh
   (``replay.harness.lockstep_checksum(mesh=...)``). The dp=1 mesh
   checksum must be bit-identical to single-chip on the same device
   config: sharded placement is a layout change, never a numerics
   change.

2. **Lockstep replay fleet** — three serves over the same color-keyed
   all-mover blob fleet (models/blob.py: every detection's class id
   names its owner stream) at dp=1 (2 streams), dp=2 (4 streams) and
   dp=4 (8 streams): 2 streams per mesh slice by the collector's
   crc32 placement, buckets (2, 4, 8) so every dp lands a zero-padding
   shard-segmented batch. ROI gating, the temporal cascade
   (tiny_videomae head), quality thumbs and the capacity ledger are
   all enabled — the features the single-chip-only notices used to
   turn off under a mesh.

Gates, exit non-zero on breach (ISSUE r17 acceptance):

- 1-device lockstep checksum == the committed pre-PR golden, and the
  dp=1 mesh lockstep checksum == single-chip bit-identical,
- ZERO misrouted scatter-backs (a detection carrying another stream's
  color key) and zero unrouted canvas detections, at every dp,
- capacity conservation: aggregate AND per-shard rel_drift == 0.0
  (the per-shard attribution folds exactly by construction — any
  drift is a sharded-attribution bug),
- aggregate fps at dp=4 >= ``--min-scale`` x dp=1 (weak scaling: 4x
  the streams at the same per-stream rate; default 3.2x),
- the cascade head actually ran ON the mesh (a ``cascade/`` model in
  the perf buckets at dp>1) and per-shard perf attribution is present
  (snapshot ``shards``),
- ``vep_perf_shard_*`` / ``vep_capacity_shard_*`` exposition
  lint-clean.

Runs in ~2 min on the CPU twin; wired as ``make multichip-serve-smoke``.
One JSON line on stdout; ``--out`` additionally writes the artifact
(committed as MULTICHIP_SERVE_r01.json).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# 8 virtual CPU devices: a CPU rehearsal of the mesh path. XLA_FLAGS is
# read when the backend initializes, which nothing has done yet.
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()

# Streams whose crc32 shard placement (engine/collector.py stream_shard)
# spreads exactly 2 per mesh slice at each dp — verified constants, so
# the smoke never depends on hash luck.
STREAMS_BY_DP = {
    1: ["cam0", "cam4"],
    2: ["cam0", "cam1", "cam4", "cam5"],
    4: ["cam0", "cam1", "cam2", "cam3", "cam4", "cam5", "cam6", "cam7"],
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--duration", type=float, default=8.0,
                    help="measured seconds per serve leg (default 8)")
    ap.add_argument("--prime", type=float, default=6.0,
                    help="seconds of pre-measurement serving per leg so "
                         "compiles and cascade clip fill land outside "
                         "the fps window (default 6)")
    ap.add_argument("--frames", type=int, default=240,
                    help="lockstep trace length (default 240 = the "
                         "committed golden)")
    ap.add_argument("--min-scale", type=float, default=3.2,
                    help="required fps(dp=4) / fps(dp=1) (default 3.2)")
    ap.add_argument("--out", default="",
                    help="also write the artifact JSON here")
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_platforms", "cpu")
    backend = jax.default_backend()
    if len(jax.devices()) < 8:
        raise SystemExit(
            f"multichip_serve_smoke: need 8 virtual devices, have "
            f"{len(jax.devices())} — XLA_FLAGS was bound too late")

    import queue as _queue

    import numpy as np

    from video_edge_ai_proxy_tpu.bus.interface import FrameMeta
    from video_edge_ai_proxy_tpu.bus.memory_bus import MemoryFrameBus
    from video_edge_ai_proxy_tpu.engine import InferenceEngine
    from video_edge_ai_proxy_tpu.engine.collector import stream_shard
    from video_edge_ai_proxy_tpu.models import registry
    from video_edge_ai_proxy_tpu.models.blob import blob_color
    from video_edge_ai_proxy_tpu.obs.metrics import (
        lint_exposition, registry as metrics_registry,
    )
    from video_edge_ai_proxy_tpu.parallel import make_mesh
    from video_edge_ai_proxy_tpu.replay.harness import lockstep_checksum
    from video_edge_ai_proxy_tpu.replay.recorder import record_synthetic_trace
    from video_edge_ai_proxy_tpu.uplink.queue import AnnotationQueue
    from video_edge_ai_proxy_tpu.utils.config import EngineConfig

    # -- leg 1: lockstep parity, single-chip vs dp=1 mesh H2D ------------
    tmpdir = tempfile.mkdtemp(prefix="vep_mesh_smoke_")
    trace_path = os.path.join(tmpdir, "trace.bin")
    record_synthetic_trace(trace_path, ["det0", "det1"], width=128,
                           height=96, fps=30.0, gop=30, frames=args.frames)
    # Pre-PR anchor: the committed golden was recorded on the plain
    # 1-device CPU backend. --xla_force_host_platform_device_count
    # changes XLA's CPU codegen (reduction tiling), so the anchor leg
    # replays in a subprocess without the flag; check_golden raises on
    # drift there.
    import subprocess

    env = dict(os.environ)
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f)
    anchor_code = (
        "import jax, json;"
        "jax.config.update('jax_platforms', 'cpu');"
        "from video_edge_ai_proxy_tpu.replay.harness import"
        " lockstep_checksum;"
        "from video_edge_ai_proxy_tpu.replay.checksum import check_golden;"
        f"r = lockstep_checksum({trace_path!r}, model='tiny_yolov8');"
        f"g = check_golden('soak:lockstep:tiny_yolov8:{backend}:"
        f"{args.frames}f', r['checksum'],"
        " tool='multichip_serve_smoke');"
        "print(json.dumps({'checksum': r['checksum'], 'golden': g}))"
    )
    proc = subprocess.run(
        [sys.executable, "-c", anchor_code], env=env, cwd=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True)
    if proc.returncode != 0:
        raise SystemExit(
            "multichip_serve_smoke: 1-device golden anchor failed:\n"
            + proc.stderr.strip()[-2000:])
    anchor = json.loads(proc.stdout.strip().splitlines()[-1])
    single = lockstep_checksum(trace_path, model="tiny_yolov8")
    mesh1 = lockstep_checksum(
        trace_path, model="tiny_yolov8",
        mesh=make_mesh(dp=1, devices=jax.devices()[:1]))

    # -- leg 2: replay fleet at dp=1 / dp=2 / dp=4 -----------------------
    model = "tiny_blob_gauge"
    spec = registry.get(model)
    side = spec.input_size            # frames == model input: exact boxes
    blob_w, blob_h = max(8, side // 6), max(8, side // 8)
    span = side - blob_w - 16         # triangle-wave travel (all movers)

    def scene(stream: int, step: int):
        frame = np.full((side, side, 3), 114, np.uint8)
        phase = step % (2 * span)
        x0 = 8 + (phase if phase < span else 2 * span - phase)
        y0 = 8 + 4 * stream
        frame[y0:y0 + blob_h, x0:x0 + blob_w] = blob_color(stream)
        return frame

    def serve(dp: int) -> dict:
        streams = STREAMS_BY_DP[dp]
        owners = {sid: int(sid[3:]) for sid in streams}
        for sid in streams:           # placement really is 2 per slice
            assert len([s for s in streams
                        if stream_shard(s, dp) == stream_shard(sid, dp)]) \
                == len(streams) // dp
        bus = MemoryFrameBus()
        try:
            eng = InferenceEngine(
                bus,
                EngineConfig(
                    model=model, mesh={"dp": dp},
                    batch_buckets=(2, 4, 8), tick_ms=10, prof=False,
                    roi=True, roi_canvas=side,
                    roi_min_crop=max(8, side // 8),
                    roi_full_interval_ms=500,
                    cascade=True, cascade_model="tiny_videomae",
                    capacity=True,
                ),
                annotations=AnnotationQueue(handler=lambda batch: True),
            )
            eng.warmup()
            for sid in streams:
                bus.create_stream(sid, side * side * 3)
            results_q: _queue.Queue = _queue.Queue()
            with eng._sub_lock:
                eng._subscribers.append((results_q, None))
            truth = {}                 # (device_id, ts) -> owner stream
            results = []
            eng.start()
            try:
                step = 0
                last_ts = 0
                window_start_ts = None
                t_end_prime = time.monotonic() + args.prime
                deadline = None
                published = 0
                while True:
                    now = time.monotonic()
                    if deadline is None and now >= t_end_prime:
                        deadline = now + args.duration
                        window_start_ts = last_ts + 1
                    if deadline is not None and now >= deadline:
                        break
                    ts = max(int(time.time() * 1000), last_ts + 1)
                    last_ts = ts
                    for sid in streams:
                        truth[(sid, ts)] = owners[sid]
                        bus.publish(
                            sid, scene(owners[sid], step),
                            FrameMeta(width=side, height=side, channels=3,
                                      timestamp_ms=ts, is_keyframe=True))
                        if deadline is not None:
                            published += 1
                    step += 1
                    time.sleep(0.03)
                    while True:
                        try:
                            results.append(results_q.get_nowait())
                        except _queue.Empty:
                            break
                window_s = args.duration
            finally:
                eng.stop()
            while True:
                try:
                    results.append(results_q.get_nowait())
                except _queue.Empty:
                    break
            snap = eng.perf.snapshot()
            conserve = (eng.capacity.conservation()
                        if eng.capacity is not None else None)
        finally:
            bus.close()

        results = [r for r in results if r is not None]  # stop() sentinel
        misrouted, matched, measured = 0, 0, 0
        misrouted_examples = []
        for r in results:
            owner = truth.get((r.device_id, r.timestamp))
            if owner is None:
                continue
            if window_start_ts is not None \
                    and r.timestamp >= window_start_ts:
                measured += 1
            for d in r.detections:
                if d.class_id != owner:
                    misrouted += 1
                    if len(misrouted_examples) < 10:
                        misrouted_examples.append({
                            "stream": r.device_id, "owner": owner,
                            "class_id": d.class_id,
                            "box": [d.box.left, d.box.top,
                                    d.box.width, d.box.height],
                            "confidence": round(d.confidence, 3),
                            "batch_size": r.batch_size,
                            "latency_ms": round(r.latency_ms, 1),
                        })
                else:
                    matched += 1
        cascade_models = sorted({
            b["model"] for b in snap["buckets"]
            if b["model"].startswith("cascade/")})
        shard_frames = {
            s["shard"]: s["frames"]
            for s in snap.get("shards", ())
            if not s["model"].startswith("cascade/")}
        roi_stats = snap.get("roi") or {}
        return {
            "dp": dp,
            "streams": len(streams),
            "results": len(results),
            "matched_detections": matched,
            "misrouted": misrouted,
            "misrouted_examples": misrouted_examples,
            "unrouted": roi_stats.get("unrouted", 0),
            "fps": round(measured / window_s, 1) if window_s else None,
            "published_in_window": published,
            "device_frames": sum(b["frames"] for b in snap["buckets"]),
            "cascade_models": cascade_models,
            "cascade_head_batches": (snap.get("cascade") or {}).get(
                "head_batches", 0),
            "perf_shard_frames": shard_frames,
            "roi": {k: roi_stats.get(k) for k in
                    ("crops", "canvases", "unrouted")},
            "conservation": conserve,
        }

    legs = {dp: serve(dp) for dp in (1, 2, 4)}

    # Lint the new per-shard metric families off the live registry that
    # just served the dp=4 leg.
    text = metrics_registry.render()
    problems = [p for p in lint_exposition(text)
                if "vep_perf_shard" in p or "vep_capacity_shard" in p]

    scale = None
    if legs[1]["fps"] and legs[4]["fps"]:
        scale = round(legs[4]["fps"] / legs[1]["fps"], 2)
    out = {
        "tool": "multichip_serve_smoke",
        "backend": backend,
        "model": model,
        "devices": len(jax.devices()),
        "duration_s": args.duration,
        "prime_s": args.prime,
        "lockstep": {
            "frames": args.frames,
            "anchor_1dev": anchor["checksum"],
            "golden": anchor["golden"],
            "single_chip_8dev": single["checksum"],
            "mesh_dp1": mesh1["checksum"],
            "bit_identical": mesh1["checksum"] == single["checksum"],
        },
        "serve": {f"dp{dp}": leg for dp, leg in legs.items()},
        "fps_scale_dp4_over_dp1": scale,
        "exposition_problems": problems,
        "gates": {"min_scale": args.min_scale},
    }
    print(json.dumps(out), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=2)
            f.write("\n")

    if mesh1["checksum"] != single["checksum"]:
        raise SystemExit(
            f"multichip_serve_smoke: dp=1 mesh lockstep checksum "
            f"{mesh1['checksum']} != single-chip {single['checksum']} — "
            "the mesh H2D path changed serving numerics")
    for dp, leg in legs.items():
        if leg["matched_detections"] < 20:
            raise SystemExit(
                f"multichip_serve_smoke: dp={dp} only "
                f"{leg['matched_detections']} matched detections — the "
                "serve never reached steady state")
        if leg["misrouted"] or leg["unrouted"]:
            raise SystemExit(
                f"multichip_serve_smoke: dp={dp} misrouted="
                f"{leg['misrouted']} unrouted={leg['unrouted']} — ROI "
                "scatter-back crossed a shard boundary")
        cons = leg["conservation"]
        if cons is None or cons["rel_drift"] != 0.0:
            raise SystemExit(
                f"multichip_serve_smoke: dp={dp} aggregate conservation "
                f"drift {cons and cons['rel_drift']} != 0.0")
        if dp > 1:
            shards = (cons.get("shards") or {})
            if len(shards) != dp:
                raise SystemExit(
                    f"multichip_serve_smoke: dp={dp} capacity ledger has "
                    f"{sorted(shards)} shard rows, want {dp}")
            for s, rec in shards.items():
                if rec["rel_drift"] != 0.0:
                    raise SystemExit(
                        f"multichip_serve_smoke: dp={dp} shard {s} "
                        f"conservation drift {rec['rel_drift']} != 0.0")
            if not leg["cascade_models"] \
                    or not leg["cascade_head_batches"]:
                raise SystemExit(
                    f"multichip_serve_smoke: dp={dp} cascade head never "
                    f"ran on the mesh: {leg['cascade_models']} "
                    f"({leg['cascade_head_batches']} head batches)")
            if len(leg["perf_shard_frames"]) != dp \
                    or any(v <= 0
                           for v in leg["perf_shard_frames"].values()):
                raise SystemExit(
                    f"multichip_serve_smoke: dp={dp} per-shard perf "
                    f"attribution incomplete: {leg['perf_shard_frames']}")
    if problems:
        raise SystemExit(
            f"multichip_serve_smoke: per-shard exposition not "
            f"lint-clean: {problems}")
    if scale is None or scale < args.min_scale:
        raise SystemExit(
            f"multichip_serve_smoke: fps scale dp4/dp1 {scale} < "
            f"{args.min_scale} (dp1 {legs[1]['fps']} fps, dp4 "
            f"{legs[4]['fps']} fps)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
