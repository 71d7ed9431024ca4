"""Serving latency budget, measured stage by stage (VERDICT r3 weak #1).

The <40 ms p50 north-star serving SLA (BASELINE.json) previously rested on
arithmetic: device time was nailed by bench.py, but no measurement
decomposed the FRAMEWORK's own host-side path — bus publish -> collector
pickup -> dispatch -> drain -> emit -> subscriber receive. This tool runs
the real engine loop (``EngineConfig.stage_trace``) against in-process
synthetic cameras on the production shm bus and reports p50/p95 per stage.

Three legs split the measurement (a composition from before the engine
loop could be driven at 16x1080p against an attached chip; ROADMAP S2
replaces it with one observed pipeline):

- engine-loop leg, by default at a reduced geometry
  (``--engine-geometry``; pass the real one on a chip host): the live
  loop's dispatch overhead (collect->submit), postprocess (drain->emit),
  and subscriber hop (emit->recv) — stages whose cost barely depends on
  source frame size;
- pure-host leg at the REAL geometry: bus publish -> collector pickup
  and the collect() call (shm read + assembly + pad) with no device;
- chip leg at the REAL geometry: scan-folded device batch time, exactly
  bench.py's methodology.

    production_e2e_p50 = host_pub_to_collect(real)
                       + collect_to_submit(loop)
                       + device_batch_ms(real)
                       + drain_to_emit(loop) + emit_to_recv(loop)

(No tick_ms term since r5: event-driven drain emits when the device
finishes; incremental assembly overlaps frame copies with arrival.)

Every term is a measurement from this run; only the SUM is a composition,
and the loop leg's raw stages are reported alongside so nothing hides.
The record stamps ``backend`` and ``device_kind``: only a TPU run's
device leg is a device metric.

    python tools/bench_latency.py --record LATENCY.json
"""

from __future__ import annotations

import argparse
import json
import os
import queue
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

STAGES = [
    ("pub_to_collect", "frame on the bus -> collector picked it up"),
    ("collect_to_submit", "batch assembly + device dispatch"),
    ("submit_to_drain", "double-buffer wait until drain begins"),
    ("drain_fetch", "D2H fetch of the batch outputs"),
    ("drain_to_emit", "postprocess + proto build + tracker"),
    ("emit_to_recv", "subscriber queue hop"),
    ("e2e", "publish timestamp -> subscriber receive"),
]


def percentiles(xs):
    if not xs:
        return {"p50": None, "p95": None, "n": 0}
    a = np.asarray(xs, np.float64)
    return {"p50": round(float(np.percentile(a, 50)), 3),
            "p95": round(float(np.percentile(a, 95)), 3),
            "n": len(xs)}


def run(model: str, streams: int, src_hw, fps: float, duration_s: float,
        bus_backend: str, tick_ms: int, log=print) -> dict:
    import tempfile

    from video_edge_ai_proxy_tpu.bus import FrameMeta, open_bus
    from video_edge_ai_proxy_tpu.engine import InferenceEngine
    from video_edge_ai_proxy_tpu.utils.config import EngineConfig

    h, w = src_hw
    # Fresh bus dir: stale rings from earlier runs would be enumerated as
    # live streams and their hours-old frame timestamps would poison the
    # stage percentiles.
    tmp = tempfile.mkdtemp(prefix="vep_lat_loop_", dir="/dev/shm") \
        if bus_backend == "shm" else ""
    bus = open_bus(bus_backend, tmp) if tmp else open_bus(bus_backend)
    buckets = tuple(b for b in (1, 2, 4, 8, 16) if b <= max(streams, 1))
    eng = InferenceEngine(bus, EngineConfig(
        model=model, tick_ms=tick_ms, stage_trace=True,
        batch_buckets=buckets,
        annotation_emit="all", track=True,
    ))
    log(f"warmup + compile ({model}, {streams}x{h}x{w}) ...")
    eng.warmup()
    # Incremental assembly dispatches PARTIAL buckets as frames trickle
    # in (r4's synchronized burst only ever built the full bucket), so
    # every bucket must be compiled before the timed window or mid-run
    # compiles dominate the trace. Production does the same via
    # cfg.prewarm at boot.
    for b in buckets:
        log(f"prewarm bucket {b} ...")
        eng.compile_for((h, w), b)
    # The engine's default trace buffer (4096) holds ~28% of a default
    # 16-stream x 30 fps x 30 s run; size it to the whole window so the
    # percentiles cover the full measurement, not just its tail.
    import collections

    eng.stage_records = collections.deque(
        maxlen=max(4096, int(streams * fps * duration_s * 2)))
    eng.start()

    recv_times = {}
    recv_lock = threading.Lock()

    def subscriber():
        for res in eng.subscribe():
            with recv_lock:
                recv_times[(res.device_id, res.timestamp)] = time.time()

    sub = threading.Thread(target=subscriber, daemon=True)
    sub.start()

    frames = [
        np.random.default_rng(i).integers(0, 256, (h, w, 3), np.uint8)
        for i in range(streams)
    ]
    for i in range(streams):
        bus.create_stream(f"lat{i:02d}", h * w * 3)

    # First frames force the (geometry, bucket) compiles before timing.
    for i in range(streams):
        bus.publish(f"lat{i:02d}", frames[i], FrameMeta(
            width=w, height=h, channels=3,
            timestamp_ms=int(time.time() * 1000), is_keyframe=True))
    t_wait = time.monotonic()
    while not eng.stage_records and time.monotonic() - t_wait < 600:
        time.sleep(0.5)
    eng.stage_records.clear()
    with recv_lock:
        recv_times.clear()

    log(f"publishing {streams} streams at {fps} fps for {duration_s}s ...")
    stop = threading.Event()

    def camera(i: int):
        period = 1.0 / fps
        nxt = time.monotonic()
        while not stop.is_set():
            ts = int(time.time() * 1000)
            bus.publish(f"lat{i:02d}", frames[i], FrameMeta(
                width=w, height=h, channels=3,
                timestamp_ms=ts, is_keyframe=True))
            nxt += period
            delay = nxt - time.monotonic()
            if delay > 0:
                stop.wait(delay)
            else:
                nxt = time.monotonic()

    cams = [threading.Thread(target=camera, args=(i,), daemon=True)
            for i in range(streams)]
    for c in cams:
        c.start()
    time.sleep(duration_s)
    stop.set()
    for c in cams:
        c.join(timeout=2)
    time.sleep(1.0)          # let the last inflight drain
    records = list(eng.stage_records)
    eng.stop()
    bus.close()
    if tmp:
        import shutil

        shutil.rmtree(tmp, ignore_errors=True)

    stage_ms = {name: [] for name, _ in STAGES}
    for r in records:
        key = (r["device_id"], r["ts_pub_ms"])
        with recv_lock:
            t_recv = recv_times.get(key)
        if not r["ts_pub_ms"] or not r["t_collect"]:
            continue
        stage_ms["pub_to_collect"].append(
            r["t_collect"] * 1000 - r["ts_pub_ms"])
        stage_ms["collect_to_submit"].append(
            (r["t_submit"] - r["t_collect"]) * 1000)
        stage_ms["submit_to_drain"].append(
            (r["t_drain0"] - r["t_submit"]) * 1000)
        stage_ms["drain_fetch"].append(
            (r["t_drained"] - r["t_drain0"]) * 1000)
        stage_ms["drain_to_emit"].append(
            (r["t_emitted"] - r["t_drained"]) * 1000)
        if t_recv is not None:
            stage_ms["emit_to_recv"].append(
                (t_recv - r["t_emitted"]) * 1000)
            stage_ms["e2e"].append(t_recv * 1000 - r["ts_pub_ms"])

    return {
        "frames_traced": len(records),
        "stages_ms": {name: percentiles(stage_ms[name])
                      for name, _ in STAGES},
        "stage_legend": dict(STAGES),
    }


def host_leg(streams: int, src_hw, ticks: int = 200,
             bus_backend: str = "shm", fps: float = 30.0,
             tick_ms: int = 10) -> dict:
    """Pure host-side cost of the frame plane at the REAL geometry, no
    device in the loop, with the engine's production overlap structure:
    each camera's publish is immediately followed by the assembly sweep
    that copies it into its pooled batch slot (incremental assembly,
    Collector.plan_assembly/assemble_step), and collect() at the tick
    boundary only finalizes. Publishes are staggered over the tick at the
    real camera cadence — the r4 burst pattern (publish all N, then copy
    all N at collect time) put the entire ~100 MB/tick frame plane
    between a frame's publish and its dispatch, measuring 3x the memcpy
    floor; the overlap moves those copies into the arrival gaps exactly
    as the engine's doorbell-woken assemble_until does.

    Serial single-thread methodology, same as r4's host leg: on a host
    with few cores, free-running camera THREADS would measure how 17
    threads share them, not stage cost. (In production, cameras are
    separate processes on separate cores; the loop leg measures the live
    threaded engine at a core-sustainable geometry.)"""
    import tempfile

    from video_edge_ai_proxy_tpu.bus import FrameMeta, open_bus
    from video_edge_ai_proxy_tpu.engine import Collector

    h, w = src_hw
    # Fresh bus dir: stale rings from earlier runs/legs must not inflate
    # the stream enumeration (each idle ring adds a read per tick).
    tmp = tempfile.mkdtemp(prefix="vep_lat_", dir="/dev/shm") \
        if bus_backend == "shm" else ""
    bus = open_bus(bus_backend, tmp) if tmp else open_bus(bus_backend)
    try:
        frames = [
            np.random.default_rng(i).integers(0, 256, (h, w, 3), np.uint8)
            for i in range(streams)
        ]
        for i in range(streams):
            bus.create_stream(f"host{i:02d}", h * w * 3)
        col = Collector(bus, buckets=tuple(
            sorted({1, 2, 4, 8, streams})))
        tick_s = tick_ms / 1000.0
        period = 1.0 / fps
        # Camera i's next publish due time, staggered across the period.
        start = time.monotonic() + tick_s
        due = [start + i * (period / streams) for i in range(streams)]
        pub_to_collect, collect_call = [], []
        for t in range(ticks):
            t0 = time.monotonic()
            groups = col.collect()
            tw1 = time.time()
            t1 = time.monotonic()
            if t >= 5:           # skip warmup ticks (page faults, plans)
                collect_call.append((t1 - t0) * 1000)
                for g in groups:
                    for meta in g.metas:
                        if meta.timestamp_ms:
                            pub_to_collect.append(
                                tw1 * 1000 - meta.timestamp_ms)
            col.plan_assembly()
            deadline = t0 + tick_s
            # Publish each due camera at its due time, then sweep it into
            # its batch slot — the copy overlaps the arrival gap.
            while True:
                nxt = min(due)
                now = time.monotonic()
                if now >= deadline:
                    break   # tick budget spent; backlog defers a tick
                if nxt >= deadline:
                    time.sleep(deadline - now)
                    break
                if nxt > now:
                    time.sleep(nxt - now)
                i = due.index(nxt)
                bus.publish(f"host{i:02d}", frames[i], FrameMeta(
                    width=w, height=h, channels=3,
                    timestamp_ms=int(time.time() * 1000),
                    is_keyframe=True))
                due[i] += period
                col.assemble_step()
        # Raw memcpy floor: the frame plane's job is fundamentally "move
        # streams x H x W x 3 bytes once"; this is what ONE pass costs on
        # this host's memory system, so (collect_call / memcpy) is the
        # framework's overhead factor, portable across hosts.
        src = np.stack(frames)
        dstbuf = np.empty_like(src)
        memcpy_ms = []
        for _ in range(20):
            t0 = time.perf_counter()
            np.copyto(dstbuf, src)
            memcpy_ms.append((time.perf_counter() - t0) * 1000)
        return {
            "host_pub_to_collect_ms": percentiles(pub_to_collect),
            "host_collect_call_ms": percentiles(collect_call),
            "host_memcpy_floor_ms": round(min(memcpy_ms), 3),
            "host_fps_in": fps,
            "host_tick_ms": tick_ms,
            "ticks": ticks,
        }
    finally:
        bus.close()
        if tmp:
            import shutil

            shutil.rmtree(tmp, ignore_errors=True)


def device_batch_ms(model: str, streams: int, src_hw, iters: int) -> dict:
    """On-chip time for one serving batch, exactly like bench.py (scan
    over iters, one dispatch+fetch, best-of-3)."""
    import jax
    import jax.numpy as jnp

    from bench import timed_best
    from video_edge_ai_proxy_tpu.engine.runner import build_serving_step
    from video_edge_ai_proxy_tpu.models import registry

    spec = registry.get(model)
    model_mod, variables = spec.init_params(jax.random.PRNGKey(0))
    step = build_serving_step(model_mod, spec)

    @jax.jit
    def megastep(base_u8):
        def body(carry, i):
            out = step(variables, base_u8 + i.astype(jnp.uint8))
            return carry + out["valid"].sum(), None

        total, _ = jax.lax.scan(
            body, jnp.zeros((), jnp.int32), jnp.arange(iters))
        return total

    rng = np.random.default_rng(0)
    base_dev = jax.device_put(rng.integers(
        0, 256, (streams,) + tuple(src_hw) + (3,), dtype=np.uint8))
    np.asarray(megastep(base_dev))
    elapsed, _ = timed_best(lambda: megastep(base_dev))
    return {"device_batch_ms": round(elapsed / iters * 1000.0, 3)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--model", default="yolov8n")
    ap.add_argument("--streams", type=int, default=16)
    ap.add_argument("--height", type=int, default=1080)
    ap.add_argument("--width", type=int, default=1920)
    ap.add_argument("--engine-geometry", default="270x480",
                    help="HxW for the live engine-loop leg (default: a "
                         "reduced size a CPU rehearsal sustains; the "
                         "REAL-geometry frame-plane costs then come from "
                         "the pure-host leg and the scan-folded chip leg)")
    ap.add_argument("--fps", type=float, default=30.0)
    ap.add_argument("--duration", type=float, default=30.0)
    ap.add_argument("--bus", default="shm", choices=("shm", "memory"))
    ap.add_argument("--tick-ms", type=int, default=10)
    ap.add_argument("--iters", type=int, default=150,
                    help="scan length for the on-chip leg")
    ap.add_argument("--host-ticks", type=int, default=200)
    ap.add_argument("--skip-device-leg", action="store_true")
    ap.add_argument("--skip-host-leg", action="store_true")
    ap.add_argument("--record", default="")
    args = ap.parse_args(argv)

    import jax

    eh, _, ew = args.engine_geometry.partition("x")
    engine_hw = (int(eh), int(ew))
    real_hw = (args.height, args.width)
    record = {
        "model": args.model,
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "streams": args.streams,
        "src_hw": list(real_hw),
        "engine_loop_hw": list(engine_hw),
        "fps_in": args.fps,
        "tick_ms": args.tick_ms,
        "bus": args.bus,
    }
    record.update(run(
        args.model, args.streams, engine_hw, args.fps,
        args.duration, args.bus, args.tick_ms))

    if not args.skip_host_leg:
        print("host leg (real geometry, no device) ...", flush=True)
        record.update(host_leg(args.streams, real_hw, args.host_ticks,
                               args.bus, fps=args.fps,
                               tick_ms=args.tick_ms))

    if not args.skip_device_leg:
        print("device leg (real geometry, scan-folded) ...", flush=True)
        record.update(device_batch_ms(
            args.model, args.streams, real_hw, args.iters))
        s = record["stages_ms"]
        hp = record.get("host_pub_to_collect_ms", {}).get("p50")
        terms = [
            hp,                                   # frame plane @ real geom
            s["collect_to_submit"]["p50"],        # dispatch overhead
            record["device_batch_ms"],            # on-chip @ real geom
            s["drain_to_emit"]["p50"],            # postprocess + proto
            s["emit_to_recv"]["p50"],             # subscriber hop
        ]
        if all(v is not None for v in terms):
            record["production_e2e_p50_ms"] = round(sum(terms), 2)
            # No tick_ms term since r5: the drain thread blocks on the
            # device outputs and emits the moment the batch finishes
            # (event-driven drain) — results no longer wait for the next
            # tick boundary. The drain thread's OS wake-up (it is already
            # parked inside the output fetch when the device completes)
            # rides inside device_batch_ms's error bars.
            record["composition"] = (
                "host_pub_to_collect(real) + collect_to_submit(loop) + "
                "device_batch_ms(real) + drain_to_emit(loop) + "
                "emit_to_recv(loop)"
            )
            record["sla_ms"] = 40.0
            record["sla_met"] = record["production_e2e_p50_ms"] < 40.0

    print(json.dumps(record))
    if args.record:
        with open(args.record, "w") as f:
            json.dump(record, f, indent=2)
            f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
