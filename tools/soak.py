"""Soak the full stack: N cameras -> bus -> engine -> gRPC clients.

Operational confidence tooling (SURVEY.md §4e: latency/throughput harness;
the reference's only integration story was manual docker-compose driving,
``README.md:109-136``). Boots a real Server (subprocess workers, shm bus,
TPU/CPU engine, gRPC + REST), attaches a VideoLatestImage client per
camera, optionally kills random workers to exercise supervision, and
prints one JSON summary: frames seen per client, inference results,
restarts observed, healthz verdicts and — from a run whose engine is on a
TPU — client frame rate and end-to-end latency percentiles. With the
engine on the CPU backend (``--cpu`` or ``JAX_PLATFORMS=cpu``) the run is
a rehearsal: the line says ``"backend": "cpu"`` and carries counts only.

Usage:
  python tools/soak.py [--cameras 8] [--seconds 60] [--chaos]
                       [--engine/--no-engine] [--backend shm]
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cameras", type=int, default=8)
    ap.add_argument("--seconds", type=float, default=60.0)
    ap.add_argument("--chaos", action="store_true",
                    help="kill a random worker every ~10 s (supervision soak)")
    ap.add_argument("--engine", action="store_true", default=True)
    ap.add_argument("--no-engine", dest="engine", action="store_false")
    ap.add_argument("--backend", default="shm", choices=("shm", "redis"))
    ap.add_argument("--redis_addr", default="")
    ap.add_argument("--model", default="yolov8n",
                    help="engine model (tiny_yolov8 for CPU-backend smokes)")
    ap.add_argument("--cpu", action="store_true",
                    help="CPU rehearsal: pin the CPU backend (same as "
                         "JAX_PLATFORMS=cpu); prints counts, no rates")
    ap.add_argument("--size", default="1280x720",
                    help="camera geometry WxH (tiny models want small frames)")
    args = ap.parse_args()

    if args.cpu:
        import jax

        jax.config.update("jax_platforms", "cpu")

    import grpc

    from video_edge_ai_proxy_tpu.proto import pb, pb_grpc
    from video_edge_ai_proxy_tpu.serve.models import StreamProcess
    from video_edge_ai_proxy_tpu.serve.server import Server
    from video_edge_ai_proxy_tpu.utils.config import Config

    tmp = tempfile.mkdtemp(prefix="vep_soak_")
    cfg = Config()
    cfg.bus.shm_dir = os.path.join("/dev/shm", f"vep_soak_{os.getpid()}")
    cfg.bus.backend = args.backend
    if args.redis_addr:
        cfg.bus.redis_addr = args.redis_addr
    cfg.annotation.endpoint = "http://127.0.0.1:1/annotate"  # no egress
    cfg.engine.model = args.model
    try:
        w, h = (int(v) for v in args.size.lower().split("x"))
    except ValueError:
        ap.error(f"--size must be WxH, got {args.size!r}")
    srv = Server(cfg, data_dir=tmp, grpc_port=0, rest_port=0,
                 enable_engine=args.engine)
    srv.start()

    cams = [f"soak{i}" for i in range(args.cameras)]
    for name in cams:
        srv.process_manager.start(StreamProcess(
            name=name,
            rtsp_endpoint=f"test://pattern?w={w}&h={h}&fps=30&gop=30",
        ))

    stop = threading.Event()
    stats = {c: {"frames": 0, "reconnects": 0} for c in cams}
    latencies: list[float] = []
    lat_lock = threading.Lock()
    inference = {"results": 0}

    def client(name: str) -> None:
        channel = grpc.insecure_channel(f"127.0.0.1:{srv.bound_grpc_port}")
        stub = pb_grpc.ImageStub(channel)

        def reqs():
            while not stop.is_set():
                yield pb.VideoFrameRequest(device_id=name)
                time.sleep(1 / 30)

        while not stop.is_set():
            try:
                for vf in stub.VideoLatestImage(reqs()):
                    if stop.is_set():
                        break
                    if vf.width:
                        stats[name]["frames"] += 1
                        if vf.timestamp:
                            with lat_lock:
                                latencies.append(
                                    time.time() * 1000 - vf.timestamp)
            except grpc.RpcError:
                stats[name]["reconnects"] += 1  # 15 s deadline / restarts
        channel.close()

    def inference_client() -> None:
        channel = grpc.insecure_channel(f"127.0.0.1:{srv.bound_grpc_port}")
        stub = pb_grpc.ImageStub(channel)
        while not stop.is_set():
            try:
                # Client-side deadline: unlike VideoLatestImage (15 s server
                # deadline), Inference streams forever — without a timeout a
                # result-less stream would block this thread past shutdown.
                for _res in stub.Inference(pb.InferenceRequest(), timeout=5):
                    inference["results"] += 1
                    if stop.is_set():
                        break
            except grpc.RpcError:
                if not stop.is_set():
                    time.sleep(0.5)
        channel.close()

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in cams]
    if args.engine:
        threads.append(threading.Thread(target=inference_client, daemon=True))
    for t in threads:
        t.start()

    import urllib.request

    rest = f"http://127.0.0.1:{srv._rest.bound_port}"
    health = {"ok": 0, "degraded": 0}
    kills = 0
    deadline = time.monotonic() + args.seconds
    rng = random.Random(0)
    next_chaos = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        time.sleep(2.0)
        try:
            with urllib.request.urlopen(rest + "/healthz", timeout=5) as r:
                health["ok" if r.status == 200 else "degraded"] += 1
        except urllib.error.HTTPError:
            health["degraded"] += 1
        except Exception:
            pass
        if args.chaos and time.monotonic() >= next_chaos:
            victim = rng.choice(cams)
            rec = srv.process_manager.info(victim)
            if rec.state and rec.state.pid:
                try:
                    os.kill(rec.state.pid, 9)
                    kills += 1
                except ProcessLookupError:
                    pass
            next_chaos = time.monotonic() + 10.0

    stop.set()
    for t in threads:
        t.join(timeout=10)
    # post-chaos: every camera must come back. A kill in the final seconds
    # is still inside the supervisor's detect+backoff+respawn pipeline
    # (up to ~3 s), so give healing a bounded grace instead of sampling a
    # healthy supervisor mid-restart.
    heal_deadline = time.monotonic() + 8.0
    while True:
        running = sum(
            1 for c in cams
            if srv.process_manager.info(c).state.running
        )
        if running == len(cams) or time.monotonic() >= heal_deadline:
            break
        time.sleep(0.5)
    engine_stats = srv.engine.stats() if srv.engine else {}
    # r23: the final decision-journal state rides in the artifact — what
    # the control planes decided during the soak and why, with causal
    # links (validate with tools/obs_export.py --journal).
    journal = (srv.engine.journal.snapshot(tail=64)
               if srv.engine is not None
               and srv.engine.journal is not None else None)
    srv.stop()
    # Soak runs repeat; each must reclaim its tmpfs rings and registry dir.
    import shutil

    shutil.rmtree(cfg.bus.shm_dir, ignore_errors=True)
    shutil.rmtree(tmp, ignore_errors=True)

    with lat_lock:
        lat_sorted = sorted(latencies)

    def pct(p):
        return round(lat_sorted[int(p * (len(lat_sorted) - 1))], 1) \
            if lat_sorted else None

    total = sum(s["frames"] for s in stats.values())
    backend = None
    if args.engine:
        import jax

        backend = jax.default_backend()
    print(json.dumps({
        "cameras": args.cameras,
        "seconds": args.seconds,
        "backend": backend,
        "frames_total": total,
        # Rates and latencies with the engine in the path are device
        # metrics: a CPU rehearsal prints counts only.
        **({"client_fps": round(total / args.seconds, 1),
            "latency_ms_p50": pct(0.50),
            "latency_ms_p95": pct(0.95)}
           if backend in (None, "tpu") else {}),
        "reconnects": sum(s["reconnects"] for s in stats.values()),
        "inference_results": inference["results"],
        "engine_streams": len(engine_stats),
        "chaos_kills": kills,
        "running_after": running,
        "healthz": health,
        "journal": journal,
    }))


if __name__ == "__main__":
    main()
