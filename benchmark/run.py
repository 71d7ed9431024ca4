"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip: a fresh shm frame bus -> ``InferenceEngine`` ->
``engine.subscribe()``, i.e. every data-plane layer from the bus to the emit
queue. Cameras are this benchmark's own publisher processes (jax-free).
Weights come from ``--seed``, made on the device. The cameras free-run at
the traffic file's frame rate; the run loads the (model, geometry, bucket)
programs its cell uses, waits until every clip window is full and every
camera has been answered, measures for ``--seconds``, decides ``correct``
against the plain reference once the window has closed, prints one JSON
line and exits.
Without a TPU it fails: there is no CPU fallback under a device metric's
name.
"""

from __future__ import annotations

import time

_T0 = time.monotonic()          # process start, for setup_s

import argparse                 # noqa: E402
import collections              # noqa: E402
import json                     # noqa: E402
import os                       # noqa: E402
import shutil                   # noqa: E402
import sys                      # noqa: E402
import tempfile                 # noqa: E402
import threading                # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# the program logs to stdout; the result line has to be the last one there
os.environ.setdefault("VEP_TPU_LOG_LEVEL", "ERROR")
for p in (HERE, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

SAMPLE_PER_MODEL = 32           # results compared with the reference
WARM_ROUNDS = 1                 # answers every camera has before the window
WARM_WAIT_S = 150.0             # how long those may take


def log(*a):
    print("[vbench]", *a, file=sys.stderr, flush=True)


def rss_gb() -> float:
    """This process's resident memory (GB), from /proc."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) / 1e6
    except OSError:
        pass
    return 0.0


def percentile(values, q):
    """Linear-interpolated percentile of a non-empty list."""
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def seeded_spec(base, variables):
    """``base`` with the benchmark's weights: the engine asks a spec for
    ``init_params`` and gets these, whatever key it passes."""
    import dataclasses

    @dataclasses.dataclass(frozen=True)
    class Seeded(type(base)):
        def init_params(self, rng=None, batch=1):
            return self.build(), variables

    return Seeded(**{f.name: getattr(base, f.name)
                     for f in dataclasses.fields(base)})


def prewarm_entries(cams, role_model, buckets):
    """[h, w, bucket, model] for every bucket of the engine's list that a
    group of cameras can be dispatched in (a configuration that states the
    one bucket its fleet fills gets one program a model)."""
    groups = collections.Counter((role_model[c[2]], c[3], c[4]) for c in cams)
    out = []
    for (model, h, w), n in sorted(groups.items()):
        top = next((b for b in buckets if b >= n), buckets[-1])
        out.extend([h, w, b, model] for b in buckets if b <= top)
    return out


def run(workload: str, seed: int, seconds: float, trace: bool,
        require_chip: bool = True, bench: dict | None = None) -> dict:
    """Drive one cell; returns the result line as a dict. ``require_chip``
    is False only in the tests' CPU rehearsal, whose line then names ``cpu``
    and carries no device metric."""
    from vbench import correct, flops, loader, trace_reduce, weights
    from vbench import traffic as traffic_mod

    cell = loader.cell(workload, bench)
    config, traffic = cell["config"], cell["traffic"]
    cams = traffic_mod.cameras(traffic, seed)
    role_model = dict(config["roles"])
    try:
        from video_edge_ai_proxy_tpu.bus.native import build_library
    except ImportError as exc:
        raise SystemExit(f"the system under test is not here: {exc}")
    # the TPU runtime takes ~10 s to come up (it premaps 13.9 GB of host
    # memory); the publishers start and the program's modules import
    # meanwhile. The publishers are plain children that never touch jax.
    import jax

    boot = threading.Thread(target=lambda: jax.devices(), daemon=True)
    boot.start()
    build_library()             # once, before the publishers race for it

    shm_dir = tempfile.mkdtemp(
        prefix="vbench_", dir="/dev/shm" if os.path.isdir("/dev/shm") else None)
    pubs = traffic_mod.Publishers(shm_dir, cell["traffic_path"], seed)
    phases = {"publishers_spawned": time.monotonic() - _T0}
    eng = bus = tracer = spans_were = None
    trace_dir = None
    shipped_specs = []          # the registry's own entries, put back after
    try:
        from video_edge_ai_proxy_tpu.bus.shm_bus import ShmFrameBus
        from video_edge_ai_proxy_tpu.engine import InferenceEngine
        from video_edge_ai_proxy_tpu.models import registry
        from video_edge_ai_proxy_tpu.obs import tracer
        from video_edge_ai_proxy_tpu.utils import compile_cache
        from video_edge_ai_proxy_tpu.utils.config import EngineConfig

        phases["program_imported"] = time.monotonic() - _T0
        boot.join()
        devices = jax.devices()
        dev = devices[0]
        on_chip = dev.platform == "tpu"
        if require_chip and (not on_chip
                             or len(devices) < cell["workload"]["chips"]):
            raise SystemExit(
                f"needs {cell['workload']['chips']} TPU chip(s); jax found "
                f"{len(devices)} x {dev.platform}")
        phases["device_up"] = time.monotonic() - _T0
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        cache_dir = compile_cache.configure(os.path.join(ROOT, ".jax_cache"))
        peak = loader.peak(cell["peaks"], dev.device_kind) if on_chip else None

        # -- weights from the seed, on the device, one jitted call a model
        model_of, flat, kept_of = {}, {}, {}
        models = loader.models(config)
        for salt, m in enumerate(models):
            name = m["registry_model"]
            base = registry.get(name)
            shipped_specs.append(base)
            module = base.build()
            fam = loader.family(m["family"])
            # the configuration file is what is run: refuse a registry
            # model whose sizes differ from it
            bad = fam.check_sizes(module, m["sizes"])
            if bad:
                raise SystemExit(f"configuration file and registry model "
                                 f"disagree (file, program): {bad}")
            flat[name] = weights.generate(seed, m["family"], m["sizes"], salt)
            registry.register(seeded_spec(base, weights.as_variables(
                flat[name], fam.template(base, module))))
            model_of[name] = m
            kept_of[name] = fam.kept
            phases[f"weights_{name}"] = time.monotonic() - _T0
        default_model = models[0]["registry_model"]
        phases["weights_done"] = time.monotonic() - _T0

        # -- the engine, shipped defaults plus the configuration's overrides
        overrides = {k: v["value"] for k, v in config["engine"].items()}
        if "batch_buckets" in overrides:
            overrides["batch_buckets"] = tuple(overrides["batch_buckets"])
        ecfg = EngineConfig(model=default_model, compile_cache_dir=cache_dir,
                            stage_trace=trace, **overrides)
        ecfg.prewarm = prewarm_entries(cams, role_model,
                                       tuple(ecfg.batch_buckets))
        by_id = {c[1]: c for c in cams}
        bus = ShmFrameBus(shm_dir)
        eng = InferenceEngine(
            bus, ecfg,
            model_resolver=lambda d: role_model[by_id[d][2]]
            if d in by_id else "")
        fps = float(traffic["fps"])
        eng.stage_records = collections.deque(
            maxlen=max(4096, int(len(cams) * (seconds + 60.0) * fps)))
        # the program's own lineage spans, every frame: which frames the
        # collector read is what says what each clip was made of
        spans_were = (tracer.enabled, tracer.sample_every, tracer.ring)
        tracer.clear()
        tracer.configure(enabled=True, sample_every=1, ring=4096)
        rss = {"start": rss_gb()}
        eng.warmup()
        phases["engine_built"] = time.monotonic() - _T0
        log(f"engine warm-up: {len(ecfg.prewarm)} programs "
            f"{[(e[3], e[2]) for e in ecfg.prewarm]}")

        # -- the cameras free-run from here on; the engine's first tick
        # finds a frame from every one of them
        pubs.wait_ready()
        wall_minus_mono = time.time() - time.monotonic()
        t0 = time.monotonic() + 0.1
        pubs.run(t0, wall_minus_mono)
        time.sleep(0.1 + 2.0 / fps)
        phases["cameras_running"] = time.monotonic() - _T0
        eng.start()             # compiles (or loads) every prewarm entry
        phases["programs_warm"] = time.monotonic() - _T0

        # -- subscriber: every result, with its receive time and what its
        # model's family compares of it
        received, recv_lock = [], threading.Lock()

        def subscriber():
            for res in eng.subscribe():
                now = time.monotonic()
                kept = kept_of.get(res.model)
                rec = {"device_id": res.device_id, "packet": res.frame_packet,
                       "timestamp": res.timestamp, "model": res.model,
                       "kept": kept(res) if kept else None,
                       "t": now}
                with recv_lock:
                    received.append(rec)

        sub = threading.Thread(target=subscriber, name="vbench-sub",
                               daemon=True)
        sub.start()

        # -- warm: every clip window full and every camera answered, so
        # every program and buffer of the cell has been through the device
        t_give_up = time.monotonic() + WARM_WAIT_S
        while True:
            with recv_lock:
                n_of = collections.Counter(r["device_id"] for r in received)
            if all(n_of[c[1]] >= WARM_ROUNDS for c in cams):
                break
            if time.monotonic() > t_give_up:
                _dump_state(eng, received)
                raise SystemExit(
                    f"warm-up: {sum(1 for c in cams if n_of[c[1]])} of "
                    f"{len(cams)} cameras answered in {WARM_WAIT_S:.0f} s; "
                    "the cell does not run")
            time.sleep(0.005)
        rss["warm"] = rss_gb()
        with recv_lock:
            for r in received:
                phases.setdefault(f"first_result_{r['model']}", r["t"] - _T0)
        compiles0 = len(eng.perf.snapshot()["compiles"])
        h2d0 = {(h["model"], h["bucket"]): (h["bytes"], h["seconds"])
                for h in eng.perf.snapshot()["h2d"]}
        shed0 = eng.shed_frames

        # -- the measured window
        mark = None
        if trace:
            from jax.profiler import ProfileOptions

            trace_dir = tempfile.mkdtemp(prefix="vbench_trace_")
            # Device events only. The host tracer records every DMA
            # descriptor of a 1.6 GB placement: tens of GB of host memory in
            # seconds (it ended a 40 GiB machine). The trace's clock starts
            # when start_trace is called, to a few ms (read on the chip).
            po = ProfileOptions()
            po.python_tracer_level = 0
            po.host_tracer_level = 0
            po.enable_hlo_proto = False
            mark = time.monotonic()
            jax.profiler.start_trace(trace_dir, profiler_options=po)
        t_start = time.monotonic()
        setup_s = t_start - _T0
        time.sleep(seconds)
        t_end = t_start + seconds
        with recv_lock:
            results = [r for r in received if r["t"] < t_end]
        if trace:
            jax.profiler.stop_trace()
        rss["window"] = rss_gb()
        peak_bytes = int((dev.memory_stats() or {}).get(
            "peak_bytes_in_use", 0))
        snap = eng.perf.snapshot()
        stage = list(eng.stage_records)
        ladder = eng.ladder.snapshot() if eng.ladder is not None else {}
        shed = eng.shed_frames - shed0
        events = tracer.events()
        late = pubs.halt()
        eng.stop()
        eng = None
        phases["engine_stopped"] = time.monotonic() - _T0
    finally:
        pubs.stop()
        if eng is not None:
            eng.stop()
        if bus is not None:
            bus.close()
        shutil.rmtree(shm_dir, ignore_errors=True)
        if spans_were is not None:
            tracer.clear()
            tracer.configure(enabled=spans_were[0], sample_every=spans_were[1],
                             ring=spans_were[2])
        if shipped_specs:
            from video_edge_ai_proxy_tpu.models import registry as _registry

            for spec in shipped_specs:      # and this run's weights let go
                _registry.register(spec)

    # -- end-to-end numbers, over all the results of the window
    def due_of(r):
        return traffic_mod.due_time(traffic, by_id[r["device_id"]], t0,
                                    r["packet"])

    in_window = [r for r in results if r["t"] >= t_start]
    lat_ms = [(r["t"] - due_of(r)) * 1000.0 for r in in_window
              if r["device_id"] in by_id]
    n_of = collections.Counter(r["device_id"] for r in in_window)
    rounds = max(n_of.values(), default=0)
    model_of_camera = {c[1]: model_of[role_model[c[2]]] for c in cams}
    failed = correct.unanswered(
        events, results, cams,
        {d: loader.family(m["family"]).sample_frames(m["sizes"])
         for d, m in model_of_camera.items()},
        t_start, t_end, wall_minus_mono)
    attempted = len(in_window) + failed
    e2e = {
        "latency_p50_ms": percentile(lat_ms, 50) if lat_ms else None,
        "latency_p95_ms": percentile(lat_ms, 95) if lat_ms else None,
        "setup_s": setup_s,
    }

    # -- correct: routing of every result, a seeded sample by the reference
    def stamp_of(cam, k):
        return traffic_mod.stamp_ms(
            traffic_mod.due_time(traffic, cams[cam], t0, k), wall_minus_mono)

    numbers = {"misrouted": correct.routing_errors(
        results, cams, role_model, stamp_of)}
    numbers["window_compiles"] = len(snap["compiles"]) - compiles0
    sample = correct.draw_sample(
        correct.eligible(in_window, correct.reads_by_camera(events),
                         model_of_camera),
        SAMPLE_PER_MODEL, seed)
    t_ref = time.monotonic()
    if sample and shed == 0:
        rows = correct.reference_rows(sample, cams, seed, model_of, flat,
                                      loader.reference)
        numbers.update(correct.compare(
            [r["kept"] for r in sample], rows, [r["model"] for r in sample],
            model_of))
    ref_s = time.monotonic() - t_ref
    phases["reference_from"] = t_ref - _T0
    rss["reference"] = rss_gb()
    ok, checks = correct.verdict(numbers, config["limits"])
    ok = ok and failed == 0 and bool(lat_ms)

    # -- per-layer numbers (the traced run)
    metrics = {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell["workload"]["chips"],
              "memory_peak_bytes": peak_bytes}
    out = {"correct": ok, "attempted": attempted, "failed": failed}
    if trace:
        ctx = {
            "cell": cell, "seconds": seconds, "t_start": t_start,
            "rounds": rounds,
            "late_s": [x[1] for x in late if t_start <= x[0] < t_end],
            "stage": [s for s in stage
                      if t_start <= s["t_emitted"] - wall_minus_mono < t_end],
            "wall_minus_mono": wall_minus_mono,
            "h2d": _h2d_delta(snap["h2d"], h2d0),
            "results": in_window, "peak": peak,
            "trace": None, "model_of": model_of, "cams": cams,
            "role_model": role_model, "flops": flops,
        }
        if on_chip:
            xp = trace_reduce.read_xplane(trace_reduce.find_xplane(trace_dir))
            ctx["trace"] = _window_trace(xp, mark, t_start, seconds,
                                         config["step_modules"], trace_reduce)
            device["busy_s"] = ctx["trace"]["busy_s"]
            device["window_s"] = seconds
            out["breakdown"] = _breakdown(ctx, trace_reduce)
        for m in cell["per_layer"]:
            value = loader.layer_metric(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell["end_to_end"]:
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    out["metrics"] = metrics
    out["device"] = device
    phases["window_open"] = setup_s
    out["notes"] = {
        "end_to_end": e2e, "reference_s": ref_s, "sampled": len(sample),
        "unjudged": {k: v for k, v in numbers.items()
                     if k not in config["limits"]},
        "results_in_window": len(in_window), "rounds": rounds,
        "answers_per_camera": sorted(collections.Counter(
            n_of[c[1]] for c in cams).items()),
        "shed_frames": shed, "ladder": ladder,
        "host_rss_gb": rss, "setup_phases_s": phases,
        "generator_late_max_ms": max((x[1] for x in late), default=0.0)
        * 1000.0,
        "frames_published": len(late), "fps": fps,
        "buckets_used": sorted({(h["model"], h["bucket"])
                                for h in snap["h2d"] if h["batches"]}),
    }
    if trace:
        from vbench import spans

        w = ctx["wall_minus_mono"]
        out["notes"]["batches"] = [
            {"n": b["n"], "bucket": b["bucket"],
             "pub_to_collect_ms": round((b["t_collect"] - b["pub_s"]) * 1e3),
             "dispatch_ms": round((b["t_submit"] - b["t_collect"]) * 1e3),
             "step_wait_ms": round((b["t_drained"] - b["t_submit"]) * 1e3),
             "emit_ms": round((b["t_emitted"] - b["t_drained"]) * 1e3),
             "at_s": round(b["t_collect"] - w - t_start, 3)}
            for b in spans.batches(ctx["stage"])][:64]
        out["notes"]["h2d"] = [
            {k: h[k] for k in ("model", "bucket", "batches", "mbps",
                               "hidden_pct")} for h in snap["h2d"]]
    out["notes"]["wall_s"] = time.monotonic() - _T0
    out["checks"] = checks
    return out


def _dump_state(eng, received) -> None:
    """What the engine was doing when a run gave up (stderr only)."""
    import faulthandler

    snap = eng.perf.snapshot()
    log("state:", json.dumps({
        "ticks": eng.ticks, "batches": eng.batches,
        "shed_frames": eng.shed_frames,
        "ladder": eng.ladder.snapshot() if eng.ladder else None,
        "h2d": snap["h2d"], "compiles": len(snap["compiles"]),
        "aot_fallbacks": snap["aot_fallbacks"],
        "received_by_packet": sorted(collections.Counter(
            r["packet"] for r in received).items()),
        "streams_seen": len(eng.stats()),
        "subscriber_drops": eng.subscriber_drops}))
    faulthandler.dump_traceback(file=sys.stderr, all_threads=True)


def _h2d_delta(now: list, before: dict) -> dict:
    b = s = 0.0
    for h in now:
        b0, s0 = before.get((h["model"], h["bucket"]), (0, 0.0))
        b += h["bytes"] - b0
        s += h["seconds"] - s0
    return {"bytes": b, "seconds": s}


def _window_trace(xp, mark, t_start, seconds, step_modules, tr) -> dict:
    """The device's events of the window, on the harness's monotonic clock."""
    if not xp["devices"]:
        raise SystemExit("the trace holds no device plane")
    shift = mark                            # trace clock -> monotonic
    busy, ops_all, mods_all, step_s, step_runs, lines = [], [], [], 0.0, 0, {}
    for name, d in sorted(xp["devices"].items()):
        ops = tr.clip([(n, s + shift, dur) for n, s, dur in d["ops"]],
                      t_start, t_start + seconds)
        mods = tr.clip([(n, s + shift, dur) for n, s, dur in d["modules"]],
                       t_start, t_start + seconds)
        busy.append(tr.busy_seconds(ops))
        ops_all.append(ops)
        mods_all.append(mods)
        s, r = tr.module_seconds(mods, step_modules)
        step_s += s
        step_runs += r
        lines[name] = d["lines"]
    n = len(busy)
    return {"busy_s": sum(busy) / n, "ops": ops_all[0], "step_s": step_s / n,
            "step_runs": step_runs, "lines": lines, "module_events": mods_all[0],
            "modules": sorted({m[0].split("(", 1)[0]
                               for d in xp["devices"].values()
                               for m in d["modules"]})}


def _breakdown(ctx, tr) -> dict:
    """Top device operations, and the idle gaps by what the engine's tick
    thread was doing (from its stage records: it collects a round's
    batch, waits for the placement and dispatches, then collects the next;
    the drain runs beside it)."""
    from vbench import spans

    t = ctx["trace"]
    w = ctx["wall_minus_mono"]
    host, prev = [], ctx["t_start"]
    for b in spans.batches(ctx["stage"]):
        c, sub = b["t_collect"] - w, b["t_submit"] - w
        host += [("collect", min(prev, c), c), ("h2d", c, sub)]
        prev = sub
    idle = tr.gaps(t["ops"], ctx["t_start"], ctx["t_start"] + ctx["seconds"])
    return {"device_ops": tr.top_ops(t["ops"]),
            "idle_gaps": tr.name_gaps(idle, host, "collect, not yet emitted")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    out = run(args.workload, args.seed, args.seconds, bool(args.trace))
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    log(f"correct={out['correct']} attempted={out['attempted']} "
        f"failed={out['failed']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
