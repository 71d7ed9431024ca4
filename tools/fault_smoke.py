"""Device-fault smoke: kill a mesh shard under live serving and prove
the engine detects, fails over to the survivor mesh, keeps the other
shards' stream pins, and conserves every frame outside the declared
fault window (engine/fault.py, ``InferenceEngine._execute_failover``).

Two chaos legs on the CPU twin (8 virtual devices), both scripted as
``shard_fault`` events in a :class:`replay.faults.FaultPlan` so the
injection schedule is part of the artifact:

1. **Hard fault, dp4 -> dp3 (gated)** — an 8-stream blob fleet serves
   on a dp=4 mesh; at the scripted time shard 1's step raises an XLA-
   shaped error carrying ``fault_shard`` (what a real ``XlaRuntimeError``
   naming a dead chip looks like after attribution). Gates: detection
   within 2 engine ticks of the raise, failover wall-clock within
   ``fault_failover_budget_ms``, the dead shard's streams serving again
   on survivors within ``--evac-bound`` seconds, survivor shards keeping
   >= 90% of their pre-fault stream pins, and — after quiesce — the
   FaultLedger balancing to ZERO frames lost or duplicated with every
   ``device_fault`` drop inside the declared window.

2. **Stall on a survivor, dp3 -> dp2 (informational)** — on the mesh
   leg 1 left behind, the dispatch deadline is dropped so the drain
   watchdog's hysteresis opens a stall suspicion, and an injected probe
   attributes it to one shard (the default probe round-trips real
   devices; virtual CPU devices cannot wedge, so the probe verdict is
   the scripted part). Proves the repin composes across cascaded
   faults — a stream that survived failover #1 routes correctly after
   failover #2 — and that stall detection walks suspicion -> probe ->
   failover end to end.

Also gated: ``vep_fault_*`` exposition lint-clean. The ``fault=False``
bit-identity pin (watchdog off = byte-identical serving) lives in
tests/test_fault.py, not here — it needs the golden subprocess anchor.

Runs in ~1 min on the CPU twin; wired as ``make fault-smoke``. One JSON
line on stdout; ``--out`` additionally writes the artifact (committed
as FAULT_r01.json).
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

STREAMS = ["cam0", "cam1", "cam2", "cam3", "cam4", "cam5", "cam6", "cam7"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--prime", type=float, default=5.0,
                    help="seconds of healthy serving before the fault "
                         "so compiles land outside the measurement "
                         "(default 5)")
    ap.add_argument("--settle", type=float, default=5.0,
                    help="seconds of survivor-mesh serving after each "
                         "failover (default 5)")
    ap.add_argument("--evac-bound", type=float, default=5.0,
                    help="gated bound, seconds from failover completion "
                         "to the dead shard's streams serving again "
                         "(default 5)")
    ap.add_argument("--out", default="",
                    help="also write the artifact JSON here")
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_platforms", "cpu")
    backend = jax.default_backend()
    if len(jax.devices()) < 8:
        raise SystemExit(
            f"fault_smoke: need 8 virtual devices, have "
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
    from video_edge_ai_proxy_tpu.replay.faults import FaultEvent, FaultPlan
    from video_edge_ai_proxy_tpu.uplink.queue import AnnotationQueue
    from video_edge_ai_proxy_tpu.utils.config import EngineConfig

    model = "tiny_blob_gauge"
    spec = registry.get(model)
    side = spec.input_size
    blob_w, blob_h = max(8, side // 6), max(8, side // 8)
    span = side - blob_w - 16

    def scene(stream: int, step: int):
        frame = np.full((side, side, 3), 114, np.uint8)
        phase = step % (2 * span)
        x0 = 8 + (phase if phase < span else 2 * span - phase)
        y0 = 8 + 4 * stream
        frame[y0:y0 + blob_h, x0:x0 + blob_w] = blob_color(stream)
        return frame

    # The chaos script: one hard shard kill after the prime window, one
    # stall on the survivor mesh after the first settle window. Committed
    # verbatim in the artifact so a failing run replays exactly.
    hard_shard = 1                      # dp4 numbering
    stall_shard = 1                     # dp3 (post-failover) numbering
    plan = FaultPlan([
        FaultEvent(at_s=args.prime, kind="shard_fault",
                   device_id=str(hard_shard)),
        FaultEvent(at_s=args.prime + args.settle, kind="shard_fault",
                   device_id=str(stall_shard), duration_s=1.0),
    ])

    tmpdir = tempfile.mkdtemp(prefix="vep_fault_smoke_")
    bus = MemoryFrameBus()
    eng = InferenceEngine(
        bus,
        EngineConfig(
            model=model, mesh={"dp": 4},
            batch_buckets=(2, 4, 8), tick_ms=10, prof=False,
            fault=True,
            fault_dispatch_deadline_ms=5000.0,
            fault_hysteresis=2,
            fault_failover_budget_ms=30000.0,
            aot_cache=True,
            aot_cache_dir=os.path.join(tmpdir, "aot"),
        ),
        annotations=AnnotationQueue(handler=lambda batch: True),
    )
    eng.warmup()
    for sid in STREAMS:
        bus.create_stream(sid, side * side * 3)
    results_q: _queue.Queue = _queue.Queue()
    with eng._sub_lock:
        eng._subscribers.append((results_q, None))

    # -- injection: a per-shard failing step wrapper (replay/faults.py
    # shard_fault, hard mode). One shot; otherwise delegates.
    orig_step = eng._step
    inject = {"arm": False, "shard": None, "tick": None, "ts": None}

    def step_with_fault(src_hw, bucket, model=None):
        if inject["arm"]:
            inject["arm"] = False
            inject["tick"] = eng.ticks
            inject["ts"] = time.monotonic()
            exc = RuntimeError(
                f"INTERNAL: injected shard_fault — device for shard "
                f"{inject['shard']} halted")
            exc.fault_shard = inject["shard"]
            raise exc
        return orig_step(src_hw, bucket, model)

    eng._step = step_with_fault

    # Stall-mode injection (second shard_fault event): the probe verdict
    # is scripted — virtual CPU devices cannot actually wedge.
    probe_votes = []

    def scripted_probe():
        if probe_votes:
            return [probe_votes.pop()]
        return []

    def failover_events():
        return [e for e in eng.faults.snapshot()["events"]
                if e.get("event") == "failover"]

    def detected_events():
        return [e for e in eng.faults.snapshot()["events"]
                if e.get("event") == "detected"]

    results = []

    def drain_results():
        while True:
            try:
                r = results_q.get_nowait()
            except _queue.Empty:
                return
            if r is not None:
                results.append((time.monotonic(), r))

    legs = {}
    eng.start()
    try:
        t_start = time.monotonic()
        step = 0
        last_ts = 0
        fired = []
        deadline_restore_at = None
        end_at = t_start + args.prime + 2 * args.settle
        while time.monotonic() < end_at:
            now = time.monotonic()
            for ev in plan.pop_due(now - t_start):
                fired.append(ev)
                if ev.duration_s > 0:
                    # Stall mode: collapse the dispatch deadline so the
                    # drain watchdog's hysteresis trips on real batches,
                    # and script the probe's verdict.
                    probe_votes.append(int(ev.device_id))
                    eng.faults.probe_fn = scripted_probe
                    eng.faults.deadline_ms = 0.01
                    deadline_restore_at = len(failover_events()) + 1
                    legs["stall_armed_ts"] = now
                else:
                    inject["shard"] = int(ev.device_id)
                    inject["arm"] = True
            if deadline_restore_at is not None \
                    and len(failover_events()) >= deadline_restore_at:
                # Failover #2 done: restore the real deadline before
                # healthy batches keep tripping the watchdog.
                eng.faults.deadline_ms = \
                    eng._cfg.fault_dispatch_deadline_ms
                deadline_restore_at = None
            ts = max(int(time.time() * 1000), last_ts + 1)
            last_ts = ts
            for i, sid in enumerate(STREAMS):
                bus.publish(
                    sid, scene(i, step),
                    FrameMeta(width=side, height=side, channels=3,
                              timestamp_ms=ts, is_keyframe=True))
            step += 1
            time.sleep(0.03)
            drain_results()
    finally:
        eng.stop()
    drain_results()
    bus.close()

    snap = eng.faults.snapshot()
    ledger = snap["ledger"]
    fails = failover_events()
    dets = detected_events()

    # -- leg 1: hard fault dp4 -> dp3 ------------------------------------
    hard_det = next((e for e in dets if e["kind"] == "xla_error"), None)
    hard_fail = fails[0] if fails else None
    detect_ticks = (hard_det["tick"] - inject["tick"]
                    if hard_det and inject["tick"] is not None else None)
    # Streams pinned to the dead shard pre-fault must serve again on the
    # survivor mesh: first post-failover result per evacuated stream.
    evac_streams = [sid for sid in STREAMS
                    if stream_shard(sid, 4) == hard_shard]
    evac_first_ms = None
    if hard_fail is not None and inject["ts"] is not None:
        t_fail_done = None
        # note_failover stamps wall time; anchor on the injection's
        # monotonic ts + the reported failover wall instead.
        t_fail_done = inject["ts"] + hard_fail["failover_ms"] / 1000.0
        firsts = {}
        for t_r, r in results:
            if r.device_id in firsts or t_r < t_fail_done:
                continue
            if r.device_id in evac_streams:
                firsts[r.device_id] = (t_r - t_fail_done) * 1000.0
        if len(firsts) == len(evac_streams):
            evac_first_ms = max(firsts.values())
        legs["evac_firsts_ms"] = {k: round(v, 1)
                                  for k, v in sorted(firsts.items())}
    pin_retention = None
    if hard_fail is not None:
        st = hard_fail["streams"]
        surviving = st["total"] - st["repinned"]
        pin_retention = (st["kept"] / surviving) if surviving else None

    # -- leg 2: stall dp3 -> dp2 (informational) -------------------------
    stall_det = next((e for e in dets if e["kind"] == "stall"), None)
    stall_fail = fails[1] if len(fails) > 1 else None
    # Repin composition: a stream that survived failover #1 must route to
    # a live shard after failover #2 (collector shard_fn in range).
    compose_ok = None
    if stall_fail is not None:
        live = eng._shards
        compose_ok = all(
            0 <= eng._shard_of(sid) % live < live for sid in STREAMS)

    text = metrics_registry.render()
    problems = [p for p in lint_exposition(text) if "vep_fault" in p]

    # r23 decision journal: the final event log rides in the artifact,
    # plus the conservation check — every failover the fault plane
    # executed must have a journal event with a non-null quantitative
    # trigger (an unexplained autonomous action is a gate failure).
    journal_events = (eng.journal.events()
                      if eng.journal is not None else [])
    journaled_failovers = [
        ev for ev in journal_events
        if ev["actor"] == "fault" and ev["action"] == "failover"]
    journal_conservation = {
        "failovers": len(fails),
        "journaled": len(journaled_failovers),
        "with_trigger": sum(1 for ev in journaled_failovers
                            if ev.get("trigger")),
        "with_cause": sum(1 for ev in journaled_failovers
                          if ev.get("cause") is not None),
    }

    out = {
        "tool": "fault_smoke",
        "backend": backend,
        "model": model,
        "devices": len(jax.devices()),
        "streams": len(STREAMS),
        "plan": [json.loads(plan.to_json())[i] for i in range(2)],
        "hard_fault": {
            "shard": hard_shard,
            "detected": hard_det,
            "detect_ticks": detect_ticks,
            "failover": hard_fail,
            "evacuated_streams": evac_streams,
            "evac_first_result_ms": (round(evac_first_ms, 1)
                                     if evac_first_ms is not None else None),
            "pin_retention": (round(pin_retention, 3)
                              if pin_retention is not None else None),
            **{k: v for k, v in legs.items() if k == "evac_firsts_ms"},
        },
        "stall_fault": {
            "shard": stall_shard,
            "detected": stall_det,
            "failover": stall_fail,
            "repin_composes": compose_ok,
            "informational": True,
        },
        "ledger": ledger,
        "journal": {"events": journal_events},
        "journal_conservation": journal_conservation,
        "results": len(results),
        "failovers": snap["failovers"],
        "survivor_shards": snap["shards"],
        "exposition_problems": problems,
        "gates": {
            "detect_ticks_max": 2,
            "failover_budget_ms": eng._cfg.fault_failover_budget_ms,
            "evac_bound_ms": args.evac_bound * 1000.0,
            "pin_retention_min": 0.9,
        },
    }
    print(json.dumps(out), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=2)
            f.write("\n")

    # -- gates (leg 1 + ledger + exposition) -----------------------------
    if hard_det is None or hard_fail is None:
        raise SystemExit(
            f"fault_smoke: hard shard fault never detected/failed-over "
            f"(detected={hard_det}, failover={hard_fail})")
    if detect_ticks is None or detect_ticks > 2:
        raise SystemExit(
            f"fault_smoke: detection took {detect_ticks} ticks > 2")
    if hard_fail["over_budget"] or hard_fail["failover_ms"] > \
            eng._cfg.fault_failover_budget_ms:
        raise SystemExit(
            f"fault_smoke: failover took {hard_fail['failover_ms']:.0f} ms "
            f"> budget {eng._cfg.fault_failover_budget_ms:.0f} ms")
    if hard_fail["survivors"] != 3 or hard_fail["shards_dead"] != [1]:
        raise SystemExit(
            f"fault_smoke: wrong failover shape: {hard_fail}")
    if evac_first_ms is None or evac_first_ms > args.evac_bound * 1000.0:
        raise SystemExit(
            f"fault_smoke: evacuated streams not serving within "
            f"{args.evac_bound}s of failover (worst {evac_first_ms} ms, "
            f"firsts {legs.get('evac_firsts_ms')})")
    if pin_retention is None or pin_retention < 0.9:
        raise SystemExit(
            f"fault_smoke: surviving shards kept only "
            f"{pin_retention} of their stream pins (< 0.9)")
    if ledger["lost"] != 0:
        raise SystemExit(
            f"fault_smoke: {ledger['lost']} frames LOST after quiesce — "
            f"conservation broken: {ledger}")
    if ledger["duplicated"] != 0:
        raise SystemExit(
            f"fault_smoke: {ledger['duplicated']} duplicate emissions "
            f"across failover: {ledger}")
    if ledger["lost_outside_window"] != 0:
        raise SystemExit(
            f"fault_smoke: {ledger['lost_outside_window']} frames lost "
            f"OUTSIDE the declared fault window: {ledger}")
    if not ledger["dropped"].get("device_fault"):
        raise SystemExit(
            "fault_smoke: no device_fault drops recorded — the fault "
            "window never exercised the ledger")
    if problems:
        raise SystemExit(
            f"fault_smoke: vep_fault_* exposition not lint-clean: "
            f"{problems}")
    if eng.journal is not None and (
            journal_conservation["journaled"] < len(fails)
            or journal_conservation["with_trigger"]
            < journal_conservation["journaled"]):
        raise SystemExit(
            f"fault_smoke: journal conservation broken — every failover "
            f"needs a journal event with a non-null trigger: "
            f"{journal_conservation}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
