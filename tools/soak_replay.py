"""Replay-driven chaos soak + determinism + e2e latency harness.

The r6 operational-confidence tool (ISSUE r6 acceptance). Three legs, each
writing into one committed artifact:

1. **Determinism** — record a synthetic multi-camera trace, replay it
   TWICE through the lockstep pipeline (bus -> collector -> serving step,
   replay/harness.py), and require byte-identical content checksums
   (replay/checksum.py). A seeded numerics fault must move the value
   (tests/test_replay.py proves the negative control).
2. **Chaos soak** (``--duration``, >=120 s for the acceptance run) — the
   full mixed fleet (6 detect + 5 embed + 5 classify) on one engine with
   per-stream model routing, driven by replay cameras under a scripted
   FaultPlan (camera kill/re-add, frame-gap burst, bus stall, slow
   subscriber). Records per-family latency percentiles, bucket_fill over
   time, step-cache stability, and cross-family result misrouting (must
   be zero).
3. **E2E** (``--e2e``, on by default) — a real Server with a subprocess
   ingest worker reading ``replay://`` through the shm bus, engine and
   gRPC serve, measured publish->client-receive: the first true
   single-path latency percentile artifact (``E2E_r06.json``).

This tool checks ORCHESTRATION correctness, so by default it is a CPU
rehearsal: it pins the CPU backend (tiny model twins, same serving
families) whatever device is attached, gates on counts and checksums,
and prints no latency or rate — the artifacts it writes stamp
``"backend": "cpu"`` and their wall-clock fields are host numbers, not
device metrics. ``--native`` keeps the attached device for the
single-process legs (lockstep, soak, e2e); the multi-member ``--fleet``
leg is always CPU (N member processes cannot share one chip).

Usage:
  python tools/soak_replay.py --duration 120            # acceptance run
  python tools/soak_replay.py --duration 20 --no-e2e    # quick smoke
  python tools/soak_replay.py --duration 20 --no-e2e \
      --faults uplink_down,bus_flap,device_stall        # chaos smoke

With ``--faults`` the soak runs the resilience fault script instead of
the churn plan and gates hard on the resilience invariants: annotation
conservation (delivered + explicit spool evictions == published — zero
silent loss), a fully-drained uplink at exit (zero deadlocks), and
subscriber drops bounded by the frame budget. ``make chaos-smoke`` runs
all three kinds deterministically.

``--fleet N`` replaces the three legs with the r14 fleet-telemetry leg:
N member Server subprocesses (each a full replay worker -> shm bus ->
engine -> gRPC/REST pipeline) under one FleetAggregator, hard-gating a
lint-clean merged exposition, every member present, at least one fully
cross-process-stitched trace (worker -> bus -> engine -> client via the
on-wire trace_id) and merged-counter conservation; artifact
``FLEETOBS_r01.json`` (``make fleet-obs-smoke``).

``--faults`` also accepts the r10 output-quality kinds (black_frame,
frozen_frame, score_drift): the soak then arms the quality tracker at
soak-scale hysteresis plus a live canary loop and HARD-GATES that every
injected quality fault was detected (verdict transition within the
latency bound; canary mismatch + watchdog episode for score_drift) with
ZERO false-positive verdicts over the clean remainder of the window. The
quality attribution section is written to ``--quality-out``
(``QUALITY_r07.json``). ``make quality-smoke`` runs all three.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--duration", type=float, default=120.0,
                    help="chaos-soak measured window, seconds (>=120 for "
                         "the acceptance artifact)")
    ap.add_argument("--out", default="SOAK_r06.json",
                    help="soak+determinism artifact path")
    ap.add_argument("--e2e", action="store_true", default=True)
    ap.add_argument("--no-e2e", dest="e2e", action="store_false")
    ap.add_argument("--e2e-out", default="E2E_r06.json")
    ap.add_argument("--e2e-duration", type=float, default=30.0)
    ap.add_argument("--native", action="store_true",
                    help="run the single-process legs on the attached "
                         "device instead of the CPU rehearsal")
    ap.add_argument("--model", default="",
                    help="lockstep/e2e model (default: tiny_yolov8 on "
                         "cpu, yolov8n otherwise)")
    ap.add_argument("--frames", type=int, default=240,
                    help="frames per camera in the determinism trace")
    ap.add_argument("--size", default="128x96",
                    help="camera geometry WxH (tiny models want small "
                         "frames)")
    ap.add_argument("--trace-out", default="",
                    help="write the soak's sampled frame-lineage spans as "
                         "Chrome trace-event JSON (load in Perfetto / "
                         "chrome://tracing; validate with "
                         "tools/obs_export.py --check)")
    ap.add_argument("--faults", default="",
                    help="comma list of resilience (uplink_down, bus_flap, "
                         "device_stall) and/or quality (black_frame, "
                         "frozen_frame, score_drift) fault kinds for the "
                         "soak, scheduled in disjoint windows; omitted = "
                         "the default churn plan")
    ap.add_argument("--quality-out", default="QUALITY_r07.json",
                    help="quality attribution artifact path (written only "
                         "when --faults selects quality kinds)")
    ap.add_argument("--profile-on-burn", action="store_true",
                    help="arm obs/prof.py burn-triggered captures in the "
                         "soak engine (soak-scale trigger knobs) and "
                         "HARD-GATE that at least one triggered capture "
                         "bundle exists on disk when faults fired — the "
                         "'profile the excursion in the act' acceptance "
                         "check (make prof-smoke)")
    ap.add_argument("--prof-dir", default="",
                    help="retention-ring directory for --profile-on-burn "
                         "bundles (default: a fresh temp dir; printed in "
                         "the prof leg)")
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="r14 fleet-telemetry leg INSTEAD of the three "
                         "default legs: N member Server subprocesses + "
                         "one FleetAggregator, hard-gating merged-page "
                         "lint, member presence, cross-process trace "
                         "stitching and counter conservation "
                         "(make fleet-obs-smoke)")
    ap.add_argument("--fleet-out", default="FLEETOBS_r01.json",
                    help="fleet-telemetry artifact path (--fleet)")
    ap.add_argument("--fleet-duration", type=float, default=12.0,
                    help="per-member replay window for --fleet, seconds")
    args = ap.parse_args(argv)

    import jax

    if not args.native:
        jax.config.update("jax_platforms", "cpu")
    backend = jax.default_backend()

    from video_edge_ai_proxy_tpu.replay.checksum import check_golden
    from video_edge_ai_proxy_tpu.replay.harness import (
        lockstep_checksum, run_e2e, run_fleet_soak,
    )
    from video_edge_ai_proxy_tpu.replay.recorder import record_synthetic_trace

    model = args.model or ("yolov8n" if backend == "tpu" else "tiny_yolov8")
    try:
        w, h = (int(v) for v in args.size.lower().split("x"))
    except ValueError:
        ap.error(f"--size must be WxH, got {args.size!r}")

    # -- fleet-telemetry leg (--fleet N): replaces the default legs -------
    if args.fleet:
        from video_edge_ai_proxy_tpu.replay.harness import run_fleet_obs

        fleet = run_fleet_obs(
            n_members=args.fleet, duration_s=args.fleet_duration,
            width=w, height=h, model=model)
        fleet["tool"] = "soak_replay"
        fleet["backend"] = "cpu"
        with open(args.fleet_out, "w") as f:
            json.dump(fleet, f, indent=2)
            f.write("\n")
        gates = fleet["gates"]
        print(json.dumps({
            "leg": "fleet", "artifact": args.fleet_out,
            "members": fleet["members"], "gates": gates,
            "client_results": fleet["client_results"],
            "health": [
                {k: row[k] for k in ("instance", "score", "up", "stale",
                                     "ladder_rung", "streams")}
                for row in fleet["health"]],
        }), flush=True)
        failures = []
        if not gates["merged_lint_clean"]:
            failures.append(
                f"merged exposition lint: {fleet['lint_errors']}")
        if not gates["member_lint_clean"]:
            failures.append("a member /metrics page failed lint")
        if not gates["all_members_present"]:
            failures.append(
                f"member missing/stale at quiesce: {fleet['health']}")
        if not gates["stitched_traces"]:
            failures.append(
                "no fully-stitched cross-process trace (worker -> bus -> "
                "engine -> client)")
        if not gates["counters_conserved"]:
            failures.append(
                f"merged counters != member sums: "
                f"{fleet['counter_mismatches']}")
        if not gates["fleet_trace_valid"]:
            failures.append(
                f"merged fleet timeline invalid: "
                f"{fleet['trace_problems']}")
        if failures:
            raise SystemExit("fleet obs failure: " + "; ".join(failures))
        return

    artifact: dict = {"tool": "soak_replay", "backend": backend}
    # Latencies and rates are device metrics: printed from a chip run
    # only. A CPU rehearsal prints counts, checksums and gates.
    on_chip = backend == "tpu"

    # -- leg 1: record -> replay x2 determinism ---------------------------
    tmp = tempfile.mkdtemp(prefix="vep_replay_")
    trace_path = os.path.join(tmp, "determinism.vtrace")
    record_synthetic_trace(
        trace_path, ["det0", "det1"], width=w, height=h, fps=30.0,
        gop=30, frames=args.frames)
    t0 = time.monotonic()
    run1 = lockstep_checksum(trace_path, model=model)
    run2 = lockstep_checksum(trace_path, model=model)
    det = {
        "trace_frames": run1["frames"],
        "model": model,
        "checksum_run1": run1["checksum"],
        "checksum_run2": run2["checksum"],
        "identical": run1["checksum"] == run2["checksum"],
        "seconds": round(time.monotonic() - t0, 1),
    }
    if not det["identical"]:
        raise SystemExit(
            f"replay determinism failure: two replays of {trace_path} "
            f"produced {run1['checksum']} != {run2['checksum']}")
    # Same pinned trace recipe + pinned weights across runs of this tool:
    # golden-gate the value per backend (record-only when missing).
    key = f"soak:lockstep:{model}:{backend}:{args.frames}f"
    det["checksum_key"] = key
    det["checksum_golden"] = check_golden(
        key, run1["checksum"], tool="soak_replay")
    artifact["determinism"] = det
    print(json.dumps({"leg": "determinism", **det}), flush=True)

    # -- leg 2: chaos soak ------------------------------------------------
    fault_plan = None
    quality_kinds: tuple = ()
    if args.faults:
        from video_edge_ai_proxy_tpu.replay.faults import (
            KINDS, QUALITY_KINDS, RESILIENCE_KINDS, FaultPlan,
        )
        kinds = [k.strip() for k in args.faults.split(",") if k.strip()]
        bad = sorted(set(kinds) - set(KINDS))
        if bad:
            ap.error(f"unknown fault kind(s) {bad}; choose from "
                     f"{sorted(RESILIENCE_KINDS + QUALITY_KINDS)}")
        churn = sorted(
            set(kinds) - set(RESILIENCE_KINDS) - set(QUALITY_KINDS))
        if churn:
            ap.error(f"--faults selects resilience/quality kinds only "
                     f"({sorted(RESILIENCE_KINDS + QUALITY_KINDS)}); the "
                     f"churn kinds {churn} run in the default plan when "
                     f"--faults is omitted")
        rkinds = [k for k in kinds if k in RESILIENCE_KINDS]
        quality_kinds = tuple(k for k in kinds if k in QUALITY_KINDS)
        if rkinds:
            fault_plan = FaultPlan.resilience(args.duration, kinds=rkinds)
        # quality kinds ride through run_fleet_soak(quality_kinds=...),
        # which schedules them and arms the tracker + canary; with no
        # resilience kinds selected, fault_plan stays None and the
        # harness suppresses the churn plan for a clean quality window.
    soak = run_fleet_soak(duration_s=args.duration, src_hw=(h, w),
                          fault_plan=fault_plan,
                          profile_on_burn=args.profile_on_burn,
                          prof_dir=args.prof_dir or None,
                          quality_kinds=quality_kinds)
    artifact["soak"] = soak
    print(json.dumps({
        "leg": "soak",
        "duration_s": soak["duration_s"],
        "streams": soak["streams"],
        "results_measured": soak["results_measured"],
        "misrouted_results": soak["misrouted_results"],
        "subscriber_drops": soak["subscriber_drops"],
        "step_cache": soak["step_cache"]["final"],
        "step_cache_stable": soak["step_cache"]["stable"],
        **({"per_family_latency_ms": soak["per_family_latency_ms"],
            "stage_breakdown": soak["obs"]["stage_breakdown"]}
           if on_chip else {}),
    }), flush=True)
    if soak["misrouted_results"]:
        raise SystemExit(
            f"soak failure: {soak['misrouted_results']} results crossed "
            f"model families (examples: {soak['misrouted_examples']})")
    res = soak["resilience"]
    uplink = res["uplink"]
    print(json.dumps({
        "leg": "resilience",
        "ladder": res["ladder"],
        "shed_frames": res["shed_frames"],
        "breaker": uplink["breaker"],
        "published": uplink["published"],
        "delivered_events": uplink["delivered_events"],
        "post_failures": uplink["post_failures"],
        "spool": {k: uplink["spool"][k] for k in (
            "spooled_batches", "drained_batches", "dropped_events",
            "pending_batches")},
        "conserved": uplink["conserved"],
    }), flush=True)
    # r9: device-performance attribution + SLO burn state. Informational
    # (the artifact's "perf"/"slo" sections carry the full detail): a
    # long CPU soak may legitimately burn the fps objective — that's the
    # SLO engine working, not a soak failure.
    slo = soak.get("slo")
    print(json.dumps({
        "leg": "slo",
        **({"fps": soak["perf"]["fps"]} if on_chip else {}),
        "compiled_programs": sum(
            rec["programs"] for rec in soak["perf"]["compiles"]),
        "burning": slo["burning"] if slo else None,
        "burn": {name: s["burn"] for name, s in slo["slos"].items()}
        if slo else None,
        "episodes": {name: s["episodes"]
                     for name, s in slo["slos"].items()} if slo else None,
    }), flush=True)
    # r10: burn-triggered profiling. The gate is the acceptance check —
    # when faults fired with --profile-on-burn, at least one TRIGGERED
    # capture bundle must exist on disk with its device trace, span
    # window and snapshot all linked from the manifest ("profile the
    # excursion, not the average" — merge it with obs_export.py --merge).
    if args.profile_on_burn:
        prof = soak.get("prof") or {}
        triggered = [
            m for m in prof.get("captures", [])
            if m.get("trigger") in ("slo_episode", "ladder_escalation")
        ]
        print(json.dumps({
            "leg": "prof",
            "dir": prof.get("dir"),
            "bundles": prof.get("bundles"),
            "retained_bytes": prof.get("retained_bytes"),
            "errors": prof.get("errors"),
            "triggered_captures": [
                {k: m.get(k) for k in (
                    "bundle", "trigger", "wall_ms", "span_events",
                    "slo_episode", "error")}
                for m in triggered
            ],
        }), flush=True)
        if soak["faults_applied"]:
            ok = [
                m for m in triggered
                if m.get("error") is None
                and m.get("device_trace")
                and os.path.isfile(os.path.join(m["path"], "manifest.json"))
                and os.path.isfile(
                    os.path.join(m["path"], m["device_trace"]))
                and os.path.isfile(os.path.join(m["path"], m["spans"]))
            ]
            if not ok:
                raise SystemExit(
                    "prof failure: faults fired but no intact "
                    "burn-triggered capture bundle exists (triggered="
                    f"{len(triggered)}, errors={prof.get('errors')}, "
                    f"dir={prof.get('dir')}) — the excursion went "
                    "unprofiled")
    # r10 quality gates: every injected quality fault detected within the
    # latency bound, ZERO false-positive verdicts anywhere in the soak
    # window outside the fault windows, and the canary integrity loop
    # fired (>=1 watchdog episode) iff score_drift was injected.
    if quality_kinds:
        quality = soak.get("quality")
        if not quality:
            raise SystemExit(
                "quality failure: quality kinds were requested but the "
                "soak produced no quality section — tracker never armed")
        # Bound: soak-scale enter hysteresis (0.6 s) + observation
        # cadence + verdict-window lag, with CPU-soak scheduling slack.
        latency_bound_s = 5.0
        quality["latency_bound_s"] = latency_bound_s
        print(json.dumps({
            "leg": "quality",
            "faults": [
                {k: f.get(k) for k in (
                    "kind", "device_id", "detected", "latency_s",
                    "latency_ticks", "mismatch_cycles")}
                for f in quality["faults"]
            ],
            "false_positives": quality["false_positives"],
            "canary": {k: (quality["canary"] or {}).get(k) for k in (
                "loop_len", "match_cycles", "mismatch_cycles",
                "void_cycles")},
            "canary_watchdog_episodes":
                quality["canary_watchdog_episodes"],
            "latency_bound_s": latency_bound_s,
        }), flush=True)
        with open(args.quality_out, "w") as f:
            json.dump(quality, f, indent=2)
            f.write("\n")
        for rep in quality["faults"]:
            if not rep["detected"]:
                raise SystemExit(
                    f"quality failure: injected {rep['kind']} on "
                    f"{rep['device_id'] or '<global>'} at "
                    f"{rep['at_s']}s went undetected")
            if rep["latency_s"] is not None and \
                    rep["latency_s"] > latency_bound_s:
                raise SystemExit(
                    f"quality failure: {rep['kind']} detected but "
                    f"{rep['latency_s']}s late (bound "
                    f"{latency_bound_s}s)")
        if quality["false_positives"]:
            raise SystemExit(
                "quality failure: verdict transitions outside every "
                f"fault window: {quality['false_positives']} — the "
                "hysteresis is flapping on healthy streams")
        drift_armed = "score_drift" in quality_kinds
        episodes = quality["canary_watchdog_episodes"]
        if drift_armed and episodes < 1:
            raise SystemExit(
                "quality failure: score_drift injected but the canary "
                "integrity loop opened no watchdog episode")
        if not drift_armed and episodes:
            raise SystemExit(
                f"quality failure: {episodes} canary_integrity episodes "
                "without score_drift injected — false integrity alarm")
    # Chaos gates (ISSUE: zero deadlocks, zero lost annotations, bounded
    # subscriber drops). Reaching this line at all is the deadlock gate's
    # first half; a drained uplink is the second.
    if not uplink["conserved"]:
        raise SystemExit(
            "chaos failure: annotation conservation broken — published="
            f"{uplink['published']} != delivered="
            f"{uplink['delivered_events']} + spool_dropped="
            f"{uplink['spool']['dropped_events']}")
    if uplink["final_queue_depth"] or uplink["spool"]["pending_batches"]:
        raise SystemExit(
            "chaos failure: uplink failed to drain after recovery "
            f"(queue depth {uplink['final_queue_depth']}, spool "
            f"{uplink['spool']['pending_batches']} batches) — wedged "
            "retry/breaker/spool path")
    max_drops = int(args.duration * soak["streams"] * 30.0)
    if soak["subscriber_drops"] > max_drops:
        raise SystemExit(
            f"chaos failure: {soak['subscriber_drops']} subscriber drops "
            f"exceeds the {max_drops} frame budget — drain thread was "
            "blocked, not shedding")
    if args.trace_out:
        # run_fleet_soak leaves its span rings intact after restoring the
        # tracer config, so the export happens here, post-run.
        from video_edge_ai_proxy_tpu.obs import tracer
        from video_edge_ai_proxy_tpu.obs.spans import to_chrome_trace
        trace_obj = to_chrome_trace(tracer.events())
        with open(args.trace_out, "w") as f:
            json.dump(trace_obj, f)
            f.write("\n")
        print(json.dumps({
            "leg": "trace",
            "events": len(trace_obj["traceEvents"]),
            "artifact": args.trace_out,
        }), flush=True)

    # -- leg 3: full-pipeline e2e ----------------------------------------
    if args.e2e:
        e2e = run_e2e(duration_s=args.e2e_duration, width=w, height=h,
                      model=model)
        artifact["e2e"] = e2e
        with open(args.e2e_out, "w") as f:
            json.dump(e2e, f, indent=2)
            f.write("\n")
        print(json.dumps({
            "leg": "e2e",
            "results_measured": e2e["results_measured"],
            **({"latency_ms": e2e["latency_ms"]} if on_chip else {}),
            "artifact": args.e2e_out,
        }), flush=True)

    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=2)
        f.write("\n")
    print(json.dumps({
        "leg": "summary", "artifact": args.out,
        "determinism_ok": det["identical"],
        "misrouted_results": soak["misrouted_results"],
    }), flush=True)


if __name__ == "__main__":
    main()
