"""Decision-journal smoke: degrade a live engine through a real SLO
burn and prove the control planes explain themselves (obs/journal.py,
ISSUE r23).

Four legs on the CPU twin (8 virtual devices):

1. **Causal chain (gated)** — an 8-stream blob fleet serves with the
   latency objective set below the physically possible end-to-end
   latency, so the detect-latency SLO burns its budget from the first
   evaluation. The chain the acceptance demands then forms on its own:
   ``slo episode_open`` -> ``ladder escalate`` (pressure breakdown says
   ``slo_burning``) -> per-stream ``engine cascade_stretch`` (temporal
   head cadence doubles). Gates: the REAL ``/api/v1/why?stream=S``
   endpoint resolves a root-first chain of >= 3 links, rooted at the
   slo episode with every link carrying a non-null quantitative
   trigger; ``/api/v1/journal?actor=ladder`` filters; conservation —
   every ladder transition the state machine counted has exactly one
   journal event, and the artifact passes the ``tools/obs_export.py
   --journal`` schema validator (100% of autonomous actions
   journaled with triggers, no dangling cause links).

2. **Fleet-merge determinism (gated)** — the same member event lists
   fed to ``merge_journals`` in both scrape-arrival orders must
   produce byte-identical merged logs (ties on wall time collapse to
   the stable ``(ts, member, seq)`` order).

3. **Record overhead (gated)** — mean ``record()`` wall time over
   20 000 events (ring eviction included) must stay under 50 us =
   0.5% of the 10 ms tick budget. The measured number is carried in
   the artifact and quoted in BASELINE.md.

4. **journal=False bit-identity (gated)** — the kill-switch pin:
   the device outputs an engine emits fold the SAME checksum with the
   journal on as with it off (recording is a pure side effect off the
   serving path), and ``journal=False`` leaves no journal object
   anywhere (engine, ladder, slo).

Also gated: ``vep_journal_*`` exposition lint-clean. Runs in ~1 min on
the CPU twin; wired as ``make journal-smoke``. One JSON line on
stdout; ``--out`` additionally writes the artifact (committed as
JOURNAL_r01.json).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# 8 virtual CPU devices: a CPU rehearsal of the mesh path. XLA_FLAGS is
# read when the backend initializes, which nothing has done yet.
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()

STREAMS = ["cam0", "cam1", "cam2", "cam3", "cam4", "cam5", "cam6", "cam7"]

OVERHEAD_EVENTS = 20_000
OVERHEAD_BUDGET_US = 50.0          # 0.5% of a 10 ms tick


class _PM:
    """Process-manager stub for RestServer (journal endpoints only)."""

    def list(self):
        return []


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--burn-bound", type=float, default=30.0,
                    help="gated bound, seconds from first frame to the "
                         "per-stream cascade_stretch event (default 30)")
    ap.add_argument("--out", default="",
                    help="also write the artifact JSON here")
    args = ap.parse_args(argv)

    import jax

    jax.config.update("jax_platforms", "cpu")
    backend = jax.default_backend()
    if len(jax.devices()) < 8:
        raise SystemExit(
            f"journal_smoke: need 8 virtual devices, have "
            f"{len(jax.devices())} — XLA_FLAGS was bound too late")

    import queue as _queue

    import numpy as np

    from tools.obs_export import find_journal, validate_journal
    from video_edge_ai_proxy_tpu.bus.interface import FrameMeta
    from video_edge_ai_proxy_tpu.bus.memory_bus import MemoryFrameBus
    from video_edge_ai_proxy_tpu.engine import InferenceEngine
    from video_edge_ai_proxy_tpu.models import registry
    from video_edge_ai_proxy_tpu.models.blob import blob_color
    from video_edge_ai_proxy_tpu.obs.journal import (
        DecisionJournal, merge_journals,
    )
    from video_edge_ai_proxy_tpu.obs.metrics import (
        lint_exposition, registry as metrics_registry,
    )
    from video_edge_ai_proxy_tpu.serve.rest_api import RestServer
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

    # -- leg 1: live engine, forced SLO burn -----------------------------
    # slo_latency_ms=1 with frames published 150 ms old: every emitted
    # detect frame is a bad SLI event, both burn windows exceed the
    # threshold immediately (warmup_s=0), and the burn is the FIRST
    # pressure the ladder sees (frames stay under the 500 ms staleness
    # bound, queues stay shallow at this publish rate) — so the fresh
    # escalation roots its cause at the slo episode_open event.
    bus = MemoryFrameBus()
    eng = InferenceEngine(
        bus,
        EngineConfig(
            model=model,
            batch_buckets=(2, 4, 8), tick_ms=10,
            prefetch=False, prof=False,
            cascade=True, cascade_model="tiny_videomae",
            cascade_every_n=4,
            slo_latency_ms=1.0, slo_warmup_s=0.0,
            slo_eval_interval_s=0.25,
            ladder_escalate_after_s=0.3,
        ),
        annotations=AnnotationQueue(handler=lambda batch: True),
    )
    assert eng.journal is not None, "journal default-on broke"
    eng.warmup()
    for sid in STREAMS:
        bus.create_stream(sid, side * side * 3)

    def stretch_events():
        return [ev for ev in eng.journal.events(actor="engine",
                                                action="cascade_stretch")
                if ev["subject"] and ev["subject"][0] == "stream"]

    stretched_at_s = None
    eng.start()
    try:
        t_start = time.monotonic()
        step = 0
        deadline = t_start + args.burn_bound
        while time.monotonic() < deadline:
            ts = int(time.time() * 1000) - 150
            for i, sid in enumerate(STREAMS):
                bus.publish(
                    sid, scene(i, step),
                    FrameMeta(width=side, height=side, channels=3,
                              timestamp_ms=ts, is_keyframe=True))
            step += 1
            if stretch_events():
                stretched_at_s = time.monotonic() - t_start
                break
            time.sleep(0.05)
    finally:
        eng.stop()
    bus.close()

    journal_events = eng.journal.events()
    per_stream = stretch_events()
    target = per_stream[0]["subject"][1] if per_stream else STREAMS[0]

    # The acceptance path: the REAL REST endpoint answers why().
    rest = RestServer(_PM(), None, host="127.0.0.1", port=0, engine=eng)
    rest.start()
    try:
        base = f"http://127.0.0.1:{rest.bound_port}"
        with urllib.request.urlopen(
                f"{base}/api/v1/why?stream={target}") as r:
            why = json.loads(r.read())
        with urllib.request.urlopen(
                f"{base}/api/v1/journal?actor=ladder") as r:
            ladder_view = json.loads(r.read())
    finally:
        rest.stop()

    chain_actions = [(ev["actor"], ev["action"]) for ev in why["chain"]]
    chain_triggers_ok = all(ev.get("trigger") for ev in why["chain"])
    ladder_transitions = sum(eng.ladder.transitions.values()) \
        if eng.ladder is not None else 0
    ladder_journaled = len(eng.journal.events(actor="ladder"))
    slo_episodes_open = len(eng.journal.events(actor="slo",
                                               action="episode_open"))

    # Schema + trigger-completeness validation, same code path operators
    # run offline on this artifact (tools/obs_export.py --journal).
    schema_problems = validate_journal(
        find_journal({"journal": {"events": journal_events}}))

    # -- leg 2: fleet-merge determinism ----------------------------------
    t0 = 1_000_000.0
    ev_a = [{"seq": s, "ts": t0 + dt, "actor": "ladder",
             "action": "escalate", "subject": ["ladder", "engine"],
             "trigger": {"to": "shed"}, "cause": None}
            for s, dt in ((1, 0.0), (2, 0.5), (3, 0.5))]
    ev_b = [{"seq": s, "ts": t0 + dt, "actor": "router",
             "action": "migrate", "subject": ["stream", "cam1"],
             "trigger": {"reason": "member_shedding"}, "cause": None}
            for s, dt in ((1, 0.0), (2, 0.5), (3, 1.0))]
    merged_ab = merge_journals({"a": ev_a, "b": ev_b})
    merged_ba = merge_journals({"b": list(reversed(ev_b)),
                                "a": list(reversed(ev_a))})
    merge_deterministic = merged_ab == merged_ba and len(merged_ab) == 6

    # -- leg 3: record() overhead ----------------------------------------
    bench = DecisionJournal(4096)
    causes = [None] * 64
    t_rec = time.perf_counter()
    for i in range(OVERHEAD_EVENTS):
        causes[i % 64] = bench.record(
            "engine", "cascade_stretch",
            subject=("stream", STREAMS[i % len(STREAMS)]),
            trigger={"rung": "shed", "factor": 2, "every_n": 4},
            cause=causes[(i + 1) % 64])
    record_mean_us = (time.perf_counter() - t_rec) / OVERHEAD_EVENTS * 1e6

    # -- leg 4: journal=False bit-identity -------------------------------
    from video_edge_ai_proxy_tpu.replay.checksum import (
        CHECKSUM_MASK, device_checksum, finalize_checksum,
    )

    def checksum_run(journal_on: bool):
        b = MemoryFrameBus()
        try:
            b.create_stream("cam1", side * side * 3)
            e = InferenceEngine(
                b, EngineConfig(model=model, batch_buckets=(1, 2, 4),
                                tick_ms=5, prefetch=False,
                                journal=journal_on),
                annotations=AnnotationQueue(handler=lambda batch: True))
            e.warmup()
            if journal_on:
                assert e.journal is not None
            else:
                # Kill switch leaves no hooks anywhere downstream.
                assert e.journal is None
                assert e.ladder is None or e.ladder.journal is None
            e._drain_q = _queue.Queue(maxsize=8)
            carry = 0
            for f in range(4):
                b.publish("cam1", scene(0, 3 * f),
                          FrameMeta(width=side, height=side, channels=3,
                                    timestamp_ms=int(time.time() * 1000),
                                    is_keyframe=True))
                groups = e._collector.collect()
                e._dispatch(groups, time.perf_counter())
                inflight = e._drain_q.get(timeout=30)
                part = int(np.asarray(device_checksum(inflight.outputs)))
                carry = (carry + part) & CHECKSUM_MASK
                e._emit(inflight)
                e._collector.release(inflight.group)
                e._drain_q.task_done()
            return finalize_checksum(carry)
        finally:
            b.close()

    sum_on, sum_off = checksum_run(True), checksum_run(False)

    text = metrics_registry.render()
    lint_problems = [p for p in lint_exposition(text)
                     if "vep_journal" in p]

    out = {
        "tool": "journal_smoke",
        "backend": backend,
        "model": model,
        "devices": len(jax.devices()),
        "streams": len(STREAMS),
        "chain": {
            "stream": target,
            "stretched_at_s": (round(stretched_at_s, 2)
                               if stretched_at_s is not None else None),
            "why": why,
            "ladder_events_via_rest": len(ladder_view.get("events", [])),
        },
        "conservation": {
            "ladder_transitions": ladder_transitions,
            "ladder_journaled": ladder_journaled,
            "slo_episodes_open": slo_episodes_open,
            "schema_problems": schema_problems,
        },
        "merge": {
            "deterministic": merge_deterministic,
            "events": len(merged_ab),
        },
        "overhead": {
            "events": OVERHEAD_EVENTS,
            "record_mean_us": round(record_mean_us, 2),
            "budget_us": OVERHEAD_BUDGET_US,
        },
        "kill_switch": {
            "checksum_on": sum_on,
            "checksum_off": sum_off,
            "bit_identical": sum_on == sum_off,
        },
        "journal": {"events": journal_events},
        "exposition_problems": lint_problems,
        "gates": {
            "why_links_min": 3,
            "record_mean_us_max": OVERHEAD_BUDGET_US,
            "burn_bound_s": args.burn_bound,
        },
    }
    print(json.dumps(out), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=2)
            f.write("\n")

    # -- gates -----------------------------------------------------------
    if not per_stream or stretched_at_s is None:
        raise SystemExit(
            f"journal_smoke: no per-stream cascade_stretch event within "
            f"{args.burn_bound}s — the burn never walked the ladder "
            f"(rung {eng.ladder.rung if eng.ladder else None!r}, "
            f"slo_burning {eng._slo_burning})")
    if not why["found"] or why["links"] < 3 or why["evicted_root"]:
        raise SystemExit(
            f"journal_smoke: /api/v1/why?stream={target} chain "
            f"incomplete: found={why['found']} links={why['links']} "
            f"evicted_root={why['evicted_root']}")
    if chain_actions[0] != ("slo", "episode_open") \
            or ("ladder", "escalate") not in chain_actions \
            or chain_actions[-1][1] not in ("cascade_stretch",
                                            "cascade_unstretch"):
        raise SystemExit(
            f"journal_smoke: chain is not slo burn -> ladder -> cadence "
            f"stretch: {chain_actions}")
    if not chain_triggers_ok:
        raise SystemExit(
            f"journal_smoke: chain link missing its quantitative "
            f"trigger: {why['chain']}")
    if not ladder_view.get("events"):
        raise SystemExit(
            "journal_smoke: /api/v1/journal?actor=ladder returned no "
            "events — endpoint filter broken")
    if ladder_journaled != ladder_transitions or slo_episodes_open < 1:
        raise SystemExit(
            f"journal_smoke: conservation broken — "
            f"{ladder_transitions} ladder transitions vs "
            f"{ladder_journaled} journal events, "
            f"{slo_episodes_open} slo episodes")
    if schema_problems:
        raise SystemExit(
            f"journal_smoke: artifact fails the --journal validator: "
            f"{schema_problems}")
    if not merge_deterministic:
        raise SystemExit(
            "journal_smoke: merge_journals is arrival-order dependent")
    if record_mean_us > OVERHEAD_BUDGET_US:
        raise SystemExit(
            f"journal_smoke: record() mean {record_mean_us:.1f} us > "
            f"{OVERHEAD_BUDGET_US} us (0.5% of the 10 ms tick)")
    if sum_on != sum_off or sum_on == 0:
        raise SystemExit(
            f"journal_smoke: journal=False not bit-identical "
            f"({sum_on} vs {sum_off}) — recording leaked into serving")
    if lint_problems:
        raise SystemExit(
            f"journal_smoke: vep_journal_* exposition not lint-clean: "
            f"{lint_problems}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
