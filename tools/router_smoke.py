"""Fleet-router smoke: multi-member live-migration soak with hard gates.

The r16 acceptance tool (``make router-smoke``; committed artifact
``ROUTER_r01.json``). Boots N REAL serve-only Server subprocesses (full
REST/gRPC + engine each), places N*2 replay streams across them through
``serve/router.py``'s consistent-hash ring, then runs the two fault legs
(replay/harness.py run_router_soak):

- **burn** — force one member's SLO-burn verdict; its ladder must walk
  shed -> shed_to_fleet and the router must migrate the member's streams
  to healthy peers (drain -> cutover -> resume at the replay cursor)
  BEFORE the local ladder reaches bucket_downshift.
- **kill** — SIGKILL one member; the router must re-place every one of
  its streams with detection-to-resumed latency within one scrape
  interval.

Hard gates (exit non-zero on breach):

- burn leg: streams evacuated, and the burning member's transition
  counters show ``shed_to_fleet >= 1`` with ``bucket_downshift == 0`` at
  migration completion (horizontal re-placement beat vertical
  degradation);
- kill leg: every stream re-placed; detect->resumed <= scrape interval
  and wall kill->resumed <= scrape interval + 1 s;
- conservation ledger balanced for EVERY stream: delivered packet ids
  gap-free from first delivery, ZERO lost, ZERO duplicated across the
  handoffs (exactly-once, proven from the per-member gRPC clients);
- every completed migration lineage-verified: a stitched
  worker -> bus -> engine -> client trace id chain on the destination
  (and the source, on the graceful leg);
- the router's ``vep_router_*`` exposition is lint-clean.

Orchestration-correctness tool and a CPU rehearsal by construction: the
gates are counts, and the member processes (each its own engine) are
started with ``JAX_PLATFORMS=cpu`` — N engines cannot share one chip. ~2-3 min.

Usage:
  python tools/router_smoke.py                      # acceptance run
  python tools/router_smoke.py --members 3 --out ROUTER_r01.json
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n", 1)[0])
    ap.add_argument("--members", type=int, default=3)
    ap.add_argument("--streams-per-member", type=int, default=2)
    ap.add_argument("--model", default="")
    ap.add_argument("--size", default="128x96")
    ap.add_argument("--fps", type=float, default=2.0,
                    help="per-stream frame rate; must sit below the "
                         "backend's tick rate so steady state is "
                         "lossless and the ledger attributes gaps to "
                         "migration alone")
    ap.add_argument("--scrape-interval", type=float, default=1.0)
    ap.add_argument("--ladder-escalate", type=float, default=8.0,
                    help="rung spacing: migration must complete inside "
                         "one window (shed_to_fleet -> bucket_downshift)")
    ap.add_argument("--out", default="ROUTER_r01.json")
    ap.add_argument("--workdir", default="",
                    help="keep the soak scratch dir (member stderr, span "
                         "dumps) instead of a deleted temp dir")
    args = ap.parse_args(argv)

    from video_edge_ai_proxy_tpu.replay.harness import run_router_soak

    model = args.model or "tiny_yolov8"
    try:
        w, h = (int(v) for v in args.size.lower().split("x"))
    except ValueError:
        ap.error(f"--size must be WxH, got {args.size!r}")

    out = run_router_soak(
        n_members=args.members,
        streams_per_member=args.streams_per_member,
        width=w, height=h, fps=args.fps, model=model,
        scrape_interval_s=args.scrape_interval,
        ladder_escalate_s=args.ladder_escalate,
        workdir=args.workdir or None)
    out["tool"] = "router_smoke"
    out["backend"] = "cpu"
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")

    gates = out["gates"]
    print(json.dumps({
        "leg": "router", "artifact": args.out,
        "members": out["members"], "streams": out["streams"],
        "gates": gates,
        "burn_migrate_s": out["burn"]["migrate_s"],
        "kill_replace_detect_s": out["kill"]["replace_detect_s"],
        "kill_replace_wall_s": out["kill"]["replace_wall_s"],
        "ledger": {k: out["ledger"][k]
                   for k in ("balanced", "lost", "duplicated")},
    }), flush=True)

    failures = []
    if not gates["attach_clean"]:
        failures.append("router attach failed on a member")
    if not gates["burn_streams_evacuated"]:
        failures.append(
            f"burn leg: streams not migrated off {out['burn']['member']}")
    if not gates["burn_shed_to_fleet_before_downshift"]:
        failures.append(
            "burn leg: ladder reached bucket_downshift before the fleet "
            f"handoff completed: {out['burn']['transitions_at_migration']}")
    if not gates["kill_streams_replaced"]:
        failures.append(
            f"kill leg: streams not re-placed off {out['kill']['member']}")
    if not gates["kill_replace_within_scrape"]:
        failures.append(
            "kill leg: detect->resumed "
            f"{out['kill']['replace_detect_s']}s > scrape interval")
    if not gates["kill_replace_wall_bounded"]:
        failures.append(
            f"kill leg: wall kill->resumed {out['kill']['replace_wall_s']}s "
            "> scrape interval + 1s")
    if not gates["ledger_balanced"]:
        failures.append(
            f"conservation ledger imbalance: lost={out['ledger']['lost']} "
            f"duplicated={out['ledger']['duplicated']}")
    if not gates["migrated_lineage_stitched"]:
        failures.append(
            f"migration without a stitched lineage chain: {out['lineage']}")
    if not gates["router_metrics_lint_clean"]:
        failures.append(
            f"router exposition lint: {out['lint_errors']}")
    if failures:
        raise SystemExit("router smoke failure: " + "; ".join(failures))


if __name__ == "__main__":
    main()
