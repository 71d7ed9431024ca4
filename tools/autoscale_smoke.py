"""Autoscale smoke: supervisor + AOT prewarm cache soak with hard gates.

The r19 acceptance tool (``make autoscale-smoke``; committed artifact
``AUTOSCALE_r01.json``). Boots two REAL serve-only members against a
shared persistent AOT compile cache (m0 cold — it populates the cache
and the prewarm manifest; m1 warm), then runs a FleetSupervisor with a
real subprocess spawner over a production-shaped LoadShape churn
schedule (replay/harness.py run_autoscale_soak): diurnal ramp,
connect/disconnect storm, hot-spot camera, mixed model tenants.

Hard gates (exit non-zero on breach):

- scale-out beat the burn: the one spawn fired on reason
  ``saturation_forecast`` while fleet min_headroom was still positive —
  capacity arrived BEFORE saturation, not after;
- the spawned member's program set came purely from the prewarm
  manifest (no --prewarm flags on its command line) with every compile
  a persistent-cache hit, and Popen -> first-served-frame landed inside
  one capacity-forecast scrape interval;
- storm admission latency bounded: every storm stream delivered, with
  connect -> first-frame p99 under the bound;
- retire on sustained surplus, and NO flap: exactly one spawn, one
  retire, member set back at min_members;
- conservation ledger balanced for EVERY stream from the very first
  frame — zero lost, zero duplicated across admission, storm churn,
  scale-out and the retire drain (members prewarm every program they
  serve, so there is no compile ramp to excuse);
- the ``vep_supervisor_*`` exposition is lint-clean.

Orchestration-correctness tool and a CPU rehearsal by construction: the
gates are counts, and the member processes (each its own engine) are
started with ``JAX_PLATFORMS=cpu`` — N engines cannot share one chip. ~3-4 min.

Usage:
  python tools/autoscale_smoke.py                    # acceptance run
  python tools/autoscale_smoke.py --out AUTOSCALE_r01.json
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n", 1)[0])
    ap.add_argument("--model", default="")
    ap.add_argument("--size", default="128x96")
    ap.add_argument("--scrape-interval", type=float, default=1.0,
                    help="router liveness scrape (placement/migration "
                         "cadence)")
    ap.add_argument("--capacity-scrape-interval", type=float, default=30.0,
                    help="the O(10 s) capacity-forecast scrape cadence "
                         "the spawn->first-frame gate is defined "
                         "against (distinct from the liveness scrape)")
    ap.add_argument("--spawn-horizon", type=float, default=600.0)
    ap.add_argument("--surplus-headroom", type=float, default=0.3)
    ap.add_argument("--surplus-hold", type=float, default=8.0)
    ap.add_argument("--storm-admission-bound", type=float, default=12.0)
    ap.add_argument("--out", default="AUTOSCALE_r01.json")
    ap.add_argument("--workdir", default="",
                    help="keep the soak scratch dir (member stderr, the "
                         "AOT cache + manifest) instead of a deleted "
                         "temp dir")
    args = ap.parse_args(argv)

    from video_edge_ai_proxy_tpu.replay.harness import run_autoscale_soak

    model = args.model or "tiny_yolov8"
    try:
        w, h = (int(v) for v in args.size.lower().split("x"))
    except ValueError:
        ap.error(f"--size must be WxH, got {args.size!r}")

    out = run_autoscale_soak(
        width=w, height=h, model=model,
        scrape_interval_s=args.scrape_interval,
        capacity_scrape_interval_s=args.capacity_scrape_interval,
        spawn_horizon_s=args.spawn_horizon,
        surplus_headroom=args.surplus_headroom,
        surplus_hold_s=args.surplus_hold,
        storm_admission_bound_s=args.storm_admission_bound,
        workdir=args.workdir or None)
    out["tool"] = "autoscale_smoke"
    out["backend"] = "cpu"
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")

    gates = out["gates"]
    print(json.dumps({
        "leg": "autoscale", "artifact": args.out,
        "gates": gates,
        "boots": {m: b["boot_s"] for m, b in out["boots"].items()},
        "spawn_first_frame_s": out["spawn"]["first_frame_s"],
        "storm_p99_s": out["storm"]["p99_s"],
        "ledger": {k: out["ledger"][k]
                   for k in ("balanced", "lost", "duplicated")},
    }), flush=True)

    failures = []
    if not gates["attach_clean"]:
        failures.append("router attach failed on a member")
    if not gates["scale_out_on_forecast"]:
        failures.append(
            "no spawn with reason saturation_forecast: "
            f"{out['spawn']['event']}")
    if not gates["scale_out_beats_burn"]:
        failures.append(
            "spawn landed after headroom went non-positive: "
            f"{out['spawn']['event']}")
    if not gates["spawn_prewarm_from_manifest"]:
        failures.append(
            "spawned member's program set did not come complete from "
            f"the manifest: {out['spawn']['prewarm']}")
    if not gates["spawn_first_frame_within_scrape"]:
        failures.append(
            f"spawn->first-served-frame {out['spawn']['first_frame_s']}s "
            "> one capacity scrape interval "
            f"({out['config']['capacity_scrape_interval_s']}s)")
    if not gates["storm_admission_bounded"]:
        failures.append(
            f"storm admission p99 {out['storm']['p99_s']}s > "
            f"{out['config']['storm_admission_bound_s']}s or streams "
            "undelivered")
    if not gates["retire_on_surplus"]:
        failures.append("no retire on sustained surplus")
    if not gates["no_flap"]:
        failures.append(
            "member set flapped (want exactly 1 spawn + 1 retire, back "
            "at min_members)")
    if not gates["ledger_balanced"]:
        failures.append(
            f"conservation ledger imbalance: lost={out['ledger']['lost']} "
            f"duplicated={out['ledger']['duplicated']}")
    if not gates["no_admission_errors"]:
        failures.append(f"admission errors: {out['failures']}")
    if not gates["supervisor_metrics_lint_clean"]:
        failures.append(
            f"supervisor exposition lint: {out['lint_errors']}")
    if failures:
        raise SystemExit("autoscale smoke failure: " + "; ".join(failures))


if __name__ == "__main__":
    main()
