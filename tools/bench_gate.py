"""Bench regression gate: newest bench.py line vs the committed trajectory.

``bench.py`` prints one JSON line per run; acceptance runs are committed
as ``BENCH_r*.json`` artifacts (shape: {"n", "cmd", "rc", "tail",
"parsed": {...bench dict...}}). This tool closes the loop the artifacts
only documented: it parses the latest bench output (file argument or
stdin), finds every committed artifact with the SAME ``metric`` string,
and fails (exit 1) when the new value regresses more than ``--tolerance``
(default 5%) below the best committed value.

Semantics chosen for unattended CI (``make perf-gate``):

- **Metric-matched only.** A metric with no committed baseline — today
  every metric: no ``BENCH_r*.json`` is committed — reports "no baseline"
  and passes (first-run semantics). bench.py itself exits non-zero
  without a TPU, so there is no CPU line to gate.
- **Best-of-trajectory baseline.** Gating against max(committed) rather
  than latest(committed) means a slow r(N) acceptance run can never
  ratchet the bar downward.

Usage:
  python bench.py | tee /tmp/bench.json && python tools/bench_gate.py /tmp/bench.json
  python tools/bench_gate.py -            # read bench output from stdin
  python tools/bench_gate.py out.json --tolerance 0.03
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse_bench_output(text: str) -> dict:
    """Last JSON object line holding a bench dict ({"metric", "value"}).
    Accepts raw bench.py stdout (progress lines + one JSON line) and
    artifact-shaped wrappers ({"parsed": {...}})."""
    best = None
    # A whole artifact file (pretty-printed JSON) parses in one shot;
    # bench stdout falls through to the line scan.
    try:
        obj = json.loads(text)
    except ValueError:
        obj = None
    if isinstance(obj, dict):
        if isinstance(obj.get("parsed"), dict):
            obj = obj["parsed"]
        if "metric" in obj and "value" in obj:
            return obj
    for line in text.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and isinstance(obj.get("parsed"), dict):
            obj = obj["parsed"]
        if isinstance(obj, dict) and "metric" in obj and "value" in obj:
            best = obj
    if best is None:
        raise SystemExit(
            "bench_gate: no bench JSON line ({'metric': .., 'value': ..}) "
            "found in input")
    return best


def load_trajectory(baseline_dir: str) -> list:
    """Every committed BENCH_r*.json's parsed bench dict, tagged with its
    artifact name, ordered by artifact name (r01, r02, ...)."""
    out = []
    for path in sorted(glob.glob(os.path.join(baseline_dir,
                                              "BENCH_r*.json"))):
        try:
            with open(path) as f:
                art = json.load(f)
        except (OSError, ValueError):
            continue
        parsed = art.get("parsed") if isinstance(art, dict) else None
        if isinstance(parsed, dict) and "metric" in parsed \
                and "value" in parsed:
            parsed = dict(parsed)
            parsed["_artifact"] = os.path.basename(path)
            out.append(parsed)
    return out


def gate(current: dict, trajectory: list, tolerance: float) -> dict:
    """Pure decision: returns the report dict; report["pass"] is the
    verdict (unit-tested without artifacts on disk)."""
    metric = current["metric"]
    value = float(current["value"])
    matched = [t for t in trajectory if t.get("metric") == metric]
    report = {
        "tool": "bench_gate",
        "metric": metric,
        "value": value,
        "tolerance": tolerance,
        "trajectory": [
            {"artifact": t.get("_artifact"), "value": t.get("value")}
            for t in matched
        ],
    }
    # Informational carry-through (round 8): the H2D overlap evidence
    # rides the report so perf-gate logs show it, but it never gates —
    # older artifacts predate the field and a first TPU run must keep its
    # metric-matched first-run pass.
    if current.get("h2d_hidden_pct") is not None:
        report["h2d_hidden_pct"] = current["h2d_hidden_pct"]
    # Same pattern for the round-9 ROI serving evidence: when the bench
    # line carries MOSAIC numbers (roi_smoke.py fields folded in), they
    # ride along for the log — informational only, never gated.
    for key in ("roi_equivalent_fps", "roi_canvas_occupancy_pct"):
        if current.get(key) is not None:
            report[key] = current[key]
    if not matched:
        report.update(passed=True, reason="no committed baseline for "
                      f"metric {metric!r} (first run records the bar)")
        return report
    reference = max(float(t["value"]) for t in matched)
    floor = reference * (1.0 - tolerance)
    report.update(reference=reference, floor=round(floor, 1))
    if value >= floor:
        report.update(passed=True,
                      reason=f"{value} >= floor {floor:.1f} "
                      f"({reference} - {tolerance:.0%})")
    else:
        report.update(passed=False,
                      reason=f"regression: {value} < floor {floor:.1f} "
                      f"(best committed {reference} - {tolerance:.0%})")
    return report


def router_replace_info(baseline_dir: str):
    """Newest committed ROUTER_r*.json's re-placement latency, or None.

    Round 13 informational carry-through: perf-gate logs show the fleet
    router's measured kill-leg latency (detect->resumed and wall
    kill->resumed, plus the conservation-ledger verdict) next to the fps
    verdict. NEVER gated here — router_smoke.py hard-gates its own run;
    this is trend visibility only.
    """
    paths = sorted(glob.glob(os.path.join(baseline_dir, "ROUTER_r*.json")))
    for path in reversed(paths):
        try:
            with open(path) as f:
                art = json.load(f)
        except (OSError, ValueError):
            continue
        kill = art.get("kill") if isinstance(art, dict) else None
        if isinstance(kill, dict):
            return {
                "artifact": os.path.basename(path),
                "members": art.get("members"),
                "streams": art.get("streams"),
                "replace_detect_s": kill.get("replace_detect_s"),
                "replace_wall_s": kill.get("replace_wall_s"),
                "ledger_balanced": art.get("ledger", {}).get("balanced"),
            }
    return None


def cascade_info(baseline_dir: str):
    """Newest committed CASCADE_r*.json's cadence/latency row, or None.

    Round 14 informational carry-through: perf-gate logs show the
    temporal cascade's measured head cadence and enter-event detect
    latency next to the fps verdict. NEVER gated here —
    cascade_smoke.py hard-gates its own run; this is trend visibility
    only.
    """
    paths = sorted(glob.glob(os.path.join(baseline_dir, "CASCADE_r*.json")))
    for path in reversed(paths):
        try:
            with open(path) as f:
                art = json.load(f)
        except (OSError, ValueError):
            continue
        if not isinstance(art, dict) or "cascade_head_cadence" not in art:
            continue
        return {
            "artifact": os.path.basename(path),
            "cascade_every_n": art.get("cascade_every_n"),
            "cascade_head_cadence": art.get("cascade_head_cadence"),
            "cascade_event_latency_ticks": art.get(
                "cascade_event_latency_ticks"),
            "slot_high_water": art.get("slot_high_water"),
        }
    return None


def capacity_info(baseline_dir: str):
    """Newest committed CAPACITY_r*.json's ledger/forecast row, or None.

    Round 18 informational carry-through: perf-gate logs show the
    capacity plane's conservation drift, tap overhead, and admission-
    storm verdict next to the fps verdict. NEVER gated here —
    capacity_smoke.py hard-gates its own run; this is trend visibility
    only.
    """
    paths = sorted(glob.glob(os.path.join(baseline_dir, "CAPACITY_r*.json")))
    for path in reversed(paths):
        try:
            with open(path) as f:
                art = json.load(f)
        except (OSError, ValueError):
            continue
        if not isinstance(art, dict) or "ledger" not in art:
            continue
        ledger = art.get("ledger") or {}
        forecast = art.get("forecast") or {}
        admission = art.get("admission") or {}
        return {
            "artifact": os.path.basename(path),
            "conservation_rel_drift": (ledger.get("conservation") or {}
                                       ).get("rel_drift"),
            "ledger_tap_pct_of_tick_budget": ledger.get(
                "ledger_tap_pct_of_tick_budget"),
            "tts_monotone_decreasing": forecast.get(
                "tts_monotone_decreasing"),
            "saturating_member_admissions": admission.get(
                "saturating_member_admissions"),
        }
    return None


def hbm_info(baseline_dir: str):
    """Newest committed HBM_r*.json's memory-ledger row, or None.

    Round 21 informational carry-through: perf-gate logs show the HBM
    attribution plane's pool-byte exactness, OOM-forecast monotonicity,
    and memory-aware-admission verdict next to the fps verdict. NEVER
    gated here — hbm_smoke.py hard-gates its own run; this is trend
    visibility only.
    """
    paths = sorted(glob.glob(os.path.join(baseline_dir, "HBM_r*.json")))
    for path in reversed(paths):
        try:
            with open(path) as f:
                art = json.load(f)
        except (OSError, ValueError):
            continue
        if not isinstance(art, dict) or "pools" not in art:
            continue
        pools = art.get("pools") or {}
        forecast = art.get("forecast") or {}
        admission = art.get("admission") or {}
        replay = art.get("replay") or {}
        return {
            "artifact": os.path.basename(path),
            "pool_max_abs_delta_bytes": pools.get("max_abs_delta_bytes"),
            "tto_monotone_decreasing": forecast.get(
                "tto_monotone_decreasing"),
            "exhausted_member_placements": admission.get(
                "exhausted_member_placements"),
            "hbm_off_bitexact": replay.get("hbm_off_bitexact"),
        }
    return None


def autoscale_info(baseline_dir: str):
    """Newest committed AUTOSCALE_r*.json's lifecycle row, or None.

    Round 19 informational carry-through: perf-gate logs show the
    autoscale soak's spawn latency (cold vs manifest-warm boot, spawn ->
    first-served-frame) and flap/ledger verdicts next to the fps
    verdict. NEVER gated here — autoscale_smoke.py hard-gates its own
    run; this is trend visibility only.
    """
    paths = sorted(glob.glob(os.path.join(baseline_dir,
                                          "AUTOSCALE_r*.json")))
    for path in reversed(paths):
        try:
            with open(path) as f:
                art = json.load(f)
        except (OSError, ValueError):
            continue
        if not isinstance(art, dict) or "spawn" not in art:
            continue
        gates = art.get("gates") or {}
        spawn = art.get("spawn") or {}
        boots = art.get("boots") or {}
        return {
            "artifact": os.path.basename(path),
            "cold_boot_s": (boots.get("m0") or {}).get("boot_s"),
            "warm_boot_s": (boots.get("m1") or {}).get("boot_s"),
            "spawn_boot_s": spawn.get("boot_s"),
            "spawn_first_frame_s": spawn.get("first_frame_s"),
            "storm_p99_s": (art.get("storm") or {}).get("p99_s"),
            "no_flap": gates.get("no_flap"),
            "ledger_balanced": gates.get("ledger_balanced"),
        }
    return None


def stem_stage_info(baseline_dir: str):
    """Newest committed MFU_yolo_*.json's stem-stage row, or None.

    Round 12 informational carry-through: perf-gate logs show where the
    detect stem stands (the 1%-MFU stage the s2d work targets) next to
    the fps verdict, labeled with the artifact it came from. NEVER gated
    — MFU artifacts are chip-run evidence with their own stability gate
    (tools/profile_mfu.py --require-stable), not a CI bar.
    """
    paths = sorted(glob.glob(os.path.join(baseline_dir, "MFU_yolo_*.json")))
    for path in reversed(paths):
        try:
            with open(path) as f:
                art = json.load(f)
        except (OSError, ValueError):
            continue
        for row in art.get("stages", []) if isinstance(art, dict) else []:
            if str(row.get("stage", "")).startswith("stem"):
                return {
                    "artifact": os.path.basename(path),
                    "config": art.get("config"),
                    "stage": row.get("stage"),
                    "stem_ms": row.get("stage_ms"),
                    "stage_mfu_pct": row.get("stage_mfu_pct"),
                }
    return None


def multichip_serve_info(baseline_dir: str):
    """Newest committed MULTICHIP_SERVE_r*.json's scaling row, or None.

    Round 17 informational carry-through: perf-gate logs show the mesh
    serving smoke's dp1/dp2/dp4 fps, the dp4/dp1 scale factor, and the
    lockstep bit-identical verdict next to the fps verdict. NEVER gated
    here — multichip_serve_smoke.py hard-gates its own run (min scale,
    zero misroutes, conservation drift); this is trend visibility only.
    """
    paths = sorted(glob.glob(os.path.join(baseline_dir,
                                          "MULTICHIP_SERVE_r*.json")))
    for path in reversed(paths):
        try:
            with open(path) as f:
                art = json.load(f)
        except (OSError, ValueError):
            continue
        if not isinstance(art, dict) or "serve" not in art:
            continue
        serve = art.get("serve") or {}
        legs = {leg: (serve.get(leg) or {}).get("fps")
                for leg in ("dp1", "dp2", "dp4")}
        dp4 = serve.get("dp4") or {}
        return {
            "artifact": os.path.basename(path),
            "fps": legs,
            "scale_dp4_over_dp1": art.get("fps_scale_dp4_over_dp1"),
            "bit_identical": (art.get("lockstep") or {}).get(
                "bit_identical"),
            "dp4_misrouted": dp4.get("misrouted"),
            "dp4_unrouted": dp4.get("unrouted"),
            "dp4_conservation_rel_drift": (dp4.get("conservation")
                                           or {}).get("rel_drift"),
        }
    return None


def fault_info(baseline_dir: str):
    """Newest committed FAULT_r*.json's shard-loss row, or None.

    Round 22 informational carry-through: perf-gate logs show the
    device-fault smoke's detection latency, failover wall time, stream
    evacuation latency, pin retention, and the frame-conservation
    verdict next to the fps verdict. NEVER gated here — fault_smoke.py
    hard-gates its own run (detect ticks, failover budget, evac bound,
    retention floor, zero lost/dup outside the declared windows); this
    is trend visibility only.
    """
    paths = sorted(glob.glob(os.path.join(baseline_dir, "FAULT_r*.json")))
    for path in reversed(paths):
        try:
            with open(path) as f:
                art = json.load(f)
        except (OSError, ValueError):
            continue
        if not isinstance(art, dict) or "hard_fault" not in art:
            continue
        hard = art.get("hard_fault") or {}
        fail = hard.get("failover") or {}
        ledger = art.get("ledger") or {}
        return {
            "artifact": os.path.basename(path),
            "detect_ticks": hard.get("detect_ticks"),
            "failover_ms": fail.get("failover_ms"),
            "evac_first_result_ms": hard.get("evac_first_result_ms"),
            "pin_retention": hard.get("pin_retention"),
            "ledger_lost": ledger.get("lost"),
            "ledger_duplicated": ledger.get("duplicated"),
            "ledger_lost_outside_window": ledger.get("lost_outside_window"),
        }
    return None


def journal_info(baseline_dir: str):
    """Newest committed JOURNAL_r*.json's decision-journal row, or None.

    Round 23 informational carry-through: perf-gate logs show the
    journal smoke's why()-chain depth, record() overhead, and the
    kill-switch bit-identity verdict next to the fps verdict. NEVER
    gated here — journal_smoke.py hard-gates its own run (chain
    completeness, conservation, merge determinism, overhead budget,
    journal-off bit-identity); this is trend visibility only.
    """
    paths = sorted(glob.glob(os.path.join(baseline_dir, "JOURNAL_r*.json")))
    for path in reversed(paths):
        try:
            with open(path) as f:
                art = json.load(f)
        except (OSError, ValueError):
            continue
        if not isinstance(art, dict) or "chain" not in art:
            continue
        chain = art.get("chain") or {}
        why = chain.get("why") or {}
        overhead = art.get("overhead") or {}
        conservation = art.get("conservation") or {}
        kill = art.get("kill_switch") or {}
        return {
            "artifact": os.path.basename(path),
            "why_links": why.get("links"),
            "stretched_at_s": chain.get("stretched_at_s"),
            "ladder_transitions": conservation.get("ladder_transitions"),
            "ladder_journaled": conservation.get("ladder_journaled"),
            "record_mean_us": overhead.get("record_mean_us"),
            "merge_deterministic": (art.get("merge") or {}).get(
                "deterministic"),
            "off_bit_identical": kill.get("bit_identical"),
        }
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("input", nargs="?", default="-",
                    help="bench.py output file, or - for stdin")
    ap.add_argument("--tolerance", type=float, default=0.05,
                    help="allowed fractional drop below the best "
                         "committed value (default 0.05 = -5%%)")
    ap.add_argument("--baseline-dir", default=REPO,
                    help="directory holding BENCH_r*.json artifacts")
    args = ap.parse_args(argv)

    if args.input == "-":
        text = sys.stdin.read()
    else:
        with open(args.input) as f:
            text = f.read()
    current = parse_bench_output(text)
    trajectory = load_trajectory(args.baseline_dir)
    report = gate(current, trajectory, args.tolerance)
    stem = stem_stage_info(args.baseline_dir)
    if stem is not None:
        report["stem_stage"] = stem          # informational, never gated
    router = router_replace_info(args.baseline_dir)
    if router is not None:
        report["router_replace"] = router    # informational, never gated
    cascade = cascade_info(args.baseline_dir)
    if cascade is not None:
        report["cascade"] = cascade          # informational, never gated
    capacity = capacity_info(args.baseline_dir)
    if capacity is not None:
        report["capacity"] = capacity        # informational, never gated
    hbm = hbm_info(args.baseline_dir)
    if hbm is not None:
        report["hbm"] = hbm                  # informational, never gated
    autoscale = autoscale_info(args.baseline_dir)
    if autoscale is not None:
        report["autoscale"] = autoscale      # informational, never gated
    multichip = multichip_serve_info(args.baseline_dir)
    if multichip is not None:
        report["multichip_serve"] = multichip  # informational, never gated
    fault = fault_info(args.baseline_dir)
    if fault is not None:
        report["fault"] = fault              # informational, never gated
    journal = journal_info(args.baseline_dir)
    if journal is not None:
        report["journal"] = journal          # informational, never gated
    print(json.dumps(report, indent=2))
    return 0 if report["passed"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
