"""Per-stage MFU decomposition for the serving configs (VERDICT r4 #6).

The round-3 MFU table proves the harness reaches 50 % on ViT-B/16 but
records ResNet-50x16 at 31.3 % and VideoMAE x8x8 at 25.9 % with no
breakdown. This tool decomposes a config's serving step into measured
stages — preprocess, stem/tubelet embed, trunk stages / encoder depth,
head — so each percentage is justified by numbers, not guesses.

Method: PREFIX TIMING through XLA dead-code elimination. For each
milestone (a named flax submodule), a jitted program runs the model with
``capture_intermediates`` and returns ONLY that intermediate's sum — XLA
prunes everything downstream, so the program measures the prefix ending
at the milestone. Stage cost = difference of adjacent prefixes. Each
prefix is scan-folded and timed exactly like bench.py (per-iteration
input perturbation, best-of-3), and each prefix's FLOPs come from the
SAME compiled program's cost analysis — so stage MFU = dFLOPs / dTime /
peak is internally consistent. The peak is the device's row of the one
peaks table (obs/perf.py, keyed by ``device_kind``): run from the command
line on a device without a row this exits non-zero.

    python tools/profile_mfu.py --config resnet50x16 --record MFU_resnet.json
    python tools/profile_mfu.py --config videomae_b_x8 --record MFU_vmae.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from bench import timed_best

from video_edge_ai_proxy_tpu.obs.perf import (
    peak_tflops_for, require_peak_tflops,
)

SRC_H, SRC_W = 1080, 1920

# config -> (model name, batch, milestones). A milestone is
# (label, module-path suffix) matched against the flax intermediates
# tree; "__preprocess__" and "__full__" are synthetic endpoints.
CONFIGS = {
    "resnet50x16": ("resnet50", 16, [
        ("preprocess(1080p->224)", "__preprocess__"),
        ("stem 7x7 s2 + pool", "stem"),
        ("stage1 (C256 56^2 x3)", "stage0_block2"),
        ("stage2 (C512 28^2 x4)", "stage1_block3"),
        ("stage3 (C1024 14^2 x6)", "stage2_block5"),
        ("stage4 (C2048 7^2 x3)", "stage3_block2"),
        ("pool+head", "__full__"),
    ]),
    "videomae_b_x8": ("videomae_b", 8, [
        ("preprocess(8f 1080p->224)", "__preprocess__"),
        ("tubelet embed", "tubelet"),
        ("encoder blocks 0-2", "block2"),
        ("encoder blocks 3-5", "block5"),
        ("encoder blocks 6-8", "block8"),
        ("encoder blocks 9-11", "block11"),
        ("mean+head", "__full__"),
    ]),
    "vit_b16_x32": ("vit_b16", 32, [
        ("preprocess(1080p->224)", "__preprocess__"),
        ("patchify", "patch_embed"),
        ("encoder blocks 0-5", "block5"),
        ("encoder blocks 6-11", "block11"),
        ("head", "__full__"),
    ]),
    # North star: the detect path decomposes through the letterbox, the
    # backbone pyramid, decode, and NMS endpoints.
    "yolov8n_x16": ("yolov8n", 16, [
        ("preprocess(letterbox 1080p->640)", "__preprocess__"),
        ("stem+P2 (C<=32, 320^2)", "c2f_2"),
        ("P3 (C64, 80^2)", "c2f_3"),
        ("P4 (C128, 40^2)", "c2f_4"),
        ("P5+SPPF (C256, 20^2)", "sppf"),
        ("neck+heads+DFL decode", "__model__"),
        ("NMS + unletterbox", "__full__"),
    ]),
    # Round 15: the s2d-stem variant of the north star — same milestones,
    # but the preprocess endpoint is the FUSED letterbox+normalize+s2d
    # megakernel (one read of the 1080p plane) and the stem runs 2x2
    # stride-1 on the 320²x12 folded plane. MFU_yolo_r05 charged 2.7 ms
    # to preprocess (21.6%) and 7.6 ms to stem+P2 (0.9%); this config
    # measures whether the fold recovers them.
    "yolov8n_s2d_x16": ("yolov8n_s2d", 16, [
        ("preprocess(fused letterbox+s2d 1080p->320^2x12)", "__preprocess__"),
        ("stem+P2 (C12->C32, 320^2)", "c2f_2"),
        ("P3 (C64, 80^2)", "c2f_3"),
        ("P4 (C128, 40^2)", "c2f_4"),
        ("P5+SPPF (C256, 20^2)", "sppf"),
        ("neck+heads+DFL decode", "__model__"),
        ("NMS + unletterbox", "__full__"),
    ]),
    # CPU-backend smoke twins (tests): tiny models, the same machinery.
    "tiny_resnet_x2": ("tiny_resnet", 2, [
        ("preprocess", "__preprocess__"),
        ("stem", "stem"),
        ("stage1", "stage0_block0"),
        ("head", "__full__"),
    ]),
    "tiny_yolo_x2": ("tiny_yolov8", 2, [
        ("preprocess", "__preprocess__"),
        ("P3", "c2f_3"),
        ("decode", "__model__"),
        ("nms", "__full__"),
    ]),
    "tiny_yolo_s2d_x2": ("tiny_yolov8_s2d", 2, [
        ("preprocess", "__preprocess__"),
        ("P3", "c2f_3"),
        ("decode", "__model__"),
        ("nms", "__full__"),
    ]),
}


def _find_leaf(tree, suffix, path=()):
    """Depth-first: the first intermediates leaf whose module path ends
    with ``suffix``. Returns (joined path, array) or None."""
    if isinstance(tree, dict):
        for k, v in tree.items():
            hit = _find_leaf(v, suffix, path + (k,))
            if hit is not None:
                return hit
        return None
    if isinstance(tree, (tuple, list)):
        arr = tree[0] if tree else None
        if arr is None:
            return None
        mods = [p for p in path if p != "__call__"]
        if mods and mods[-1] == suffix:
            return "/".join(mods), arr
        return None
    return None


def build_prefix(spec, model, variables, milestone, batch, clip_len):
    """Jitted scan-folded program measuring the serving prefix up to
    ``milestone``; returns (fn, args, flops) with flops from the compiled
    program's own cost analysis. Detect models route through the real
    letterbox/decode/NMS endpoints ("__model__" = decode done, no NMS;
    "__full__" = the exact serving step)."""
    from video_edge_ai_proxy_tpu.engine.runner import build_serving_step
    from video_edge_ai_proxy_tpu.ops.preprocess import (
        preprocess_classify, preprocess_clip, preprocess_letterbox,
        preprocess_letterbox_fused,
    )

    size = spec.input_size
    detect = spec.kind == "detect"
    serving = build_serving_step(model, spec) if detect else None
    pre = preprocess_clip if clip_len else preprocess_classify
    # s2d-stem models serve through the fused letterbox+s2d megakernel
    # (engine/runner.py build_serving_step makes the same dispatch) — the
    # prefix programs must measure the program that actually serves.
    fused = detect and getattr(
        getattr(model, "cfg", None), "stem", "classic") == "s2d"

    def prefix_once(v, frames_u8):
        if detect:
            if milestone == "__full__":
                out = serving(v, frames_u8)
                # Every output feeds the scalar, or XLA DCE would prune
                # unletterbox_boxes and the kept-box/class gathers and
                # this would NOT be the exact serving step.
                return (jnp.sum(out["boxes"].astype(jnp.float32))
                        + jnp.sum(out["scores"].astype(jnp.float32))
                        + jnp.sum(out["classes"].astype(jnp.float32))
                        + jnp.sum(out["valid"].astype(jnp.float32)))
            if fused:
                x, _lb = preprocess_letterbox_fused(frames_u8, size)
            else:
                x, _lb = preprocess_letterbox(frames_u8, size)
            if milestone == "__preprocess__":
                return jnp.sum(x.astype(jnp.float32))
            if milestone == "__model__":
                boxes, max_logit, _ids = model.apply(v, x, decode="serving")
                return (jnp.sum(boxes.astype(jnp.float32))
                        + jnp.sum(max_logit.astype(jnp.float32)))
            out, state = model.apply(
                v, x, decode="serving",
                capture_intermediates=True, mutable=["intermediates"],
            )
        else:
            x = pre(frames_u8, (size, size))
            if milestone == "__preprocess__":
                return jnp.sum(x.astype(jnp.float32))
            if milestone == "__full__":
                out = model.apply(v, x)
                return jnp.sum(out.astype(jnp.float32))
            out, state = model.apply(
                v, x, capture_intermediates=True, mutable=["intermediates"]
            )
        hit = _find_leaf(state["intermediates"], milestone)
        if hit is None:
            raise KeyError(
                f"milestone {milestone!r} not found in intermediates"
            )
        return jnp.sum(hit[1].astype(jnp.float32))

    iters = 30

    @jax.jit
    def megastep(v, base_u8):
        def body(carry, i):
            s = prefix_once(v, base_u8 + i.astype(jnp.uint8))
            return carry + s, None

        total, _ = jax.lax.scan(
            body, jnp.zeros((), jnp.float32), jnp.arange(iters))
        return total

    shape = ((batch,) + ((clip_len,) if clip_len else ())
             + (SRC_H, SRC_W, 3))
    rng = np.random.default_rng(0)
    base = jax.device_put(rng.integers(0, 256, shape, dtype=np.uint8))
    v_dev = jax.device_put(variables)
    lowered = megastep.lower(v_dev, base)
    cost = lowered.compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    # XLA's HLO cost analysis counts a while/scan BODY once (not body x
    # trip count), so the reported flops are already per-iteration —
    # verified against bench_configs' recorded per-step GFLOP (ViT-B/16
    # x32: 1237.1 both ways).
    flops = float((cost or {}).get("flops", 0.0))
    return megastep, (v_dev, base), flops, iters


SPREAD_STABLE = 1.3     # worst median/min across rounds below this = clean


def _window_spread(round_ms) -> float:
    """Stability signal (prefix costs span 100x, so no absolute bar
    fits them all): how far the per-round minima spread. A clean set of
    windows keeps every prefix's median within ~20% of its min."""
    vals = [
        float(np.median(r)) / min(r) for r in round_ms if min(r) > 0.05
    ]
    return max(vals) if vals else 1.0


def run_config(config: str, rounds: int = 4,
               max_rounds: int | None = None) -> dict:
    from video_edge_ai_proxy_tpu.models import registry

    model_name, batch, milestones = CONFIGS[config]
    spec = registry.get(model_name)
    model, variables = spec.init_params(jax.random.PRNGKey(0))
    backend = jax.default_backend()
    device_kind = jax.devices()[0].device_kind
    # None off the peaks table (the CPU twin the tests run): the MFU
    # columns are then null; main() refuses such a device outright.
    peak = peak_tflops_for(device_kind)

    # Compile every prefix first, then measure them ROUND-ROBIN across
    # several rounds and keep each prefix's minimum: timing each prefix
    # in its own window lets any drift between windows land entirely in
    # the differences (a -13 ms "stage" was recorded that way);
    # interleaving puts every prefix through the same windows.
    built = []
    for label, milestone in milestones:
        print(f"  compile -> {label} ...", flush=True)
        fn, args, flops, iters = build_prefix(
            spec, model, variables, milestone, batch, spec.clip_len)
        np.asarray(fn(*args))          # compile + warm
        built.append((label, fn, args, flops, iters))
    round_ms = [[] for _ in built]

    def one_round(idx: int, total: int) -> None:
        print(f"  measuring (round {idx + 1}/{total}) ...", flush=True)
        for bi, (label, fn, args, flops, iters) in enumerate(built):
            # Best-of-3 inside timed_best; window stability is judged
            # from the cross-round spread below.
            elapsed, _ = timed_best(lambda fn=fn, args=args: fn(*args))
            round_ms[bi].append(elapsed / iters * 1e3)

    for r in range(rounds):
        one_round(r, rounds)
    # Stability gate (round 15): MFU_yolo_r05 shipped with
    # windows_stable=false / spread 1.504, making its re-measured stage
    # deltas untrustworthy. Instead of recording a bad artifact, keep
    # adding round-robin rounds (each round gives every prefix another
    # chance at a clean window, tightening median/min) until the spread
    # settles or the round budget runs out; --require-stable turns a
    # still-unstable result into a nonzero exit.
    max_rounds = max_rounds if max_rounds is not None else rounds * 3
    spread = _window_spread(round_ms)
    done = rounds
    while spread >= SPREAD_STABLE and done < max_rounds:
        print(f"  window spread {spread:.3f} >= {SPREAD_STABLE}; "
              "adding a round ...", flush=True)
        one_round(done, max_rounds)
        done += 1
        spread = _window_spread(round_ms)
    best_ms = [min(r) for r in round_ms]
    windows_stable = spread < SPREAD_STABLE
    # A prefix is a superset of every earlier one, so its true time is
    # monotone non-decreasing; enforce that (cumulative max) so residual
    # window noise cannot produce negative stage costs.
    iso_ms = np.maximum.accumulate(np.asarray(best_ms))
    rows = []
    prev_ms = 0.0
    prev_gf = 0.0
    for bi, (label, fn, args, flops, iters) in enumerate(built):
        pref_ms = float(iso_ms[bi])
        pref_gf = flops / 1e9
        d_ms = pref_ms - prev_ms
        d_gf = pref_gf - prev_gf
        rows.append({
            "stage": label,
            "prefix_ms": round(pref_ms, 3),
            "prefix_gflop": round(pref_gf, 2),
            "stage_ms": round(d_ms, 3),
            "stage_gflop": round(d_gf, 2),
            "stage_tflops": round(d_gf / d_ms, 1) if d_ms > 0.05 else None,
            "stage_mfu_pct": round(100 * d_gf / d_ms / peak, 1)
            if d_ms > 0.05 and peak else None,
        })
        prev_ms, prev_gf = pref_ms, pref_gf
    total_ms, total_gf = prev_ms, prev_gf
    return {
        "config": config,
        "model": model_name,
        "batch": batch,
        "backend": backend,
        "device_kind": device_kind,
        "peak_tflops": peak,
        "stages": rows,
        "total_ms": round(total_ms, 3),
        "total_gflop": round(total_gf, 2),
        "total_mfu_pct": (round(100 * total_gf / total_ms / peak, 1)
                          if peak else None),
        "rounds": done,
        "window_spread": round(float(spread), 3),
        "windows_stable": bool(windows_stable),
        "stability_gate": {
            "threshold": SPREAD_STABLE,
            "base_rounds": rounds,
            "rounds_run": done,
            "max_rounds": max_rounds,
            "extra_rounds": done - rounds,
        },
        "note": "prefix timing via capture_intermediates + XLA DCE; "
                "stage = difference of adjacent prefixes; FLOPs from each "
                "compiled prefix's cost analysis (internally consistent); "
                "window_spread = worst median/min across measurement "
                "rounds; unstable windows retry with extra "
                "round-robin rounds up to max_rounds before recording",
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--config", required=True, choices=sorted(CONFIGS))
    ap.add_argument("--record", default="")
    ap.add_argument("--rounds", type=int, default=4,
                    help="measurement rounds per prefix (more rounds let "
                         "the per-prefix minimum converge)")
    ap.add_argument("--max-rounds", type=int, default=None,
                    help="stability-gate round budget (default rounds*3): "
                         "rounds keep adding while window_spread >= "
                         f"{SPREAD_STABLE}")
    ap.add_argument("--require-stable", action="store_true",
                    help="exit nonzero when windows are still unstable "
                         "after max-rounds (the artifact is written "
                         "either way, stamped windows_stable=false)")
    args = ap.parse_args(argv)
    require_peak_tflops(jax.devices()[0].device_kind)
    out = run_config(args.config, rounds=args.rounds,
                     max_rounds=args.max_rounds)
    print(json.dumps(out))
    if args.record:
        with open(args.record, "w") as f:
            json.dump(out, f, indent=2)
            f.write("\n")
    if args.require_stable and not out["windows_stable"]:
        print(f"window spread {out['window_spread']} >= {SPREAD_STABLE} "
              f"after {out['rounds']} rounds: stage deltas untrustworthy",
              file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
