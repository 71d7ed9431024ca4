"""Measure all five BASELINE.json configs on the current serving code.

One JSON line per config (same scan-fold + best-of-3 methodology as
bench.py; see tools/profile_ns.py for why inputs are perturbed per
iteration). bench.py stays the driver-facing north-star metric; this is
the full matrix for BASELINE.md's table. Off the TPU it runs a toy-size
correctness rehearsal (content checksums against the committed CPU
goldens): its lines then carry ``"backend": "cpu"``, counts and
checksums, and no rate or MFU column.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

SRC_H, SRC_W = 1080, 1920

# (name, model, streams, iters) — clip length comes from the model spec.
CONFIGS = [
    ("config1_mobilenet_1stream", "mobilenet_v2", 1, 100),
    ("config2_yolov8n_4stream", "yolov8n", 4, 100),
    ("config3_resnet50_16stream", "resnet50", 16, 50),
    ("config4_vit_b16_32stream", "vit_b16", 32, 30),
    ("config5_videomae_8x8clip", "videomae_b", 8, 20),
]


def main() -> None:
    from bench import timed_best

    from video_edge_ai_proxy_tpu.engine.runner import build_serving_step
    from video_edge_ai_proxy_tpu.models import registry
    from video_edge_ai_proxy_tpu.obs.perf import (
        cost_summary, require_peak_tflops,
    )
    from video_edge_ai_proxy_tpu.replay.checksum import (
        check_golden, fold_checksum, zero_class_prior,
    )

    backend = jax.default_backend()
    device_kind = jax.devices()[0].device_kind
    peak = (require_peak_tflops(device_kind) if backend == "tpu" else None)
    rng = np.random.default_rng(0)
    for name, model_name, streams, iters in CONFIGS:
        if backend != "tpu":
            streams, iters = min(streams, 2), 2
        spec = registry.get(model_name)
        model, variables = spec.init_params(jax.random.PRNGKey(0))
        if spec.kind == "detect":
            # Same bench.py methodology: random-init class priors suppress
            # every score below the NMS threshold, which zeroes the content
            # checksum and removes the NMS work from the measured program.
            variables = zero_class_prior(variables)
        step = build_serving_step(model, spec)
        shape = (streams,) + ((spec.clip_len,) if spec.clip_len else ()) + \
            (SRC_H if backend == "tpu" else 270,
             SRC_W if backend == "tpu" else 480, 3)
        base = rng.integers(0, 256, shape, dtype=np.uint8)

        @jax.jit
        def mega(params, u8):
            # params is an ARGUMENT, not a closure capture: captured trees
            # are baked into the HLO as constants — 170 MB of them for an
            # 86M-param ViT, compiled and cached as part of the program.
            def body(carry, i):
                out = step(params, u8 + i.astype(jnp.uint8))
                # Content-derived checksum (replay/checksum.py) — covers
                # all three output families; replaces the float leaf-sum,
                # which drowned small numeric drift in big-tensor noise.
                return fold_checksum(carry, out), None

            tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.int32),
                                  jnp.arange(iters))
            return tot

        dev = jax.device_put(base)
        var_dev = jax.device_put(variables)
        t0 = time.perf_counter()
        np.asarray(mega(var_dev, dev))
        compile_s = time.perf_counter() - t0
        best, total = timed_best(lambda: mega(var_dev, dev))
        frames_per_iter = streams * (spec.clip_len or 1)
        batch_ms = best / iters * 1e3
        key = f"configs:{name}:{backend}:{streams}x{iters}"
        check_golden(key, int(total), tool="bench_configs")
        rec = {
            "config": name,
            "model": model_name,
            "backend": backend,
            "device_kind": device_kind,
            "compile_s": round(compile_s, 1),
            "checksum": int(total),
            "checksum_key": key,
        }
        # XLA's own FLOP count for ONE serving step, from the COMPILED
        # step (cost_summary; the extra single-step compile is the price
        # of the count).
        flops = cost_summary(
            jax.jit(step).lower(var_dev, dev).compile()).get("flops", 0.0)
        if flops > 0:
            rec["step_gflops"] = round(flops / 1e9, 1)
        if backend == "tpu":
            # Rates are device metrics: a CPU rehearsal prints none.
            rec["fps"] = round(frames_per_iter * iters / best, 1)
            rec["batch_ms"] = round(batch_ms, 2)
            if flops > 0:
                # MFU bookkeeping (VERDICT r2 #7): FLOPs / measured step
                # time / the device's row of the one peaks table
                # (obs/perf.py) — no row, no MFU.
                achieved = flops / (batch_ms / 1e3)
                rec["achieved_tflops_s"] = round(achieved / 1e12, 2)
                rec["peak_tflops"] = peak
                rec["mfu_pct"] = round(100 * achieved / (peak * 1e12), 2)
        print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
