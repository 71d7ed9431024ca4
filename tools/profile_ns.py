"""Stage-level profiling of the north-star serving program on real TPU.

Usage: ``python tools/profile_ns.py [--stages]``

Methodology (same as bench.py): each probe is folded into ONE compiled
program — ``lax.scan`` over ITERS iterations with the input perturbed by
the loop index — and timed around a single dispatch + scalar fetch, so
host dispatch amortizes out. Two hard-won rules:

- Perturb EVERY input per iteration. XLA's loop-invariant code motion
  hoists a constant-input body out of the scan and you time nothing.
- Compare only within one run: probes of one run share a machine and a
  compile, and are comparable; runs are not, until their spread is known.

Findings log (relative, 16×1080p → YOLOv8n 640, see BASELINE.md):
- letterbox: NHWC dense-matmul form wins. Tried and lost: reshape-mean
  box decimation (14x slower — strided-layout reduce), strided-slice sums,
  depthwise strided conv, reduce_window, planar-NCHW matmuls, int8 MXU
  H-pass. The u8→bf16 cast + C=3 lane underfill bound it at ~2 ms.
- forward: stem/down2/c2f_2 (≤32 ch at ≥160² spatial) are >half of the
  time — lane underfill again (C≪128), not MXU FLOPs. A space-to-depth
  stem recovers ~10-15 % of forward but changes the architecture; kept as
  an experiment, not the default.
- NMS: exact top_k(8400→256) ≈ the whole suppression kernel; approx_max_k
  and the 8-row-blocked Pallas loop each shave ~0.1 ms.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

ITERS = 50
STREAMS = 16
SRC_H, SRC_W = 1080, 1920


def timed(name, fn, *args):
    """Scan-fold fn(*args) ITERS times with perturbed inputs; print ms."""

    @jax.jit
    def mega(*a):
        def body(carry, i):
            pert = [x + i.astype(jnp.uint8) if x.dtype == jnp.uint8
                    else x + i.astype(x.dtype) * 1e-3 for x in a]
            out = fn(*pert)
            s = sum(jnp.sum(l).astype(jnp.float32)
                    for l in jax.tree.leaves(out))
            return carry + s, None

        total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                                jnp.arange(ITERS))
        return total

    t0 = time.perf_counter()
    np.asarray(mega(*args))
    compile_s = time.perf_counter() - t0
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(mega(*args))
        best = min(best, time.perf_counter() - t0)
    ms = best / ITERS * 1000.0
    print(f"{name:44s} {ms:8.3f} ms/iter   (compile {compile_s:.1f}s)",
          flush=True)
    return ms


def main(stages: bool = False):
    from video_edge_ai_proxy_tpu.engine.runner import build_serving_step
    from video_edge_ai_proxy_tpu.models import registry
    from video_edge_ai_proxy_tpu.ops.nms import batched_nms
    from video_edge_ai_proxy_tpu.ops.preprocess import preprocess_letterbox

    print("backend:", jax.default_backend(), flush=True)
    rng = np.random.default_rng(0)
    base_dev = jax.device_put(rng.integers(
        0, 256, (STREAMS, SRC_H, SRC_W, 3), dtype=np.uint8))

    spec = registry.get("yolov8n")
    model, variables = spec.init_params(jax.random.PRNGKey(0))
    serving = build_serving_step(model, spec)

    timed("full serving step", lambda u8: serving(variables, u8), base_dev)
    timed("letterbox (NHWC matmul)",
          lambda u8: preprocess_letterbox(u8, 640)[0], base_dev)

    x640 = jnp.asarray(rng.standard_normal((STREAMS, 640, 640, 3)),
                       jnp.bfloat16)
    timed("model.apply (decode=True)",
          lambda x: model.apply(variables, x), x640)

    a = 8400
    boxes = jnp.asarray(rng.uniform(0, 640, (STREAMS, a, 4)), jnp.float32)
    scores = jnp.asarray(rng.uniform(0, 1, (STREAMS, a)), jnp.float32) ** 4
    cls = jnp.asarray(rng.integers(0, 80, (STREAMS, a)), jnp.float32)
    timed("batched_nms (approx topk)",
          lambda b, s, c: batched_nms(b, s, c.astype(jnp.int32),
                                      approx_topk=True),
          boxes, scores, cls)
    timed("batched_nms (exact topk)",
          lambda b, s, c: batched_nms(b, s, c.astype(jnp.int32),
                                      approx_topk=False),
          boxes, scores, cls)
    timed("top_k(8400->256) + gather only",
          lambda b, s: jax.vmap(
              lambda bi, si: (lambda ts, ti: (bi[ti], ts))(
                  *jax.lax.top_k(si, 256)))(b, s),
          boxes, scores)

    if not stages:
        return

    import flax.linen as nn

    from video_edge_ai_proxy_tpu.models.common import ConvBN
    from video_edge_ai_proxy_tpu.models.yolov8 import C2f, SPPF, DetectHead

    def apply_probe(mod, shape, name, seed=0):
        x = jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
        v = mod.init(jax.random.PRNGKey(seed), x)
        timed(name, lambda xx: jax.tree.map(
            lambda y: y.astype(jnp.float32), mod.apply(v, xx)), x)

    B = STREAMS
    apply_probe(ConvBN(16, stride=2, name="stem"), (B, 640, 640, 3),
                "stem conv 3->16 s2 @640")
    apply_probe(ConvBN(32, stride=2, name="down2"), (B, 320, 320, 16),
                "down2 conv 16->32 s2 @320")
    apply_probe(C2f(32, 1, True, name="c2f_2"), (B, 160, 160, 32),
                "c2f_2 (32, n=1) @160")
    apply_probe(ConvBN(64, stride=2, name="down3"), (B, 160, 160, 32),
                "down3 conv 32->64 s2 @160")
    apply_probe(C2f(64, 2, True, name="c2f_3"), (B, 80, 80, 64),
                "c2f_3 (64, n=2) @80")
    apply_probe(ConvBN(128, stride=2, name="down4"), (B, 80, 80, 64),
                "down4 conv 64->128 s2 @80")
    apply_probe(C2f(128, 2, True, name="c2f_4"), (B, 40, 40, 128),
                "c2f_4 (128, n=2) @40")

    class Tail(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = ConvBN(256, stride=2, name="down5")(x)
            x = C2f(256, 1, True, name="c2f_5")(x)
            return SPPF(256, name="sppf")(x)

    apply_probe(Tail(), (B, 40, 40, 128), "down5+c2f_5+sppf @20")

    cfg = model.cfg

    class HeadOnly(nn.Module):
        @nn.compact
        def __call__(self, feats):
            return DetectHead(cfg, [f.shape[-1] for f in feats],
                              name="detect")(feats)

    feats = [jnp.asarray(rng.standard_normal((B, 80, 80, 64)), jnp.bfloat16),
             jnp.asarray(rng.standard_normal((B, 40, 40, 128)), jnp.bfloat16),
             jnp.asarray(rng.standard_normal((B, 20, 20, 256)), jnp.bfloat16)]
    head = HeadOnly()
    hv = head.init(jax.random.PRNGKey(1), feats)
    timed("detect head (3 levels)",
          lambda a_, b_, c_: [o.astype(jnp.float32)
                              for pair in head.apply(hv, [a_, b_, c_])
                              for o in pair],
          *feats)


if __name__ == "__main__":
    main(stages="--stages" in sys.argv)
