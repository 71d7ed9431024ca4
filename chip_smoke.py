"""Does the serving path still start on the chip?  ``python chip_smoke.py``

One process, one TPU chip, random weights from ``--seed``. Phases, in order;
the first failure ends the run with a non-zero exit and no result line:

1. **Device** — jax must find a TPU (``JAX_PLATFORMS=cpu python
   chip_smoke.py`` fails, it does not shrink). Prints versions, device kind
   and count, ``bytes_limit``, and the compile-cache directory in use
   (``JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``).
2. **Kernels** — the Pallas kernels of the serving path on the chip,
   each against its plain XLA twin on the same seeded input, and each
   compiled program checked for its ``tpu_custom_call``: NMS at K=256 alone
   and vmapped over 16 rows (keep masks equal exactly); flash attention at
   the ``videomae_b_long`` shape [1,6272,12,64] and at T=784, bf16, against
   ``default_attention`` within a stated tolerance; the latent and the
   grouped-query prefill kernels against the plain softmax in float32.
3. **Server** — ``Server(cfg, enable_engine=True)`` in this process, the path
   ``python -m video_edge_ai_proxy_tpu.serve.server --engine`` takes:
   yolov8n at its registry widths, every other ``EngineConfig`` field at its
   shipped default, seeded weights served through ``engine.checkpoint_path``
   with the class prior zeroed so NMS sees candidates. 16 synthetic 1080p
   cameras are registered over REST with the control plane alone up (ingest
   workers are separate, jax-free processes); the server is then restarted
   with the engine and re-adopts the live workers from its registry, so the
   first tick sees all 16 cameras and the 16-row bucket is the first
   program compiled and run, cold or warm. A gRPC client lists streams,
   pulls frames and reads ``Inference`` until every camera delivered three
   results. Then ``/healthz``, the results, the compiled program and the
   log are checked; the server stops and every worker is killed by pid.
4. **Cache** — programs compiled vs served from the persistent cache, so a
   second run in the same checkout shows hits.

``--chips 4`` (run by hand on a four-chip host; the driver never passes it)
runs ONLY the dp-mesh path and what it is compared with: one recorded trace
through ``lockstep_checksum`` on one device (each shard's rows in turn) and
on a dp=4 mesh of real chips (checksums equal), then a short ``engine.mesh={"dp": 4}`` serve whose frame
batches, prefetch placements, thumbnail pools and variables must sit on four
distinct devices with every shard serving frames and none misrouted.

Set-up seconds and counts are printed; no fps, latency or utilization is —
that is the benchmark's job. The LAST line of stdout is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import logging
import os
import shutil
import signal
import sys
import tempfile
import threading
import time
import urllib.request
import warnings

import numpy as np

MODEL = "yolov8n"        # registry widths; the flagship detector
CAMERAS = 16
SRC_W, SRC_H = 1920, 1080
# Not a rate test, and deliberately slow: with random weights and the class
# prior zeroed EVERY frame carries the maximum 100 detections, and the
# engine's host-side drain (proto build, tracker, annotation emit per
# detection) then saturates well below the device's rate. At 15 fps x 16 the
# degradation ladder climbed to admission_pause and stayed there, starving
# half the fleet (first chip run of this script; PERF.md).
CAMERA_FPS = 2
RESULTS_PER_CAMERA = 3
NMS_K = 256              # batched_nms max_candidates (serving default)
IOU_THRESH = 0.45
# bf16 flash attention against its dense twin. Inputs are unit-variance
# bf16 with q scaled by 3, so the softmax is peaked and outputs are O(0.5)
# with |values| up to ~4. The twin is evaluated in float32 at the highest
# matmul precision, so the comparison measures the kernel and not the
# twin's own bf16 logits (run in bf16 the twin rounds logits of ~15 to
# +-0.06, several percent on a probability; that difference is printed,
# not judged). What is left is the kernel's bf16 output rounding, 2^-9
# relative (0.008 at 4), and its single-pass MXU products.
ATTN_MAX_ABS = 0.03
ATTN_MEAN_ABS = 0.003
SERVER_DEADLINE_S = 900.0


class SmokeFailure(SystemExit):
    """A phase failed. Exit code 1, message on stderr, no result line."""

    def __init__(self, msg: str):
        super().__init__(f"chip_smoke FAILED: {msg}")


def say(msg: str) -> None:
    print(f"[chip_smoke +{time.monotonic() - _T0:6.1f}s] {msg}", flush=True)


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


_T0 = time.monotonic()


# ---------------------------------------------------------------------------
# phase 1: device


def device_phase(chips: int):
    import jax
    import jaxlib

    from video_edge_ai_proxy_tpu.utils import compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SmokeFailure(
            f"jax found no TPU: platform={dev.platform!r} "
            f"({dev.device_kind} x{len(devices)}), JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '')!r}. This script proves "
            "the serving path on the chip; it does not run on another "
            "backend.")
    check(len(devices) >= chips,
          f"--chips {chips} needs {chips} devices, jax reports "
          f"{len(devices)}")
    from importlib import metadata

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:   # only a label for the line
        libtpu = "unknown"
    stats = dev.memory_stats() or {}
    check(stats.get("bytes_limit"),
          f"{dev} reports no bytes_limit in memory_stats(): {sorted(stats)}")
    cache_dir = compile_cache.configure(compile_cache.checkout_dir())
    say(f"device: jax {jax.__version__} jaxlib {jaxlib.__version__} libtpu "
        f"{libtpu}; {dev.device_kind} x{len(devices)}; bytes_limit "
        f"{stats['bytes_limit']}; compile cache {cache_dir} "
        f"({len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0} "
        "entries)")
    return dev


class CacheCounter:
    """Persistent compile-cache traffic of this process, from jax's own
    monitoring events."""

    def __init__(self):
        import jax.monitoring

        self.requests = self.hits = self.writes = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1

    def snapshot(self) -> dict:
        return {"compile_requests": self.requests,
                "persistent_cache_hits": self.hits,
                "persistent_cache_writes": self.writes}


# ---------------------------------------------------------------------------
# phase 2: kernels against their twins


def _seeded_boxes(rng: np.random.Generator, rows: int) -> np.ndarray:
    """[rows, K, 4] xyxy boxes in a 640² plane, overlapping heavily, with
    no pair's IoU within 1e-4 of the threshold — so the kernel and its
    twin must agree exactly however each rounds its divide. Rows are
    redrawn one by one until they have that margin (3 in 4 do)."""
    out = []
    while len(out) < rows:
        cxy = rng.uniform(40, 600, (NMS_K, 2))
        wh = rng.uniform(20, 220, (NMS_K, 2))
        boxes = np.concatenate([cxy - wh / 2, cxy + wh / 2], -1)
        x1, y1, x2, y2 = (boxes[:, i] for i in range(4))
        iw = np.clip(np.minimum(x2[:, None], x2[None])
                     - np.maximum(x1[:, None], x1[None]), 0, None)
        ih = np.clip(np.minimum(y2[:, None], y2[None])
                     - np.maximum(y1[:, None], y1[None]), 0, None)
        inter = iw * ih
        area = (x2 - x1) * (y2 - y1)
        iou = inter / (area[:, None] + area[None] - inter)
        if np.abs(iou - IOU_THRESH).min() > 1e-4:
            out.append(boxes.astype(np.float32))
    return np.stack(out)


def kernels_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from video_edge_ai_proxy_tpu.models.transformer import default_attention
    from video_edge_ai_proxy_tpu.ops.flash_attention import flash_attention
    from video_edge_ai_proxy_tpu.ops.nms import (
        nms_keep_mask_pallas, nms_keep_mask_xla,
    )

    rng = np.random.default_rng(seed)

    def pallas_nms(b):
        return nms_keep_mask_pallas(b, IOU_THRESH)

    def xla_nms(b):
        return nms_keep_mask_xla(b, IOU_THRESH)

    for rows in (1, 16):
        boxes = _seeded_boxes(rng, rows)
        if rows == 1:
            kernel, twin, arg = pallas_nms, xla_nms, jnp.asarray(boxes[0])
        else:
            kernel, twin = jax.vmap(pallas_nms), jax.vmap(xla_nms)
            arg = jnp.asarray(boxes)
        compiled = jax.jit(kernel).lower(arg).compile()
        check("tpu_custom_call" in compiled.as_text(),
              f"NMS x{rows}: no tpu_custom_call in the compiled program")
        got = np.asarray(compiled(arg))
        want = np.asarray(jax.jit(twin)(arg))
        check(got.shape == want.shape and got.dtype == np.bool_,
              f"NMS x{rows}: shape/dtype {got.shape} {got.dtype}")
        check(np.array_equal(got, want),
              f"NMS x{rows}: Pallas keep mask differs from the XLA twin in "
              f"{int((got != want).sum())} of {got.size} slots")
        say(f"kernel: NMS K={NMS_K} x{rows} == XLA twin "
            f"({int(got.sum())} of {got.size} kept)")

    for b, t in ((8, 784), (1, 6272)):
        q, k, v = (jnp.asarray(rng.standard_normal((b, t, 12, 64)),
                               jnp.bfloat16) for _ in range(3))
        q = q * 3
        compiled = jax.jit(flash_attention).lower(q, k, v).compile()
        check("tpu_custom_call" in compiled.as_text(),
              f"flash T={t}: no tpu_custom_call in the compiled program")
        got = np.asarray(compiled(q, k, v), np.float32)
        check(got.shape == (b, t, 12, 64) and np.isfinite(got).all(),
              f"flash T={t}: shape {got.shape} or non-finite values")
        with jax.default_matmul_precision("highest"):
            want = np.asarray(jax.jit(default_attention)(
                *(x.astype(jnp.float32) for x in (q, k, v))))
        err = np.abs(got - want)
        twin_err = np.abs(np.asarray(
            jax.jit(default_attention)(q, k, v), np.float32) - want)
        say(f"kernel: flash attention [{b},{t},12,64] bf16 vs the dense "
            f"twin in f32: max abs {err.max():.4f} mean abs "
            f"{err.mean():.5f} (bounds {ATTN_MAX_ABS} / {ATTN_MEAN_ABS}; "
            f"output rms {np.sqrt((want ** 2).mean()):.3f}; the twin run "
            f"in bf16 is off by max {twin_err.max():.4f} mean "
            f"{twin_err.mean():.5f})")
        check(err.max() <= ATTN_MAX_ABS and err.mean() <= ATTN_MEAN_ABS,
              f"flash T={t}: outside the stated bf16 tolerance")


def latent_prefill_phase(seed: int) -> None:
    """The latent attention's prefill kernel at the MLA cells' shapes (a
    chunk of 4 streams x 32 heads x 784 new positions over up to 3,328
    cached rows of 640 in a pool of 64 slots), compiled by Mosaic and run,
    against the plain softmax in float32 over each stream's context and new
    rows (what the CPU tests compare the interpreted kernel with)."""
    import jax
    import jax.numpy as jnp

    from video_edge_ai_proxy_tpu.models import mla

    b, t, h, dn, dr, dv, r, row, cap = 4, 784, 32, 128, 64, 128, 512, 640, \
        3328
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    bf = jnp.bfloat16
    pad = lambda a: jnp.pad(  # noqa: E731
        a, ((0, 0), (0, 0), (0, row - r - dr))).astype(bf)
    q = jax.random.normal(k[0], (b, t, h, dn + dr)).astype(bf)
    new = pad(jax.random.normal(k[1], (b, t, r + dr)))
    pool = pad(jax.random.normal(k[2], (64, 4096, r + dr)))[None]
    w_uk = (jax.random.normal(k[3], (r, h, dn)) * r ** -0.5).astype(bf)
    w_uv = (jax.random.normal(k[4], (r, h, dv)) * r ** -0.5).astype(bf)
    slots = jnp.asarray([5, 17, 0, 99])         # the last: a padded row
    ctx = jnp.asarray([32, 1616, 3200, 257])
    scale = (dn + dr) ** -0.5
    # what lies past a context may be anything
    for i in range(b):
        pool = pool.at[0, min(int(slots[i]), 63), int(ctx[i]):].set(1e30)
    compiled = jax.jit(
        lambda *a: mla.mla_prefill_attention(*a, scale, cap, 0)).lower(
        q, new, w_uk, w_uv, pool, slots, ctx).compile()
    check("tpu_custom_call" in compiled.as_text(),
          "latent prefill: no tpu_custom_call in the compiled program")
    got = np.asarray(compiled(q, new, w_uk, w_uv, pool, slots, ctx),
                     np.float32)
    check(got.shape == (b, t, h * dv) and np.isfinite(got).all(),
          f"latent prefill: shape {got.shape} or non-finite values")

    @jax.jit
    def plain(q, rows, n):
        """One stream in float32: ``rows`` [cap + t, row], of which the
        cached part counts up to ``n``."""
        f = lambda a: a.astype(jnp.float32)  # noqa: E731
        kv = lambda w: f(jnp.einsum(  # noqa: E731
            "sr,rhd->shd", rows[:, :r], w))     # rounded as the kernel's
        keys = jnp.concatenate(
            [kv(w_uk), jnp.broadcast_to(f(rows[:, None, r:r + dr]),
                                        (cap + t, h, dr))], -1)
        s = jnp.einsum("thd,shd->hts", f(q), keys) * scale
        at = jnp.arange(cap + t)[None]
        seen = jnp.where(at < cap, at < n,
                         at - cap <= jnp.arange(t)[:, None])
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", p, kv(w_uv)).reshape(t, -1)

    with jax.default_matmul_precision("highest"):
        want = np.stack([np.asarray(plain(
            q[i], jnp.concatenate(
                [jnp.where(jnp.arange(cap)[:, None] < ctx[i],
                           pool[0, min(int(slots[i]), 63), :cap], 0),
                 new[i]]), ctx[i])) for i in range(b)])
    err = np.abs(got - want)
    say(f"kernel: latent prefill attention [{b},{t},{h},{dn}+{dr}] bf16 "
        f"over depths {ctx.tolist()} vs the plain softmax in f32: max abs "
        f"{err.max():.4f} mean abs {err.mean():.5f} (bounds {ATTN_MAX_ABS} "
        f"/ {ATTN_MEAN_ABS}; output rms {np.sqrt((want ** 2).mean()):.3f})")
    check(err.max() <= ATTN_MAX_ABS and err.mean() <= ATTN_MEAN_ABS,
          "latent prefill: outside the stated bf16 tolerance")


def gqa_prefill_phase(seed: int) -> None:
    """The grouped-query attention's prefill kernel at both of its heads'
    cell shapes (a chunk of 4 streams x 784 new positions over up to 3,328
    cached positions of a pool of 64 slots: LFM2's 32 query heads on 8
    key-value heads of 64, Falcon-H1's 10 on 2 of 128), compiled by Mosaic
    and run, against the plain softmax in float32 over each stream's
    context and new positions (what the CPU tests compare the interpreted
    kernel with)."""
    import jax
    import jax.numpy as jnp

    from video_edge_ai_proxy_tpu.ops.flash_attention import (
        gqa_prefill_attention)

    b, t, cap, layers = 4, 784, 3328, 2
    for h, kv, hd in ((32, 8, 64), (10, 2, 128)):
        g = h // kv
        k = jax.random.split(jax.random.PRNGKey(seed), 5)
        bf = jnp.bfloat16
        q = jax.random.normal(k[0], (b, t, h, hd)).astype(bf)
        new_k, new_v = (jax.random.normal(x, (b, kv, hd, t)).astype(bf)
                        for x in k[1:3])
        pool = tuple(jax.random.normal(x, (layers, 64, kv, hd, 4096)).astype(
            bf) for x in k[3:])
        slots = jnp.asarray([5, 17, 0, 99])     # the last: a padded row
        ctx = jnp.asarray([32, 1616, 3200, 257])
        # what lies past a context may be anything
        for i in range(b):
            pool = tuple(p.at[1, min(int(slots[i]), 63), :, :,
                              int(ctx[i]):].set(jnp.nan) for p in pool)
        args = (q, new_k, new_v, *pool, 1, slots, ctx)
        compiled = jax.jit(
            lambda *a: gqa_prefill_attention(*a, cap=cap)).lower(
            *args).compile()
        check("tpu_custom_call" in compiled.as_text(),
              "gqa prefill: no tpu_custom_call in the compiled program")
        got = np.asarray(compiled(*args), np.float32)
        check(got.shape == (b, t, h * hd) and np.isfinite(got).all(),
              f"gqa prefill: shape {got.shape} or non-finite values")

        @jax.jit
        def plain(q, keys, values, n):
            """One stream in float32: ``keys`` / ``values`` [KV, hd, cap +
            t], of which the cached part counts up to ``n``."""
            f = lambda a: a.astype(jnp.float32)  # noqa: E731
            s = jnp.einsum("tkgd,kds->kgts", f(q).reshape(t, kv, g, hd),
                           f(keys)) * hd ** -0.5
            at = jnp.arange(cap + t)[None]
            seen = jnp.where(at < cap, at < n,
                             at - cap <= jnp.arange(t)[:, None])
            p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            return jnp.einsum("kgts,kds->tkgd", p, f(values)).reshape(t, -1)

        def cache(p, i):
            row = p[1, min(int(slots[i]), 63), :, :, :cap]
            return jnp.where(jnp.arange(cap) < ctx[i], row, 0)

        with jax.default_matmul_precision("highest"):
            want = np.stack([np.asarray(plain(
                q[i], jnp.concatenate([cache(pool[0], i), new_k[i]], -1),
                jnp.concatenate([cache(pool[1], i), new_v[i]], -1), ctx[i]))
                for i in range(b)])
        err = np.abs(got - want)
        say(f"kernel: gqa prefill attention [{b},{t},{h}/{kv},{hd}] bf16 "
            f"over depths {ctx.tolist()} vs the plain softmax in f32: max "
            f"abs {err.max():.4f} mean abs {err.mean():.5f} (bounds "
            f"{ATTN_MAX_ABS} / {ATTN_MEAN_ABS}; output rms "
            f"{np.sqrt((want ** 2).mean()):.3f})")
        check(err.max() <= ATTN_MAX_ABS and err.mean() <= ATTN_MEAN_ABS,
              "gqa prefill: outside the stated bf16 tolerance")


def stream_head_phase(seed: int, name: str = "tiny_videomae_lfm2") -> None:
    """The ``stream`` step kind on the chip at a tiny twin's size: two
    rounds of two streams through ``build_serving_step`` and a
    ``StreamStatePool`` (state donated, read and written by slot inside
    the program), the second continuing the first's state. Every head:
    LFM2's conv state and key-value cache, Xing4's latent cache, whose
    decode loop the prediction module drafts for, DeepSeek-V2's latent
    cache alone, whose router counts the pairs it routed in all,
    Falcon-H1's key-value cache beside its conv state and its float32
    state-space state, which each round writes over its slot's, and
    Nemotron-H's three kinds held by a layer's place among its kind, its
    latent expert layers counting the pairs routed in all."""
    import jax
    import jax.numpy as jnp

    from video_edge_ai_proxy_tpu.engine.runner import build_serving_step
    from video_edge_ai_proxy_tpu.engine.stream_state import StreamStatePool
    from video_edge_ai_proxy_tpu.models import registry

    spec = registry.get(name)
    model, variables = spec.init_params(jax.random.PRNGKey(seed))
    variables = spec.prepare(model, variables)
    c = model.cfg
    step = jax.jit(build_serving_step(model, spec), donate_argnums=(2,))
    pool = StreamStatePool(model, grow=4)
    rng = np.random.default_rng(seed)
    frames = rng.integers(0, 255, (4, spec.clip_len, 96, 128, 3), np.uint8)
    n_i = len(c.instruction_ids)
    for r in (1, 2):
        plan = pool.plan(["a", "b"], 4)
        out = dict(step(variables, frames, pool.state, plan["idx"],
                        plan["pos0"], plan["reset"], plan["rounds"]))
        pool.state = out.pop("state")
        host = {k: np.asarray(v) for k, v in out.items()}
        check(np.isfinite(host["top_probs"]).all()
              and (host["top_probs"] > 0).all(),
              f"stream head round {r}: non-finite or zero probabilities")
        check(host["positions"][:2].tolist()
              == (plan["pos0"][:2] + c.round_positions).tolist()
              and (plan["pos0"][:2] >= n_i).all(),
              f"stream head round {r}: positions {host['positions']}")
        check((host["history"][:2, :c.decode_steps * int(host["rounds"][0])]
               >= 0).all(), f"stream head round {r}: token history has holes")
        # a head whose router is limited to a few expert groups may send its
        # two held experts nothing in a round: it counts the pairs routed in
        # all, and those are what has to be there
        if "moe_load" in host:
            check(int(host.get("moe_pairs_total",
                               host["moe_load"].sum())) > 0,
                  f"stream head round {r}: no routed pair"
                  + ("" if "moe_pairs_total" in host
                     else " on the held experts"))
        if "ssm" in pool.state:
            ssm = np.asarray(pool.state["ssm"])[plan["idx"][:2]]
            check(ssm.dtype == np.float32 and np.isfinite(ssm).all()
                  and np.abs(ssm).max() > 0,
                  f"stream head round {r}: the state-space state was not "
                  "written")
        if "mtp_drafted" in host:
            check(1 <= int(host["decode_iters"]) <= c.decode_steps
                  and (host["mtp_accepted"] <= host["mtp_drafted"]).all()
                  and (host["draft_probs"] > 0).all(),
                  f"stream head round {r}: the drafted loop's counts")
        if "moe_pairs_total" in host:
            check(int(host["moe_load"].sum()) <= int(host["moe_pairs_total"])
                  and 0 <= int(host["moe_group_hits"])
                  <= int(host["moe_pairs_total"]),
                  f"stream head round {r}: the router's counts")
    held = pool.nbytes()
    check(held == sum(int(a.nbytes)
                      for a in jax.tree_util.tree_leaves(pool.state)),
          "stream head: pool bytes differ from its buffers'")
    say(f"stream head {name}: 2 rounds x 2 streams through the state pool "
        f"({held} B of {sorted(pool.state)} on "
        f"{sorted(str(d) for d in pool.state['tokens'].devices())}"
        + (f"), {int(host['moe_load'].sum())} routed pairs on the held "
           "experts " if "moe_load" in host else "), no experts ")
        + (f"of {int(host['moe_pairs_total'])} "
           if "moe_pairs_total" in host else "")
        + f"in the last round, tokens {host['tokens'][0].tolist()}"
        + (f", {int(host['decode_iters'])} decode iterations"
           if "decode_iters" in host else ""))


# ---------------------------------------------------------------------------
# phase 3: the server


class LogWatch(logging.Handler):
    """What the package logged at WARNING or above, and every Python
    warning raised, while the server ran."""

    def __init__(self):
        super().__init__(level=logging.WARNING)
        self.records: list = []
        self.warnings: list = []

    def emit(self, record: logging.LogRecord) -> None:
        self.records.append(record)

    @contextlib.contextmanager
    def watching(self):
        pkg = logging.getLogger("vep_tpu")
        pkg.addHandler(self)
        prev = warnings.showwarning

        def show(message, category, filename, lineno, file=None, line=None):
            self.warnings.append((category.__name__, str(message),
                                  f"{filename}:{lineno}"))
            prev(message, category, filename, lineno, file, line)

        # catch_warnings restores the filters and showwarning on exit;
        # "default" shows each warning once per location, deprecations
        # included (Python hides those outside __main__ otherwise).
        with warnings.catch_warnings():
            warnings.simplefilter("default")
            warnings.showwarning = show
            try:
                yield self
            finally:
                pkg.removeHandler(self)


def seeded_checkpoint(path: str, model: str, seed: int) -> None:
    """Random weights from ``seed`` in the canonical checkpoint format,
    class prior zeroed (replay/checksum.py) so NMS sees candidates."""
    import jax

    from video_edge_ai_proxy_tpu.models import registry
    from video_edge_ai_proxy_tpu.parallel.sharding import unbox
    from video_edge_ai_proxy_tpu.replay.checksum import zero_class_prior
    from video_edge_ai_proxy_tpu.utils.checkpoint import save_msgpack

    _, variables = registry.get(model).init_params(jax.random.PRNGKey(seed))
    variables = zero_class_prior(variables)
    save_msgpack(path, jax.tree.map(np.asarray, unbox(variables)))


def _rest(base: str, path: str, body=None):
    req = urllib.request.Request(
        base + path,
        data=None if body is None else json.dumps(body).encode(),
        method="GET" if body is None else "POST")
    with urllib.request.urlopen(req, timeout=30) as resp:
        return resp.status, json.loads(resp.read() or b"null")


def _mem_available_gib() -> float:
    with open("/proc/meminfo") as f:
        for line in f:
            if line.startswith("MemAvailable:"):
                return int(line.split()[1]) / (1 << 20)
    return float("nan")


def _kill_workers(pids) -> list:
    """SIGKILL every ingest worker by pid; the pids still alive after."""
    pids = [p for p in pids if p > 1]      # 0 and -1 address process groups
    for pid in pids:
        with contextlib.suppress(ProcessLookupError):
            os.kill(pid, signal.SIGKILL)
    deadline = time.monotonic() + 15.0
    alive = list(pids)
    while alive and time.monotonic() < deadline:
        for pid in list(alive):
            with contextlib.suppress(ChildProcessError):
                os.waitpid(pid, os.WNOHANG)       # reap our own children
            try:
                with open(f"/proc/{pid}/stat") as f:
                    state = f.read().rsplit(")", 1)[1].split()[0]
            except OSError:
                state = "gone"
            if state in ("gone", "Z", "X"):
                alive.remove(pid)
        if alive:
            time.sleep(0.2)
    return alive


def server_phase(seed: int) -> dict:
    import grpc

    from video_edge_ai_proxy_tpu.proto import pb, pb_grpc
    from video_edge_ai_proxy_tpu.serve.server import Server
    from video_edge_ai_proxy_tpu.utils.config import Config

    tmp = tempfile.mkdtemp(prefix="vep_chip_smoke_")
    shm = tempfile.mkdtemp(
        prefix="vep_chip_smoke_",
        dir="/dev/shm" if os.path.isdir("/dev/shm") else None)
    cfg = Config()
    cfg.bus.shm_dir = shm
    cfg.annotation.endpoint = "http://127.0.0.1:1/annotate"   # no network
    cfg.engine.model = MODEL
    cfg.engine.checkpoint_path = os.path.join(tmp, "seeded.msgpack")
    t0 = time.monotonic()
    seeded_checkpoint(cfg.engine.checkpoint_path, cfg.engine.model, seed)
    say(f"server: seeded checkpoint written in {time.monotonic() - t0:.1f}s")

    cams = [f"cam{i:02d}" for i in range(CAMERAS)]
    results: dict = {c: [] for c in cams}
    stream_error: list = []
    stop = threading.Event()
    watch = LogWatch()
    srv = None
    pids: list = []
    facts: dict = {}
    data_dir = os.path.join(tmp, "data")
    url = f"test://pattern?w={SRC_W}&h={SRC_H}&fps={CAMERA_FPS}&gop=30"
    try:
        with watch.watching():
            # Boot 1, control plane only: register the fleet over REST.
            # Cameras that joined a running engine one by one would walk
            # the buckets 1, 2, 4, ... and reach 16 only if a compile
            # stalled a tick for longer than a camera period — true cold,
            # not from a warm cache. Stopping this server detaches the
            # workers (worker adoption, the shipped default); they keep
            # publishing.
            srv = Server(cfg, data_dir=data_dir, grpc_port=0, rest_port=0)
            srv.start()
            rest = f"http://127.0.0.1:{srv._rest.bound_port}"
            for cam in cams:
                status, _ = _rest(rest, "/api/v1/process",
                                  {"name": cam, "rtsp_endpoint": url})
                check(status == 200, f"REST register {cam}: HTTP {status}")
            channel = grpc.insecure_channel(
                f"127.0.0.1:{srv.bound_grpc_port}")
            stub = pb_grpc.ImageStub(channel)
            deadline = time.monotonic() + 120.0
            while True:
                listed = list(stub.ListStreams(pb.ListStreamRequest()))
                pids = [s.pid for s in listed if s.pid > 1]
                if len(listed) == CAMERAS and all(
                        s.running and s.health_status == "healthy"
                        for s in listed):
                    break
                check(time.monotonic() < deadline,
                      "cameras not all publishing after 120 s: "
                      + str({s.name: s.health_status for s in listed}))
                time.sleep(0.5)
            channel.close()
            srv.stop()
            srv = None
            say(f"server: {CAMERAS} cameras registered over REST ({url}), "
                "all publishing; control plane stopped, workers detached")

            # Boot 2, the path under test.
            t0 = time.monotonic()
            srv = Server(cfg, data_dir=data_dir, grpc_port=0, rest_port=0,
                         enable_engine=True)
            srv.start()
            facts["boot_s"] = round(time.monotonic() - t0, 1)
            say(f"server: up with the engine in {facts['boot_s']}s, "
                f"{CAMERAS} live workers re-adopted from the registry")
            check(sorted(p.state.pid for p in srv.process_manager.list())
                  == sorted(pids),
                  "the restarted server did not re-adopt the same workers")
            rest = f"http://127.0.0.1:{srv._rest.bound_port}"
            channel = grpc.insecure_channel(
                f"127.0.0.1:{srv.bound_grpc_port}",
                options=[("grpc.max_receive_message_length", 64 << 20)])
            stub = pb_grpc.ImageStub(channel)

            def read_results():
                try:
                    for r in stub.Inference(pb.InferenceRequest()):
                        kept = results.get(r.device_id)
                        if kept is not None and len(kept) < 64:
                            kept.append(r)     # enough to check; bounded
                        if stop.is_set():
                            return
                except grpc.RpcError as exc:
                    if not stop.is_set():
                        stream_error.append(exc)

            reader = threading.Thread(target=read_results, daemon=True,
                                      name="chip-smoke-inference-client")
            reader.start()

            seen_compiles: set = set()
            next_beat = 0.0
            deadline = time.monotonic() + SERVER_DEADLINE_S
            while True:
                snap = srv.engine.perf.snapshot()
                for rec in snap["compiles"]:
                    key = (rec["model"], rec["geometry"], rec["bucket"])
                    if key not in seen_compiles:
                        seen_compiles.add(key)
                        say(f"server: compiled {rec['model']} "
                            f"{rec['geometry']} bucket={rec['bucket']} in "
                            f"{rec['compile_s']:.1f}s "
                            f"({rec['flops'] / 1e9:.0f} GFLOP/step)")
                ran16 = any(b["bucket"] == CAMERAS and b["frames"] > 0
                            for b in snap["buckets"])
                least = min(len(v) for v in results.values())
                if least >= RESULTS_PER_CAMERA and ran16:
                    break
                check(not stream_error,
                      f"Inference stream broke: {stream_error}")
                now = time.monotonic()
                check(now < deadline,
                      f"after {SERVER_DEADLINE_S:.0f}s: fewest results per "
                      f"camera {least}, 16-row bucket ran: {ran16}, "
                      f"programs {sorted(seen_compiles)}")
                if now >= next_beat:
                    say(f"server: waiting — fewest results per camera "
                        f"{least}, programs compiled {len(seen_compiles)}, "
                        f"16-row bucket ran: {ran16} (a cold compile is "
                        f"~20 s per bucket); host MemAvailable "
                        f"{_mem_available_gib():.1f} GiB")
                    next_beat = now + 15.0
                time.sleep(0.5)

            # -- the rest of the client surface
            listed = {s.name: s for s in
                      stub.ListStreams(pb.ListStreamRequest())}
            check(set(listed) == set(cams),
                  f"ListStreams: {sorted(listed)}")
            check(all(s.running and s.source == "synthetic"
                      for s in listed.values())
                  and sorted(s.pid for s in listed.values()) == sorted(pids),
                  "ListStreams: a camera is not running/synthetic or "
                  "changed pid: "
                  + str({n: (s.running, s.source, s.pid)
                         for n, s in listed.items()}))
            frames = list(stub.VideoLatestImage(
                iter([pb.VideoFrameRequest(device_id=c) for c in cams[:3]]),
                timeout=60))
            check(len(frames) == 3, f"VideoLatestImage: {len(frames)} of 3")
            for f in frames:
                check((f.width, f.height) == (SRC_W, SRC_H)
                      and len(f.data) == SRC_W * SRC_H * 3
                      and f.trace_id != 0,
                      f"VideoLatestImage {f.device_id}: {f.width}x{f.height} "
                      f"{len(f.data)} B trace_id={f.trace_id}")

            status, health = _rest(rest, "/healthz")
            eng = health["engine"]
            check(status == 200 and eng["backend"] == "tpu"
                  and eng["device_ok"] and eng["engine_thread_alive"]
                  and eng["drain_thread_alive"]
                  and eng["transfer_thread_alive"],
                  f"/healthz {status}: {eng}")
            facts["healthz"] = {k: eng[k] for k in (
                "backend", "devices", "programs_compiled", "model")}

            stop.set()
            buckets = set(cfg.engine.batch_buckets)
            n_results = n_dets = 0
            for cam, rs in results.items():
                for r in rs:
                    n_results += 1
                    check(r.device_id == cam and r.model == MODEL
                          and r.trace_id != 0 and r.batch_size in buckets
                          and r.timestamp > 0,
                          f"result {cam}: model={r.model} trace_id="
                          f"{r.trace_id} batch_size={r.batch_size}")
                    check(0 < len(r.detections) <= 100,
                          f"result {cam}: {len(r.detections)} detections "
                          "(zeroed class prior must yield candidates)")
                    for d in r.detections:
                        n_dets += 1
                        # Random weights put boxes anywhere, the letterbox
                        # bars included, and nothing clips them: bound the
                        # coordinates loosely, the rest exactly.
                        ok = (0.25 <= d.confidence <= 1.0
                              and 0 <= d.class_id < 80 and d.class_name
                              and d.box.width > 0 and d.box.height > 0
                              and abs(d.box.left) <= 4 * SRC_W
                              and abs(d.box.top) <= 4 * SRC_W
                              and d.box.width <= 8 * SRC_W
                              and d.box.height <= 8 * SRC_W)
                        check(ok, f"result {cam}: malformed detection {d}")
            facts["results"] = n_results
            facts["detections"] = n_dets

            snap = srv.engine.perf.snapshot()
            check(snap["aot_fallbacks"] == 0,
                  f"AOT->jit fallbacks: {snap['aot_fallbacks']}")
            programs = srv.engine.compiled_programs()
            key16 = [k for k in programs
                     if k[0] == MODEL and k[2] == (SRC_H, SRC_W)
                     and k[3] == CAMERAS]
            check(len(key16) == 1 and programs[key16[0]] is not None,
                  f"no AOT executable for the 16-row program: "
                  f"{sorted(map(str, programs))}")
            text = programs[key16[0]].as_text()
            check("tpu_custom_call" in text,
                  "the served 16x1080p program has no tpu_custom_call: "
                  "the Pallas NMS is not in it")
            facts["programs"] = [
                {k: rec[k] for k in ("model", "geometry", "bucket",
                                     "compile_s")}
                for rec in snap["compiles"]]
            facts["bucket_frames"] = {
                str(b["bucket"]): b["frames"] for b in snap["buckets"]}
    finally:
        stop.set()
        with contextlib.suppress(NameError):
            channel.close()
        if srv is not None:
            pids = sorted(set(pids) | {
                p.state.pid for p in srv.process_manager.list()
                if p.state and p.state.pid})
            srv.stop()
        survivors = _kill_workers(pids)
        shutil.rmtree(shm, ignore_errors=True)
        shutil.rmtree(tmp, ignore_errors=True)
    check(not survivors, f"ingest workers survived SIGKILL: {survivors}")
    check(len(pids) == CAMERAS, f"{len(pids)} worker pids for {CAMERAS}")
    say(f"server: stopped; {len(pids)} ingest workers killed by pid, none "
        "survives")

    # -- what was logged while it ran
    donation = [w for w in watch.warnings if "donated" in w[1].lower()]
    check(not donation, f"donation warning while serving: {donation}")
    engine_errors = [
        f"{r.name}: {r.getMessage()}" for r in watch.records
        if r.levelno >= logging.ERROR
        and r.name.startswith("vep_tpu.engine")]
    check(not engine_errors, f"engine logged errors: {engine_errors[:5]}")
    deprecations = sorted({w for w in watch.warnings
                           if "Deprecat" in w[0]})
    facts["deprecation_warnings"] = [list(w) for w in deprecations]
    say(f"server: {facts['results']} results / {facts['detections']} "
        f"detections checked; frames per bucket {facts['bucket_frames']}; "
        f"AOT fallbacks 0; donation warnings 0 (one chip: donate_frames "
        f"auto does not ask); deprecation warnings {len(deprecations)}")
    for w in deprecations:
        say(f"server: deprecation: {w}")
    return facts


# ---------------------------------------------------------------------------
# --chips 4: the dp mesh on real chips


def _streams_over_shards(dp: int, per_shard: int) -> list:
    """Camera names whose crc32 placement (collector.stream_shard) puts
    ``per_shard`` of them on every dp shard."""
    from video_edge_ai_proxy_tpu.engine.collector import stream_shard

    have = {s: [] for s in range(dp)}
    i = 0
    while any(len(v) < per_shard for v in have.values()):
        name = f"cam{i}"
        s = stream_shard(name, dp)
        if len(have[s]) < per_shard:
            have[s].append(name)
        i += 1
    return sorted(sum(have.values(), []))


def _devices_of(arr) -> list:
    """(device id, first-axis slice) per addressable shard — read from
    the array, not from the mesh it was asked to live on."""
    return sorted(
        (sh.device.id, sh.index[0].start or 0, sh.index[0].stop)
        for sh in arr.addressable_shards)


def four_chip_phase(seed: int) -> dict:
    import queue

    import jax

    from video_edge_ai_proxy_tpu.bus.interface import FrameMeta
    from video_edge_ai_proxy_tpu.bus.memory_bus import MemoryFrameBus
    from video_edge_ai_proxy_tpu.engine import InferenceEngine
    from video_edge_ai_proxy_tpu.models import registry
    from video_edge_ai_proxy_tpu.models.blob import blob_color
    from video_edge_ai_proxy_tpu.parallel import make_mesh
    from video_edge_ai_proxy_tpu.replay.harness import lockstep_checksum
    from video_edge_ai_proxy_tpu.replay.recorder import (
        record_synthetic_trace,
    )
    from video_edge_ai_proxy_tpu.uplink.queue import AnnotationQueue
    from video_edge_ai_proxy_tpu.utils.config import EngineConfig

    dp = 4
    chips = jax.devices()[:dp]
    facts: dict = {}
    tmp = tempfile.mkdtemp(prefix="vep_chip_smoke4_")
    try:
        # -- leg 1: one trace, one chip vs four. The one chip runs each
        # shard's rows as a batch of their own, in turn: the same program
        # shape every chip of the mesh runs at once. (One chip running the
        # whole 4-row batch is NOT bit-identical on a TPU — the compiler
        # tiles a convolution by its batch size; measured on v5e, PERF.md.)
        trace = os.path.join(tmp, "trace.vtrace")
        names = _streams_over_shards(dp, 1)
        record_synthetic_trace(trace, names, width=SRC_W, height=SRC_H,
                               fps=30.0, gop=30, frames=6)
        t0 = time.monotonic()
        one = lockstep_checksum(trace, model=MODEL, shards=dp)
        say(f"mesh: lockstep on one chip (the {dp} shards' rows in turn): "
            f"{one} in {time.monotonic() - t0:.1f}s")
        t0 = time.monotonic()
        four = lockstep_checksum(
            trace, model=MODEL, mesh=make_mesh(dp=dp, devices=chips))
        say(f"mesh: lockstep on a dp={dp} mesh of {chips}: {four} in "
            f"{time.monotonic() - t0:.1f}s")
        check(one["checksum"] != 0 and one["frames"] == four["frames"]
              == 6 * dp,
              f"lockstep replayed {one['frames']}/{four['frames']} frames, "
              f"checksum {one['checksum']}")
        check(one["checksum"] == four["checksum"],
              f"dp={dp} mesh checksum {four['checksum']} != one-chip "
              f"{one['checksum']}: four chips do not compute what one does")
        facts["lockstep_checksum"] = one["checksum"]

        # -- leg 2: a short engine.mesh serve, placement read off arrays
        model = "blob_gauge"
        side = registry.get(model).input_size
        streams = _streams_over_shards(dp, 2)
        owner = {sid: i for i, sid in enumerate(streams)}   # color key
        blob_w, blob_h = side // 6, side // 8
        span = side - blob_w - 16

        def scene(key: int, step: int) -> np.ndarray:
            frame = np.full((side, side, 3), 114, np.uint8)
            phase = step % (2 * span)
            x0 = 8 + (phase if phase < span else 2 * span - phase)
            y0 = 8 + 4 * blob_h * (key % 4) // 2
            frame[y0:y0 + blob_h, x0:x0 + blob_w] = blob_color(key)
            return frame

        bus = MemoryFrameBus()
        placed_on: list = []
        try:
            eng = InferenceEngine(
                bus, EngineConfig(model=model, mesh={"dp": dp}),
                annotations=AnnotationQueue(handler=lambda batch: True))
            eng.warmup()
            # Every batch the transfer thread places, as it places it.
            place = eng._xfer._place

            def recording_place(frames):
                arr = place(frames)
                placed_on.append(_devices_of(arr))
                return arr

            eng._xfer._place = recording_place
            for sid in streams:
                bus.create_stream(sid, side * side * 3)
            results_q: queue.Queue = queue.Queue()
            with eng._sub_lock:
                eng._subscribers.append((results_q, None))
            truth: dict = {}
            eng.start()
            try:
                t_end = time.monotonic() + 240.0
                step, last_ts, got = 0, 0, []
                while len(got) < 40 * len(streams):
                    check(time.monotonic() < t_end,
                          f"mesh serve: {len(got)} results in 240 s")
                    ts = max(int(time.time() * 1000), last_ts + 1)
                    last_ts = ts
                    for sid in streams:
                        truth[(sid, ts)] = owner[sid]
                        bus.publish(sid, scene(owner[sid], step), FrameMeta(
                            width=side, height=side, channels=3,
                            timestamp_ms=ts, is_keyframe=True))
                    step += 1
                    time.sleep(0.03)
                    with contextlib.suppress(queue.Empty):
                        while True:
                            r = results_q.get_nowait()
                            if r is not None:
                                got.append(r)
                var_leaf = jax.tree.leaves(eng._variables)[0]
                var_devices = _devices_of(var_leaf)
                thumb_devices = sorted(
                    d.id for sub in eng._thumbs._subs
                    for d in sub._pool.devices())
                programs = eng.compiled_programs()
            finally:
                eng.stop()
            snap = eng.perf.snapshot()
        finally:
            bus.close()

        ids = sorted(d.id for d in chips)
        matched = misrouted = 0
        for r in got:
            key = truth.get((r.device_id, r.timestamp))
            if key is None:
                continue
            for d in r.detections:
                if d.class_id == key:
                    matched += 1
                else:
                    misrouted += 1
        check(matched >= 20 * len(streams) and misrouted == 0,
              f"mesh serve: {matched} matched, {misrouted} misrouted "
              "detections")
        check(placed_on, "mesh serve: the transfer thread placed nothing")
        for devs in placed_on:
            rows = max(stop for _, _, stop in devs)
            check(sorted(d for d, _, _ in devs) == ids
                  and sorted((a, b) for _, a, b in devs)
                  == [(i * rows // dp, (i + 1) * rows // dp)
                      for i in range(dp)],
                  f"a frame batch was not split over the {dp} chips: "
                  f"{devs}")
        check(sorted(d for d, _, _ in var_devices) == ids
              and all(a == 0 and b is None for _, a, b in var_devices),
              f"variables are not replicated on the {dp} chips: "
              f"{var_devices}")
        check(thumb_devices == ids,
              f"thumbnail sub-pools sit on {thumb_devices}, want {ids}")
        shard_frames = {s["shard"]: s["frames"]
                        for s in snap.get("shards", ())}
        check(len(shard_frames) == dp
              and all(v > 0 for v in shard_frames.values()),
              f"per-shard frame counts: {shard_frames}")
        check(snap["aot_fallbacks"] == 0,
              f"AOT fallbacks on the mesh: {snap['aot_fallbacks']}")
        texts = [p.as_text() for p in programs.values() if p is not None]
        check(texts and all("tpu_custom_call" in t for t in texts),
              "a mesh-served program has no Pallas NMS custom call")
        check(all("buffer_donor" in t or "input_output_alias" in t
                  for t in texts),
              "the frames argument is not donated in a mesh program")
        facts.update(
            mesh_results=len(got), matched=matched, misrouted=misrouted,
            batches_placed=len(placed_on), shard_frames=shard_frames,
            programs=len(texts))
        say(f"mesh: dp={dp} serve — {len(got)} results, {matched} matched "
            f"/ 0 misrouted detections; {len(placed_on)} batches each "
            f"split over devices {ids}; variables and thumbnail pools on "
            f"{ids}; frames per shard {shard_frames}; {len(texts)} "
            "programs, Pallas NMS inside, frames donated")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return facts


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 = ONLY the dp-mesh path and what it is "
                         "compared with, on a four-chip host")
    ap.add_argument("--seed", type=int, default=0,
                    help="weights and kernel inputs are made from this")
    args = ap.parse_args(argv)

    dev = device_phase(args.chips)
    import jax

    cache = CacheCounter()
    if args.chips == 4:
        facts = four_chip_phase(args.seed)
    else:
        kernels_phase(args.seed)
        latent_prefill_phase(args.seed)
        gqa_prefill_phase(args.seed)
        stream_head_phase(args.seed)
        stream_head_phase(args.seed, "tiny_videomae_xing4")
        stream_head_phase(args.seed, "tiny_videomae_dsv2")
        stream_head_phase(args.seed, "tiny_videomae_b_falcon_h1")
        stream_head_phase(args.seed, "tiny_videomae_nemotron3_super")
        facts = server_phase(args.seed)
    counts = cache.snapshot()
    say(f"cache: {counts} in "
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR') or '.jax_cache'}; "
        "a second run in this checkout serves its programs from it")
    say(f"facts: {json.dumps(facts, sort_keys=True)}")
    say(f"done in {time.monotonic() - _T0:.0f}s")
    # Nothing may follow the result line: silence the package logger.
    logging.getLogger("vep_tpu").handlers.clear()
    sys.stdout.flush()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
