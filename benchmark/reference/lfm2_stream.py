"""Plain float32 reference of the streaming head: VideoMAE encoder ->
connector -> LFM2-MoE decoder (``lfm2_moe``, LiquidAI/LFM2-24B-A2B
``config.json``), as ``configs/lfm2_stream.json`` cuts it.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
no cache, no pool, no kernels, no routing tricks. It imports nothing from
``video_edge_ai_proxy_tpu`` (the encoder and the preprocess are those of
``reference/vision_transformer.py``) and is handed weights made by
``vbench.weights`` from the seed.

One call is ONE FULL CAUSAL FORWARD over a stream's whole context since
its reset,

    [instruction, clip_1 tokens, served tokens_1, ..., clip_r tokens,
     served tokens_r]

teacher-forced with the tokens the program emitted, and returns the logits
at the D positions that predict round r's tokens. The sequence is always
laid out for the most rounds a context holds (one compiled shape; a causal
model's outputs do not depend on what follows them), and ``rounds`` picks
the positions.

The layers, from the published config: RMSNorm before the operator and
before the feed-forward, two residual adds. ``conv``: ``B, C, x =
split(in_proj(h), 3)``, ``y = out_proj(C * causal_conv1d(B * x))``,
depthwise, ``conv_L_cache`` taps, no bias. ``full_attention``: grouped
query, per-head RMS-normed q and k, rotate-half rotary positions, causal.
Feed-forward: the first ``num_dense_layers`` dense SwiGLU; the rest routed
SwiGLU experts: ``s = sigmoid(router(h))``, the top-k of ``s +
expert_bias``, their ``s`` renormalised to sum 1, times
``routed_scaling_factor``; computed here for EVERY held expert on every
token and weighted (zero where the token did not choose it). Only the
experts in ``experts_held`` exist: what the others would add is left out,
as on one chip of the deployment. Output head tied to the embedding.

Departures, the configuration's (``assumed`` there): the connector
(Linear, exact GELU, Linear over the encoder's final-norm tokens), the
tied head, the instruction ids, D, greedy decoding.

``quant`` selects the control: every matmul's operands rounded to float8
e4m3 with a per-tensor scale, products accumulated in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from vbench import loader


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [T, H, hd] at positions 0..T-1, rotate-half."""
    t, _, hd = x.shape
    inv = theta ** (-np.arange(0, hd, 2, dtype=np.float32) / hd)
    ang = np.arange(t, dtype=np.float32)[:, None] * inv[None]
    cos = jnp.asarray(np.concatenate([np.cos(ang)] * 2, -1))[:, None]
    sin = jnp.asarray(np.concatenate([np.sin(ang)] * 2, -1))[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _silu(x):
    return x * jax.nn.sigmoid(x)


def features(p, clips_u8, enc, mm, vt):
    """[N, T, H, W, 3] uint8 BGR -> [N, tokens, encoder width]: the
    VideoMAE encoder's final-norm output (no pooling, no classifier)."""
    s, ps, ts = enc["image_size"], enc["patch_size"], enc["tubelet_size"]
    g = s // ps
    n, t = clips_u8.shape[:2]
    x = vt.preprocess(clips_u8.reshape((n * t,) + clips_u8.shape[2:]), s, mm)
    x = x.reshape(n, t // ts, ts, g, ps, g, ps, 3)
    x = x.transpose(0, 1, 3, 5, 2, 4, 6, 7)
    x = x.reshape(n, (t // ts) * g * g, ts * ps * ps * 3)
    kernel = p["video/tubelet/proj/kernel"].reshape(ts * ps * ps * 3, -1)
    x = mm("ntk,kd->ntd", x, kernel) + p["video/tubelet/proj/bias"] \
        + p["video/pos_embed"]
    return vt.encoder(p, x, enc, mm, prefix="video/encoder")


def connector(p, x, mm, vt):
    x = mm("ntd,de->nte", x, p["connector/fc1/kernel"]) \
        + p["connector/fc1/bias"]
    x = vt._gelu(x, "gelu")
    return mm("ntd,de->nte", x, p["connector/fc2/kernel"]) \
        + p["connector/fc2/bias"]


def decoder(p, x, cfg, mm):
    """[T, d] embeddings -> [T, d] hidden before the final norm: one causal
    forward from position 0."""
    t, d = x.shape
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // heads
    eps = cfg["norm_eps"]
    theta = float(dict(cfg["rope_parameters"])["rope_theta"])
    taps = cfg["conv_L_cache"]
    held = list(cfg["experts_held"])
    causal = jnp.asarray(np.tril(np.ones((t, t), bool)))
    for i, kind in enumerate(cfg["layer_types"]):
        b = f"head/layer{i}_"
        h = _rms(x, p[b + "operator_norm/scale"], eps)
        if kind == "conv":
            bcx = mm("td,de->te", h, p[b + "conv/in_proj"])
            gate_b, gate_c, xx = jnp.split(bcx, 3, axis=-1)
            u = jnp.concatenate(
                [jnp.zeros((taps - 1, d), jnp.float32), gate_b * xx], axis=0)
            w = p[b + "conv/conv_kernel"]
            y = sum(u[j:j + t] * w[j] for j in range(taps))
            y = mm("td,de->te", gate_c * y, p[b + "conv/out_proj"])
        elif kind == "full_attention":
            q = mm("td,de->te", h, p[b + "attn/q_proj"]).reshape(t, heads, hd)
            k = mm("td,de->te", h, p[b + "attn/k_proj"]).reshape(t, kvh, hd)
            v = mm("td,de->te", h, p[b + "attn/v_proj"]).reshape(t, kvh, hd)
            q = _rope(_rms(q, p[b + "attn/q_norm/scale"], eps), theta)
            k = _rope(_rms(k, p[b + "attn/k_norm/scale"], eps), theta)
            k = jnp.repeat(k, heads // kvh, axis=1)
            v = jnp.repeat(v, heads // kvh, axis=1)
            s = mm("thd,shd->hts", q, k) / np.sqrt(hd).astype(np.float32)
            s = jnp.where(causal[None], s, -jnp.inf)
            o = mm("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
            y = mm("td,de->te", o.reshape(t, d), p[b + "attn/out_proj"])
        else:
            raise ValueError(f"unknown layer type {kind!r}")
        x = x + y
        h = _rms(x, p[b + "ffn_norm/scale"], eps)
        if i < cfg["num_dense_layers"]:
            a = _silu(mm("td,dm->tm", h, p[b + "mlp/w1"])) \
                * mm("td,dm->tm", h, p[b + "mlp/w3"])
            y = mm("tm,md->td", a, p[b + "mlp/w2"])
        else:
            scores = jax.nn.sigmoid(mm("td,de->te", h, p[b + "moe/gate"]))
            pick = scores + p[b + "moe/expert_bias"] \
                if cfg["use_expert_bias"] else scores
            _, sel = jax.lax.top_k(pick, cfg["num_experts_per_tok"])
            chosen = jnp.sum(jax.nn.one_hot(sel, scores.shape[-1]), axis=1)
            w = scores * chosen                                 # [T, routed]
            if cfg["norm_topk_prob"]:
                w = w / jnp.sum(w, axis=-1, keepdims=True)
            w = w * cfg["routed_scaling_factor"]
            a = _silu(mm("td,edm->etm", h, p[b + "moe/w1"])) \
                * mm("td,edm->etm", h, p[b + "moe/w3"])
            y = mm("etm,emd->etd", a, p[b + "moe/w2"])          # [held, T, d]
            y = jnp.sum(y * w[:, np.asarray(held)].T[:, :, None], axis=0)
        x = x + y
    return x


def stream_logits(p, frames_u8, rounds, tokens, cfg, quant=""):
    """frames [F, H, W, 3] uint8 (round j's clip is frames j .. j+7, the
    context's first round first), ``rounds`` the rounds of this context,
    ``tokens`` [max rounds * D] the served ids, round by round -> [D,
    vocabulary] logits that predict the last round's tokens."""
    vt = loader.reference("vision_transformer")
    mm = vt._einsum(quant)
    enc = dict(cfg["encoder"])
    n = enc["num_frames"]
    steps = cfg["decode_steps"]
    most = tokens.shape[0] // steps
    f = frames_u8.shape[0]
    # a round past this context's last reads clipped frame numbers: its
    # positions follow every position that is read out
    at = np.minimum(np.arange(most)[:, None] + np.arange(n)[None], f - 1)
    vis = connector(p, features(p, frames_u8[at], enc, mm, vt), mm, vt)
    emb = p["head/embed"]
    tok = emb[tokens].reshape(most, steps, -1)
    x = jnp.concatenate(
        [emb[np.asarray(cfg["instruction_ids"])],
         jnp.concatenate([vis, tok], axis=1).reshape(-1, emb.shape[1])],
        axis=0)
    h = decoder(p, x, cfg, mm)
    per = vis.shape[1] + steps
    first = len(cfg["instruction_ids"]) + per * (rounds - 1) + vis.shape[1] - 1
    h = jax.lax.dynamic_slice_in_dim(h, first, steps, axis=0)
    h = _rms(h, p["head/final_norm/scale"], cfg["norm_eps"])
    return mm("td,vd->tv", h, emb)


@functools.lru_cache(maxsize=None)
def jitted(family: str, cfg_items: tuple, quant: str = ""):
    """The forward for one (sizes, precision): (weights, frames [block, F,
    H, W, 3], rounds [block], tokens [block, max rounds * D]) -> [block, D,
    vocabulary]; one context at a time."""
    cfg = dict(cfg_items)
    one = jax.jit(functools.partial(stream_logits, cfg=cfg, quant=quant))

    def forward(p, frames_u8, rounds, tokens):
        return jnp.stack([one(p, frames_u8[i], rounds[i], tokens[i])
                          for i in range(frames_u8.shape[0])])

    return forward
