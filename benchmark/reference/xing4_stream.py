"""Plain float32 reference of the second streaming head: VideoMAE encoder ->
connector -> Xing4.0 decoder (``xing4_0``, XingChen-AGI/Xing4.0-29B-A4B
``config.json``), as ``configs/xing4_stream.json`` cuts it.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
the plain attention form over the whole context in one causal forward, no
cache, no latent-space path, no drafter, no routing tricks. It imports
nothing from ``video_edge_ai_proxy_tpu`` (the encoder, the preprocess and
the connector are those of ``reference/vision_transformer.py`` and
``reference/lfm2_stream.py``) and is handed weights made by
``vbench.weights`` from the seed.

One call is ONE FULL CAUSAL FORWARD over a stream's whole context since
its reset,

    [instruction, clip_1 tokens, served tokens_1, ..., clip_r tokens,
     served tokens_r]

teacher-forced with the tokens the program emitted (greedy decoding by
full forward passes, given those tokens), and returns [D + 1, vocabulary]
logits: the D rows that predict round r's tokens, then the prediction
module's FIRST draft of that round (its logits for the second token, from
the main model's exit state at the round's last visual position and the
embedding of the served first token). The sequence is always laid out for
the most rounds a context holds (one compiled shape; a causal model's
outputs do not depend on what follows them), and ``rounds`` picks the
positions. (Laid out for the result's own rounds instead, five shapes, the
comparison of a run took 25 s for 59, and at three and four rounds the
chip gave logits 18 off the same context's laid out for five, at the
cell's size only: PERF.md 7, PR 34.)

The layer, as ISSUE 34 wrote it down from the published config. The state
of a position is X [n, C], n = ``hc_mult``; for each sublayer F (attention,
then feed-forward), with x̂ = RMS(flatten X; g): H_pre = σ(α_pre · x̂φ_pre +
b_pre), H_post = 2σ(α_post · x̂φ_post + b_post), H_res = SK(α_res ·
reshape(x̂φ_res, n×n) + b_res) where SK(M̃) = exp(clamp(M̃)) normalised
``hc_sinkhorn_iters`` times (each row over its sum + ``hc_eps``, then each
column over its sum + ``hc_eps``); h = H_pre X, y = F(RMS(h)), X' = H_res X
+ H_postᵀ y. Entry: the embedding repeated n times; exit: the sum of the n
streams. Attention (MLA): c_q = RMS(h W_qa), [q_n | q_r] = c_q W_qb a head;
[c_kv | k_r] = h W_kva, ĉ = RMS(c_kv), [k_n | v] = ĉ W_kvb a head, k_r
shared by the heads; yarn rope (rotate-half) on q_r and k_r; scores ([q_n |
q_r] · [k_n | k_r]) (d_n + d_r)^-½ mscale², causal softmax, Σ p v, W_o.
Feed-forward: block 0 dense SwiGLU; the others s = σ(h W_g), the top-k of s
+ bias, weights s_i ÷ (Σ chosen s + 1e-20) × ``routed_scaling_factor``,
computed here for EVERY held expert on every token and weighted (zero
where the token did not choose it), plus the shared expert's SwiGLU(h),
unweighted. Only the experts in ``experts_held`` exist: what the others
would add is left out, as on one chip of the deployment. The prediction
module: u_j = [RMS(h_j) | RMS(x_{j+1})] W_eh over the main model's exit
states h (before the final norm) and the NEXT position's input embedding,
one routed block over u (causal over its own rows), its own final norm, the
shared head.

``quant`` selects the control: every matmul's operands rounded to float8
e4m3 with a per-tensor scale, products accumulated in float32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from vbench import loader


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _yarn_mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def _yarn(cfg):
    """(inverse frequencies [d_r / 2], what cos and sin are scaled by, the
    softmax scale) of the published yarn ``rope_scaling``."""
    rs = dict(cfg["rope_scaling"])
    d, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    extra = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    inter = extra / rs["factor"]

    def correction(rotations):
        return (d * math.log(rs["original_max_position_embeddings"]
                             / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction(rs["beta_fast"])), 0)
    high = min(math.ceil(correction(rs["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv = inter * ramp + extra * (1 - ramp)
    all_dim = _yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    scale = ((cfg["qk_nope_head_dim"] + d) ** -0.5) * all_dim ** 2
    return (inv.astype(np.float32),
            np.float32(_yarn_mscale(rs["factor"], rs["mscale"]) / all_dim),
            np.float32(scale))


def _rope(x, inv, m):
    """x [T, ..., d_r] at positions 0..T-1, rotate-half."""
    t = x.shape[0]
    ang = np.arange(t, dtype=np.float32)[:, None] * inv[None]
    shape = (t,) + (1,) * (x.ndim - 2) + (-1,)
    cos = jnp.asarray(np.concatenate([np.cos(ang)] * 2, -1) * m).reshape(shape)
    sin = jnp.asarray(np.concatenate([np.sin(ang)] * 2, -1) * m).reshape(shape)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _maps(p, b, x, cfg, mm):
    """X [T, n, C] -> (H_pre [T, n], H_post [T, n], H_res [T, n, n])."""
    t, n, _ = x.shape
    xh = _rms(x.reshape(t, -1), p[b + "norm_scale"], cfg["rms_norm_eps"])
    z = mm("tk,kj->tj", xh, p[b + "phi"])
    alpha, bias = p[b + "alpha"], p[b + "bias"]
    pre = jax.nn.sigmoid(alpha[0] * z[:, :n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * z[:, n:2 * n] + bias[n:2 * n])
    m = jnp.exp(jnp.clip(
        (alpha[2] * z[:, 2 * n:] + bias[2 * n:]).reshape(t, n, n),
        cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"]))
    for _ in range(cfg["hc_sinkhorn_iters"]):
        m = m / (jnp.sum(m, axis=2, keepdims=True) + cfg["hc_eps"])
        m = m / (jnp.sum(m, axis=1, keepdims=True) + cfg["hc_eps"])
    return pre, post, m


def _sublayer(p, b, x, f, cfg, mm):
    """X' = H_res X + H_postᵀ F(RMS(H_pre X))."""
    pre, post, res = _maps(p, b + "_hc/", x, cfg, mm)
    h = jnp.sum(pre[:, :, None] * x, axis=1)                       # H_pre X
    y = f(_rms(h, p[b + "_norm/scale"], cfg["rms_norm_eps"]))
    return jnp.sum(res[:, :, :, None] * x[:, None, :, :], axis=2) \
        + post[:, :, None] * y[:, None, :]


def _attention(p, b, h, cfg, mm):
    t = h.shape[0]
    heads, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    eps = cfg["rms_norm_eps"]
    inv, m, scale = _yarn(cfg)
    cq = _rms(mm("td,dr->tr", h, p[b + "q_a"]), p[b + "q_norm/scale"], eps)
    q = mm("tr,re->te", cq, p[b + "q_b"]).reshape(t, heads, dn + dr)
    ckv = mm("td,dr->tr", h, p[b + "kv_a"])
    c_hat = _rms(ckv[:, :r], p[b + "kv_norm/scale"], eps)
    kv = mm("tr,re->te", c_hat, p[b + "kv_b"]).reshape(t, heads, dn + dv)
    k_r = jnp.broadcast_to(_rope(ckv[:, r:], inv, m)[:, None], (t, heads, dr))
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], inv, m)], axis=-1)
    k = jnp.concatenate([kv[..., :dn], k_r], axis=-1)
    s = mm("thd,shd->hts", q, k) * scale
    causal = jnp.asarray(np.tril(np.ones((t, t), bool)))
    s = jnp.where(causal[None], s, -jnp.inf)
    o = mm("hts,shd->thd", jax.nn.softmax(s, axis=-1), kv[..., dn:])
    return mm("te,ed->td", o.reshape(t, heads * dv), p[b + "o"])


def _swiglu(p, b, h, mm, names=("w1", "w3", "w2")):
    a = _silu(mm("td,dm->tm", h, p[b + names[0]])) \
        * mm("td,dm->tm", h, p[b + names[1]])
    return mm("tm,md->td", a, p[b + names[2]])


def _experts(p, b, h, cfg, mm):
    held = list(cfg["experts_held"])
    scores = jax.nn.sigmoid(mm("td,de->te", h, p[b + "gate"]))
    _, sel = jax.lax.top_k(scores + p[b + "expert_bias"],
                           cfg["num_experts_per_tok"])
    w = scores * jnp.sum(jax.nn.one_hot(sel, scores.shape[-1]), axis=1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * cfg["routed_scaling_factor"]
    a = _silu(mm("td,edm->etm", h, p[b + "w1"])) \
        * mm("td,edm->etm", h, p[b + "w3"])
    y = mm("etm,emd->etd", a, p[b + "w2"])                  # [held, T, d]
    y = jnp.sum(y * w[:, np.asarray(held)].T[:, :, None], axis=0)
    return y + _swiglu(p, b, h, mm, ("shared_w1", "shared_w3", "shared_w2"))


def block(p, b, x, dense, cfg, mm):
    """One block over X [T, n, C], causal from position 0."""
    x = _sublayer(p, b + "attn", x,
                  lambda h: _attention(p, b + "attn/", h, cfg, mm), cfg, mm)
    ffn = (lambda h: _swiglu(p, b + "mlp/", h, mm)) if dense else \
        (lambda h: _experts(p, b + "moe/", h, cfg, mm))
    return _sublayer(p, b + "ffn", x, ffn, cfg, mm)


def _enter(x, cfg):
    return jnp.repeat(x[:, None, :], cfg["hc_mult"], axis=1)


def decoder(p, x, cfg, mm):
    """[T, C] embeddings -> [T, C] exit states (the sum of the residual
    streams, before the final norm): one causal forward from position 0."""
    x = _enter(x, cfg)
    for i in range(cfg["num_hidden_layers"]):
        x = block(p, f"head/layer{i}/", x, i < cfg["first_k_dense_replace"],
                  cfg, mm)
    return jnp.sum(x, axis=1)


def prediction_module(p, h, x, cfg, mm):
    """Exit states h [T, C] and input embeddings x [T, C] -> the module's
    exit states [T - 1, C]: row j sees h_j and x_{j+1}, and predicts the
    token at j + 2."""
    eps = cfg["rms_norm_eps"]
    u = jnp.concatenate(
        [_rms(h[:-1], p["head/mtp_h_norm/scale"], eps),
         _rms(x[1:], p["head/mtp_e_norm/scale"], eps)], axis=-1)
    u = mm("te,ed->td", u, p["head/mtp_eh_proj"])
    return jnp.sum(block(p, "head/mtp_block/", _enter(u, cfg), False, cfg,
                         mm), axis=1)


def stream_logits(p, frames_u8, rounds, tokens, cfg, quant=""):
    """frames [F, H, W, 3] uint8 (round j's clip is frames j .. j+7, the
    context's first round first), ``rounds`` the rounds of this context,
    ``tokens`` [max rounds * D] the served ids, round by round -> [D + 1,
    vocabulary]: the logits that predict the last round's tokens, then
    the module's first draft of that round."""
    vt = loader.reference("vision_transformer")
    st = loader.reference("lfm2_stream")
    mm = vt._einsum(quant)
    enc = dict(cfg["encoder"])
    n = enc["num_frames"]
    steps = cfg["decode_steps"]
    most = tokens.shape[0] // steps
    f = frames_u8.shape[0]
    # a round past this context's last reads clipped frame numbers: its
    # positions follow every position that is read out
    at = np.minimum(np.arange(most)[:, None] + np.arange(n)[None], f - 1)
    vis = st.connector(p, st.features(p, frames_u8[at], enc, mm, vt), mm, vt)
    emb = p["head/embed"]
    tok = emb[tokens].reshape(most, steps, -1)
    x = jnp.concatenate(
        [emb[np.asarray(cfg["instruction_ids"])],
         jnp.concatenate([vis, tok], axis=1).reshape(-1, emb.shape[1])],
        axis=0)
    h = decoder(p, x, cfg, mm)
    hm = prediction_module(p, h, x, cfg, mm)
    per = vis.shape[1] + steps
    first = len(cfg["instruction_ids"]) + per * (rounds - 1) + vis.shape[1] - 1
    eps = cfg["rms_norm_eps"]
    main = _rms(jax.lax.dynamic_slice_in_dim(h, first, steps, axis=0),
                p["head/final_norm/scale"], eps)
    draft = _rms(jax.lax.dynamic_slice_in_dim(hm, first, 1, axis=0),
                 p["head/mtp_final_norm/scale"], eps)
    return mm("td,vd->tv", jnp.concatenate([main, draft], axis=0),
              p["head/lm_head"])


@functools.lru_cache(maxsize=None)
def jitted(family: str, cfg_items: tuple, quant: str = ""):
    """The forward for one (sizes, precision): (weights, frames [block, F,
    H, W, 3], rounds [block], tokens [block, max rounds * D]) -> [block, D
    + 1, vocabulary]; one context at a time."""
    cfg = dict(cfg_items)
    one = jax.jit(functools.partial(stream_logits, cfg=cfg, quant=quant))

    def forward(p, frames_u8, rounds, tokens):
        return jnp.stack([one(p, frames_u8[i], rounds[i], tokens[i])
                          for i in range(frames_u8.shape[0])])

    return forward
