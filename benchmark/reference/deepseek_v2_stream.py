"""Plain float32 reference of the third streaming head: VideoMAE encoder ->
connector -> DeepSeek-V2 decoder (``deepseek_v2``, deepseek-ai/DeepSeek-V2
``config.json`` and ``modeling_deepseek.py``), as
``configs/deepseek_v2_stream.json`` cuts it.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
the plain attention form (per-head keys and values of every position) over
the whole context in one causal forward, no cache, no latent-space path, no
batching, every held expert on every token. It imports nothing from
``video_edge_ai_proxy_tpu`` (the encoder, the preprocess and the connector
are those of ``reference/vision_transformer.py`` and
``reference/lfm2_stream.py``, the yarn rope and the plain MLA form those of
``reference/xing4_stream.py``) and is handed weights made by
``vbench.weights`` from the seed.

One call is ONE FULL CAUSAL FORWARD over a stream's whole context since
its reset,

    [instruction, clip_1 tokens, served tokens_1, ..., clip_r tokens,
     served tokens_r]

teacher-forced with the tokens the program emitted, and returns [D,
vocabulary] logits: the rows that predict round r's tokens. The sequence is
always laid out for the most rounds a context holds (one compiled shape; a
causal model's outputs do not depend on what follows them), and ``rounds``
picks the positions, as ``reference/xing4_stream.py`` does and for its
reasons.

The layer, as ISSUE 36 wrote it down from the published files. Block: h <-
h + Attn(RMS(h)), h <- h + F(RMS(h)); F the dense SwiGLU in the first
``first_k_dense_replace`` layers, the routed layer in the rest; exit: a
final RMS norm and an untied head. Attention (MLA): c_q = RMS(h W_qa), [q_n
| q_r] = c_q W_qb a head; [c_kv | k_r] = h W_kva, ĉ = RMS(c_kv), [k_n | v] =
ĉ W_kvb a head, k_r shared by the heads; yarn rope (rotate-half) on q_r and
k_r, cos and sin scaled by mscale / mscale_all_dim; scores ([q_n | q_r] ·
[k_n | k_r]) (d_n + d_r)^-½ yarn_mscale(factor, mscale_all_dim)², causal
softmax, Σ p v, W_o. **Only the heads in ``heads_held`` exist**: W_qb, W_kvb
and W_o as handed over are those heads' slices, the output is their partial
sum, and what the other heads would add is left out, as on one chip of the
deployment. Router (``group_limited_greedy``): s = softmax(h W_g) over all
``num_routed_experts``; a group (``n_group`` groups of consecutive experts)
scores as its largest s; the ``topk_group`` best groups are kept (ties to
the lower group) and the others' scores taken as 0; the
``num_experts_per_tok`` largest of what is left are chosen (ties to the
lower id); weights = the chosen s, over (their sum + 1e-20) only where
``norm_topk_prob``, x ``routed_scaling_factor``. Every expert in
``experts_held`` is computed on every token and weighted (zero where the
token did not choose it); what the others would add is left out; the shared
SwiGLU of width ``n_shared_experts`` x ``moe_intermediate_size`` is added
unweighted. Ranks are counted by comparison, not taken from a sort.

``quant`` selects the control: every matmul's operands rounded to float8
e4m3 with a per-tensor scale, products accumulated in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from vbench import loader


def _mla():
    """The second head's reference, for what the two models publish alike:
    the RMS norm, SiLU, the SwiGLU, the yarn rope and the plain MLA form
    (``reference/xing4_stream.py``; the program shares ``models/mla.py``
    the same way). What differs, the held heads, the router, the block
    and the exit, is written out here."""
    return loader.reference("xing4_stream")


def _attention(p, b, h, cfg, mm):
    """The held heads' partial sum over [T, C], causal from position 0:
    the plain form over the ``heads_held`` heads alone, whose slices of
    W_qb, W_kvb and W_o the weights are."""
    return _mla()._attention(
        p, b, h, dict(cfg, num_attention_heads=len(cfg["heads_held"])), mm)


def _best(x, k):
    """[T, n] -> bool [T, n]: each row's ``k`` largest, ties to the lower
    index; an entry's rank is the number of entries that beat it."""
    n = x.shape[-1]
    a, b = x[:, :, None], x[:, None, :]
    first = jnp.arange(n)[None, :] < jnp.arange(n)[:, None]     # [i, j]: j < i
    beaten_by = (b > a) | ((b == a) & first[None])
    return jnp.sum(beaten_by, axis=-1) < k


def route(scores, cfg):
    """[T, E] router scores -> [T, E] weights, zero where not chosen."""
    t, e = scores.shape
    groups = int(cfg.get("n_group", 1))
    left = scores
    if groups > 1:
        best = jnp.max(scores.reshape(t, groups, e // groups), axis=-1)
        keep = jnp.repeat(_best(best, cfg["topk_group"]), e // groups,
                          axis=-1)
        left = jnp.where(keep, scores, 0.0)
    w = jnp.where(_best(left, cfg["num_experts_per_tok"]), scores, 0.0)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * cfg["routed_scaling_factor"]


def _experts(p, b, h, cfg, mm):
    held = np.asarray(cfg["experts_held"])
    scores = jax.nn.softmax(mm("td,de->te", h, p[b + "gate"]), axis=-1)
    w = route(scores, cfg)
    a = _mla()._silu(mm("td,edm->etm", h, p[b + "w1"])) \
        * mm("td,edm->etm", h, p[b + "w3"])
    y = mm("etm,emd->etd", a, p[b + "w2"])                  # [held, T, d]
    y = jnp.sum(y * w[:, held].T[:, :, None], axis=0)
    return y + _mla()._swiglu(p, b, h, mm,
                              ("shared_w1", "shared_w3", "shared_w2"))


def block(p, b, x, dense, cfg, mm):
    """One block over [T, C], causal from position 0."""
    eps, rms = cfg["rms_norm_eps"], _mla()._rms
    x = x + _attention(p, b + "attn/", rms(x, p[b + "attn_norm/scale"], eps),
                       cfg, mm)
    h = rms(x, p[b + "ffn_norm/scale"], eps)
    return x + (_mla()._swiglu(p, b + "mlp/", h, mm) if dense
                else _experts(p, b + "moe/", h, cfg, mm))


def decoder(p, x, cfg, mm):
    """[T, C] embeddings -> [T, C] exit states (before the final norm):
    one causal forward from position 0."""
    for i in range(cfg["num_hidden_layers"]):
        x = block(p, f"head/layer{i}/", x, i < cfg["first_k_dense_replace"],
                  cfg, mm)
    return x


def stream_logits(p, frames_u8, rounds, tokens, cfg, quant=""):
    """frames [F, H, W, 3] uint8 (round j's clip is frames j .. j+7, the
    context's first round first), ``rounds`` the rounds of this context,
    ``tokens`` [max rounds * D] the served ids, round by round -> [D,
    vocabulary]: the logits that predict the last round's tokens."""
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
    per = vis.shape[1] + steps
    first = len(cfg["instruction_ids"]) + per * (rounds - 1) + vis.shape[1] - 1
    out = _mla()._rms(jax.lax.dynamic_slice_in_dim(h, first, steps, axis=0),
                      p["head/final_norm/scale"], cfg["rms_norm_eps"])
    return mm("td,vd->tv", out, p["head/lm_head"])


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
