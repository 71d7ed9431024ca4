"""Plain float32 reference of the fifth streaming head: VideoMAE encoder ->
connector -> Nemotron-H decoder (``nemotron_h``,
nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16 ``config.json`` and
transformers' ``modeling_nemotron_h.py``), as
``configs/nemotron3_super_stream.json`` cuts it.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
no kernel, cache, pool, chunk or batch of pairs. **The state-space layer is
the token-by-token recurrence** (``reference/falcon_h1_stream.py``
``recurrence``: one ``lax.scan`` over positions from a zero state), not the
chunked form the program runs; **the expert layer is a dense loop over the
held experts**: every held expert on every token, weighted by the router's
weight where the token chose it and by 0 where it did not; **the
attention is full causal softmax**. It imports nothing from
``video_edge_ai_proxy_tpu`` (the encoder, the preprocess and the
connector are those of ``reference/vision_transformer.py`` and
``reference/lfm2_stream.py``) and is handed weights made by
``vbench.weights`` from the seed.

One call is ONE FULL CAUSAL FORWARD over each stream's whole context since
its reset,

    [instruction, clip_1 tokens, served tokens_1, ..., clip_r tokens,
     served tokens_r]

teacher-forced with the tokens the program emitted, and returns [D,
vocabulary] logits a context: the rows that predict round r's tokens. The
sequence is always laid out for the most rounds a context holds (one
compiled shape; a causal model's outputs do not depend on what follows
them), and ``rounds`` picks the positions. The contexts of a call run
together (``jax.vmap``).

The layers, from the published file: the first ``num_hidden_layers``
characters of ``hybrid_override_pattern``, each ``x <- x + mixer(RMS(x))``
(``layer_norm_epsilon``). ``M``: ``[z | xBC | dt] = n W_in``; ``xBC =
silu(causal depthwise conv(xBC) + b)`` over ``conv_kernel`` taps; ``dt =
softplus(dt + dt_bias)`` (no clamp), ``A = -exp(A_log)``; per head ``h_t =
exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t``, ``y_t = h_t C_t + D x_t``, B
and C the head's group's; ``y = GroupRMS(y * silu(z))`` over each group's
channels; ``W_out``. ``*``: grouped-query attention with NO rotary
embedding and no per-head norm, causal softmax at ``head_dim^-1/2``,
``W_o``. ``E``: ``s = sigmoid(x W_r)``, the top-``num_experts_per_tok`` of
``s + e_score_correction_bias`` over all ``num_routed_experts``, their
``s`` over their sum + 1e-20, times ``routed_scaling_factor``; ``u = x
W_down``; ``y = (sum_e w_e relu(u W1_e)^2 W2_e) W_up + relu(x S1)^2 S2``.
Token embeddings as they are, a final RMS norm, an untied head.
**Only this chip's share exists**: the tensors are the held heads',
groups', experts' and rows' slices, the row-parallel outputs and the
routed sum are this chip's partial sums, and what the other chips would
add is left out, as on one chip of the deployment.

Departures, the configuration's (``assumed`` there): the connector, the
instruction ids, D, greedy decoding, no prediction module.

``quant`` selects the control: every matmul's operands rounded to float8
e4m3 with a per-tensor scale, products accumulated in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from vbench import loader


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def layer_kinds(cfg):
    """The kinds of the layers run: the first ``num_hidden_layers``
    characters of the published pattern."""
    return cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]


def mixer(p, pre, n, cfg, mm):
    """The held heads' mixer over [T, d], causal from position 0 with no
    state: this chip's partial sum of W_out."""
    st = loader.reference("lfm2_stream")
    fh = loader.reference("falcon_h1_stream")
    t = n.shape[0]
    heads, hp = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, ns = cfg["n_groups"], cfg["ssm_state_size"]
    e = heads * hp
    taps = cfg["conv_kernel"]
    pz = mm("td,de->te", n, p[pre + "in_proj"])
    z, xbc, dt = jnp.split(pz, [e, 2 * e + 2 * g * ns], axis=-1)
    xbc = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1])), xbc], axis=0)
    w = p[pre + "conv_kernel"]
    xbc = st._silu(sum(xbc[j:j + t] * w[j] for j in range(taps))
                   + p[pre + "conv_bias"])
    xs, b, c = jnp.split(xbc, [e, e + g * ns], axis=-1)
    dt = jax.nn.softplus(dt + p[pre + "dt_bias"])
    y = fh.recurrence(xs.reshape(t, heads, hp), dt,
                      -jnp.exp(p[pre + "A_log"]), b.reshape(t, g, ns),
                      c.reshape(t, g, ns), p[pre + "D"], mm)
    y = y.reshape(t, e) * st._silu(z)
    y = st._rms(y.reshape(t, g, e // g), 1.0, cfg["layer_norm_epsilon"]
                ).reshape(t, e) * p[pre + "norm_scale"]
    return mm("te,ed->td", y, p[pre + "out_proj"])


def attention(p, pre, n, cfg, mm):
    """The held heads' partial sum over [T, d], causal from position 0,
    with no position embedding."""
    t = n.shape[0]
    heads, kvh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                      cfg["head_dim"])
    q = mm("td,de->te", n, p[pre + "q_proj"]).reshape(t, heads, hd)
    k = mm("td,de->te", n, p[pre + "k_proj"]).reshape(t, kvh, hd)
    v = mm("td,de->te", n, p[pre + "v_proj"]).reshape(t, kvh, hd)
    k = jnp.repeat(k, heads // kvh, axis=1)
    v = jnp.repeat(v, heads // kvh, axis=1)
    s = mm("thd,shd->hts", q, k) / np.sqrt(hd).astype(np.float32)
    s = jnp.where(jnp.asarray(np.tril(np.ones((t, t), bool)))[None], s,
                  -jnp.inf)
    o = mm("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
    return mm("te,ed->td", o.reshape(t, heads * hd), p[pre + "out_proj"])


def experts(p, pre, n, cfg, mm):
    """The latent expert layer over [T, d]: the router over all experts,
    the held experts densely in the latent, the shared expert at full
    width; this chip's partial sum of the routed experts."""
    held = np.asarray(cfg["experts_held"])
    scores = jax.nn.sigmoid(mm("td,de->te", n, p[pre + "gate"]))
    pick = scores + p[pre + "expert_bias"]
    _, sel = jax.lax.top_k(pick, cfg["num_experts_per_tok"])
    chosen = jnp.sum(jax.nn.one_hot(sel, scores.shape[-1]), axis=1)
    w = scores * chosen                                     # [T, routed]
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * cfg["routed_scaling_factor"]
    u = mm("td,dl->tl", n, p[pre + "fc1_latent_proj"])
    a = _relu2(mm("tl,elm->etm", u, p[pre + "w1"]))
    y = mm("etm,eml->etl", a, p[pre + "w2"])               # [held, T, latent]
    y = jnp.sum(y * w[:, held].T[:, :, None], axis=0)
    y = mm("tl,ld->td", y, p[pre + "fc2_latent_proj"])
    shared = _relu2(mm("td,dm->tm", n, p[pre + "shared_w1"]))
    return y + mm("tm,md->td", shared, p[pre + "shared_w2"])


_MIXERS = {"M": mixer, "*": attention, "E": experts}


def decoder(p, x, cfg, mm):
    """[T, d] embeddings -> [T, d] hidden before the final norm: one causal
    forward from position 0."""
    st = loader.reference("lfm2_stream")
    for i, kind in enumerate(layer_kinds(cfg)):
        b = f"head/layer{i}/"
        n = st._rms(x, p[b + "norm/scale"], cfg["layer_norm_epsilon"])
        x = x + _MIXERS[kind](p, b + "mixer/", n, cfg, mm)
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
    out = st._rms(jax.lax.dynamic_slice_in_dim(h, first, steps, axis=0),
                  p["head/final_norm/scale"], cfg["layer_norm_epsilon"])
    return mm("td,vd->tv", out, p["head/lm_head"])


@functools.lru_cache(maxsize=None)
def jitted(family: str, cfg_items: tuple, quant: str = ""):
    """The forward for one (sizes, precision): (weights, frames [block, F,
    H, W, 3], rounds [block], tokens [block, max rounds * D]) -> [block, D,
    vocabulary]; the block's contexts together."""
    cfg = dict(cfg_items)
    return jax.jit(jax.vmap(
        functools.partial(stream_logits, cfg=cfg, quant=quant),
        in_axes=(None, 0, 0, 0)))
