"""Plain float32 reference of the fourth streaming head: VideoMAE encoder ->
connector -> Falcon-H1 decoder (``falcon_h1``, tiiuae/Falcon-H1-34B-Instruct
``config.json`` and transformers' ``modeling_falcon_h1.py``), as
``configs/falcon_h1_stream.json`` cuts it.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
no cache, no pool, no chunks. **The state-space layer is the token-by-token
recurrence** (one ``lax.scan`` over positions from a zero state), not the
chunked form the program runs, so that the comparison does not share the
program's algorithm. It imports nothing from ``video_edge_ai_proxy_tpu``
(the encoder, the preprocess and the connector are those of
``reference/vision_transformer.py`` and ``reference/lfm2_stream.py``) and
is handed weights made by ``vbench.weights`` from the seed.

One call is ONE FULL CAUSAL FORWARD over each stream's whole context since
its reset,

    [instruction, clip_1 tokens, served tokens_1, ..., clip_r tokens,
     served tokens_r]

teacher-forced with the tokens the program emitted, and returns [D,
vocabulary] logits a context: the rows that predict round r's tokens. The
sequence is always laid out for the most rounds a context holds (one
compiled shape; a causal model's outputs do not depend on what follows
them), and ``rounds`` picks the positions. The contexts of a call run
together (``jax.vmap``), the recurrence one scan for all of them.

The layer, from the published file. ``n = RMS(x)``. Mixer: ``p = (n
ssm_in_multiplier) W_in * mup`` (``mup``: the five ``ssm_multipliers``
over z | x | B | C | dt); ``xBC = silu(causal depthwise conv(xBC) +
b)`` over ``mamba_d_conv`` taps; ``dt =
softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head ``h_t = exp(dt_t
A) h_{t-1} + dt_t x_t (outer) B_t``, ``y_t = h_t C_t + D x_t``, B and C
the head's group's; ``y = GroupRMS(y * silu(z))`` (the gate before the
norm unless ``mamba_norm_before_gate``); ``m = y W_out
ssm_out_multiplier``. Attention: ``q = (n a_in) W_q``, ``k = (n a_in) W_k
key_multiplier``, ``v = (n a_in) W_v``, rotate-half rope on q and k,
causal softmax at ``head_dim^-1/2``, ``a = o W_o
attention_out_multiplier``. ``x' = x + m + a``; ``out = x' + (up(u) *
silu(gate(u) g)) W_down d`` with ``u = RMS(x')`` and ``(g, d) =
mlp_multipliers``. Token embeddings times ``embedding_multiplier``, the
visual tokens not; a final RMS norm; ``logits = h W_head
lm_head_multiplier``. **Only this chip's share exists**: the tensors are
the held heads', group's, columns' and rows' slices, the row-parallel
outputs are this chip's partial sums, and what the other chip would add is
left out, as on one chip of the deployment.

``quant`` selects the control: every matmul's operands rounded to float8
e4m3 with a per-tensor scale, products accumulated in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from vbench import loader


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _mup(cfg):
    h, g = cfg["mamba_n_heads"], cfg["mamba_n_groups"]
    e, gn = h * cfg["mamba_d_head"], g * cfg["mamba_d_state"]
    return np.concatenate([np.full(n, m, np.float32) for n, m in zip(
        (e, e, gn, gn, h), cfg["ssm_multipliers"])])


def recurrence(x, dt, a, b, c, d, mm):
    """The state-space layer token by token from a zero state: x [T, H, P],
    dt [T, H], a [H], b, c [T, G, N], d [H] -> y [T, H, P]."""
    t, heads, p = x.shape
    per = heads // b.shape[1]
    b, c = (jnp.repeat(v, per, axis=1) for v in (b, c))         # [T, H, N]

    def one(h, at):
        xt, dtt, bt, ct = at
        h = h * jnp.exp(dtt * a)[:, None, None] \
            + (dtt[:, None] * xt)[..., None] * bt[:, None, :]
        return h, mm("hpn,hn->hp", h, ct)

    h0 = jnp.zeros((heads, p, b.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(one, h0, (x, dt, b, c))
    return y + d[:, None] * x


def mixer(p, pre, n, cfg, mm):
    """The mixer over [T, d], causal from position 0 with no state."""
    st = loader.reference("lfm2_stream")
    t = n.shape[0]
    heads, hp = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, ns = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    e = heads * hp
    taps = cfg["mamba_d_conv"]
    pz = mm("td,de->te", n * cfg["ssm_in_multiplier"], p[pre + "in_proj"]) \
        * _mup(cfg)
    z, xbc, dt = jnp.split(pz, [e, 2 * e + 2 * g * ns], axis=-1)
    xbc = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1])), xbc], axis=0)
    w = p[pre + "conv_kernel"]
    xbc = st._silu(sum(xbc[j:j + t] * w[j] for j in range(taps))
                   + p[pre + "conv_bias"])
    xs, b, c = jnp.split(xbc, [e, e + g * ns], axis=-1)
    dt = jax.nn.softplus(dt + p[pre + "dt_bias"])
    y = recurrence(xs.reshape(t, heads, hp), dt, -jnp.exp(p[pre + "A_log"]),
                   b.reshape(t, g, ns), c.reshape(t, g, ns), p[pre + "D"], mm)
    y = y.reshape(t, e)
    if not cfg["mamba_norm_before_gate"]:
        y = y * _silu(z)
    y = st._rms(y.reshape(t, g, e // g), 1.0, cfg["rms_norm_eps"]).reshape(
        t, e) * p[pre + "norm_scale"]
    if cfg["mamba_norm_before_gate"]:
        y = y * _silu(z)
    return mm("te,ed->td", y, p[pre + "out_proj"]) * cfg["ssm_out_multiplier"]


def attention(p, pre, n, cfg, mm):
    """The held heads' partial sum over [T, d], causal from position 0."""
    st = loader.reference("lfm2_stream")
    t = n.shape[0]
    heads, kvh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                      cfg["head_dim"])
    theta = float(cfg["rope_theta"])
    n = n * cfg["attention_in_multiplier"]
    q = mm("td,de->te", n, p[pre + "q_proj"]).reshape(t, heads, hd)
    k = mm("td,de->te", n, p[pre + "k_proj"]).reshape(t, kvh, hd) \
        * cfg["key_multiplier"]
    v = mm("td,de->te", n, p[pre + "v_proj"]).reshape(t, kvh, hd)
    q, k = st._rope(q, theta), st._rope(k, theta)
    k = jnp.repeat(k, heads // kvh, axis=1)
    v = jnp.repeat(v, heads // kvh, axis=1)
    s = mm("thd,shd->hts", q, k) / np.sqrt(hd).astype(np.float32)
    s = jnp.where(jnp.asarray(np.tril(np.ones((t, t), bool)))[None], s,
                  -jnp.inf)
    o = mm("hts,shd->thd", jax.nn.softmax(s, axis=-1), v)
    return mm("te,ed->td", o.reshape(t, heads * hd), p[pre + "out_proj"]) \
        * cfg["attention_out_multiplier"]


def decoder(p, x, cfg, mm):
    """[T, d] embeddings -> [T, d] hidden before the final norm: one causal
    forward from position 0."""
    st = loader.reference("lfm2_stream")
    eps = cfg["rms_norm_eps"]
    gate, down = cfg["mlp_multipliers"]
    for i in range(cfg["num_hidden_layers"]):
        b = f"head/layer{i}/"
        n = st._rms(x, p[b + "input_norm/scale"], eps)
        x = x + mixer(p, b + "mamba/", n, cfg, mm) \
            + attention(p, b + "attn/", n, cfg, mm)
        u = st._rms(x, p[b + "ffn_norm/scale"], eps)
        a = mm("td,dm->tm", u, p[b + "mlp/up_proj"]) * _silu(
            mm("td,dm->tm", u, p[b + "mlp/gate_proj"]) * gate)
        x = x + mm("tm,md->td", a, p[b + "mlp/down_proj"]) * down
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
    emb = p["head/embed"] * cfg["embedding_multiplier"]
    tok = emb[tokens].reshape(most, steps, -1)
    x = jnp.concatenate(
        [emb[np.asarray(cfg["instruction_ids"])],
         jnp.concatenate([vis, tok], axis=1).reshape(-1, emb.shape[1])],
        axis=0)
    h = decoder(p, x, cfg, mm)
    per = vis.shape[1] + steps
    first = len(cfg["instruction_ids"]) + per * (rounds - 1) + vis.shape[1] - 1
    out = st._rms(jax.lax.dynamic_slice_in_dim(h, first, steps, axis=0),
                  p["head/final_norm/scale"], cfg["rms_norm_eps"])
    return mm("td,vd->tv", out, p["head/lm_head"]) * cfg["lm_head_multiplier"]


@functools.lru_cache(maxsize=None)
def jitted(family: str, cfg_items: tuple, quant: str = ""):
    """The forward for one (sizes, precision): (weights, frames [block, F,
    H, W, 3], rounds [block], tokens [block, max rounds * D]) -> [block, D,
    vocabulary]; the block's contexts together."""
    cfg = dict(cfg_items)
    return jax.jit(jax.vmap(
        functools.partial(stream_logits, cfg=cfg, quant=quant),
        in_axes=(None, 0, 0, 0)))
