"""Family ``deepseek_v2_stream``: a VideoMAE encoder, a connector and a
DeepSeek-V2 decoder that keeps a latent cache per camera
(``models/deepseek_v2.py``, ``models/mla.py``, ``engine/stream_state.py``).
``vit.py`` lists the answers a family gives; what a result depends on, its
window, what is kept and compared of it and the reference's arguments are
the first head's (``lfm2_stream.py``: every read since the stream's reset,
with the served tokens riding on the :class:`Window`; [D, vocabulary] rows:
this head has no prediction module and no draft).

This chip's share: the ``heads_held`` heads' slices of ``q_b``, ``kv_b``
and ``o`` (32 of ``heads_total`` 128: the tensors below ARE the slices) and
the ``experts_held`` experts' stacks (10 of ``num_routed_experts`` 160; the
router keeps all 160 columns). The reference is handed the same tensors.

Spreads of its own kinds (the encoder's are ``_encoder.spread``):
``rms_scale`` 1 + N(0, 0.1^2); ``matrix`` and ``expert`` N(0, 1/fan_in);
``router`` N(0, 1/fan_in): router logits are independent and of order 1, so
the softmax over 160 puts 0.02-0.06 on a token's best experts (x 16: routed
weights of 0.3-1, of the order of the unweighted shared experts'), the
plain top-6 of 160 falls into 4.6 groups on average, and **the group limit
(3 of 8) changes the chosen six for 89% of tokens** (numpy count over
20,000 draws at this spread; the tests count it on the twin); group 0 is
kept for 37.5% of tokens and 6.25% of the pairs fall on the ten held
experts, as with an even router. ``token_table`` N(0, 1): a token's input
embedding is of the order of a visual token's; ``lm_head`` N(0, head_std^2
/ hidden): the head is untied, its logits spread by about ``head_std`` and
the top-5 are distinct.
"""

from __future__ import annotations

from vbench import flops, loader

from families import _encoder
from families._encoder import template  # noqa: F401

_sibling = loader.family("lfm2_stream")
Window = _sibling.Window
sample_frames = _sibling.sample_frames
max_rounds = _sibling.max_rounds
expected_state = _sibling.expected_state
window = _sibling.window
reference_args = _sibling.reference_args
mean_context = _sibling.mean_context
kept = _sibling.kept
as_served = _sibling.as_served
compare = _sibling.compare
_video_sizes = _sibling._video_sizes
_visual_tokens = _sibling._visual_tokens

STRUCTURED_SIZES = ("rope_scaling", "experts_held", "heads_held",
                    "instruction_ids", "encoder")
REFERENCE_BLOCK = 1         # one context (up to 12 frames, ~4,000 positions)


def _blocks(sizes):
    """[(name, dense feed-forward?)] of the blocks that are run."""
    return [(f"layer{i}", i < sizes["first_k_dense_replace"])
            for i in range(sizes["num_hidden_layers"])]


def param_spec(sizes):
    enc = _video_sizes(sizes)
    out = [("video/" + name, shape, kind, fan_in)
           for name, shape, kind, fan_in
           in loader.family("videomae").param_spec(enc)
           if not name.startswith("head/")]
    d, dv = sizes["hidden_size"], enc["hidden_size"]
    h = len(sizes["heads_held"])
    rq, r = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    dn, dr = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    dvh = sizes["v_head_dim"]
    held = len(sizes["experts_held"])
    routed = sizes["num_routed_experts"]
    m, me = sizes["intermediate_size"], sizes["moe_intermediate_size"]
    ms = sizes["n_shared_experts"] * me
    _encoder.dense(out, "connector/fc1", dv, d)
    _encoder.dense(out, "connector/fc2", d, d)
    out.append(("head/embed", (sizes["vocab_size"], d), "token_table", d))
    out.append(("head/lm_head", (sizes["vocab_size"], d), "lm_head", d))
    for name, dense in _blocks(sizes):
        p = f"head/{name}/"
        out.append((p + "attn_norm/scale", (d,), "rms_scale", 0))
        out.append((p + "attn/q_a", (d, rq), "matrix", d))
        out.append((p + "attn/q_norm/scale", (rq,), "rms_scale", 0))
        out.append((p + "attn/q_b", (rq, h * (dn + dr)), "matrix", rq))
        out.append((p + "attn/kv_a", (d, r + dr), "matrix", d))
        out.append((p + "attn/kv_norm/scale", (r,), "rms_scale", 0))
        out.append((p + "attn/kv_b", (r, h * (dn + dvh)), "matrix", r))
        out.append((p + "attn/o", (h * dvh, d), "matrix", h * dvh))
        out.append((p + "ffn_norm/scale", (d,), "rms_scale", 0))
        if dense:
            out.append((p + "mlp/w1", (d, m), "matrix", d))
            out.append((p + "mlp/w3", (d, m), "matrix", d))
            out.append((p + "mlp/w2", (m, d), "matrix", m))
        else:
            out.append((p + "moe/gate", (d, routed), "router", d))
            out.append((p + "moe/w1", (held, d, me), "expert", d))
            out.append((p + "moe/w3", (held, d, me), "expert", d))
            out.append((p + "moe/w2", (held, me, d), "expert", me))
            out.append((p + "moe/shared_w1", (d, ms), "matrix", d))
            out.append((p + "moe/shared_w3", (d, ms), "matrix", d))
            out.append((p + "moe/shared_w2", (ms, d), "matrix", ms))
    out.append(("head/final_norm/scale", (d,), "rms_scale", 0))
    return out


def spread(kind, fan_in, sizes):
    if kind == "rms_scale":
        return 1.0, 0.1
    if kind in ("expert", "router"):
        return 0.0, fan_in ** -0.5
    if kind == "token_table":
        return 0.0, 1.0
    if kind == "lm_head":
        return 0.0, float(sizes["head_std"]) * fan_in ** -0.5
    return _encoder.spread(kind, fan_in, sizes)


def check_sizes(module, sizes):
    c, h, v = module.cfg, module.cfg.head, module.cfg.video
    got = {
        "hidden_size": h.dim, "vocab_size": h.vocab_size,
        "num_hidden_layers": h.num_layers,
        "first_k_dense_replace": h.num_dense_layers,
        "heads_total": h.num_heads,
        "num_attention_heads": len(h.heads_held),
        "heads_held": list(h.heads_held),
        "q_lora_rank": h.q_lora_rank, "kv_lora_rank": h.kv_lora_rank,
        "qk_nope_head_dim": h.qk_nope_head_dim,
        "qk_rope_head_dim": h.qk_rope_head_dim, "v_head_dim": h.v_head_dim,
        "intermediate_size": h.mlp_dim,
        "moe_intermediate_size": h.moe_mlp_dim,
        "num_routed_experts": h.num_experts,
        "n_routed_experts": len(h.experts_held),
        "experts_held": list(h.experts_held),
        "num_experts_per_tok": h.top_k,
        "n_group": h.n_group, "topk_group": h.topk_group,
        "n_shared_experts": h.n_shared_experts,
        "routed_scaling_factor": h.routed_scaling_factor,
        "norm_topk_prob": h.moe.norm_topk_prob,
        "scoring_func": h.moe.scoring,
        "rms_norm_eps": h.norm_eps, "rope_theta": h.rope_theta,
        "max_position_embeddings": h.max_context,
        "instruction_ids": list(c.instruction_ids),
        "decode_steps": c.decode_steps,
    }
    bad = _encoder.disagree(got, sizes)
    rope = {"factor": h.rope_factor, "beta_fast": h.rope_beta_fast,
            "beta_slow": h.rope_beta_slow, "mscale": h.rope_mscale,
            "mscale_all_dim": h.rope_mscale_all_dim,
            "original_max_position_embeddings": h.rope_original_max}
    for k, (a, b) in _encoder.disagree(rope, sizes["rope_scaling"]).items():
        bad["rope_scaling." + k] = (a, b)
    enc = {"hidden_size": v.encoder.dim, "image_size": v.image_size,
           "num_hidden_layers": v.encoder.num_layers,
           "num_attention_heads": v.encoder.num_heads,
           "intermediate_size": v.encoder.mlp_dim,
           "patch_size": v.patch_size, "num_frames": v.num_frames,
           "tubelet_size": v.tubelet_size}
    for k, (a, b) in _encoder.disagree(enc, sizes["encoder"]).items():
        bad["encoder." + k] = (a, b)
    return bad


def sample_flops(sizes, src_h, src_w):
    """One stream's round, counting what THIS CHIP's share has to do (so
    that a share of the whole step's peak stays true): resize and encoder
    of one clip, the connector; the 784 visual positions through the blocks
    (MLA's projections at the held heads, with the new row's own
    up-projection; attention of the held heads over the mean depth of the
    de-phased schedule (``mean_context``); the up-projection of the cached
    rows a stream attends, once a stream a block (the mean context before
    the round); the dense feed-forward, or the router over all 160, the
    shared experts and the held experts at their expected share: top-k x
    held / routed of a token's pairs, 0.375 of one expert's work a token);
    then the D committed tokens through the blocks in the latent space
    (attention over 576- and 512-wide rows a held head) and the head over
    the held vocabulary rows. The masked rest of the cache, the routed
    rows that belong to other holders and the instruction's prefill (once
    a batch) are not counted."""
    enc = _video_sizes(sizes)
    d, dv = sizes["hidden_size"], enc["hidden_size"]
    ps, ts, frames = enc["patch_size"], enc["tubelet_size"], enc["num_frames"]
    tokens = _visual_tokens(sizes)
    total = (frames * flops.resize_flops(src_h, src_w, enc["image_size"])
             + 2 * tokens * (ts * ps * ps * 3) * dv
             + _encoder.encoder_flops(tokens, enc)
             + 2 * tokens * (dv * d + d * d))
    h = len(sizes["heads_held"])
    rq, r = sizes["q_lora_rank"], sizes["kv_lora_rank"]
    dn, dr = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"]
    dvh = sizes["v_head_dim"]
    steps = int(sizes["decode_steps"])
    ctx = mean_context(sizes)
    before = ctx - (tokens + steps) / 2.0     # mean context a round starts at
    pairs = (sizes["num_experts_per_tok"] * len(sizes["experts_held"])
             / sizes["num_routed_experts"])
    up = 2 * r * h * (dn + dvh)               # a row's keys and values
    project = 2 * (d * rq + rq * h * (dn + dr) + d * (r + dr) + h * dvh * d)
    ffn_dense = 3 * 2 * d * sizes["intermediate_size"]
    ffn_routed = (2 * d * sizes["num_routed_experts"]
                  + (pairs + sizes["n_shared_experts"])
                  * 3 * 2 * d * sizes["moe_intermediate_size"])
    attend_plain = 2 * h * (dn + dr + dvh) * ctx
    attend_latent = 2 * h * (r + dr + r) * ctx
    prefill = decode = 0.0
    for _, dense in _blocks(sizes):
        ffn = ffn_dense if dense else ffn_routed
        prefill += project + up + attend_plain + ffn + before * up / tokens
        decode += project + up + attend_latent + ffn
    decode += 2 * d * sizes["vocab_size"]
    return int(total + tokens * prefill + steps * decode)
