"""Family ``xing4_stream``: a VideoMAE encoder, a connector and a Xing4.0
decoder that keeps a latent cache per camera (``models/xing4.py``,
``engine/stream_state.py``). ``vit.py`` lists the answers a family gives;
what a result depends on, its window and the reference's arguments are the
sibling head's (``lfm2_stream.py``: every read since the stream's reset,
with the served tokens riding on the :class:`Window`).

The reference's row for a result is [D + 1, vocabulary]: the logits of the
round's D tokens, then the prediction module's first draft of the round.

Spreads of its own kinds (the encoder's are ``_encoder.spread``):
``rms_scale`` 1 + N(0, 0.1^2) (every RMS norm, the one inside the residual
maps too); ``matrix``, ``expert``, ``router`` and ``phi`` N(0, 1/fan_in)
(router logits and the maps' logits of order 1); ``router_bias`` N(0,
0.2^2), of the order of the gaps between neighbouring scores; ``hc_alpha``
1 + N(0, 0.1^2), so that a dropped dynamic map shows; ``hc_bias`` N(0,
0.5^2): H_res is then no identity and the pre- and post-maps differ from
stream to stream; ``token_table`` N(0, 1): a token's input embedding is of
the order of a visual token's; ``lm_head`` N(0, head_std^2 / hidden): the
head is untied, its logits spread by about ``head_std`` and the top-5 are
distinct.
"""

from __future__ import annotations

import numpy as np

from vbench import correct, flops, loader

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
_video_sizes = _sibling._video_sizes
_visual_tokens = _sibling._visual_tokens
_gaps = _sibling._gaps

STRUCTURED_SIZES = ("rope_scaling", "experts_held", "instruction_ids",
                    "encoder")
REFERENCE_BLOCK = 1         # one context (up to 12 frames, ~4,000 positions)
TOP_K = 5


def _blocks(sizes):
    """[(name, dense feed-forward?)] of the blocks that are run: the main
    model's, then the prediction module's."""
    main = [(f"layer{i}", i < sizes["first_k_dense_replace"])
            for i in range(sizes["num_hidden_layers"])]
    return main + [("mtp_block", False)] * sizes["num_nextn_predict_layers"]


def param_spec(sizes):
    enc = _video_sizes(sizes)
    out = [("video/" + name, shape, kind, fan_in)
           for name, shape, kind, fan_in
           in loader.family("videomae").param_spec(enc)
           if not name.startswith("head/")]
    d, dv = sizes["hidden_size"], enc["hidden_size"]
    h, n = sizes["num_attention_heads"], sizes["hc_mult"]
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

    def maps(p):
        out.append((p + "norm_scale", (n * d,), "rms_scale", 0))
        out.append((p + "phi", (n * d, 2 * n + n * n), "phi", n * d))
        out.append((p + "bias", (2 * n + n * n,), "hc_bias", 0))
        out.append((p + "alpha", (3,), "hc_alpha", 0))

    for name, dense in _blocks(sizes):
        p = f"head/{name}/"
        maps(p + "attn_hc/")
        out.append((p + "attn_norm/scale", (d,), "rms_scale", 0))
        out.append((p + "attn/q_a", (d, rq), "matrix", d))
        out.append((p + "attn/q_norm/scale", (rq,), "rms_scale", 0))
        out.append((p + "attn/q_b", (rq, h * (dn + dr)), "matrix", rq))
        out.append((p + "attn/kv_a", (d, r + dr), "matrix", d))
        out.append((p + "attn/kv_norm/scale", (r,), "rms_scale", 0))
        out.append((p + "attn/kv_b", (r, h * (dn + dvh)), "matrix", r))
        out.append((p + "attn/o", (h * dvh, d), "matrix", h * dvh))
        maps(p + "ffn_hc/")
        out.append((p + "ffn_norm/scale", (d,), "rms_scale", 0))
        if dense:
            out.append((p + "mlp/w1", (d, m), "matrix", d))
            out.append((p + "mlp/w3", (d, m), "matrix", d))
            out.append((p + "mlp/w2", (m, d), "matrix", m))
        else:
            out.append((p + "moe/gate", (d, routed), "router", d))
            out.append((p + "moe/expert_bias", (routed,), "router_bias", 0))
            out.append((p + "moe/w1", (held, d, me), "expert", d))
            out.append((p + "moe/w3", (held, d, me), "expert", d))
            out.append((p + "moe/w2", (held, me, d), "expert", me))
            out.append((p + "moe/shared_w1", (d, ms), "matrix", d))
            out.append((p + "moe/shared_w3", (d, ms), "matrix", d))
            out.append((p + "moe/shared_w2", (ms, d), "matrix", ms))
    out.append(("head/final_norm/scale", (d,), "rms_scale", 0))
    if sizes["num_nextn_predict_layers"]:
        out.append(("head/mtp_h_norm/scale", (d,), "rms_scale", 0))
        out.append(("head/mtp_e_norm/scale", (d,), "rms_scale", 0))
        out.append(("head/mtp_eh_proj", (2 * d, d), "matrix", 2 * d))
        out.append(("head/mtp_final_norm/scale", (d,), "rms_scale", 0))
    return out


def spread(kind, fan_in, sizes):
    if kind in ("rms_scale", "hc_alpha"):
        return 1.0, 0.1
    if kind in ("expert", "router", "phi"):
        return 0.0, fan_in ** -0.5
    if kind == "router_bias":
        return 0.0, 0.2
    if kind == "hc_bias":
        return 0.0, 0.5
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
        "num_attention_heads": h.num_heads,
        "q_lora_rank": h.q_lora_rank, "kv_lora_rank": h.kv_lora_rank,
        "qk_nope_head_dim": h.qk_nope_head_dim,
        "qk_rope_head_dim": h.qk_rope_head_dim, "v_head_dim": h.v_head_dim,
        "intermediate_size": h.mlp_dim,
        "moe_intermediate_size": h.moe_mlp_dim,
        "num_routed_experts": h.num_experts,
        "n_routed_experts": len(h.experts_held),
        "experts_held": list(h.experts_held),
        "num_experts_per_tok": h.top_k,
        "n_shared_experts": h.n_shared_experts,
        "routed_scaling_factor": h.routed_scaling_factor,
        "hc_mult": h.hc_mult, "hc_sinkhorn_iters": h.hc_sinkhorn_iters,
        "hc_eps": h.hc_eps, "mhc_h_res_clamp_min": h.hc_clamp_min,
        "mhc_h_res_clamp_max": h.hc_clamp_max,
        "rms_norm_eps": h.norm_eps, "rope_theta": h.rope_theta,
        "num_nextn_predict_layers": h.num_nextn_predict_layers,
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
    """One stream's round, counting what the program has to do: resize and
    encoder of one clip, the connector; the 784 visual positions through
    the main blocks (MLA's projections with the new row's own up-projection,
    attention over the mean depth of the de-phased schedule
    (``mean_context``), the up-projection of the cached rows a stream
    attends, once a stream a block (the mean context before the round), the
    residual maps, the dense feed-forward or the router, the shared expert
    and the held experts at their expected share: top-k x held / routed of
    a token's pairs) and through what the prediction module needs of a
    position nobody drafts from (``eh_proj``, the first map, the latent
    row); then the D committed tokens through the main blocks and the whole
    module in the latent space (attention over 576- and 512-wide rows) and
    both heads. The masked rest of the cache, padding, rejected drafts and
    the instruction's prefill (once a batch) are not counted."""
    enc = _video_sizes(sizes)
    d, dv = sizes["hidden_size"], enc["hidden_size"]
    ps, ts, frames = enc["patch_size"], enc["tubelet_size"], enc["num_frames"]
    tokens = _visual_tokens(sizes)
    total = (frames * flops.resize_flops(src_h, src_w, enc["image_size"])
             + 2 * tokens * (ts * ps * ps * 3) * dv
             + _encoder.encoder_flops(tokens, enc)
             + 2 * tokens * (dv * d + d * d))
    h, n = sizes["num_attention_heads"], sizes["hc_mult"]
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
    a_map = 2 * n * d * (2 * n + n * n) + 2 * n * d + 2 * n * n * d + 2 * n * d
    ffn_dense = 3 * 2 * d * sizes["intermediate_size"]
    ffn_routed = (2 * d * sizes["num_routed_experts"]
                  + (pairs + sizes["n_shared_experts"])
                  * 3 * 2 * d * sizes["moe_intermediate_size"])
    attend_plain = 2 * h * (dn + dr + dvh) * ctx
    attend_latent = 2 * h * (r + dr + r) * ctx
    prefill = decode = 0.0
    for _, dense in _blocks(sizes)[:sizes["num_hidden_layers"]]:
        ffn = ffn_dense if dense else ffn_routed
        prefill += project + up + attend_plain + 2 * a_map + ffn
        prefill += before * up / tokens
        decode += project + up + attend_latent + 2 * a_map + ffn
    if sizes["num_nextn_predict_layers"]:
        eh = 2 * 2 * d * d
        prefill += eh + a_map + 2 * d * (r + dr)
        decode += (eh + project + up + attend_latent + 2 * a_map
                   + ffn_routed + 2 * d * sizes["vocab_size"])
    decode += 2 * d * sizes["vocab_size"]
    return int(total + tokens * prefill + steps * decode)


def kept(res):
    """Of a served result, what is compared: the tokens since the reset,
    the top-5 (id, probability) of each of this round's tokens and of the
    round's first draft, where the state stands, and the drafts accepted."""
    h = res.head
    return {"tokens": list(h.token_ids),
            "steps": [list(zip(s.token_ids, s.probs)) for s in h.steps],
            "draft": list(zip(h.first_draft.token_ids, h.first_draft.probs)),
            "rounds": h.rounds_since_reset, "positions": h.positions,
            "accepted": h.accepted}


def as_served(row):
    """What a result would carry had the program computed ``row`` (the
    reference's [D + 1, vocabulary] logits): the control's stand-in for
    :func:`kept`. Its state is not the program's and is not judged."""
    return {"steps": [correct.topk(r, TOP_K) for r in row[:-1]],
            "draft": correct.topk(row[-1], TOP_K), "state_ok": True}


def compare(served, rows, model):
    """As the sibling head's ``compare`` over the D tokens' rows
    (``logprob_err_<model>``, ``logprob_mean_<model>``,
    ``logprob_med_<model>``, ``logprob_carry_<model>``, ``state_errors``),
    and ``logprob_draft_<model>``: the mean |log(served probability) -
    reference log-softmax| over the served top-5 of the sampled results'
    FIRST drafts, which holds the prediction module to the reference's."""
    out = _sibling.compare(served, [row[:-1] for row in rows], model)
    gaps = []
    for got, row in zip(served, rows):
        gaps.extend(_gaps({"steps": [got.get("draft", [])]}, row[-1:]))
    out[f"logprob_draft_{model}"] = float(np.mean(gaps))
    return out
