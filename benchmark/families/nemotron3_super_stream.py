"""Family ``nemotron3_super_stream``: a VideoMAE encoder, a connector and a
Nemotron-H decoder whose layers are Mamba-2 mixers, latent expert layers
and an attention layer, each alone on the residual, keeping a key-value
cache, a convolution state and a state-space state per camera
(``models/nemotron_h.py``, ``models/mamba2.py``, ``models/gqa.py``,
``models/transformer.py``, ``engine/stream_state.py``). ``vit.py`` lists
the answers a family gives; what a result depends on, its window, what is
kept and compared of it and the reference's arguments are the first
head's (``lfm2_stream.py``).

This chip's share of a 32-chip deployment: ``num_attention_heads`` of
``heads_total`` query heads and ``num_key_value_heads`` of
``kv_heads_total`` (their slices of W_q, W_k, W_v and W_o),
``mamba_num_heads`` of ``mamba_heads_total`` mixer heads in ``n_groups`` of
``n_groups_total`` whole groups (a group's B and C, its norm, its heads'
channels of z, x and dt, of the convolution and of W_out), the
``experts_held`` experts of ``num_routed_experts`` (their stacks; the
router, its bias, W_down, W_up and the shared expert whole) and
``vocab_size`` rows of the embedding and the head. The tensors below ARE
the slices; the reference is handed the same tensors.

Spreads (``assumed.weights`` of the configuration). Matrices N(0,
1/fan_in), a row-parallel one's fan-in the whole layer's (``expand`` x
``hidden_size`` for W_out, ``heads_total`` x ``head_dim`` for W_o): this
chip's partial sum is what the deployment's weights give it. ``router``
N(0, 1/fan_in) (sigmoid scores over (0.2, 0.8)), ``router_bias`` N(0,
0.2^2) (of the order of the gaps between neighbouring scores, so a dropped
bias changes most tokens' experts). ``latent_up`` (W_up) N(0, (top_k /
routed_scaling_factor)^2 / fan_in): a held pair's weight is about
``routed_scaling_factor / top_k``, so that one held pair's output is of
order one beside the shared expert's and a fault on the routed path shows.
``conv_taps`` N(0, 0.5^2) (four taps of order-one inputs give an
order-one output before the SiLU); ``dt_bias`` N(-6, 1.5^2) and ``a_log``
N(-0.5, 1), as ``falcon_h1_stream`` draws them: the heads' decay per
position is log-normal about 1.5e-3, so about half the heads remember past
a round's 792 positions and the carried state reaches the round's served
positions. ``skip`` (D) and norm scales 1 + N(0, 0.1^2), biases N(0,
0.05^2); ``token_table`` N(0, 1) (a token's embedding of the order of a
visual token's); ``lm_head`` N(0, head_std^2 / hidden) (logits spread by
about ``head_std``, the top-5 distinct).
"""

from __future__ import annotations

import math

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

STRUCTURED_SIZES = ("experts_held", "instruction_ids", "encoder")
# contexts a reference call holds: two run together (the held experts'
# [16, positions, 2,688] products and the attention's [8, positions,
# positions] scores are ~0.7 GB of float32 a context each)
REFERENCE_BLOCK = 2


def _kinds(sizes):
    """The kinds of the layers run: the first ``num_hidden_layers``
    characters of the published pattern."""
    return sizes["hybrid_override_pattern"][:sizes["num_hidden_layers"]]


def _widths(sizes):
    """(mixer channels held, convolution channels, W_in's columns)."""
    e = sizes["mamba_num_heads"] * sizes["mamba_head_dim"]
    gn = sizes["n_groups"] * sizes["ssm_state_size"]
    return e, e + 2 * gn, 2 * e + 2 * gn + sizes["mamba_num_heads"]


def param_spec(sizes):
    enc = _video_sizes(sizes)
    out = [("video/" + name, shape, kind, fan_in)
           for name, shape, kind, fan_in
           in loader.family("videomae").param_spec(enc)
           if not name.startswith("head/")]
    d, dv = sizes["hidden_size"], enc["hidden_size"]
    hd = sizes["head_dim"]
    qd = sizes["num_attention_heads"] * hd
    kvd = sizes["num_key_value_heads"] * hd
    nh = sizes["mamba_num_heads"]
    e, conv, width = _widths(sizes)
    held, routed = len(sizes["experts_held"]), sizes["num_routed_experts"]
    lat, me = sizes["moe_latent_size"], sizes["moe_intermediate_size"]
    ms = sizes["moe_shared_expert_intermediate_size"]
    _encoder.dense(out, "connector/fc1", dv, d)
    _encoder.dense(out, "connector/fc2", d, d)
    out.append(("head/embed", (sizes["vocab_size"], d), "token_table", d))
    out.append(("head/lm_head", (sizes["vocab_size"], d), "lm_head", d))
    for i, kind in enumerate(_kinds(sizes)):
        p = f"head/layer{i}/"
        out.append((p + "norm/scale", (d,), "rms_scale", 0))
        p += "mixer/"
        if kind == "M":
            out.append((p + "in_proj", (d, width), "matrix", d))
            out.append((p + "conv_kernel", (sizes["conv_kernel"], conv),
                        "conv_taps", 0))
            out.append((p + "conv_bias", (conv,), "bias", 0))
            out.append((p + "dt_bias", (nh,), "dt_bias", 0))
            out.append((p + "A_log", (nh,), "a_log", 0))
            out.append((p + "D", (nh,), "skip", 0))
            out.append((p + "norm_scale", (e,), "rms_scale", 0))
            out.append((p + "out_proj", (e, d), "matrix",
                        sizes["expand"] * d))
        elif kind == "*":
            out.append((p + "q_proj", (d, qd), "matrix", d))
            out.append((p + "k_proj", (d, kvd), "matrix", d))
            out.append((p + "v_proj", (d, kvd), "matrix", d))
            out.append((p + "out_proj", (qd, d), "matrix",
                        sizes["heads_total"] * hd))
        elif kind == "E":
            out.append((p + "gate", (d, routed), "router", d))
            out.append((p + "expert_bias", (routed,), "router_bias", 0))
            out.append((p + "w1", (held, lat, me), "matrix", lat))
            out.append((p + "w2", (held, me, lat), "matrix", me))
            out.append((p + "fc1_latent_proj", (d, lat), "matrix", d))
            out.append((p + "fc2_latent_proj", (lat, d), "latent_up", lat))
            out.append((p + "shared_w1", (d, ms), "matrix", d))
            out.append((p + "shared_w2", (ms, d), "matrix", ms))
        else:
            raise ValueError(f"unknown layer kind {kind!r}")
    out.append(("head/final_norm/scale", (d,), "rms_scale", 0))
    return out


def spread(kind, fan_in, sizes):
    if kind in ("rms_scale", "skip"):
        return 1.0, 0.1
    if kind == "conv_taps":
        return 0.0, 0.5
    if kind == "dt_bias":
        return -6.0, 1.5
    if kind == "a_log":
        return -0.5, 1.0
    if kind == "router":
        return 0.0, fan_in ** -0.5
    if kind == "router_bias":
        return 0.0, 0.2
    if kind == "latent_up":
        return 0.0, (fan_in ** -0.5 * sizes["num_experts_per_tok"]
                     / sizes["routed_scaling_factor"])
    if kind == "token_table":
        return 0.0, 1.0
    if kind == "lm_head":
        return 0.0, float(sizes["head_std"]) * fan_in ** -0.5
    return _encoder.spread(kind, fan_in, sizes)


def check_sizes(module, sizes):
    c, h, v = module.cfg, module.cfg.head, module.cfg.video
    moe = h.moe
    got = {
        "hidden_size": h.dim, "vocab_size": h.vocab_size,
        "num_hidden_layers": h.num_layers,
        "num_attention_heads": h.num_heads,
        "num_key_value_heads": h.num_kv_heads, "head_dim": h.head_dim,
        "mamba_num_heads": h.mamba_heads, "n_groups": h.mamba_groups,
        "mamba_head_dim": h.mamba_head_dim,
        "ssm_state_size": h.d_state, "conv_kernel": h.d_conv,
        "chunk_size": h.chunk,
        "moe_intermediate_size": h.moe_mlp_dim,
        "moe_shared_expert_intermediate_size": h.shared_mlp_dim,
        "moe_latent_size": h.latent_dim,
        "num_routed_experts": h.num_experts,
        "n_routed_experts": len(h.experts_held),
        "experts_held": list(h.experts_held),
        "num_experts_per_tok": h.top_k,
        "routed_scaling_factor": h.routed_scaling_factor,
        "norm_topk_prob": moe.norm_topk_prob,
        "n_group": moe.n_group, "topk_group": moe.topk_group,
        "mlp_hidden_act": moe.activation,
        "layer_norm_epsilon": h.norm_eps, "norm_eps": h.norm_eps,
        "max_position_embeddings": h.max_context,
        "instruction_ids": list(c.instruction_ids),
        "decode_steps": c.decode_steps,
        "prefill_chunk": c.prefill_chunk,
    }
    bad = _encoder.disagree(got, sizes)
    if h.pattern != _kinds(sizes):
        bad["hybrid_override_pattern"] = (_kinds(sizes), h.pattern)
    enc = {"hidden_size": v.encoder.dim, "image_size": v.image_size,
           "num_hidden_layers": v.encoder.num_layers,
           "num_attention_heads": v.encoder.num_heads,
           "intermediate_size": v.encoder.mlp_dim,
           "patch_size": v.patch_size, "num_frames": v.num_frames,
           "tubelet_size": v.tubelet_size}
    for k, (a, b) in _encoder.disagree(enc, sizes["encoder"]).items():
        bad["encoder." + k] = (a, b)
    return bad


def ssm_scan_ops(sizes, positions):
    """Operations of the chunked scan and the decode steps' recurrence in
    one stream's round, every mixer layer (``falcon_h1_stream``'s count at
    this head's sizes)."""
    return _scan_counts(sizes, positions)[0]


def ssm_scan_bytes(sizes, positions):
    """HBM bytes of the same."""
    return _scan_counts(sizes, positions)[1]


def _scan_counts(sizes, positions):
    return loader.family("falcon_h1_stream")._scan_counts({
        "mamba_chunk_size": sizes["chunk_size"],
        "mamba_d_head": sizes["mamba_head_dim"],
        "mamba_d_state": sizes["ssm_state_size"],
        "mamba_n_heads": sizes["mamba_num_heads"],
        "mamba_n_groups": sizes["n_groups"],
        "decode_steps": sizes["decode_steps"],
        "num_hidden_layers": _kinds(sizes).count("M")}, positions)


def _held_pairs(sizes):
    """Expected held (token, expert) pairs a token, an even router's:
    top-k x held / routed (0.6875 at 22 x 16 / 512)."""
    return (sizes["num_experts_per_tok"] * len(sizes["experts_held"])
            / sizes["num_routed_experts"])


def _expert_layer_ops(sizes):
    """Operations of one expert layer for one token: the router over all
    experts, W_down and W_up, the shared expert's two products, and the
    held pairs' two products in the latent at their expected share."""
    d, lat = sizes["hidden_size"], sizes["moe_latent_size"]
    return (2 * d * sizes["num_routed_experts"] + 2 * 2 * d * lat
            + 2 * 2 * d * sizes["moe_shared_expert_intermediate_size"]
            + _held_pairs(sizes) * 2 * 2 * lat
            * sizes["moe_intermediate_size"])


def moe_layer_ops(sizes, streams):
    """Operations of the prefill's expert layers for ``streams`` streams'
    rounds (``_expert_layer_ops`` a visual token a layer): the work the
    layer must do whatever implements it."""
    return (streams * _visual_tokens(sizes) * _kinds(sizes).count("E")
            * _expert_layer_ops(sizes))


def moe_layer_bytes(sizes, streams):
    """The least HBM bytes of the same: every expert layer's weights read
    once a chunk call (the router in float32, the rest in bfloat16), a
    token's input read and output written (bfloat16, model width), and a
    held pair's rows moved once each way: its latent row gathered (bfloat16
    in, out), its activated row (bfloat16, the expert width) and its output
    row (float32, the latent width) written and read."""
    d, lat = sizes["hidden_size"], sizes["moe_latent_size"]
    me = sizes["moe_intermediate_size"]
    ms = sizes["moe_shared_expert_intermediate_size"]
    weights = (4 * d * sizes["num_routed_experts"]
               + 2 * (2 * d * lat + 2 * d * ms
                      + len(sizes["experts_held"]) * 2 * lat * me))
    calls = math.ceil(streams / sizes["prefill_chunk"])
    tokens = streams * _visual_tokens(sizes)
    per_pair = 2 * 2 * lat + 2 * 2 * me + 2 * 4 * lat
    per_token = 2 * 2 * d + _held_pairs(sizes) * per_pair
    return _kinds(sizes).count("E") * (calls * weights + tokens * per_token)


def sample_flops(sizes, src_h, src_w):
    """One stream's round, counting what THIS CHIP's share has to do:
    resize and encoder of one clip, the connector; the 784 visual
    positions and the D decoded ones through the layers (a mixer's two
    projections and its convolution, its scan ``ssm_scan_ops``; the held
    heads' projections and attention over the mean depth of the de-phased
    schedule, ``mean_context``; an expert layer's ``_expert_layer_ops``)
    and the head over the held vocabulary rows for the D positions that
    emit. The instruction's prefill (once, at load) is not counted."""
    enc = _video_sizes(sizes)
    d, dv = sizes["hidden_size"], enc["hidden_size"]
    ps, ts, frames = enc["patch_size"], enc["tubelet_size"], enc["num_frames"]
    tokens = _visual_tokens(sizes)
    total = (frames * flops.resize_flops(src_h, src_w, enc["image_size"])
             + 2 * tokens * (ts * ps * ps * 3) * dv
             + _encoder.encoder_flops(tokens, enc)
             + 2 * tokens * (dv * d + d * d))
    hd = sizes["head_dim"]
    qd = sizes["num_attention_heads"] * hd
    kvd = sizes["num_key_value_heads"] * hd
    e, conv, width = _widths(sizes)
    per = {"M": 2 * d * width + 2 * sizes["conv_kernel"] * conv + 2 * e * d,
           "*": (2 * d * qd + 2 * 2 * d * kvd + 2 * qd * d
                 + 2 * 2 * mean_context(sizes) * qd),
           "E": _expert_layer_ops(sizes)}
    per_token = sum(per[kind] for kind in _kinds(sizes))
    steps = int(sizes["decode_steps"])
    total += (tokens + steps) * per_token + ssm_scan_ops(sizes, tokens)
    return int(total + steps * 2 * d * sizes["vocab_size"])
