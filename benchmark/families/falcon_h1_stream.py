"""Family ``falcon_h1_stream``: a VideoMAE encoder, a connector and a
Falcon-H1 decoder whose every layer is a Mamba-2 mixer beside GQA attention,
keeping a key-value cache, a convolution state and a state-space state per
camera (``models/falcon_h1.py``, ``ops/ssd.py``, ``models/gqa.py``,
``engine/stream_state.py``). ``vit.py`` lists the answers a family gives;
what a result depends on, its window, what is kept and compared of it and
the reference's arguments are the first head's (``lfm2_stream.py``).

This chip's share of a layer that two chips share: ``num_attention_heads``
of ``heads_total`` query heads and ``num_key_value_heads`` of
``kv_heads_total`` (their slices of W_q, W_k, W_v and W_o), ``mamba_n_heads``
of ``mamba_heads_total`` mixer heads in ``mamba_n_groups`` of
``mamba_groups_total`` groups (a group's B and C, its norm, its heads'
channels of z, x and dt, of the convolution and of W_out), the first
``intermediate_held`` of the SwiGLU's ``intermediate_size`` columns and
``vocab_size`` rows of the embedding and the head. The tensors below ARE the
slices; the reference is handed the same tensors.

Spreads (``assumed.weights`` of the configuration). Falcon-H1 carries a
constant multiplier on every path (µP); a matrix whose product is multiplied
by a constant ``c`` is drawn N(0, 1/(c^2 fan_in)), as a trained model's
weights grow to meet their multipliers, so that every path's output is of
order one and a multiplier taken as 1 moves it by 1/c: ``in_proj`` by
``ssm_in_multiplier``, ``key`` by ``key_multiplier``, ``attn_out``,
``ssm_out``, ``mlp_gate`` and ``mlp_down`` by theirs, ``token_table`` by
``embedding_multiplier`` and ``lm_head`` by ``lm_head_multiplier`` (times
``head_std``: logits spread by about it, the top-5 distinct). A row-parallel
matrix's fan-in is the whole layer's (``mamba_d_ssm``, ``heads_total`` x
``head_dim``, ``intermediate_size``): this chip's partial sum is what the
deployment's weights give it. ``conv_taps`` N(0, 2^2): the four taps bring
the x, B and C parts (``ssm_multipliers`` 0.25, 0.18, 0.5 of an order-one
projection) to order one before the SiLU. ``dt_bias`` N(-6, 1.5^2) and
``a_log`` N(-0.5, 1): the heads' decay per position ``dt A`` is log-normal
about 1.5e-3, the slow end of the published initialisation's range
(``dt`` in [1e-3, 0.1], ``A`` in [1, 16]), so that about half the heads
remember further back than a round's 792 positions and the carried state
reaches the round's served positions. ``skip`` (D) 1 + N(0, 0.1^2), norm
scales 1 + N(0, 0.1^2), biases N(0, 0.05^2).
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

STRUCTURED_SIZES = ("ssm_multipliers", "mlp_multipliers", "instruction_ids",
                    "encoder")
# contexts a reference call holds: the four run together (the recurrence
# is one scan over positions for all four; ~3.5 GB of float32 a call)
REFERENCE_BLOCK = 4
# a multiplier of the path each kind of matrix feeds (the spreads above)
_MULTIPLIER = {"in_proj": "ssm_in_multiplier", "key": "key_multiplier",
               "attn_out": "attention_out_multiplier",
               "ssm_out": "ssm_out_multiplier",
               "token_table": "embedding_multiplier",
               "lm_head": "lm_head_multiplier"}


def _widths(sizes):
    """(mixer channels held, convolution channels, W_in's columns)."""
    e = sizes["mamba_n_heads"] * sizes["mamba_d_head"]
    gn = sizes["mamba_n_groups"] * sizes["mamba_d_state"]
    return e, e + 2 * gn, 2 * e + 2 * gn + sizes["mamba_n_heads"]


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
    nh, m = sizes["mamba_n_heads"], sizes["intermediate_held"]
    e, conv, width = _widths(sizes)
    _encoder.dense(out, "connector/fc1", dv, d)
    _encoder.dense(out, "connector/fc2", d, d)
    out.append(("head/embed", (sizes["vocab_size"], d), "token_table", d))
    out.append(("head/lm_head", (sizes["vocab_size"], d), "lm_head", d))
    for i in range(sizes["num_hidden_layers"]):
        p = f"head/layer{i}/"
        out.append((p + "input_norm/scale", (d,), "rms_scale", 0))
        out.append((p + "mamba/in_proj", (d, width), "in_proj", d))
        out.append((p + "mamba/conv_kernel", (sizes["mamba_d_conv"], conv),
                    "conv_taps", 0))
        out.append((p + "mamba/conv_bias", (conv,), "bias", 0))
        out.append((p + "mamba/dt_bias", (nh,), "dt_bias", 0))
        out.append((p + "mamba/A_log", (nh,), "a_log", 0))
        out.append((p + "mamba/D", (nh,), "skip", 0))
        out.append((p + "mamba/norm_scale", (e,), "rms_scale", 0))
        out.append((p + "mamba/out_proj", (e, d), "ssm_out",
                    sizes["mamba_d_ssm"]))
        out.append((p + "attn/q_proj", (d, qd), "matrix", d))
        out.append((p + "attn/k_proj", (d, kvd), "key", d))
        out.append((p + "attn/v_proj", (d, kvd), "matrix", d))
        out.append((p + "attn/out_proj", (qd, d), "attn_out",
                    sizes["heads_total"] * hd))
        out.append((p + "ffn_norm/scale", (d,), "rms_scale", 0))
        out.append((p + "mlp/gate_proj", (d, m), "mlp_gate", d))
        out.append((p + "mlp/up_proj", (d, m), "matrix", d))
        out.append((p + "mlp/down_proj", (m, d), "mlp_down",
                    sizes["intermediate_size"]))
    out.append(("head/final_norm/scale", (d,), "rms_scale", 0))
    return out


def spread(kind, fan_in, sizes):
    if kind in ("rms_scale", "skip"):
        return 1.0, 0.1
    if kind == "conv_taps":
        return 0.0, 2.0
    if kind == "dt_bias":
        return -6.0, 1.5
    if kind == "a_log":
        return -0.5, 1.0
    if kind == "mlp_gate":
        return 0.0, fan_in ** -0.5 / sizes["mlp_multipliers"][0]
    if kind == "mlp_down":
        return 0.0, fan_in ** -0.5 / sizes["mlp_multipliers"][1]
    if kind == "token_table":
        return 0.0, 1.0 / sizes["embedding_multiplier"]
    if kind == "lm_head":
        return 0.0, (float(sizes["head_std"]) * fan_in ** -0.5
                     / sizes["lm_head_multiplier"])
    if kind in _MULTIPLIER:
        return 0.0, fan_in ** -0.5 / sizes[_MULTIPLIER[kind]]
    return _encoder.spread(kind, fan_in, sizes)


def check_sizes(module, sizes):
    c, h, v = module.cfg, module.cfg.head, module.cfg.video
    got = {
        "hidden_size": h.dim, "vocab_size": h.vocab_size,
        "num_hidden_layers": h.num_layers,
        "num_attention_heads": h.num_heads,
        "num_key_value_heads": h.num_kv_heads, "head_dim": h.head_dim,
        "mamba_n_heads": h.mamba_heads, "mamba_n_groups": h.mamba_groups,
        "mamba_d_head": h.mamba_head_dim, "mamba_d_ssm": h.mamba_d_ssm,
        "mamba_d_state": h.d_state, "mamba_d_conv": h.d_conv,
        "mamba_chunk_size": h.chunk,
        "mamba_norm_before_gate": h.norm_before_gate,
        "intermediate_size": h.mlp_dim, "intermediate_held": h.mlp_held,
        "rms_norm_eps": h.norm_eps, "rope_theta": h.rope_theta,
        "embedding_multiplier": h.embedding_multiplier,
        "lm_head_multiplier": h.lm_head_multiplier,
        "attention_in_multiplier": h.attention_in_multiplier,
        "attention_out_multiplier": h.attention_out_multiplier,
        "key_multiplier": h.key_multiplier,
        "ssm_in_multiplier": h.ssm_in_multiplier,
        "ssm_out_multiplier": h.ssm_out_multiplier,
        "ssm_multipliers": list(h.ssm_multipliers),
        "mlp_multipliers": list(h.mlp_multipliers),
        "max_position_embeddings": h.max_context,
        "instruction_ids": list(c.instruction_ids),
        "decode_steps": c.decode_steps,
    }
    bad = _encoder.disagree(got, sizes)
    enc = {"hidden_size": v.encoder.dim, "image_size": v.image_size,
           "num_hidden_layers": v.encoder.num_layers,
           "num_attention_heads": v.encoder.num_heads,
           "intermediate_size": v.encoder.mlp_dim,
           "patch_size": v.patch_size, "num_frames": v.num_frames,
           "tubelet_size": v.tubelet_size}
    for k, (a, b) in _encoder.disagree(enc, sizes["encoder"]).items():
        bad["encoder." + k] = (a, b)
    return bad


def _scan_counts(sizes, positions):
    """(operations, HBM bytes) of the chunked scan for one stream's round
    through every layer: ``positions`` prefilled in chunks of
    ``mamba_chunk_size`` (the last one padded, as the program pads it), then
    ``decode_steps`` one-position steps."""
    L, P = sizes["mamba_chunk_size"], sizes["mamba_d_head"]
    N, H = sizes["mamba_d_state"], sizes["mamba_n_heads"]
    G = sizes["mamba_n_groups"]
    k = math.ceil(positions / L)
    state = H * P * N                 # one stream's state a layer
    # a chunk: C.B of each group, the masked mix of the heads, its product
    # with dt x, what the chunk adds to the state and what the state at
    # its start gives each position, the skip; the pass between chunks
    chunk = (2 * L * L * N * G + L * L * H + 2 * L * L * P * H
             + 2 * 2 * L * P * N * H + 2 * L * P * H + 2 * state)
    # a step: the decayed state plus dt x (outer) B, read out against C
    step = 3 * state + 2 * state + 2 * P * H
    steps = int(sizes["decode_steps"])
    ops = k * chunk + steps * step
    # the prefill reads x, B, C and dt and writes y (float32, every padded
    # position), reads the carried state and writes the last, and writes
    # and reads two states a chunk (what it adds, the state at its start);
    # a step reads the state and writes it
    e = H * P
    data = k * L * (2 * e + 2 * G * N + H) * 4
    nbytes = data + 2 * state * 4 + 2 * 2 * k * state * 4 \
        + steps * 2 * state * 4
    layers = sizes["num_hidden_layers"]
    return layers * ops, layers * nbytes


def ssm_scan_ops(sizes, positions):
    """Operations of the chunked scan and the decode steps' recurrence in
    one stream's round (``_scan_counts``), every layer."""
    return _scan_counts(sizes, positions)[0]


def ssm_scan_bytes(sizes, positions):
    """HBM bytes of the same (``_scan_counts``)."""
    return _scan_counts(sizes, positions)[1]


def sample_flops(sizes, src_h, src_w):
    """One stream's round, counting what THIS CHIP's share has to do:
    resize and encoder of one clip, the connector; the 784 visual
    positions and the D decoded ones through the layers (the mixer's two
    projections, its convolution and its scan, ``ssm_scan_ops``; the held
    heads' projections and attention over the mean depth of the de-phased
    schedule, ``mean_context``; the held columns of the SwiGLU) and the
    head over the held vocabulary rows for the D positions that emit. The
    instruction's prefill (once, at load) is not counted."""
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
    per_token = (2 * d * width + 2 * sizes["mamba_d_conv"] * conv
                 + 2 * e * d
                 + 2 * d * qd + 2 * 2 * d * kvd + 2 * qd * d
                 + 2 * 2 * mean_context(sizes) * qd
                 + 3 * 2 * d * sizes["intermediate_held"])
    steps = int(sizes["decode_steps"])
    total += (tokens + steps) * per_token * sizes["num_hidden_layers"]
    total += ssm_scan_ops(sizes, tokens)
    return int(total + steps * 2 * d * sizes["vocab_size"])
