"""Family ``lfm2_stream``: a VideoMAE encoder, a connector and an LFM2-MoE
decoder that keeps a state per camera (``models/lfm2.py``,
``engine/stream_state.py``). ``vit.py`` lists the answers a family gives.

A result depends on every frame its camera has read since the stream's
last reset, and on the tokens the program emitted in the earlier rounds of
that context (the reference is teacher-forced with them: logits are
compared, not samples). ``correct.reference_rows`` hands ``reference_args``
the windows and nothing else of a result, so the served tokens ride on what
``window`` returns: a :class:`Window`, a list of frame numbers that also
holds them.

Spreads of its own kinds (the encoder's are ``_encoder.spread``):
``rms_scale`` 1 + N(0, 0.1^2); ``conv_taps`` N(0, 0.5^2) (three taps, so
the convolution's output is of the order of its input); ``expert`` and
``router`` N(0, 1/fan_in) (router logits of order 1, sigmoid scores spread
over (0.2, 0.8)); ``router_bias`` N(0, 0.2^2): of the order of the gaps
between neighbouring scores, so a dropped bias changes most tokens'
experts; ``embedding`` N(0, head_std^2 / hidden): the table is tied to the
output head, so logits spread by about ``head_std`` and the top-5 are
distinct.
"""

from __future__ import annotations

import zlib

import numpy as np

from vbench import correct, flops, loader

from families import _encoder
from families._encoder import template  # noqa: F401

STRUCTURED_SIZES = ("layer_types", "rope_parameters", "experts_held",
                    "instruction_ids", "encoder")
REFERENCE_BLOCK = 1         # one context (up to 12 frames, ~4,000 positions)
TOP_K = 5


class Window(list):
    """The frame numbers a result depends on, oldest first, with what else
    the reference needs of the result: ``rounds`` since the stream's reset
    (this one included) and the ``tokens`` served since then."""

    def __init__(self, frames, rounds, tokens):
        super().__init__(frames)
        self.rounds = int(rounds)
        self.tokens = [int(t) for t in tokens]


def _video_sizes(sizes):
    return dict(sizes["encoder"], num_labels=1)


def _layers(sizes):
    """[(operator kind, dense feed-forward?)] of the layers that are run."""
    return [(kind, i < sizes["num_dense_layers"])
            for i, kind in enumerate(sizes["layer_types"])]


def param_spec(sizes):
    enc = _video_sizes(sizes)
    out = [("video/" + name, shape, kind, fan_in)
           for name, shape, kind, fan_in
           in loader.family("videomae").param_spec(enc)
           if not name.startswith("head/")]
    d, dv = sizes["hidden_size"], enc["hidden_size"]
    hd = d // sizes["num_attention_heads"]
    kvd = sizes["num_key_value_heads"] * hd
    held = len(sizes["experts_held"])
    m, me = sizes["intermediate_size"], sizes["moe_intermediate_size"]
    _encoder.dense(out, "connector/fc1", dv, d)
    _encoder.dense(out, "connector/fc2", d, d)
    out.append(("head/embed", (sizes["vocab_size"], d), "embedding", d))
    for i, (kind, dense) in enumerate(_layers(sizes)):
        p = f"head/layer{i}_"
        out.append((p + "operator_norm/scale", (d,), "rms_scale", 0))
        if kind == "conv":
            out.append((p + "conv/in_proj", (d, 3 * d), "matrix", d))
            out.append((p + "conv/conv_kernel",
                        (sizes["conv_L_cache"], d), "conv_taps", 0))
            out.append((p + "conv/out_proj", (d, d), "matrix", d))
        else:
            out.append((p + "attn/q_proj", (d, d), "matrix", d))
            out.append((p + "attn/k_proj", (d, kvd), "matrix", d))
            out.append((p + "attn/v_proj", (d, kvd), "matrix", d))
            out.append((p + "attn/out_proj", (d, d), "matrix", d))
            out.append((p + "attn/q_norm/scale", (hd,), "rms_scale", 0))
            out.append((p + "attn/k_norm/scale", (hd,), "rms_scale", 0))
        out.append((p + "ffn_norm/scale", (d,), "rms_scale", 0))
        if dense:
            out.append((p + "mlp/w1", (d, m), "matrix", d))
            out.append((p + "mlp/w3", (d, m), "matrix", d))
            out.append((p + "mlp/w2", (m, d), "matrix", m))
        else:
            out.append((p + "moe/gate", (d, sizes["num_routed_experts"]),
                        "router", d))
            out.append((p + "moe/expert_bias",
                        (sizes["num_routed_experts"],), "router_bias", 0))
            out.append((p + "moe/w1", (held, d, me), "expert", d))
            out.append((p + "moe/w3", (held, d, me), "expert", d))
            out.append((p + "moe/w2", (held, me, d), "expert", me))
    out.append(("head/final_norm/scale", (d,), "rms_scale", 0))
    return out


def spread(kind, fan_in, sizes):
    if kind == "rms_scale":
        return 1.0, 0.1
    if kind == "conv_taps":
        return 0.0, 0.5
    if kind in ("expert", "router"):
        return 0.0, fan_in ** -0.5
    if kind == "router_bias":
        return 0.0, 0.2
    if kind == "embedding":
        return 0.0, float(sizes["head_std"]) * fan_in ** -0.5
    return _encoder.spread(kind, fan_in, sizes)


def check_sizes(module, sizes):
    c, h, v = module.cfg, module.cfg.head, module.cfg.video
    got = {
        "hidden_size": h.dim, "vocab_size": h.vocab_size,
        "num_hidden_layers": len(h.layer_types),
        "layer_types": list(h.layer_types),
        "num_dense_layers": h.num_dense_layers,
        "num_attention_heads": h.num_heads,
        "num_key_value_heads": h.num_kv_heads,
        "intermediate_size": h.mlp_dim,
        "moe_intermediate_size": h.moe_mlp_dim,
        "num_routed_experts": h.num_experts,
        "num_experts": len(h.experts_held),
        "experts_held": list(h.experts_held),
        "num_experts_per_tok": h.top_k,
        "use_expert_bias": h.use_expert_bias,
        "norm_topk_prob": h.norm_topk_prob,
        "routed_scaling_factor": h.routed_scaling_factor,
        "conv_L_cache": h.conv_l_cache, "norm_eps": h.norm_eps,
        "max_position_embeddings": h.max_context,
        "instruction_ids": list(c.instruction_ids),
        "decode_steps": c.decode_steps,
    }
    bad = _encoder.disagree(got, sizes)
    if sizes["rope_parameters"].get("rope_theta") != h.rope_theta:
        bad["rope_parameters"] = (sizes["rope_parameters"], h.rope_theta)
    enc = {"hidden_size": v.encoder.dim, "image_size": v.image_size,
           "num_hidden_layers": v.encoder.num_layers,
           "num_attention_heads": v.encoder.num_heads,
           "intermediate_size": v.encoder.mlp_dim,
           "patch_size": v.patch_size, "num_frames": v.num_frames,
           "tubelet_size": v.tubelet_size}
    for k, (a, b) in _encoder.disagree(enc, sizes["encoder"]).items():
        bad["encoder." + k] = (a, b)
    return bad


def sample_frames(sizes):
    return int(sizes["encoder"]["num_frames"])


def _visual_tokens(sizes):
    e = sizes["encoder"]
    return ((e["num_frames"] // e["tubelet_size"])
            * (e["image_size"] // e["patch_size"]) ** 2)


def _round_positions(sizes):
    return _visual_tokens(sizes) + int(sizes["decode_steps"])


def max_rounds(sizes):
    """Rounds a context holds after the instruction."""
    return ((sizes["max_position_embeddings"] - len(sizes["instruction_ids"]))
            // _round_positions(sizes))


def expected_state(device_id, answered, sizes):
    """(rounds since the reset, positions) after a camera's ``answered``-th
    answer (1-based), by the program's policy: a context holds
    ``max_rounds`` rounds, and the FIRST is cut to
    1 + crc32(device_id) % max_rounds so that a fleet that starts together
    does not reset together."""
    first = 1 + zlib.crc32(device_id.encode()) % max_rounds(sizes)
    rounds = (answered if answered <= first
              else (answered - first - 1) % max_rounds(sizes) + 1)
    return rounds, (len(sizes["instruction_ids"])
                    + rounds * _round_positions(sizes))


def window(result, reads, sizes):
    """Every read since the reset the result names: the 8 of its first
    round's clip and one more a round since. Where the harness's record
    has the served answer (``kept``), it is marked with whether its state
    is what the camera's reads and the policy give (``state_ok``:
    ``compare`` counts the others). The control's result has only
    ``packet``: one round, fixed token ids."""
    n = sample_frames(sizes)
    kept = result.get("kept")
    if kept is None:
        frames = correct.last_reads(result, reads, n)
        steps = int(sizes["decode_steps"])
        return frames and Window(
            frames, 1, [(101 * (i + 3)) % sizes["vocab_size"]
                        for i in range(steps)])
    if result["packet"] not in reads:
        return None
    answered = reads.index(result["packet"]) + 2 - n
    kept["state_ok"] = (
        (kept["rounds"], kept["positions"])
        == expected_state(result["device_id"], answered, sizes)
        and len(kept["tokens"]) == kept["rounds"] * sizes["decode_steps"])
    rounds = kept["rounds"]
    if not 1 <= rounds <= max_rounds(sizes):
        return None
    frames = correct.last_reads(result, reads, n + rounds - 1)
    return frames and Window(frames, rounds, kept["tokens"])


def reference_args(buf, windows, sizes):
    """(frames [block, F, H, W, 3], rounds [block], tokens [block,
    max_rounds * D]): round j's clip is frames j .. j + 7 of a window."""
    width = max_rounds(sizes) * int(sizes["decode_steps"])
    rounds = np.ones((len(buf),), np.int32)
    tokens = np.zeros((len(buf), width), np.int32)
    for i, w in enumerate(windows):
        rounds[i] = w.rounds
        tokens[i, :len(w.tokens)] = w.tokens[:width]
    return buf, rounds, tokens


def mean_context(sizes):
    """Mean number of keys a position attends to, over the positions of a
    round and the rounds of a full context (every depth equally often: the
    de-phased schedule)."""
    r = max_rounds(sizes)
    per = _round_positions(sizes)
    return len(sizes["instruction_ids"]) + per * (r - 1) / 2.0 + per / 2.0


def sample_flops(sizes, src_h, src_w):
    """One stream's round: resize and encoder of one clip, the connector,
    then 784 prefilled and D decoded positions through the layers that are
    run, with the held experts at their expected share (top-k x held /
    routed experts of a token's pairs: one pair a token a layer at 4 x 16 /
    64), attention over the mean depth of the de-phased schedule
    (``mean_context``; the program computes the masked rest of the 4096
    too, which is not work the model needs), and the output head for the D
    positions that emit. The instruction's prefill (once a batch) is not
    counted."""
    enc = _video_sizes(sizes)
    d, dv = sizes["hidden_size"], enc["hidden_size"]
    ps, ts, frames = enc["patch_size"], enc["tubelet_size"], enc["num_frames"]
    tokens = _visual_tokens(sizes)
    total = (frames * flops.resize_flops(src_h, src_w, enc["image_size"])
             + 2 * tokens * (ts * ps * ps * 3) * dv
             + _encoder.encoder_flops(tokens, enc)
             + 2 * tokens * (dv * d + d * d))
    hd = d // sizes["num_attention_heads"]
    kvd = sizes["num_key_value_heads"] * hd
    pairs = (sizes["num_experts_per_tok"] * len(sizes["experts_held"])
             / sizes["num_routed_experts"])
    per_token = 0.0
    for kind, dense in _layers(sizes):
        if kind == "conv":
            per_token += 2 * d * 3 * d + 2 * d * d
        else:
            per_token += (2 * 2 * d * d + 2 * 2 * d * kvd
                          + 2 * 2 * mean_context(sizes) * d)
        if dense:
            per_token += 3 * 2 * d * sizes["intermediate_size"]
        else:
            per_token += (2 * d * sizes["num_routed_experts"]
                          + pairs * 3 * 2 * d * sizes["moe_intermediate_size"])
    steps = int(sizes["decode_steps"])
    total += (tokens + steps) * per_token + steps * 2 * d * sizes["vocab_size"]
    return int(total)


def kept(res):
    """Of a served result, what is compared: the tokens since the reset,
    the top-5 (id, probability) of each of this round's steps, and where
    the state stands."""
    h = res.head
    return {"tokens": list(h.token_ids),
            "steps": [list(zip(s.token_ids, s.probs)) for s in h.steps],
            "rounds": h.rounds_since_reset, "positions": h.positions}


def as_served(row):
    """What a result would carry had the program computed ``row`` (the
    reference's [D, vocabulary] logits): the control's stand-in for
    :func:`kept`. Its state is not the program's and is not judged."""
    return {"steps": [correct.topk(r, TOP_K) for r in row],
            "state_ok": True}


def _gaps(got, row):
    """|log(served probability) - reference log-softmax| over the served
    top-5 of each of one result's steps (1e30 where a step is missing or
    names no valid id)."""
    if len(got["steps"]) != len(row):
        return [1e30]
    out = []
    for top, logits in zip(got["steps"], row):
        lp = correct.log_softmax(logits)
        if not top:
            out.append(1e30)
        for cid, p in top:
            if not 0 <= cid < len(logits) or not p > 0:
                out.append(1e30)
            else:
                out.append(abs(float(np.log(p)) - float(lp[cid])))
    return out


def compare(served, rows, model):
    """Over the served top-5 of all D steps of that model's sampled
    results, |log(served probability) - reference log-softmax|:
    ``logprob_err_<model>`` the widest, ``logprob_mean_<model>`` the mean,
    ``logprob_med_<model>`` the median (a token whose experts differ by
    rounding moves a few gaps by ~1 and with them the mean; the median
    holds still, so a fault that moves every gap a little shows in it) and
    ``logprob_carry_<model>`` the median over the results that continue a
    state (rounds since the reset >= 2: their logits depend on what the
    pool carried from the rounds before; a result without ``rounds``, the
    control's, counts as a first round). ``state_errors``: the sampled
    results whose ``rounds_since_reset`` / ``positions`` / token count are
    not what their camera's reads and the reset policy give."""
    errs, carry, bad = [], [], 0
    for got, row in zip(served, rows):
        bad += 0 if got.get("state_ok") else 1
        gaps = _gaps(got, row)
        errs.extend(gaps)
        if got.get("rounds", 1) >= 2:
            carry.extend(gaps)
    out = {f"logprob_err_{model}": max(errs),
           f"logprob_mean_{model}": float(np.mean(errs)),
           f"logprob_med_{model}": float(np.median(errs)),
           "state_errors": bad}
    if carry:
        out[f"logprob_carry_{model}"] = float(np.median(carry))
    return out
