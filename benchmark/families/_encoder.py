"""What ``vit`` and ``videomae`` share: the pre-LN encoder of
``models/transformer.py`` under a stem and a classifier, served as a top-5.
Not a family: no configuration names it.

Spreads (the benchmark's choice; the program's own initialiser leaves
LayerNorm at identity and every bias at zero, which would let a dropped
bias or scale pass): matrices N(0, 1/fan_in), biases N(0, 0.05²),
LayerNorm scale 1 + N(0, 0.1²), position table and class token N(0, 0.1²),
classifier N(0, head_std²/fan_in) so that logits spread by about
``head_std`` and the top classes are distinct.
"""

from __future__ import annotations

import numpy as np

from vbench import correct, flops

STRUCTURED_SIZES = ()       # every size of these families is a scalar
TOP_K = 5


def dense(out, name, fan_in, fan_out, kind="matrix", shape=None):
    out.append((f"{name}/kernel", shape or (fan_in, fan_out), kind, fan_in))
    out.append((f"{name}/bias", (fan_out,), "bias", 0))


def norm(out, name, d):
    out.append((f"{name}/scale", (d,), "ln_scale", 0))
    out.append((f"{name}/bias", (d,), "bias", 0))


def encoder_and_head(out, sizes, head):
    """The encoder's blocks, its final LayerNorm and the classifier, after
    whatever stem ``out`` already holds."""
    d, m = sizes["hidden_size"], sizes["intermediate_size"]
    for i in range(sizes["num_hidden_layers"]):
        b = f"encoder/block{i}"
        norm(out, f"{b}/ln1", d)
        dense(out, f"{b}/attn/qkv", d, 3 * d)
        dense(out, f"{b}/attn/out", d, d)
        norm(out, f"{b}/ln2", d)
        dense(out, f"{b}/mlp/fc1", d, m)
        dense(out, f"{b}/mlp/fc2", m, d)
    norm(out, "encoder/ln_final", d)
    dense(out, head, d, sizes["num_labels"], kind="head")
    return out


def spread(kind, fan_in, sizes):
    """(mean, standard deviation) of one kind of tensor."""
    if kind == "matrix":
        return 0.0, fan_in ** -0.5
    if kind == "head":
        return 0.0, float(sizes.get("head_std", 3.0)) * fan_in ** -0.5
    if kind == "bias":
        return 0.0, 0.05
    if kind == "ln_scale":
        return 1.0, 0.1
    if kind == "table":
        return 0.0, 0.1
    raise ValueError(kind)


def program_sizes(module):
    """The registry model's sizes under the configuration file's keys."""
    c = module.cfg
    return {"hidden_size": c.encoder.dim, "image_size": c.image_size,
            "num_hidden_layers": c.encoder.num_layers,
            "num_attention_heads": c.encoder.num_heads,
            "intermediate_size": c.encoder.mlp_dim,
            "patch_size": c.patch_size, "num_labels": c.num_classes}


def disagree(got, sizes):
    """{key: (file, program)} where the two differ."""
    return {k: (sizes.get(k), v) for k, v in got.items() if sizes.get(k) != v}


def template(base, module):
    """The program's variable tree: one bfloat16 frames argument."""
    import jax
    import jax.numpy as jnp

    return jax.eval_shape(module.init, jax.random.PRNGKey(0),
                          jnp.zeros(base.example_shape(1), jnp.bfloat16))


def encoder_flops(tokens: int, sizes: dict) -> int:
    """Per layer the q/k/v projection, the score and context products, the
    output projection and the two MLP products."""
    d, m = sizes["hidden_size"], sizes["intermediate_size"]
    per_layer = (2 * tokens * d * 3 * d        # q, k, v
                 + 2 * tokens * tokens * d     # scores, all heads
                 + 2 * tokens * tokens * d     # context
                 + 2 * tokens * d * d          # output projection
                 + 2 * 2 * tokens * d * m)     # fc1, fc2
    return sizes["num_hidden_layers"] * per_layer


def model_flops(sizes, frames, tokens, embed, src_h, src_w):
    head = 2 * sizes["hidden_size"] * sizes["num_labels"]
    return (frames * flops.resize_flops(src_h, src_w, sizes["image_size"])
            + embed + encoder_flops(tokens, sizes) + head)


def kept(res):
    """Of a served result, what is compared: its (class, probability)
    list, the served top-5."""
    return [(d.class_id, d.confidence) for d in res.detections]


def as_served(row):
    """What a result would carry had the program computed ``row`` (a
    reference logit row): the control's stand-in for :func:`kept`."""
    return correct.topk(row, TOP_K)


def compare(served, rows, model):
    """From one model's sampled results (their :func:`kept`) and the
    reference's logit rows: ``logprob_err_<model>`` the widest and
    ``logprob_mean_<model>`` the mean |log(served probability) - reference
    log-softmax| over the served top-5. (``top1_gap_<model>``, the widest
    gap by which a served top-1's reference logit lies below the
    reference's best, is returned too but carries no limit: it is 0 unless
    two classes tie within the rounding, and the float8 control reads as
    low as sound runs do; PERF.md.)"""
    errs, gap = [], 0.0
    for top, row in zip(served, rows):
        lp = correct.log_softmax(row)
        if not top:
            errs.append(1e30)
            continue
        if 0 <= top[0][0] < len(row):
            gap = max(gap, float(row.max() - row[top[0][0]]))
        for cid, p in top:
            if not 0 <= cid < len(row) or not p > 0:
                errs.append(1e30)
            else:
                errs.append(abs(float(np.log(p)) - float(lp[cid])))
    return {f"logprob_err_{model}": max(errs),
            f"logprob_mean_{model}": float(np.mean(errs)),
            f"top1_gap_{model}": gap}
