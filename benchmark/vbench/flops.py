"""Operations the algorithm needs, counted from the configuration's sizes.

One multiply-add is two operations. Counted: the separable resize as the
two dense products it is served as, the patch / tubelet embedding, per
encoder layer the q/k/v projection, the score and context products, the
output projection and the two MLP products, and the classifier. Not
counted: LayerNorm, softmax, GELU, bias adds, the uint8 cast (elementwise,
a few per cent of a per cent of the total). Nothing here asks XLA: its
``cost_analysis`` counts what the compiler emitted, not what the model
needs.
"""

from __future__ import annotations


def resize_flops(src_h: int, src_w: int, size: int, channels: int = 3) -> int:
    """Per frame: rows [size,H]x[H,W*C], then columns [size,W]x[W,size*C]."""
    if (src_h, src_w) == (size, size):
        return 0
    rows = 2 * size * src_h * src_w * channels
    cols = 2 * size * src_w * size * channels
    return rows + cols


def encoder_flops(tokens: int, cfg: dict) -> int:
    d, m = cfg["hidden_size"], cfg["intermediate_size"]
    per_layer = (2 * tokens * d * 3 * d        # q, k, v
                 + 2 * tokens * tokens * d     # scores, all heads
                 + 2 * tokens * tokens * d     # context
                 + 2 * tokens * d * d          # output projection
                 + 2 * 2 * tokens * d * m)     # fc1, fc2
    return cfg["num_hidden_layers"] * per_layer


def sample_flops(family: str, cfg: dict, src_h: int, src_w: int) -> int:
    """One served sample: a frame (vit) or a clip (videomae)."""
    d, ps, s = cfg["hidden_size"], cfg["patch_size"], cfg["image_size"]
    grid = (s // ps) ** 2
    if family == "vit":
        frames, tokens = 1, grid + 1
        embed = 2 * grid * (ps * ps * 3) * d
    elif family == "videomae":
        frames = cfg["num_frames"]
        ts = cfg["tubelet_size"]
        tokens = (frames // ts) * grid
        embed = 2 * tokens * (ts * ps * ps * 3) * d
    else:
        raise ValueError(f"unknown model family {family!r}")
    head = 2 * d * cfg["num_labels"]
    return (frames * resize_flops(src_h, src_w, s) + embed
            + encoder_flops(tokens, cfg) + head)
