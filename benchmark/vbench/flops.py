"""Operations the algorithm needs, counted from the configuration's sizes.

One multiply-add is two operations. Counted here: the separable resize as
the two dense products it is served as; counted by a model's family
(``families/<name>.py``, ``sample_flops``): its embedding, its layers'
products and its head. Not counted: normalisations, softmax, activations,
bias adds, the uint8 cast (elementwise, a few per cent of a per cent of the
total). Nothing here asks XLA: its ``cost_analysis`` counts what the
compiler emitted, not what the model needs.
"""

from __future__ import annotations

from . import loader


def resize_flops(src_h: int, src_w: int, size: int, channels: int = 3) -> int:
    """Per frame: rows [size,H]x[H,W*C], then columns [size,W]x[W,size*C]."""
    if (src_h, src_w) == (size, size):
        return 0
    rows = 2 * size * src_h * src_w * channels
    cols = 2 * size * src_w * size * channels
    return rows + cols


def sample_flops(family: str, cfg: dict, src_h: int, src_w: int) -> int:
    """One served sample of a model of ``family``, from a source frame of
    ``src_h`` x ``src_w``: the family's own count."""
    return loader.family(family).sample_flops(cfg, src_h, src_w)
