"""The toy family's plain reference: the ViT of
``reference/vision_transformer.py`` over the last frame of each window,
its sizes read from the frozen configuration (``layer_widths`` arrives as
a tuple)."""

import functools

import jax

from vbench import loader


@functools.lru_cache(maxsize=None)
def jitted(family: str, cfg_items: tuple, quant: str = ""):
    cfg = dict(cfg_items)
    widths = cfg.pop("layer_widths")
    assert isinstance(widths, tuple), widths
    cfg.update(hidden_size=widths[0], num_hidden_layers=len(widths))
    vit = loader.reference("vision_transformer").vit_logits

    def forward(p, windows_u8):
        """[N, reads, H, W, 3] uint8 -> [N, num_labels] logits."""
        return vit(p, windows_u8[:, -1], cfg, quant)

    return jax.jit(forward)
