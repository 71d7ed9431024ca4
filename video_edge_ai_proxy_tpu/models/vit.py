"""ViT-B/16 frame tagger — BASELINE config 4 (32-stream dynamic batching).

Patchify is a single strided conv (one big MXU matmul per image); the
encoder comes from `transformer.py` with logical sharding names, so the same
model runs single-chip (config 4) and mesh-sharded (parallel/).
"""

from __future__ import annotations

from dataclasses import dataclass, field

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.preprocess import pad_channels
from .common import Dtype
from .transformer import AttnFn, Encoder, EncoderConfig


@dataclass(frozen=True)
class ViTConfig:
    num_classes: int = 1000
    image_size: int = 224
    patch_size: int = 16
    encoder: EncoderConfig = field(default_factory=EncoderConfig)  # B/16 defaults
    # Lane-fill channel padding for the patchify conv (ops.preprocess
    # .pad_channels; cpad lever, LEVERS_r05): kernel grows
    # [p,p,3,D]->[p,p,pad,D], zero input planes keep outputs identical;
    # import_weights zero-pads checkpoints. 0 = off.
    patch_pad_c: int = 0

    @property
    def num_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2


def tiny_vit_config(num_classes: int = 10) -> ViTConfig:
    return ViTConfig(
        num_classes=num_classes,
        image_size=32,
        patch_size=8,
        encoder=EncoderConfig(num_layers=2, dim=64, num_heads=4, mlp_dim=128),
    )


class ViT(nn.Module):
    cfg: ViTConfig
    dtype: Dtype = jnp.bfloat16
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray, train: bool = False) -> jnp.ndarray:
        c = self.cfg
        p = c.patch_size
        with jax.named_scope("embed"):
            x = x.astype(self.dtype)
            x = pad_channels(x, c.patch_pad_c)
            x = nn.Conv(
                c.encoder.dim, kernel_size=(p, p), strides=(p, p),
                padding="VALID", dtype=self.dtype, name="patch_embed",
            )(x)
        b = x.shape[0]
        x = x.reshape(b, -1, c.encoder.dim)
        cls = self.param(
            "cls_token", nn.initializers.zeros, (1, 1, c.encoder.dim), jnp.float32
        ).astype(self.dtype)
        x = jnp.concatenate([jnp.tile(cls, (b, 1, 1)), x], axis=1)
        pos = self.param(
            "pos_embed",
            nn.initializers.normal(0.02),
            (1, c.num_patches + 1, c.encoder.dim),
            jnp.float32,
        )
        x = x + pos.astype(self.dtype)
        x = Encoder(c.encoder, self.dtype, self.attn_fn, name="encoder")(
            x, deterministic=not train
        )
        with jax.named_scope("cls_head"):
            return nn.Dense(
                c.num_classes, dtype=jnp.float32, name="classifier")(x[:, 0])
