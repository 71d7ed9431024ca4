"""VideoMAE action recognizer — BASELINE config 5 (8-frame clips, 8 cameras).

Tubelet embedding (2×16×16) is a 3-D strided conv; the token sequence
(T/2 · H/16 · W/16 = 4·14·14 = 784 for 8×224²) flows through the shared
encoder. The temporal axis is just more tokens (SURVEY.md §5.7: clip length
8 needs no ring attention — but the encoder's `attn_fn` hook accepts the
sequence-parallel implementation from `parallel/ring_attention.py` the
moment clips grow to hundreds of frames).

Mean-pool classification head (the VideoMAE fine-tune head). The MAE
pretraining objective (tube masking + pixel reconstruction) lives in
`masked_pretrain_loss` so the training path exercises the full
encoder-decoder, not just the classifier.
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
class VideoMAEConfig:
    num_classes: int = 400            # Kinetics-400
    image_size: int = 224
    patch_size: int = 16
    num_frames: int = 8
    tubelet_size: int = 2
    # Lane-fill channel padding for the tubelet conv (ops.preprocess
    # .pad_channels; cpad lever, LEVERS_r05): proj kernel grows
    # [ts,p,p,3,D]->[ts,p,p,pad,D], zero input planes keep outputs
    # identical; import_weights zero-pads checkpoints. 0 = off.
    patch_pad_c: int = 0
    encoder: EncoderConfig = field(default_factory=EncoderConfig)
    # Light decoder for the MAE pretrain objective (VideoMAE uses a narrow
    # 4-layer decoder; scaled here with the encoder config).
    decoder_layers: int = 4
    decoder_dim: int = 384

    @property
    def tokens_per_frame_group(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    @property
    def num_tokens(self) -> int:
        return (self.num_frames // self.tubelet_size) * self.tokens_per_frame_group

    @property
    def pixels_per_token(self) -> int:
        return self.tubelet_size * self.patch_size * self.patch_size * 3


def tiny_videomae_config(num_classes: int = 5) -> VideoMAEConfig:
    return VideoMAEConfig(
        num_classes=num_classes,
        image_size=32,
        patch_size=8,
        num_frames=4,
        tubelet_size=2,
        encoder=EncoderConfig(num_layers=2, dim=64, num_heads=4, mlp_dim=128),
        decoder_layers=1,
        decoder_dim=32,
    )


class TubeletEmbed(nn.Module):
    dim: int
    patch_size: int
    tubelet_size: int
    dtype: Dtype = jnp.bfloat16
    pad_c: int = 0     # lane-fill channel padding (VideoMAEConfig.patch_pad_c)

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        """[B, T, H, W, 3] -> [B, tokens, dim]."""
        p, ts = self.patch_size, self.tubelet_size
        with jax.named_scope("embed"):
            x = pad_channels(x.astype(self.dtype), self.pad_c)
            x = nn.Conv(
                self.dim, kernel_size=(ts, p, p), strides=(ts, p, p),
                padding="VALID", dtype=self.dtype, name="proj",
            )(x)
        b = x.shape[0]
        return x.reshape(b, -1, self.dim)


class VideoMAE(nn.Module):
    cfg: VideoMAEConfig
    dtype: Dtype = jnp.bfloat16
    attn_fn: Optional[AttnFn] = None

    def setup(self):
        c = self.cfg
        self.embed = TubeletEmbed(
            c.encoder.dim, c.patch_size, c.tubelet_size, self.dtype,
            pad_c=c.patch_pad_c, name="tubelet"
        )
        self.pos_embed = self.param(
            "pos_embed", nn.initializers.normal(0.02),
            (1, c.num_tokens, c.encoder.dim), jnp.float32,
        )
        self.encoder = Encoder(c.encoder, self.dtype, self.attn_fn, name="encoder")
        self.head = nn.Dense(c.num_classes, dtype=jnp.float32, name="head")

    def features(self, clips: jnp.ndarray, train: bool = False) -> jnp.ndarray:
        """[B, T, H, W, 3] -> [B, tokens, dim]: the encoder's final-norm
        output, every tubelet token (what a head behind the encoder takes;
        the classifier's weights are not touched)."""
        x = self.embed(clips) + self.pos_embed.astype(self.dtype)
        return self.encoder(x, deterministic=not train)

    def __call__(self, clips: jnp.ndarray, train: bool = False) -> jnp.ndarray:
        """Fine-tune / inference path: [B, T, H, W, 3] -> [B, num_classes]."""
        x = self.features(clips, train)
        with jax.named_scope("cls_head"):
            return self.head(jnp.mean(x.astype(jnp.float32), axis=1))

    def encode_visible(self, clips: jnp.ndarray, keep_mask: jnp.ndarray,
                       train: bool = True) -> jnp.ndarray:
        """MAE pretrain encoder pass over ALL tokens with masked tokens
        zeroed (static-shape variant of token dropping: on TPU a gather to
        a data-dependent token count would force dynamic shapes, so we trade
        the FLOPs of encoding masked positions for a fixed graph).
        keep_mask: [B, tokens] bool, True = visible."""
        x = self.embed(clips) + self.pos_embed.astype(self.dtype)
        x = jnp.where(keep_mask[..., None], x, jnp.zeros_like(x))
        return self.encoder(x, deterministic=not train)


class VideoMAEDecoder(nn.Module):
    """Narrow decoder reconstructing masked tubelet pixels."""

    cfg: VideoMAEConfig
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, tokens: jnp.ndarray, deterministic: bool = True) -> jnp.ndarray:
        c = self.cfg
        dec_cfg = EncoderConfig(
            num_layers=c.decoder_layers, dim=c.decoder_dim,
            num_heads=max(1, c.decoder_dim // 64), mlp_dim=c.decoder_dim * 4,
        )
        x = nn.Dense(c.decoder_dim, dtype=self.dtype, name="dec_embed")(tokens)
        pos = self.param(
            "dec_pos", nn.initializers.normal(0.02),
            (1, c.num_tokens, c.decoder_dim), jnp.float32,
        )
        x = x + pos.astype(self.dtype)
        x = Encoder(dec_cfg, self.dtype, name="decoder")(x, deterministic)
        return nn.Dense(c.pixels_per_token, dtype=jnp.float32, name="dec_pred")(x)


def tubelet_pixels(clips: jnp.ndarray, cfg: VideoMAEConfig) -> jnp.ndarray:
    """[B, T, H, W, 3] -> [B, tokens, pixels_per_token] ground-truth targets,
    ordered to match TubeletEmbed's conv output (t-group, h, w)."""
    b, t, h, w, _ = clips.shape
    p, ts = cfg.patch_size, cfg.tubelet_size
    x = clips.reshape(b, t // ts, ts, h // p, p, w // p, p, 3)
    x = x.transpose(0, 1, 3, 5, 2, 4, 6, 7)  # b, tg, hh, ww, ts, p, p, c
    return x.reshape(b, (t // ts) * (h // p) * (w // p), ts * p * p * 3)


def masked_pretrain_loss(
    model: VideoMAE,
    decoder: VideoMAEDecoder,
    params,
    clips: jnp.ndarray,
    keep_mask: jnp.ndarray,
) -> jnp.ndarray:
    """VideoMAE objective: MSE on normalized pixels of MASKED tokens only."""
    enc = model.apply(
        params["encoder"], clips, keep_mask, train=True,
        method=VideoMAE.encode_visible,
    )
    pred = decoder.apply(params["decoder"], enc, deterministic=False)
    target = tubelet_pixels(clips.astype(jnp.float32), model.cfg)
    mu = target.mean(axis=-1, keepdims=True)
    sd = target.std(axis=-1, keepdims=True) + 1e-6
    target = (target - mu) / sd
    err = jnp.mean((pred - target) ** 2, axis=-1)          # [B, tokens]
    masked = ~keep_mask
    return jnp.sum(err * masked) / jnp.maximum(jnp.sum(masked), 1)
