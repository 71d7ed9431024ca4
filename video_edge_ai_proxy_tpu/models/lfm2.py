"""LFM2-MoE decoder as a per-stream streaming head behind the VideoMAE
encoder (``lfm2_moe``: LiquidAI/LFM2-24B-A2B ``config.json``).

The reference ships frames to external clients and has no model at all
(`/root/reference/README.md:5-27`); this is the video-language head of
ROADMAP R9: every round a clip camera's window goes through VideoMAE, its
tubelet tokens through a connector into the decoder, are PREFILLED into
that stream's state, and a few tokens are decoded greedily.

The decoder, as the published config lays it out: RMSNorm before the
operator and before the feed-forward, two residual adds a layer. Operator
by ``layer_types``: ``conv``: ``B, C, x = split(in_proj(h), 3)``,
``u = B * x``, ``y = out_proj(C * causal_conv1d(u))`` (depthwise, kernel
``conv_L_cache``, no bias), whose state is the last ``conv_L_cache - 1``
positions of ``u``; ``full_attention``: grouped-query attention, q and k
RMS-normed per head, rotary positions (rotate-half), causal
(``models/gqa.py``, shared with ``models/falcon_h1.py``).
Feed-forward: the first ``num_dense_layers`` dense SwiGLU, the rest
``transformer.TopKMoeMlp`` (sigmoid router over all experts, top-k of
score + bias, renormalised, dropless, this chip's ``experts_held``).
Output head tied to the embedding.

State, per stream: ``conv`` [conv layers, L-1, d] and one slot of ``kv`` =
(keys, values), each [attention layers, slots, kv heads, head_dim,
max_context]; a stream's length is kept by the host
(``engine/stream_state.py``). The round itself (connector, instruction
for the streams that reset, visual prefill in chunks of streams, D decode
steps, all committed to the state) is ``stream_head.serve_round``, shared
with the other streaming head (``models/xing4.py``), as are the norm, the
dense feed-forward, the connector and the cast at load; what is LFM2's own
is here: its operators, its two kinds of state and what the round asks of
a head (:class:`VideoMAELfm2`'s ``seed_round`` ... ``commit_round``).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import ClassVar, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from . import gqa, stream_head
from .common import Dtype
from .gqa import CachedAttention, flush_round
from .stream_head import (Connector, RmsNorm, SwiGlu, _kernel,  # noqa: F401
                          add_counts, cast_for_serving, moe_outputs,
                          prepare_for_serving, top_tokens)
from .transformer import TopKMoeConfig, TopKMoeMlp, zero_counts
from .videomae import VideoMAE, VideoMAEConfig, tiny_videomae_config


@dataclass(frozen=True)
class Lfm2Config:
    vocab_size: int = 65536
    dim: int = 2048
    # published layer 0 and two whole periods (published layers 2-9)
    layer_types: Tuple[str, ...] = (
        "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "conv")
    num_dense_layers: int = 1
    num_heads: int = 32
    num_kv_heads: int = 8
    mlp_dim: int = 11776              # dense SwiGLU width
    moe_mlp_dim: int = 1536           # one routed expert's width
    num_experts: int = 64             # router width
    top_k: int = 4
    experts_held: Tuple[int, ...] = tuple(range(16))
    use_expert_bias: bool = True
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    conv_l_cache: int = 3
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    max_context: int = 4096
    # what the shared attention asks beyond the sizes (gqa.GqaConfig)
    qk_norm: ClassVar[bool] = True
    key_multiplier: ClassVar[float] = 1.0
    rotary: ClassVar[bool] = True

    @property
    def head_dim(self) -> int:
        return self.dim // self.num_heads

    @property
    def conv_layers(self) -> int:
        return sum(t == "conv" for t in self.layer_types)

    @property
    def attn_layers(self) -> int:
        return sum(t == "full_attention" for t in self.layer_types)

    @property
    def moe(self) -> TopKMoeConfig:
        return TopKMoeConfig(
            dim=self.dim, mlp_dim=self.moe_mlp_dim,
            num_experts=self.num_experts, top_k=self.top_k,
            experts_held=tuple(self.experts_held),
            use_expert_bias=self.use_expert_bias,
            norm_topk_prob=self.norm_topk_prob,
            routed_scaling_factor=self.routed_scaling_factor)


# the standing instruction: 32 token ids, a constant of the registry entry
# (the configuration file repeats them; the benchmark's check_sizes compares)
INSTRUCTION_IDS = tuple((7919 * (i + 1)) % 65521 for i in range(32))


@dataclass(frozen=True)
class StreamHeadConfig(stream_head.StreamHeadConfig):
    """VideoMAE encoder -> connector -> LFM2 head, and the round's policy."""
    video: VideoMAEConfig = field(default_factory=VideoMAEConfig)
    head: Lfm2Config = field(default_factory=Lfm2Config)
    instruction_ids: Tuple[int, ...] = INSTRUCTION_IDS


def tiny_stream_head_config() -> StreamHeadConfig:
    """CPU twin: every mechanism at toy widths (2 periods of a shorter
    pattern, 8 experts of which 4 are held, top-2)."""
    return StreamHeadConfig(
        video=tiny_videomae_config(),
        head=Lfm2Config(
            vocab_size=96, dim=32,
            layer_types=("conv", "full_attention", "conv",
                         "full_attention", "conv"),
            num_dense_layers=1, num_heads=4, num_kv_heads=2, mlp_dim=80,
            moe_mlp_dim=24, num_experts=8, top_k=2,
            experts_held=(0, 1, 2, 3), max_context=160),
        instruction_ids=(5, 17, 3, 90),
        decode_steps=3, prefill_chunk=2)


class ShortConv(nn.Module):
    """Gated short convolution; ``state`` [B, L-1, d] is the last L-1
    positions of ``u`` before this call."""
    cfg: Lfm2Config
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, h, state):
        c, d = self.cfg, self.cfg.dim
        taps = c.conv_l_cache
        w_in = _kernel(self, "in_proj", (d, 3 * d), ("embed", "mlp"))
        w_conv = self.param("conv_kernel", nn.initializers.normal(0.3),
                            (taps, d), jnp.float32)
        w_out = _kernel(self, "out_proj", (d, d), ("mlp", "embed"))
        with jax.named_scope("head_conv"):
            b, cc, x = jnp.split(h @ w_in.astype(self.dtype), 3, axis=-1)
            u = b * x
            full = jnp.concatenate([state.astype(self.dtype), u], axis=1)
            t = u.shape[1]
            # tap j multiplies the position (taps - 1 - j) back
            y = sum(full[:, j:j + t] * w_conv[j].astype(self.dtype)
                    for j in range(taps))
            return (cc * y) @ w_out.astype(self.dtype), full[:, t:]


class Lfm2Stack(nn.Module):
    """The decoder's layers over [B, T, d] embeddings that continue each
    stream's state at ``pos0`` [B]."""
    cfg: Lfm2Config
    dtype: Dtype = jnp.bfloat16

    def setup(self):
        c = self.cfg
        self.embed_table = self.param(
            "embed", nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("vocab", "embed")),
            (c.vocab_size, c.dim), jnp.float32)
        ops, ffns, n1, n2 = [], [], [], []
        for i, kind in enumerate(c.layer_types):
            n1.append(RmsNorm(c.norm_eps, self.dtype,
                              name=f"layer{i}_operator_norm"))
            n2.append(RmsNorm(c.norm_eps, self.dtype,
                              name=f"layer{i}_ffn_norm"))
            if kind == "conv":
                ops.append(ShortConv(c, self.dtype, name=f"layer{i}_conv"))
            elif kind == "full_attention":
                ops.append(CachedAttention(
                    c, sum(k != "conv" for k in c.layer_types[:i]),
                    self.dtype, name=f"layer{i}_attn"))
            else:
                raise ValueError(f"unknown layer type {kind!r}")
            if i < c.num_dense_layers:
                ffns.append(SwiGlu(c.dim, c.mlp_dim, self.dtype,
                                   name=f"layer{i}_mlp"))
            else:
                ffns.append(TopKMoeMlp(c.moe, self.dtype,
                                       name=f"layer{i}_moe"))
        self.ops, self.ffns, self.n1, self.n2 = ops, ffns, n1, n2
        self.final_norm = RmsNorm(c.norm_eps, self.dtype, name="final_norm")

    def embed(self, ids):
        return jnp.take(self.embed_table, ids, axis=0).astype(self.dtype)

    def logits(self, h):
        """float32 logits over the tied table from [..., d] hidden."""
        with jax.named_scope("head_lm"):
            h = self.final_norm(h)
            return jnp.einsum(
                "...d,vd->...v", h, self.embed_table.astype(self.dtype),
                preferred_element_type=jnp.float32)

    def __call__(self, x, conv, pool, rbuf, slots, pos0, r0=0):
        """x [B, T, d], the round's positions ``r0 .. r0 + T - 1`` of
        streams whose round began at ``pos0`` [B]; conv [B, conv layers,
        L-1, d] the rows' own state; pool = (keys, values), each
        [attention layers, slots, KV, hd, S], read only, of which row b
        owns slot ``slots[b]``; rbuf = (keys, values), each [attention
        layers, B, KV, hd, R], the round's own. Returns (h, conv, rbuf,
        counts), the expert layers' counts (``transformer.zero_counts``)
        summed."""
        c = self.cfg
        b, t, d = x.shape
        convs, ci, ai = [], 0, 0
        rk, rv = rbuf
        counts = zero_counts(c.moe)
        x = x.astype(self.dtype)
        for i, kind in enumerate(c.layer_types):
            h = self.n1[i](x)
            if kind == "conv":
                y, s = self.ops[i](h, conv[:, ci])
                convs.append(s)
                ci += 1
            else:
                y, (k, v) = self.ops[i](h, pool, (rk[ai], rv[ai]), slots,
                                        pos0, r0)
                rk, rv = rk.at[ai].set(k), rv.at[ai].set(v)
                ai += 1
            x = x + y
            h = self.n2[i](x)
            if i < c.num_dense_layers:
                x = x + self.ffns[i](h)
            else:
                with jax.named_scope("head_moe"):
                    y, n = self.ffns[i](h.reshape(b * t, d))
                x = x + y.reshape(b, t, d)
                counts = add_counts(counts, n)
        if convs:
            conv = jnp.stack(convs, axis=1).astype(conv.dtype)
        return x, conv, (rk, rv), counts


class VideoMAELfm2(nn.Module):
    """VideoMAE encoder (no classifier) -> connector -> LFM2 head."""
    cfg: StreamHeadConfig
    dtype: Dtype = jnp.bfloat16
    # the kind of state the round buffer is flushed into
    cache_kind = "kv"

    def setup(self):
        c = self.cfg
        self.video = VideoMAE(c.video, self.dtype, name="video")
        self.connector = Connector(c.head.dim, self.dtype, name="connector")
        self.head = Lfm2Stack(c.head, self.dtype, name="head")

    def encode(self, clips):
        """[B, T, H, W, 3] preprocessed clips -> [B, tokens, head dim]."""
        return self.connector(self.video.features(clips))

    def forward(self, x, conv, pool, rbuf, slots, pos0, r0=0):
        return self.head(x, conv, pool, rbuf, slots, pos0, r0)

    def embed(self, ids):
        return self.head.embed(ids)

    def logits(self, h):
        return self.head.logits(h)

    # what the engine's ``stream`` step kind and the pool ask of a model
    @nn.nowrap
    def empty_state(self, slots: int):
        """Zeroed state for ``slots`` streams in the model's dtype, by
        kind, and the axis of each kind's buffers that counts the slots."""
        conv, kv = empty_state(self.cfg.head, slots, dtype=self.dtype)
        return {"conv": conv, "kv": kv}, {"conv": 0, "kv": 1}

    @nn.nowrap
    def serve_round(self, variables, clips, state, slots, pos0, reset,
                    preprocess=lambda clips: clips):
        return stream_head.serve_round(self, variables, clips, state, slots,
                                       pos0, reset, preprocess)

    @nn.nowrap
    def instruction_state(self, variables):
        """The standing instruction through a fresh state: the conv state
        and the keys and values every context starts from ({"conv": [1,
        conv layers, L-1, d], "kv": (keys, values), each [attention layers,
        1, KV, hd, instruction length]}). A function of the weights
        alone."""
        c, hc = self.cfg, self.cfg.head
        apply = lambda method, *a: self.apply(variables, *a, method=method)  # noqa: E731
        ids = jnp.asarray(c.instruction_ids, jnp.int32)
        zero = jnp.zeros((1,), jnp.int32)
        conv, none = empty_state(hc, 1, 0, self.dtype)
        _, conv, kv, _ = apply(
            VideoMAELfm2.forward, apply(VideoMAELfm2.embed, ids)[None], conv,
            none, round_buffer(hc, 1, len(c.instruction_ids), self.dtype),
            zero, zero)
        return {"conv": conv, "kv": kv}

    # what ``stream_head.serve_round`` asks of a head
    @nn.nowrap
    def empty_counts(self):
        return zero_counts(self.cfg.head.moe)

    @nn.nowrap
    def seed_round(self, variables, state, slots, reset):
        """The key-value pool with the instruction's keys and values as
        the first rows of every slot (read and written in place, by slot),
        and the rows' conv state (small: gathered by slot), the
        instruction's for a stream that resets."""
        ins = variables["instruction"]
        n_i = len(self.cfg.instruction_ids)
        conv = jnp.take(state["conv"], slots, axis=0, mode="clip")
        conv = jnp.where(reset[:, None, None, None],
                         ins["conv"].astype(conv.dtype), conv)
        kv = tuple(a.at[..., :n_i].set(jnp.broadcast_to(
            i.astype(a.dtype), a[..., :n_i].shape))
            for a, i in zip(state["kv"], ins["kv"]))
        return kv, conv

    @nn.nowrap
    def round_buffer(self, rows: int, dtype):
        return round_buffer(self.cfg.head, rows, self.cfg.round_positions,
                            dtype)

    @nn.nowrap
    def prefill(self, variables, x, kv, conv, rbuf, slots, pos0):
        h, conv, rbuf, counts = self.apply(
            variables, x, conv, kv, rbuf, slots, pos0,
            method=VideoMAELfm2.forward)
        return h[:, -1], conv, rbuf, counts

    @nn.nowrap
    def decode(self, variables, kv, h, conv, rbuf, slots, pos0, counts):
        """D steps that each commit one position a stream."""
        c = self.cfg
        apply = lambda method, *a: self.apply(variables, *a, method=method)  # noqa: E731

        def step(carry, r):
            h, conv, rbuf, counts = carry
            with jax.named_scope("head_decode"):
                tok, top_i, top_p = top_tokens(
                    apply(VideoMAELfm2.logits, h))
                h, conv, rbuf, m = apply(
                    VideoMAELfm2.forward,
                    apply(VideoMAELfm2.embed, tok)[:, None],
                    conv, kv, rbuf, slots, pos0, c.visual_tokens + r)
            return ((h[:, 0], conv, rbuf, add_counts(counts, m)),
                    (tok, top_i, top_p))

        (_, conv, rbuf, counts), (toks, top_i, top_p) = jax.lax.scan(
            step, (h, conv, rbuf, counts),
            jnp.arange(c.decode_steps, dtype=pos0.dtype))
        hc = c.head
        return {"tokens": toks.T, "top_ids": top_i.transpose(1, 0, 2),
                "top_probs": top_p.transpose(1, 0, 2), "rows": conv,
                "rbuf": rbuf, **moe_outputs(counts),
                # what the round's prefill attention visited, summed over
                # its chunks and layers
                **gqa.prefill_visits(
                    pos0, c.visual_tokens, hc.num_heads // hc.num_kv_heads,
                    gqa.prefill_cap(kv[0].shape[-1], c.round_positions),
                    hc.attn_layers)}

    @nn.nowrap
    def commit_round(self, state, kv, conv, rbuf, slots, pos0):
        return {"conv": state["conv"].at[slots].set(
                    conv.astype(state["conv"].dtype), mode="drop"),
                "kv": flush_round(kv, rbuf, slots, pos0)}

    def __call__(self, clips):
        """A fresh stream's first round without a pool: the logits that
        predict its first token, [B, vocab] (what ``init`` traces)."""
        c = self.cfg
        x = self.encode(clips)
        b = x.shape[0]
        ids = jnp.asarray(c.instruction_ids, jnp.int32)
        x = jnp.concatenate(
            [jnp.broadcast_to(self.embed(ids)[None], (b, len(ids), c.head.dim)),
             x], axis=1)
        conv, pool = empty_state(c.head, b, 0)
        h, _, _, _ = self.head(
            x, conv, pool, round_buffer(c.head, b, x.shape[1]),
            jnp.arange(b), jnp.zeros((b,), jnp.int32))
        return self.logits(h[:, -1])


def round_buffer(cfg: Lfm2Config, rows: int, positions: int,
                 dtype=jnp.bfloat16):
    """Zeroed (keys, values) of one round of ``rows`` streams."""
    return gqa.round_buffer(cfg, cfg.attn_layers, rows, positions, dtype)


def empty_state(cfg: Lfm2Config, slots: int, context=None,
                dtype=jnp.bfloat16):
    """Zeroed (conv, kv) for ``slots`` streams of ``context`` positions
    (None: the configuration's ``max_context``)."""
    context = cfg.max_context if context is None else context
    conv = jnp.zeros((slots, cfg.conv_layers, cfg.conv_l_cache - 1, cfg.dim),
                     dtype)
    return conv, gqa.empty_pool(cfg, cfg.attn_layers, slots, context, dtype)
