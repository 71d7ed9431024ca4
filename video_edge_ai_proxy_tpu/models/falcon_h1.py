"""Falcon-H1's decoder as a per-stream streaming head behind the VideoMAE
encoder (``falcon_h1``: tiiuae/Falcon-H1-34B-Instruct ``config.json`` and
transformers' ``models/falcon_h1/modeling_falcon_h1.py``).

The fourth head of ROADMAP R9, served exactly where the other three are:
the round, the connector and the cast at load are
``models/stream_head.py``'s, the attention and its key-value pool
``models/gqa.py``'s (shared with ``models/lfm2.py``), the Mamba-2 mixer
``models/mamba2.py``'s (shared with ``models/nemotron_h.py``) with its
chunked scan ``ops/ssd.py``'s. Every layer is a PARALLEL hybrid: a
Mamba-2 mixer and GQA attention read the same normed input and their
outputs are added to the residual together. With ``x`` the layer's input
[T, d] and ``n = RMS(x)`` (eps 1e-5; ``modeling_falcon_h1.py`` 1035-1040,
1118):

- **Mixer** (``FalconH1Mixer.torch_forward``, 777-986):
  ``p = (n * ssm_in_multiplier) W_in * mup`` (790-792; ``mup`` scales the
  five parts z | x | B | C | dt by ``ssm_multipliers``, ``compute_mup_vector``
  1172-1206); ``z, xBC, dt = split(p)`` (793); ``xBC = silu(conv4(xBC) +
  b_conv)``, causal and depthwise, its state the last 3 positions of
  ``xBC`` before the convolution (831); ``xs, B, C = split(xBC)``, B and C
  one group's, shared by its heads (906-907); ``dt = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)`` (841, 901); per head ``h_t =
  exp(dt_t A) h_{t-1} + dt_t xs_t (outer) B_t``, ``y_t = h_t C_t + D xs_t``
  (875, 889, 895); ``y = GroupRMS(y * silu(z))`` over each group's
  channels, the gate first (``norm_before_gate`` false: 403-415); ``m = (y
  W_out) * ssm_out_multiplier`` (986, 1110).
- **Attention** (``FalconH1Attention``, 347-388): ``q = (n a_in) W_q``,
  ``k = ((n a_in) W_k) * key_multiplier`` (360), ``v = (n a_in) W_v``; q
  and k rotated (rotate-half, theta 1e11), no per-head norm; causal
  softmax at ``head_dim^-1/2`` (328); ``a = Attn W_o *
  attention_out_multiplier`` (1113, 1123).
- **Rest**: ``x' = x + m + a`` (1125-1128); ``out = x' + SwiGLU(RMS(x'))``,
  ``SwiGLU(u) = (up(u) * silu(gate(u) mlp_multipliers[0])) W_down *
  mlp_multipliers[1]`` (1020-1021, 1130-1134).
- **Around the stack**: token embeddings times ``embedding_multiplier``
  (1270); the connector's visual tokens are not multiplied, as
  ``inputs_embeds`` are not; a final RMS norm (1318); ``logits = (h
  W_head) * lm_head_multiplier`` (1535), untied.

**This chip's share** of a layer that two chips share (the configuration's
``deployment``): ``num_heads`` query heads and ``num_kv_heads`` key-value
heads of the attention (their slices of W_q, W_k, W_v, W_o), the mixer's
``mamba_groups`` groups with their ``mamba_heads`` heads (a group's B, C,
its norm and its heads' channels of z, x, dt, the convolution and W_out),
``mlp_held`` of the SwiGLU's ``mlp_dim`` columns, ``vocab_size`` rows of
the embedding and the head. The row-parallel products (W_o, W_out,
W_down) give this chip's partial sum; nothing stands in for the other
chip's, and the residual is counted once. A whole group a chip keeps the
group norm exact with no exchange.

State, per stream, by kind: ``kv`` = (keys, values), each [layers, slots,
KV, hd, max_context], appended a round at a time by the shared
``gqa.flush_round``; ``conv`` [slots, layers, 3, conv channels] and
``ssm`` [slots, layers, heads, head_dim, d_state] in **float32** (a
recurrence carried over up to five rounds of 792 positions; the published
kernels accumulate it in float32), carried as batch rows and OVERWRITTEN
when the round commits. Slot-major where the keys and values are
layer-major: the rows a round carries are gathered by slot along axis 0.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from . import gqa, mamba2, stream_head
from .common import Dtype
from .gqa import CachedAttention, GqaConfig, flush_round
from .mamba2 import Mamba2Config, Mamba2Mixer, gated_norm
from .stream_head import (Connector, RmsNorm, _kernel, moe_outputs,
                          top_tokens)
from .videomae import VideoMAE, VideoMAEConfig, tiny_videomae_config


@dataclass(frozen=True)
class FalconH1Config:
    vocab_size: int = 32640           # rows held: the first eighth of 261,120
    dim: int = 5120
    num_layers: int = 4               # published layers 0-3 of 72
    num_heads: int = 10               # query heads held: 0-9 of 20
    num_kv_heads: int = 2             # key-value heads held: 0-1 of 4
    head_dim: int = 128
    mamba_heads: int = 16             # mixer heads held: 0-15 of 32
    mamba_groups: int = 1             # groups held: group 0 of 2
    mamba_head_dim: int = 128
    mamba_d_ssm: int = 4096           # the layer's whole mixer width
    d_state: int = 256
    d_conv: int = 4
    chunk: int = 128
    mlp_dim: int = 21504              # the layer's whole SwiGLU width
    mlp_held: int = 10752             # its columns held: 0-10751
    norm_before_gate: bool = False
    norm_eps: float = 1e-5
    rope_theta: float = 1e11
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    ssm_multipliers: Tuple[float, ...] = (
        0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
        0.3535533905932738)
    mlp_multipliers: Tuple[float, ...] = (
        0.1767766952966369, 0.011160714285714284)
    max_context: int = 4096

    @property
    def mixer(self) -> Mamba2Config:
        """The held share of the mixer, with its multipliers."""
        return Mamba2Config(
            dim=self.dim, mamba_heads=self.mamba_heads,
            mamba_groups=self.mamba_groups,
            mamba_head_dim=self.mamba_head_dim, d_state=self.d_state,
            d_conv=self.d_conv, chunk=self.chunk, norm_eps=self.norm_eps,
            norm_before_gate=self.norm_before_gate,
            ssm_in_multiplier=self.ssm_in_multiplier,
            ssm_out_multiplier=self.ssm_out_multiplier,
            ssm_multipliers=tuple(self.ssm_multipliers))

    @property
    def ssm_dim(self) -> int:
        return self.mixer.ssm_dim

    @property
    def conv_dim(self) -> int:
        return self.mixer.conv_dim

    @property
    def in_proj_dim(self) -> int:
        return self.mixer.in_proj_dim

    def mup(self) -> np.ndarray:
        return self.mixer.mup()

    @property
    def attn(self) -> GqaConfig:
        return GqaConfig(
            dim=self.dim, num_heads=self.num_heads,
            num_kv_heads=self.num_kv_heads, head_dim=self.head_dim,
            norm_eps=self.norm_eps, rope_theta=self.rope_theta,
            qk_norm=False, key_multiplier=self.key_multiplier)


# the standing instruction: 32 token ids of the held vocabulary slice, a
# constant of the registry entry (the configuration file repeats them)
INSTRUCTION_IDS = tuple((7919 * (i + 1)) % 32633 for i in range(32))


@dataclass(frozen=True)
class StreamHeadConfig(stream_head.StreamHeadConfig):
    """VideoMAE encoder -> connector -> Falcon-H1 head, and the round's
    policy."""
    video: VideoMAEConfig = field(default_factory=VideoMAEConfig)
    head: FalconH1Config = field(default_factory=FalconH1Config)
    instruction_ids: Tuple[int, ...] = INSTRUCTION_IDS


def tiny_stream_head_config() -> StreamHeadConfig:
    """CPU twin: every mechanism at toy widths: two layers, the whole of
    each (both of two mixer groups, every head, every column), a chunk of
    8 under a round of 50 visual tokens (no multiple of it)."""
    return StreamHeadConfig(
        video=dataclasses.replace(tiny_videomae_config(), image_size=40),
        head=FalconH1Config(
            vocab_size=96, dim=32, num_layers=2, num_heads=4,
            num_kv_heads=2, head_dim=8, mamba_heads=4, mamba_groups=2,
            mamba_head_dim=8, mamba_d_ssm=32, d_state=8, chunk=8,
            mlp_dim=80, mlp_held=80, max_context=224),
        instruction_ids=(5, 17, 3, 90, 41), decode_steps=3,
        prefill_chunk=2)


class FalconMlp(nn.Module):
    """The held columns of the SwiGLU, with its two multipliers."""
    cfg: FalconH1Config
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, h):
        c = self.cfg
        d, m = c.dim, c.mlp_held
        w1 = _kernel(self, "gate_proj", (d, m), ("embed", "mlp"))
        w3 = _kernel(self, "up_proj", (d, m), ("embed", "mlp"))
        w2 = _kernel(self, "down_proj", (m, d), ("mlp", "embed"))
        with jax.named_scope("head_dense_mlp"):
            a = (h @ w3.astype(self.dtype)) * nn.silu(
                (h @ w1.astype(self.dtype)) * c.mlp_multipliers[0])
            return (a @ w2.astype(self.dtype)) * c.mlp_multipliers[1]


class FalconH1Block(nn.Module):
    """The mixer and the attention side by side over one normed input,
    then the SwiGLU, each added to the residual."""
    cfg: FalconH1Config
    layer: int = 0
    dtype: Dtype = jnp.bfloat16

    def setup(self):
        c = self.cfg
        self.input_norm = RmsNorm(c.norm_eps, self.dtype, name="input_norm")
        # the gated norm as this module names it, where the benchmark's
        # family tests plant a fault in it
        self.mamba = Mamba2Mixer(c.mixer, self.dtype, gated_norm,
                                 name="mamba")
        self.attn = CachedAttention(c.attn, self.layer, self.dtype,
                                    name="attn")
        self.ffn_norm = RmsNorm(c.norm_eps, self.dtype, name="ffn_norm")
        self.mlp = FalconMlp(c, self.dtype, name="mlp")

    def __call__(self, x, conv, ssm, pool, rbuf, slots, pos0, r0):
        c = self.cfg
        n = self.input_norm(x)
        m, conv, ssm = self.mamba(n, conv, ssm)
        if c.attention_in_multiplier != 1.0:
            n = n * c.attention_in_multiplier
        a, rbuf = self.attn(n, pool, rbuf, slots, pos0, r0)
        x = x + m + a * c.attention_out_multiplier
        return x + self.mlp(self.ffn_norm(x)), conv, ssm, rbuf


class FalconH1Stack(nn.Module):
    """The decoder's layers over [B, T, d] embeddings that continue each
    stream's state at ``pos0`` [B]."""
    cfg: FalconH1Config
    dtype: Dtype = jnp.bfloat16

    def setup(self):
        c = self.cfg
        table = lambda name: self.param(  # noqa: E731
            name, nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("vocab", "embed")),
            (c.vocab_size, c.dim), jnp.float32)
        self.embed_table, self.lm_head = table("embed"), table("lm_head")
        self.layers = [FalconH1Block(c, i, self.dtype, name=f"layer{i}")
                       for i in range(c.num_layers)]
        self.final_norm = RmsNorm(c.norm_eps, self.dtype, name="final_norm")

    def embed(self, ids):
        return (jnp.take(self.embed_table, ids, axis=0)
                * self.cfg.embedding_multiplier).astype(self.dtype)

    def logits(self, h):
        """float32 logits over the held rows of the (untied) head from
        [..., d] hidden, through the final norm."""
        with jax.named_scope("head_lm"):
            return jnp.einsum(
                "...d,vd->...v", self.final_norm(h),
                self.lm_head.astype(self.dtype),
                preferred_element_type=jnp.float32) \
                * self.cfg.lm_head_multiplier

    def __call__(self, x, conv, ssm, pool, rbuf, slots, pos0, r0=0):
        """x [B, T, d], the round's positions ``r0 .. r0 + T - 1`` of
        streams whose round began at ``pos0`` [B]; conv [B, layers, d_conv
        - 1, conv channels] and ssm [B, layers, heads, head_dim, d_state]
        the rows' own state; pool = (keys, values), each [layers, slots,
        KV, hd, S], read only, of which row b owns slot ``slots[b]``; rbuf
        = (keys, values), each [layers, B, KV, hd, R], the round's own.
        Returns (h, conv, ssm, rbuf)."""
        rk, rv = rbuf
        x = x.astype(self.dtype)
        for i, layer in enumerate(self.layers):
            x, cs, ss, (k, v) = layer(x, conv[:, i], ssm[:, i], pool,
                                      (rk[i], rv[i]), slots, pos0, r0)
            conv = conv.at[:, i].set(cs.astype(conv.dtype))
            ssm = ssm.at[:, i].set(ss.astype(ssm.dtype))
            rk, rv = rk.at[i].set(k), rv.at[i].set(v)
        return x, conv, ssm, (rk, rv)


class VideoMAEFalconH1(nn.Module):
    """VideoMAE encoder (no classifier) -> connector -> Falcon-H1 head."""
    cfg: StreamHeadConfig
    dtype: Dtype = jnp.bfloat16
    # the kind of state the round buffer is flushed into
    cache_kind = "kv"

    def setup(self):
        c = self.cfg
        self.video = VideoMAE(c.video, self.dtype, name="video")
        self.connector = Connector(c.head.dim, self.dtype, name="connector")
        self.head = FalconH1Stack(c.head, self.dtype, name="head")

    def encode(self, clips):
        """[B, T, H, W, 3] preprocessed clips -> [B, tokens, head dim]."""
        return self.connector(self.video.features(clips))

    def forward(self, x, conv, ssm, pool, rbuf, slots, pos0, r0=0):
        return self.head(x, conv, ssm, pool, rbuf, slots, pos0, r0)

    def embed(self, ids):
        return self.head.embed(ids)

    def logits(self, h):
        return self.head.logits(h)

    def __call__(self, clips):
        """A fresh stream's first round without a pool: the logits that
        predict its first token, [B, vocab] (what ``init`` traces)."""
        c = self.cfg
        x = self.encode(clips)
        b = x.shape[0]
        ids = jnp.asarray(c.instruction_ids, jnp.int32)
        x = jnp.concatenate([jnp.broadcast_to(
            self.embed(ids)[None], (b, len(ids), c.head.dim)), x], axis=1)
        state = empty_state(c.head, b, 0, self.dtype)
        h, _, _, _ = self.head(
            x, state["conv"], state["ssm"], state["kv"],
            gqa.round_buffer(c.head.attn, c.head.num_layers, b, x.shape[1],
                             self.dtype),
            jnp.arange(b), jnp.zeros((b,), jnp.int32))
        return self.logits(h[:, -1])

    # what the engine's ``stream`` step kind and the pool ask of a model
    @nn.nowrap
    def empty_state(self, slots: int):
        """Zeroed state for ``slots`` streams by kind (the SSM state in
        float32, the others in the model's dtype), and the axis of each
        kind's buffers that counts the slots."""
        return (empty_state(self.cfg.head, slots, dtype=self.dtype),
                {"conv": 0, "ssm": 0, "kv": 1})

    @nn.nowrap
    def serve_round(self, variables, clips, state, slots, pos0, reset,
                    preprocess=lambda clips: clips):
        return stream_head.serve_round(self, variables, clips, state, slots,
                                       pos0, reset, preprocess)

    @nn.nowrap
    def instruction_state(self, variables):
        """The standing instruction through a fresh state: the conv and SSM
        state and the keys and values every context starts from ({"conv":
        [1, layers, d_conv - 1, channels], "ssm": [1, layers, heads,
        head_dim, d_state], "kv": (keys, values), each [layers, 1, KV, hd,
        instruction length]}). A function of the weights alone."""
        c, hc = self.cfg, self.cfg.head
        ids = jnp.asarray(c.instruction_ids, jnp.int32)
        zero = jnp.zeros((1,), jnp.int32)
        state = empty_state(hc, 1, 0, self.dtype)
        x = self.apply(variables, ids, method=VideoMAEFalconH1.embed)[None]
        _, conv, ssm, kv = self.apply(
            variables, x, state["conv"], state["ssm"], state["kv"],
            gqa.round_buffer(hc.attn, hc.num_layers, 1, len(ids),
                             self.dtype),
            zero, zero, method=VideoMAEFalconH1.forward)
        return {"conv": conv, "ssm": ssm, "kv": kv}

    # what ``stream_head.serve_round`` asks of a head
    @nn.nowrap
    def empty_counts(self):
        return {}

    @nn.nowrap
    def seed_round(self, variables, state, slots, reset):
        """The key-value pool with the instruction's keys and values as
        the first rows of every slot (read and written in place, by slot),
        and the rows' conv and SSM state (gathered by slot), the
        instruction's for a stream that resets."""
        return mamba2.seed_state(variables["instruction"], state, slots,
                                 reset, len(self.cfg.instruction_ids))

    @nn.nowrap
    def round_buffer(self, rows: int, dtype):
        hc = self.cfg.head
        return gqa.round_buffer(hc.attn, hc.num_layers, rows,
                                self.cfg.round_positions, dtype)

    @nn.nowrap
    def prefill(self, variables, x, kv, rows, rbuf, slots, pos0):
        h, conv, ssm, rbuf = self.apply(
            variables, x, *rows, kv, rbuf, slots, pos0,
            method=VideoMAEFalconH1.forward)
        return h[:, -1], (conv, ssm), rbuf, {}

    @nn.nowrap
    def decode(self, variables, kv, h, rows, rbuf, slots, pos0, counts):
        """D steps that each commit one position a stream."""
        c = self.cfg
        apply = lambda method, *a: self.apply(variables, *a, method=method)  # noqa: E731

        def step(carry, r):
            h, rows, rbuf = carry
            with jax.named_scope("head_decode"):
                tok, top_i, top_p = top_tokens(
                    apply(VideoMAEFalconH1.logits, h))
                h, conv, ssm, rbuf = apply(
                    VideoMAEFalconH1.forward,
                    apply(VideoMAEFalconH1.embed, tok)[:, None],
                    *rows, kv, rbuf, slots, pos0, c.visual_tokens + r)
            return (h[:, 0], (conv, ssm), rbuf), (tok, top_i, top_p)

        (_, rows, rbuf), (toks, top_i, top_p) = jax.lax.scan(
            step, (h, rows, rbuf),
            jnp.arange(c.decode_steps, dtype=pos0.dtype))
        hc = c.head
        return {"tokens": toks.T, "top_ids": top_i.transpose(1, 0, 2),
                "top_probs": top_p.transpose(1, 0, 2), "rows": rows,
                "rbuf": rbuf, **moe_outputs(counts),
                # what the round's prefill attention visited, summed over
                # its chunks and layers
                **gqa.prefill_visits(
                    pos0, c.visual_tokens, hc.num_heads // hc.num_kv_heads,
                    gqa.prefill_cap(kv[0].shape[-1], c.round_positions),
                    hc.num_layers)}

    @nn.nowrap
    def commit_round(self, state, kv, rows, rbuf, slots, pos0):
        """The round's keys and values appended to each slot; its conv and
        SSM state written over the slot's."""
        return {**mamba2.commit_rows(state, rows, slots),
                "kv": flush_round(kv, rbuf, slots, pos0)}


def empty_state(cfg: FalconH1Config, slots: int, context=None,
                dtype=jnp.bfloat16) -> dict:
    """Zeroed {"conv", "ssm", "kv"} for ``slots`` streams of ``context``
    positions (None: the configuration's ``max_context``)."""
    context = cfg.max_context if context is None else context
    return {
        "conv": jnp.zeros((slots, cfg.num_layers, cfg.d_conv - 1,
                           cfg.conv_dim), dtype),
        "ssm": jnp.zeros((slots, cfg.num_layers, cfg.mamba_heads,
                          cfg.mamba_head_dim, cfg.d_state), jnp.float32),
        "kv": gqa.empty_pool(cfg.attn, cfg.num_layers, slots, context,
                             dtype)}
