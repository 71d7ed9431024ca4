"""Xing4.0's decoder as a per-stream streaming head behind the VideoMAE
encoder (``xing4_0``: XingChen-AGI/Xing4.0-29B-A4B ``config.json``).

The reference ships frames to external clients and has no model at all
(`/root/reference/README.md:5-27`); this is the second head of ROADMAP R9,
served exactly where ``models/lfm2.py`` is: the round, the connector and
the cast at load are ``models/stream_head.py``'s. What is this head's own:

- **The residual is ``hc_mult`` streams wide** (manifold-constrained
  hyper-connections, arXiv:2512.24880): the state of a position is
  ``X [n, C]`` (held streams-first, [n, B, T, C]). Before a sublayer ``F``, from ``x̂ = RMS(flatten X)``:
  ``H_pre = σ(α_pre · x̂φ_pre + b_pre)`` [n], ``H_post = 2σ(α_post ·
  x̂φ_post + b_post)`` [n], ``H_res = SK(α_res · x̂φ_res + b_res)`` [n, n]
  (exp of the clamped logits, then ``hc_sinkhorn_iters`` times rows over
  their sums + ``hc_eps``, columns over theirs); ``h = H_pre X``, ``y =
  F(RMS(h))``, ``X' = H_res X + H_postᵀ y``. Entry: the embedding repeated
  n times; exit: the sum of the streams. The maps are float32.
- **Attention is latent (MLA)**, and lives in ``models/mla.py`` (shared
  with ``models/deepseek_v2.py``; its equations, its two paths over the one
  cache, the yarn rope, ``flush_round`` and ``seed_rows`` are written down
  there): **a position's cache row is ``[ĉ | rope(k_r)]``**, 576 numbers,
  ``k_r`` shared by all heads. This head holds all 32 of its heads
  (``Xing4Config.mla``).
- **Feed-forward**: block 0 dense SwiGLU, the others
  ``transformer.TopKMoeMlp`` with the shared expert (sigmoid router over
  all experts, top-k of score + bias, renormalised, × 2, dropless, this
  chip's ``experts_held``).
- **The prediction module** (one; DeepSeek-V3's MTP): ``u = [RMS(h_i) |
  RMS(e_{i+1})] W_eh`` through one routed block with its own cache, its own
  final norm, the shared embedding and head: logits for position ``i + 2``.
  Its row ``j`` pairs the main model's exit state at ``j`` with the input
  at ``j + 1``, so its cache trails the main one by a position, and the
  exit state of a stream's last position is carried to the next round.
  It is the decode loop's depth-1 drafter (:meth:`VideoMAEXing4.decode`).

State, per stream: one slot of ``latent`` [blocks, slots, max_context,
576] (the main blocks, then the module's; a position's row minor-most: the
layout the TPU compiler gives both paths' products, which it would copy
the whole pool into otherwise) and ``exit`` [slots, C].
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from . import mla, stream_head
from .common import Dtype
from .mla import MlaAttention, MlaConfig, flush_round, seed_rows
from .stream_head import Connector, RmsNorm, SwiGlu, _kernel, top_tokens
from .transformer import TopKMoeConfig, TopKMoeMlp
from .videomae import VideoMAE, VideoMAEConfig, tiny_videomae_config


@dataclass(frozen=True)
class Xing4Config:
    vocab_size: int = 32768           # the first quarter of 131072
    dim: int = 3584
    num_layers: int = 5               # published layer 0 and four routed
    num_dense_layers: int = 1
    num_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mlp_dim: int = 9216               # dense SwiGLU width
    moe_mlp_dim: int = 1024           # one routed expert's width
    num_experts: int = 64             # router width
    top_k: int = 4
    experts_held: Tuple[int, ...] = tuple(range(16))
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.0
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp_min: float = -30.0
    hc_clamp_max: float = 30.0
    norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 64.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    num_nextn_predict_layers: int = 1
    max_context: int = 4096

    @property
    def mla(self) -> MlaConfig:
        """The attention's own sizes (``models/mla.py``): every head held."""
        return MlaConfig(
            dim=self.dim, num_heads=self.num_heads,
            q_lora_rank=self.q_lora_rank, kv_lora_rank=self.kv_lora_rank,
            qk_nope_head_dim=self.qk_nope_head_dim,
            qk_rope_head_dim=self.qk_rope_head_dim,
            v_head_dim=self.v_head_dim, norm_eps=self.norm_eps,
            rope_theta=self.rope_theta, rope_factor=self.rope_factor,
            rope_original_max=self.rope_original_max,
            rope_beta_fast=self.rope_beta_fast,
            rope_beta_slow=self.rope_beta_slow,
            rope_mscale=self.rope_mscale,
            rope_mscale_all_dim=self.rope_mscale_all_dim)

    @property
    def latent_dim(self) -> int:
        return self.mla.latent_dim

    @property
    def row_dim(self) -> int:
        """A cache row's width in memory (``MlaConfig.row_dim``: 640)."""
        return self.mla.row_dim

    @property
    def blocks(self) -> int:
        """Blocks that keep a latent cache: the main ones, then the
        prediction module's."""
        return self.num_layers + self.num_nextn_predict_layers

    @property
    def moe(self) -> TopKMoeConfig:
        return TopKMoeConfig(
            dim=self.dim, mlp_dim=self.moe_mlp_dim,
            num_experts=self.num_experts, top_k=self.top_k,
            experts_held=tuple(self.experts_held),
            routed_scaling_factor=self.routed_scaling_factor,
            shared_mlp_dim=self.n_shared_experts * self.moe_mlp_dim)


# the standing instruction: 32 token ids of the held vocabulary slice, a
# constant of the registry entry (the configuration file repeats them)
INSTRUCTION_IDS = tuple((7919 * (i + 1)) % 32749 for i in range(32))


@dataclass(frozen=True)
class StreamHeadConfig(stream_head.StreamHeadConfig):
    """VideoMAE encoder -> connector -> Xing4 head, and the round's policy."""
    video: VideoMAEConfig = field(default_factory=VideoMAEConfig)
    head: Xing4Config = field(default_factory=Xing4Config)
    instruction_ids: Tuple[int, ...] = INSTRUCTION_IDS
    prefill_chunk: int = 8


def tiny_stream_head_config(vocab_size: int = 96) -> StreamHeadConfig:
    """CPU twin: every mechanism at toy widths (one dense and two routed
    blocks and the module, 8 experts of which 4 are held, top-2, the
    published 4 residual streams and 20 Sinkhorn iterations)."""
    return StreamHeadConfig(
        video=tiny_videomae_config(),
        head=Xing4Config(
            vocab_size=vocab_size, dim=32, num_layers=3, num_dense_layers=1,
            num_heads=4, q_lora_rank=24, kv_lora_rank=16,
            qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
            mlp_dim=80, moe_mlp_dim=24, num_experts=8, top_k=2,
            experts_held=(0, 1, 2, 3), hc_sinkhorn_iters=20,
            rope_original_max=64, max_context=160),
        instruction_ids=tuple(i % vocab_size for i in (5, 17, 3, 90)),
        decode_steps=3, prefill_chunk=2)


# -- the residual ------------------------------------------------------------

class HyperResidual(nn.Module):
    """One sublayer's three maps from the residual streams themselves: X
    [n, ..., C] (the streams lead: each is a plain [..., C] array, where a
    [..., n, C] layout would put 4 rows on an 8-row tile) -> (H_pre [n, N],
    H_post [n, N], H_res [n, n, N]), N the positions, minor-most (a [N, 4,
    4] layout would pad every 4 x 4 to a tile). float32 throughout, as the
    router is."""
    cfg: Xing4Config

    @nn.compact
    def __call__(self, x):
        c, n = self.cfg, self.cfg.hc_mult
        width = n * c.dim
        scale = self.param("norm_scale", nn.initializers.ones_init(),
                           (width,), jnp.float32)
        phi = self.param("phi", nn.initializers.xavier_uniform(),
                         (width, 2 * n + n * n), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros_init(),
                          (2 * n + n * n,), jnp.float32)
        alpha = self.param("alpha", nn.initializers.ones_init(), (3,),
                           jnp.float32)
        with jax.named_scope("mhc_maps"):
            # x̂ φ = (x (g ⊙ φ)) / rms(x), x the n streams side by side: the
            # streams go into the product as they are, one after the other,
            # and neither a joined nor a normed copy of them is made
            x = x.reshape(n, -1, c.dim)
            w = (scale[:, None] * phi).reshape(n, c.dim, -1)
            z = sum(jnp.dot(x[k], w[k], precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
                    for k in range(n))
            ms = sum(jnp.mean(jnp.square(x[k].astype(jnp.float32)), axis=-1)
                     for k in range(n)) / n
            z = (z * jax.lax.rsqrt(ms + c.norm_eps)[:, None]).T
            pre = jax.nn.sigmoid(alpha[0] * z[:n] + bias[:n, None])
            post = 2.0 * jax.nn.sigmoid(
                alpha[1] * z[n:2 * n] + bias[n:2 * n, None])
            res = (alpha[2] * z[2 * n:] + bias[2 * n:, None]).reshape(
                n, n, -1)
            return pre, post, sinkhorn(res, c)


def sinkhorn(logits, cfg: Xing4Config):
    """[n, n, N] logits -> doubly stochastic [n, n, N]: exp of the clamped
    logits, then ``hc_sinkhorn_iters`` times each row over (its sum +
    ``hc_eps``), then each column over (its sum + ``hc_eps``)."""
    m = jnp.exp(jnp.clip(logits, cfg.hc_clamp_min, cfg.hc_clamp_max))
    for _ in range(cfg.hc_sinkhorn_iters):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + cfg.hc_eps)
        m = m / (jnp.sum(m, axis=0, keepdims=True) + cfg.hc_eps)
    return m


def hc_read(pre, x):
    """h = H_pre X: [n, ..., C] -> [..., C]."""
    n, c = x.shape[0], x.shape[-1]
    xf = x.reshape(n, -1, c)
    h = sum(pre[k][:, None] * xf[k].astype(jnp.float32) for k in range(n))
    return h.reshape(x.shape[1:]).astype(x.dtype)


def hc_write(res, post, x, y):
    """X' = H_res X + H_postᵀ y."""
    n, c = x.shape[0], x.shape[-1]
    xf = x.reshape(n, -1, c).astype(jnp.float32)
    yf = y.reshape(-1, c).astype(jnp.float32)
    out = [sum(res[i, j][:, None] * xf[j] for j in range(n))
           + post[i][:, None] * yf for i in range(n)]
    return jnp.stack(out).reshape(x.shape).astype(x.dtype)


class Xing4Block(nn.Module):
    """Two sublayers, each between its own residual maps: latent attention,
    then the dense or the routed feed-forward."""
    cfg: Xing4Config
    dense: bool = False
    dtype: Dtype = jnp.bfloat16

    def setup(self):
        c = self.cfg
        self.attn_hc = HyperResidual(c, name="attn_hc")
        self.attn_norm = RmsNorm(c.norm_eps, self.dtype, name="attn_norm")
        self.attn = MlaAttention(c.mla, self.dtype, name="attn")
        self.ffn_hc = HyperResidual(c, name="ffn_hc")
        self.ffn_norm = RmsNorm(c.norm_eps, self.dtype, name="ffn_norm")
        if self.dense:
            self.mlp = SwiGlu(c.dim, c.mlp_dim, self.dtype, name="mlp")
        else:
            self.moe = TopKMoeMlp(c.moe, self.dtype, name="moe")

    def latent(self, x, pos):
        """The cache rows of X's positions, and nothing else of the block
        (what a position leaves behind for later ones)."""
        pre, _, _ = self.attn_hc(x)
        return self.attn.latent(self.attn_norm(hc_read(pre, x)), pos)

    def __call__(self, x, pool, rbuf, slots, ctx, at, cap, block):
        """``pool`` the whole latent pool, ``block`` this block's index in
        it; ``rbuf`` this block's own."""
        b, t = x.shape[1:3]
        pre, post, res = self.attn_hc(x)
        y, rbuf = self.attn(self.attn_norm(hc_read(pre, x)), pool, rbuf,
                            slots, ctx, at, cap, block)
        x = hc_write(res, post, x, y)
        pre, post, res = self.ffn_hc(x)
        h = self.ffn_norm(hc_read(pre, x))
        if self.dense:
            y, n = self.mlp(h), 0
        else:
            with jax.named_scope("head_moe"):
                y, n = self.moe(h.reshape(b * t, -1))
        return hc_write(res, post, x, y), rbuf, n


class Xing4Stack(nn.Module):
    """The decoder's blocks and its prediction module over [B, T, C]
    embeddings that continue each stream's state."""
    cfg: Xing4Config
    dtype: Dtype = jnp.bfloat16

    def setup(self):
        c = self.cfg
        table = lambda name: self.param(  # noqa: E731
            name, nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("vocab", "embed")),
            (c.vocab_size, c.dim), jnp.float32)
        self.embed_table, self.lm_head = table("embed"), table("lm_head")
        self.layers = [Xing4Block(c, i < c.num_dense_layers, self.dtype,
                                  name=f"layer{i}")
                       for i in range(c.num_layers)]
        self.final_norm = RmsNorm(c.norm_eps, self.dtype, name="final_norm")
        self.mtp_h_norm = RmsNorm(c.norm_eps, self.dtype, name="mtp_h_norm")
        self.mtp_e_norm = RmsNorm(c.norm_eps, self.dtype, name="mtp_e_norm")
        self.mtp_eh_proj = _kernel(self, "mtp_eh_proj", (2 * c.dim, c.dim),
                                   ("mlp", "embed"))
        self.mtp_block = Xing4Block(c, False, self.dtype, name="mtp_block")
        self.mtp_final_norm = RmsNorm(c.norm_eps, self.dtype,
                                      name="mtp_final_norm")

    def embed(self, ids):
        return jnp.take(self.embed_table, ids, axis=0).astype(self.dtype)

    def logits(self, h, mtp: bool = False):
        """float32 logits over the (untied) head from [..., C] exit
        states, through the main model's final norm or the module's."""
        with jax.named_scope("head_lm"):
            h = (self.mtp_final_norm if mtp else self.final_norm)(h)
            return jnp.einsum(
                "...d,vd->...v", h, self.lm_head.astype(self.dtype),
                preferred_element_type=jnp.float32)

    def _enter(self, x):
        return jnp.broadcast_to(x.astype(self.dtype)[None],
                                (self.cfg.hc_mult,) + x.shape)

    def _exit(self, x):
        return jnp.sum(x.astype(jnp.float32), axis=0).astype(self.dtype)

    def __call__(self, x, pool, rbuf, slots, ctx, at=None, cap=0):
        """x [B, T, C]: T positions of streams whose context holds ``ctx``
        [B] positions (the round's start); pool [blocks, slots, S, d],
        read only, of which row b owns slot ``slots[b]``; rbuf [blocks, B,
        R, d] the round's own. Returns (exit states [B, T, C], rbuf,
        load) with ``load`` [held] the routed pairs each held expert took,
        summed over the blocks."""
        load = jnp.zeros((len(self.cfg.moe.held),), jnp.int32)
        x = self._enter(x)
        for i, block in enumerate(self.layers):
            x, rows, n = block(x, pool, rbuf[i], slots, ctx, at, cap, i)
            rbuf, load = rbuf.at[i].set(rows), load + n
        return self._exit(x), rbuf, load

    def _mtp_input(self, h, e):
        u = jnp.concatenate([self.mtp_h_norm(h), self.mtp_e_norm(e)], -1)
        return self._enter(u @ self.mtp_eh_proj.astype(self.dtype))

    def mtp_rows(self, h, e, rbuf, ctx):
        """The module's cache rows for T prefilled positions (exit states
        ``h`` [B, T, C] paired with the NEXT positions' inputs ``e``), into
        the start of its round buffer: nothing else of the module is needed
        of a position nobody drafts from."""
        i = self.cfg.num_layers
        pos = ctx[:, None] + jnp.arange(h.shape[1], dtype=ctx.dtype)[None]
        rows = self.mtp_block.latent(self._mtp_input(h, e), pos)
        return rbuf.at[i].set(jax.lax.dynamic_update_slice_in_dim(
            rbuf[i], rows.astype(rbuf.dtype), 0, axis=1))

    def mtp(self, h, e, pool, rbuf, slots, ctx, at):
        """The module on T decode positions: (its exit states [B, T, C],
        rbuf, load)."""
        i = self.cfg.num_layers
        x, rows, n = self.mtp_block(self._mtp_input(h, e), pool, rbuf[i],
                                    slots, ctx, at, 0, i)
        return self._exit(x), rbuf.at[i].set(rows), n


class VideoMAEXing4(nn.Module):
    """VideoMAE encoder (no classifier) -> connector -> Xing4 head."""
    cfg: StreamHeadConfig
    dtype: Dtype = jnp.bfloat16

    def setup(self):
        c = self.cfg
        self.video = VideoMAE(c.video, self.dtype, name="video")
        self.connector = Connector(c.head.dim, self.dtype, name="connector")
        self.head = Xing4Stack(c.head, self.dtype, name="head")

    def encode(self, clips):
        """[B, T, H, W, 3] preprocessed clips -> [B, tokens, head dim]."""
        return self.connector(self.video.features(clips))

    def forward(self, x, pool, rbuf, slots, ctx, at=None, cap=0):
        return self.head(x, pool, rbuf, slots, ctx, at, cap)

    def mtp_rows(self, h, e, rbuf, ctx):
        return self.head.mtp_rows(h, e, rbuf, ctx)

    def mtp(self, h, e, pool, rbuf, slots, ctx, at):
        return self.head.mtp(h, e, pool, rbuf, slots, ctx, at)

    def embed(self, ids):
        return self.head.embed(ids)

    def logits(self, h, mtp: bool = False):
        return self.head.logits(h, mtp)

    def __call__(self, clips):
        """A fresh stream's first round without a pool: the logits that
        predict its first token and the module's first draft, [B, 2,
        vocab] (what ``init`` traces)."""
        c = self.cfg
        x = self.encode(clips)
        b = x.shape[0]
        ids = jnp.asarray(c.instruction_ids, jnp.int32)
        x = jnp.concatenate(
            [jnp.broadcast_to(self.embed(ids)[None], (b, len(ids), c.head.dim)),
             x], axis=1)
        t = x.shape[1]
        zero = jnp.zeros((b,), jnp.int32)
        pool = empty_latent(c.head, b, 1, self.dtype)   # nothing cached
        rbuf = empty_latent(c.head, b, t + 1, self.dtype)
        h, rbuf, _ = self.head(x, pool, rbuf, jnp.arange(b), zero)
        first = self.logits(h[:, -1])
        rbuf = self.head.mtp_rows(h[:, :-1], x[:, 1:], rbuf, zero)
        tok = jnp.argmax(first, axis=-1)
        hm, _, _ = self.head.mtp(h[:, -1:], self.embed(tok)[:, None], pool,
                                 rbuf, jnp.arange(b), zero, zero + t - 1)
        return jnp.stack([first, self.logits(hm[:, 0], True)], axis=1)

    # what the engine's ``stream`` step kind and the pool ask of a model
    @nn.nowrap
    def empty_state(self, slots: int):
        """Zeroed state for ``slots`` streams in the model's dtype, by
        kind, and the axis of each kind's buffers that counts the slots."""
        c = self.cfg.head
        return ({"latent": empty_latent(c, slots, c.max_context, self.dtype),
                 "exit": jnp.zeros((slots, c.dim), self.dtype)},
                {"latent": 1, "exit": 0})

    @nn.nowrap
    def serve_round(self, variables, clips, state, slots, pos0, reset,
                    preprocess=lambda clips: clips):
        return stream_head.serve_round(self, variables, clips, state, slots,
                                       pos0, reset, preprocess)

    @nn.nowrap
    def instruction_state(self, variables):
        """The standing instruction through a fresh state: the cache rows
        every context starts from ({"latent": [blocks, 1, instruction
        length, d], of which the module's block holds one row fewer, "exit":
        [1, C] the exit state of its last position}). A function of the
        weights alone."""
        c = self.cfg
        apply = lambda method, *a: self.apply(variables, *a, method=method)  # noqa: E731
        ids = jnp.asarray(c.instruction_ids, jnp.int32)
        zero = jnp.zeros((1,), jnp.int32)
        x = apply(VideoMAEXing4.embed, ids)[None]
        h, rbuf, _ = apply(
            VideoMAEXing4.forward, x, empty_latent(c.head, 1, 0, self.dtype),
            empty_latent(c.head, 1, len(ids), self.dtype), zero, zero)
        rbuf = apply(VideoMAEXing4.mtp_rows, h[:, :-1], x[:, 1:], rbuf, zero)
        return {"latent": rbuf, "exit": h[:, -1]}

    # what ``stream_head.serve_round`` asks of a head
    @nn.nowrap
    def empty_counts(self):
        return jnp.zeros((len(self.cfg.head.moe.held),), jnp.int32)

    @nn.nowrap
    def seed_round(self, variables, state, slots, reset):
        """The latent pool (read and written in place, by slot) with the
        instruction's rows as the first of the slots that reset (the
        module's block holds one fewer), and the rows' exit state
        (gathered by slot), the instruction's for a stream that resets."""
        ins = variables["instruction"]
        n_i, main = len(self.cfg.instruction_ids), self.cfg.head.num_layers
        rows = ins["latent"]
        # the rows that reset, whose slots hold another context's rows; the
        # module's block holds one row fewer
        keep = (jnp.arange(n_i) < n_i - 1)[None, None, :, None] | (
            jnp.arange(rows.shape[0]) < main)[:, None, None, None]
        pool = seed_rows(state["latent"], rows, slots, reset, keep)
        exit_ = jnp.take(state["exit"], slots, axis=0, mode="clip")
        return pool, jnp.where(reset[:, None],
                               ins["exit"].astype(exit_.dtype), exit_)

    @nn.nowrap
    def round_buffer(self, rows: int, dtype):
        # three rows past the round's: a rejected draft's row, and where
        # a row that has its tokens writes while the others finish
        return empty_latent(self.cfg.head, rows,
                            self.cfg.round_positions + 3, dtype)

    @property
    def _cap(self) -> int:
        """Positions that can be context when a round starts (the pool
        resets a stream whose round would pass ``max_context``), up to a
        lane tile, within the pool."""
        c = self.cfg
        return min(-(-(c.head.max_context - c.round_positions) // 128) * 128,
                   c.head.max_context)

    @nn.nowrap
    def prefill(self, variables, x, pool, exit_, rbuf, slots, pos0):
        apply = lambda method, *a: self.apply(variables, *a, method=method)  # noqa: E731
        h, rbuf, load = apply(VideoMAEXing4.forward, x, pool, rbuf, slots,
                              pos0, None, self._cap)
        # the module's row j pairs the exit state at j with the input at
        # j + 1: the stream's last position before this round comes first
        before = jnp.concatenate(
            [exit_[:, None].astype(h.dtype), h[:, :-1]], axis=1)
        rbuf = apply(VideoMAEXing4.mtp_rows, before, x, rbuf, pos0 - 1)
        return h[:, -1], h[:, -1], rbuf, load

    @nn.nowrap
    def decode(self, variables, pool, h, exit_, rbuf, slots, pos0, load):
        """Greedy decoding with the prediction module as a depth-1
        self-drafter. An iteration runs the main model on two positions a
        stream, the next token and the module's draft of the one after:
        the first position's logits give the token that follows; where it
        IS the draft the second's give one more, and two positions are
        committed, else one (the draft's cache row is dead: the next
        iteration overwrites it, in the main cache and in the module's).
        The loop ends when every row has committed its D tokens; a row
        that has is masked. Tokens, distributions and state are what one
        position an iteration would give."""
        c = self.cfg
        d, n_v = c.decode_steps, c.visual_tokens
        b = h.shape[0]
        apply = lambda method, *a: self.apply(variables, *a, method=method)  # noqa: E731
        seen = pos0 - 1                 # the module's cache trails by one

        def draft(h, toks, rbuf, at):
            """The module over exit states ``h`` [B, T, C] and the tokens
            that follow them: its logits [B, T, vocab]."""
            with jax.named_scope("mtp_draft"):
                hm, rbuf, m = apply(
                    VideoMAEXing4.mtp, h, apply(VideoMAEXing4.embed, toks),
                    pool, rbuf, slots, seen, at)
                return apply(VideoMAEXing4.logits, hm, True), rbuf, m

        def place(buf, k, mask, value):
            """``value`` [B, ...] into ``buf`` [B, D, ...] at each row's
            ``k[b]`` where ``mask[b]`` (a k past D writes nothing)."""
            hit = mask[:, None] & (jnp.arange(d)[None] == k[:, None])
            hit = hit.reshape(hit.shape + (1,) * (buf.ndim - 2))
            return jnp.where(hit, value[:, None], buf)

        with jax.named_scope("head_decode"):
            tok, top_i, top_p = top_tokens(apply(VideoMAEXing4.logits, h))
            lg, rbuf, m = draft(h[:, None], tok[:, None], rbuf,
                                jnp.full((b,), n_v, pos0.dtype))
            first = top_tokens(lg[:, 0])
        everyone = jnp.ones((b,), bool)
        zero = jnp.zeros((b,), jnp.int32)
        carry = {
            "done": zero,               # tokens committed to the cache
            "tok": tok, "draft": first[0], "h": h, "rbuf": rbuf,
            "load": load + m, "iters": jnp.zeros((), jnp.int32),
            "drafted": zero, "accepted": zero,
            "tokens": place(jnp.zeros((b, d), jnp.int32), zero, everyone,
                            tok),
            "top_ids": place(jnp.zeros((b, d) + top_i.shape[1:], jnp.int32),
                             zero, everyone, top_i),
            "top_probs": place(jnp.zeros((b, d) + top_p.shape[1:],
                                         top_p.dtype), zero, everyone, top_p),
        }

        def iteration(s):
            with jax.named_scope("head_decode"):
                done = s["done"]
                live = done < d
                at = (n_v + done).astype(pos0.dtype)
                x = apply(VideoMAEXing4.embed,
                          jnp.stack([s["tok"], s["draft"]], axis=1))
                h2, rbuf, m = apply(VideoMAEXing4.forward, x, pool,
                                    s["rbuf"], slots, pos0, at)
                lg = apply(VideoMAEXing4.logits, h2)            # [B, 2, V]
                t1, i1, p1 = top_tokens(lg[:, 0])
                t2, i2, p2 = top_tokens(lg[:, 1])
                accept = live & (t1 == s["draft"])
                two = accept & (done + 2 <= d)
                out = {k: place(place(s[k], done + 1, live, a), done + 2,
                                two, b_)
                       for k, a, b_ in (("tokens", t1, t2),
                                        ("top_ids", i1, i2),
                                        ("top_probs", p1, p2))}
                lgm, rbuf, mm = draft(h2, jnp.stack([t1, t2], axis=1), rbuf,
                                      at + 1)
                guess = jnp.argmax(lgm, axis=-1).astype(jnp.int32)
                keep = lambda new, old: jnp.where(  # noqa: E731
                    live.reshape((b,) + (1,) * (new.ndim - 1)), new, old)
                pick = lambda a: jnp.where(  # noqa: E731
                    two.reshape((b,) + (1,) * (a.ndim - 2)), a[:, 1], a[:, 0])
                return {
                    **out, "rbuf": rbuf, "load": s["load"] + m + mm,
                    "iters": s["iters"] + 1,
                    "done": jnp.where(live, done + 1 + two, done),
                    "tok": keep(jnp.where(two, t2, t1), s["tok"]),
                    "draft": keep(pick(guess), s["draft"]),
                    "h": keep(pick(h2), s["h"]),
                    "drafted": s["drafted"] + live,
                    "accepted": s["accepted"] + accept}

        s = jax.lax.while_loop(lambda s: jnp.any(s["done"] < d), iteration,
                               carry)
        return {"tokens": s["tokens"], "top_ids": s["top_ids"],
                "top_probs": s["top_probs"], "rows": s["h"],
                "rbuf": s["rbuf"], "moe_load": s["load"],
                "mtp_drafted": s["drafted"], "mtp_accepted": s["accepted"],
                "decode_iters": s["iters"],
                # what the round's prefill attention visited, summed over
                # its chunks and blocks (the module's block prefills rows
                # alone)
                **mla.prefill_visits(pos0, n_v, self._cap,
                                     c.head.num_layers),
                "draft_ids": first[1], "draft_probs": first[2]}

    @nn.nowrap
    def commit_round(self, state, pool, exit_, rbuf, slots, pos0):
        return {"latent": flush_round(pool, rbuf, slots, pos0,
                                      self.cfg.round_positions,
                                      self.cfg.head.num_layers),
                "exit": state["exit"].at[slots].set(
                    exit_.astype(state["exit"].dtype), mode="drop")}


def empty_latent(cfg: Xing4Config, rows: int, positions: int,
                 dtype=jnp.bfloat16):
    """Zeroed latent rows [blocks, rows, positions, r + d_r]: the pool
    (rows = slots), a round buffer (rows = the batch's)."""
    return mla.empty_latent(cfg.mla, cfg.blocks, rows, positions, dtype)
