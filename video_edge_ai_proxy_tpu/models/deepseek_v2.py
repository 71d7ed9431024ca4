"""DeepSeek-V2's decoder as a per-stream streaming head behind the VideoMAE
encoder (``deepseek_v2``: deepseek-ai/DeepSeek-V2 ``config.json`` and its
``modeling_deepseek.py``).

The reference ships frames to external clients and has no model at all
(`/root/reference/README.md:5-27`); this is the third head of ROADMAP R9,
served exactly where ``models/lfm2.py`` and ``models/xing4.py`` are: the
round, the connector and the cast at load are ``models/stream_head.py``'s,
the attention and its latent cache ``models/mla.py``'s, the expert layer
``models/transformer.py``'s. The layer, as published:

- **Block** (plain pre-norm residual): ``h <- h + Attn(RMS(h))``, then ``h
  <- h + F(RMS(h))``; ``F`` is the dense SwiGLU (12288) in layer 0 and the
  routed layer in the rest; the exit is a final RMS norm and an untied
  head; eps 1e-6.
- **Attention** is latent (``mla.MlaAttention``): ``c_q = RMS(h W_qa)``,
  ``[q_n | q_r] = c_q W_qb`` a head; ``[c_kv | k_r] = h W_kva``, ``ĉ =
  RMS(c_kv)``; the cache row is ``[ĉ | rope(k_r)]`` (576 numbers); ``[k_n |
  v] = ĉ W_kvb`` a head; yarn (factor 40, original 4096, β 32 / 1) on the
  rope part, cos and sin scaled by ``mscale / mscale_all_dim`` = 1; scores
  scaled by ``192^-½ · yarn_mscale(40, 0.707)²``. Prefill up-projects the
  cached rows, decode folds ``W_kvb`` into the query and the output.
  **This chip holds ``heads_held`` of the ``num_heads``** (32 of 128): the
  held heads' slices of ``W_qb``, ``W_kvb`` and ``W_o`` are what there is,
  and the output is those heads' partial sum; nothing stands in for the
  others.
- **Router** (``group_limited_greedy``; ``transformer.topk_route``): ``s =
  softmax(h W_g)`` in float32 over all 160 experts, no bias; a group's
  score is the largest ``s`` among its 20 consecutive experts; the best 3
  of the 8 groups are kept and the others' scores taken as 0; the top-6 of
  what is left; weights = those 6 scores x 16, NOT renormalised. ``y = Σ
  w_e E_e(h)`` over the chosen experts **held here** (``experts_held``: 10
  of 160), plus the shared SwiGLU (two shared experts: one of width 3072),
  unweighted. Dropless.
- **Decode** runs in the latent space and commits one position a stream an
  iteration (no prediction module: ``decode_iters`` = D).

State, per stream: one slot of ``latent`` [blocks, slots, max_context, 640]
and nothing else (a one-kind head: no ``exit`` row, nothing carried a batch
row). Counted a round, beside the held experts' load: the (token, expert)
pairs routed in all, and the tokens whose three kept groups include the
group held here.
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
from .stream_head import (Connector, RmsNorm, SwiGlu, add_counts,
                          moe_outputs, top_tokens)
from .transformer import TopKMoeConfig, TopKMoeMlp, zero_counts
from .videomae import VideoMAE, VideoMAEConfig, tiny_videomae_config


@dataclass(frozen=True)
class DeepseekV2Config:
    vocab_size: int = 12800           # the first eighth of 102400
    dim: int = 5120
    num_layers: int = 5               # published layer 0 and four routed
    num_dense_layers: int = 1
    num_heads: int = 128
    heads_held: Tuple[int, ...] = tuple(range(32))
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mlp_dim: int = 12288              # dense SwiGLU width
    moe_mlp_dim: int = 1536           # one routed expert's width
    num_experts: int = 160            # router width
    top_k: int = 6
    n_group: int = 8
    topk_group: int = 3
    experts_held: Tuple[int, ...] = tuple(range(10))
    n_shared_experts: int = 2
    routed_scaling_factor: float = 16.0
    norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 40.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 0.707
    rope_mscale_all_dim: float = 0.707
    max_context: int = 4096

    @property
    def mla(self) -> MlaConfig:
        return MlaConfig(
            dim=self.dim, num_heads=self.num_heads,
            heads_held=tuple(self.heads_held),
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
    def moe(self) -> TopKMoeConfig:
        return TopKMoeConfig(
            dim=self.dim, mlp_dim=self.moe_mlp_dim,
            num_experts=self.num_experts, top_k=self.top_k,
            experts_held=tuple(self.experts_held),
            use_expert_bias=False, norm_topk_prob=False,
            routed_scaling_factor=self.routed_scaling_factor,
            shared_mlp_dim=self.n_shared_experts * self.moe_mlp_dim,
            scoring="softmax", n_group=self.n_group,
            topk_group=self.topk_group)


# the standing instruction: 32 token ids of the held vocabulary slice, a
# constant of the registry entry (the configuration file repeats them)
INSTRUCTION_IDS = tuple((7919 * (i + 1)) % 12799 for i in range(32))


@dataclass(frozen=True)
class StreamHeadConfig(stream_head.StreamHeadConfig):
    """VideoMAE encoder -> connector -> DeepSeek-V2 head, and the round's
    policy."""
    video: VideoMAEConfig = field(default_factory=VideoMAEConfig)
    head: DeepseekV2Config = field(default_factory=DeepseekV2Config)
    instruction_ids: Tuple[int, ...] = INSTRUCTION_IDS
    # 4 streams a chunk: the step's temporaries are 2.95 GB (3.91 at 8), and
    # the cell holds 13 GB beside them (PERF.md section 4)
    prefill_chunk: int = 4


def tiny_stream_head_config(vocab_size: int = 96) -> StreamHeadConfig:
    """CPU twin: every mechanism at toy widths (one dense and two routed
    blocks; 4 heads of which 2 are held; 16 experts in 4 groups of which 2
    are kept, top-3, and half of group 0 held, as the cell holds half of
    its group 0)."""
    return StreamHeadConfig(
        video=tiny_videomae_config(),
        head=DeepseekV2Config(
            vocab_size=vocab_size, dim=32, num_layers=3, num_dense_layers=1,
            num_heads=4, heads_held=(0, 1), q_lora_rank=24, kv_lora_rank=16,
            qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
            mlp_dim=80, moe_mlp_dim=24, num_experts=16, top_k=3, n_group=4,
            topk_group=2, experts_held=(0, 1), rope_original_max=64,
            max_context=160),
        instruction_ids=tuple(i % vocab_size for i in (5, 17, 3, 90)),
        decode_steps=3, prefill_chunk=2)


class DeepseekV2Block(nn.Module):
    """Latent attention, then the dense or the routed feed-forward, each
    added to the plain residual after its own pre-norm."""
    cfg: DeepseekV2Config
    dense: bool = False
    dtype: Dtype = jnp.bfloat16

    def setup(self):
        c = self.cfg
        self.attn_norm = RmsNorm(c.norm_eps, self.dtype, name="attn_norm")
        self.attn = MlaAttention(c.mla, self.dtype, name="attn")
        self.ffn_norm = RmsNorm(c.norm_eps, self.dtype, name="ffn_norm")
        if self.dense:
            self.mlp = SwiGlu(c.dim, c.mlp_dim, self.dtype, name="mlp")
        else:
            self.moe = TopKMoeMlp(c.moe, self.dtype, name="moe")

    def __call__(self, x, pool, rbuf, slots, ctx, at, cap, block):
        """``pool`` the whole latent pool, ``block`` this block's index in
        it; ``rbuf`` this block's own."""
        b, t, d = x.shape
        y, rbuf = self.attn(self.attn_norm(x), pool, rbuf, slots, ctx, at,
                            cap, block)
        x = x + y
        h = self.ffn_norm(x)
        if self.dense:
            return x + self.mlp(h), rbuf, None
        with jax.named_scope("head_moe"):
            y, n = self.moe.routed(h.reshape(b * t, d))
        return x + y.reshape(b, t, d), rbuf, n


class DeepseekV2Stack(nn.Module):
    """The decoder's blocks over [B, T, C] embeddings that continue each
    stream's state."""
    cfg: DeepseekV2Config
    dtype: Dtype = jnp.bfloat16

    def setup(self):
        c = self.cfg
        table = lambda name: self.param(  # noqa: E731
            name, nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("vocab", "embed")),
            (c.vocab_size, c.dim), jnp.float32)
        self.embed_table, self.lm_head = table("embed"), table("lm_head")
        self.layers = [DeepseekV2Block(c, i < c.num_dense_layers, self.dtype,
                                       name=f"layer{i}")
                       for i in range(c.num_layers)]
        self.final_norm = RmsNorm(c.norm_eps, self.dtype, name="final_norm")

    def embed(self, ids):
        return jnp.take(self.embed_table, ids, axis=0).astype(self.dtype)

    def logits(self, h):
        """float32 logits over the (untied) head from [..., C] exit
        states, through the final norm."""
        with jax.named_scope("head_lm"):
            return jnp.einsum(
                "...d,vd->...v", self.final_norm(h),
                self.lm_head.astype(self.dtype),
                preferred_element_type=jnp.float32)

    def __call__(self, x, pool, rbuf, slots, ctx, at=None, cap=0):
        """x [B, T, C]: T positions of streams whose context holds ``ctx``
        [B] positions (the round's start); pool [blocks, slots, S, d],
        read only, of which row b owns slot ``slots[b]``; rbuf [blocks, B,
        R, d] the round's own. Returns (exit states [B, T, C], rbuf,
        counts), the expert layers' counts (``transformer.zero_counts``)
        summed over the blocks."""
        counts = zero_counts(self.cfg.moe)
        x = x.astype(self.dtype)
        for i, block in enumerate(self.layers):
            x, rows, n = block(x, pool, rbuf[i], slots, ctx, at, cap, i)
            rbuf = rbuf.at[i].set(rows)
            counts = counts if n is None else add_counts(counts, n)
        return x, rbuf, counts


class VideoMAEDeepseekV2(nn.Module):
    """VideoMAE encoder (no classifier) -> connector -> DeepSeek-V2 head."""
    cfg: StreamHeadConfig
    dtype: Dtype = jnp.bfloat16
    # the kind of state the round buffer is flushed into
    cache_kind = "latent"

    def setup(self):
        c = self.cfg
        self.video = VideoMAE(c.video, self.dtype, name="video")
        self.connector = Connector(c.head.dim, self.dtype, name="connector")
        self.head = DeepseekV2Stack(c.head, self.dtype, name="head")

    def encode(self, clips):
        """[B, T, H, W, 3] preprocessed clips -> [B, tokens, head dim]."""
        return self.connector(self.video.features(clips))

    def forward(self, x, pool, rbuf, slots, ctx, at=None, cap=0):
        return self.head(x, pool, rbuf, slots, ctx, at, cap)

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
        x = jnp.concatenate(
            [jnp.broadcast_to(self.embed(ids)[None], (b, len(ids), c.head.dim)),
             x], axis=1)
        h, _, _ = self.head(
            x, self._latent(b, 1), self._latent(b, x.shape[1]),
            jnp.arange(b), jnp.zeros((b,), jnp.int32))
        return self.logits(h[:, -1])

    @nn.nowrap
    def _latent(self, rows: int, positions: int, dtype=None):
        c = self.cfg.head
        return mla.empty_latent(c.mla, c.num_layers, rows, positions,
                                dtype or self.dtype)

    # what the engine's ``stream`` step kind and the pool ask of a model
    @nn.nowrap
    def empty_state(self, slots: int):
        """Zeroed state for ``slots`` streams in the model's dtype, by
        kind, and the axis of each kind's buffers that counts the slots."""
        return ({"latent": self._latent(slots, self.cfg.head.max_context)},
                {"latent": 1})

    @nn.nowrap
    def serve_round(self, variables, clips, state, slots, pos0, reset,
                    preprocess=lambda clips: clips):
        return stream_head.serve_round(self, variables, clips, state, slots,
                                       pos0, reset, preprocess)

    @nn.nowrap
    def instruction_state(self, variables):
        """The standing instruction through a fresh state: the cache rows
        every context starts from ({"latent": [blocks, 1, instruction
        length, d]}). A function of the weights alone."""
        ids = jnp.asarray(self.cfg.instruction_ids, jnp.int32)
        zero = jnp.zeros((1,), jnp.int32)
        x = self.apply(variables, ids, method=VideoMAEDeepseekV2.embed)[None]
        _, rbuf, _ = self.apply(
            variables, x, self._latent(1, 0), self._latent(1, len(ids)),
            zero, zero, method=VideoMAEDeepseekV2.forward)
        return {"latent": rbuf}

    # what ``stream_head.serve_round`` asks of a head
    @nn.nowrap
    def empty_counts(self):
        return zero_counts(self.cfg.head.moe)

    @nn.nowrap
    def seed_round(self, variables, state, slots, reset):
        """The latent pool (read and written in place, by slot) with the
        instruction's rows as the first of the slots that reset; nothing
        is carried a batch row."""
        return seed_rows(state["latent"], variables["instruction"]["latent"],
                         slots, reset), ()

    @nn.nowrap
    def round_buffer(self, rows: int, dtype):
        return self._latent(rows, self.cfg.round_positions, dtype)

    @property
    def _cap(self) -> int:
        """Positions that can be context when a round starts (the pool
        resets a stream whose round would pass ``max_context``), up to a
        lane tile, within the pool."""
        c = self.cfg
        return min(-(-(c.head.max_context - c.round_positions) // 128) * 128,
                   c.head.max_context)

    @nn.nowrap
    def prefill(self, variables, x, pool, rows, rbuf, slots, pos0):
        h, rbuf, counts = self.apply(
            variables, x, pool, rbuf, slots, pos0, None, self._cap,
            method=VideoMAEDeepseekV2.forward)
        return h[:, -1], rows, rbuf, counts

    @nn.nowrap
    def decode(self, variables, pool, h, rows, rbuf, slots, pos0, counts):
        """D iterations in the latent space that each commit one position
        a stream."""
        c = self.cfg
        apply = lambda method, *a: self.apply(variables, *a, method=method)  # noqa: E731

        def step(carry, r):
            h, rbuf, counts = carry
            with jax.named_scope("head_decode"):
                tok, top_i, top_p = top_tokens(
                    apply(VideoMAEDeepseekV2.logits, h))
                h, rbuf, m = apply(
                    VideoMAEDeepseekV2.forward,
                    apply(VideoMAEDeepseekV2.embed, tok)[:, None], pool,
                    rbuf, slots, pos0,
                    jnp.full(pos0.shape, c.visual_tokens, pos0.dtype) + r)
            return (h[:, 0], rbuf, add_counts(counts, m)), (tok, top_i, top_p)

        (_, rbuf, counts), (toks, top_i, top_p) = jax.lax.scan(
            step, (h, rbuf, counts),
            jnp.arange(c.decode_steps, dtype=pos0.dtype))
        return {"tokens": toks.T, "top_ids": top_i.transpose(1, 0, 2),
                "top_probs": top_p.transpose(1, 0, 2), "rows": rows,
                "rbuf": rbuf, **moe_outputs(counts),
                "moe_pairs_total": counts["pairs"],
                "moe_group_hits": counts["group_hits"],
                # what the round's prefill attention visited, summed over
                # its chunks and blocks
                **mla.prefill_visits(pos0, c.visual_tokens, self._cap,
                                     c.head.num_layers),
                # and what its decode attention fetched, over its
                # iterations and blocks
                **mla.decode_visits(pool, slots, pos0, self._cap,
                                    c.decode_steps * c.head.num_layers),
                "decode_iters": jnp.asarray(c.decode_steps, jnp.int32)}

    @nn.nowrap
    def commit_round(self, state, pool, rows, rbuf, slots, pos0):
        return {"latent": flush_round(pool, rbuf, slots, pos0,
                                      self.cfg.round_positions,
                                      self.cfg.head.num_layers)}
