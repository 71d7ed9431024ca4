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
RMS-normed per head, rotary positions (rotate-half), causal.
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
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from . import stream_head
from .common import Dtype
from .stream_head import (Connector, RmsNorm, SwiGlu, _kernel,  # noqa: F401
                          cast_for_serving, prepare_for_serving, top_tokens)
from .transformer import TopKMoeConfig, TopKMoeMlp
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


def rope(x, pos, theta: float):
    """Rotate-half rotary embedding: x [B, T, H, hd], pos [B, T]."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[..., None] * inv             # [B, T, hd/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, :, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, :, None]
    x = x.astype(jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def prefill_attention(q, pool_k, pool_v, new_k, new_v, slots, pos0):
    """A round's first T positions: q [B, T, H, hd] against each row's slot
    of the pool (``pool_k`` / ``pool_v`` [slots, KV, hd, S]: the positions
    before ``pos0`` [B] are the stream's context; the rest is stale or
    unwritten, and masked) and, causally, against the T new keys and values
    themselves (``new_k`` / ``new_v`` [B, KV, hd, T]). One stream at a
    time, so the [T, S + T] scores of one are all that is held."""
    b, t, h, hd = q.shape
    kv = new_k.shape[1]
    g = h // kv
    causal = jnp.tril(jnp.ones((t, t), bool))

    def one(args):
        # a leading axis of one stream: the batched products below are the
        # form the TPU compiler lays out well (written without the axis,
        # this loop took twice as long on the chip)
        qc, sc, pc, kn, vn = (a[None] for a in args)
        kc = jnp.concatenate(
            [jnp.take(pool_k, sc, axis=0, mode="clip"), kn], axis=-1)
        vc = jnp.concatenate(
            [jnp.take(pool_v, sc, axis=0, mode="clip"), vn], axis=-1)
        s = jnp.einsum("btkgd,bkds->bkgts", qc.reshape(1, t, kv, g, hd),
                       kc).astype(jnp.float32) * hd ** -0.5
        old = jnp.arange(pool_k.shape[-1])[None, :] < pc[:, None]   # [1, S]
        mask = jnp.concatenate(
            [jnp.broadcast_to(old[:, None], (1, t, old.shape[1])),
             causal[None]], axis=-1)
        s = jnp.where(mask[:, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(qc.dtype)
        return jnp.einsum("bkgts,bkds->btkgd", p, vc).reshape(t, h * hd)

    return jax.lax.map(one, (q, slots, pos0, new_k, new_v))


def decode_attention(q, pool_k, pool_v, round_k, round_v, slots, pos0, r):
    """One new position a stream: q [B, H, hd] against the pool, read IN
    PLACE in slot order (the queries are carried to their slots and the
    partial results back by a one-hot product: [B, slots] against a few
    KB a row, where gathering the rows would copy the cache), and against
    this round's own keys and values ``round_k`` / ``round_v`` [B, KV, hd,
    R], of which the first ``r + 1`` are written. The two partial softmaxes
    are merged by their maxima and sums."""
    b, h, hd = q.shape
    c, kv = pool_k.shape[0], pool_k.shape[1]
    g = h // kv
    scale = hd ** -0.5
    qg = q.reshape(b, kv, g, hd)
    onehot = (slots[:, None] == jnp.arange(c)[None]).astype(jnp.float32)

    def partial(query, keys, values, mask):
        s = jnp.einsum("bkgd,bkds->bkgs", query, keys).astype(
            jnp.float32) * scale
        s = jnp.where(mask[:, None, None], s, -1e30)
        m = jnp.max(s, axis=-1)
        e = jnp.exp(s - m[..., None])
        o = jnp.einsum("bkgs,bkds->bkgd", e.astype(values.dtype), values)
        return m, jnp.sum(e, axis=-1), o.astype(jnp.float32)

    # the pool's part, in slot order
    q_slot = jnp.einsum("bc,bkgd->ckgd", onehot, qg.astype(jnp.float32))
    pos_slot = jnp.einsum("bc,b->c", onehot, pos0.astype(jnp.float32))
    old = jnp.arange(pool_k.shape[-1])[None] < pos_slot[:, None]   # [c, S]
    m_a, l_a, o_a = partial(q_slot.astype(q.dtype), pool_k, pool_v, old)
    m_a, l_a, o_a = (jnp.einsum("bc,c...->b...", onehot, x)
                     for x in (m_a, l_a, o_a))
    # this round's part, in batch order
    new = jnp.arange(round_k.shape[-1])[None] <= r
    m_b, l_b, o_b = partial(qg, round_k, round_v,
                            jnp.broadcast_to(new, (b, new.shape[1])))
    m = jnp.maximum(m_a, m_b)
    w_a, w_b = jnp.exp(m_a - m), jnp.exp(m_b - m)
    o = (o_a * w_a[..., None] + o_b * w_b[..., None]) \
        / (l_a * w_a + l_b * w_b)[..., None]
    return o.astype(q.dtype).reshape(b, h * hd)


class CachedAttention(nn.Module):
    """GQA with per-head RMS-normed q and k and rotary positions, over a
    stream's context in the key-value pool (read only: ``pool`` = (keys,
    values), each [attention layers, slots, KV, hd, S], positions
    minor-most, the layout the TPU compiler gives a cache that decode
    steps read) and over this round's own positions, kept in a round
    buffer ``rbuf`` = (keys, values), each [B, KV, hd, R], and written to
    the pool once, when the round is over (:func:`flush_round`): inside
    the round's loops the pool is not written, so it is never copied."""
    cfg: Lfm2Config
    layer: int = 0
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, h, pool, rbuf, slots, pos0, r0):
        c, d, hd = self.cfg, self.cfg.dim, self.cfg.head_dim
        b, t, _ = h.shape
        kvd = c.num_kv_heads * hd
        wq = _kernel(self, "q_proj", (d, d), ("embed", "qkv"))
        wk = _kernel(self, "k_proj", (d, kvd), ("embed", "qkv"))
        wv = _kernel(self, "v_proj", (d, kvd), ("embed", "qkv"))
        wo = _kernel(self, "out_proj", (d, d), ("qkv", "embed"))
        with jax.named_scope("head_attn"):
            pos = pos0[:, None] + r0 + jnp.arange(t, dtype=pos0.dtype)[None]
            q = (h @ wq.astype(self.dtype)).reshape(b, t, c.num_heads, hd)
            k = (h @ wk.astype(self.dtype)).reshape(b, t, c.num_kv_heads, hd)
            v = (h @ wv.astype(self.dtype)).reshape(b, t, c.num_kv_heads, hd)
            q = rope(RmsNorm(c.norm_eps, jnp.float32, name="q_norm")(q),
                     pos, c.rope_theta).astype(self.dtype)
            k = rope(RmsNorm(c.norm_eps, jnp.float32, name="k_norm")(k),
                     pos, c.rope_theta).astype(self.dtype)
            k, v = (a.transpose(0, 2, 3, 1).astype(rbuf[0].dtype)
                    for a in (k, v))                    # [B, KV, hd, T]
            rk = jax.lax.dynamic_update_slice_in_dim(rbuf[0], k, r0, axis=3)
            rv = jax.lax.dynamic_update_slice_in_dim(rbuf[1], v, r0, axis=3)
            keys, values = pool[0][self.layer], pool[1][self.layer]
            if t == 1:
                o = decode_attention(q[:, 0], keys, values, rk, rv, slots,
                                     pos0, r0)[:, None]
            else:
                # a prefill starts its round: the T new positions are all
                # of the round that is written
                o = prefill_attention(q, keys, values, k, v, slots, pos0)
            return o @ wo.astype(self.dtype), (rk, rv)


def flush_round(pool, rbuf, slots, pos0):
    """The round's keys and values (``rbuf`` = (keys, values), each
    [attention layers, B, KV, hd, R]) into each row's slot of the pool at
    its ``pos0``, in place: a loop over the rows, one guarded slice update
    a row (all layers at once; a batched scatter with this window is
    refused by the TPU compiler). A row whose slot is past the pool (a
    padded batch row) writes nothing."""
    last = pool[0].shape[1] - 1

    def row(i, pool):
        out = []
        for dst, src in zip(pool, rbuf):
            new = jax.lax.dynamic_slice_in_dim(src, i, 1, axis=1).astype(
                dst.dtype)
            at = (0, jnp.minimum(slots[i], last), 0, 0, pos0[i])
            old = jax.lax.dynamic_slice(dst, at, new.shape)
            out.append(jax.lax.dynamic_update_slice(
                dst, jnp.where(slots[i] <= last, new, old), at))
        return tuple(out)

    return jax.lax.fori_loop(0, rbuf[0].shape[1], row, tuple(pool))


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
        load) with ``load`` [held] the routed pairs each held expert took,
        summed over the layers."""
        c = self.cfg
        b, t, d = x.shape
        convs, ci, ai = [], 0, 0
        rk, rv = rbuf
        load = jnp.zeros((len(c.moe.held),), jnp.int32)
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
                load = load + n
        if convs:
            conv = jnp.stack(convs, axis=1).astype(conv.dtype)
        return x, conv, (rk, rv), load


class VideoMAELfm2(nn.Module):
    """VideoMAE encoder (no classifier) -> connector -> LFM2 head."""
    cfg: StreamHeadConfig
    dtype: Dtype = jnp.bfloat16

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
        return jnp.zeros((len(self.cfg.head.moe.held),), jnp.int32)

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
        h, conv, rbuf, load = self.apply(
            variables, x, conv, kv, rbuf, slots, pos0,
            method=VideoMAELfm2.forward)
        return h[:, -1], conv, rbuf, load

    @nn.nowrap
    def decode(self, variables, kv, h, conv, rbuf, slots, pos0, load):
        """D steps that each commit one position a stream."""
        c = self.cfg
        apply = lambda method, *a: self.apply(variables, *a, method=method)  # noqa: E731

        def step(carry, r):
            h, conv, rbuf, load = carry
            with jax.named_scope("head_decode"):
                tok, top_i, top_p = top_tokens(
                    apply(VideoMAELfm2.logits, h))
                h, conv, rbuf, m = apply(
                    VideoMAELfm2.forward,
                    apply(VideoMAELfm2.embed, tok)[:, None],
                    conv, kv, rbuf, slots, pos0, c.visual_tokens + r)
            return (h[:, 0], conv, rbuf, load + m), (tok, top_i, top_p)

        (_, conv, rbuf, load), (toks, top_i, top_p) = jax.lax.scan(
            step, (h, conv, rbuf, load),
            jnp.arange(c.decode_steps, dtype=pos0.dtype))
        return {"tokens": toks.T, "top_ids": top_i.transpose(1, 0, 2),
                "top_probs": top_p.transpose(1, 0, 2), "rows": conv,
                "rbuf": rbuf, "moe_load": load}

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
    return tuple(jnp.zeros((cfg.attn_layers, rows, cfg.num_kv_heads,
                            cfg.head_dim, positions), dtype) for _ in "kv")


def empty_state(cfg: Lfm2Config, slots: int, context=None,
                dtype=jnp.bfloat16):
    """Zeroed (conv, kv) for ``slots`` streams of ``context`` positions
    (None: the configuration's ``max_context``)."""
    context = cfg.max_context if context is None else context
    conv = jnp.zeros((slots, cfg.conv_layers, cfg.conv_l_cache - 1, cfg.dim),
                     dtype)
    kv = tuple(jnp.zeros((cfg.attn_layers, slots, cfg.num_kv_heads,
                          cfg.head_dim, context), dtype) for _ in "kv")
    return conv, kv
