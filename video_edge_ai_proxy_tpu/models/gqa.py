"""Grouped-query attention over a stream's key-value pool, shared by the
streaming heads that cache keys and values (``models/lfm2.py``,
``models/falcon_h1.py``), as ``models/mla.py`` is by the latent heads.

The attention: ``q = h W_q``, ``k = h W_k`` (times ``key_multiplier``
where the configuration has one), ``v = h W_v``; q and k RMS-normed per
head where ``qk_norm``; rotary positions (rotate-half) on q and k; causal
softmax at ``head_dim^-1/2`` over the stream's context in the pool and the
round's own positions; ``W_o``. The query width is ``num_heads x
head_dim``, which need not be the model width (a chip that holds some of
the heads holds their slices of ``W_q`` and ``W_o``).

Two paths over the one pool: a prefill's T positions are one Pallas kernel
a chunk (``ops/flash_attention.py`` :func:`gqa_prefill_attention`: a
program a stream and the query heads of one key-value head, the stream's
slot read where it lies, the key blocks past its context not visited);
a decode step's one position is :func:`decode_attention`.

A configuration is anything with the fields of :class:`GqaConfig`
(``Lfm2Config`` has them). What differs between the heads is named there:
the per-head norm, the key scale and the heads held.
"""

from __future__ import annotations

from dataclasses import dataclass

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.flash_attention import gqa_prefill_attention, gqa_prefill_visits
from .common import Dtype
from .stream_head import RmsNorm, _kernel


@dataclass(frozen=True)
class GqaConfig:
    dim: int
    num_heads: int                    # query heads (those held)
    num_kv_heads: int                 # key-value heads (those held)
    head_dim: int
    norm_eps: float
    rope_theta: float
    qk_norm: bool = True
    key_multiplier: float = 1.0


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


def prefill_cap(context: int, rows: int) -> int:
    """Positions that can be context when a round of ``rows`` positions
    starts in a pool of ``context`` (the pool resets a stream whose round
    would pass it), up to a lane tile, within the pool."""
    return max(min(-(-(context - rows) // 128) * 128, context), 0)


def prefill_visits(pos0, t: int, g: int, cap: int, layers: int):
    """{"attn_blocks_live", "attn_blocks_dense"}: the tiles (a key block
    against a lane tile of 128 query columns) the prefill's kernel visits
    in ``layers`` attentions over a batch whose contexts hold ``pos0`` [B]
    positions, by the kernel's own rule, and what passes over all ``cap``
    cacheable and T new positions would visit (a key-value head's; every
    head's alike)."""
    live, dense = gqa_prefill_visits(pos0, t, g, cap)
    return {"attn_blocks_live": live * layers,
            "attn_blocks_dense": dense * layers}


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
    """GQA with rotary positions (and, where ``qk_norm``, per-head
    RMS-normed q and k), over a stream's context in the key-value pool
    (read only: ``pool`` = (keys, values), each [attention layers, slots,
    KV, hd, S], positions minor-most, the layout the TPU compiler gives a
    cache that decode steps read) and over this round's own positions, kept
    in a round buffer ``rbuf`` = (keys, values), each [B, KV, hd, R], and
    written to the pool once, when the round is over (:func:`flush_round`):
    inside the round's loops the pool is not written, so it is never
    copied."""
    cfg: GqaConfig
    layer: int = 0
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, h, pool, rbuf, slots, pos0, r0):
        c, d, hd = self.cfg, self.cfg.dim, self.cfg.head_dim
        b, t, _ = h.shape
        qd, kvd = c.num_heads * hd, c.num_kv_heads * hd
        wq = _kernel(self, "q_proj", (d, qd), ("embed", "qkv"))
        wk = _kernel(self, "k_proj", (d, kvd), ("embed", "qkv"))
        wv = _kernel(self, "v_proj", (d, kvd), ("embed", "qkv"))
        wo = _kernel(self, "out_proj", (qd, d), ("qkv", "embed"))
        with jax.named_scope("head_attn"):
            pos = pos0[:, None] + r0 + jnp.arange(t, dtype=pos0.dtype)[None]
            q = (h @ wq.astype(self.dtype)).reshape(b, t, c.num_heads, hd)
            k = (h @ wk.astype(self.dtype)).reshape(b, t, c.num_kv_heads, hd)
            v = (h @ wv.astype(self.dtype)).reshape(b, t, c.num_kv_heads, hd)
            if c.key_multiplier != 1.0:
                k = k * c.key_multiplier
            norm = (lambda x, name: RmsNorm(  # noqa: E731
                c.norm_eps, jnp.float32, name=name)(x)) if c.qk_norm \
                else (lambda x, name: x)
            q = rope(norm(q, "q_norm"), pos, c.rope_theta).astype(self.dtype)
            k = rope(norm(k, "k_norm"), pos, c.rope_theta).astype(self.dtype)
            k, v = (a.transpose(0, 2, 3, 1).astype(rbuf[0].dtype)
                    for a in (k, v))                    # [B, KV, hd, T]
            rk = jax.lax.dynamic_update_slice_in_dim(rbuf[0], k, r0, axis=3)
            rv = jax.lax.dynamic_update_slice_in_dim(rbuf[1], v, r0, axis=3)
            if t == 1:
                o = decode_attention(q[:, 0], pool[0][self.layer],
                                     pool[1][self.layer], rk, rv, slots,
                                     pos0, r0)[:, None]
            else:
                # a prefill starts its round: the T new positions are all
                # of the round that is written
                o = gqa_prefill_attention(
                    q, k, v, *pool, self.layer, slots, pos0,
                    cap=prefill_cap(pool[0].shape[-1], rbuf[0].shape[-1]))
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


def round_buffer(cfg, layers: int, rows: int, positions: int,
                 dtype=jnp.bfloat16):
    """Zeroed (keys, values) of one round of ``rows`` streams."""
    return tuple(jnp.zeros((layers, rows, cfg.num_kv_heads, cfg.head_dim,
                            positions), dtype) for _ in "kv")


def empty_pool(cfg, layers: int, slots: int, context: int,
               dtype=jnp.bfloat16):
    """Zeroed (keys, values) for ``slots`` streams of ``context``
    positions."""
    return tuple(jnp.zeros((layers, slots, cfg.num_kv_heads, cfg.head_dim,
                            context), dtype) for _ in "kv")
