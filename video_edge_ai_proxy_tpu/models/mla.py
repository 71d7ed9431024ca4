"""Multi-head latent attention (MLA) over a per-stream latent cache, as the
streaming heads that publish it run it (``models/xing4.py``: ``xing4_0``;
``models/deepseek_v2.py``: ``deepseek_v2``).

The reference ships frames to external clients and has no model at all
(`/root/reference/README.md:5-27`); the heads are ROADMAP R9's. What is
here is one attention and its cache, parametrised by :class:`MlaConfig`
alone (no head's own config reaches it):

- ``c_q = RMS(h W_qa)``, ``[q_n | q_r] = c_q W_qb`` a head; ``[c_kv | k_r]
  = h W_kva``, ``ĉ = RMS(c_kv)``; **a position's cache row is ``[ĉ |
  rope(k_r)]``**, ``kv_lora_rank + qk_rope_head_dim`` numbers (576 at the
  published sizes), ``k_r`` shared by all heads; ``[k_n | v] = ĉ W_kvb`` a
  head; yarn rope on the rope part only, cos and sin scaled by ``mscale /
  mscale_all_dim``; scores scaled by ``(d_n + d_r)^-½ ·
  yarn_mscale(factor, mscale_all_dim)²``.
- Two paths over the one cache: :func:`mla_prefill_attention` up-projects
  the cached rows it attends to per-head keys and values (a key block at
  a time inside one kernel a chunk, never in memory);
  :func:`mla_decode_attention` folds ``W_kvb``'s key half
  into the query and its value half into the output and attends over the
  latent rows as they lie.
- **Told which heads it holds** (``heads_held``: their ids among the
  ``num_heads``; empty = all): ``W_qb``, ``W_kvb`` and ``W_o`` are the
  held heads' slices, stacked in that order, and the output is those
  heads' partial sum ``Σ_held o_h W_o[h]``. Heads that live on another
  holder add nothing here: the caller sums the holders' partial outputs,
  as it does ``transformer.TopKMoeMlp``'s (on one chip of a deployment,
  nothing stands in for the others). ``W_qa``, ``W_kva``, the two norms
  and the cache row are every holder's alike: the row has no heads.

A round's rows live in a round buffer and reach the pool once, when the
round is over (:func:`flush_round`); the standing instruction's rows are
laid into the slots that reset (:func:`seed_rows`).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops.flash_attention import (
    latent_decode_attention, latent_decode_depths, latent_decode_visits,
    latent_prefill_attention, latent_prefill_visits)
from .common import Dtype
from .stream_head import RmsNorm, _kernel


@dataclass(frozen=True)
class MlaConfig:
    dim: int
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    heads_held: Tuple[int, ...] = ()
    norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 64.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0

    @property
    def held(self) -> tuple:
        return tuple(self.heads_held) or tuple(range(self.num_heads))

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def row_dim(self) -> int:
        """A cache row's width in memory: the latent row up to a lane
        tile (576 -> 640: the TPU pads a 576-wide minor axis to 640 anyway,
        and with an axis it would pad the compiler keeps the whole pool in
        a second layout beside the first)."""
        return -(-self.latent_dim // 128) * 128


# -- yarn rope -----------------------------------------------------------------

def yarn_mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(cfg: MlaConfig) -> np.ndarray:
    """[d_r / 2] inverse frequencies: extrapolated (as published) below the
    low correction dimension, interpolated (÷ factor) above the high one,
    blended by the linear ramp between."""
    d = cfg.qk_rope_head_dim
    extra = cfg.rope_theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    inter = extra / cfg.rope_factor

    def correction(rotations):
        return (d * math.log(cfg.rope_original_max
                             / (rotations * 2 * math.pi))
                / (2 * math.log(cfg.rope_theta)))

    low = max(math.floor(correction(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction(cfg.rope_beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def rope(x, pos, cfg: MlaConfig):
    """Rotate-half yarn rope: x [B, T, (H,) d_r], pos [B, T]."""
    ang = pos.astype(jnp.float32)[..., None] * yarn_inv_freq(cfg)
    m = (yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
         / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1) * m
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1) * m
    if x.ndim == 4:
        cos, sin = cos[:, :, None], sin[:, :, None]
    x = x.astype(jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def softmax_scale(cfg: MlaConfig) -> float:
    return ((cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
            * yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim) ** 2)


# -- the two attention paths over the one latent cache ------------------------

def mla_prefill_attention(q, new, w_uk, w_uv, pool, slots, ctx, scale, cap,
                          block=None):
    """A round's first T positions: ``q`` [B, T, H, d_n + d_r] (the rope
    part roped) against each row's slot of the pool (``pool`` [slots, S,
    row], or the whole [blocks, slots, S, row] with ``block`` naming this
    attention's: the positions before ``ctx`` [B] are the stream's
    context; the rest is stale or unwritten, and never reaches a result;
    only the first ``cap`` can be context when a round starts) and,
    causally, against the T new rows themselves (``new`` [B, T, row]).
    One kernel a chunk (``ops/flash_attention.py``
    :func:`latent_prefill_attention`): a (stream, head) a program, the
    stream's slot read where it lies, whole rows; a key block of cached
    rows is up-projected to that head's keys and values (``w_uk`` [r, H,
    d_n], ``w_uv`` [r, H, d_v]) on the chip and scored under one running
    softmax with the new rows' blocks, so no per-head key, value or score
    is ever in memory; the blocks past ``ctx`` are not visited, and a new
    rows' block only by the queries that can see it. The keys' rope part
    is the rows' own tail ``rows[:, r:]``, shared by the heads: the query
    is laid out [q_n | q_r | 0] against [k_n | that tail], one query a
    column (the kernel holds its scores keys-major). Returns [B, T, H *
    d_v]."""
    b, t, h, _ = q.shape
    r, dn = w_uk.shape[0], w_uk.shape[-1]
    tail = new.shape[-1] - r - (q.shape[-1] - dn)
    q = jnp.pad(q, ((0, 0),) * 3 + ((0, tail),)).transpose(0, 2, 3, 1)
    if block is None:
        pool, block = pool[None], 0
    o = latent_prefill_attention(
        q, new, pool, w_uk.transpose(1, 0, 2), w_uv.transpose(1, 2, 0),
        slots, ctx, block, scale=scale, rank=r, cap=cap)
    return o.transpose(0, 3, 1, 2).reshape(b, t, -1)


def prefill_visits(ctx, t: int, cap: int, blocks: int):
    """{"attn_blocks_live", "attn_blocks_dense"}: the tiles (a key block
    against a lane tile of 128 queries) :func:`mla_prefill_attention`
    visits in ``blocks`` attentions over a batch whose contexts hold
    ``ctx`` [B] positions, by the kernel's own rule, and what passes over
    all ``cap + t`` keys would visit."""
    live, dense = latent_prefill_visits(ctx, t, cap)
    return {"attn_blocks_live": live * blocks,
            "attn_blocks_dense": dense * blocks}


def mla_decode_attention(q_n, q_r, w_uk, w_uv, pool, rbuf, slots, ctx, upto,
                         scale, block=None):
    """A few new positions a stream, in the latent space: ``q_n`` [B, T, H,
    d_n] is carried through ``w_uk`` to the cache's own width (``q_n W_ukᵀ``
    [r] a head), joined with ``q_r``, and attends over the 576-wide rows as
    they lie: its context in the pool (``pool`` [slots, S, row], or the
    whole [blocks, slots, S, row] with ``block`` naming this attention's),
    read where it lies by one kernel (``ops/flash_attention.py``
    :func:`latent_decode_attention`: a stream a program, the key blocks its
    context reaches and no others), and this round's own rows ``rbuf`` [B,
    R, r + d_r], of which query t of row b sees those up to ``upto[b, t]``.
    The two partial softmaxes are merged by their maxima and sums; the
    output leaves the latent space through ``w_uv``."""
    b, t, h, _ = q_n.shape
    r = w_uk.shape[0]
    width = pool.shape[-1]          # the rows' width in memory, zero-padded
    q = jnp.concatenate(
        [jnp.einsum("bthd,rhd->bthr", q_n, w_uk), q_r,
         jnp.zeros(q_r.shape[:-1] + (width - r - q_r.shape[-1],),
                   q_r.dtype)], axis=-1)
    q = q.reshape(b, t * h, width)
    if block is None:
        pool, block = pool[None], 0
    m_a, l_a, o_a = latent_decode_attention(q, pool, slots, ctx, block,
                                            scale=scale, rank=r)
    # this round's part
    s = jnp.einsum("bqd,bsd->bqs", q, rbuf,
                   preferred_element_type=jnp.float32) * scale
    new = jnp.arange(rbuf.shape[1])[None, None] <= upto[:, :, None]
    s = jnp.where(jnp.repeat(new, h, axis=1), s, -1e30)     # [B, T*H, R]
    m_b = jnp.max(s, axis=-1)
    e = jnp.exp(s - m_b[..., None])
    l_b = jnp.sum(e, axis=-1)
    # the rows whole (their rope part's output is dropped after): no slice
    # of the buffer is made
    o_b = jnp.einsum("bqs,bsd->bqd", e.astype(rbuf.dtype), rbuf,
                     preferred_element_type=jnp.float32)[..., :r]
    m = jnp.maximum(m_a, m_b)
    w_a, w_b = jnp.exp(m_a - m), jnp.exp(m_b - m)
    o = (o_a * w_a[..., None] + o_b * w_b[..., None]) \
        / (l_a * w_a + l_b * w_b)[..., None]
    o = jnp.einsum("bthr,rhd->bthd",
                   o.astype(q_n.dtype).reshape(b, t, h, r), w_uv)
    return o.reshape(b, t, -1)


def decode_visits(pool, slots, ctx, cap: int, calls):
    """{"decode_blocks_live", "decode_blocks_dense"}: the key blocks
    :func:`mla_decode_attention` fetches from the pool (``pool`` [blocks,
    slots, S, row]) in ``calls`` attentions over a batch whose contexts
    hold ``ctx`` [B] positions in slots ``slots`` [B], by the kernel's own
    rule, and what passes over all ``cap`` cacheable positions would
    fetch."""
    live, dense = latent_decode_visits(
        latent_decode_depths(ctx, slots, pool.shape[1], pool.shape[-2]),
        cap, pool.shape[-2])
    return {"decode_blocks_live": live * calls,
            "decode_blocks_dense": dense * calls}


def write_rows(rbuf, new, at):
    """``new`` [B, T, d] into ``rbuf`` [B, R, d] at each row's own
    ``at[b]``, by selection (a scatter with a window a row is refused by
    the TPU compiler inside a loop)."""
    idx = jnp.arange(rbuf.shape[1])[None] - at[:, None]             # [B, R]
    for t in range(new.shape[1]):
        rbuf = jnp.where((idx == t)[..., None], new[:, t:t + 1], rbuf)
    return rbuf


class MlaAttention(nn.Module):
    """Latent attention over a stream's context in the latent pool (read
    only: ``pool`` [blocks, slots, S, row], of which this attention's is
    ``pool[block]``; a pool of one block may come as [slots, S, row]) and
    this round's own rows in the round buffer ``rbuf`` [B, R, row],
    written to the pool once, when the round is over
    (:func:`flush_round`). ``q_b``, ``kv_b`` and ``o``
    hold the ``cfg.held`` heads' slices and the output is their partial
    sum (module docstring)."""
    cfg: MlaConfig
    dtype: Dtype = jnp.bfloat16

    def setup(self):
        c, d, h = self.cfg, self.cfg.dim, len(self.cfg.held)
        qk = c.qk_nope_head_dim + c.qk_rope_head_dim
        self.q_a = _kernel(self, "q_a", (d, c.q_lora_rank), ("embed", "qkv"))
        self.q_norm = RmsNorm(c.norm_eps, self.dtype, name="q_norm")
        self.q_b = _kernel(self, "q_b", (c.q_lora_rank, h * qk),
                           ("embed", "qkv"))
        self.kv_a = _kernel(self, "kv_a", (d, c.latent_dim),
                            ("embed", "qkv"))
        self.kv_norm = RmsNorm(c.norm_eps, self.dtype, name="kv_norm")
        self.kv_b = _kernel(
            self, "kv_b",
            (c.kv_lora_rank, h * (c.qk_nope_head_dim + c.v_head_dim)),
            ("embed", "qkv"))
        self.o = _kernel(self, "o", (h * c.v_head_dim, d), ("qkv", "embed"))

    def latent(self, h, pos):
        """[B, T, C] -> the positions' cache rows [B, T, r + d_r]."""
        c, r = self.cfg, self.cfg.kv_lora_rank
        ckv = h @ self.kv_a.astype(self.dtype)
        return jnp.concatenate(
            [self.kv_norm(ckv[..., :r]),
             rope(ckv[..., r:], pos, c).astype(self.dtype),
             jnp.zeros(h.shape[:-1] + (c.row_dim - c.latent_dim,),
                       self.dtype)], axis=-1)

    def __call__(self, h, pool, rbuf, slots, ctx, at, cap, block=None):
        """``at`` None: a prefill, whose T positions start the round's
        buffer; else [B], where each row's T decode positions go."""
        # (the whole layer is ``head_attn``, as the first head's: the
        # projections and the rotation lie outside the two inner scopes)
        with jax.named_scope("head_attn"):
            c = self.cfg
            b, t, _ = h.shape
            heads = len(c.held)
            dn, dr, dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
            pos = ctx[:, None] + jnp.arange(t, dtype=ctx.dtype)[None]
            if at is not None:
                pos = pos + at[:, None]
            new = self.latent(h, pos).astype(rbuf.dtype)
            q = self.q_norm(h @ self.q_a.astype(self.dtype)) \
                @ self.q_b.astype(self.dtype)
            q = q.reshape(b, t, heads, dn + dr)
            q_n = q[..., :dn]
            q_r = rope(q[..., dn:], pos, c).astype(self.dtype)
            q = jnp.concatenate([q_n, q_r], axis=-1)
            w = self.kv_b.astype(self.dtype).reshape(
                c.kv_lora_rank, heads, dn + dv)
            w_uk, w_uv = w[..., :dn], w[..., dn:]
            if at is None:
                with jax.named_scope("mla_prefill"):
                    rbuf = jax.lax.dynamic_update_slice_in_dim(
                        rbuf, new, 0, axis=1)
                    o = mla_prefill_attention(
                        q, new, w_uk, w_uv, pool, slots, ctx,
                        softmax_scale(c), min(cap, pool.shape[-2]), block)
            else:
                with jax.named_scope("mla_decode"):
                    rbuf = write_rows(rbuf, new, at)
                    upto = at[:, None] + jnp.arange(t, dtype=at.dtype)[None]
                    o = mla_decode_attention(
                        q_n, q_r, w_uk, w_uv, pool, rbuf, slots, ctx, upto,
                        softmax_scale(c), block)
            return o @ self.o.astype(self.dtype), rbuf


# -- the cache: a pool of slots, a round's buffer -----------------------------

def empty_latent(cfg: MlaConfig, blocks: int, rows: int, positions: int,
                 dtype=jnp.bfloat16):
    """Zeroed latent rows [blocks, rows, positions, row]: the pool (rows =
    slots), a round buffer (rows = the batch's)."""
    return jnp.zeros((blocks, rows, positions, cfg.row_dim), dtype)


def seed_rows(pool, rows, slots, reset, keep=None):
    """``rows`` [blocks, 1, n, d] (the standing instruction's) as the first
    rows of the slots that reset, in place: a guarded slice update a row,
    as the flush makes them (one update of all slots at once has the TPU
    compiler copy the whole pool into another layout and back). ``keep``
    (broadcastable to ``rows``): which of them are written at all. A row
    whose slot is past the pool (a padded batch row) writes nothing."""
    last = pool.shape[1] - 1
    rows = rows.astype(pool.dtype)

    def row(i, pool):
        at = (0, jnp.minimum(slots[i], last), 0, 0)
        old = jax.lax.dynamic_slice(pool, at, rows.shape)
        write = reset[i] & (slots[i] <= last)
        if keep is not None:
            write = write & keep
        return jax.lax.dynamic_update_slice(
            pool, jnp.where(write, rows, old), at)

    return jax.lax.fori_loop(0, slots.shape[0], row, pool)


def flush_round(pool, rbuf, slots, pos0, keep, main_blocks):
    """The round's first ``keep`` rows (``rbuf`` [blocks, B, R, d]) into
    each row's slot of the pool, in place: the first ``main_blocks``
    blocks' at ``pos0``, the rest (a prediction module's, whose cache
    trails by one) a position before. A loop over the rows, guarded slice
    updates (``models/lfm2.py`` ``flush_round``). A row whose slot is past
    the pool (a padded batch row) writes nothing."""
    blocks, _, _, d = rbuf.shape
    last = pool.shape[1] - 1
    parts = [(lo, hi, back) for lo, hi, back in
             ((0, main_blocks, 0), (main_blocks, blocks, 1)) if hi > lo]

    def row(i, pool):
        for lo, hi, back in parts:
            new = jax.lax.dynamic_slice(
                rbuf, (lo, i, 0, 0), (hi - lo, 1, keep, d)).astype(pool.dtype)
            at = (lo, jnp.minimum(slots[i], last), pos0[i] - back, 0)
            old = jax.lax.dynamic_slice(pool, at, new.shape)
            pool = jax.lax.dynamic_update_slice(
                pool, jnp.where(slots[i] <= last, new, old), at)
        return pool

    return jax.lax.fori_loop(0, rbuf.shape[1], row, pool)
