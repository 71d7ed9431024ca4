"""Flash attention as a Pallas TPU kernel.

The within-chip counterpart to `parallel/ring_attention.py`: ring attention
shards the *sequence across chips* (K/V ride ICI), this kernel makes each
chip's local attention O(T) in memory — the [Tq, Tk] logits matrix lives
only as a VMEM block, never in HBM. Together they are the long-context
story (SURVEY.md §5.7: clip lengths that outgrow one chip's HBM).

Kernel shape: grid = (B*H, Tq/block_q); each program owns one query block
and scans the full K/V for its (batch, head) — K/V stay VMEM-resident
(fine through ~16k tokens at d=64 bf16; beyond that the sequence is
sharded by the ring anyway). Online softmax carries fp32 running max /
denominator / accumulator, so the result is exact dense attention.

Drop-in `attn_fn` for `models/transformer.Encoder` ([B, T, H, D] in/out,
non-causal, like `default_attention`). The XLA twin used off-TPU is the
same math via `interpret=True`.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

_NEG = -1e30


def _key_mask_logits(logits, base, block, true_t):
    """-inf the logit columns that are right-padding (kpos >= true_t)."""
    rows = logits.shape[0]
    kpos = base + lax.broadcasted_iota(jnp.int32, (rows, block), 1)
    return jnp.where(kpos < true_t, logits, _NEG)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k: int,
                  true_t: int):
    """q [1, bq, D]; k/v [1, Tp, D]; o [1, bq, D]; lse [1, bq, 1]
    (trailing unit dim keeps the block lane-compatible on TPU).
    Tp % block_k == 0. lse (log-sum-exp per q row) feeds the backward."""
    q = q_ref[0].astype(jnp.float32)               # [bq, D]
    bq, d = q.shape
    tp = k_ref.shape[1]
    scale = d ** -0.5

    m0 = jnp.full((bq,), _NEG, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    a0 = jnp.zeros((bq, d), jnp.float32)

    def body(i, carry):
        m, l, acc = carry
        k_blk = k_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                   # [bq, bk]
        logits = _key_mask_logits(logits, i * block_k, block_k, true_t)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[:, None])
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    m, l, acc = lax.fori_loop(0, tp // block_k, body, (m0, l0, a0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse_ref[0] = (m + jnp.log(l_safe))[:, None]


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_k", "true_t", "interpret"),
)
def _flash_call(q, k, v, *, block_q, block_k, true_t, interpret):
    bh, tp, d = q.shape
    kernel = functools.partial(_flash_kernel, block_k=block_k, true_t=true_t)
    return pl.pallas_call(
        kernel,
        grid=(bh, tp // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, tp, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, tp, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tp, d), q.dtype),
            jax.ShapeDtypeStruct((bh, tp, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, *, block_k: int, true_t: int):
    """One q block: dq = sum_k (p * (dO v^T - delta)) k * scale."""
    q = q_ref[0].astype(jnp.float32)                # [bq, D]
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, :, 0]                          # [bq]
    delta = delta_ref[0, :, 0]
    bq, d = q.shape
    tp = k_ref.shape[1]
    scale = d ** -0.5

    def body(i, dq):
        k_blk = k_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        logits = _key_mask_logits(logits, i * block_k, block_k, true_t)
        p = jnp.exp(logits - lse[:, None])          # [bq, bk]
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                           # [bq, bk]
        ds = p * (dp - delta[:, None])
        return dq + jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    dq = lax.fori_loop(0, tp // block_k, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, *, block_q: int, true_t: int):
    """One k block: dv = sum_q p^T dO; dk = sum_q (p*(dp-delta))^T q."""
    k = k_ref[0].astype(jnp.float32)                # [bk, D]
    v = v_ref[0].astype(jnp.float32)
    bk, d = k.shape
    tp = q_ref.shape[1]
    scale = d ** -0.5
    base = pl.program_id(1) * bk                    # this k-block's offset

    def body(i, carry):
        dk, dv = carry
        q_blk = q_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        do_blk = do_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        lse_blk = lse_ref[0, pl.ds(i * block_q, block_q), 0]
        delta_blk = delta_ref[0, pl.ds(i * block_q, block_q), 0]
        logits = jax.lax.dot_general(
            q_blk, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                   # [bq, bk]
        logits = _key_mask_logits(logits, base, bk, true_t)
        p = jnp.exp(logits - lse_blk[:, None])
        dv = dv + jax.lax.dot_general(
            p, do_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                           # [bk, D]
        dp = jax.lax.dot_general(
            do_blk, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                           # [bq, bk]
        ds = p * (dp - delta_blk[:, None])
        dk = dk + jax.lax.dot_general(
            ds, q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        return dk, dv

    dk, dv = lax.fori_loop(
        0, tp // block_q, body,
        (jnp.zeros((bk, d), jnp.float32), jnp.zeros((bk, d), jnp.float32)),
    )
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_q", "block_k", "true_t", "interpret"),
)
def _flash_bwd_call(q, k, v, do, lse, delta, *, block_q, block_k, true_t,
                    interpret):
    bh, tp, d = q.shape
    qspec = pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0))
    qrow = pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0))
    full = pl.BlockSpec((1, tp, d), lambda i, j: (i, 0, 0))
    full_row = pl.BlockSpec((1, tp, 1), lambda i, j: (i, 0, 0))

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, block_k=block_k, true_t=true_t),
        grid=(bh, tp // block_q),
        in_specs=[qspec, full, full, qspec, qrow, qrow],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((bh, tp, d), q.dtype),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    kspec = pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, block_q=block_q, true_t=true_t),
        grid=(bh, tp // block_k),
        in_specs=[full, kspec, kspec, full, full_row, full_row],
        out_specs=[kspec, kspec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tp, d), k.dtype),
            jax.ShapeDtypeStruct((bh, tp, d), v.dtype),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def _pack(x, tp):
    """[B, T, H, D] -> [B*H, Tp, D] with right-padding."""
    b, t, h, d = x.shape
    x = x.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    if tp != t:
        x = jnp.pad(x, ((0, 0), (0, tp - t), (0, 0)))
    return x


def _unpack(x, shape):
    b, t, h, d = shape
    return x[:, :t].reshape(b, h, t, d).transpose(0, 2, 1, 3)


def _padded_t(t, block_q, block_k):
    # Grid and in-kernel loops both index the padded length, so it must be
    # a multiple of BOTH block sizes.
    lcm = math.lcm(block_q, block_k)
    return -(-t // lcm) * lcm


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _flash(block_q: int, block_k: int, interpret: bool, q, k, v):
    return _flash_fwd(block_q, block_k, interpret, q, k, v)[0]


def _flash_fwd(block_q, block_k, interpret, q, k, v):
    t = q.shape[1]
    tp = _padded_t(t, block_q, block_k)
    qp, kp, vp = _pack(q, tp), _pack(k, tp), _pack(v, tp)
    out, lse = _flash_call(
        qp, kp, vp, block_q=block_q, block_k=block_k, true_t=t,
        interpret=interpret,
    )
    return _unpack(out, q.shape), (qp, kp, vp, out, lse, q.shape)


def _flash_bwd(block_q, block_k, interpret, residuals, g):
    # Flash backward: dq/dk/dv Pallas kernels with the forward's saved
    # log-sum-exp — O(T) memory like the forward (no dense logits tensor).
    qp, kp, vp, out, lse, shape = residuals
    t = shape[1]
    tp = qp.shape[1]
    do = _pack(g, tp)
    # delta = rowsum(dO * O); zero on padded rows (do is zero there), so
    # padded queries contribute nothing to dk/dv.
    delta = jnp.sum(
        do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1,
        keepdims=True,
    )
    dq, dk, dv = _flash_bwd_call(
        qp, kp, vp, do, lse, delta,
        block_q=block_q, block_k=block_k, true_t=t, interpret=interpret,
    )
    return _unpack(dq, shape), _unpack(dk, shape), _unpack(dv, shape)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Exact softmax attention, [B, T, H, D] -> [B, T, H, D].

    Arbitrary T (right-padded to the block grid and masked in-kernel) and
    differentiable end to end at O(T) memory: the custom VJP runs dq and
    dk/dv Pallas kernels against the forward's saved log-sum-exp.
    ``interpret`` defaults to True off-TPU so CPU tests run the same
    kernel bodies.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    t = q.shape[1]
    # Mosaic requires block dims in a BlockSpec's second-to-minor position
    # (the backward kernels' q/k tiles) to be multiples of 8.
    block_q = max(8, -(-min(block_q, max(8, t)) // 8) * 8)
    block_k = max(8, -(-min(block_k, max(8, t)) // 8) * 8)
    return _flash(block_q, block_k, interpret, q, k, v)
