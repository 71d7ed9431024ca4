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

Beside it, :func:`latent_prefill_attention`: the same running softmax over
a latent cache (`models/mla.py`): a stream's cached rows read from its slot
of the pool as they lie, up-projected to one head's keys and values a key
block at a time inside the kernel, the stream's depth a prefetched scalar
that decides how many key blocks a program visits.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _softmax_block(m, l, logits, keys=-1):
    """One key block's scores into a running softmax's statistics, all
    float32: ``m``, ``l`` the running maximum and sum, ``logits`` the
    block's scores (masked ones at ``_NEG``), ``keys`` their key axis (-1,
    queries down the rows: m, l [bq, 1]; 0, keys down the rows: m, l [1,
    bq], so that the reductions run down the sublanes and the statistics
    lie along the lanes). Returns (m, l, alpha, p): the statistics after
    the block, the factor the accumulator so far shrinks by, the block's
    weights to add ``p v`` with."""
    m_new = jnp.maximum(m, logits.max(axis=keys, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(logits - m_new)
    return m_new, l * alpha + p.sum(axis=keys, keepdims=True), alpha, p


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

    m0 = jnp.full((bq, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    a0 = jnp.zeros((bq, d), jnp.float32)

    def body(i, carry):
        k_blk = k_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                   # [bq, bk]
        logits = _key_mask_logits(logits, i * block_k, block_k, true_t)
        m, l, acc = carry
        m, l, alpha, p = _softmax_block(m, l, logits)
        return m, l, acc * alpha + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    m, l, acc = lax.fori_loop(0, tp // block_k, body, (m0, l0, a0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l_safe)


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


# -- prefill over a latent cache (models/mla.py) ------------------------------

_LANES = 128


def _block(n: int, limit: int) -> int:
    """The largest divisor of ``n`` up to ``limit`` that is a whole number
    of lane tiles (128), else of sublane tiles (16: a bfloat16 tile's
    rows), else ``n`` itself as one block."""
    for tile in (_LANES, 16):
        for d in range(min(limit, n) // tile * tile, 0, -tile):
            if n % d == 0:
                return d
    return n


def latent_prefill_blocks(t: int, cap: int) -> tuple:
    """(block_t, block_k) from the shapes alone: the key blocks of the T
    new positions' rows and of the ``cap`` cached positions."""
    return _block(t, _LANES), _block(cap, 2 * _LANES) if cap else 0


def _first_query(k0: int) -> int:
    """The first query a program scores against new-row keys from ``k0``
    on: the queries before ``k0`` do not see them, and a block of queries
    starts at a lane tile."""
    return k0 // _LANES * _LANES


def latent_prefill_visits(ctx, t: int, cap: int):
    """(live, dense): the tiles (a key block against a lane tile of 128
    queries) the kernel visits for streams whose contexts hold ``ctx`` [B]
    positions, summed over them, and what a pass over all ``cap + t`` keys
    would visit. The kernel's own rule: a cached key block is visited,
    against every query, if it starts before ``ctx``; a new rows' key block
    against the queries from the lane tile that holds its first key on."""
    block_t, block_k = latent_prefill_blocks(t, cap)
    columns = -(-t // _LANES)
    new = sum(-(-(t - _first_query(k0)) // _LANES)
              for k0 in range(0, t, block_t))
    cached = -(-jnp.clip(ctx, 0, cap) // block_k) if cap else 0 * ctx
    live = jnp.sum(cached * columns + new).astype(jnp.int32)
    blocks = (cap // block_k if cap else 0) + t // block_t
    return live, jnp.asarray(ctx.shape[0] * blocks * columns, jnp.int32)


def _latent_prefill_kernel(slots_ref, ctx_ref, block_ref, q_ref, new_ref,
                           *refs, scale: float, rank: int, block_t: int,
                           block_k: int):
    """One (stream, head), the scores keys-major ([keys, queries]: the
    softmax's reductions run down the sublanes and its statistics lie along
    the lanes). q [d_nope + tail, T] (``tail`` = row - rank: the rope part,
    zero past it); new [T, row] the round's own rows; pool [cap, row] the
    stream's slot (absent where nothing can be cached); wk [rank, d_nope]
    and wv [d_v, rank] the head's up-projection; o [d_v, T]; m, l [1, T]
    and acc [d_v, T] the running softmax, float32; s, p [block, T] a key
    block's scores and weights."""
    del slots_ref, block_ref            # the index maps read them
    pool_ref = refs[0] if len(refs) == 9 else None
    wk_ref, wv_ref, o_ref, m_ref, l_ref, acc_ref, s_ref, p_ref = refs[-8:]
    ctx = ctx_ref[pl.program_id(0)]
    t = q_ref.shape[1]
    wk, wv = wk_ref[...], wv_ref[...]
    m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def attend(rows, seen=None, k0=None):
        """Latent rows [bk, row] into the running softmax: up-projected to
        this head's keys [bk, d_nope + tail] (the rope part is every
        head's alike) and values [d_v, bk], scored against all queries in
        one product, weighted in another; between the two, the softmax a
        lane tile of queries at a time, so that the chain from a score to
        its weight stays in registers. ``seen`` [bk, 1]: the rows that
        count (cached rows; None = all); ``k0``: the first row's place
        among the new rows, which a query sees causally, and only the
        queries from its lane tile on are scored."""
        bk = rows.shape[0]
        q0 = 0 if k0 is None else _first_query(k0)
        latent = rows[:, :rank]
        keys = jnp.concatenate(
            [jnp.dot(latent, wk, preferred_element_type=jnp.float32).astype(
                rows.dtype), rows[:, rank:]], axis=1)
        values = lax.dot_general(
            wv, latent, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).astype(rows.dtype)
        s_ref[:bk, q0:] = jnp.dot(keys, q_ref[:, q0:],
                                  preferred_element_type=jnp.float32)
        for c0 in range(q0, t, _LANES):
            at = slice(c0, min(c0 + _LANES, t))
            s = s_ref[:bk, at] * scale
            if seen is not None:
                s = jnp.where(seen, s, _NEG)
            if k0 is not None and c0 < k0 + bk - 1:     # crosses the diagonal
                s = jnp.where(
                    k0 + lax.broadcasted_iota(jnp.int32, s.shape, 0)
                    <= c0 + lax.broadcasted_iota(jnp.int32, s.shape, 1),
                    s, _NEG)
            m_ref[:, at], l_ref[:, at], alpha, p = _softmax_block(
                m_ref[:, at], l_ref[:, at], s, keys=0)
            p_ref[:bk, at] = p.astype(p_ref.dtype)
            acc_ref[:, at] = acc_ref[:, at] * alpha
        acc_ref[:, q0:] += jnp.dot(values, p_ref[:bk, q0:],
                                   preferred_element_type=jnp.float32)

    if pool_ref is not None:
        # the context: whole blocks unmasked, the block that straddles
        # ``ctx`` masked (and its rows past ``ctx`` zeroed: they may hold
        # anything, and 0 x NaN is NaN in the value product), the rest
        # never touched
        whole = ctx // block_k
        lax.fori_loop(
            0, whole, lambda j, _: attend(pool_ref[pl.ds(
                pl.multiple_of(j * block_k, block_k), block_k), :]), None)

        @pl.when(ctx % block_k != 0)
        def _():
            base = pl.multiple_of(whole * block_k, block_k)
            seen = base + lax.broadcasted_iota(
                jnp.int32, (block_k, 1), 0) < ctx
            attend(jnp.where(seen, pool_ref[pl.ds(base, block_k), :], 0),
                   seen=seen)

    for k0 in range(0, t, block_t):
        attend(new_ref[k0:k0 + block_t, :], k0=k0)
    o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "rank", "cap", "interpret"),
)
def latent_prefill_attention(q, new, pool, w_k, w_v, slots, ctx, block, *,
                             scale: float, rank: int, cap: int,
                             interpret: Optional[bool] = None):
    """Causal attention of T new positions a stream over its context in a
    latent cache and over themselves, the scores never leaving the chip.

    ``q`` [B, H, d_nope + tail, T]: a head's queries, one a column, the
    last ``tail`` = row - rank numbers of each against the rows' own tail
    (the shared rope key, zero where the row is padding); ``new`` [B, T,
    row] the new positions' rows; ``pool`` [blocks, slots, S, row] the
    whole cache, of which this attention's is ``pool[block]`` (the kernel
    is handed the whole and reads its slots where they lie: a slice handed
    to a kernel is a copy of the block; ``block`` is an operand, so a
    stack's attentions share one trace and one compiled kernel) and stream
    b's context is the
    first ``ctx[b]`` (at most ``cap``) rows of slot ``slots[b]`` (clipped
    into the pool: a padded row reads some slot, finitely) while the rest
    may hold anything; ``w_k`` [H, rank, d_nope] and ``w_v`` [H, d_v, rank]
    each head's up-projection of a row's first ``rank`` numbers to its
    keys' first ``d_nope`` and to its values. Returns [B, H, d_v, T].

    Grid (stream, head); a program reads its stream's slot as it lies
    (whole rows, the first ``cap``: fetched once a stream, the block index
    does not change with the head) and visits the key blocks that start
    before ``ctx[b]``, then the new rows' blocks, each against the queries
    that can see it. Operands enter both products in their own dtype;
    scores, maximum, sum and accumulator are float32; the output is cast
    once."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, h, dq, t = q.shape
    row, dn, dv = new.shape[-1], w_k.shape[-1], w_v.shape[1]
    bt, bk = latent_prefill_blocks(t, cap)
    kernel = functools.partial(
        _latent_prefill_kernel, scale=scale, rank=rank, block_t=bt,
        block_k=bk)
    last = pool.shape[1] - 1
    in_specs = [
        pl.BlockSpec((None, None, dq, t), lambda i, j, s, c, k: (i, j, 0, 0)),
        pl.BlockSpec((None, t, row), lambda i, j, s, c, k: (i, 0, 0)),
        pl.BlockSpec((None, None, cap, row),
                     lambda i, j, s, c, k: (k[0], jnp.clip(s[i], 0, last), 0,
                                            0)),
        pl.BlockSpec((None, rank, dn), lambda i, j, s, c, k: (j, 0, 0)),
        pl.BlockSpec((None, dv, rank), lambda i, j, s, c, k: (j, 0, 0)),
    ]
    args = [q, new, pool, w_k, w_v]
    if not cap:
        del in_specs[2], args[2]        # nothing can be cached: no pool
    blocks = dq * t + t * row + cap * row + rank * (dn + dv) + dv * t
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b, h), in_specs=in_specs,
            out_specs=pl.BlockSpec((None, None, dv, t),
                                   lambda i, j, s, c, k: (i, j, 0, 0)),
            scratch_shapes=[pltpu.VMEM((1, t), jnp.float32),
                            pltpu.VMEM((1, t), jnp.float32),
                            pltpu.VMEM((dv, t), jnp.float32),
                            pltpu.VMEM((max(bt, bk), t), jnp.float32),
                            pltpu.VMEM((max(bt, bk), t), q.dtype)]),
        out_shape=jax.ShapeDtypeStruct((b, h, dv, t), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # every block twice (the pipeline's two buffers), and room for
            # the running softmax and a tile's temporaries
            vmem_limit_bytes=2 * blocks * q.dtype.itemsize + (32 << 20)),
        interpret=interpret,
    )(slots.astype(jnp.int32), jnp.clip(ctx, 0, cap).astype(jnp.int32),
      jnp.asarray(block, jnp.int32).reshape(1), *args)
