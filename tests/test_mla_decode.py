"""Latent attention's decode path (``models/mla.py``
``mla_decode_attention``): its pool half is one Pallas kernel
(``ops/flash_attention.py`` ``latent_decode_attention``, interpreted here)
that reads each stream's slot where it lies and fetches only the key blocks
its context reaches. Checked against the plain formulation it replaced,
kept below as the reference: the queries carried to slot order by a one-hot
product, every position of every slot scored and those past the context
masked, the partial results carried back. CPU: results and counts only."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from video_edge_ai_proxy_tpu.models import mla
from video_edge_ai_proxy_tpu.ops.flash_attention import (
    latent_decode_block, latent_decode_visits)

H, DN, DR, DV, R = 32, 16, 8, 16, 32        # 32 heads; rows of 40 in 128
ROW, SLOTS, POSITIONS, BLOCKS = 128, 5, 512, 6
SCALE = 0.3


def _reference(q_n, q_r, w_uk, w_uv, pool, rbuf, slots, ctx, upto, scale):
    """The pool's part in slot order over every position, the round's part
    in batch order, merged by their maxima and sums."""
    b, t, h, _ = q_n.shape
    r = w_uk.shape[0]
    c = pool.shape[0]
    hi = jax.lax.Precision.HIGHEST
    width = pool.shape[-1]
    q = jnp.concatenate(
        [jnp.einsum("bthd,rhd->bthr", q_n, w_uk), q_r,
         jnp.zeros(q_r.shape[:-1] + (width - r - q_r.shape[-1],),
                   q_r.dtype)], axis=-1)
    q = q.reshape(b, t * h, width)
    onehot = (slots[:, None] == jnp.arange(c)[None]).astype(jnp.float32)

    def partial(query, rows, mask):
        s = jnp.einsum("bqd,bsd->bqs", query, rows,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask, s, -1e30)
        m = jnp.max(s, axis=-1)
        e = jnp.exp(s - m[..., None])
        o = jnp.einsum("bqs,bsd->bqd", e.astype(rows.dtype), rows,
                       preferred_element_type=jnp.float32)
        return m, jnp.sum(e, axis=-1), o[..., :r]

    q_slot = jnp.einsum("bc,bqd->cqd", onehot, q.astype(jnp.float32))
    ctx_slot = jnp.einsum("bc,b->c", onehot, ctx.astype(jnp.float32),
                          precision=hi)
    seen = jnp.arange(pool.shape[1])[None] < ctx_slot[:, None]
    part_a = partial(q_slot.astype(q.dtype), pool, seen[:, None])
    m_a, l_a, o_a = (jnp.einsum("bc,c...->b...", onehot, x, precision=hi)
                     for x in part_a)
    new = jnp.arange(rbuf.shape[1])[None, None] <= upto[:, :, None]
    new = jnp.repeat(new, h, axis=1)
    m_b, l_b, o_b = partial(q, rbuf, new)
    m = jnp.maximum(m_a, m_b)
    w_a, w_b = jnp.exp(m_a - m), jnp.exp(m_b - m)
    o = (o_a * w_a[..., None] + o_b * w_b[..., None]) \
        / (l_a * w_a + l_b * w_b)[..., None]
    o = jnp.einsum("bthr,rhd->bthd",
                   o.astype(q_n.dtype).reshape(b, t, h, r), w_uv)
    return o.reshape(b, t, -1)


# a key block is 256 rows of the 512: depths none, one row, exactly one
# block, one block and a straddling one, all (cap); the last row is padded
# (its slot past the pool)
SLOTS_OF = [3, 0, 4, 1, 2, SLOTS]
DEPTHS = [0, 1, 256, 300, 512, 40]


def _case(t, dtype, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    b = len(DEPTHS)
    q_n = jax.random.normal(k[0], (b, t, H, DN))
    q_r = jax.random.normal(k[1], (b, t, H, DR))
    w_uk = jax.random.normal(k[2], (R, H, DN)) * R ** -0.5
    w_uv = jax.random.normal(k[3], (R, H, DV)) * R ** -0.5
    rows = jax.random.normal(k[4], (BLOCKS, SLOTS, POSITIONS, R + DR))
    pool = jnp.pad(rows, ((0, 0),) * 3 + ((0, ROW - R - DR),))
    rbuf = jnp.pad(jax.random.normal(k[5], (b, 12, R + DR)),
                   ((0, 0), (0, 0), (0, ROW - R - DR)))
    at = jax.random.randint(k[6], (b,), 0, 12 - t)
    upto = at[:, None] + jnp.arange(t)[None]
    cast = lambda a: a.astype(dtype)  # noqa: E731
    return (cast(q_n), cast(q_r), cast(w_uk), cast(w_uv), cast(pool),
            cast(rbuf), jnp.asarray(SLOTS_OF), jnp.asarray(DEPTHS), upto)


@pytest.mark.parametrize("block", [0, BLOCKS - 1], ids=["first", "last"])
@pytest.mark.parametrize("t", [1, 2])
def test_the_kernel_gives_the_plain_formulation(t, block):
    """float32 throughout: the running softmax over the fetched key blocks
    is the one softmax over every position, masked, to rounding; rows past
    a context hold NaN for the kernel (it never lets them reach a
    result) and their finite values for the reference (it multiplies
    every row)."""
    q_n, q_r, w_uk, w_uv, pool, rbuf, slots, ctx, upto = _case(
        t, jnp.float32)
    given = pool
    for s, n in zip(SLOTS_OF, DEPTHS):
        if s < SLOTS:
            given = given.at[block, s, n:].set(jnp.nan)
    got = mla.mla_decode_attention(q_n, q_r, w_uk, w_uv, given, rbuf, slots,
                                   ctx, upto, SCALE, block)
    want = _reference(q_n, q_r, w_uk, w_uv, pool[block], rbuf, slots, ctx,
                      upto, SCALE)
    assert got.shape == (len(DEPTHS), t, H * DV)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("t", [1, 2])
def test_bfloat16_operands_give_the_plain_formulation_to_their_rounding(t):
    """The cells' precision: bfloat16 operands into both products, the
    softmax's statistics and the weighted sum float32. The kernel's weights
    are rounded against the running maximum rather than the final one,
    which moves a result by a bfloat16 rounding at most."""
    q_n, q_r, w_uk, w_uv, pool, rbuf, slots, ctx, upto = _case(
        t, jnp.bfloat16, seed=1)
    got = mla.mla_decode_attention(q_n, q_r, w_uk, w_uv, pool, rbuf, slots,
                                   ctx, upto, SCALE, 2)
    want = _reference(q_n, q_r, w_uk, w_uv, pool[2], rbuf, slots, ctx, upto,
                      SCALE)
    assert got.dtype == jnp.bfloat16
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=2e-2,
                               atol=2e-2 * np.abs(want).max())


def test_a_pool_of_one_block_needs_no_index():
    """[slots, S, row] is the pool of one block."""
    q_n, q_r, w_uk, w_uv, pool, rbuf, slots, ctx, upto = _case(
        2, jnp.float32, seed=2)
    got = mla.mla_decode_attention(q_n, q_r, w_uk, w_uv, pool[4], rbuf,
                                   slots, ctx, upto, SCALE)
    want = mla.mla_decode_attention(q_n, q_r, w_uk, w_uv, pool, rbuf, slots,
                                    ctx, upto, SCALE, 4)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_the_count_is_the_kernels_fetch_rule():
    """A key block is fetched if it starts before the stream's depth; a
    padded row fetches none. 512 positions are two key blocks of 256:
    depths 0 / 1 / 256 / 300 / 512 fetch 0 / 1 / 1 / 2 / 2 of them, the
    padded row none, in each of 3 attentions; a dense pass over all 512
    cacheable positions fetches 2 a stream."""
    assert latent_decode_block(POSITIONS) == 256
    pool = jax.ShapeDtypeStruct((BLOCKS, SLOTS, POSITIONS, ROW), jnp.float32)
    got = mla.decode_visits(pool, jnp.asarray(SLOTS_OF),
                            jnp.asarray(DEPTHS), 512, 3)
    assert int(got["decode_blocks_live"]) == 3 * (0 + 1 + 1 + 2 + 2 + 0)
    assert int(got["decode_blocks_dense"]) == 3 * 6 * 2
    # the cells' shapes: a 4,096-deep pool in key blocks of 256, 3,328
    # cacheable positions 13 of them
    assert latent_decode_block(4096) == 256
    live, dense = latent_decode_visits(jnp.asarray([0, 1, 1616, 3200, 3328]),
                                       3328, 4096)
    assert (int(live), int(dense)) == (0 + 1 + 7 + 13 + 13, 5 * 13)
