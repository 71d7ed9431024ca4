"""The way back from an expert layer's sorted pairs to its tokens, as a
Pallas TPU kernel.

``models/transformer.py`` ``TopKMoeMlp`` computes its held (token, expert)
pairs as rows sorted by expert; each token then wants the weighted sum of
its own k rows, most of them absent (held elsewhere). Written in XLA that
is k gathers of [N, d] float32 (or one of [N, k, d]) and a sum: the TPU
compiler fuses no gather into its consumer, so every pair's row is written
to HBM and read back, held or not. Here a program owns a block of tokens,
fetches only the rows that are there (one DMA a row, the next block's
issued before this block's are summed) and adds them, so what crosses HBM
is the rows present once and the output once.

Off the chip the same kernel runs interpreted (``interpret=True``), so
the CPU tests run it.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128


def combine_block(n: int, d: int) -> int:
    """Tokens a program owns where the output is [n, d]: the largest of
    32, 16, 8 that divides ``n``; 0 where none does or ``d`` is no
    multiple of 128, and the kernel does not take the shape."""
    if d % _LANES:
        return 0
    return next((b for b in (32, 16, 8) if n % b == 0), 0)


def _kernel(count_ref, src_ref, w_ref, rows_hbm, y_ref, got, sems, *,
            k: int):
    """One block of ``tb`` tokens: ``got`` [2, tb * k, d / 128, 128] the
    fetched rows of this block and of the next, ``sems`` a DMA semaphore
    each; ``y`` [tb, d / 128, 128]. A pair's row is fetched and added only
    where its index is under ``count``."""
    tb = y_ref.shape[0]
    slots = tb * k
    i = pl.program_id(0)
    count = count_ref[0]

    def each(block, buf, body):
        """``body(slot, copy)`` for the pairs of ``block`` that are there."""
        def one(s, carry):
            r = src_ref[block * slots + s]

            @pl.when(r < count)
            def _():
                body(s, pltpu.make_async_copy(
                    rows_hbm.at[r], got.at[buf, s], sems.at[buf, s]))
            return carry
        lax.fori_loop(0, slots, one, 0)

    @pl.when(i == 0)
    def _():
        each(0, 0, lambda s, copy: copy.start())

    @pl.when(i + 1 < pl.num_programs(0))
    def _():
        each(i + 1, (i + 1) % 2, lambda s, copy: copy.start())

    y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)
    buf = i % 2

    def add(s, copy):
        copy.wait()
        y_ref[s // k] += w_ref[i * slots + s] * got[buf, s]

    each(i, buf, add)


@functools.partial(jax.jit, static_argnames=("interpret",))
def combine_rows(rows, src, w, count, *, interpret: Optional[bool] = None):
    """``y`` [N, d] float32, ``y[t] = sum_j w[t, j] * rows[src[t, j]]``
    over the ``j`` with ``src[t, j] < count``: ``rows`` [C, d] float32 (the
    rows past ``count`` are never read, whatever they hold), ``src`` [N, k]
    int32, ``w`` [N, k], ``count`` a scalar; a shape that
    :func:`combine_block` takes. Each token's k rows are added in the order
    of ``j``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n, k = src.shape
    c, d = rows.shape
    tb = combine_block(n, d)
    tiles = d // _LANES
    row = (tiles, _LANES)
    out = pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(n // tb,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tb,) + row, lambda i, *_: (i, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, tb * k) + row, jnp.float32),
                            pltpu.SemaphoreType.DMA((2, tb * k))]),
        out_shape=jax.ShapeDtypeStruct((n,) + row, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # the two blocks of fetched rows, the output's two buffers
            vmem_limit_bytes=4 * d * (2 * tb * k + 2 * tb) + (16 << 20)),
        interpret=interpret,
    )(jnp.asarray(count, jnp.int32).reshape(1),
      src.reshape(n * k).astype(jnp.int32),
      w.reshape(n * k).astype(jnp.float32),
      # a row whole in the leading axis: one DMA of d numbers
      rows.astype(jnp.float32).reshape((c,) + row))
    return out.reshape(n, d)
