"""The way back from an expert layer's sorted pairs to its tokens, as a
Pallas TPU kernel.

``models/transformer.py`` ``TopKMoeMlp`` computes its held (token, expert)
pairs as rows sorted by expert, the held ones first; each token then wants
the weighted sum of its own rows among them. Written in XLA that is a
gather of every pair's row, held or not, and a sum: the TPU compiler fuses
no gather into its consumer, so each pair's row is written to HBM and read
back. Here the kernel walks only the ``count`` held rows, in the order they
lie: it fetches them in whole contiguous blocks (one DMA a block, the next
block's started before this one is added) and adds each, weighted, into its
token's row of an output slice that stays in VMEM, zeroed once and written
out once. What crosses HBM is the held rows once and the output once, and
the work follows the held rows, not the N x k pair slots.

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
# VMEM a call may hold: its [n, lanes] float32 output slice and two blocks
# of fetched rows (a v5e core has 128 MiB)
_VMEM_BUDGET = 64 << 20
# the most bytes a block of fetched rows holds (a block is 8 rows at least)
_BLOCK_BYTES = 1 << 20


def _blocks_bytes(lanes: int) -> int:
    """VMEM of the two blocks of fetched rows, at most."""
    return 2 * max(_BLOCK_BYTES, 8 * 4 * lanes)


def combine_slice(n: int, d: int) -> int:
    """Lanes of the output slice a program keeps in VMEM where the output
    is [n, d]: the widest multiple of 128 that divides ``d`` and whose
    [n, lanes] float32, beside two blocks of fetched rows, stay within
    ``_VMEM_BUDGET``; 0 where none does, ``n`` is no multiple of 8 or ``d``
    no multiple of 128, and the kernel does not take the shape."""
    if n % 8 or d % _LANES:
        return 0
    tiles = d // _LANES
    return next((d // s for s in range(1, tiles + 1) if tiles % s == 0
                 and 4 * n * (d // s) + _blocks_bytes(d // s)
                 <= _VMEM_BUDGET), 0)


def _block_rows(c: int, lanes: int) -> int:
    """Rows a fetch moves: the most of 512, 256, ..., 8 that divides ``c``
    (so no block passes the last row) within ``_BLOCK_BYTES``, else 8."""
    return next((b for b in (512, 256, 128, 64, 32, 16)
                 if c % b == 0 and 4 * b * lanes <= _BLOCK_BYTES), 8)


def _kernel(count_ref, tok_ref, w_ref, rows_hbm, y_hbm, acc, got, sems,
            out_sem, *, block: int):
    """One slice of ``lanes`` columns: ``acc`` [n, lanes] the output slice,
    ``got`` [2, block, lanes] the fetched rows of this block and of the
    next, a DMA semaphore each. Rows ``0 .. count - 1`` are added, block by
    block; the last block's rows past ``count`` are fetched, not added."""
    j = pl.program_id(0)
    lanes = acc.shape[1]
    count = count_ref[0]
    blocks = (count + block - 1) // block
    cols = pl.ds(pl.multiple_of(j * lanes, _LANES), lanes)

    def fetch(b, buf):
        return pltpu.make_async_copy(
            rows_hbm.at[pl.ds(pl.multiple_of(b * block, 8), block), cols],
            got.at[buf], sems.at[buf])

    @pl.when(blocks > 0)
    def _():
        fetch(0, 0).start()

    def zero(i, carry):
        acc[pl.ds(pl.multiple_of(i * 8, 8), 8), :] = jnp.zeros(
            (8, lanes), acc.dtype)
        return carry

    lax.fori_loop(0, acc.shape[0] // 8, zero, 0)

    def each(b, carry):
        buf = b % 2

        @pl.when(b + 1 < blocks)
        def _():
            fetch(b + 1, 1 - buf).start()

        fetch(b, buf).wait()
        first = b * block

        def add(i, carry):
            r = first + i
            acc[pl.ds(tok_ref[r], 1), :] += w_ref[r] * got[buf, pl.ds(i, 1), :]
            return carry

        lax.fori_loop(0, jnp.minimum(block, count - first), add, 0)
        return carry

    lax.fori_loop(0, blocks, each, 0)
    out = pltpu.make_async_copy(acc, y_hbm.at[:, cols], out_sem.at[0])
    out.start()
    out.wait()


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def combine_rows(rows, tok, w, count, n: int, *,
                 interpret: Optional[bool] = None):
    """``y`` [n, d] float32, ``y[t] = sum_r w[r] * rows[r]`` over the
    ``r < count`` with ``tok[r] = t``: ``rows`` [C, d] float32, the held
    pairs' rows in sorted order (the rows past ``count`` are never added,
    whatever they hold), ``tok`` [C] int32 and ``w`` [C] each row's token
    and weight, ``count`` a scalar; ``C`` a multiple of 8 and [n, d] a
    shape that :func:`combine_slice` takes. A token's rows are added in
    the order they lie (the experts' order), each into the running sum."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    c, d = rows.shape
    lanes = combine_slice(n, d)
    if c % 8 or not lanes:
        raise ValueError(f"combine_rows takes no {c} rows into [{n}, {d}]")
    block = _block_rows(c, lanes)
    return pl.pallas_call(
        functools.partial(_kernel, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(d // lanes,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((n, lanes), jnp.float32),
                            pltpu.VMEM((2, block, lanes), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SemaphoreType.DMA((1,))]),
        out_shape=jax.ShapeDtypeStruct((n, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # the output slice and the two blocks of fetched rows
            vmem_limit_bytes=4 * lanes * (n + 2 * block) + (16 << 20)),
        interpret=interpret,
    )(jnp.asarray(count, jnp.int32).reshape(1), tok.astype(jnp.int32),
      w.astype(jnp.float32), rows.astype(jnp.float32))
