"""Ring attention: sequence/context parallelism over the ``sp`` mesh axis.

Long-context support is first-class even though today's clips are short
(SURVEY.md §5.7): when the token count outgrows one chip's HBM, the
sequence is sharded across ``sp`` and attention runs blockwise — each step
attends the local Q block against the resident K/V block while
`lax.ppermute` rotates K/V around the ring, overlapping the ICI transfer
with the matmuls. Softmax is accumulated online (flash-attention style
running max/denominator), so the result is *exactly* full softmax
attention, never an approximation.

Drops into the encoder via the `attn_fn` hook (`models/transformer.py`):
`make_ring_attn_fn(mesh)` returns a function with the same [B, T, H, D]
signature as `default_attention`, implemented as a nested `shard_map`.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


_NEG = -1e30  # "masked" logit; avoids -inf NaNs when a whole block is masked


def _online_block(q, k_blk, v_blk, key_valid, m, l, o):
    """One blockwise-softmax accumulation step.

    q: [B, Tq, H, D]; k_blk/v_blk: [B, Tk, H, D]; key_valid: [Tk] bool;
    m, l: [B, H, Tq] running max / denominator; o: [B, Tq, H, D] numerator.
    """
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bthd,bshd->bhts", q, k_blk).astype(jnp.float32) * scale
    logits = jnp.where(key_valid[None, None, None, :], logits, _NEG)
    m_new = jnp.maximum(m, logits.max(axis=-1))
    alpha = jnp.exp(m - m_new)                       # rescale old accumulators
    p = jnp.exp(logits - m_new[..., None])           # [B, H, Tq, Tk]
    l_new = l * alpha + p.sum(axis=-1)
    o_new = o * alpha.transpose(0, 2, 1)[..., None] + jnp.einsum(
        "bhts,bshd->bthd", p.astype(v_blk.dtype), v_blk
    ).astype(jnp.float32)
    return m_new, l_new, o_new


def ring_attention_local(q, k, v, axis_name: str = "sp", true_t: Optional[int] = None):
    """Attention over a sequence sharded on ``axis_name``; call under
    shard_map. q/k/v: local shards [B, T_local, H, D].

    ``true_t``: global unpadded token count. Key positions >= true_t (the
    right-pad added to make T divisible by the ring size) are masked out of
    the softmax; the mask for each in-flight block is derived from which
    shard the block originated on (after s rotations, device i holds the
    block that started on device (i - s) mod n).
    """
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    b, tq, h, d = q.shape
    m0 = jnp.full((b, h, tq), _NEG, jnp.float32)
    l0 = jnp.zeros((b, h, tq), jnp.float32)
    o0 = jnp.zeros((b, tq, h, d), jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]
    local_pos = jnp.arange(tq)

    def body(carry, s):
        k_blk, v_blk, m, l, o = carry
        if true_t is None:
            key_valid = jnp.ones((tq,), bool)
        else:
            src = (my - s) % n
            key_valid = src * tq + local_pos < true_t
        m, l, o = _online_block(q, k_blk, v_blk, key_valid, m, l, o)
        # Rotate K/V around the ring; XLA overlaps the ppermute with the
        # next iteration's matmuls (async collective).
        k_nxt = lax.ppermute(k_blk, axis_name, perm)
        v_nxt = lax.ppermute(v_blk, axis_name, perm)
        return (k_nxt, v_nxt, m, l, o), None

    (_, _, m, l, o), _ = lax.scan(
        body, (k, v, m0, l0, o0), jnp.arange(n), length=n
    )
    out = o / jnp.maximum(l.transpose(0, 2, 1)[..., None], 1e-30)
    return out.astype(q.dtype)


def make_seq_parallel_attn_fn(
    mesh: Mesh,
    choose_local,
    batch_axis: Optional[str] = "dp",
    seq_axis: str = "sp",
    head_axis: Optional[str] = "tp",
):
    """Shared wrapper for sequence-parallel attention variants: global
    [B, T, H, D] in/out, sequence sharded over ``seq_axis`` inside the
    shard_map, batch and heads partitioned over ``batch_axis``/``head_axis``.

    ``choose_local(h_local)`` picks the per-shard attention body (ring,
    all-to-all, ...) given the per-device head count after head-axis
    sharding — the one place the variants differ. The padding/fallback
    subtleties live here exactly once:

    - Sequences whose length is not divisible by the ``seq_axis`` size
      (e.g. ViT's 196 patches + 1 cls token) are right-padded before the
      shard_map and the pad keys masked out of the softmax, so the result
      is bit-equal to dense attention on the unpadded sequence.
    - Axes that don't divide the actual (static) shape fall back to
      replication — e.g. model.init traces with batch 1 under dp=2.
    """
    n_sp = mesh.shape[seq_axis]

    def attn(q, k, v):
        ba = batch_axis if batch_axis and q.shape[0] % mesh.shape[batch_axis] == 0 else None
        ha = head_axis if head_axis and q.shape[2] % mesh.shape[head_axis] == 0 else None
        h_local = q.shape[2] // (mesh.shape[head_axis] if ha else 1)
        spec = P(ba, seq_axis, ha, None)
        t = q.shape[1]
        t_pad = -(-t // n_sp) * n_sp
        sharded = shard_map(
            functools.partial(
                choose_local(h_local), axis_name=seq_axis,
                true_t=None if t_pad == t else t,
            ),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )
        if t_pad != t:
            pad = ((0, 0), (0, t_pad - t), (0, 0), (0, 0))
            q, k, v = (jnp.pad(a, pad) for a in (q, k, v))
        out = sharded(q, k, v)
        return out[:, :t] if t_pad != t else out

    return attn


def make_ring_attn_fn(
    mesh: Mesh,
    batch_axis: Optional[str] = "dp",
    seq_axis: str = "sp",
    head_axis: Optional[str] = "tp",
):
    """Build a ring-attention `attn_fn` for `models/transformer.Encoder`
    (see `make_seq_parallel_attn_fn` for the shared padding/fallback
    behavior)."""
    return make_seq_parallel_attn_fn(
        mesh, lambda h_local: ring_attention_local,
        batch_axis=batch_axis, seq_axis=seq_axis, head_axis=head_axis,
    )
