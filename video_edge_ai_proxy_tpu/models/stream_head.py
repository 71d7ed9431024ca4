"""What the streaming heads share (``models/lfm2.py``, ``models/xing4.py``,
``models/deepseek_v2.py``): a decoder behind the VideoMAE encoder that keeps
a state per camera.

The reference ships frames to external clients and has no model at all
(`/root/reference/README.md:5-27`). Here, every round, a clip camera's
window goes through VideoMAE, its tubelet tokens through a connector into a
decoder, are PREFILLED into that stream's state, and a few tokens are
decoded greedily. What is one head's own (its layers, its kinds of state,
how it decodes) lives in its module; this one holds the round's policy
(:class:`StreamHeadConfig`), the operators every head has (:class:`RmsNorm`,
:class:`SwiGlu`, :class:`Connector`), what the engine does once when it
takes such a model (:func:`prepare_for_serving`) and the round itself
(:func:`serve_round`): instruction, prefill in chunks of streams, decode,
flush, as a pure function over a head that answers

- ``empty_counts() -> load``: what its expert layers count over a round,
  zeroed (the routed pairs each held expert took, [held] int32; a head
  that counts more gives a pytree of them);
- ``seed_round(variables, state, slots, reset) -> (pool, rows)``: the
  standing instruction laid into the state; ``pool`` is what the round
  reads in place by slot (the caches), ``rows`` what it carries a batch row
  (gathered by slot: a pytree, batch axis 0; ``()`` where the caches are
  all its state);
- ``round_buffer(rows, dtype) -> rbuf``: the round's own cache rows (a
  pytree, batch axis 1), written to the pool once, when the round is over;
- ``prefill(variables, x, pool, rows, rbuf, slots, pos0) -> (h, rows, rbuf,
  load)``: a chunk's visual tokens ``x`` [n, V, d]; ``h`` [n, d] what the
  first token is read from;
- ``decode(variables, pool, h, rows, rbuf, slots, pos0, load) -> dict``:
  ``tokens``, ``top_ids``, ``top_probs`` (and whatever else the head
  reports), with ``rows``, ``rbuf``, ``moe_load`` as the round left them;
- ``commit_round(state, pool, rows, rbuf, slots, pos0) -> state``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from .common import Dtype
from .videomae import VideoMAEConfig

TOP_K_TOKENS = 5


@dataclass(frozen=True)
class StreamHeadConfig:
    """VideoMAE encoder -> connector -> a decoder head, and the round's
    policy."""
    video: VideoMAEConfig
    head: Any
    instruction_ids: Tuple[int, ...]
    decode_steps: int = 8
    # streams per prefill chunk (bounds the dense layer's activations)
    prefill_chunk: int = 16

    @property
    def visual_tokens(self) -> int:
        return self.video.num_tokens

    @property
    def round_positions(self) -> int:
        return self.visual_tokens + self.decode_steps

    @property
    def max_rounds(self) -> int:
        """Rounds a context holds after the instruction."""
        return ((self.head.max_context - len(self.instruction_ids))
                // self.round_positions)


def _kernel(mod, name, shape, axes):
    return mod.param(name, nn.with_logical_partitioning(
        nn.initializers.xavier_uniform(), axes), shape, jnp.float32)


class RmsNorm(nn.Module):
    eps: float
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones_init(),
                           (x.shape[-1],), jnp.float32)
        x = x.astype(jnp.float32)
        x = x * jax.lax.rsqrt(
            jnp.mean(x * x, axis=-1, keepdims=True) + self.eps)
        return (x * scale.astype(jnp.float32)).astype(self.dtype)


class SwiGlu(nn.Module):
    dim: int
    mlp_dim: int
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, h):
        d, m = self.dim, self.mlp_dim
        w1 = _kernel(self, "w1", (d, m), ("embed", "mlp"))
        w3 = _kernel(self, "w3", (d, m), ("embed", "mlp"))
        w2 = _kernel(self, "w2", (m, d), ("mlp", "embed"))
        with jax.named_scope("head_dense_mlp"):
            a = nn.silu(h @ w1.astype(self.dtype)) * (h @ w3.astype(self.dtype))
            return a @ w2.astype(self.dtype)


class Connector(nn.Module):
    """LLaVA-style per-token projector: Linear -> GELU -> Linear."""
    dim: int
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        with jax.named_scope("head_connector"):
            x = nn.Dense(self.dim, dtype=self.dtype, name="fc1")(x)
            return nn.Dense(self.dim, dtype=self.dtype, name="fc2")(
                nn.gelu(x, approximate=False))


# matrices that stay float32 when the head is cast: the routers, and the
# maps of a hyper-connected residual
_FLOAT32_MATRICES = ("gate", "phi")


def cast_for_serving(variables):
    """The head's and the connector's matrices in bfloat16, once, when the
    engine takes the model (the config's own dtype; a decode step is bound
    by reading them). Vectors (norm scales, biases, the router's bias) and
    the router stay float32: 0.13M parameters a layer, and the choice of
    experts is then made on float32 scores. The encoder stays as every
    cell runs it."""
    is_box = lambda x: isinstance(x, nn.meta.AxisMetadata)  # noqa: E731

    def cast(path, leaf):
        keys = [getattr(k, "key", str(k)) for k in path]
        raw = leaf.unbox() if is_box(leaf) else leaf
        if (keys[1] in ("head", "connector") and raw.ndim >= 2
                and keys[-1] not in _FLOAT32_MATRICES
                and raw.dtype == jnp.float32):
            raw = raw.astype(jnp.bfloat16)
            return leaf.replace_boxed(raw) if is_box(leaf) else raw
        return leaf

    return jax.tree_util.tree_map_with_path(cast, variables, is_leaf=is_box)


def prepare_for_serving(model, variables):
    """What the engine does once when it takes the model
    (``ModelSpec.prepare``): a bfloat16 model's head is cast
    (:func:`cast_for_serving`), and the instruction's state
    (``model.instruction_state``: a function of the weights alone) is
    computed and kept beside the weights as the collection
    ``instruction``."""
    if model.dtype == jnp.bfloat16:
        variables = cast_for_serving(variables)
    return {**variables, "instruction": jax.jit(
        model.instruction_state)(variables)}


def top_tokens(logits):
    """(greedy id [B], top ids [B, 5], their probabilities) of float32
    logits [B, vocabulary]."""
    with jax.named_scope("head_sample"):
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(
            probs, min(TOP_K_TOKENS, logits.shape[-1]))
        return top_i[:, 0].astype(jnp.int32), top_i.astype(jnp.int32), top_p


def serve_round(model, variables, clips, state, slots, pos0, reset,
                preprocess=lambda clips: clips):
    """One round of a batch of streams (a pure function of its arguments).

    ``variables`` as :func:`prepare_for_serving` leaves them;
    ``clips`` [B, T, H, W, 3], which ``preprocess`` turns into the
    encoder's input (chunk by chunk); ``state`` the model's whole state
    pool by name (``model.empty_state``; row b owns slot ``slots[b]``; a
    slot past the pool is a padded row), read during the round and written
    once at its end, in place; ``pos0`` [B] where each stream's visual
    tokens start (its length, or the instruction's length if it resets
    now); ``reset`` [B] bool.
    Returns a dict: ``tokens`` [B, D] the greedy ids, ``top_ids`` /
    ``top_probs`` [B, D, 5] of each token's distribution, ``state`` the
    pool after the round (visual tokens and all D decoded tokens
    committed), ``moe_load`` [held] routed pairs a held expert took
    (prefill and decode), and what else the head's ``decode`` reports.
    """
    c = model.cfg
    b = clips.shape[0]
    tree = jax.tree_util.tree_map

    # Every context starts with the standing instruction, so its cache rows
    # are the first of EVERY slot (written anew each round: a few MB), and
    # a stream that resets takes the rest of its state.
    with jax.named_scope("head_seed"):
        pool, rows = model.seed_round(variables, state, slots, reset)

    # Preprocess, encoder, connector and visual prefill, streams in chunks
    # (the whole batch at once would hold the encoder's [B, 12, V, V]
    # scores and the dense layer's activations). The pool is only read;
    # the round's cache rows go to the round buffer.
    n = c.prefill_chunk if b % c.prefill_chunk == 0 else b
    rbuf = model.round_buffer(b, jax.tree_util.tree_leaves(pool)[0].dtype)

    def prefill(i, carry):
        h, rows, rbuf, load = carry
        cut = lambda a, ax=0: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            a, i * n, n, axis=ax)
        put = lambda a, v, ax=0: jax.lax.dynamic_update_slice_in_dim(  # noqa: E731
            a, v.astype(a.dtype), i * n, ax)
        x = model.apply(variables, preprocess(cut(clips)),
                        method="encode")                        # [n, V, d]
        hn, rn, bn, m = model.prefill(
            variables, x, pool, tree(cut, rows),
            tree(lambda a: cut(a, 1), rbuf), cut(slots), cut(pos0))
        return (put(h, hn), tree(put, rows, rn),
                tree(lambda a, v: put(a, v, 1), rbuf, bn),
                tree(jnp.add, load, m))

    # (the encoder inside the loop keeps ``embed`` / ``encoder_block``:
    # obs/stages.py tells prefill from decode by the outer name)
    with jax.named_scope("head_prefill"):
        h, rows, rbuf, load = jax.lax.fori_loop(
            0, b // n, prefill,
            (jnp.zeros((b, c.head.dim), model.dtype), rows, rbuf,
             model.empty_counts()))

    out = model.decode(variables, pool, h, rows, rbuf, slots, pos0, load)
    with jax.named_scope("head_flush"):
        out["state"] = model.commit_round(
            state, pool, out.pop("rows"), out.pop("rbuf"), slots, pos0)
    return out
