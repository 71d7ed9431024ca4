"""Shared transformer encoder for the ViT family (ViT-B/16, VideoMAE).

TPU-first choices:
- Weights carry flax *logical axis names* (`nn.with_logical_partitioning`)
  so `parallel/sharding.py` can map them onto a device mesh (tp over
  "heads"/"mlp", fsdp over "embed") without touching model code.
- Attention is a pluggable function: the default is plain fused softmax
  attention (XLA fuses it fine at these sizes); `parallel/ring_attention.py`
  drops in a sequence-parallel implementation for long token counts by
  passing `attn_fn`.
- Optional `remat` wraps each block in `jax.checkpoint` to trade FLOPs for
  HBM during fine-tuning.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops.combine_rows import combine_block, combine_rows
from .common import Dtype

# attn_fn(q, k, v) -> out, all [B, T, H, D]
AttnFn = Callable[[jnp.ndarray, jnp.ndarray, jnp.ndarray], jnp.ndarray]


@dataclass(frozen=True)
class EncoderConfig:
    num_layers: int = 12
    dim: int = 768
    num_heads: int = 12
    mlp_dim: int = 3072
    dropout: float = 0.0
    remat: bool = False
    # >0 replaces the dense MLP with a mixture-of-experts MLP whose expert
    # axis carries the "expert" logical name (sharded over the mesh's ep
    # axis by parallel/sharding.py rules).
    num_experts: int = 0
    # "soft" = dense mixture (all experts on all tokens, exact but E× FLOPs);
    # "top1" = switch routing with static capacity (scale-out path).
    moe_router: str = "soft"
    # top1 only: per-expert slots = capacity_factor * tokens / num_experts.
    capacity_factor: float = 1.25


def default_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """Plain softmax attention over [B, T, H, D]; fp32 softmax for stability."""
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bthd,bshd->bhts", q, k).astype(jnp.float32) * scale
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhts,bshd->bthd", probs, v)


# Past this token count the dense [T, T] logits tensor dominates HBM and the
# Pallas flash kernel wins decisively (measured on v5e: 14x at T=8192).
FLASH_THRESHOLD_T = 1024


def auto_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """Shape-dispatched default: dense attention for short sequences (XLA
    fuses it fine), the Pallas flash kernel for long ones on TPU. Decision
    happens at trace time — static shapes, one compiled program either way."""
    if q.shape[1] >= FLASH_THRESHOLD_T and jax.default_backend() == "tpu":
        from ..ops.flash_attention import flash_attention

        return flash_attention(q, k, v)
    return default_attention(q, k, v)


def _dense(features, logical_axes, dtype, name):
    return nn.Dense(
        features,
        dtype=dtype,
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.xavier_uniform(), logical_axes
        ),
        bias_init=nn.with_logical_partitioning(
            nn.initializers.zeros_init(), (logical_axes[-1],)
        ),
        name=name,
    )


class SelfAttention(nn.Module):
    cfg: EncoderConfig
    dtype: Dtype = jnp.bfloat16
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray, deterministic: bool = True) -> jnp.ndarray:
        c = self.cfg
        head_dim = c.dim // c.num_heads
        b, t, _ = x.shape
        qkv = _dense(3 * c.dim, ("embed", "qkv"), self.dtype, "qkv")(x)
        qkv = qkv.reshape(b, t, 3, c.num_heads, head_dim)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        attn = (self.attn_fn or auto_attention)(q, k, v)
        attn = attn.reshape(b, t, c.dim)
        return _dense(c.dim, ("qkv", "embed"), self.dtype, "out")(attn)


class Mlp(nn.Module):
    cfg: EncoderConfig
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jnp.ndarray, deterministic: bool = True) -> jnp.ndarray:
        c = self.cfg
        h = _dense(c.mlp_dim, ("embed", "mlp"), self.dtype, "fc1")(x)
        h = nn.gelu(h)
        if c.dropout:
            h = nn.Dropout(c.dropout)(h, deterministic=deterministic)
        return _dense(c.dim, ("mlp", "embed"), self.dtype, "fc2")(h)


def _expert_weights(mod: nn.Module, cfg, *, stack: int = 0,
                    gated: bool = False, dim: int = 0):
    """The [E, d, mlp] / [E, mlp, d] expert stacks, shared by every MoE
    variant (one definition of the 'expert' logical sharding axis).
    ``stack`` is how many experts this holder keeps (default: all
    ``cfg.num_experts``); ``gated`` adds the SwiGLU up-projection ``w3``
    beside ``w1`` and returns (w1, w3, w2); ``dim`` is the width the
    experts read and write (default: ``cfg.dim``)."""
    e = stack or cfg.num_experts
    d = dim or cfg.dim

    def one(name, axes, shape):
        return mod.param(
            name,
            nn.with_logical_partitioning(
                nn.initializers.xavier_uniform(), ("expert",) + axes),
            (e,) + shape, jnp.float32,
        )

    w1 = one("w1", ("embed", "mlp"), (d, cfg.mlp_dim))
    w2 = one("w2", ("mlp", "embed"), (cfg.mlp_dim, d))
    if gated:
        return w1, one("w3", ("embed", "mlp"), (d, cfg.mlp_dim)), w2
    return w1, w2


class MoeMlp(nn.Module):
    """Soft mixture-of-experts MLP (expert-parallel demonstration path).

    All experts run on all tokens and are mixed by softmax gates — fully
    static shapes, no capacity/dropping logic, exact gradients. The expert
    dimension is sharded over the ``ep`` mesh axis via the "expert" logical
    name; XLA turns the mixing contraction into a psum over ep. Top-k
    routing with capacity buckets is the scale-out path once expert counts
    grow past what dense mixing affords.
    """

    cfg: EncoderConfig
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jnp.ndarray, deterministic: bool = True) -> jnp.ndarray:
        c = self.cfg
        e = c.num_experts
        gates = jax.nn.softmax(
            _dense(e, ("embed", "expert_gate"), jnp.float32, "gate")(
                x.astype(jnp.float32)
            ),
            axis=-1,
        )                                                      # [B, T, E]
        w1, w2 = _expert_weights(self, c)
        w1, w2 = w1.astype(self.dtype), w2.astype(self.dtype)
        h = nn.gelu(jnp.einsum("btd,edm->betm", x, w1))
        if c.dropout:
            h = nn.Dropout(c.dropout)(h, deterministic=deterministic)
        y = jnp.einsum("betm,emd->betd", h, w2)
        return jnp.einsum("bte,betd->btd", gates.astype(self.dtype), y)


class RoutedMoeMlp(nn.Module):
    """Top-1 (switch) routed MoE MLP with static capacity.

    Fully static shapes: each expert owns ``capacity`` slots; tokens beyond
    an expert's capacity are dropped (contribute zero, standard switch
    behavior). Dispatch is a scatter into an [E*C(+1), D] slot buffer and a
    gather back — no [N, E, C] dispatch tensor, so memory stays O(N*D).
    Expert weights carry the "expert" logical axis (ep sharding). The
    load-balance auxiliary (Switch aux = E * sum(f_e * p_e)) is sown under
    ('losses', 'moe_aux') for the trainer to add.
    """

    cfg: EncoderConfig
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jnp.ndarray, deterministic: bool = True) -> jnp.ndarray:
        c = self.cfg
        e = c.num_experts
        b, t, d = x.shape
        n = b * t
        cap = max(1, int(n / e * c.capacity_factor))

        flat = x.reshape(n, d)
        logits = _dense(e, ("embed", "expert_gate"), jnp.float32, "gate")(
            flat.astype(jnp.float32)
        )
        gates = jax.nn.softmax(logits, axis=-1)            # [N, E]
        gate_val = gates.max(axis=-1)                      # [N]
        expert_idx = gates.argmax(axis=-1)                 # [N]

        # position of each token within its expert's queue
        onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.int32)
        pos = (jnp.cumsum(onehot, axis=0) - 1) * onehot    # [N, E]
        pos_tok = pos.sum(axis=-1)                         # [N]
        keep = pos_tok < cap
        # dropped tokens land in a sentinel row past the real slots
        slot = jnp.where(keep, expert_idx * cap + pos_tok, e * cap)

        buf = jnp.zeros((e * cap + 1, d), self.dtype).at[slot].add(
            jnp.where(keep[:, None], flat, 0).astype(self.dtype)
        )
        expert_in = buf[: e * cap].reshape(e, cap, d)

        w1, w2 = _expert_weights(self, c)
        w1, w2 = w1.astype(self.dtype), w2.astype(self.dtype)
        h = nn.gelu(jnp.einsum("ecd,edm->ecm", expert_in, w1))
        if c.dropout:
            h = nn.Dropout(c.dropout)(h, deterministic=deterministic)
        y = jnp.einsum("ecm,emd->ecd", h, w2).reshape(e * cap, d)
        y = jnp.concatenate([y, jnp.zeros((1, d), y.dtype)], axis=0)

        out = y[slot] * (gate_val * keep)[:, None].astype(self.dtype)

        # Switch load-balance aux: E * sum_e(fraction_routed_e * mean_prob_e)
        frac = onehot.astype(jnp.float32).mean(axis=0)
        prob = gates.mean(axis=0)
        self.sow("losses", "moe_aux", e * jnp.sum(frac * prob))
        return out.reshape(b, t, d)


@dataclass(frozen=True)
class TopKMoeConfig:
    """A routed expert layer as ``lfm2_moe``, ``xing4_0``, ``deepseek_v2``
    and ``nemotron_h`` publish it: the router scores ALL ``num_experts``;
    this holder computes the experts in ``experts_held`` (their ids among
    the ``num_experts``; empty = all). ``shared_mlp_dim``: the width of the
    shared expert every token takes beside the routed ones, unweighted (0:
    none, and no parameter). ``activation``: ``swiglu`` (``silu(x W1) * x
    W3``, then W2) or ``relu2`` (non-gated squared ReLU, ``relu(x W1)^2``
    then W2), for the routed and the shared experts alike.
    ``latent_dim``: 0, or the width of a LATENT the routed experts live in
    (``nemotron_h``'s ``moe_latent_size``): the router and the shared
    expert read the layer's input ``x``, the routed experts read ``x
    W_down`` and their weighted sum goes back to the model width through
    ``W_up``. Three routers (:func:`topk_route`):

    - ``lfm2_moe``, ``xing4_0``, ``nemotron_h`` (one group): ``scoring``
      sigmoid, the top-k of score + learned bias over all experts,
      renormalised, x the scaling factor;
    - ``deepseek_v2`` (``group_limited_greedy``): ``scoring`` softmax over
      all experts in float32, no bias; the experts lie in ``n_group``
      groups of consecutive ids, a group scores as its best expert, only
      the best ``topk_group`` groups' experts can be chosen, the top-k of
      those; weights not renormalised (``norm_topk_prob`` false), x the
      scaling factor. The group limit needs every expert's score, on a
      holder of ten of them too."""
    dim: int
    mlp_dim: int
    num_experts: int
    top_k: int
    experts_held: tuple = ()
    use_expert_bias: bool = True
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    shared_mlp_dim: int = 0
    scoring: str = "sigmoid"            # or "softmax"
    n_group: int = 1
    topk_group: int = 1
    activation: str = "swiglu"          # or "relu2"
    latent_dim: int = 0

    @property
    def expert_dim(self) -> int:
        """The width the routed experts read and write."""
        return self.latent_dim or self.dim

    @property
    def held(self) -> tuple:
        return tuple(self.experts_held) or tuple(range(self.num_experts))

    @property
    def groups_held(self) -> tuple:
        """The groups that hold at least one expert held here."""
        per = self.num_experts // self.n_group
        return tuple(sorted({e // per for e in self.held}))


def kept_groups(pick: jnp.ndarray, cfg: TopKMoeConfig):
    """[N, n_group] bool: the ``topk_group`` groups a token may choose
    from, by each group's best ``pick`` (ties to the lower group)."""
    n = pick.shape[0]
    best = jnp.max(pick.reshape(n, cfg.n_group, -1), axis=-1)
    _, top = jax.lax.top_k(best, cfg.topk_group)
    return jnp.any(top[:, :, None] == jnp.arange(cfg.n_group)[None, None],
                   axis=1)


def topk_route(scores: jnp.ndarray, bias, cfg: TopKMoeConfig):
    """([N, k] expert ids, [N, k] weights) from [N, E] router scores
    (sigmoid or softmax already applied): the top-k of ``scores + bias``
    (with ``n_group`` > 1: of those in the token's :func:`kept_groups`, the
    others' taken as 0, as published; ties to the lower id), weighted by
    the unbiased scores of the chosen, renormalised to sum 1 where
    ``norm_topk_prob`` (over their sum + 1e-20, as published), times
    ``routed_scaling_factor``."""
    pick = scores + bias if bias is not None else scores
    if cfg.n_group > 1:
        keep = jnp.repeat(kept_groups(pick, cfg),
                          cfg.num_experts // cfg.n_group, axis=-1)
        pick = jnp.where(keep, pick, 0.0)
    _, sel = jax.lax.top_k(pick, cfg.top_k)
    w = jnp.take_along_axis(scores, sel, axis=-1)
    if cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return sel, w * cfg.routed_scaling_factor


# the grouped products' row tile, and the fewest rows a rung below the top
# may have: a few tiles, so that decode's calls (256-512 pairs) keep one
# body and the scan over decode steps no conditional
_ROW_TILE = 128
_LEAST_RUNG = 8 * _ROW_TILE


def pair_rungs(cfg: TopKMoeConfig, n: int) -> tuple:
    """The row counts :class:`TopKMoeMlp` may size its per-pair arrays by
    in a call on ``n`` tokens, largest first, from shapes alone: ``n x
    top_k`` (every routed pair, so no routing can pass the top rung and the
    layer stays dropless), then halves of the rung before, each up to a
    row tile, for as long as a half holds twice the pairs an even router
    sends the held experts (``n x top_k x held / num_experts``) and has at
    least ``_LEAST_RUNG`` rows. One rung where the call is small (decode),
    every expert is held here, or the way back's kernel does not take the
    shape (``ops/combine_rows.py`` ``combine_block``)."""
    top = n * cfg.top_k
    if not combine_block(n, cfg.expert_dim, cfg.top_k):
        return (top,)
    floor = max(2 * top * len(cfg.held) / cfg.num_experts, _LEAST_RUNG)
    rungs = [top]
    while True:
        half = -(-rungs[-1] // (2 * _ROW_TILE)) * _ROW_TILE
        if half < floor:
            return tuple(rungs)
        rungs.append(half)


def zero_counts(cfg: TopKMoeConfig) -> dict:
    """What :class:`TopKMoeMlp` counts of a call, zeroed: the start of a
    head's sum over its layers and steps."""
    zero = jnp.zeros((), jnp.int32)
    return {"held": jnp.zeros((len(cfg.held),), jnp.int32), "rows": zero,
            "pairs": zero, "group_hits": zero}


class TopKMoeMlp(nn.Module):
    """Dropless top-k routed experts, told which experts it holds.

    The sibling of :class:`RoutedMoeMlp` (top-1, static capacity, drops)
    for serving a published router: every token's ``top_k`` experts are
    chosen over all ``num_experts``; the (token, expert) pairs whose expert
    is held here are sorted by expert and run through the grouped matmuls
    of the activation (three for SwiGLU, two for squared ReLU;
    ``jax.lax.ragged_dot``: XLA's own grouped-matmul lowering on TPU, a
    masked dense product elsewhere), whatever the load of each expert: no
    capacity, no dropped token, all tokens to one expert included. The
    per-pair arrays are as long as the smallest rung of :func:`pair_rungs`
    that holds the held pairs, chosen in the step from their count
    (``jax.lax.switch``, one copy of the body a rung; the top rung is every
    routed pair). Pairs whose expert lives on another holder add nothing
    here: the caller sums the holders' partial outputs (on one chip of a
    deployment, nothing stands in for the others). The stacks hold
    ``len(experts_held)`` experts and carry the "expert" logical axis (ep
    sharding). A shared expert (``shared_mlp_dim``) is every holder's
    alike: its output is added here unweighted, and counted once where
    holders are summed. With a ``latent_dim``, the pairs gather rows of the
    latent ``x W_down``, the per-pair arrays and the way back are at the
    latent width, and ``W_up`` takes the weighted sum back (both
    projections under ``moe_latent``, every holder's alike).

    Returns ``(y [N, d], counts)``, the counts (:func:`zero_counts`)
    ``held`` [held] the pairs each held expert took, ``rows`` the rows
    the per-pair arrays had (the rung run), ``pairs`` the pairs routed in
    all (N x ``top_k``: ``held`` summed over every holder) and
    ``group_hits`` the tokens whose kept groups include one that holds an
    expert held here (every token where the router has no group limit).
    :meth:`routed` is the same call.
    """

    cfg: TopKMoeConfig
    dtype: Dtype = jnp.bfloat16

    def __call__(self, x: jnp.ndarray):
        return self.routed(x)

    @nn.compact
    def routed(self, x: jnp.ndarray):
        c = self.cfg
        held = c.held
        n, d = x.shape
        k, h = c.top_k, len(held)
        gate = self.param(
            "gate",
            nn.with_logical_partitioning(
                nn.initializers.xavier_uniform(), ("embed", "expert_gate")),
            (d, c.num_experts), jnp.float32)
        bias = self.param(
            "expert_bias", nn.initializers.zeros_init(),
            (c.num_experts,), jnp.float32) if c.use_expert_bias else None
        with jax.named_scope("moe_route"):
            logits = jnp.dot(
                x.astype(jnp.float32), gate.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)
            if c.scoring not in ("sigmoid", "softmax"):
                raise ValueError(f"unknown scoring {c.scoring!r}; expected "
                                 "'sigmoid' or 'softmax'")
            scores = (jax.nn.softmax(logits, axis=-1)
                      if c.scoring == "softmax" else jax.nn.sigmoid(logits))
            sel, w = topk_route(scores, bias, c)                # [N, k]
            hits = jnp.asarray(n, jnp.int32)
            if c.n_group > 1:
                pick = scores + bias if bias is not None else scores
                hits = jnp.sum(jnp.any(
                    kept_groups(pick, c)[:, list(c.groups_held)], axis=-1),
                    dtype=jnp.int32)

        # global expert id -> row of this holder's stack, h = elsewhere
        # (built on the host, and counted by comparison: inside a loop the
        # TPU compiler refuses the scatters ``.at[].set`` and ``bincount``
        # would be)
        lut = np.full((c.num_experts,), h, np.int32)
        lut[list(held)] = np.arange(h, dtype=np.int32)
        key = jnp.asarray(lut)[sel].reshape(n * k)
        order = jnp.argsort(key, stable=True)                   # local first
        sizes = jnp.sum(key[:, None] == jnp.arange(h, dtype=jnp.int32)[None],
                        axis=0, dtype=jnp.int32)
        count = jnp.sum(sizes)

        if c.activation not in ("swiglu", "relu2"):
            raise ValueError(f"unknown activation {c.activation!r}; "
                             "expected 'swiglu' or 'relu2'")
        gated, de = c.activation == "swiglu", c.expert_dim
        ws = tuple(a.astype(self.dtype) for a in _expert_weights(
            self, c, stack=h, gated=gated, dim=de))
        # The held pairs sort first, so the first ``count`` rows of
        # ``order`` are all that is computed: each rung moves its C rows,
        # the smallest that holds them is run. A pair's row lies where it
        # sorted; one held elsewhere sorted past ``count``, and the way
        # back (a kernel: XLA would write every pair's row out and read it
        # back) reads only the rows under it.
        rungs = pair_rungs(c, n)
        src = jnp.argsort(order).reshape(n, k)

        def rung(rows):
            def moved(x, order, src, sizes, count, w, *ws):
                with jax.named_scope("moe_experts"):
                    xs = jnp.take(x, order[:rows] // k, axis=0, mode="clip")
                    if gated:
                        w1, w3, w2 = ws
                        up = jax.lax.ragged_dot(xs, w3, sizes)
                        act = nn.silu(jax.lax.ragged_dot(xs, w1, sizes)) * up
                    else:
                        w1, w2 = ws
                        act = jnp.square(nn.relu(
                            jax.lax.ragged_dot(xs, w1, sizes)))
                    ys = jax.lax.ragged_dot(
                        act, w2, sizes, preferred_element_type=jnp.float32)
                if combine_block(n, de, k):
                    return combine_rows(ys, src, w, count)
                # a shape the kernel does not take has the one rung of
                # every pair; rows past the last group are whatever the
                # grouped products left there
                ys = jnp.where((jnp.arange(rows) < count)[:, None], ys, 0.0)
                return jnp.einsum("nkd,nk->nd", jnp.take(ys, src, axis=0), w)
            return moved

        at = jnp.sum(count <= jnp.asarray(rungs[1:], jnp.int32),
                     dtype=jnp.int32)           # the smallest rung that holds
        if c.latent_dim:
            w_down, w_up = (self.param(
                name, nn.with_logical_partitioning(
                    nn.initializers.xavier_uniform(), axes), shape,
                jnp.float32).astype(self.dtype) for name, shape, axes in (
                    ("fc1_latent_proj", (d, de), ("embed", "mlp")),
                    ("fc2_latent_proj", (de, d), ("mlp", "embed"))))
            with jax.named_scope("moe_latent"):
                u = x.astype(self.dtype) @ w_down
        else:
            u = x.astype(self.dtype)
        operands = (u, order, src, sizes, count, w.astype(jnp.float32)) + ws
        y = (rung(rungs[0])(*operands) if len(rungs) == 1 else
             jax.lax.switch(at, [rung(r) for r in rungs], *operands))
        if c.latent_dim:
            with jax.named_scope("moe_latent"):
                y = jnp.dot(y.astype(self.dtype), w_up,
                            preferred_element_type=jnp.float32)
        if c.shared_mlp_dim:
            m = c.shared_mlp_dim
            shared = (("shared_w1", (d, m), ("embed", "mlp")),
                      ("shared_w3", (d, m), ("embed", "mlp")),
                      ("shared_w2", (m, d), ("mlp", "embed")))
            s1, *s3, s2 = (self.param(
                name, nn.with_logical_partitioning(
                    nn.initializers.xavier_uniform(), axes), shape,
                jnp.float32).astype(self.dtype) for name, shape, axes in (
                    shared if gated else shared[::2]))
            with jax.named_scope("moe_shared"):
                xd = x.astype(self.dtype)
                a = (nn.silu(xd @ s1) * (xd @ s3[0]) if gated
                     else jnp.square(nn.relu(xd @ s1)))
                y = y + jnp.dot(a, s2, preferred_element_type=jnp.float32)
        return y.astype(self.dtype), {
            "held": sizes, "rows": jnp.asarray(rungs, jnp.int32)[at],
            "pairs": jnp.asarray(n * k, jnp.int32), "group_hits": hits}


class EncoderBlock(nn.Module):
    cfg: EncoderConfig
    dtype: Dtype = jnp.bfloat16
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray, deterministic: bool = True) -> jnp.ndarray:
        c = self.cfg
        if not c.num_experts:
            mlp_cls = Mlp
        elif c.moe_router == "top1":
            mlp_cls = RoutedMoeMlp
        elif c.moe_router == "soft":
            mlp_cls = MoeMlp
        else:
            raise ValueError(
                f"unknown moe_router {c.moe_router!r}; expected 'soft' or 'top1'"
            )
        # one stable name for every layer's operations in a device trace
        # (flax's own scope is the instance name, block<i>)
        with jax.named_scope("encoder_block"):
            h = nn.LayerNorm(dtype=jnp.float32, name="ln1")(x.astype(jnp.float32)).astype(self.dtype)
            x = x + SelfAttention(c, self.dtype, self.attn_fn, name="attn")(h, deterministic)
            h = nn.LayerNorm(dtype=jnp.float32, name="ln2")(x.astype(jnp.float32)).astype(self.dtype)
            x = x + mlp_cls(c, self.dtype, name="mlp")(h, deterministic)
        return x


class Encoder(nn.Module):
    cfg: EncoderConfig
    dtype: Dtype = jnp.bfloat16
    attn_fn: Optional[AttnFn] = None

    @nn.compact
    def __call__(self, x: jnp.ndarray, deterministic: bool = True) -> jnp.ndarray:
        block = EncoderBlock
        if self.cfg.remat:
            block = nn.remat(EncoderBlock, static_argnums=(2,))
        for i in range(self.cfg.num_layers):
            x = block(self.cfg, self.dtype, self.attn_fn, name=f"block{i}")(
                x, deterministic
            )
        return nn.LayerNorm(dtype=jnp.float32, name="ln_final")(
            x.astype(jnp.float32)
        ).astype(self.dtype)
