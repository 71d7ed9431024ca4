"""Nemotron-3-Super's decoder as a per-stream streaming head behind the
VideoMAE encoder (``nemotron_h``: nvidia/NVIDIA-Nemotron-3-Super-120B-A12B
``config.json`` and transformers'
``models/nemotron_h/modeling_nemotron_h.py``).

The fifth head of ROADMAP R9, served exactly where the other four are: the
round, the connector and the cast at load are ``models/stream_head.py``'s,
the attention and its key-value pool ``models/gqa.py``'s, the Mamba-2 mixer
``models/mamba2.py``'s (shared with ``models/falcon_h1.py``), the expert
layer ``models/transformer.py``'s. The stack is the config's
``hybrid_override_pattern``, one character a layer, and every layer is ONE
mixer alone on the residual (``NemotronHBlock``): ``x <- x +
mixer(RMS(x))`` (eps 1e-5, ``residual_in_fp32`` false), the mixer one of

- ``M``, Mamba-2 (``NemotronHMamba2Mixer``): ``mamba2.Mamba2Mixer`` with
  every multiplier 1: ``[z | xBC | dt] = n W_in``, a causal depthwise
  convolution of 4 taps with a bias and SiLU, ``dt = softplus(dt +
  dt_bias)`` with no clamp, ``A = -exp(A_log)``, the state-space recurrence
  with the skip D, ``GroupRMS(y * silu(z))`` over groups of 1,024
  channels (the gate first), ``W_out``; no other bias;
- ``*``, attention (``NemotronHAttention``): GQA, q/k/v/o with no bias and
  no per-head norm, causal softmax at ``head_dim^-1/2``, and **no rotary
  embedding**: the published attention applies none (the model's order
  comes from its mixers), so ``rope_theta`` and ``partial_rotary_factor``
  of the config are not read;
- ``E``, a LATENT expert layer (``NemotronHMOE``; ``transformer.TopKMoeMlp``
  with ``latent_dim``): ``s = sigmoid(x W_r)`` in float32 on the
  full-width input, the top-22 of ``s + e_score_correction_bias`` over all
  512 experts (one group), ``w = 5 s_sel / (sum s_sel + 1e-20)``; ``u = x
  W_down`` (4,096 -> 1,024); ``y = (sum_j w_j relu(u W1_j)^2 W2_j) W_up +
  relu(x S1)^2 S2``: routed experts of 1,024 -> 2,688 -> 1,024 in the
  latent, non-gated squared ReLU, and a shared expert of 4,096 -> 5,376
  -> 4,096 beside them, unweighted.

Around the stack: token embeddings as they are, the connector's visual
tokens as they are, a final RMS norm, an untied head.

**This chip's share** of a layer (the configuration's ``deployment``):
``num_heads`` query heads and ``num_kv_heads`` key-value heads of the
attention, the mixer's ``mamba_groups`` whole groups with their
``mamba_heads`` heads, ``experts_held`` of the 512 experts (the router,
its bias, ``W_down``, ``W_up`` and the shared expert whole, every chip's
alike), ``vocab_size`` rows of the embedding and the head. The
row-parallel products (``o_proj``, ``out_proj``) and the routed experts'
sum give this chip's partial sums; nothing stands in for the other chips.

State, per stream, by kind, each indexed by a layer's place AMONG ITS KIND
(an ``E`` layer holds none): ``kv`` = (keys, values), each [attention
layers, slots, KV, hd, max_context], appended a round at a time by the
shared ``gqa.flush_round``; ``conv`` [slots, mixer layers, 3, conv
channels] and ``ssm`` [slots, mixer layers, heads, head_dim, d_state] in
float32, carried as batch rows and OVERWRITTEN when the round commits.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from . import gqa, mamba2, stream_head
from .common import Dtype
from .gqa import CachedAttention, GqaConfig, flush_round
from .mamba2 import Mamba2Config, Mamba2Mixer
from .stream_head import (Connector, RmsNorm, add_counts, moe_outputs,
                          top_tokens)
from .transformer import TopKMoeConfig, TopKMoeMlp, zero_counts
from .videomae import VideoMAE, VideoMAEConfig, tiny_videomae_config

MIXER, EXPERTS, ATTENTION = "M", "E", "*"


@dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 16384           # rows held: the first eighth of 131,072
    dim: int = 4096
    pattern: str = "MEMEMEM*EME"      # published layers 0-10 of 88
    num_heads: int = 8                # query heads held: 0-7 of 32
    num_kv_heads: int = 1             # key-value heads held: 0 of 2
    head_dim: int = 128
    mamba_heads: int = 32             # mixer heads held: 0-31 of 128
    mamba_groups: int = 2             # groups held: 0-1 of 8
    mamba_head_dim: int = 64
    d_state: int = 128
    d_conv: int = 4
    chunk: int = 128
    moe_mlp_dim: int = 2688           # one routed expert's width
    shared_mlp_dim: int = 5376
    latent_dim: int = 1024
    num_experts: int = 512            # router width
    top_k: int = 22
    experts_held: Tuple[int, ...] = tuple(range(16))
    routed_scaling_factor: float = 5.0
    norm_eps: float = 1e-5
    max_context: int = 4096

    @property
    def num_layers(self) -> int:
        return len(self.pattern)

    @property
    def kinds(self) -> Tuple[Tuple[str, int], ...]:
        """(kind, index among the layers of that kind) of every layer."""
        seen = {MIXER: 0, EXPERTS: 0, ATTENTION: 0}
        out = []
        for kind in self.pattern:
            out.append((kind, seen[kind]))
            seen[kind] += 1
        return tuple(out)

    def count(self, kind: str) -> int:
        return self.pattern.count(kind)

    @property
    def mixer(self) -> Mamba2Config:
        return Mamba2Config(
            dim=self.dim, mamba_heads=self.mamba_heads,
            mamba_groups=self.mamba_groups,
            mamba_head_dim=self.mamba_head_dim, d_state=self.d_state,
            d_conv=self.d_conv, chunk=self.chunk, norm_eps=self.norm_eps)

    @property
    def attn(self) -> GqaConfig:
        return GqaConfig(
            dim=self.dim, num_heads=self.num_heads,
            num_kv_heads=self.num_kv_heads, head_dim=self.head_dim,
            norm_eps=self.norm_eps, rope_theta=0.0, qk_norm=False,
            rotary=False)

    @property
    def moe(self) -> TopKMoeConfig:
        return TopKMoeConfig(
            dim=self.dim, mlp_dim=self.moe_mlp_dim,
            num_experts=self.num_experts, top_k=self.top_k,
            experts_held=tuple(self.experts_held),
            routed_scaling_factor=self.routed_scaling_factor,
            shared_mlp_dim=self.shared_mlp_dim, activation="relu2",
            latent_dim=self.latent_dim)


# the standing instruction: 32 token ids of the held vocabulary slice, a
# constant of the registry entry (the configuration file repeats them)
INSTRUCTION_IDS = tuple((7919 * (i + 1)) % 16381 for i in range(32))


@dataclass(frozen=True)
class StreamHeadConfig(stream_head.StreamHeadConfig):
    """VideoMAE encoder -> connector -> Nemotron-H head, and the round's
    policy."""
    video: VideoMAEConfig = field(default_factory=VideoMAEConfig)
    head: NemotronHConfig = field(default_factory=NemotronHConfig)
    instruction_ids: Tuple[int, ...] = INSTRUCTION_IDS
    # 4 streams a chunk: the expert layer's top rung is every pair of the
    # chunk (3,136 tokens x 22 = 68,992 rows, ~1.2 GB of per-pair
    # temporaries); at 8 the way back's kernel would prefetch two per-pair
    # vectors of 137,984 entries, past the 1 MiB of a core's scalar memory
    # (PERF.md section 4)
    prefill_chunk: int = 4


def tiny_stream_head_config() -> StreamHeadConfig:
    """CPU twin: every mechanism at toy widths: the three kinds (two
    mixers, two expert layers, one attention), two mixer groups, 4 of 16
    experts held and top-4, a latent of 16 under a width of 32, GQA 2:1; a
    chunk of 8 under a round of 50 visual tokens (no multiple of it)."""
    return StreamHeadConfig(
        video=dataclasses.replace(tiny_videomae_config(), image_size=40),
        head=NemotronHConfig(
            vocab_size=96, dim=32, pattern="MEM*E", num_heads=4,
            num_kv_heads=2, head_dim=8, mamba_heads=4, mamba_groups=2,
            mamba_head_dim=8, d_state=8, chunk=8, moe_mlp_dim=24,
            shared_mlp_dim=40, latent_dim=16, num_experts=16, top_k=4,
            experts_held=(0, 1, 2, 3), max_context=224),
        instruction_ids=(5, 17, 3, 90, 41), decode_steps=3,
        prefill_chunk=2)


class NemotronHBlock(nn.Module):
    """One mixer of the layer's kind after its pre-norm; returns what it
    adds to the residual (and, for the kinds with state, that state; for
    the expert layer, its counts)."""
    cfg: NemotronHConfig
    kind: str
    index: int = 0                    # the layer's place among its kind
    dtype: Dtype = jnp.bfloat16

    def setup(self):
        c = self.cfg
        self.norm = RmsNorm(c.norm_eps, self.dtype, name="norm")
        if self.kind == MIXER:
            self.mixer = Mamba2Mixer(c.mixer, self.dtype, name="mixer")
        elif self.kind == ATTENTION:
            self.mixer = CachedAttention(c.attn, self.index, self.dtype,
                                         name="mixer")
        elif self.kind == EXPERTS:
            self.mixer = TopKMoeMlp(c.moe, self.dtype, name="mixer")
        else:
            raise ValueError(f"unknown layer kind {self.kind!r}; expected "
                             f"one of {MIXER + EXPERTS + ATTENTION!r}")

    def __call__(self, x, *state):
        h = self.norm(x)
        if self.kind != EXPERTS:
            return self.mixer(h, *state)
        b, t, d = x.shape
        with jax.named_scope("head_moe"):
            y, n = self.mixer.routed(h.reshape(b * t, d))
        return y.reshape(b, t, d), n


class NemotronHStack(nn.Module):
    """The decoder's layers over [B, T, d] embeddings that continue each
    stream's state at ``pos0`` [B]."""
    cfg: NemotronHConfig
    dtype: Dtype = jnp.bfloat16

    def setup(self):
        c = self.cfg
        table = lambda name: self.param(  # noqa: E731
            name, nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("vocab", "embed")),
            (c.vocab_size, c.dim), jnp.float32)
        self.embed_table, self.lm_head = table("embed"), table("lm_head")
        self.layers = [NemotronHBlock(c, kind, i, self.dtype,
                                      name=f"layer{n}")
                       for n, (kind, i) in enumerate(c.kinds)]
        self.final_norm = RmsNorm(c.norm_eps, self.dtype, name="final_norm")

    def embed(self, ids):
        return jnp.take(self.embed_table, ids, axis=0).astype(self.dtype)

    def logits(self, h):
        """float32 logits over the held rows of the (untied) head from
        [..., d] hidden, through the final norm."""
        with jax.named_scope("head_lm"):
            return jnp.einsum(
                "...d,vd->...v", self.final_norm(h),
                self.lm_head.astype(self.dtype),
                preferred_element_type=jnp.float32)

    def __call__(self, x, conv, ssm, pool, rbuf, slots, pos0, r0=0):
        """x [B, T, d], the round's positions ``r0 .. r0 + T - 1`` of
        streams whose round began at ``pos0`` [B]; conv [B, mixer layers,
        d_conv - 1, conv channels] and ssm [B, mixer layers, heads,
        head_dim, d_state] the rows' own state; pool = (keys, values), each
        [attention layers, slots, KV, hd, S], read only, of which row b owns
        slot ``slots[b]``; rbuf = (keys, values), each [attention layers, B,
        KV, hd, R], the round's own. Returns (h, conv, ssm, rbuf, counts),
        the expert layers' counts (``transformer.zero_counts``) summed."""
        counts = zero_counts(self.cfg.moe)
        rk, rv = rbuf
        x = x.astype(self.dtype)
        for layer, (kind, i) in zip(self.layers, self.cfg.kinds):
            if kind == MIXER:
                y, cs, ss = layer(x, conv[:, i], ssm[:, i])
                conv = conv.at[:, i].set(cs.astype(conv.dtype))
                ssm = ssm.at[:, i].set(ss.astype(ssm.dtype))
            elif kind == ATTENTION:
                y, (k, v) = layer(x, pool, (rk[i], rv[i]), slots, pos0, r0)
                rk, rv = rk.at[i].set(k), rv.at[i].set(v)
            else:
                y, n = layer(x)
                counts = add_counts(counts, n)
            x = x + y
        return x, conv, ssm, (rk, rv), counts


class VideoMAENemotronH(nn.Module):
    """VideoMAE encoder (no classifier) -> connector -> Nemotron-H head."""
    cfg: StreamHeadConfig
    dtype: Dtype = jnp.bfloat16
    # the kind of state the round buffer is flushed into
    cache_kind = "kv"

    def setup(self):
        c = self.cfg
        self.video = VideoMAE(c.video, self.dtype, name="video")
        self.connector = Connector(c.head.dim, self.dtype, name="connector")
        self.head = NemotronHStack(c.head, self.dtype, name="head")

    def encode(self, clips):
        """[B, T, H, W, 3] preprocessed clips -> [B, tokens, head dim]."""
        return self.connector(self.video.features(clips))

    def forward(self, x, conv, ssm, pool, rbuf, slots, pos0, r0=0):
        return self.head(x, conv, ssm, pool, rbuf, slots, pos0, r0)

    def embed(self, ids):
        return self.head.embed(ids)

    def logits(self, h):
        return self.head.logits(h)

    def __call__(self, clips):
        """A fresh stream's first round without a pool: the logits that
        predict its first token, [B, vocab] (what ``init`` traces)."""
        c = self.cfg
        x = self.encode(clips)
        b = x.shape[0]
        ids = jnp.asarray(c.instruction_ids, jnp.int32)
        x = jnp.concatenate([jnp.broadcast_to(
            self.embed(ids)[None], (b, len(ids), c.head.dim)), x], axis=1)
        state = empty_state(c.head, b, 0, self.dtype)
        h = self.head(
            x, state["conv"], state["ssm"], state["kv"],
            self._round_buffer(b, x.shape[1], self.dtype),
            jnp.arange(b), jnp.zeros((b,), jnp.int32))[0]
        return self.logits(h[:, -1])

    @nn.nowrap
    def _round_buffer(self, rows: int, positions: int, dtype):
        hc = self.cfg.head
        return gqa.round_buffer(hc.attn, hc.count(ATTENTION), rows,
                                positions, dtype)

    # what the engine's ``stream`` step kind and the pool ask of a model
    @nn.nowrap
    def empty_state(self, slots: int):
        """Zeroed state for ``slots`` streams by kind (the SSM state in
        float32, the others in the model's dtype), and the axis of each
        kind's buffers that counts the slots."""
        return (empty_state(self.cfg.head, slots, dtype=self.dtype),
                {"conv": 0, "ssm": 0, "kv": 1})

    @nn.nowrap
    def serve_round(self, variables, clips, state, slots, pos0, reset,
                    preprocess=lambda clips: clips):
        return stream_head.serve_round(self, variables, clips, state, slots,
                                       pos0, reset, preprocess)

    @nn.nowrap
    def instruction_state(self, variables):
        """The standing instruction through a fresh state: the conv and SSM
        state and the keys and values every context starts from ({"conv":
        [1, mixer layers, d_conv - 1, channels], "ssm": [1, mixer layers,
        heads, head_dim, d_state], "kv": (keys, values), each [attention
        layers, 1, KV, hd, instruction length]}). A function of the weights
        alone."""
        c, hc = self.cfg, self.cfg.head
        ids = jnp.asarray(c.instruction_ids, jnp.int32)
        zero = jnp.zeros((1,), jnp.int32)
        state = empty_state(hc, 1, 0, self.dtype)
        x = self.apply(variables, ids, method=VideoMAENemotronH.embed)[None]
        _, conv, ssm, kv, _ = self.apply(
            variables, x, state["conv"], state["ssm"], state["kv"],
            self._round_buffer(1, len(ids), self.dtype), zero, zero,
            method=VideoMAENemotronH.forward)
        return {"conv": conv, "ssm": ssm, "kv": kv}

    # what ``stream_head.serve_round`` asks of a head
    @nn.nowrap
    def empty_counts(self):
        return zero_counts(self.cfg.head.moe)

    @nn.nowrap
    def seed_round(self, variables, state, slots, reset):
        """The key-value pool with the instruction's keys and values as
        the first rows of every slot (read and written in place, by slot),
        and the rows' conv and SSM state (gathered by slot), the
        instruction's for a stream that resets."""
        return mamba2.seed_state(variables["instruction"], state, slots,
                                 reset, len(self.cfg.instruction_ids))

    @nn.nowrap
    def round_buffer(self, rows: int, dtype):
        return self._round_buffer(rows, self.cfg.round_positions, dtype)

    @nn.nowrap
    def prefill(self, variables, x, kv, rows, rbuf, slots, pos0):
        h, conv, ssm, rbuf, counts = self.apply(
            variables, x, *rows, kv, rbuf, slots, pos0,
            method=VideoMAENemotronH.forward)
        return h[:, -1], (conv, ssm), rbuf, counts

    @nn.nowrap
    def decode(self, variables, kv, h, rows, rbuf, slots, pos0, counts):
        """D steps that each commit one position a stream."""
        c = self.cfg
        apply = lambda method, *a: self.apply(variables, *a, method=method)  # noqa: E731

        def step(carry, r):
            h, rows, rbuf, counts = carry
            with jax.named_scope("head_decode"):
                tok, top_i, top_p = top_tokens(
                    apply(VideoMAENemotronH.logits, h))
                h, conv, ssm, rbuf, m = apply(
                    VideoMAENemotronH.forward,
                    apply(VideoMAENemotronH.embed, tok)[:, None],
                    *rows, kv, rbuf, slots, pos0, c.visual_tokens + r)
            return ((h[:, 0], (conv, ssm), rbuf, add_counts(counts, m)),
                    (tok, top_i, top_p))

        (_, rows, rbuf, counts), (toks, top_i, top_p) = jax.lax.scan(
            step, (h, rows, rbuf, counts),
            jnp.arange(c.decode_steps, dtype=pos0.dtype))
        hc = c.head
        return {"tokens": toks.T, "top_ids": top_i.transpose(1, 0, 2),
                "top_probs": top_p.transpose(1, 0, 2), "rows": rows,
                "rbuf": rbuf, **moe_outputs(counts),
                # the pairs routed over ALL experts, and the tokens whose
                # kept groups hold one held here (every token: one group)
                "moe_pairs_total": counts["pairs"],
                "moe_group_hits": counts["group_hits"],
                # what the round's prefill attention visited, summed over
                # its chunks and attention layers
                **gqa.prefill_visits(
                    pos0, c.visual_tokens, hc.num_heads // hc.num_kv_heads,
                    gqa.prefill_cap(kv[0].shape[-1], c.round_positions),
                    hc.count(ATTENTION))}

    @nn.nowrap
    def commit_round(self, state, kv, rows, rbuf, slots, pos0):
        """The round's keys and values appended to each slot; its conv and
        SSM state written over the slot's."""
        return {**mamba2.commit_rows(state, rows, slots),
                "kv": flush_round(kv, rbuf, slots, pos0)}


def empty_state(cfg: NemotronHConfig, slots: int, context=None,
                dtype=jnp.bfloat16) -> dict:
    """Zeroed {"conv", "ssm", "kv"} for ``slots`` streams of ``context``
    positions (None: the configuration's ``max_context``): conv and SSM
    state for the mixer layers, keys and values for the attention layers."""
    context = cfg.max_context if context is None else context
    m = cfg.mixer
    layers = cfg.count(MIXER)
    return {
        "conv": jnp.zeros((slots, layers, m.d_conv - 1, m.conv_dim), dtype),
        "ssm": jnp.zeros((slots, layers, m.mamba_heads, m.mamba_head_dim,
                          m.d_state), jnp.float32),
        "kv": gqa.empty_pool(cfg.attn, cfg.count(ATTENTION), slots,
                             context, dtype)}
