"""The Mamba-2 mixer the state-space heads share (``models/falcon_h1.py``,
``models/nemotron_h.py``), with the chunked scan of ``ops/ssd.py``.

Over the normed input ``n`` [B, T, d] (``FalconH1Mixer.torch_forward`` of
transformers' ``modeling_falcon_h1.py`` 777-986; ``NemotronHMamba2Mixer``
of ``modeling_nemotron_h.py`` is the same layer with every multiplier 1):
``p = (n * ssm_in_multiplier) W_in * mup`` (``mup`` scales the five parts
z | x | B | C | dt by ``ssm_multipliers``); ``z, xBC, dt = split(p)``;
``xBC = silu(conv(xBC) + b_conv)``, causal and depthwise over ``d_conv``
taps, its state the last ``d_conv - 1`` positions of ``xBC`` before the
convolution; ``xs, B, C = split(xBC)``, B and C one group's, shared by its
heads; ``dt = softplus(dt + dt_bias)`` (no clamp), ``A = -exp(A_log)``; per
head ``h_t = exp(dt_t A) h_{t-1} + dt_t xs_t (outer) B_t``, ``y_t = h_t C_t
+ D xs_t``; ``y = GroupRMS(y * silu(z))`` over each group's channels, the
gate first unless ``norm_before_gate``; ``m = (y W_out) *
ssm_out_multiplier``. No biases but the convolution's.

A holder keeps ``mamba_heads`` heads in ``mamba_groups`` WHOLE groups (a
group's B, C, its norm and its heads' channels of z, x, dt, the
convolution and W_out): the group norm is then exact with no exchange, and
W_out's product is this holder's partial sum.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..ops.ssd import ssd_scan, ssd_step
from .common import Dtype
from .stream_head import _kernel


@dataclass(frozen=True)
class Mamba2Config:
    """The mixer's sizes (those held) and multipliers. A head's own
    configuration may stand in for it where it has these fields
    (``FalconH1Config`` has them)."""
    dim: int
    mamba_heads: int
    mamba_groups: int
    mamba_head_dim: int
    d_state: int
    d_conv: int
    chunk: int
    norm_eps: float
    norm_before_gate: bool = False
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: Tuple[float, ...] = (1.0,) * 5

    @property
    def ssm_dim(self) -> int:
        """The mixer channels held (z, x, y, W_out's rows)."""
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels of the convolution: x, B and C."""
        return self.ssm_dim + 2 * self.mamba_groups * self.d_state

    @property
    def in_proj_dim(self) -> int:
        """Columns of W_in: z, x, B, C and dt."""
        return self.ssm_dim + self.conv_dim + self.mamba_heads

    def mup(self) -> np.ndarray:
        """[in_proj_dim] float32: ``ssm_multipliers`` over z | x | B | C |
        dt (``compute_mup_vector``)."""
        e, gn = self.ssm_dim, self.mamba_groups * self.d_state
        parts = (e, e, gn, gn, self.mamba_heads)
        return np.concatenate([np.full(n, m, np.float32) for n, m in zip(
            parts, self.ssm_multipliers)])


def gated_norm(y, z, scale, groups: int, eps: float, before_gate: bool):
    """``RMSNormGated``: y [..., e] gated by silu(z) and RMS-normed over
    each of ``groups`` groups of channels, the gate after the norm where
    ``before_gate`` (the norm before the gate), else before it."""
    gate = jax.nn.silu(z)
    if not before_gate:
        y = y * gate
    e = y.shape[-1]
    y = y.reshape(y.shape[:-1] + (groups, e // groups))
    y = (y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
         ).reshape(y.shape[:-2] + (e,)) * scale
    return y * gate if before_gate else y


class Mamba2Mixer(nn.Module):
    """The layer's state-space mixer over the normed input ``n`` [B, T, d],
    continuing each row's ``conv`` [B, d_conv - 1, conv channels] and
    ``ssm`` [B, heads, head_dim, d_state] state; returns (its output
    [B, T, d], the two states after the T positions). ``norm`` is the
    gated norm, as the head that builds the mixer names it."""
    cfg: Mamba2Config
    dtype: Dtype = jnp.bfloat16
    norm: Callable = gated_norm

    @nn.compact
    def __call__(self, n, conv, ssm):
        c, f32 = self.cfg, jnp.float32
        b, t, _ = n.shape
        e, nh, g = c.ssm_dim, c.mamba_heads, c.mamba_groups
        w_in = _kernel(self, "in_proj", (c.dim, c.in_proj_dim),
                       ("embed", "mlp"))
        w_conv = self.param("conv_kernel", nn.initializers.normal(0.5),
                            (c.d_conv, c.conv_dim), f32)
        b_conv = self.param("conv_bias", nn.initializers.zeros_init(),
                            (c.conv_dim,), f32)
        dt_bias = self.param("dt_bias", nn.initializers.zeros_init(),
                             (nh,), f32)
        a_log = self.param("A_log", nn.initializers.zeros_init(), (nh,), f32)
        skip = self.param("D", nn.initializers.ones_init(), (nh,), f32)
        scale = self.param("norm_scale", nn.initializers.ones_init(), (e,),
                           f32)
        w_out = _kernel(self, "out_proj", (e, c.dim), ("mlp", "embed"))
        mup = c.mup()
        with jax.named_scope("head_ssm"):
            if c.ssm_in_multiplier != 1.0:
                n = n * c.ssm_in_multiplier
            p = (n @ w_in.astype(self.dtype)).astype(f32)
            if (mup != 1.0).any():
                p = p * jnp.asarray(mup)
            z, xbc, dt = jnp.split(p, [e, e + c.conv_dim], axis=-1)
            full = jnp.concatenate([conv.astype(f32), xbc], axis=1)
            # tap j multiplies the position (d_conv - 1 - j) back
            xbc = jax.nn.silu(sum(full[:, j:j + t] * w_conv[j]
                                  for j in range(c.d_conv)) + b_conv)
            xs, bb, cc = jnp.split(xbc, [e, e + g * c.d_state], axis=-1)
            xs = xs.reshape(b, t, nh, c.mamba_head_dim)
            bb, cc = (v.reshape(b, t, g, c.d_state) for v in (bb, cc))
            dt = jax.nn.softplus(dt + dt_bias)
            a = -jnp.exp(a_log)
            if t == 1:
                y, ssm = ssd_step(xs[:, 0], dt[:, 0], a, bb[:, 0], cc[:, 0],
                                  skip, ssm)
                y = y[:, None]
            else:
                y, ssm = ssd_scan(xs, dt, a, bb, cc, skip, ssm, c.chunk)
            y = self.norm(y.reshape(b, t, e), z, scale, g, c.norm_eps,
                          c.norm_before_gate)
            m = y.astype(self.dtype) @ w_out.astype(self.dtype)
            if c.ssm_out_multiplier != 1.0:
                m = m * c.ssm_out_multiplier
            return m, full[:, t:], ssm


def seed_state(ins, state, slots, reset, n_i: int):
    """The start of a round of a head whose state is a key-value pool
    (``kv``, read and written in place by slot) and its mixers' ``conv`` and
    ``ssm`` rows (gathered by slot): the instruction's ``n_i`` keys and
    values ``ins["kv"]`` as the first rows of every slot, and its conv and
    SSM state for a stream that resets. Returns (pool, rows)."""
    def rows(kind):
        mine = jnp.take(state[kind], slots, axis=0, mode="clip")
        at = reset.reshape((-1,) + (1,) * (mine.ndim - 1))
        return jnp.where(at, ins[kind].astype(mine.dtype), mine)

    kv = tuple(a.at[..., :n_i].set(jnp.broadcast_to(
        i.astype(a.dtype), a[..., :n_i].shape))
        for a, i in zip(state["kv"], ins["kv"]))
    return kv, (rows("conv"), rows("ssm"))


def commit_rows(state, rows, slots) -> dict:
    """The round's conv and SSM rows written over their slots' (a slot past
    the pool, a padded row, writes nothing)."""
    put = lambda kind, v: state[kind].at[slots].set(  # noqa: E731
        v.astype(state[kind].dtype), mode="drop")
    return {"conv": put("conv", rows[0]), "ssm": put("ssm", rows[1])}
