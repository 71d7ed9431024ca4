"""Mamba-2's state-space layer in its chunked "state-space dual" form
(Dao & Gu 2024; ``modeling_falcon_h1.py`` ``FalconH1Mixer.torch_forward``
writes the same four steps without einsums), and its one-step recurrence.

Per head, with a state ``h`` [P, N] (P channels of the head, N the state
size) and scalars ``a`` < 0 (the head's decay rate) and ``d`` (its skip):

    h_t = exp(dt_t a) h_{t-1} + dt_t x_t (outer) B_t
    y_t = h_t C_t + d x_t

``B`` and ``C`` are a group's, shared by the group's heads (heads ``g H/G
.. (g+1) H/G - 1`` read group ``g``). :func:`ssd_scan` runs a whole
prefill: positions in chunks of ``chunk``; inside a chunk the products
in the quadratic (attention-like) form, between chunks the state passed by
one ``lax.scan``, starting from each stream's carried state and returning
the state it ends in. A position whose ``dt`` is 0 leaves the state as it
was (exp(0) = 1, nothing added): that is how a length that is no multiple
of the chunk is padded. :func:`ssd_step` is one position, the decode step.
Both run in float32 under ``jax.named_scope("ssm_scan")``; the products'
precision is the backend's default for float32 operands, as the published
kernels take their inputs in the model's dtype and accumulate in float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _groups(a, g, axis):
    """The heads' axis ``axis`` of ``a`` split into [G, H/G] (the heads of
    a group consecutive)."""
    axis %= a.ndim
    return a.reshape(a.shape[:axis] + (g, a.shape[axis] // g)
                     + a.shape[axis + 1:])


def ssd_scan(x, dt, a, b, c, d, h0, chunk: int):
    """x [S, T, H, P]; dt [S, T, H] (after the softplus; 0 leaves the state
    unchanged); a [H] (negative), d [H]; b, c [S, T, G, N]; h0 [S, H, P, N]
    each stream's state before its first position. Returns (y [S, T, H, P],
    h [S, H, P, N] after its last position), float32."""
    with jax.named_scope("ssm_scan"):
        f32 = jnp.float32
        x, dt, b, c, h0 = (v.astype(f32) for v in (x, dt, b, c, h0))
        s, t, nh, p = x.shape
        g, n = b.shape[2:]
        pad = -t % chunk
        if pad:
            widen = lambda v: jnp.pad(  # noqa: E731
                v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
            x, dt, b, c = (widen(v) for v in (x, dt, b, c))
        k = (t + pad) // chunk
        cut = lambda v: v.reshape((s, k, chunk) + v.shape[2:])  # noqa: E731
        x, dt, b, c = (cut(v) for v in (x, dt, b, c))
        da = jnp.cumsum(dt * a.astype(f32), axis=2)          # [S, K, L, H]
        xd = _groups(x * dt[..., None], g, -2)      # [S, K, L, G, H/G, P]
        # inside a chunk: y_l = sum_{s <= l} exp(da_l - da_s) (C_l.B_s) xd_s
        cb = jnp.einsum("sklgn,skmgn->skglm", c, b)     # [S, K, G, L, L]
        gap = da[:, :, :, None, :] - da[:, :, None, :, :]   # [S,K,L,L,H]
        causal = jnp.tril(jnp.ones((chunk, chunk), bool))[:, :, None]
        decay = jnp.where(causal, jnp.exp(jnp.where(causal, gap, 0.0)), 0.0)
        mix = _groups(decay, g, -1) * jnp.moveaxis(cb, 2, 4)[..., None]
        y = jnp.einsum("sklmgh,skmghp->sklghp", mix, xd)    # [S,K,L,G,H/G,P]
        # what a chunk adds to the state, decayed to its end
        last = da[:, :, -1:, :]                         # [S, K, 1, H]
        to_end = _groups(jnp.exp(last - da), g, -1)         # [S, K, L, G, H/G]
        added = jnp.einsum("sklgn,sklghp->skghpn", b,
                           xd * to_end[..., None])      # [S,K,G,H/G,P,N]
        # between chunks: the state at each chunk's start, from the carry
        fall = jnp.exp(last[:, :, 0, :])                # [S, K, H]

        def carry(h, step):
            more, f = step
            return h * f[..., None, None] + more, h

        h_end, h_start = jax.lax.scan(
            carry, h0, (jnp.moveaxis(added.reshape(s, k, nh, p, n), 1, 0),
                        jnp.moveaxis(fall, 1, 0)))
        # [S, K, G, H/G, P, N]
        h_start = _groups(jnp.moveaxis(h_start, 0, 1), g, 2)
        # what the state at the chunk's start gives each position
        y = y + jnp.einsum("sklgn,skghpn->sklghp", c, h_start) \
            * _groups(jnp.exp(da), g, -1)[..., None]
        y = y.reshape(s, k * chunk, nh, p)[:, :t]
        return y + d.astype(f32)[:, None] * x.reshape(
            s, k * chunk, nh, p)[:, :t], h_end


def ssd_step(x, dt, a, b, c, d, h):
    """One position: x [S, H, P], dt [S, H], b, c [S, G, N], h [S, H, P, N]
    -> (y [S, H, P], the state after it), float32."""
    with jax.named_scope("ssm_scan"):
        f32 = jnp.float32
        x, dt, b, c, h = (v.astype(f32) for v in (x, dt, b, c, h))
        s, nh, p = x.shape
        g = b.shape[1]
        fall = jnp.exp(dt * a.astype(f32))                  # [S, H]
        h = _groups(h * fall[..., None, None], g, 1) \
            + _groups(x * dt[..., None], g, 1)[..., None] \
            * b[:, :, None, None, :]                        # [S,G,H/G,P,N]
        y = jnp.einsum("sghpn,sgn->sghp", h, c).reshape(s, nh, p)
        return y + d.astype(f32)[:, None] * x, h.reshape(s, nh, p, -1)
