"""Plain float32 reference of the two served transformers.

ViT-B/16 (Dosovitskiy et al. 2020, ``google/vit-base-patch16-224``) and the
VideoMAE-B/16 classifier (Tong et al. 2022, ``MCG-NJU/videomae-base``) as the
served configuration runs them: straightforward ``jax.numpy`` in float32 at
``highest`` matmul precision, no kernels, no batching tricks, its own resize.
It imports nothing from ``video_edge_ai_proxy_tpu`` and is handed weights
made by ``vbench.weights`` from the seed, never anything the program made.

Departures from the published models are the configuration's, listed under
``assumed`` in ``configs/*.json`` (learned position table and a final encoder
LayerNorm before the mean pool for VideoMAE, LayerNorm eps 1e-6, the q/k/v
projection stored as one [dim, 3*dim] matrix laid out (3, heads, head_dim)).
The published GELU is the exact erf form and so is this one; the program's
tanh approximation rides inside the tolerance.

``quant`` selects the control: every matmul's operands rounded to float8
e4m3 with a per-tensor scale (the nearest serving precision below the
configuration's bfloat16), products accumulated in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

IMAGENET_MEAN = np.array([0.485, 0.456, 0.406], np.float32)
IMAGENET_STD = np.array([0.229, 0.224, 0.225], np.float32)


def resize_matrix(src: int, dst: int) -> np.ndarray:
    """[dst, src] antialiased bilinear (triangle filter, half-pixel
    centres, rows normalised) — what ``jax.image.resize(..., 'bilinear')``
    computes when shrinking."""
    scale = src / dst
    support = max(1.0, scale)
    centres = (np.arange(dst, dtype=np.float64) + 0.5) * scale - 0.5
    taps = np.arange(src, dtype=np.float64)
    w = np.clip(1.0 - np.abs(taps[None, :] - centres[:, None]) / support,
                0.0, None)
    return (w / w.sum(axis=1, keepdims=True)).astype(np.float32)


def _fp8(x):
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = 448.0 / amax
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def _einsum(quant):
    def mm(spec, a, b):
        if quant == "fp8":
            a, b = _fp8(a), _fp8(b)
        elif quant:
            raise ValueError(f"unknown control precision {quant!r}")
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
    return mm


def preprocess(frames_u8, size: int, mm):
    """[N, H, W, 3] uint8 BGR -> [N, size, size, 3] float32 RGB, ImageNet
    normalised; plain stretch, no aspect preservation."""
    h, w = frames_u8.shape[1:3]
    x = frames_u8.astype(jnp.float32) / 255.0
    if (h, w) != (size, size):
        x = mm("hH,nHWc->nhWc", jnp.asarray(resize_matrix(h, size)), x)
        x = mm("wW,nhWc->nhwc", jnp.asarray(resize_matrix(w, size)), x)
    x = x[..., ::-1]
    return (x - IMAGENET_MEAN) / IMAGENET_STD


def _layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _gelu(x, act):
    if act == "gelu":
        return 0.5 * x * (1.0 + jax.lax.erf(x / np.sqrt(2.0).astype(np.float32)))
    raise ValueError(f"unknown hidden_act {act!r}")


def encoder(p, x, cfg, mm, prefix="encoder"):
    """Pre-LN encoder: x + attn(ln1 x), x + mlp(ln2 x), final LayerNorm."""
    heads = cfg["num_attention_heads"]
    dim = cfg["hidden_size"]
    hd = dim // heads
    eps = cfg["layer_norm_eps"]
    for i in range(cfg["num_hidden_layers"]):
        b = f"{prefix}/block{i}"
        h = _layer_norm(x, p[f"{b}/ln1/scale"], p[f"{b}/ln1/bias"], eps)
        qkv = mm("btd,de->bte", h, p[f"{b}/attn/qkv/kernel"]) \
            + p[f"{b}/attn/qkv/bias"]
        n, t = qkv.shape[:2]
        qkv = qkv.reshape(n, t, 3, heads, hd)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        scores = mm("bthd,bshd->bhts", q, k) / np.sqrt(hd).astype(np.float32)
        probs = jax.nn.softmax(scores, axis=-1)
        ctx = mm("bhts,bshd->bthd", probs, v).reshape(n, t, dim)
        x = x + mm("btd,de->bte", ctx, p[f"{b}/attn/out/kernel"]) \
            + p[f"{b}/attn/out/bias"]
        h = _layer_norm(x, p[f"{b}/ln2/scale"], p[f"{b}/ln2/bias"], eps)
        h = mm("btd,dm->btm", h, p[f"{b}/mlp/fc1/kernel"]) \
            + p[f"{b}/mlp/fc1/bias"]
        h = _gelu(h, cfg["hidden_act"])
        x = x + mm("btm,md->btd", h, p[f"{b}/mlp/fc2/kernel"]) \
            + p[f"{b}/mlp/fc2/bias"]
    return _layer_norm(x, p[f"{prefix}/ln_final/scale"],
                       p[f"{prefix}/ln_final/bias"], eps)


def vit_logits(p, frames_u8, cfg, quant=""):
    """[N, H, W, 3] uint8 BGR -> [N, num_labels] logits (class token)."""
    mm = _einsum(quant)
    s, ps = cfg["image_size"], cfg["patch_size"]
    g = s // ps
    x = preprocess(frames_u8, s, mm)
    n = x.shape[0]
    x = x.reshape(n, g, ps, g, ps, 3).transpose(0, 1, 3, 2, 4, 5)
    x = x.reshape(n, g * g, ps * ps * 3)
    kernel = p["patch_embed/kernel"].reshape(ps * ps * 3, -1)
    x = mm("ntk,kd->ntd", x, kernel) + p["patch_embed/bias"]
    cls = jnp.broadcast_to(p["cls_token"], (n, 1, x.shape[-1]))
    x = jnp.concatenate([cls, x], axis=1) + p["pos_embed"]
    x = encoder(p, x, cfg, mm)
    return mm("nd,dc->nc", x[:, 0], p["classifier/kernel"]) \
        + p["classifier/bias"]


def videomae_logits(p, clips_u8, cfg, quant=""):
    """[N, T, H, W, 3] uint8 BGR -> [N, num_labels] logits (mean pool)."""
    mm = _einsum(quant)
    s, ps, ts = cfg["image_size"], cfg["patch_size"], cfg["tubelet_size"]
    g = s // ps
    n, t = clips_u8.shape[:2]
    x = preprocess(clips_u8.reshape((n * t,) + clips_u8.shape[2:]), s, mm)
    x = x.reshape(n, t // ts, ts, g, ps, g, ps, 3)
    x = x.transpose(0, 1, 3, 5, 2, 4, 6, 7)
    x = x.reshape(n, (t // ts) * g * g, ts * ps * ps * 3)
    kernel = p["tubelet/proj/kernel"].reshape(ts * ps * ps * 3, -1)
    x = mm("ntk,kd->ntd", x, kernel) + p["tubelet/proj/bias"] + p["pos_embed"]
    x = encoder(p, x, cfg, mm)
    return mm("nd,dc->nc", jnp.mean(x, axis=1), p["head/kernel"]) \
        + p["head/bias"]


FORWARD = {"vit": vit_logits, "videomae": videomae_logits}


@functools.lru_cache(maxsize=None)
def jitted(family: str, cfg_items: tuple, quant: str = ""):
    """The jitted forward for one (family, sizes, precision)."""
    cfg = dict(cfg_items)
    return jax.jit(functools.partial(FORWARD[family], cfg=cfg, quant=quant))
