"""On-device preprocessing: uint8 frames in, model-ready bf16 batches out.

Design (SURVEY.md §7 hard part 2 — H2D bandwidth): frames cross PCIe as
uint8 NHWC BGR24 exactly as they sit on the frame bus (1 byte/px; 16×1080p
×30fps ≈ 186 MB/s instead of 745 MB/s as f32). Everything downstream —
BGR→RGB flip, cast, resize, normalize, dtype pack — happens inside the jitted
graph so XLA fuses it into the first conv's input pipeline.

The reference leaves all of this to external clients (``README.md:202``
documents raw BGR24 on the bus; ``examples/opencv_display.py:46-53`` rebuilds
the numpy array client-side). Here it is a device op.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# Standard ImageNet statistics (RGB order), used by every classifier in the
# model zoo.
IMAGENET_MEAN = (0.485, 0.456, 0.406)
IMAGENET_STD = (0.229, 0.224, 0.225)


@functools.lru_cache(maxsize=64)
def _resize_matrix(src: int, dst: int) -> np.ndarray:
    """[dst, src] bilinear resize matrix (antialiased triangle filter for
    downscaling, matching jax.image.resize(method='bilinear') semantics:
    half-pixel centers, per-row weight normalization)."""
    scale = src / dst
    s = max(1.0, scale)                 # antialias: widen kernel when shrinking
    out = np.zeros((dst, src), np.float32)
    for o in range(dst):
        center = (o + 0.5) * scale - 0.5
        lo = int(np.floor(center - s)) + 1
        hi = int(np.ceil(center + s))
        idx = np.arange(lo, hi + 1)
        w = np.maximum(0.0, 1.0 - np.abs(idx - center) / s)
        valid = (idx >= 0) & (idx < src)
        idx, w = idx[valid], w[valid]
        out[o, idx] = w / w.sum()
    return out


def resize_bilinear_mxu(
    x: jnp.ndarray,
    dst_hw: tuple[int, int],
    *,
    in_scale: float | None = None,
    out_dtype: jnp.dtype | None = None,
) -> jnp.ndarray:
    """Separable bilinear resize as two dense matmuls.

    [N, H, W, C] -> [N, h, w, C]. On TPU a gather-based image resize of
    full-HD frames is HBM-layout-bound (~4.5 ms for 16x1080p); expressing
    the same linear map as [h,H] and [w,W] contractions puts it on the MXU
    (~2 ms measured, bounded by the u8->bf16 cast). Weights are trace-time
    constants (lru-cached per geometry).

    ``in_scale`` (round 15, the fused-stem path): accept integer (uint8)
    input directly and fold the ``in_scale`` normalization constant into
    the trace-time row matrix. The resize is linear, so
    ``resize(x * s) == resize_with_scaled_weights(x)`` exactly in exact
    arithmetic — but the per-pixel ``astype(...) * s`` elementwise pass
    over the FULL-RES plane disappears: the only op touching the source
    plane is the first contraction, whose operand convert XLA fuses into
    the matmul read. ``out_dtype`` names the compute/output dtype for this
    path (default bfloat16).
    """
    if in_scale is None:
        if not jnp.issubdtype(x.dtype, jnp.floating):
            raise TypeError(
                f"resize_bilinear_mxu needs a float input, got {x.dtype}; "
                "scale uint8 frames first (frames.astype(...) / 255) or "
                "pass in_scale= to fold the scale into the resize weights"
            )
        dtype = x.dtype
        scale = 1.0
    else:
        dtype = out_dtype or jnp.bfloat16
        scale = float(in_scale)
        x = x.astype(dtype)
    h, w = x.shape[1], x.shape[2]
    th, tw = dst_hw
    if (h, w) == (th, tw):
        return x * jnp.asarray(scale, dtype) if scale != 1.0 else x
    rh = jnp.asarray(_resize_matrix(h, th) * scale, dtype)
    rw = jnp.asarray(_resize_matrix(w, tw), dtype)
    with jax.named_scope("pre_resize"):
        y = jnp.einsum("hH,nHWc->nhWc", rh, x)
        return jnp.einsum("wW,nhWc->nhwc", rw, y)


def pad_channels(x: jnp.ndarray, pad_c: int) -> jnp.ndarray:
    """Zero-pad the trailing channel axis up to ``pad_c`` (lane fill).

    TPU vector registers are 128 lanes wide; a 3-channel image tensor
    feeding the first conv leaves most of the lane dimension idle and the
    im2col/reshape XLA emits for the stem picks a slow layout. Padding
    channels with zeros (3 -> 8 measured +3.2% end-to-end on the yolov8
    stem, LEVERS_r05 "cpad8") is numerically free: zero input channels
    contribute nothing through a conv, so logits are bit-identical once
    the weights are zero-padded to match (models/import_weights.py
    pads checkpoints on load). No-op when ``pad_c`` <= current channels,
    so model configs can default to 0."""
    c = x.shape[-1]
    if pad_c <= c:
        return x
    widths = ((0, 0),) * (x.ndim - 1) + ((0, pad_c - c),)
    return jnp.pad(x, widths)


def preprocess_classify(
    frames_u8: jnp.ndarray,
    size: tuple[int, int] = (224, 224),
    mean: tuple[float, ...] = IMAGENET_MEAN,
    std: tuple[float, ...] = IMAGENET_STD,
    out_dtype: jnp.dtype = jnp.bfloat16,
) -> jnp.ndarray:
    """Classifier path: [N, H, W, 3] uint8 BGR -> [N, h, w, 3] normalized.

    Resize is plain bilinear (stretch, no aspect preservation) — matching
    what CPU clients of the reference typically do before a classifier.
    """
    with jax.named_scope("pre_cast_scale"):
        x = frames_u8.astype(out_dtype) * (1.0 / 255.0)
    x = resize_bilinear_mxu(x, size)[..., ::-1]          # BGR -> RGB, small
    with jax.named_scope("pre_normalize"):
        mean_a = jnp.asarray(mean, dtype=jnp.float32)
        inv_std = jnp.asarray([1.0 / s for s in std], dtype=jnp.float32)
        x = (x.astype(jnp.float32) - mean_a) * inv_std
        return x.astype(out_dtype)


def preprocess_clip(
    clips_u8: jnp.ndarray,
    size: tuple[int, int] = (224, 224),
    mean: tuple[float, ...] = IMAGENET_MEAN,
    std: tuple[float, ...] = IMAGENET_STD,
    out_dtype: jnp.dtype = jnp.bfloat16,
) -> jnp.ndarray:
    """Video path (BASELINE config 5): [N, T, H, W, 3] uint8 -> normalized.

    The temporal axis is just an extra leading axis folded into the batch for
    the resize (SURVEY.md §5.7 — clip length 8 needs no sequence tricks at
    preprocess time).
    """
    n, t = clips_u8.shape[:2]
    flat = clips_u8.reshape((n * t,) + clips_u8.shape[2:])
    out = preprocess_classify(flat, size=size, mean=mean, std=std, out_dtype=out_dtype)
    return out.reshape((n, t) + out.shape[1:])


class LetterboxParams(NamedTuple):
    """Static geometry of a letterbox resize — needed to map detector boxes
    back to source-frame pixel coordinates."""

    scale: float      # source px * scale = letterboxed px
    pad_x: float      # left padding in letterboxed px
    pad_y: float      # top padding in letterboxed px
    new_w: int
    new_h: int


def letterbox_params(src_hw: tuple[int, int], dst: int) -> LetterboxParams:
    """Compute letterbox geometry for a (static) source shape.

    Shapes are static per batch bucket, so this runs in Python at trace time
    and bakes constants into the graph — no dynamic shapes reach XLA.
    """
    h, w = src_hw
    scale = min(dst / h, dst / w)
    new_h, new_w = int(round(h * scale)), int(round(w * scale))
    pad_y = (dst - new_h) / 2.0
    pad_x = (dst - new_w) / 2.0
    return LetterboxParams(scale, pad_x, pad_y, new_w, new_h)


def preprocess_letterbox(
    frames_u8: jnp.ndarray,
    dst: int = 640,
    pad_value: float = 114.0 / 255.0,
    out_dtype: jnp.dtype = jnp.bfloat16,
) -> tuple[jnp.ndarray, LetterboxParams]:
    """Detector path: [N, H, W, 3] uint8 BGR -> [N, dst, dst, 3] letterboxed
    RGB in [0, 1] (the YOLO-family input convention), plus the geometry to
    undo it on output boxes.
    """
    params = letterbox_params(frames_u8.shape[1:3], dst)
    with jax.named_scope("pre_cast_scale"):
        x = frames_u8.astype(out_dtype) * (1.0 / 255.0)
    x = resize_bilinear_mxu(x, (params.new_h, params.new_w))[..., ::-1]
    top = int(round(params.pad_y))
    left = int(round(params.pad_x))
    x = jnp.pad(
        x,
        ((0, 0), (top, dst - params.new_h - top), (left, dst - params.new_w - left), (0, 0)),
        constant_values=pad_value,
    )
    return x.astype(out_dtype), params


@functools.lru_cache(maxsize=64)
def _letterbox_axis_matrix(src: int, new: int, dst: int, offset: int,
                           scale: float = 1.0) -> np.ndarray:
    """[dst, src] matrix for one letterbox axis: the [new, src] resize
    matrix embedded at ``offset``, zero rows elsewhere (the padding band),
    with an optional constant ``scale`` folded into the weights. A single
    contraction with this matrix resizes AND places the image inside the
    letterboxed canvas — no separate ``jnp.pad`` pass."""
    m = np.zeros((dst, src), np.float32)
    m[offset:offset + new] = _resize_matrix(src, new)
    return m * scale


def space_to_depth(x: jnp.ndarray) -> jnp.ndarray:
    """[N, H, W, C] -> [N, H/2, W/2, 4C]: fold 2x2 spatial blocks into
    channels. Channel layout is ``(2a + b) * C + c`` for row offset ``a``,
    column offset ``b`` — the SAME layout models/yolov8.py's in-graph fold
    and models/import_weights.py's kernel rewrite assume, kept in one
    place so the three can never drift."""
    b, h, w, c = x.shape
    x = x.reshape(b, h // 2, 2, w // 2, 2, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(b, h // 2, w // 2, 4 * c)


def preprocess_letterbox_fused(
    frames_u8: jnp.ndarray,
    dst: int = 640,
    pad_value: float = 114.0 / 255.0,
    out_dtype: jnp.dtype = jnp.bfloat16,
) -> tuple[jnp.ndarray, LetterboxParams]:
    """Fused letterbox + normalize + space-to-depth megakernel (round 15).

    [N, H, W, 3] uint8 BGR -> [N, dst/2, dst/2, 12] letterboxed RGB in
    [0, 1], already folded into the s2d layout the ``stem="s2d"`` detect
    stem consumes, plus the same LetterboxParams as
    :func:`preprocess_letterbox`.

    Why a separate kernel (BASELINE.md round-5 rejected the bare s2d fold
    at 0.85x: a standalone 2x2 fold of the full-size bf16 plane is a pure
    VPU relayout, ~1.5 ms of new cost): here the fold is FREE — the
    letterbox row/column matrices are split by output parity at trace
    time, so the two resize matmuls emit the [n, h, w, a, b, c] blocked
    layout directly and the s2d "reshape" is just the final axis collapse
    XLA folds into the matmul output layout. On top of that the 1080p
    source plane is read exactly once (MFU_yolo_r05: the two-pass path's
    u8->bf16 cast pass made preprocess 2.7 ms): 1/255 rides the row
    matrix (resize_bilinear_mxu's in_scale trick), the pad value is a
    trace-time additive mask on the SMALL plane, and the BGR->RGB flip
    happens on the folded output's 3-channel groups.

    Numerics: same linear map as the two-pass path, different summation
    order/rounding points -> tolerance parity with
    ``space_to_depth(preprocess_letterbox(...))``, not bit parity
    (tests/test_stem_s2d.py pins the tolerance). The classic path is
    untouched — its replay checksums stay bit-identical.
    """
    if dst % 2:
        raise ValueError(f"preprocess_letterbox_fused needs an even dst, got {dst}")
    params = letterbox_params(frames_u8.shape[1:3], dst)
    src_h, src_w = frames_u8.shape[1], frames_u8.shape[2]
    top = int(round(params.pad_y))
    left = int(round(params.pad_x))
    half = dst // 2
    # Parity-split letterbox matrices ([2, dst/2, src]): row a of the
    # output's 2x2 block comes from the even/odd rows of the full [dst,
    # src] matrix. 1/255 folds into the row matrix; both are trace-time
    # constants per (geometry, dst).
    rh = _letterbox_axis_matrix(src_h, params.new_h, dst, top, 1.0 / 255.0)
    rw = _letterbox_axis_matrix(src_w, params.new_w, dst, left)
    rh2 = jnp.asarray(np.stack([rh[0::2], rh[1::2]]), out_dtype)
    rw2 = jnp.asarray(np.stack([rw[0::2], rw[1::2]]), out_dtype)
    x = frames_u8.astype(out_dtype)          # fuses into the first matmul
    y = jnp.einsum("ahH,nHWc->nahWc", rh2, x)
    y = jnp.einsum("bwW,nahWc->nhwabc", rw2, y)
    # Pad band: the zero rows of the letterbox matrices left exact zeros
    # outside the resized image; add the pad value there via a trace-time
    # constant mask in the SAME blocked layout (n h w a b broadcast c).
    inside_r = np.zeros((dst,), np.float32)
    inside_r[top:top + params.new_h] = 1.0
    inside_c = np.zeros((dst,), np.float32)
    inside_c[left:left + params.new_w] = 1.0
    outside = (1.0 - np.outer(inside_r, inside_c)) * pad_value
    outside = outside.reshape(half, 2, half, 2).transpose(0, 2, 1, 3)
    y = y + jnp.asarray(outside, out_dtype)[None, :, :, :, :, None]
    # BGR -> RGB on the 3-channel groups, then collapse (a, b, c) ->
    # (2a + b) * 3 + c: the space_to_depth layout (see above).
    y = y[..., ::-1]
    return y.reshape(y.shape[0], half, half, 12).astype(out_dtype), params


def unletterbox_boxes(
    boxes_xyxy: jnp.ndarray, params: LetterboxParams
) -> jnp.ndarray:
    """Map detector-output xyxy boxes (letterboxed px) back to source px."""
    shift = jnp.asarray(
        [params.pad_x, params.pad_y, params.pad_x, params.pad_y],
        dtype=boxes_xyxy.dtype,
    )
    return (boxes_xyxy - shift) / params.scale


# BT.601 luma weights in the bus frame's BGR plane order (channel 0 = B,
# see module docstring — frames cross the bus as raw BGR24).
_LUMA_BGR = (0.114, 0.587, 0.299)


def frame_quality_stats(
    frames_u8: jnp.ndarray,
    prev_thumbs: jnp.ndarray,
    thumb_hw: tuple[int, int],
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Device-side frame-health statistics for obs/quality.py.

    [N, H, W, 3] uint8 BGR + the previous tick's [N, th, tw] f32 luma
    thumbnails -> (stats [N, 3] f32, thumbs [N, th, tw] f32) where the
    stats columns are (luma_mean, luma_var, diff_energy):

    - ``luma_mean`` / ``luma_var`` — mean and variance of the downsampled
      luma plane in [0, 1] (black-frame detection; thumbnail-domain, so
      the variance is a smoothed lower bound of the full-res one — the
      host thresholds in utils/config.py are calibrated to that).
    - ``diff_energy`` — MSE between this frame's thumbnail and the
      per-stream thumbnail carried as device state across ticks
      (frozen-feed detection, and the per-stream motion-gating signal
      MOSAIC-style ROI multiplexing needs, ROADMAP item 1).

    Folded into the serving step (engine/runner.py build_serving_step)
    so the stats ride the existing result transfer: all f32 (norm-stat
    convention), static shapes per (geometry, bucket), the luma
    reduction fuses into the MXU resize matmuls (resize_bilinear_mxu),
    and the [N, th, tw] thumbnail is the only extra device-resident
    state. The previous thumbnail of a stream's first frame is zeros;
    the host tracker discards that first diff.
    """
    w = jnp.asarray(_LUMA_BGR, jnp.float32)
    y = jnp.einsum("nhwc,c->nhw", frames_u8.astype(jnp.float32), w)
    y = y * (1.0 / 255.0)
    thumbs = resize_bilinear_mxu(y[..., None], thumb_hw)[..., 0]
    mean = jnp.mean(thumbs, axis=(1, 2))
    var = jnp.var(thumbs, axis=(1, 2))
    diff = jnp.mean(
        jnp.square(thumbs - prev_thumbs.astype(jnp.float32)), axis=(1, 2))
    return jnp.stack([mean, var, diff], axis=-1), thumbs
