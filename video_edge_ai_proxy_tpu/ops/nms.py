"""Greedy NMS as a fixed-iteration device op (SURVEY.md §7 hard part 3).

Greedy NMS has a sequential data dependence (a box survives only if no
higher-scored *surviving* box overlaps it), which is why CPU frameworks do it
host-side with dynamic control flow. On TPU that would mean a D2H sync in the
hot path. Instead we run it as a fixed-K masked suppression:

    keep = 1^K
    for i in 0..K-1:            # K static == max_candidates
        keep &= ~(keep[i] & iou[i, :] > t & j > i)

which is *exactly* greedy NMS (each iteration applies row i's suppression
only if box i itself survived all previous rounds), with static shapes and a
static trip count — XLA/Mosaic compile it without host round-trips.

Two implementations with identical outputs:

- ``nms_keep_mask_pallas`` — single-block Pallas kernel: IoU matrix built in
  VMEM scratch and consumed by the suppression loop on-chip, so the K×K
  matrix never touches HBM.
- ``nms_keep_mask_xla``    — ``lax.fori_loop`` twin; reference semantics and
  the CPU/test path.

``batched_nms`` is the user-facing op: score filter → top-k candidates →
class-offset trick → keep mask → top max_det, vmapped over the batch.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .boxes import box_iou_matrix

# Class-aware NMS via the coordinate-offset trick: boxes of different classes
# are translated far apart so they can never overlap. 8192 px safely exceeds
# any input resolution we letterbox to.
_CLASS_OFFSET = 8192.0


# ---------------------------------------------------------------------------
# XLA implementation (reference semantics; CPU/test path)
# ---------------------------------------------------------------------------


def nms_keep_mask_xla(boxes: jnp.ndarray, iou_thresh: float) -> jnp.ndarray:
    """[K, 4] xyxy boxes sorted by score desc -> [K] bool keep mask."""
    k = boxes.shape[0]
    iou = box_iou_matrix(boxes, boxes)
    idx = jnp.arange(k)

    def body(i, keep):
        suppress = keep[i] & (iou[i] > iou_thresh) & (idx > i)
        return keep & ~suppress

    return lax.fori_loop(0, k, body, jnp.ones((k,), dtype=bool))


# ---------------------------------------------------------------------------
# Pallas implementation
# ---------------------------------------------------------------------------


def _nms_kernel(boxes_ref, boxes_t_ref, out_ref, iou_ref, keep_ref, *, iou_thresh):
    """Single-block kernel. boxes [K, 4], boxes_t [4, K] (same data,
    pre-transposed host-side so every in-kernel broadcast is a clean
    (K,1)×(1,K) -> (K,K) 2-D op on the VPU). Scratch: iou [K, K] f32,
    keep [1, K] f32. Output: [1, K] int32.
    """
    k = boxes_ref.shape[0]

    x1, y1 = boxes_ref[:, 0:1], boxes_ref[:, 1:2]          # [K, 1]
    x2, y2 = boxes_ref[:, 2:3], boxes_ref[:, 3:4]
    x1t, y1t = boxes_t_ref[0:1, :], boxes_t_ref[1:2, :]    # [1, K]
    x2t, y2t = boxes_t_ref[2:3, :], boxes_t_ref[3:4, :]

    inter_w = jnp.maximum(jnp.minimum(x2, x2t) - jnp.maximum(x1, x1t), 0.0)
    inter_h = jnp.maximum(jnp.minimum(y2, y2t) - jnp.maximum(y1, y1t), 0.0)
    inter = inter_w * inter_h                               # [K, K]
    area = jnp.maximum(x2 - x1, 0.0) * jnp.maximum(y2 - y1, 0.0)  # [K, 1]
    area_t = jnp.maximum(x2t - x1t, 0.0) * jnp.maximum(y2t - y1t, 0.0)  # [1, K]
    iou_ref[:, :] = inter / jnp.maximum(area + area_t - inter, 1e-9)

    keep_ref[:, :] = jnp.ones((1, k), dtype=jnp.float32)
    lane = lax.broadcasted_iota(jnp.int32, (1, k), 1)

    # Rows are consumed in blocks of 8: one dynamic-start slice per block,
    # then 8 statically-unrolled suppression steps. Semantics are identical
    # to the row-at-a-time loop (each step still sees every prior update of
    # `keep`), but the fori_loop trip count drops 8× — the loop overhead,
    # not the VPU math, dominates at K=256.
    block = 8 if k % 8 == 0 else 1

    def body(b, _):
        base = b * block
        rows = iou_ref[pl.ds(base, block), :]               # [block, K]
        for r in range(block):
            i = base + r
            row = rows[r:r + 1, :]                          # [1, K]
            # keep[i] as a broadcastable scalar (no dynamic lane indexing).
            keep_i = jnp.sum(jnp.where(lane == i, keep_ref[:, :], 0.0))
            suppress = (row > iou_thresh) & (lane > i) & (keep_i > 0.0)
            keep_ref[:, :] = jnp.where(suppress, 0.0, keep_ref[:, :])
        return 0

    lax.fori_loop(0, k // block, body, 0)
    out_ref[:, :] = (keep_ref[:, :] > 0.0).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("iou_thresh", "interpret"))
def _nms_pallas_call(boxes, boxes_t, *, iou_thresh, interpret):
    k = boxes.shape[0]
    kernel = functools.partial(_nms_kernel, iou_thresh=iou_thresh)
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((1, k), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((k, k), jnp.float32),
            pltpu.VMEM((1, k), jnp.float32),
        ],
        interpret=interpret,
    )(boxes, boxes_t)
    return out[0] > 0


def nms_keep_mask_pallas(
    boxes: jnp.ndarray, iou_thresh: float, interpret: Optional[bool] = None
) -> jnp.ndarray:
    """Pallas twin of :func:`nms_keep_mask_xla`. ``interpret`` defaults to
    True off-TPU so tests exercise the same kernel body on CPU."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    boxes = boxes.astype(jnp.float32)
    return _nms_pallas_call(
        boxes, boxes.T, iou_thresh=float(iou_thresh), interpret=interpret
    )


def nms_keep_mask(boxes: jnp.ndarray, iou_thresh: float) -> jnp.ndarray:
    """Backend-dispatching keep mask ([K,4] sorted-desc boxes -> [K] bool)."""
    if jax.default_backend() == "tpu":
        return nms_keep_mask_pallas(boxes, iou_thresh)
    return nms_keep_mask_xla(boxes, iou_thresh)


# ---------------------------------------------------------------------------
# User-facing batched op
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=(
        "iou_thresh",
        "score_thresh",
        "max_candidates",
        "max_det",
        "use_pallas",
        "approx_topk",
    ),
)
def batched_nms(
    boxes: jnp.ndarray,
    scores: jnp.ndarray,
    classes: Optional[jnp.ndarray] = None,
    *,
    iou_thresh: float = 0.45,
    score_thresh: float = 0.25,
    max_candidates: int = 256,
    max_det: int = 100,
    use_pallas: Optional[bool] = None,
    approx_topk: bool = False,
):
    """Class-aware batched NMS with fully static shapes.

    boxes: [B, A, 4] xyxy; scores: [B, A]; classes: [B, A] int32 (or None
    for class-agnostic). Returns (boxes [B, max_det, 4], scores [B, max_det],
    classes [B, max_det], valid [B, max_det]); invalid slots are zeroed.
    A is the raw anchor count (e.g. 8400 at 640²); the O(K²) suppression only
    sees the top ``max_candidates``.

    ``approx_topk`` (default off) selects the candidate set with
    ``lax.approx_max_k`` instead of an exact sort: ~0.95 expected recall at
    the candidate cut line, exact ranking among what it returns
    (aggregate_to_topk). Caveat before enabling: approx_max_k bins are
    contiguous *index* ranges, so a dropped anchor is a bin-collision loser
    — often a same-object neighbour, but a distinct lower-scored object
    sharing a bin with a stronger detection (across a grid-row wrap or a
    pyramid-level boundary) can be lost before NMS sees it. Measured gain
    on TPU at the north-star shape is ~3 % of NMS time, which is why exact
    selection stays the default on every backend.
    """
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    if classes is None:
        classes = jnp.zeros(scores.shape, dtype=jnp.int32)
    num_anchors = scores.shape[-1]
    n_cand = min(max_candidates, num_anchors)
    n_det = min(max_det, n_cand)

    def single(boxes_i, scores_i, classes_i):
        scores_i = jnp.where(scores_i >= score_thresh, scores_i, 0.0)
        if approx_topk and n_cand < num_anchors:
            top_scores, top_idx = lax.approx_max_k(scores_i, n_cand)
        else:
            top_scores, top_idx = lax.top_k(scores_i, n_cand)
        top_boxes = boxes_i[top_idx]
        top_classes = classes_i[top_idx]
        shifted = top_boxes + (top_classes[:, None].astype(top_boxes.dtype)) * _CLASS_OFFSET
        # Zero-score (filtered) slots become degenerate boxes at the class-0
        # origin: IoU 0 with everything, then re-filtered by `valid` below.
        shifted = jnp.where(top_scores[:, None] > 0.0, shifted, 0.0)
        if use_pallas:
            keep = nms_keep_mask_pallas(shifted, iou_thresh)
        else:
            keep = nms_keep_mask_xla(shifted, iou_thresh)
        kept_scores = jnp.where(keep, top_scores, 0.0)
        out_scores, out_idx = lax.top_k(kept_scores, n_det)
        valid = out_scores > 0.0
        out_boxes = jnp.where(valid[:, None], top_boxes[out_idx], 0.0)
        out_classes = jnp.where(valid, top_classes[out_idx], 0)
        pad = max_det - n_det  # keep the public output shape stable
        if pad:
            out_boxes = jnp.pad(out_boxes, ((0, pad), (0, 0)))
            out_scores = jnp.pad(out_scores, (0, pad))
            out_classes = jnp.pad(out_classes, (0, pad))
            valid = jnp.pad(valid, (0, pad))
        return out_boxes, out_scores, out_classes, valid

    return jax.vmap(single)(
        boxes.astype(jnp.float32), scores.astype(jnp.float32), classes
    )
