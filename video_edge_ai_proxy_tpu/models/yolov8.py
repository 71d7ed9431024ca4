"""YOLOv8 detector — BASELINE configs 2 & north star (16×1080p, ≥1000 fps).

Anchor-free YOLOv8 (CSP backbone with C2f blocks, SPPF, PAN-FPN neck,
decoupled DFL head) in NHWC bf16. Everything through box decode is one
jitted graph with static shapes; NMS lives in `ops/nms.py` (Pallas on TPU).

TPU notes:
- All three head levels are decoded in-graph and concatenated to the flat
  [B, A, ...] layout the NMS op consumes — no host-side glue between
  forward and postprocess.
- DFL decode (softmax-expectation over 16 bins) is a [*, 4, 16] × [16]
  contraction — trivially fused by XLA.
- The nano scaling (depth 0.33 / width 0.25) is a config, not a fork:
  s/m/l/x are the same module tree.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Sequence

import flax.linen as nn
import jax.numpy as jnp

from ..ops.boxes import dist_to_bbox
from ..ops.preprocess import pad_channels
from .common import ConvBN, Dtype, make_divisible, round_depth


@dataclass(frozen=True)
class YOLOv8Config:
    num_classes: int = 80
    depth_mult: float = 0.33      # n
    width_mult: float = 0.25      # n
    max_channels: int = 1024
    reg_max: int = 16             # DFL bins
    strides: Sequence[int] = (8, 16, 32)
    # Stem variant. "classic": stride-2 3x3 conv on [B,S,S,3] (the stock
    # architecture, the checkpoint contract). "s2d": space-to-depth stem
    # (round 15) — fold 2x2 spatial blocks into channels (3 -> 12), then a
    # stride-1 2x2 conv with asymmetric ((1,0),(1,0)) padding on the 320²
    # plane. Same output geometry, 4x the input lanes for the MXU, and —
    # unlike the rejected round-5 s2d experiment (a fresh 3x3 stem that
    # broke checkpoints and lost 0.85x) — EXACTLY the same function: every
    # classic stem kernel folds losslessly into the 2x2 layout
    # (models/import_weights.py s2d_fold_kernel), so stock checkpoints
    # transfer and detections stay numerically equivalent.
    stem: str = "classic"
    # int8 activation path (round 15): every ConvBN except the stem runs
    # int8 x int8 against calibrated per-tensor input scales and in-graph
    # per-output-channel weight scales (models/common.py _Int8Conv). The
    # param tree is identical to fp, so checkpoints serve either way after
    # a calibration pass (models/quantize.py calibrate_serving). Serving
    # only; head 1x1 out-convs and DFL/NMS decode stay fp32.
    act_int8: bool = False
    # Channel-padded stem (the one lane-fill lever that DOES transfer
    # checkpoints): zero-pad the input from 3 to this many channels before
    # the stem conv, whose kernel grows [3,3,3,C]->[3,3,pad,C]. The extra
    # input planes are zeros, so ANY weights in the extra kernel channels
    # produce identical outputs — an imported checkpoint just zero-pads
    # its stem kernel (models/import_weights.py). 0 = off.
    stem_pad_c: int = 0

    def ch(self, c: int) -> int:
        return make_divisible(min(c, self.max_channels) * self.width_mult)

    def depth(self, n: int) -> int:
        return round_depth(n, self.depth_mult)


def yolov8n_config(num_classes: int = 80) -> YOLOv8Config:
    # stem_pad_c=8: measured +3.2% on the device step at the north-star
    # shape (two runs, 12.35/12.36 vs 12.74 ms; rounds 3 and 5,
    # LEVERS_r05.json), reproducible, and checkpoint-transferable (the importer
    # zero-pads the stem kernel). The round-5 s2d experiment lost 0.85x
    # AND broke checkpoints; the round-15 stem="s2d" is a different,
    # lossless fold — see YOLOv8Config.stem. pad_channels no-ops when the
    # input already has >= pad channels, so stem_pad_c=8 is inert under
    # the 12-channel s2d plane.
    return YOLOv8Config(num_classes=num_classes, stem_pad_c=8)


def yolov8s_config(num_classes: int = 80) -> YOLOv8Config:
    return YOLOv8Config(num_classes=num_classes, depth_mult=0.33,
                        width_mult=0.5, stem_pad_c=8)


def tiny_yolov8_config(num_classes: int = 4) -> YOLOv8Config:
    """Test config: 1/8 width, input 64² -> 84 anchors."""
    return YOLOv8Config(num_classes=num_classes, depth_mult=0.33, width_mult=0.125)


class Bottleneck(nn.Module):
    features: int
    shortcut: bool = True
    dtype: Dtype = jnp.bfloat16
    act_int8: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray, train: bool = False) -> jnp.ndarray:
        q = self.act_int8
        h = ConvBN(self.features, kernel=3, dtype=self.dtype, act_int8=q,
                   name="cv1")(x, train)
        h = ConvBN(self.features, kernel=3, dtype=self.dtype, act_int8=q,
                   name="cv2")(h, train)
        if self.shortcut and x.shape[-1] == self.features:
            h = h + x
        return h


class C2f(nn.Module):
    """Cross-stage partial block: split, n bottlenecks, dense concat."""

    features: int
    n: int = 1
    shortcut: bool = True
    dtype: Dtype = jnp.bfloat16
    act_int8: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray, train: bool = False) -> jnp.ndarray:
        hidden = self.features // 2
        q = self.act_int8
        h = ConvBN(2 * hidden, kernel=1, dtype=self.dtype, act_int8=q,
                   name="cv1")(x, train)
        parts = [h[..., :hidden], h[..., hidden:]]
        for i in range(self.n):
            parts.append(
                Bottleneck(hidden, self.shortcut, self.dtype, q, name=f"m{i}")(
                    parts[-1], train
                )
            )
        return ConvBN(self.features, kernel=1, dtype=self.dtype, act_int8=q,
                      name="cv2")(jnp.concatenate(parts, axis=-1), train)


class SPPF(nn.Module):
    """Spatial pyramid pooling (fast): 3 chained 5×5 maxpools, concat."""

    features: int
    dtype: Dtype = jnp.bfloat16
    act_int8: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray, train: bool = False) -> jnp.ndarray:
        hidden = self.features // 2
        h = ConvBN(hidden, kernel=1, dtype=self.dtype, act_int8=self.act_int8,
                   name="cv1")(x, train)
        pools = [h]
        for _ in range(3):
            pools.append(nn.max_pool(pools[-1], (5, 5), strides=(1, 1), padding="SAME"))
        return ConvBN(self.features, kernel=1, dtype=self.dtype,
                      act_int8=self.act_int8, name="cv2")(
            jnp.concatenate(pools, axis=-1), train
        )


def _upsample2(x: jnp.ndarray) -> jnp.ndarray:
    """Nearest ×2 — pure reshape/broadcast, no gather."""
    b, h, w, c = x.shape
    x = jnp.broadcast_to(x[:, :, None, :, None, :], (b, h, 2, w, 2, c))
    return x.reshape(b, 2 * h, 2 * w, c)


class DetectHead(nn.Module):
    """Decoupled per-level head: box branch (4·reg_max DFL logits) and class
    branch (num_classes logits)."""

    cfg: YOLOv8Config
    level_ch: Sequence[int]
    dtype: Dtype = jnp.bfloat16
    act_int8: bool = False

    @nn.compact
    def __call__(self, feats, train: bool = False):
        import math

        c = self.cfg
        c_box = max(16, self.level_ch[0] // 4, c.reg_max * 4)
        c_cls = max(self.level_ch[0], min(c.num_classes, 100))
        q = self.act_int8
        outs = []
        for i, f in enumerate(feats):
            box = ConvBN(c_box, kernel=3, dtype=self.dtype, act_int8=q,
                         name=f"box{i}_cv1")(f, train)
            box = ConvBN(c_box, kernel=3, dtype=self.dtype, act_int8=q,
                         name=f"box{i}_cv2")(box, train)
            # DFL bin prior: decay the bias over distance bins so the
            # initial expected ltrb distance is ~1.5 strides instead of
            # the uniform-softmax 7.5. Random-init boxes then start near
            # object scale, so first-assignment IoUs (the TAL target
            # weights) are O(0.1) rather than O(0.001) — without this,
            # from-scratch fine-tunes spend hundreds of steps in a
            # background-suppression-only regime before any positive
            # signal emerges. Imported checkpoints overwrite it.
            dfl_prior = jnp.tile(
                -0.5 * jnp.arange(c.reg_max, dtype=jnp.float32), 4)
            box = nn.Conv(4 * c.reg_max, (1, 1), dtype=jnp.float32, name=f"box{i}_out",
                          bias_init=lambda *_a, v=dfl_prior: v)(
                box.astype(jnp.float32)
            )
            # Prior bias (the ultralytics Detect.bias_init scheme): start
            # class probabilities at roughly 5 objects per 640-px image
            # per level instead of sigmoid(0)=0.5 on every anchor. From
            # scratch, a zero bias makes the initial loss almost entirely
            # background BCE — the fastest descent direction is "push all
            # logits down", which outruns the positives and collapses the
            # head (see detect_loss.assign's relative-floor note).
            # Imported checkpoints overwrite these values.
            prior = math.log(5 / c.num_classes / (640 / c.strides[i]) ** 2)
            cls = ConvBN(c_cls, kernel=3, dtype=self.dtype, act_int8=q,
                         name=f"cls{i}_cv1")(f, train)
            cls = ConvBN(c_cls, kernel=3, dtype=self.dtype, act_int8=q,
                         name=f"cls{i}_cv2")(cls, train)
            cls = nn.Conv(c.num_classes, (1, 1), dtype=jnp.float32, name=f"cls{i}_out",
                          bias_init=nn.initializers.constant(prior))(
                cls.astype(jnp.float32)
            )
            outs.append((box, cls))
        return outs


def _anchor_points(h: int, w: int, stride: int):
    """Cell-center anchor points in input pixels, [h*w, 2] (x, y)."""
    ys = (jnp.arange(h, dtype=jnp.float32) + 0.5) * stride
    xs = (jnp.arange(w, dtype=jnp.float32) + 0.5) * stride
    gx, gy = jnp.meshgrid(xs, ys)
    return jnp.stack([gx.reshape(-1), gy.reshape(-1)], axis=-1)


def decode_level(box_logits, stride: int, reg_max: int):
    """DFL decode one level: [B, h, w, 4*reg_max] -> xyxy [B, h*w, 4] px."""
    b, h, w, _ = box_logits.shape
    logits = box_logits.reshape(b, h * w, 4, reg_max)
    probs = nn.softmax(logits, axis=-1)
    bins = jnp.arange(reg_max, dtype=jnp.float32)
    dist = jnp.einsum("bafr,r->baf", probs, bins) * stride   # ltrb, px
    return dist_to_bbox(dist, _anchor_points(h, w, stride))


class YOLOv8(nn.Module):
    cfg: YOLOv8Config
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jnp.ndarray, train: bool = False, decode=True):
        """[B, S, S, 3] normalized RGB -> head output, by ``decode`` mode:

        - ``True``: decoded ``(boxes [B,A,4], scores [B,A,C])``, scores are
          per-class sigmoid probabilities (the stable public contract).
        - ``False``: raw per-level ``(box_logits, cls_logits)`` pairs (the
          detection-loss path).
        - ``"serving"``: ``(boxes [B,A,4], max_logit [B,A], cls_ids [B,A])``
          — class reduction in logit space. Sigmoid is monotone, so
          ``sigmoid(max_logit)`` equals the decode=True best-class score and
          ``cls_ids`` its argmax, but the sigmoid over all A×C logits never
          happens; the serving engine applies it to the A winners only.
          Every ``kind="detect"`` registry model supports this mode — it is
          the contract `engine/runner.py` serves detectors through.
        """
        c = self.cfg
        d, ch = c.depth, c.ch
        q = c.act_int8
        x = x.astype(self.dtype)

        # Backbone
        if c.stem == "s2d":
            # Accepts either the raw [B, S, S, 3] plane (folds it here) or
            # the pre-folded [B, S/2, S/2, 12] plane straight out of
            # ops/preprocess.preprocess_letterbox_fused.
            if x.shape[-1] == 3:
                from ..ops.preprocess import space_to_depth

                x = space_to_depth(x)
            x = pad_channels(x, c.stem_pad_c)
            # Stride-1 2x2 conv, pad ((1,0),(1,0)): the lossless fold of
            # the classic stride-2 3x3 conv onto the s2d plane — output
            # pixel p of the classic stem reads input rows 2p-1..2p+1,
            # which land in s2d rows p-1 (offset 1) and p (offsets 0/1);
            # the leading pad supplies the p-1 = -1 zero row exactly like
            # the classic conv's top padding. Taps the classic kernel
            # never reads are zero in the folded kernel
            # (models/import_weights.py s2d_fold_kernel). Kept fp even
            # under act_int8 (first-layer exemption, standard PTQ rule).
            x = ConvBN(ch(64), kernel=2, stride=1, padding=((1, 0), (1, 0)),
                       dtype=self.dtype, name="stem")(x, train)              # P1
        else:
            # Lane-fill: zero input planes cost bandwidth but let XLA
            # tile the stem conv with full input-channel vectors.
            x = pad_channels(x, c.stem_pad_c)
            x = ConvBN(ch(64), stride=2, dtype=self.dtype, name="stem")(x, train)   # P1
        x = ConvBN(ch(128), stride=2, dtype=self.dtype, act_int8=q,
                   name="down2")(x, train)                                   # P2
        x = C2f(ch(128), d(3), True, self.dtype, q, name="c2f_2")(x, train)
        x = ConvBN(ch(256), stride=2, dtype=self.dtype, act_int8=q,
                   name="down3")(x, train)                                   # P3
        p3 = C2f(ch(256), d(6), True, self.dtype, q, name="c2f_3")(x, train)
        x = ConvBN(ch(512), stride=2, dtype=self.dtype, act_int8=q,
                   name="down4")(p3, train)                                  # P4
        p4 = C2f(ch(512), d(6), True, self.dtype, q, name="c2f_4")(x, train)
        x = ConvBN(ch(1024), stride=2, dtype=self.dtype, act_int8=q,
                   name="down5")(p4, train)                                  # P5
        x = C2f(ch(1024), d(3), True, self.dtype, q, name="c2f_5")(x, train)
        p5 = SPPF(ch(1024), self.dtype, q, name="sppf")(x, train)

        # PAN-FPN neck
        x = jnp.concatenate([_upsample2(p5), p4], axis=-1)
        n4 = C2f(ch(512), d(3), False, self.dtype, q, name="neck_up4")(x, train)
        x = jnp.concatenate([_upsample2(n4), p3], axis=-1)
        n3 = C2f(ch(256), d(3), False, self.dtype, q, name="neck_up3")(x, train)  # out P3
        x = ConvBN(ch(256), stride=2, dtype=self.dtype, act_int8=q,
                   name="neck_down4")(n3, train)
        o4 = C2f(ch(512), d(3), False, self.dtype, q, name="neck_out4")(
            jnp.concatenate([x, n4], axis=-1), train
        )                                                                            # out P4
        x = ConvBN(ch(512), stride=2, dtype=self.dtype, act_int8=q,
                   name="neck_down5")(o4, train)
        o5 = C2f(ch(1024), d(3), False, self.dtype, q, name="neck_out5")(
            jnp.concatenate([x, p5], axis=-1), train
        )                                                                            # out P5

        levels = [n3, o4, o5]
        head_out = DetectHead(
            c, [f.shape[-1] for f in levels], self.dtype, q, name="detect"
        )(levels, train)

        if decode is False:
            return head_out

        boxes, cls_flat = [], []
        for (box_l, cls_l), stride in zip(head_out, c.strides):
            boxes.append(decode_level(box_l, stride, c.reg_max))
            b_, h_, w_, _ = cls_l.shape
            cls_flat.append(cls_l.reshape(b_, h_ * w_, c.num_classes))
        boxes = jnp.concatenate(boxes, axis=1)
        cls_flat = jnp.concatenate(cls_flat, axis=1)
        if decode == "serving":
            return (boxes, cls_flat.max(axis=-1),
                    cls_flat.argmax(axis=-1).astype(jnp.int32))
        return boxes, nn.sigmoid(cls_flat)
