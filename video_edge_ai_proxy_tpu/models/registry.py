"""Model registry: one name → everything the inference engine needs.

The engine (`engine/runner.py`) is model-agnostic; a `ModelSpec` bundles the
module, its input geometry, which device-side preprocess to use, and how to
turn raw outputs into wire-ready results. The five registered defaults are
the five BASELINE.json configs; registering a new family is one entry, not
an engine change.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .blob import BlobGauge, BlobGaugeConfig
from .lfm2 import StreamHeadConfig, VideoMAELfm2, tiny_stream_head_config
from .mobilenet_v2 import MobileNetV2, MobileNetV2Config, tiny_mobilenet_v2_config
from .resnet import ResNet, ResNetConfig, tiny_resnet_config
from .stream_head import prepare_for_serving
from .videomae import VideoMAE, VideoMAEConfig, tiny_videomae_config
from .vit import ViT, ViTConfig, tiny_vit_config
from .yolov8 import YOLOv8, tiny_yolov8_config, yolov8n_config, yolov8s_config


@dataclass(frozen=True)
class ModelSpec:
    name: str
    build: Callable[[], Any]              # () -> nn.Module
    input_size: int                       # square side the model consumes
    preprocess: str                       # "classify" | "letterbox" | "clip"
    kind: str                             # "classify" | "detect" | "embed" | "video" | "stream"
    clip_len: int = 0                     # >0 for video models
    description: str = ""
    # (module, variables) -> variables, applied once when the engine takes
    # the model (a stream head's weights are cast to bfloat16 here and its
    # instruction's state is computed)
    prepare: Optional[Callable[[Any, Any], Any]] = None

    def init_params(self, rng: Optional[jax.Array] = None, batch: int = 1):
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        model = self.build()
        x = jnp.zeros(self.example_shape(batch), jnp.bfloat16)
        # jit the init: eager per-op dispatch costs seconds of compile time
        # per op on some backends; one fused compile is orders faster.
        return model, jax.jit(model.init)(rng, x)

    def example_shape(self, batch: int = 1) -> Tuple[int, ...]:
        s = self.input_size
        if self.clip_len:
            return (batch, self.clip_len, s, s, 3)
        return (batch, s, s, 3)


_REGISTRY: Dict[str, ModelSpec] = {}


def register(spec: ModelSpec) -> ModelSpec:
    _REGISTRY[spec.name] = spec
    return spec


def get(name: str) -> ModelSpec:
    if name not in _REGISTRY:
        raise KeyError(
            f"unknown model '{name}'; registered: {sorted(_REGISTRY)}"
        )
    return _REGISTRY[name]


def names() -> list:
    return sorted(_REGISTRY)


# --- BASELINE.json configs 1-5 -------------------------------------------

register(ModelSpec(
    "mobilenet_v2", lambda: MobileNetV2(MobileNetV2Config()),
    input_size=224, preprocess="classify", kind="classify",
    description="config 1: single-stream frame classification",
))
register(ModelSpec(
    "yolov8n", lambda: YOLOv8(yolov8n_config()),
    input_size=640, preprocess="letterbox", kind="detect",
    description="config 2 + north star: batched detection",
))
register(ModelSpec(
    "yolov8n_s2d", lambda: YOLOv8(
        dataclasses.replace(yolov8n_config(), stem="s2d")
    ),
    input_size=640, preprocess="letterbox", kind="detect",
    description="north-star variant: space-to-depth stem (round 15) — "
                "2x2 stride-1 stem on the folded 320²x12 plane; stock "
                "yolov8n checkpoints transfer via the lossless kernel "
                "fold (models/import_weights.py s2d_fold_kernel), "
                "detections numerically equivalent",
))
register(ModelSpec(
    "yolov8s", lambda: YOLOv8(yolov8s_config()),
    input_size=640, preprocess="letterbox", kind="detect",
    description="small-variant detection",
))
register(ModelSpec(
    "resnet50", lambda: ResNet(ResNetConfig()),
    input_size=224, preprocess="classify", kind="embed",
    description="config 3: 16-stream re-ID feature extraction",
))
register(ModelSpec(
    "vit_b16", lambda: ViT(ViTConfig()),
    input_size=224, preprocess="classify", kind="classify",
    description="config 4: 32-stream frame tagging",
))
register(ModelSpec(
    "videomae_b", lambda: VideoMAE(VideoMAEConfig()),
    input_size=224, preprocess="clip", kind="video", clip_len=8,
    description="config 5: 8-frame clip action recognition",
))
register(ModelSpec(
    "videomae_b_long", lambda: VideoMAE(VideoMAEConfig(num_frames=64)),
    input_size=224, preprocess="clip", kind="video", clip_len=64,
    description="long-context clips: 64 frames -> 6272 tokens, attention "
                "auto-dispatches to the Pallas flash kernel",
))

register(ModelSpec(
    "videomae_b_lfm2", lambda: VideoMAELfm2(StreamHeadConfig()),
    input_size=224, preprocess="clip", kind="stream", clip_len=8,
    prepare=prepare_for_serving,
    description="streaming video-language head (models/lfm2.py): VideoMAE-B "
                "encoder -> connector -> LFM2-MoE decoder at the published "
                "widths, one chip's share (9 layers, 16 of 64 experts a "
                "layer); per-stream conv state and key-value cache in "
                "engine/stream_state.py; 784 tokens prefilled and 8 decoded "
                "a stream a round",
))



def _xing4(tiny: bool = False):
    """The second streaming head, imported when it is first built: a
    process that serves another model never loads its module."""
    from . import xing4

    if tiny:
        return xing4.VideoMAEXing4(xing4.tiny_stream_head_config(),
                                   dtype=jnp.float32)
    return xing4.VideoMAEXing4(xing4.StreamHeadConfig())


register(ModelSpec(
    "videomae_b_xing4", _xing4,
    input_size=224, preprocess="clip", kind="stream", clip_len=8,
    prepare=prepare_for_serving,
    description="second streaming head (models/xing4.py): VideoMAE-B "
                "encoder -> connector -> Xing4.0-29B-A4B decoder at the "
                "published widths, one chip's share (1 dense + 4 routed "
                "blocks and the prediction module, 16 of 64 experts a "
                "block, a quarter of the vocabulary); latent attention with "
                "a 576-wide cache row a position a block and a four-stream "
                "hyper-connected residual; per-stream latent cache in "
                "engine/stream_state.py; 784 tokens prefilled and 8 decoded "
                "a stream a round, the prediction module drafting",
))


def _dsv2(tiny: bool = False):
    """The third streaming head, imported when it is first built."""
    from . import deepseek_v2 as dsv2

    if tiny:
        return dsv2.VideoMAEDeepseekV2(dsv2.tiny_stream_head_config(),
                                       dtype=jnp.float32)
    return dsv2.VideoMAEDeepseekV2(dsv2.StreamHeadConfig())


register(ModelSpec(
    "videomae_b_dsv2", _dsv2,
    input_size=224, preprocess="clip", kind="stream", clip_len=8,
    prepare=prepare_for_serving,
    description="third streaming head (models/deepseek_v2.py): VideoMAE-B "
                "encoder -> connector -> DeepSeek-V2 decoder at the "
                "published widths, one chip's share of a 16-chip layer (1 "
                "dense + 4 routed blocks, 32 of 128 heads, 10 of 160 "
                "experts a block, an eighth of the vocabulary); latent "
                "attention (models/mla.py) told which heads it holds, a "
                "softmax router limited to 3 of 8 expert groups, two "
                "shared experts; per-stream latent cache in "
                "engine/stream_state.py and no other state; 784 tokens "
                "prefilled and 8 decoded a stream a round, one position an "
                "iteration",
))

# --- diagnostic gauges ----------------------------------------------------

register(ModelSpec(
    "blob_gauge", lambda: BlobGauge(BlobGaugeConfig()),
    input_size=640, preprocess="letterbox", kind="detect",
    description="detect-identity measurement gauge (models/blob.py): "
                "exact pixel bboxes of color-keyed synthetic blobs; the "
                "ROI round-trip gate (tools/roi_smoke.py) serves it to "
                "prove pack->detect->scatter-back preserves geometry",
))
register(ModelSpec(
    "tiny_blob_gauge", lambda: BlobGauge(BlobGaugeConfig()),
    input_size=64, preprocess="letterbox", kind="detect",
    description="CPU/CI twin of blob_gauge (tests/test_roi.py)",
))

# --- tiny twins (tests / CI on CPU) --------------------------------------

register(ModelSpec(
    "tiny_mobilenet_v2", lambda: MobileNetV2(tiny_mobilenet_v2_config()),
    input_size=32, preprocess="classify", kind="classify",
))
register(ModelSpec(
    "tiny_yolov8", lambda: YOLOv8(tiny_yolov8_config()),
    input_size=64, preprocess="letterbox", kind="detect",
))
register(ModelSpec(
    "tiny_yolov8_s2d", lambda: YOLOv8(
        dataclasses.replace(tiny_yolov8_config(), stem="s2d")
    ),
    input_size=64, preprocess="letterbox", kind="detect",
    description="CPU/CI twin of yolov8n_s2d (tests/test_stem_s2d.py, "
                "tools/stem_smoke.py)",
))
register(ModelSpec(
    "tiny_resnet", lambda: ResNet(tiny_resnet_config()),
    input_size=32, preprocess="classify", kind="embed",
))
register(ModelSpec(
    "tiny_vit", lambda: ViT(tiny_vit_config()),
    input_size=32, preprocess="classify", kind="classify",
))
register(ModelSpec(
    "tiny_videomae", lambda: VideoMAE(tiny_videomae_config()),
    input_size=32, preprocess="clip", kind="video", clip_len=4,
))
register(ModelSpec(
    "tiny_videomae_lfm2",
    lambda: VideoMAELfm2(tiny_stream_head_config(), dtype=jnp.float32),
    input_size=32, preprocess="clip", kind="stream", clip_len=4,
    prepare=prepare_for_serving,
    description="CPU/CI twin of videomae_b_lfm2 (tests/test_stream_head.py), "
                "in float32 throughout (no cast at load), so that a test "
                "tells a broken state from rounding",
))
register(ModelSpec(
    "tiny_videomae_xing4", lambda: _xing4(tiny=True),
    input_size=32, preprocess="clip", kind="stream", clip_len=4,
    prepare=prepare_for_serving,
    description="CPU/CI twin of videomae_b_xing4 (tests/test_xing4_head.py), "
                "in float32 throughout",
))
register(ModelSpec(
    "tiny_videomae_dsv2", lambda: _dsv2(tiny=True),
    input_size=32, preprocess="clip", kind="stream", clip_len=4,
    prepare=prepare_for_serving,
    description="CPU/CI twin of videomae_b_dsv2 (tests/test_deepseek_v2_head"
                ".py), in float32 throughout",
))
