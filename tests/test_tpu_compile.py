"""The serving path's Pallas kernels, compiled for a DESCRIBED TPU v5e.

The TPU compiler is installed with jax and compiles for a chip that is
described, not attached (``jax.experimental.topologies``): it refuses what
the chip's compiler would refuse — a misaligned slice, too much VMEM, a
kernel it cannot partition — which interpret mode (every other test of
these kernels) cannot see. Nothing runs, so this says nothing about results
or times; the run on the chip is ``chip_smoke.py``.

Code that picks its path from ``jax.default_backend()`` sees the CPU here
and takes its XLA twin, so each test names the kernel (``interpret=False``,
``use_pallas=True``) or steers the choice itself; the program has no option
for it.
"""

import functools
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def v5e():
    """Sharding on one chip of a described v5e 2x2, or skip."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:   # no libtpu / topology unknown to it
        pytest.skip(f"cannot describe a TPU v5e here: {exc}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: the next run would warn
    and recompile. Keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


class TestNmsKernel:
    """K=256 is the serving ``max_candidates``; 16 and 64 are the batch
    buckets the detector serves at (vmapped over the batch)."""

    def _nms(self):
        from video_edge_ai_proxy_tpu.ops.nms import nms_keep_mask_pallas

        return functools.partial(
            nms_keep_mask_pallas, iou_thresh=0.45, interpret=False)

    def test_single_row(self, v5e):
        boxes = jax.ShapeDtypeStruct((256, 4), jnp.float32, sharding=v5e)
        assert "tpu_custom_call" in _compiled_text(self._nms(), boxes)

    @pytest.mark.parametrize("rows", [16, 64])
    def test_vmapped_over_batch(self, v5e, rows):
        boxes = jax.ShapeDtypeStruct((rows, 256, 4), jnp.float32,
                                     sharding=v5e)
        text = _compiled_text(jax.vmap(self._nms()), boxes)
        assert "tpu_custom_call" in text


class TestFlashAttentionKernel:
    """12 heads x 64, bf16: T=784 is videomae_b, T=6272 videomae_b_long —
    the one registered config past ``FLASH_THRESHOLD_T``."""

    def _qkv(self, v5e, b, t):
        s = jax.ShapeDtypeStruct((b, t, 12, 64), jnp.bfloat16, sharding=v5e)
        return s, s, s

    @pytest.mark.parametrize("b,t", [(8, 784), (1, 6272)])
    def test_forward(self, v5e, b, t):
        from video_edge_ai_proxy_tpu.ops.flash_attention import (
            flash_attention,
        )

        fwd = functools.partial(flash_attention, interpret=False)
        assert "tpu_custom_call" in _compiled_text(fwd, *self._qkv(v5e, b, t))

    def test_backward(self, v5e):
        from video_edge_ai_proxy_tpu.ops.flash_attention import (
            flash_attention,
        )

        def loss(q, k, v):
            return flash_attention(q, k, v, interpret=False).astype(
                jnp.float32).sum()

        text = _compiled_text(
            jax.grad(loss, argnums=(0, 1, 2)), *self._qkv(v5e, 1, 784))
        # forward (recomputed residuals) + dq + dk/dv kernels
        assert text.count("tpu_custom_call") >= 3


class TestLatentPrefillKernel:
    """The MLA cells' shapes: a chunk of 8 (``xing64_360p``) or 4
    (``dsv2_64_360p``) streams x 32 heads x 784 new positions over up to
    3,328 cached rows of 640 numbers, read from one block of a pool that
    is handed over whole."""

    @pytest.mark.parametrize("chunk,blocks", [(8, 6), (4, 5)])
    def test_it_compiles_and_copies_no_block_of_the_pool(
            self, v5e, monkeypatch, chunk, blocks):
        from video_edge_ai_proxy_tpu.models import mla

        _as_if_on_tpu(monkeypatch)
        bf = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.bfloat16,
                               sharding=v5e)
        i32 = jax.ShapeDtypeStruct((chunk,), jnp.int32, sharding=v5e)
        compiled = jax.jit(
            lambda *a: mla.mla_prefill_attention(*a, 0.07, 3328, 2)).lower(
            bf((chunk, 784, 32, 192)), bf((chunk, 784, 640)),
            bf((512, 32, 128)), bf((512, 32, 128)),
            bf((blocks, 64, 4096, 640)), i32, i32).compile()
        assert "tpu_custom_call" in compiled.as_text()
        # a block of the pool is 335 MB: nothing of that size beside the
        # arguments (a slice handed to the kernel would be a copy of it)
        assert compiled.memory_analysis().temp_size_in_bytes < 300 << 20


def _as_if_on_tpu(monkeypatch):
    """Steer the backend-keyed choices (ops/nms.py ``batched_nms``,
    models/transformer.py ``auto_attention``, each kernel's ``interpret``
    default) the way an attached chip would — in the test, not through an
    option of the program."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.slow
def test_videomae_long_forward_has_flash_kernel_inside(v5e, monkeypatch):
    """The whole ``videomae_b_long`` encoder forward (64 frames -> 6,272
    tokens) with the flash kernel INSIDE it, one block deep: the kernel
    must survive the surrounding program's layouts, not only compile
    alone. ~6 s, so ``slow`` like the whole detect step below."""
    import dataclasses

    from video_edge_ai_proxy_tpu.models import registry

    _as_if_on_tpu(monkeypatch)
    spec = registry.get("videomae_b_long")
    model = spec.build()
    model = model.clone(cfg=dataclasses.replace(
        model.cfg, encoder=dataclasses.replace(
            model.cfg.encoder, num_layers=1)))
    clip = jax.ShapeDtypeStruct(
        (1, spec.clip_len, spec.input_size, spec.input_size, 3),
        jnp.bfloat16, sharding=v5e)
    variables = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros(clip.shape, clip.dtype), train=False))
    variables = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
        variables)
    text = _compiled_text(
        lambda v, x: model.apply(v, x, train=False), variables, clip)
    assert "tpu_custom_call" in text


@pytest.mark.slow
def test_whole_yolov8n_step_16x1080p_has_pallas_nms_inside(v5e, monkeypatch):
    """The exact program the engine serves by default — letterbox,
    YOLOv8n, DFL decode, NMS, quality stats — at [16,1080,1920,3] uint8,
    with the Pallas NMS inside it. ~20 s of compile, hence ``slow``."""
    from video_edge_ai_proxy_tpu.engine.runner import build_serving_step
    from video_edge_ai_proxy_tpu.models import registry

    _as_if_on_tpu(monkeypatch)
    spec = registry.get("yolov8n")
    model = spec.build()
    variables = jax.eval_shape(
        lambda: spec.init_params(jax.random.PRNGKey(0))[1])
    variables = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
        variables)
    frames = jax.ShapeDtypeStruct((16, 1080, 1920, 3), jnp.uint8,
                                  sharding=v5e)
    thumbs = jax.ShapeDtypeStruct((16, 32, 32), jnp.float32, sharding=v5e)
    step = build_serving_step(model, spec, quality_thumb=32)
    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        variables, frames, thumbs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30


def test_windowed_clip_step_rewrites_the_window_in_place(v5e, monkeypatch):
    """The windowed ``videomae_b`` step (engine/runner.py ``_windowed``)
    at 1080p, 4 rows over 4 slots: the donated window pool comes back
    aliased (no second pool), the windows reach the body through ONE
    ordered copy (a loop of whole-frame slice updates into an allocated,
    never zeroed buffer), not through a gather split into column strips
    and stitched back, which is what ``jnp.take`` compiled to. ~10 s."""
    from video_edge_ai_proxy_tpu.engine.runner import build_serving_step
    from video_edge_ai_proxy_tpu.models import registry

    _as_if_on_tpu(monkeypatch)
    spec = registry.get("videomae_b")
    model = spec.build()
    on_chip = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
        a.shape, a.dtype, sharding=v5e)
    variables = jax.tree.map(on_chip, jax.eval_shape(
        lambda: spec.init_params(jax.random.PRNGKey(0))[1]))
    n, geom = 4, (1080, 1920, 3)
    frames = jax.ShapeDtypeStruct((n,) + geom, jnp.uint8, sharding=v5e)
    window = jax.ShapeDtypeStruct((n, spec.clip_len) + geom, jnp.uint8,
                                  sharding=v5e)
    ints = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=v5e)
    compiled = jax.jit(
        build_serving_step(model, spec, window=True),
        donate_argnums=(2,)).lower(
            variables, frames, window, ints, ints).compile()
    pool = n * spec.clip_len * 1080 * 1920 * 3
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool
    # the ordered copy and the body's own; the gather's strips took 3 pools
    assert mem.temp_size_in_bytes < 2.5 * pool
    text = compiled.as_text()
    assert "mini-gather" not in text and "AllocateBuffer" in text


class TestStreamHeadLayers:
    """The streaming head's expert layer and cached attention at the
    published widths (models/lfm2.py), compiled INSIDE a loop, as the
    serving step runs them (prefill chunks, decode steps): the TPU
    compiler refused a scatter there (``bincount``, ``.at[].set``, a
    batched slice update) that it takes outside a loop."""

    def test_expert_layer_in_a_loop_is_a_grouped_matmul(self, v5e):
        import flax.linen as nn

        from video_edge_ai_proxy_tpu.models.transformer import (
            TopKMoeConfig, TopKMoeMlp,
        )

        layer = TopKMoeMlp(TopKMoeConfig(
            dim=2048, mlp_dim=1536, num_experts=64, top_k=4,
            experts_held=tuple(range(16))))
        shapes = jax.eval_shape(lambda: nn.meta.unbox(layer.init(
            jax.random.PRNGKey(0), jnp.zeros((8, 2048), jnp.bfloat16))))
        variables = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
            shapes)

        def twice(variables, x):
            def body(_, carry):
                x, load = carry
                y, n = layer.apply(variables, x)
                return x + y, load + n
            return jax.lax.fori_loop(
                0, 2, body, (x, jnp.zeros((16,), jnp.int32)))

        x = jax.ShapeDtypeStruct((6272, 2048), jnp.bfloat16, sharding=v5e)
        text = _compiled_text(twice, variables, x)
        # XLA's own grouped-matmul lowering of ragged_dot
        assert "ragged-dot" in text and "tpu_custom_call" in text

    @pytest.mark.parametrize("t", [784, 1], ids=["prefill", "decode"])
    def test_cached_attention_in_a_loop(self, v5e, t):
        import flax.linen as nn

        from video_edge_ai_proxy_tpu.models import lfm2

        cfg = lfm2.Lfm2Config()
        attn = lfm2.CachedAttention(cfg)
        b, r = 8, 792
        on_chip = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
            a.shape, a.dtype, sharding=v5e)
        pool = jax.tree_util.tree_map(
            on_chip, jax.eval_shape(lambda: lfm2.empty_state(cfg, b)[1]))
        rbuf = tuple(on_chip(a) for a in jax.eval_shape(
            lambda: tuple(a[0] for a in lfm2.round_buffer(cfg, b, r))))
        h = jax.ShapeDtypeStruct((b, t, cfg.dim), jnp.bfloat16, sharding=v5e)
        ints = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=v5e)
        variables = jax.tree_util.tree_map(on_chip, jax.eval_shape(
            lambda: nn.meta.unbox(attn.init(
                jax.random.PRNGKey(0), jnp.zeros((1, 1, cfg.dim),
                                                 jnp.bfloat16),
                lfm2.empty_state(cfg, 1, 8)[1],
                tuple(a[0] for a in lfm2.round_buffer(cfg, 1, 4)),
                jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32), 0))))

        def twice(variables, h, pool, rbuf, slots, pos0):
            def body(i, carry):
                h, rbuf = carry
                y, rbuf = attn.apply(variables, h, pool, rbuf, slots, pos0,
                                     0 if t > 1 else 784 + i)
                return h + y, rbuf
            return jax.lax.fori_loop(0, 2, body, (h, rbuf))

        assert "while" in _compiled_text(
            twice, variables, h, pool, rbuf, ints, ints)

    def test_flush_writes_the_donated_pool_in_place(self, v5e):
        """The round's keys and values go into the pool by a loop of slice
        updates: with the pool donated, no copy of it is compiled."""
        from video_edge_ai_proxy_tpu.models import lfm2

        cfg = lfm2.Lfm2Config()
        b = 8
        on_chip = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
            a.shape, a.dtype, sharding=v5e)
        pool = jax.tree_util.tree_map(
            on_chip, jax.eval_shape(lambda: lfm2.empty_state(cfg, b)[1]))
        rbuf = jax.tree_util.tree_map(
            on_chip, jax.eval_shape(lambda: lfm2.round_buffer(cfg, b, 792)))
        ints = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=v5e)
        text = jax.jit(lfm2.flush_round, donate_argnums=(0,)).lower(
            pool, rbuf, ints, ints).compile().as_text()
        assert "dynamic-update-slice" in text and "while" in text
        whole = "bf16[%d,%d,%d,%d,%d]" % pool[0].shape
        assert not [line for line in text.splitlines()
                    if " copy(" in line and line.split("=")[1].strip()
                    .startswith(whole)]


class TestXing4HeadLayers:
    """The second streaming head's own operators at the published widths
    (models/xing4.py), compiled INSIDE a loop as the serving step runs
    them: latent attention's two paths over the one cache, the residual
    maps, and the flush of a round's latent rows into the donated pool."""

    @staticmethod
    def _shapes(v5e, b):
        from video_edge_ai_proxy_tpu.models import xing4

        cfg = xing4.Xing4Config()
        on_chip = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
            a.shape, a.dtype, sharding=v5e)
        pool = on_chip(jax.eval_shape(
            lambda: xing4.empty_latent(cfg, b, cfg.max_context)))
        rbuf = on_chip(jax.eval_shape(
            lambda: xing4.empty_latent(cfg, b, 795)))
        ints = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=v5e)
        return xing4, cfg, on_chip, pool, rbuf, ints

    @pytest.mark.parametrize("t", [784, 2], ids=["prefill", "decode"])
    def test_latent_attention_in_a_loop(self, v5e, t):
        import flax.linen as nn

        xing4, cfg, on_chip, pool, rbuf, ints = self._shapes(v5e, 8)
        attn = xing4.MlaAttention(cfg.mla)
        h = jax.ShapeDtypeStruct((8, t, cfg.dim), jnp.bfloat16, sharding=v5e)
        variables = jax.tree_util.tree_map(on_chip, jax.eval_shape(
            lambda: nn.meta.unbox(attn.init(
                jax.random.PRNGKey(0),
                jnp.zeros((1, 2, cfg.dim), jnp.bfloat16),
                xing4.empty_latent(cfg, 1, 8)[0],
                xing4.empty_latent(cfg, 1, 4)[0],
                jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32),
                jnp.zeros((1,), jnp.int32), 0))))

        def twice(variables, h, pool, rbuf, slots, pos0):
            def body(i, carry):
                h, rows = carry
                y, rows = attn.apply(
                    variables, h, pool[i], rows, slots, pos0,
                    None if t > 2 else pos0 * 0 + 784 + i, 3328)
                return h + y, rows
            return jax.lax.fori_loop(0, 2, body, (h, rbuf[0]))

        text = _compiled_text(twice, variables, h, pool, rbuf, ints, ints)
        assert "while" in text
        # neither path makes another layout of the pool: the 640-wide rows
        # are the layout both paths' products take
        whole = "bf16[%d,%d,%d,%d]" % pool.shape
        assert not [line for line in text.splitlines()
                    if " copy(" in line and whole in line.split("=")[1][:60]]

    def test_residual_maps_in_a_loop(self, v5e):
        import flax.linen as nn

        xing4, cfg, on_chip, _, _, _ = self._shapes(v5e, 8)
        maps = xing4.HyperResidual(cfg)
        x = jax.ShapeDtypeStruct((4, 8, 784, cfg.dim), jnp.bfloat16,
                                 sharding=v5e)
        variables = jax.tree_util.tree_map(on_chip, jax.eval_shape(
            lambda: nn.meta.unbox(maps.init(
                jax.random.PRNGKey(0),
                jnp.zeros((4, 1, 2, cfg.dim), jnp.bfloat16)))))

        def twice(variables, x):
            def body(_, x):
                pre, post, res = maps.apply(variables, x)
                return xing4.hc_write(res, post, x, xing4.hc_read(pre, x))
            return jax.lax.fori_loop(0, 2, body, x)

        assert "while" in _compiled_text(twice, variables, x)

    def test_flush_writes_the_donated_latent_pool_in_place(self, v5e):
        xing4, cfg, _, pool, rbuf, ints = self._shapes(v5e, 8)
        text = jax.jit(
            functools.partial(xing4.flush_round, keep=792, main_blocks=5),
            donate_argnums=(0,)).lower(pool, rbuf, ints, ints).compile(
        ).as_text()
        assert "dynamic-update-slice" in text and "while" in text
        whole = "bf16[%d,%d,%d,%d]" % pool.shape
        assert not [line for line in text.splitlines()
                    if " copy(" in line and line.split("=")[1].strip()
                    .startswith(whole)]



class TestDeepseekV2HeadLayers:
    """The third streaming head's own shapes at the published widths
    (models/deepseek_v2.py): latent attention at 32 held heads of 128
    (models/mla.py) and the group-limited softmax router over 160 experts
    of which ten are held (models/transformer.py), compiled INSIDE a loop
    as the serving step runs them."""

    @pytest.mark.parametrize("t", [784, 1], ids=["prefill", "decode"])
    def test_latent_attention_at_the_held_heads_in_a_loop(self, v5e, t):
        import flax.linen as nn

        from video_edge_ai_proxy_tpu.models import deepseek_v2, mla

        cfg = deepseek_v2.DeepseekV2Config()
        attn = mla.MlaAttention(cfg.mla)
        b = 4
        on_chip = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
            a.shape, a.dtype, sharding=v5e)
        pool = on_chip(jax.eval_shape(lambda: mla.empty_latent(
            cfg.mla, cfg.num_layers, b, cfg.max_context)))
        rbuf = on_chip(jax.eval_shape(lambda: mla.empty_latent(
            cfg.mla, cfg.num_layers, b, 792)))
        ints = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=v5e)
        h = jax.ShapeDtypeStruct((b, t, cfg.dim), jnp.bfloat16, sharding=v5e)
        shapes = jax.eval_shape(lambda: nn.meta.unbox(attn.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 2, cfg.dim), jnp.bfloat16),
            mla.empty_latent(cfg.mla, 1, 1, 8)[0],
            mla.empty_latent(cfg.mla, 1, 1, 4)[0],
            jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32),
            jnp.zeros((1,), jnp.int32), 0)))
        # the stacks hold the held heads' slices, not the model's 128
        assert shapes["params"]["q_b"].shape == (1536, 32 * 192)
        assert shapes["params"]["kv_b"].shape == (512, 32 * 256)
        assert shapes["params"]["o"].shape == (32 * 128, 5120)
        variables = jax.tree_util.tree_map(on_chip, shapes)

        def twice(variables, h, pool, rbuf, slots, pos0):
            def body(i, carry):
                h, rows = carry
                y, rows = attn.apply(
                    variables, h, pool[i], rows, slots, pos0,
                    None if t > 1 else pos0 * 0 + 784 + i, 3328)
                return h + y, rows
            return jax.lax.fori_loop(0, 2, body, (h, rbuf[0]))

        text = _compiled_text(twice, variables, h, pool, rbuf, ints, ints)
        assert "while" in text
        whole = "bf16[%d,%d,%d,%d]" % pool.shape
        assert not [line for line in text.splitlines()
                    if " copy(" in line and whole in line.split("=")[1][:60]]

    def test_group_limited_expert_layer_in_a_loop(self, v5e):
        import flax.linen as nn

        from video_edge_ai_proxy_tpu.models import deepseek_v2
        from video_edge_ai_proxy_tpu.models.transformer import TopKMoeMlp

        cfg = deepseek_v2.DeepseekV2Config().moe
        assert (cfg.scoring, cfg.n_group, cfg.topk_group) == ("softmax", 8, 3)
        layer = TopKMoeMlp(cfg)
        shapes = jax.eval_shape(lambda: nn.meta.unbox(layer.init(
            jax.random.PRNGKey(0), jnp.zeros((8, 5120), jnp.bfloat16))))
        assert shapes["params"]["gate"].shape == (5120, 160)
        assert shapes["params"]["w1"].shape == (10, 5120, 1536)
        assert "expert_bias" not in shapes["params"]
        variables = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
            shapes)

        def twice(variables, x):
            def body(_, carry):
                x, load, hits = carry
                y, n, _, hit = layer.apply(variables, x,
                                           method=TopKMoeMlp.routed)
                return x + y, load + n, hits + hit
            return jax.lax.fori_loop(
                0, 2, body, (x, jnp.zeros((10,), jnp.int32),
                             jnp.zeros((), jnp.int32)))

        x = jax.ShapeDtypeStruct((3136, 5120), jnp.bfloat16, sharding=v5e)
        text = _compiled_text(twice, variables, x)
        assert "ragged-dot" in text and "tpu_custom_call" in text
