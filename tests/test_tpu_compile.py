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


def _computation(text, name):
    """The instruction lines of computation ``name`` (``%region_3.1``) of
    an optimised HLO text (not those of the computations it calls)."""
    lines, inside = [], False
    for line in text.splitlines():
        if line.endswith("{") and ") -> " in line:
            inside = line.split(" ", 1)[0] == name
        elif inside and line.startswith("  ") and " = " in line:
            lines.append(line.strip().removeprefix("ROOT "))
    return lines


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
                return x + y, load + n["held"]
            return jax.lax.fori_loop(
                0, 2, body, (x, jnp.zeros((16,), jnp.int32)))

        x = jax.ShapeDtypeStruct((6272, 2048), jnp.bfloat16, sharding=v5e)
        text = _compiled_text(twice, variables, x)
        # XLA's own grouped-matmul lowering of ragged_dot
        assert "ragged-dot" in text and "tpu_custom_call" in text

    @pytest.mark.parametrize("t", [784, 1], ids=["prefill", "decode"])
    def test_cached_attention_in_a_loop(self, v5e, monkeypatch, t):
        """A prefill's attention is the Pallas kernel, inside the loop, and
        reads the pool where it lies: its temporaries stay under one
        layer's pool, so no copy of one is made."""
        import flax.linen as nn

        from video_edge_ai_proxy_tpu.models import lfm2

        _as_if_on_tpu(monkeypatch)
        cfg = lfm2.Lfm2Config()
        attn = lfm2.CachedAttention(cfg)
        b, r, slots = 8, 792, 64
        on_chip = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
            a.shape, a.dtype, sharding=v5e)
        pool = jax.tree_util.tree_map(
            on_chip, jax.eval_shape(lambda: lfm2.empty_state(cfg, slots)[1]))
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

        compiled = jax.jit(twice).lower(
            variables, h, pool, rbuf, ints, ints).compile()
        text = compiled.as_text()
        assert "while" in text
        if t > 1:
            loop = next(line for line in text.splitlines()
                        if " while(" in line)
            body = loop.split("body=", 1)[1].split(",")[0].split()[0]
            assert any("tpu_custom_call" in line
                       for line in _computation(text, body))
            layer = pool[0].size // pool[0].shape[0] * 2
            assert compiled.memory_analysis().temp_size_in_bytes < layer

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

    def test_group_limited_expert_layer_in_a_loop(self, v5e, monkeypatch):
        import flax.linen as nn

        from video_edge_ai_proxy_tpu.models import deepseek_v2
        from video_edge_ai_proxy_tpu.models.transformer import (
            TopKMoeMlp, pair_rungs)

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
                y, n = layer.apply(variables, x, method=TopKMoeMlp.routed)
                return x + y, load + n["held"], hits + n["group_hits"]
            return jax.lax.fori_loop(
                0, 2, body, (x, jnp.zeros((10,), jnp.int32),
                             jnp.zeros((), jnp.int32)))

        x = jax.ShapeDtypeStruct((3136, 5120), jnp.bfloat16, sharding=v5e)
        # the way back's kernel, not its interpreted twin
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        text = _compiled_text(twice, variables, x)
        assert "ragged-dot" in text and "tpu_custom_call" in text
        # the capacity rungs of the cell's chunk (4 streams x 784 tokens x
        # top-6): one branch a rung, chosen in the loop's body, each
        # gathering its own row count of tokens
        rungs = pair_rungs(cfg, 3136)
        assert rungs == (18816, 9472, 4736, 2432)
        assert " conditional(" in text
        for rows in rungs:
            assert "bf16[%d,5120]" % rows in text, rows
        # no rung below the top makes an [N x k, d] or [N, k, d] float32
        # array: the weighted sum over k is a kernel that reads the rows
        # there are
        cond = next(line for line in text.splitlines()
                    if " conditional(" in line)
        branches = cond.split("branch_computations={", 1)[1].split("}")[0]
        for i, name in enumerate(branches.split(", ")):
            lines = _computation(text, name)
            assert [line for line in lines
                    if line.startswith("%combine_rows")], name
            made = [line.split(" = ", 1)[1][:20] for line in lines]
            assert i == 0 or not [m for m in made if m.startswith(
                ("f32[3136,6,5120]", "f32[18816,5120]"))], name

    def test_expert_layer_at_decode_has_one_rung(self, v5e, monkeypatch):
        """Decode's 64 tokens (one position a stream, top-6: 384 pairs)
        are under a rung's few tiles: the layer is the one body of all
        pairs, with no conditional, and its way back is the kernel, as in
        prefill: no [N, k, d] float32 array is made."""
        import flax.linen as nn

        from video_edge_ai_proxy_tpu.models import deepseek_v2
        from video_edge_ai_proxy_tpu.models.transformer import (
            TopKMoeMlp, pair_rungs)

        cfg = deepseek_v2.DeepseekV2Config().moe
        assert pair_rungs(cfg, 64) == (384,)
        layer = TopKMoeMlp(cfg)
        variables = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
            jax.eval_shape(lambda: nn.meta.unbox(layer.init(
                jax.random.PRNGKey(0), jnp.zeros((8, 5120), jnp.bfloat16)))))

        def steps(variables, x):
            def body(_, x):
                return x + layer.apply(variables, x)[0]
            return jax.lax.fori_loop(0, 2, body, x)

        x = jax.ShapeDtypeStruct((64, 5120), jnp.bfloat16, sharding=v5e)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        text = _compiled_text(steps, variables, x)
        assert "ragged-dot" in text and " conditional(" not in text
        assert [line for line in text.splitlines()
                if line.lstrip().startswith("%combine_rows")]
        assert "f32[64,6,5120]" not in text


class TestFalconH1HeadLayers:
    """The fourth streaming head at the published widths
    (models/falcon_h1.py): the Mamba-2 mixer of one chip's share (16 heads
    of 128 channels, one group, a 256-wide float32 state a head) with its
    chunked scan (ops/ssd.py) in the prefill and its one-position step in
    decode, compiled INSIDE a loop as the serving step runs them; and the
    cell's whole step, which has to fit the chip beside the benchmark's
    float32 draw."""

    @pytest.mark.parametrize("t", [784, 1], ids=["prefill", "decode"])
    def test_mixer_in_a_loop(self, v5e, t):
        import flax.linen as nn

        from video_edge_ai_proxy_tpu.models import falcon_h1

        cfg = falcon_h1.FalconH1Config()
        mixer = falcon_h1.Mamba2Mixer(cfg)
        b = 8
        state = (b, cfg.mamba_heads, cfg.mamba_head_dim, cfg.d_state)
        shapes = jax.eval_shape(lambda: nn.meta.unbox(mixer.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 2, cfg.dim), jnp.bfloat16),
            jnp.zeros((1, cfg.d_conv - 1, cfg.conv_dim), jnp.bfloat16),
            jnp.zeros((1,) + state[1:], jnp.float32))))
        assert shapes["params"]["in_proj"].shape == (5120, 4624)
        variables = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
            shapes)

        def twice(variables, n, conv, ssm):
            def body(_, carry):
                n, conv, ssm = carry
                m, conv, ssm = mixer.apply(variables, n, conv, ssm)
                return n + m, conv.astype(jnp.bfloat16), ssm
            return jax.lax.fori_loop(0, 2, body, (n, conv, ssm))

        text = _compiled_text(
            twice, variables,
            jax.ShapeDtypeStruct((b, t, cfg.dim), jnp.bfloat16, sharding=v5e),
            jax.ShapeDtypeStruct((b, cfg.d_conv - 1, cfg.conv_dim),
                                 jnp.bfloat16, sharding=v5e),
            jax.ShapeDtypeStruct(state, jnp.float32, sharding=v5e))
        assert "while" in text
        assert "f32[%d,%d,%d,%d]" % state in text

    @pytest.mark.slow
    def test_the_cells_step_fits_the_chip(self, v5e, monkeypatch):
        """``falconh1_64_360p``'s step (64 clips of 360p, the pool of 64
        streams donated) compiled for a described v5e: the state comes back
        aliased, and the step's arguments and temporaries, beside the
        float32 draw that the benchmark keeps for its reference, stay
        under the chip's 16 GB. ~50 s of compile, hence ``slow``."""
        from video_edge_ai_proxy_tpu.engine.runner import build_serving_step
        from video_edge_ai_proxy_tpu.models import registry

        _as_if_on_tpu(monkeypatch)
        spec = registry.get("videomae_b_falcon_h1")
        model = spec.build()
        on_chip = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
            a.shape, a.dtype, sharding=v5e)
        drawn = jax.eval_shape(
            lambda: spec.init_params(jax.random.PRNGKey(0))[1])
        variables = jax.tree.map(on_chip, jax.eval_shape(
            lambda v: spec.prepare(model, v), drawn))
        b, c = 64, model.cfg
        state = dict(jax.eval_shape(lambda: model.empty_state(b))[0],
                     tokens=jax.ShapeDtypeStruct(
                         (b, c.max_rounds * c.decode_steps), jnp.int32))
        state = jax.tree.map(on_chip, state)
        ints = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=v5e)
        compiled = jax.jit(build_serving_step(model, spec),
                           donate_argnums=(2,)).lower(
            variables, jax.ShapeDtypeStruct((b, 8, 360, 640, 3), jnp.uint8,
                                            sharding=v5e),
            state, ints, ints,
            jax.ShapeDtypeStruct((b,), bool, sharding=v5e), ints).compile()
        mem = compiled.memory_analysis()
        held = sum(a.size * a.dtype.itemsize
                   for a in jax.tree_util.tree_leaves(state))
        assert mem.alias_size_in_bytes >= held
        draw = 4 * sum(a.size for a in jax.tree_util.tree_leaves(drawn))
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
            + draw < 16e9


class TestNemotronHHeadLayers:
    """The fifth streaming head at the published widths
    (models/nemotron_h.py): the latent expert layer (top-22 of 512, 16
    held, squared ReLU in a 1,024-wide latent) at the cell's prefill chunk
    (4 streams: at 8 the way back's two prefetched per-pair vectors,
    137,984 entries each, pass the 1 MiB of scalar memory) and at decode,
    the unrotated attention whose 8 query heads read one key-value head,
    and the mixer at this head's sizes (32 heads of 64 in two groups, a
    128-wide state), each compiled INSIDE a loop as the serving step runs
    them; and the cell's whole step, which has to fit the chip beside the
    benchmark's float32 draw."""

    def test_latent_expert_layer_in_a_loop(self, v5e, monkeypatch):
        import flax.linen as nn

        from video_edge_ai_proxy_tpu.models import nemotron_h
        from video_edge_ai_proxy_tpu.models.transformer import (
            TopKMoeMlp, pair_rungs)

        cfg = nemotron_h.NemotronHConfig().moe
        layer = TopKMoeMlp(cfg)
        shapes = jax.eval_shape(lambda: nn.meta.unbox(layer.init(
            jax.random.PRNGKey(0), jnp.zeros((8, 4096), jnp.bfloat16))))
        p = shapes["params"]
        assert p["w1"].shape == (16, 1024, 2688) and "w3" not in p
        assert p["fc1_latent_proj"].shape == (4096, 1024)
        assert p["shared_w1"].shape == (4096, 5376) and "shared_w3" not in p
        variables = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
            shapes)

        def twice(variables, x):
            def body(_, carry):
                x, load = carry
                y, n = layer.apply(variables, x, method=TopKMoeMlp.routed)
                return x + y, load + n["held"]
            return jax.lax.fori_loop(
                0, 2, body, (x, jnp.zeros((16,), jnp.int32)))

        # the way back's kernel, not its interpreted twin
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        x = jax.ShapeDtypeStruct((3136, 4096), jnp.bfloat16, sharding=v5e)
        text = _compiled_text(twice, variables, x)
        assert "ragged-dot" in text and " conditional(" in text
        # every rung gathers its rows of the LATENT, not of the input
        rungs = pair_rungs(cfg, 3136)
        for rows in rungs:
            assert "bf16[%d,1024]" % rows in text, rows
            assert "bf16[%d,4096]" % rows not in text, rows
        cond = next(line for line in text.splitlines()
                    if " conditional(" in line)
        branches = cond.split("branch_computations={", 1)[1].split("}")[0]
        for name in branches.split(", "):
            assert [line for line in _computation(text, name)
                    if line.startswith("%combine_rows")], name
        # decode's 64 tokens: one rung, no conditional
        assert pair_rungs(cfg, 64) == (1408,)
        x = jax.ShapeDtypeStruct((64, 4096), jnp.bfloat16, sharding=v5e)
        text = _compiled_text(twice, variables, x)
        assert "ragged-dot" in text and " conditional(" not in text

    def test_latent_expert_layer_sorts_once_and_walks_the_held_rows(
            self, v5e, monkeypatch):
        """The layer at the cell's chunk (3,136 tokens, top-22: 68,992
        pairs a call): one sort of the N x k keys (the held pairs first;
        the way back needs no inverse permutation), and in every rung's
        branch the way back is the kernel, which the stage map gives
        ``head_moe`` / ``moe_combine``, reading the grouped products'
        [C, 1,024] float32 rows as they lie (no [C, 8, 128] copy of them);
        no rung below the top makes an [N x k, 1,024] float32 array."""
        import flax.linen as nn

        from video_edge_ai_proxy_tpu.models import nemotron_h
        from video_edge_ai_proxy_tpu.models.transformer import (
            TopKMoeMlp, pair_rungs)
        from video_edge_ai_proxy_tpu.obs import stages

        cfg = nemotron_h.NemotronHConfig().moe
        assert (cfg.top_k, cfg.expert_dim) == (22, 1024)
        layer = TopKMoeMlp(cfg)
        variables = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
            jax.eval_shape(lambda: nn.meta.unbox(layer.init(
                jax.random.PRNGKey(0), jnp.zeros((8, 4096), jnp.bfloat16)))))

        def call(variables, x):
            with jax.named_scope("head_moe"):
                return layer.apply(variables, x, method=TopKMoeMlp.routed)

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        compiled = jax.jit(call).lower(variables, jax.ShapeDtypeStruct(
            (3136, 4096), jnp.bfloat16, sharding=v5e)).compile()
        text = compiled.as_text()
        pairs = 3136 * 22
        assert len([line for line in text.splitlines() if " sort(" in line
                    and "s32[%d]" % pairs in line.split(" sort(")[0]]) == 1
        rungs = pair_rungs(cfg, 3136)
        assert rungs == (68992, 34560, 17280, 8704, 4352)
        kernels = {k: p for k, p in stages.stage_map(compiled).items()
                   if k.startswith("%combine_rows")}
        assert len(kernels) == len(rungs)
        assert set(kernels.values()) == {("head_moe", "moe_combine")}
        for rows in rungs:
            assert "f32[%d,8,128]" % rows not in text, rows
        cond = next(line for line in text.splitlines()
                    if " conditional(" in line)
        branches = cond.split("branch_computations={", 1)[1].split("}")[0]
        for i, name in enumerate(branches.split(", ")):
            made = [line.split(" = ", 1)[1][:20]
                    for line in _computation(text, name)]
            assert i == 0 or not [m for m in made if m.startswith(
                "f32[%d,1024]" % pairs)], name

    @pytest.mark.parametrize("t", [784, 1], ids=["prefill", "decode"])
    def test_unrotated_attention_of_eight_heads_on_one(self, v5e,
                                                       monkeypatch, t):
        """A prefill's attention is the Pallas kernel with the 8 query
        heads of the one key-value head held as its columns, inside the
        loop, reading the pool where it lies."""
        import flax.linen as nn

        from video_edge_ai_proxy_tpu.models import gqa, nemotron_h

        _as_if_on_tpu(monkeypatch)
        cfg = nemotron_h.NemotronHConfig().attn
        assert (cfg.num_heads, cfg.num_kv_heads, cfg.rotary) == (8, 1, False)
        attn = gqa.CachedAttention(cfg)
        b, r, slots = 8, 792, 64
        on_chip = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
            a.shape, a.dtype, sharding=v5e)
        pool = jax.tree_util.tree_map(on_chip, jax.eval_shape(
            lambda: gqa.empty_pool(cfg, 1, slots, 4096)))
        rbuf = tuple(on_chip(a) for a in jax.eval_shape(
            lambda: tuple(a[0] for a in gqa.round_buffer(cfg, 1, b, r))))
        h = jax.ShapeDtypeStruct((b, t, 4096), jnp.bfloat16, sharding=v5e)
        ints = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=v5e)
        variables = jax.tree_util.tree_map(on_chip, jax.eval_shape(
            lambda: nn.meta.unbox(attn.init(
                jax.random.PRNGKey(0), jnp.zeros((1, 1, 4096), jnp.bfloat16),
                gqa.empty_pool(cfg, 1, 1, 8),
                tuple(a[0] for a in gqa.round_buffer(cfg, 1, 1, 4)),
                jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32), 0))))

        def twice(variables, h, pool, rbuf, slots, pos0):
            def body(i, carry):
                h, rbuf = carry
                y, rbuf = attn.apply(variables, h, pool, rbuf, slots, pos0,
                                     0 if t > 1 else 784 + i)
                return h + y, rbuf
            return jax.lax.fori_loop(0, 2, body, (h, rbuf))

        compiled = jax.jit(twice).lower(
            variables, h, pool, rbuf, ints, ints).compile()
        text = compiled.as_text()
        assert "while" in text
        if t > 1:
            assert "tpu_custom_call" in text
            layer = pool[0].size // pool[0].shape[0] * 2
            assert compiled.memory_analysis().temp_size_in_bytes < layer

    @pytest.mark.parametrize("t", [784, 1], ids=["prefill", "decode"])
    def test_mixer_in_a_loop(self, v5e, t):
        import flax.linen as nn

        from video_edge_ai_proxy_tpu.models import mamba2, nemotron_h

        cfg = nemotron_h.NemotronHConfig().mixer
        mixer = mamba2.Mamba2Mixer(cfg)
        b = 8
        state = (b, cfg.mamba_heads, cfg.mamba_head_dim, cfg.d_state)
        shapes = jax.eval_shape(lambda: nn.meta.unbox(mixer.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 2, cfg.dim), jnp.bfloat16),
            jnp.zeros((1, cfg.d_conv - 1, cfg.conv_dim), jnp.bfloat16),
            jnp.zeros((1,) + state[1:], jnp.float32))))
        assert shapes["params"]["in_proj"].shape == (4096, 4640)
        variables = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e),
            shapes)

        def twice(variables, n, conv, ssm):
            def body(_, carry):
                n, conv, ssm = carry
                m, conv, ssm = mixer.apply(variables, n, conv, ssm)
                return n + m, conv.astype(jnp.bfloat16), ssm
            return jax.lax.fori_loop(0, 2, body, (n, conv, ssm))

        text = _compiled_text(
            twice, variables,
            jax.ShapeDtypeStruct((b, t, cfg.dim), jnp.bfloat16, sharding=v5e),
            jax.ShapeDtypeStruct((b, cfg.d_conv - 1, cfg.conv_dim),
                                 jnp.bfloat16, sharding=v5e),
            jax.ShapeDtypeStruct(state, jnp.float32, sharding=v5e))
        assert "while" in text
        assert "f32[%d,%d,%d,%d]" % state in text

    @pytest.mark.slow
    def test_the_cells_step_fits_the_chip(self, v5e, monkeypatch):
        """``nemotron3s_64_360p``'s step (64 clips of 360p, the pool of 64
        streams donated) compiled for a described v5e: the state comes back
        aliased, and the step's arguments and temporaries, beside the
        float32 draw that the benchmark keeps for its reference, stay
        under the chip's 16 GB."""
        from video_edge_ai_proxy_tpu.engine.runner import build_serving_step
        from video_edge_ai_proxy_tpu.models import registry

        _as_if_on_tpu(monkeypatch)
        spec = registry.get("videomae_b_nemotron3_super")
        model = spec.build()
        on_chip = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
            a.shape, a.dtype, sharding=v5e)
        drawn = jax.eval_shape(
            lambda: spec.init_params(jax.random.PRNGKey(0))[1])
        variables = jax.tree.map(on_chip, jax.eval_shape(
            lambda v: spec.prepare(model, v), drawn))
        b, c = 64, model.cfg
        state = dict(jax.eval_shape(lambda: model.empty_state(b))[0],
                     tokens=jax.ShapeDtypeStruct(
                         (b, c.max_rounds * c.decode_steps), jnp.int32))
        state = jax.tree.map(on_chip, state)
        ints = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=v5e)
        compiled = jax.jit(build_serving_step(model, spec),
                           donate_argnums=(2,)).lower(
            variables, jax.ShapeDtypeStruct((b, 8, 360, 640, 3), jnp.uint8,
                                            sharding=v5e),
            state, ints, ints,
            jax.ShapeDtypeStruct((b,), bool, sharding=v5e), ints).compile()
        mem = compiled.memory_analysis()
        held = sum(a.size * a.dtype.itemsize
                   for a in jax.tree_util.tree_leaves(state))
        assert mem.alias_size_in_bytes >= held
        draw = 4 * sum(a.size for a in jax.tree_util.tree_leaves(drawn))
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
            + draw < 16e9
