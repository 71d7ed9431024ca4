"""Grouped-query prefill attention as one kernel a chunk
(``ops/flash_attention.py`` ``gqa_prefill_attention``, called by
``models/gqa.py`` ``CachedAttention``), interpreted on the CPU in float32
against the stream-by-stream softmax it replaced, kept here as the
reference; the count of the tiles it visits, which both grouped-query
heads report; and that a stack's attentions trace the kernel once."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from video_edge_ai_proxy_tpu.models import gqa, registry
from video_edge_ai_proxy_tpu.ops import flash_attention as fa


def _reference(q, pool_k, pool_v, new_k, new_v, slots, pos0):
    """The attention as a loop over streams (the program's before the
    kernel): q [B, T, H, hd] against each row's slot of one layer's pool
    (``pool_k`` / ``pool_v`` [slots, KV, hd, S], the positions before
    ``pos0`` [B] the context, the rest masked) and, causally, against the T
    new keys and values [B, KV, hd, T]. Returns [B, T, H x hd]."""
    b, t, h, hd = q.shape
    kv = new_k.shape[1]
    g = h // kv
    causal = jnp.tril(jnp.ones((t, t), bool))

    def one(args):
        qc, sc, pc, kn, vn = (a[None] for a in args)
        kc = jnp.concatenate(
            [jnp.take(pool_k, sc, axis=0, mode="clip"), kn], axis=-1)
        vc = jnp.concatenate(
            [jnp.take(pool_v, sc, axis=0, mode="clip"), vn], axis=-1)
        s = jnp.einsum("btkgd,bkds->bkgts", qc.reshape(1, t, kv, g, hd),
                       kc).astype(jnp.float32) * hd ** -0.5
        old = jnp.arange(pool_k.shape[-1])[None, :] < pc[:, None]   # [1, S]
        mask = jnp.concatenate(
            [jnp.broadcast_to(old[:, None], (1, t, old.shape[1])),
             causal[None]], axis=-1)
        s = jnp.where(mask[:, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1).astype(qc.dtype)
        return jnp.einsum("bkgts,bkds->btkgd", p, vc).reshape(t, h * hd)

    return jax.lax.map(one, (q, slots, pos0, new_k, new_v))


# 144 new positions (two key blocks of 128, the second mostly padding)
# after up to 512 cacheable ones (two key blocks of 256) in a pool of 656
T, CAP, SLOTS, LAYERS = 144, 512, 7, 2
CASES = {
    # none, one, mid-block, one under / at / one over a key block's edge,
    # all that can be cached
    "contexts": ([0, 3, 5, 1, 2, 6, 4], [0, 1, 100, 255, 256, 257, CAP]),
    # a padded batch row's slot lies past the pool: it reads some slot
    "a_padded_row": ([2, 5, 99, 0], [7, 300, 40, CAP]),
}
SHAPES = {"lfm2": (64, 8, 4), "falcon_h1": (128, 2, 5)}   # hd, KV, g


def _inputs(hd, kv, g, b):
    k = jax.random.split(jax.random.PRNGKey(hd + g), 5)
    q = jax.random.normal(k[0], (b, T, g * kv, hd))
    new_k, new_v = (jax.random.normal(x, (b, kv, hd, T)) for x in k[1:3])
    pool = tuple(jax.random.normal(x, (LAYERS, SLOTS, kv, hd, CAP + T))
                 for x in k[3:])
    return q, new_k, new_v, pool


@pytest.mark.parametrize("how", ["alone", "nan_past_the_contexts",
                                 "in_a_loop"])
@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_kernel_is_the_loop_it_replaced(shape, case, how):
    """Streams in different slots at different depths, both heads' widths
    (LFM2: 8 key-value heads of 64 and groups of 4; Falcon-H1: 2 of 128
    and groups of 5): the kernel is the stream-by-stream softmax to 1e-5
    of the output's scale. What lies past a context, NaN included, never
    reaches a result; inside a ``fori_loop``, as the prefill's chunks call
    it, it is the same."""
    hd, kv, g = SHAPES[shape]
    slots, ctx = (jnp.asarray(a) for a in CASES[case])
    assert gqa.prefill_cap(CAP + T, T) == CAP
    q, new_k, new_v, pool = _inputs(hd, kv, g, len(slots))
    given = pool
    if how == "nan_past_the_contexts":
        for s, n in zip(CASES[case][0], CASES[case][1]):
            given = tuple(p.at[:, min(s, SLOTS - 1), :, :, n:].set(jnp.nan)
                          for p in given)
    call = functools.partial(fa.gqa_prefill_attention, cap=CAP)
    if how == "in_a_loop":
        got = jax.jit(lambda q, pool: jax.lax.fori_loop(
            0, 2, lambda i, acc: acc + call(q, new_k, new_v, *pool, i, slots,
                                            ctx),
            jnp.zeros((len(slots), T, g * kv * hd))))(q, given)
        want = sum(_reference(q, pool[0][i], pool[1][i], new_k, new_v,
                              slots, ctx) for i in range(LAYERS))
    else:
        got = call(q, new_k, new_v, *given, 1, slots, ctx)
        want = _reference(q, pool[0][1], pool[1][1], new_k, new_v, slots,
                          ctx)
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == (len(slots), T, g * kv * hd)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_nothing_cached_takes_no_pool(shape):
    """``init``'s forward and the standing instruction run through a pool
    of no positions: nothing can be cached, and the kernel takes no
    pool."""
    hd, kv, g = SHAPES[shape]
    q, new_k, new_v, _ = _inputs(hd, kv, g, 2)
    cfg = gqa.GqaConfig(64, g * kv, kv, hd, 1e-5, 1e4)
    pool = gqa.empty_pool(cfg, LAYERS, 1, 0, jnp.float32)
    assert gqa.prefill_cap(0, T) == 0
    zero = jnp.zeros((2,), jnp.int32)
    got = fa.gqa_prefill_attention(q, new_k, new_v, *pool, 0, jnp.arange(2),
                                   zero, cap=0)
    want = _reference(q, pool[0][0], pool[1][0], new_k, new_v,
                      jnp.arange(2), zero)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() \
        <= 1e-5 * np.abs(np.asarray(want)).max()


def test_the_count_of_the_tiles_it_visits():
    """By hand, at the LFM2 cell's shapes: 784 new positions in a pool of
    4,096, 3,328 of which can be context (26 key blocks of 128); the new
    positions are 7 key blocks, every one visited; a group of 4 heads'
    3,136 query columns are 25 lane tiles, each visited by every block the
    kernel visits."""
    assert gqa.prefill_cap(4096, 792) == 3328
    assert fa.gqa_prefill_blocks(784, 4, 3328) == (128, 128, 3200)
    got = gqa.prefill_visits(jnp.asarray([32, 1616, 3200]), 784, 4, 3328, 2)
    assert int(got["attn_blocks_live"]) == 2 * (1 + 13 + 25 + 3 * 7) * 25
    assert int(got["attn_blocks_dense"]) == 2 * 3 * (26 + 7) * 25
    # Falcon-H1's groups of 5: 3,920 columns, 31 lane tiles
    assert fa.gqa_prefill_blocks(784, 5, 3328) == (128, 128, 3968)
    # a pool no lane tile divides is one key block
    assert fa.gqa_prefill_blocks(50, 2, 224) == (128, 224, 128)
    # the instruction through a fresh state: nothing can be cached, and its
    # 32 positions x 4 heads are one lane tile against one key block
    got = gqa.prefill_visits(jnp.asarray([0]), 32, 4, 0, 2)
    assert (int(got["attn_blocks_live"]), int(got["attn_blocks_dense"])) \
        == (2, 2)


def test_the_count_is_the_kernels_own():
    """The key blocks the kernel's loops visit, counted as it runs
    (interpreted, a callback in the block body), each against all of a
    group's lane tiles of query columns, are the count's."""
    hd, kv, g = SHAPES["falcon_h1"]
    slots, ctx = (jnp.asarray(a) for a in CASES["contexts"])
    q, new_k, new_v, pool = _inputs(hd, kv, g, len(slots))
    visits = []
    softmax = fa._softmax_block

    def counted(m, l, logits, keys=-1):
        jax.debug.callback(lambda: visits.append(1))
        return softmax(m, l, logits, keys)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(fa, "_softmax_block", counted)
        jax.clear_caches()
        jax.block_until_ready(fa.gqa_prefill_attention(
            q, new_k, new_v, *pool, 1, slots, ctx, cap=CAP))
    jax.clear_caches()
    live, _ = fa.gqa_prefill_visits(ctx, T, g, CAP)
    tiles = fa.gqa_prefill_blocks(T, g, CAP)[2] // 128
    assert len(visits) * tiles == kv * int(live)


@pytest.mark.parametrize("name", ["tiny_videomae_lfm2",
                                  "tiny_videomae_b_falcon_h1"])
def test_a_stack_traces_the_kernel_once(name):
    """Every attention of a stack shares one trace of the kernel (the layer
    is an operand, prefetched, not a static argument): ``init``'s forward,
    the standing instruction and the serving step are one trace each."""
    from video_edge_ai_proxy_tpu.engine import runner

    traces = []
    kernel = fa._gqa_prefill_kernel

    def counted(*a, **k):
        traces.append(1)
        return kernel(*a, **k)

    spec = registry.get(name)
    model = spec.build()
    c = model.cfg
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(fa, "_gqa_prefill_kernel", counted)
        jax.clear_caches()
        params = jax.eval_shape(
            lambda: spec.init_params(jax.random.PRNGKey(0))[1])
        assert len(traces) == 1
        variables = jax.eval_shape(lambda p: spec.prepare(model, p), params)
        assert len(traces) == 2
        b = 2
        state, _ = jax.eval_shape(lambda: model.empty_state(b))
        state = dict(state, tokens=jax.ShapeDtypeStruct(
            (b, c.max_rounds * c.decode_steps), jnp.int32))
        ints = jax.ShapeDtypeStruct((b,), jnp.int32)
        size = c.video.image_size
        jax.jit(runner.build_serving_step(model, spec)).lower(
            variables, jax.ShapeDtypeStruct(
                (b, spec.clip_len, size, size, 3), jnp.uint8),
            state, ints, ints, jax.ShapeDtypeStruct((b,), bool), ints)
        assert len(traces) == 3
    jax.clear_caches()


@pytest.mark.parametrize("name", ["tiny_videomae_lfm2",
                                  "tiny_videomae_b_falcon_h1"])
def test_a_round_reports_what_its_prefill_attention_visited(name):
    """Both grouped-query heads' rounds carry ``attn_blocks_live`` /
    ``attn_blocks_dense``: the kernel's count over the round's streams and
    attention layers."""
    spec = registry.get(name)
    model = spec.build()
    c = model.cfg
    variables = spec.prepare(model, spec.init_params(jax.random.PRNGKey(0))[1])
    b, n_i = 2, len(c.instruction_ids)
    size = c.video.image_size
    clips = jax.random.normal(jax.random.PRNGKey(1),
                              (b, c.video.num_frames, size, size, 3))
    pos0 = jnp.asarray([n_i, n_i + c.round_positions], jnp.int32)
    state = model.empty_state(b)[0]
    out = model.serve_round(variables, clips, state, jnp.arange(b), pos0,
                            jnp.asarray([True, False]))
    hc = c.head
    layers = hc.attn_layers if hasattr(hc, "attn_layers") else hc.num_layers
    want = gqa.prefill_visits(
        pos0, c.visual_tokens, hc.num_heads // hc.num_kv_heads,
        gqa.prefill_cap(hc.max_context, c.round_positions), layers)
    for kind in ("live", "dense"):
        assert int(out[f"attn_blocks_{kind}"]) == int(
            want[f"attn_blocks_{kind}"])
    # the twins' new positions are one key block and their columns one lane
    # tile, and what can be cached one key block: 2 tiles a stream an
    # attention
    assert int(out["attn_blocks_dense"]) == 2 * b * layers
