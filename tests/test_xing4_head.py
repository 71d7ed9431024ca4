"""The second streaming head (``models/xing4.py``): its latent attention's
two paths, its hyper-connected residual, its expert layer with the shared
expert (``models/transformer.py`` ``TopKMoeMlp``), its prediction module as
the decode loop's drafter, and its state in the pool
(``engine/stream_state.py``) through the ``stream`` step kind, against the
benchmark's plain reference (``benchmark/reference/xing4_stream.py``,
loaded by path) on seeded weights at tiny sizes. CPU, float32: results and
counts only."""

import dataclasses
import json
import os
import sys

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from vbench import loader, weights  # noqa: E402

from video_edge_ai_proxy_tpu.engine import runner  # noqa: E402
from video_edge_ai_proxy_tpu.engine.stream_state import (  # noqa: E402
    StreamStatePool, first_context_rounds)
from video_edge_ai_proxy_tpu.models import mla, registry, xing4  # noqa: E402
from video_edge_ai_proxy_tpu.models.transformer import (  # noqa: E402
    TopKMoeConfig, TopKMoeMlp, topk_route)

TINY = "tiny_videomae_xing4"
H, W = 48, 64


def _tiny_sizes():
    with open(os.path.join(BENCH, "tests", "data", "tiny_xing4.json")) as f:
        return loader.models(json.load(f))[0]


def _nest(flat, prefix):
    tree = {k[len(prefix):]: v for k, v in flat.items()
            if k.startswith(prefix)}
    return {"params": flax.traverse_util.unflatten_dict(tree, sep="/")}


def _reference():
    return loader.reference("xing4_stream"), loader.reference(
        "vision_transformer")


def _variables(seed, module=None):
    m = _tiny_sizes()
    fam = loader.family(m["family"])
    spec = registry.get(TINY)
    if module is None:
        module = spec.build()
        assert fam.check_sizes(module, m["sizes"]) == {}
    sizes = dict(m["sizes"], vocab_size=module.cfg.head.vocab_size,
                 instruction_ids=list(module.cfg.instruction_ids))
    flat = weights.generate(seed, m["family"], sizes)
    return dict(m, sizes=sizes), fam, spec, module, flat, spec.prepare(
        module, weights.as_variables(flat, fam.template(spec, module)))


# -- the two attention paths against the plain form --------------------------

def test_both_attention_paths_give_the_plain_form():
    """One stream's 21 positions: 9 cached in the pool, 10 prefilled (the
    cached rows up-projected), 2 decoded in the latent space (the
    up-projection absorbed): each is the plain form's row, which attends
    per-head keys and values over the whole sequence."""
    m = _tiny_sizes()
    sizes = m["sizes"]
    flat = weights.generate(3, m["family"], sizes)
    ref, vt = _reference()
    cfg = xing4.tiny_stream_head_config().head
    attn = mla.MlaAttention(cfg.mla, dtype=jnp.float32)
    params = _nest(flat, "head/layer1/attn/")
    n_old, n_new, n_dec = 9, 10, 2
    t = n_old + n_new + n_dec
    h = jax.random.normal(jax.random.PRNGKey(5), (t, sizes["hidden_size"]))
    want = np.asarray(ref._attention(flat, "head/layer1/attn/", h, sizes,
                                     vt._einsum("")))
    d = cfg.row_dim             # 24 numbers a row, in a 128-wide lane tile
    rows = attn.apply(params, h[None, :n_old], jnp.arange(n_old)[None],
                      method=mla.MlaAttention.latent)
    # the stream owns slot 1 of 3; the others hold noise that is masked
    pool = jax.random.normal(jax.random.PRNGKey(6), (3, 32, d))
    pool = pool.at[1, :n_old].set(rows[0])
    slots, ctx = jnp.asarray([1]), jnp.asarray([n_old])
    rbuf = jnp.zeros((1, n_new + n_dec + 1, d))
    got, rbuf = attn.apply(params, h[None, n_old:n_old + n_new], pool, rbuf,
                           slots, ctx, None, 16)
    np.testing.assert_allclose(np.asarray(got[0]),
                               want[n_old:n_old + n_new], atol=2e-5)
    got, rbuf = attn.apply(params, h[None, n_old + n_new:], pool, rbuf,
                           slots, ctx, jnp.asarray([n_new]), 0)
    np.testing.assert_allclose(np.asarray(got[0]), want[n_old + n_new:],
                               atol=2e-5)
    # the cache rows are the latent form: one row a position, no heads,
    # zero past the latent and the rope part
    assert rbuf.shape == (1, n_new + n_dec + 1, d)
    assert cfg.latent_dim == cfg.kv_lora_rank + cfg.qk_rope_head_dim == 24
    assert np.asarray(rbuf)[0, :n_new + n_dec, :24].any()
    assert not np.asarray(rbuf)[..., 24:].any()


def _prefill_case(name):
    """(sizes, slots, ctx, what the rows past ``ctx`` hold) of one case of
    the test below; a key block of the cache is 256 rows at ``cap`` 1024
    (``ops/flash_attention.py`` ``latent_prefill_blocks``)."""
    small = dict(h=2, dn=8, dr=4, dv=8, r=16, row=128, t=5, cap=1024,
                 slots_in_pool=7, positions=1200)
    edges = ([5, 0, 3, 6, 1, 2], [0, 1, 255, 256, 257, 1024])
    return {
        # contexts that end in the first, second and last quarter of what
        # can be cached
        "attends_only_the_quarters_a_context_reaches_into":
            (small, [2, 0, 3], [7, 300, 1000], None),
        # none, one, one under / at / one over a key block's edge, all
        "edges_of_a_key_block": (small, *edges, None),
        # what lies past a context never reaches a result
        "rows_past_the_context_hold_1e30": (small, *edges, 1e30),
        "rows_past_the_context_hold_nan": (small, *edges, np.nan),
        # a padded batch row's slot is past the pool: it reads the last
        "a_padded_row_reads_some_slot": (small, [2, 7, 99], [7, 300, 40],
                                         None),
        # the published widths through the interpreter
        "published_widths": (dict(h=2, dn=128, dr=64, dv=128, r=512,
                                  row=640, t=7, cap=256, slots_in_pool=2,
                                  positions=300), [1, 0], [100, 256], None),
    }[name]


@pytest.mark.parametrize("case", [
    "attends_only_the_quarters_a_context_reaches_into",
    "edges_of_a_key_block", "rows_past_the_context_hold_1e30",
    "rows_past_the_context_hold_nan", "a_padded_row_reads_some_slot",
    "published_widths"])
def test_prefill(case):
    """Streams in different slots at different depths: each is the plain
    float32 softmax over its context and, causally, the new rows."""
    z, slots, ctx, garbage = _prefill_case(case)
    h, dn, dr, dv, r, t, cap = (z[k] for k in
                                ("h", "dn", "dr", "dv", "r", "t", "cap"))
    n_s, b = z["slots_in_pool"], len(slots)
    k = jax.random.split(jax.random.PRNGKey(0), 6)
    q = jax.random.normal(k[0], (b, t, h, dn + dr))
    new = jax.random.normal(k[1], (b, t, z["row"]))
    pool = jax.random.normal(k[2], (n_s, z["positions"], z["row"]))
    w_uk = jax.random.normal(k[3], (r, h, dn)) * r ** -0.5
    w_uv = jax.random.normal(k[4], (r, h, dv)) * r ** -0.5
    held = [min(s, n_s - 1) for s in slots]
    given = pool
    if garbage is not None:
        for s, n in zip(held, ctx):
            given = given.at[s, n:].set(garbage)
    got = mla.mla_prefill_attention(
        q, new, w_uk, w_uv, given, jnp.asarray(slots), jnp.asarray(ctx),
        0.3, cap)
    assert got.shape == (b, t, h * dv)
    for i, (s, n) in enumerate(zip(held, ctx)):
        rows = jnp.concatenate([pool[s, :n], new[i]], axis=0)
        keys = jnp.concatenate(
            [jnp.einsum("sr,rhd->shd", rows[:, :r], w_uk),
             jnp.broadcast_to(rows[:, None, r:r + dr], (n + t, h, dr))], -1)
        s_ = jnp.einsum("thd,shd->hts", q[i], keys) * 0.3
        mask = jnp.arange(n + t)[None] <= n + jnp.arange(t)[:, None]
        p = jax.nn.softmax(jnp.where(mask[None], s_, -jnp.inf), axis=-1)
        want = jnp.einsum("hts,shd->thd", p, jnp.einsum(
            "sr,rhd->shd", rows[:, :r], w_uv)).reshape(t, -1)
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(want),
                                   atol=2e-5)


def test_prefill_counts_the_tiles_it_visits():
    """The step's own count against a hand count. A tile is a key block
    against a lane tile of 128 queries. 12 new positions are one key block
    and one lane tile; 1024 cacheable positions are four key blocks of
    256: a context of 0 / 1 / 256 / 257 / 1024 visits 0 / 1 / 1 / 2 / 4 of
    them, in each of 3 attentions."""
    from video_edge_ai_proxy_tpu.ops.flash_attention import (
        latent_prefill_blocks)

    assert latent_prefill_blocks(12, 1024) == (12, 256)
    got = mla.prefill_visits(jnp.asarray([0, 1, 256, 257, 1024]), 12, 1024,
                             3)
    assert int(got["attn_blocks_live"]) == 3 * ((0 + 1 + 1 + 2 + 4) + 5)
    assert int(got["attn_blocks_dense"]) == 3 * 5 * (4 + 1)
    # the cells' shapes: 784 queries are 7 lane tiles; 3,328 cacheable
    # positions 13 key blocks; the new rows 7 key blocks of 112, whose
    # first keys lie in lane tiles 0, 0, 1, 2, 3, 4, 5: they are scored
    # against 7, 7, 6, 5, 4, 3, 2 lane tiles of queries, 34 of 49
    assert latent_prefill_blocks(784, 3328) == (112, 256)
    got = mla.prefill_visits(jnp.asarray([32, 1616, 3200]), 784, 3328, 1)
    assert int(got["attn_blocks_live"]) == (1 + 7 + 13) * 7 + 3 * 34
    assert int(got["attn_blocks_dense"]) == 3 * (13 + 7) * 7
    # nothing cacheable (the instruction through a fresh state)
    got = mla.prefill_visits(jnp.asarray([0]), 32, 0, 2)
    assert (int(got["attn_blocks_live"]), int(got["attn_blocks_dense"])) \
        == (2, 2)


def test_a_holder_of_some_heads_gives_their_partial_sum():
    """Told which heads it holds (the DeepSeek-V2 form), the attention is
    those heads' share of the whole: two holders' outputs add up to the
    output of one that holds all four, on both paths."""
    cfg = dataclasses.replace(xing4.tiny_stream_head_config().head.mla,
                              num_heads=4)
    dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                  cfg.v_head_dim)
    whole = mla.MlaAttention(cfg, dtype=jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 6, cfg.dim))
    pool = jax.random.normal(jax.random.PRNGKey(2), (2, 3, 40, cfg.row_dim))
    rbuf = jnp.zeros((2, 9, cfg.row_dim))
    slots, ctx = jnp.asarray([2, 0]), jnp.asarray([17, 33])
    params = flax.core.meta.unbox(whole.init(
        jax.random.PRNGKey(3), h, pool, rbuf, slots, ctx, None, 40, 1))

    def share(held):
        p = dict(params["params"])
        cols = lambda w, d: np.asarray(w).reshape(  # noqa: E731
            w.shape[0], 4, d)[:, list(held)].reshape(w.shape[0], -1)
        p["q_b"], p["kv_b"] = cols(p["q_b"], dn + dr), cols(p["kv_b"],
                                                             dn + dv)
        p["o"] = np.asarray(p["o"]).reshape(4, dv, -1)[list(held)].reshape(
            len(held) * dv, -1)
        return mla.MlaAttention(dataclasses.replace(cfg, heads_held=held),
                                dtype=jnp.float32), {"params": p}

    for at, cap in ((None, 40), (jnp.asarray([6, 6]), 0)):
        want, _ = whole.apply(params, h, pool, rbuf, slots, ctx, at, cap, 1)
        parts = [m.apply(p, h, pool, rbuf, slots, ctx, at, cap, 1)[0]
                 for m, p in (share((1, 3)), share((0, 2)))]
        assert float(jnp.abs(parts[0]).max()) > 1e-3
        np.testing.assert_allclose(np.asarray(parts[0] + parts[1]),
                                   np.asarray(want), atol=2e-5)


def test_yarn_is_the_published_blend():
    cfg = xing4.Xing4Config()
    inv = mla.yarn_inv_freq(cfg.mla)
    plain = cfg.rope_theta ** (-np.arange(0, 64, 2) / 64)
    # the fastest pairs keep their frequency, the slowest are slowed 64 x
    np.testing.assert_allclose(inv[:8], plain[:8], rtol=1e-6)
    np.testing.assert_allclose(inv[-8:], plain[-8:] / 64, rtol=1e-6)
    assert np.all(np.diff(inv) < 0)
    assert abs(mla.softmax_scale(cfg.mla) - 0.1447) < 1e-4


# -- the residual's maps ------------------------------------------------------

def test_h_res_is_doubly_stochastic_and_without_sinkhorn_it_is_not():
    cfg = xing4.tiny_stream_head_config().head
    m = _tiny_sizes()
    flat = weights.generate(4, m["family"], m["sizes"])
    x = jax.random.normal(jax.random.PRNGKey(1), (13, cfg.hc_mult, cfg.dim))
    params = _nest(flat, "head/layer1/ffn_hc/")
    # the program holds the streams first, the reference a position's [n, C]
    streams = x.transpose(1, 0, 2)
    pre, post, res = xing4.HyperResidual(cfg).apply(params, streams)
    assert pre.shape == post.shape == (4, 13) and res.shape == (4, 4, 13)
    assert np.all(np.asarray(pre) > 0) and np.all(np.asarray(pre) < 1)
    assert np.all(np.asarray(post) > 0) and np.all(np.asarray(post) < 2)
    for axis in (0, 1):
        np.testing.assert_allclose(np.asarray(res.sum(axis)), 1.0, atol=1e-4)
    # no identity: the drawn biases mix the streams
    assert float(np.abs(np.asarray(res)[0, 1]).max()) > 0.05
    # against the plain reference's maps
    ref, vt = _reference()
    rp, rq, rr = ref._maps(flat, "head/layer1/ffn_hc/", x, m["sizes"],
                           vt._einsum(""))
    np.testing.assert_allclose(np.asarray(res), np.asarray(rr).transpose(
        1, 2, 0), atol=1e-5)
    np.testing.assert_allclose(np.asarray(pre), np.asarray(rp).T, atol=1e-5)
    np.testing.assert_allclose(np.asarray(post), np.asarray(rq).T, atol=1e-5)
    # planted: the map left unnormalised
    raw = xing4.HyperResidual(dataclasses.replace(
        cfg, hc_sinkhorn_iters=0)).apply(params, streams)[2]
    assert float(np.abs(np.asarray(raw.sum(0)) - 1.0).max()) > 1e-2


# -- the expert layer: the shared expert, the shares, LFM2 bit-equal ---------

def _moe_params(seed, n_experts=16, dim=32, width=24, shared=24):
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    full = {"gate": jax.random.normal(k[0], (dim, n_experts)) * dim ** -0.5,
            "expert_bias": jax.random.normal(k[1], (n_experts,)) * 0.2,
            "w1": jax.random.normal(k[2], (n_experts, dim, width)) * 0.2,
            "w3": jax.random.normal(k[3], (n_experts, dim, width)) * 0.2,
            "w2": jax.random.normal(k[4], (n_experts, width, dim)) * 0.2}
    if shared:
        full.update(
            shared_w1=jax.random.normal(k[5], (dim, shared)) * 0.2,
            shared_w3=jax.random.normal(k[6], (dim, shared)) * 0.2,
            shared_w2=jax.random.normal(k[7], (shared, dim)) * 0.2)
    return full


def _share(full, held):
    ids = np.asarray(held)
    return {"params": dict(full, w1=full["w1"][ids], w3=full["w3"][ids],
                           w2=full["w2"][ids])}


def test_the_four_shares_add_up_to_the_uncut_reference_layer():
    """Four holders of 4 of the 16 experts each, every one adding the
    shared expert: their sum, with the shared expert counted once, is the
    plain reference's layer with all 16 held, scaled by 2."""
    full = _moe_params(0)
    x = jax.random.normal(jax.random.PRNGKey(9), (40, 32))
    ref, vt = _reference()
    cfg = {"experts_held": list(range(16)), "num_experts_per_tok": 4,
           "norm_topk_prob": True, "routed_scaling_factor": 2}
    want = ref._experts({"moe/" + k: v for k, v in full.items()}, "moe/", x,
                        cfg, vt._einsum(""))
    shared = (jax.nn.silu(x @ full["shared_w1"]) * (x @ full["shared_w3"])) \
        @ full["shared_w2"]
    total, pairs = 0.0, 0
    for held in (range(0, 4), range(4, 8), range(8, 12), range(12, 16)):
        layer = TopKMoeMlp(TopKMoeConfig(
            dim=32, mlp_dim=24, num_experts=16, top_k=4,
            experts_held=tuple(held), routed_scaling_factor=2.0,
            shared_mlp_dim=24), dtype=jnp.float32)
        y, counts = layer.apply(_share(full, held), x)
        total, pairs = total + y, pairs + int(counts["held"].sum())
    assert pairs == 40 * 4
    np.testing.assert_allclose(np.asarray(total - 3 * shared),
                               np.asarray(want), rtol=2e-4, atol=2e-5)
    # the scaling is in it: at 1 for 2 the routed part halves
    half = ref._experts({"moe/" + k: v for k, v in full.items()}, "moe/", x,
                        dict(cfg, routed_scaling_factor=1), vt._einsum(""))
    assert float(jnp.abs(want - half).max()) > 0.01


def _lfm2_layer_as_it_was(params, x, cfg: TopKMoeConfig):
    """``TopKMoeMlp`` as PR 29 wrote it (no epsilon in the divisor, no
    shared expert), every held expert on every token: the parent's
    arithmetic for the comparison below."""
    scores = jax.nn.sigmoid(jnp.dot(x, params["gate"],
                                    precision=jax.lax.Precision.HIGHEST))
    _, sel = jax.lax.top_k(scores + params["expert_bias"], cfg.top_k)
    w = jnp.take_along_axis(scores, sel, axis=-1)
    return sel, w / jnp.sum(w, axis=-1, keepdims=True)


def test_lfm2s_expert_layer_is_bit_equal_with_the_published_epsilon():
    """LFM2 runs the layer without a shared expert and at scaling 1: its
    tree has no new parameter, its routing weights are the parent's bit for
    bit (1e-20 is far under half an ulp of a sum of sigmoid scores), and so
    is its output."""
    cfg = TopKMoeConfig(dim=32, mlp_dim=24, num_experts=16, top_k=4,
                        experts_held=tuple(range(4)))
    full = _moe_params(2, shared=0)
    x = jax.random.normal(jax.random.PRNGKey(4), (64, 32))
    layer = TopKMoeMlp(cfg, dtype=jnp.float32)
    tree = layer.init(jax.random.PRNGKey(0), x)["params"]
    assert sorted(tree) == ["expert_bias", "gate", "w1", "w2", "w3"]
    scores = jax.nn.sigmoid(jnp.dot(x, full["gate"],
                                    precision=jax.lax.Precision.HIGHEST))
    sel, w = topk_route(scores, full["expert_bias"], cfg)
    sel0, w0 = _lfm2_layer_as_it_was(full, x, cfg)
    np.testing.assert_array_equal(np.asarray(sel), np.asarray(sel0))
    np.testing.assert_array_equal(np.asarray(w), np.asarray(w0))
    # and the layer's output from those weights, against the same layer
    # with the parent's routing patched in
    y, _ = layer.apply(_share(full, range(4)), x)
    import video_edge_ai_proxy_tpu.models.transformer as tr

    real = tr.topk_route
    tr.topk_route = lambda scores, bias, c: (sel0, w0)
    try:
        y0, _ = layer.apply(_share(full, range(4)), x)
    finally:
        tr.topk_route = real
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y0))


# -- the whole stack through the pool against one full forward -------------

def test_rounds_through_the_pool_match_one_full_forward():
    """Two streams, started a round apart, six rounds through the ``stream``
    step and the state pool (prefill with the cached rows up-projected,
    drafted decode in the latent space, a reset when the context is full
    and the cut first context): every round's logits and its first draft's
    are the reference's, which sees the whole context at once."""
    m, fam, spec, module, flat, variables = _variables(7)
    c = module.cfg
    step = jax.jit(runner.build_serving_step(module, spec),
                   donate_argnums=(2,))
    pool = StreamStatePool(module, grow=2)
    ref = loader.reference(m["reference"]).jitted(
        m["family"], loader.frozen(m["sizes"]))
    rng = np.random.default_rng(0)
    n = c.video.num_frames
    frames = {d: rng.integers(0, 255, (n + 6, H, W, 3), dtype=np.uint8)
              for d in ("cam_a", "cam_b")}
    first = {d: first_context_rounds(d, c.max_rounds) for d in frames}
    history = {d: [] for d in frames}
    resets = {d: 0 for d in frames}
    for r in range(6):
        ids = ["cam_a"] + (["cam_b"] if r >= 1 else [])
        k = {d: r - (d == "cam_b") for d in ids}     # the stream's own round
        batch = np.zeros((2, n, H, W, 3), np.uint8)
        for i, d in enumerate(ids):
            batch[i] = frames[d][k[d]:k[d] + n]
        plan = pool.plan(ids, 2)
        out = step(variables, batch, pool.state, plan["idx"], plan["pos0"],
                   plan["reset"], plan["rounds"])
        pool.state = out.pop("state")
        assert 1 <= int(out["decode_iters"]) <= c.decode_steps
        for i, d in enumerate(ids):
            rounds = int(out["rounds"][i])
            resets[d] += int(plan["reset"][i])
            assert (rounds, int(out["positions"][i])) == fam.expected_state(
                d, k[d] + 1, m["sizes"])
            hist = [int(t) for t in np.asarray(out["history"][i]) if t >= 0]
            assert len(hist) == rounds * c.decode_steps
            assert hist[-c.decode_steps:] == out["tokens"][i].tolist()
            start = k[d] - (rounds - 1)
            window = frames[d][start:start + n + rounds - 1]
            buf = np.zeros((1, n + c.max_rounds - 1, H, W, 3), np.uint8)
            buf[0, :len(window)] = window
            w = fam.Window(range(len(window)), rounds, hist)
            logits = np.asarray(ref(flat, *fam.reference_args(
                buf, [w], m["sizes"])))[0]
            assert logits.shape == (c.decode_steps + 1, c.head.vocab_size)
            lp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
            got = np.log(np.asarray(out["top_probs"][i]))
            want = np.take_along_axis(lp[:-1], np.asarray(out["top_ids"][i]),
                                      -1)
            np.testing.assert_allclose(got, want, atol=0.03)
            np.testing.assert_allclose(
                np.log(np.asarray(out["draft_probs"][i])),
                lp[-1][np.asarray(out["draft_ids"][i])], atol=0.03)
            history[d].append(rounds)
    assert history["cam_a"][:first["cam_a"] + 1] == list(
        range(1, first["cam_a"] + 1)) + [1]
    assert resets["cam_a"] >= 2 and resets["cam_b"] >= 1
    assert max(history["cam_a"]) >= 3      # >= 3 rounds carried in a context


# -- the drafter changes how many iterations a round takes, nothing else ----

def _plain_greedy(module, variables, clips, state, slots, pos0, reset):
    """A round decoded one position an iteration by the main model alone
    (no drafter), then the prediction module's cache rows of the committed
    positions: the tokens, distributions and state the drafted loop has to
    reproduce."""
    from video_edge_ai_proxy_tpu.models.stream_head import top_tokens

    c = module.cfg
    cls = xing4.VideoMAEXing4
    apply = lambda method, *a: module.apply(variables, *a, method=method)  # noqa: E731
    pool, exit_ = module.seed_round(variables, state, slots, reset)
    rbuf = module.round_buffer(clips.shape[0], pool.dtype)
    x = apply(cls.encode, clips)
    h, exit_, rbuf, _ = module.prefill(variables, x, pool, exit_, rbuf,
                                       slots, pos0)
    toks, tops = [], []
    for r in range(c.decode_steps):
        tok, top_i, top_p = top_tokens(apply(cls.logits, h))
        toks.append(tok)
        tops.append((top_i, top_p))
        at = jnp.full(pos0.shape, c.visual_tokens + r, pos0.dtype)
        _, rbuf, _ = apply(cls.mtp, h[:, None], apply(cls.embed, tok)[:, None],
                           pool, rbuf, slots, pos0 - 1, at)
        hn, rbuf, _ = apply(cls.forward, apply(cls.embed, tok)[:, None],
                            pool, rbuf, slots, pos0, at)
        h = hn[:, 0]
    new = module.commit_round(state, pool, h, rbuf, slots, pos0)
    return (jnp.stack(toks, 1), jnp.stack([t[0] for t in tops], 1),
            jnp.stack([t[1] for t in tops], 1), new)


def test_self_drafting_is_plain_greedy_with_both_outcomes():
    """A twin whose vocabulary is 8 tokens, so that the module's draft is
    right some of the time: over three rounds of four streams the drafted
    loop gives plain greedy's tokens, distributions and state, drafts are
    accepted and rejected, and a row's iterations are its 3 tokens less
    the second positions it committed."""
    module = xing4.VideoMAEXing4(xing4.tiny_stream_head_config(8),
                                 dtype=jnp.float32)
    _, _, _, _, _, variables = _variables(21, module)
    c = module.cfg
    b = 4
    state = module.empty_state(b)[0]
    slots = jnp.arange(b)
    rng = np.random.default_rng(3)
    accepted = drafted = 0
    for r in range(3):
        clips = jnp.asarray(rng.normal(size=(b, 4, 32, 32, 3)), jnp.float32)
        pos0 = jnp.full((b,), len(c.instruction_ids) + r * c.round_positions,
                        jnp.int32)
        reset = jnp.full((b,), r == 0)
        want = _plain_greedy(module, variables, clips, state, slots, pos0,
                             reset)
        out = module.serve_round(variables, clips, state, slots, pos0, reset)
        np.testing.assert_array_equal(np.asarray(out["tokens"]),
                                      np.asarray(want[0]))
        np.testing.assert_array_equal(np.asarray(out["top_ids"]),
                                      np.asarray(want[1]))
        np.testing.assert_allclose(np.asarray(out["top_probs"]),
                                   np.asarray(want[2]), atol=1e-5)
        end = int(pos0[0]) + c.round_positions
        for kind in ("latent", "exit"):
            got, ref = np.asarray(out["state"][kind]), np.asarray(
                want[3][kind])
            np.testing.assert_allclose(got, ref, atol=2e-5, err_msg=kind)
        # nothing is committed past the round: a rejected draft's row died
        assert not np.asarray(out["state"]["latent"])[:, :, end:].any()
        acc, dr = np.asarray(out["mtp_accepted"]), np.asarray(
            out["mtp_drafted"])
        # an iteration commits one position, or two where the draft held
        # (the last token's draft cannot be committed)
        assert np.all(dr <= c.decode_steps - acc + 1)
        assert np.all(dr >= c.decode_steps - acc)
        assert int(out["decode_iters"]) == dr.max()
        # the twin's calls have one rung: every routed pair is moved
        moved, routed = np.asarray(out["moe_pair_rows"]).tolist()
        assert moved == routed >= int(np.asarray(out["moe_load"]).sum()) > 0
        accepted, drafted = accepted + int(acc.sum()), drafted + int(dr.sum())
        state = out["state"]
    assert 0 < accepted < drafted


# -- the pool holds whatever kinds of state a head declares ------------------

@pytest.mark.parametrize("model,kinds", [
    ("tiny_videomae_lfm2", {"conv": 0, "kv": 1}),
    (TINY, {"latent": 1, "exit": 0})])
def test_the_pool_grows_and_frees_a_heads_state_by_its_declared_kinds(
        model, kinds):
    module = registry.get(model).build()
    state, axes = module.empty_state(4)
    assert axes == kinds and set(state) == set(kinds)
    pool = StreamStatePool(module, grow=4)
    ids = [f"cam{i}" for i in range(4)]
    pool.plan(ids, 4)
    assert set(pool.state) == set(kinds) | {"tokens"}
    assert pool.capacity == 4
    bytes0 = pool.nbytes()
    assert bytes0 == sum(a.nbytes for a in jax.tree_util.tree_leaves(
        pool.state))
    # what the streams hold survives a growth, each kind along its own axis
    pool.state = jax.tree_util.tree_map(
        lambda a: a + jnp.arange(a.size, dtype=jnp.float32).reshape(
            a.shape).astype(a.dtype) % 7, pool.state)
    held = jax.tree_util.tree_map(np.asarray, pool.state)
    pool.plan(ids + ["more"], 8)
    assert pool.capacity == 8 and pool.nbytes() == 2 * bytes0
    for kind, axis in dict(kinds, tokens=0).items():
        for new, old in zip(jax.tree_util.tree_leaves(pool.state[kind]),
                            jax.tree_util.tree_leaves(held[kind])):
            np.testing.assert_array_equal(
                np.take(np.asarray(new), np.arange(4), axis=axis), old)
    # a stream that leaves frees its slot; the newcomer takes it, reset
    slot = pool._slots["cam1"]
    pool.pop("cam1")
    plan = pool.plan(["cam0", "new"], 8)
    assert plan["idx"][1] == slot and plan["reset"][1]
    assert pool.nbytes() == 2 * bytes0
    pool.lost()
    assert pool.nbytes() == 0 and len(pool) == 0


def test_the_latent_cache_is_one_row_of_576_a_position_a_block():
    """The published sizes: what the pool holds a stream, and against what
    per-head keys and values would take."""
    c = xing4.Xing4Config()
    state = jax.eval_shape(
        lambda: xing4.VideoMAEXing4(xing4.StreamHeadConfig()).empty_state(
            64)[0])
    # 576 numbers a row, held in 640 (a lane tile: what the chip pads a
    # 576-wide minor axis to)
    assert (c.latent_dim, c.row_dim) == (576, 640)
    assert state["latent"].shape == (6, 64, 4096, 640)
    assert state["latent"].dtype == jnp.bfloat16
    nbytes = int(np.prod(state["latent"].shape)) * 2
    assert abs(nbytes / 1e9 - 2.01) < 0.01
    per_head = 6 * 64 * 4096 * c.num_heads * (192 + 128) * 2
    assert per_head / nbytes > 15
