"""The third streaming head (``models/deepseek_v2.py``): latent attention
told which heads it holds (``models/mla.py``), the group-limited softmax
router (``models/transformer.py`` ``topk_route``), its counters, and its
one kind of state in the pool (``engine/stream_state.py``) through the
``stream`` step kind and the engine, against the benchmark's plain
reference (``benchmark/reference/deepseek_v2_stream.py``, loaded by path)
on seeded weights at tiny sizes. CPU, float32: results and counts only."""

import json
import os
import sys
import threading
import time

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

from video_edge_ai_proxy_tpu.bus.interface import FrameMeta  # noqa: E402
from video_edge_ai_proxy_tpu.bus.memory_bus import MemoryFrameBus  # noqa: E402
from video_edge_ai_proxy_tpu.engine import InferenceEngine  # noqa: E402
from video_edge_ai_proxy_tpu.engine import runner  # noqa: E402
from video_edge_ai_proxy_tpu.engine.stream_state import (  # noqa: E402
    StreamStatePool, first_context_rounds)
from video_edge_ai_proxy_tpu.models import (  # noqa: E402
    deepseek_v2, mla, registry)
from video_edge_ai_proxy_tpu.models.transformer import (  # noqa: E402
    TopKMoeConfig, TopKMoeMlp, kept_groups, topk_route)
from video_edge_ai_proxy_tpu.obs import registry as obs_registry  # noqa: E402
from video_edge_ai_proxy_tpu.uplink.queue import AnnotationQueue  # noqa: E402
from video_edge_ai_proxy_tpu.utils.config import EngineConfig  # noqa: E402

TINY = "tiny_videomae_dsv2"
H, W = 48, 64


def _tiny_sizes():
    with open(os.path.join(BENCH, "tests", "data", "tiny_dsv2.json")) as f:
        return loader.models(json.load(f))[0]


def _reference():
    return loader.reference("deepseek_v2_stream"), loader.reference(
        "vision_transformer")


def _variables(seed):
    m = _tiny_sizes()
    fam = loader.family(m["family"])
    spec = registry.get(TINY)
    module = spec.build()
    assert fam.check_sizes(module, m["sizes"]) == {}
    flat = weights.generate(seed, m["family"], m["sizes"])
    return m, fam, spec, module, flat, spec.prepare(
        module, weights.as_variables(flat, fam.template(spec, module)))


# -- (b) the shares tie to the model: heads ----------------------------------

ATTN = dict(dim=32, num_heads=4, q_lora_rank=24, kv_lora_rank=16,
            qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
            rope_factor=40.0, rope_original_max=64, rope_mscale=0.707,
            rope_mscale_all_dim=0.707)
ATTN_SIZES = {"kv_lora_rank": 16, "qk_nope_head_dim": 8,
              "qk_rope_head_dim": 8, "v_head_dim": 8, "rms_norm_eps": 1e-6,
              "rope_theta": 10000,
              "rope_scaling": {"factor": 40, "beta_fast": 32, "beta_slow": 1,
                               "mscale": 0.707, "mscale_all_dim": 0.707,
                               "original_max_position_embeddings": 64}}


def _attn_weights(seed):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    n = lambda key, shape, fan: jax.random.normal(key, shape) * fan ** -0.5  # noqa: E731
    return {"q_a": n(k[0], (32, 24), 32), "q_b": n(k[1], (24, 4 * 16), 24),
            "kv_a": n(k[2], (32, 24), 32), "kv_b": n(k[3], (16, 4 * 16), 16),
            "o": n(k[4], (4 * 8, 32), 32),
            "q_norm/scale": 1 + 0.1 * jax.random.normal(k[5], (24,)),
            "kv_norm/scale": 1 + 0.1 * jax.random.normal(k[6], (16,))}


def _head_share(full, heads):
    """The held heads' slices of ``q_b``, ``kv_b`` (columns a head) and
    ``o`` (rows a head), stacked in the order of ``heads``."""
    cols = np.concatenate([np.arange(16 * h, 16 * h + 16) for h in heads])
    rows = np.concatenate([np.arange(8 * h, 8 * h + 8) for h in heads])
    share = dict(full, q_b=full["q_b"][:, cols], kv_b=full["kv_b"][:, cols],
                 o=full["o"][rows])
    return {"params": flax.traverse_util.unflatten_dict(share, sep="/")}


@pytest.mark.parametrize("shares", [
    ((0, 1), (2, 3)), ((2,), (0, 3, 1)), ((0, 1, 2, 3),)],
    ids=["halves", "uneven-unordered", "whole"])
def test_the_head_shares_partial_outputs_sum_to_the_uncut_attention(shares):
    """One stream's 21 positions (9 cached, 10 prefilled, 2 decoded in the
    latent space): each holder computes its held heads' part of the output
    projection, and the parts of all holders add up to the plain
    reference's attention with all four heads. The cache rows are every
    holder's alike: they have no heads."""
    full = _attn_weights(3)
    ref, vt = _reference()
    n_old, n_new, n_dec = 9, 10, 2
    t = n_old + n_new + n_dec
    h = jax.random.normal(jax.random.PRNGKey(5), (t, 32))
    want = np.asarray(ref._attention(
        {"a/" + k: v for k, v in full.items()}, "a/", h,
        dict(ATTN_SIZES, heads_held=[0, 1, 2, 3]), vt._einsum("")))
    total, rows_of = 0.0, []
    for heads in shares:
        cfg = mla.MlaConfig(heads_held=tuple(heads), **ATTN)
        attn = mla.MlaAttention(cfg, dtype=jnp.float32)
        params = _head_share(full, heads)
        rows = attn.apply(params, h[None, :n_old], jnp.arange(n_old)[None],
                          method=mla.MlaAttention.latent)
        rows_of.append(np.asarray(rows))
        pool = jnp.zeros((2, 32, cfg.row_dim)).at[1, :n_old].set(rows[0])
        slots, ctx = jnp.asarray([1]), jnp.asarray([n_old])
        rbuf = jnp.zeros((1, n_new + n_dec, cfg.row_dim))
        pre, rbuf = attn.apply(params, h[None, n_old:n_old + n_new], pool,
                               rbuf, slots, ctx, None, 16)
        dec, rbuf = attn.apply(params, h[None, n_old + n_new:], pool, rbuf,
                               slots, ctx, jnp.asarray([n_new]), 0)
        total = total + np.concatenate([np.asarray(pre[0]),
                                        np.asarray(dec[0])])
    np.testing.assert_allclose(total, want[n_old:], atol=3e-5)
    for rows in rows_of[1:]:
        np.testing.assert_array_equal(rows, rows_of[0])
    if len(shares) > 1:
        # one share alone is not the whole: nothing stands in for the rest
        assert float(np.abs(total - want[n_old:]).max()) < 1e-4 < float(
            np.abs(np.asarray(pre[0]) - want[n_old:n_old + n_new]).max())


def test_a_head_slice_from_the_wrong_head_is_another_attention():
    full = _attn_weights(4)
    cfg = mla.MlaConfig(heads_held=(0, 1), **ATTN)
    attn = mla.MlaAttention(cfg, dtype=jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(6), (1, 6, 32))
    args = (jnp.zeros((1, 8, cfg.row_dim)), jnp.zeros((1, 6, cfg.row_dim)),
            jnp.asarray([0]), jnp.asarray([0]), None, 0)
    right, _ = attn.apply(_head_share(full, (0, 1)), h, *args)
    wrong, _ = attn.apply(_head_share(full, (0, 2)), h, *args)
    assert float(jnp.abs(right - wrong).max()) > 0.05


# -- (b) the shares tie to the model: experts --------------------------------

MOE = dict(dim=32, mlp_dim=24, num_experts=16, top_k=3, n_group=4,
           topk_group=2, use_expert_bias=False, norm_topk_prob=False,
           routed_scaling_factor=16.0, shared_mlp_dim=48, scoring="softmax")
MOE_SIZES = {"num_experts_per_tok": 3, "n_group": 4, "topk_group": 2,
             "norm_topk_prob": False, "routed_scaling_factor": 16}


def _moe_weights(seed, dim=32, width=24, shared=48, n=16):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    return {"gate": jax.random.normal(k[0], (dim, n)) * dim ** -0.5,
            "w1": jax.random.normal(k[1], (n, dim, width)) * 0.2,
            "w3": jax.random.normal(k[2], (n, dim, width)) * 0.2,
            "w2": jax.random.normal(k[3], (n, width, dim)) * 0.2,
            "shared_w1": jax.random.normal(k[4], (dim, shared)) * 0.2,
            "shared_w3": jax.random.normal(k[5], (dim, shared)) * 0.2,
            "shared_w2": jax.random.normal(k[6], (shared, dim)) * 0.2}


def _expert_share(full, held):
    ids = np.asarray(held)
    return {"params": dict(full, w1=full["w1"][ids], w3=full["w3"][ids],
                           w2=full["w2"][ids])}


def test_the_expert_shares_sum_to_the_uncut_routed_layer():
    """Eight holders of 2 of the 16 experts (half a group each, as the
    cell's chip holds half of group 0), every one adding the shared
    experts: their sum, with the shared experts counted once, is the plain
    reference's layer with all 16 held. The counts add up too: every
    holder reports all N x 3 pairs routed, the held loads sum to them, and
    a group's two holders are hit by the same tokens."""
    full = _moe_weights(0)
    x = jax.random.normal(jax.random.PRNGKey(9), (40, 32))
    ref, vt = _reference()
    want = ref._experts({"m/" + k: v for k, v in full.items()}, "m/", x,
                        dict(MOE_SIZES, experts_held=list(range(16))),
                        vt._einsum(""))
    shared = (jax.nn.silu(x @ full["shared_w1"]) * (x @ full["shared_w3"])) \
        @ full["shared_w2"]
    total, local, hits = 0.0, 0, []
    for first in range(0, 16, 2):
        held = (first, first + 1)
        layer = TopKMoeMlp(TopKMoeConfig(experts_held=held, **MOE),
                           dtype=jnp.float32)
        y, load, pairs, hit = layer.apply(
            _expert_share(full, held), x, method=TopKMoeMlp.routed)
        assert int(pairs) == 40 * 3
        total, local = total + y, local + int(load.sum())
        hits.append(int(hit))
    assert local == 40 * 3
    assert hits[0::2] == hits[1::2]         # the two halves of a group
    assert sum(hits[0::2]) == 40 * 2        # two kept groups a token
    np.testing.assert_allclose(np.asarray(total - 7 * shared),
                               np.asarray(want), rtol=2e-4, atol=3e-5)
    # the scaling and the group limit are in it
    for other in (dict(routed_scaling_factor=1), dict(n_group=1)):
        off = ref._experts({"m/" + k: v for k, v in full.items()}, "m/", x,
                           dict(MOE_SIZES, experts_held=list(range(16)),
                                **other), vt._einsum(""))
        assert float(jnp.abs(want - off).max()) > 0.01, other


# -- (c) the router against the reference ------------------------------------

def _dense_weights(sel, w, n):
    out = np.zeros((sel.shape[0], n), np.float32)
    np.put_along_axis(out, np.asarray(sel), np.asarray(w), axis=-1)
    return out


def test_the_group_limit_changes_the_choice_and_is_the_references():
    """Scores built so that the plain top-3 of 16 is not the group-limited
    one: three groups hold one strong expert each, and the third strongest
    expert's group is cut."""
    cfg = TopKMoeConfig(experts_held=(0, 1), **MOE)
    ref, _ = _reference()
    s = np.full((3, 16), 0.01, np.float32)
    # token 0: strong experts 1 (g0), 6 (g1), 9 (g2); g2 is the weakest
    s[0, [1, 6, 9]] = 0.30, 0.25, 0.20
    s[0, 2] = 0.10                       # second of g0: takes 9's place
    # token 1: everything in one group beats the rest
    s[1, 12:16] = 0.2, 0.3, 0.1, 0.15
    # token 2: the limit changes nothing (the top-3 lie in two groups)
    s[2, [4, 5, 10]] = 0.3, 0.2, 0.25
    s = s / s.sum(-1, keepdims=True)
    sel, w = topk_route(jnp.asarray(s), None, cfg)
    assert sorted(np.asarray(sel[0]).tolist()) == [1, 2, 6]
    assert sorted(np.asarray(sel[1]).tolist()) == [12, 13, 15]
    assert sorted(np.asarray(sel[2]).tolist()) == [4, 5, 10]
    plain = np.argsort(-s, axis=-1)[:, :3]
    assert sorted(plain[0].tolist()) == [1, 6, 9]
    np.testing.assert_array_equal(
        np.asarray(kept_groups(jnp.asarray(s), cfg)),
        [[True, True, False, False], [True, False, False, True],
         [False, True, True, False]])
    # weights: the chosen scores x 16, not renormalised
    np.testing.assert_allclose(np.sort(np.asarray(w[0])),
                               np.sort(s[0, [1, 2, 6]]) * 16, rtol=1e-6)
    want = np.asarray(ref.route(jnp.asarray(s), MOE_SIZES))
    np.testing.assert_allclose(_dense_weights(sel, w, 16), want, rtol=1e-6)
    # and on drawn scores, where most tokens' choice is changed by it
    x = jax.nn.softmax(jax.random.normal(jax.random.PRNGKey(1), (500, 16)))
    sel, w = topk_route(x, None, cfg)
    np.testing.assert_allclose(_dense_weights(sel, w, 16),
                               np.asarray(ref.route(x, MOE_SIZES)),
                               rtol=1e-6)
    free = np.sort(np.argsort(-np.asarray(x), axis=-1)[:, :3], axis=-1)
    changed = np.any(np.sort(np.asarray(sel), axis=-1) != free, axis=-1)
    assert 0.2 < changed.mean() < 0.9


def test_ties_go_to_the_lower_group_and_the_lower_expert_as_in_the_reference():
    cfg = TopKMoeConfig(experts_held=(0, 1), **MOE)
    ref, _ = _reference()
    s = np.full((2, 16), 1 / 16, np.float32)        # every score equal
    s[1, :] = 0.02
    s[1, [3, 7, 11, 15]] = 0.17                     # four groups tie
    sel, w = topk_route(jnp.asarray(s), None, cfg)
    assert np.asarray(sel[0]).tolist() == [0, 1, 2]
    assert sorted(np.asarray(sel[1]).tolist()) == [0, 3, 7]
    np.testing.assert_allclose(
        _dense_weights(sel, w, 16),
        np.asarray(ref.route(jnp.asarray(s), MOE_SIZES)), rtol=1e-6)


# -- (e) the counters add up -------------------------------------------------

def test_the_layers_counts_are_a_numpy_count():
    full = _moe_weights(5)
    x = jax.random.normal(jax.random.PRNGKey(2), (300, 32))
    cfg = TopKMoeConfig(experts_held=(4, 5), **MOE)     # half of group 1
    assert cfg.groups_held == (1,)
    layer = TopKMoeMlp(cfg, dtype=jnp.float32)
    _, load, pairs, hits = layer.apply(_expert_share(full, (4, 5)), x,
                                       method=TopKMoeMlp.routed)
    s = np.asarray(jax.nn.softmax(x @ full["gate"], axis=-1), np.float64)
    best = s.reshape(300, 4, 4).max(-1)
    kept = np.argsort(-best, axis=-1, kind="stable")[:, :2]
    masked = np.where(np.repeat(
        (kept[:, :, None] == np.arange(4)).any(1), 4, axis=-1), s, 0)
    chosen = np.argsort(-masked, axis=-1, kind="stable")[:, :3]
    assert int(pairs) == 300 * 3
    assert int(hits) == int((kept == 1).any(-1).sum())
    assert load.tolist() == [int((chosen == e).sum()) for e in (4, 5)]
    # a holder with experts of two groups is hit by either
    both = TopKMoeConfig(experts_held=(3, 4), **MOE)
    assert both.groups_held == (0, 1)
    _, _, _, hits2 = TopKMoeMlp(both, dtype=jnp.float32).apply(
        _expert_share(full, (3, 4)), x, method=TopKMoeMlp.routed)
    assert int(hits2) == int(((kept == 0) | (kept == 1)).any(-1).sum())
    # and a router without groups counts every token
    free = TopKMoeConfig(**dict(MOE, n_group=1, topk_group=1),
                         experts_held=(4, 5))
    _, _, pairs3, hits3 = TopKMoeMlp(free, dtype=jnp.float32).apply(
        _expert_share(full, (4, 5)), x, method=TopKMoeMlp.routed)
    assert (int(pairs3), int(hits3)) == (900, 300)


# -- (a) the whole stack through the pool against one full forward ----------

def test_rounds_through_the_pool_match_one_full_forward():
    """Two streams, started a round apart, six rounds through the ``stream``
    step and the state pool (prefill with the cached rows up-projected,
    decode in the latent space one position an iteration, a reset when the
    context is full and the cut first context): every round's logits are
    the reference's, which sees the whole context at once, with the same
    two held heads and the same two held experts; and the round's counts
    are its positions'."""
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
        assert set(pool.state) == {"latent", "tokens"}
        assert int(out["decode_iters"]) == c.decode_steps
        # both rows of the bucket go through the two routed layers, the
        # padded one too
        tokens = 2 * c.round_positions * 2
        assert int(out["moe_pairs_total"]) == tokens * c.head.top_k
        assert 0 <= int(out["moe_group_hits"]) <= tokens
        assert int(np.asarray(out["moe_load"]).sum()) <= int(
            out["moe_group_hits"]) * 2
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
            assert logits.shape == (c.decode_steps, c.head.vocab_size)
            lp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
            got = np.log(np.asarray(out["top_probs"][i]))
            want = np.take_along_axis(lp, np.asarray(out["top_ids"][i]), -1)
            np.testing.assert_allclose(got, want, atol=0.03)
            history[d].append(rounds)
    assert history["cam_a"][:first["cam_a"] + 1] == list(
        range(1, first["cam_a"] + 1)) + [1]
    assert resets["cam_a"] >= 2 and resets["cam_b"] >= 1
    assert max(history["cam_a"]) >= 3      # >= 3 rounds carried in a context


def test_a_first_round_without_a_pool_is_the_steps_first_token():
    _, _, spec, module, _, variables = _variables(8)
    clips = jax.random.normal(jax.random.PRNGKey(2), (2, 4, 32, 32, 3))
    logits = module.apply(variables, clips)
    c = module.cfg
    out = module.serve_round(
        variables, clips, module.empty_state(2)[0], jnp.arange(2),
        jnp.full((2,), len(c.instruction_ids), jnp.int32),
        jnp.ones((2,), bool))
    assert out["tokens"][:, 0].tolist() == jnp.argmax(logits, -1).tolist()
    np.testing.assert_allclose(
        np.asarray(out["top_probs"][:, 0, 0]),
        np.asarray(jax.nn.softmax(logits, -1).max(-1)), rtol=2e-3)


# -- (d) a one-kind head in the state pool -----------------------------------

def test_the_pool_holds_a_head_whose_only_state_is_its_latent_cache():
    """Alloc, growth, reset and the freeing of a slot for a head that
    declares one kind and carries nothing a batch row."""
    module = registry.get(TINY).build()
    state, axes = module.empty_state(4)
    assert axes == {"latent": 1} and set(state) == {"latent"}
    c = module.cfg
    assert state["latent"].shape == (c.head.num_layers, 4,
                                     c.head.max_context, 128)
    pool = StreamStatePool(module, grow=4)
    ids = [f"cam{i}" for i in range(4)]
    plan = pool.plan(ids, 4)
    assert plan["reset"].all() and set(pool.state) == {"latent", "tokens"}
    assert pool.capacity == 4
    bytes0 = pool.nbytes()
    assert bytes0 == sum(a.nbytes for a in jax.tree_util.tree_leaves(
        pool.state))
    # what the streams hold survives a growth, along the slot axis
    pool.state = jax.tree_util.tree_map(
        lambda a: a + jnp.arange(a.size, dtype=jnp.float32).reshape(
            a.shape).astype(a.dtype) % 7, pool.state)
    held = jax.tree_util.tree_map(np.asarray, pool.state)
    pool.plan(ids + ["more"], 8)
    assert pool.capacity == 8 and pool.nbytes() == 2 * bytes0
    np.testing.assert_array_equal(
        np.asarray(pool.state["latent"])[:, :4], held["latent"])
    np.testing.assert_array_equal(
        np.asarray(pool.state["tokens"])[:4], held["tokens"])
    # a reset lays the instruction's rows into the slot and nothing else
    # is carried: ``seed_round`` gives no rows
    variables = {"instruction": {"latent": jnp.ones(
        (c.head.num_layers, 1, len(c.instruction_ids), 128))}}
    seeded, rows = module.seed_round(
        variables, {"latent": pool.state["latent"]}, jnp.asarray([2, 8]),
        jnp.asarray([True, True]))
    assert rows == ()
    n_i = len(c.instruction_ids)
    assert np.all(np.asarray(seeded)[:, 2, :n_i] == 1)
    np.testing.assert_array_equal(np.asarray(seeded)[:, 2, n_i:],
                                  np.asarray(pool.state["latent"])[:, 2, n_i:])
    np.testing.assert_array_equal(                  # slot 8 is past the pool
        np.delete(np.asarray(seeded), 2, axis=1),
        np.delete(np.asarray(pool.state["latent"]), 2, axis=1))
    # a stream that leaves frees its slot; the newcomer takes it, reset
    slot = pool._slots["cam1"]
    pool.pop("cam1")
    plan = pool.plan(["cam0", "new"], 8)
    assert plan["idx"][1] == slot and plan["reset"][1]
    assert pool.nbytes() == 2 * bytes0
    pool.lost()
    assert pool.nbytes() == 0 and len(pool) == 0


def test_the_published_sizes_are_the_chips_share():
    c = deepseek_v2.DeepseekV2Config()
    assert (len(c.heads_held), c.num_heads) == (32, 128)
    assert (len(c.experts_held), c.num_experts) == (10, 160)
    assert c.moe.groups_held == (0,) and c.moe.shared_mlp_dim == 3072
    assert (c.mla.latent_dim, c.mla.row_dim) == (576, 640)
    assert abs(mla.softmax_scale(c.mla) - 192 ** -0.5 * 1.5904) < 1e-4
    state = jax.eval_shape(lambda: deepseek_v2.VideoMAEDeepseekV2(
        deepseek_v2.StreamHeadConfig()).empty_state(64)[0])
    assert state["latent"].shape == (5, 64, 4096, 640)
    assert abs(int(np.prod(state["latent"].shape)) * 2 / 1e9 - 1.68) < 0.01
    assert max(deepseek_v2.INSTRUCTION_IDS) < c.vocab_size
    assert len(set(deepseek_v2.INSTRUCTION_IDS)) == 32


# -- through the engine, on the bus ------------------------------------------

def _counter(name):
    fam = {f.name: f for f in obs_registry.families()}
    return fam[name].labels().value


def test_engine_serves_the_head_and_its_counters_reach_trace_and_metrics(
        monkeypatch):
    monkeypatch.setattr(InferenceEngine, "_TRACKER_GC_GRACE_S", 0.2)
    bus = MemoryFrameBus()
    cams = [f"clip{i}" for i in range(3)]
    for cam in cams:
        bus.create_stream(cam, H * W * 3)
    cfg = EngineConfig(model=TINY, batch_buckets=(4,), tick_ms=5,
                       stage_trace=True, ladder=False)
    eng = InferenceEngine(bus, cfg,
                          annotations=AnnotationQueue(handler=lambda b: True))
    eng.warmup()
    got = []

    def subscriber():
        for res in eng.subscribe():
            got.append(res)

    threading.Thread(target=subscriber, daemon=True).start()
    routed0 = _counter("vep_moe_pairs_routed_total")
    hits0 = _counter("vep_moe_group_hits_total")
    local0 = _counter("vep_moe_pairs_total")
    eng.start()
    rng = np.random.default_rng(0)
    c = deepseek_v2.tiny_stream_head_config()
    clip_len = registry.get(TINY).clip_len

    def publish(ids, k):
        for cam in ids:
            bus.publish(cam, rng.integers(0, 255, (H, W, 3), dtype=np.uint8),
                        FrameMeta(width=W, height=H, channels=3, packet=k,
                                  timestamp_ms=int(time.time() * 1000),
                                  is_keyframe=True))

    try:
        k, deadline = 0, time.time() + 90
        while len(got) < 4 * len(cams) and time.time() < deadline:
            k += 1
            publish(cams, k)
            time.sleep(0.05)
        assert len(got) >= 4 * len(cams), len(got)
        for r in got:
            assert r.model == TINY
            assert len(r.head.steps) == c.decode_steps
            assert r.head.positions == 4 + (r.head.rounds_since_reset
                                            * c.round_positions)
            assert r.head.accepted == 0 and not r.head.first_draft.token_ids
        recs = [r for r in eng.stage_records if "moe_pairs_total" in r]
        assert recs
        for rec in recs:
            # the bucket's four rows through the two routed layers
            tokens = 4 * c.round_positions * 2
            assert rec["moe_pairs_total"] == tokens * c.head.top_k
            assert 0 <= rec["moe_group_hits"] <= tokens
            assert rec["moe_pairs_local"] <= rec["moe_pairs_total"]
            assert rec["head_decode_iters"] == c.decode_steps
            assert "mtp_drafted" not in rec
        batches = {tuple(r["batch"]): r for r in recs}.values()
        assert _counter("vep_moe_pairs_routed_total") - routed0 >= sum(
            r["moe_pairs_total"] for r in batches)
        assert _counter("vep_moe_group_hits_total") - hits0 >= sum(
            r["moe_group_hits"] for r in batches)
        assert _counter("vep_moe_pairs_total") - local0 >= sum(
            r["moe_pairs_local"] for r in batches)
        # GC: a stream that leaves the bus frees its slot of the one-kind
        # pool; the others go on
        pool = eng._head_pools[TINY]
        assert len(pool) == 3 and set(pool.state) == {"latent", "tokens"}
        held, n0 = pool.nbytes(), len(got)
        bus.drop_stream("clip2")
        deadline = time.time() + 30
        while len(pool) == 3 and time.time() < deadline:
            k += 1
            publish(cams[:2], k)
            time.sleep(0.1)
        assert len(pool) == 2 and "clip2" not in list(pool)
        assert len(got) > n0 and pool.nbytes() == held
    finally:
        eng.stop()
        bus.close()
