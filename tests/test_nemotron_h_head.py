"""The fifth streaming head (``models/nemotron_h.py``): the latent expert
layer (``models/transformer.py`` ``TopKMoeMlp`` with ``latent_dim`` and
squared ReLU) against a dense loop, the shares of its experts, of the
mixer's groups (``models/mamba2.py``) and of the attention's key-value
heads (``models/gqa.py``, unrotated) against the uncut layers, the pool
whose state is indexed by a layer's place among its kind, and prefill then
decode through the ``stream`` step kind and the engine, against the
benchmark's plain reference (``benchmark/reference/nemotron3_super_stream
.py``, loaded by path) on seeded weights at tiny sizes. CPU, float32:
results and counts only."""

import dataclasses
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
    gqa, mamba2, nemotron_h, registry, stream_head)
from video_edge_ai_proxy_tpu.models.transformer import (  # noqa: E402
    TopKMoeConfig, TopKMoeMlp, pair_rungs)
from video_edge_ai_proxy_tpu.uplink.queue import AnnotationQueue  # noqa: E402
from video_edge_ai_proxy_tpu.utils.config import EngineConfig  # noqa: E402

TINY = "tiny_videomae_nemotron3_super"
H, W = 48, 64


def _tiny():
    with open(os.path.join(BENCH, "tests", "data",
                           "tiny_nemotron3.json")) as f:
        return loader.models(json.load(f))[0]


def _variables(seed, dtype=None):
    m = _tiny()
    fam = loader.family(m["family"])
    spec = registry.get(TINY)
    module = spec.build()
    if dtype is not None:
        module = module.clone(dtype=dtype)
    assert fam.check_sizes(module, m["sizes"]) == {}
    flat = weights.generate(seed, m["family"], m["sizes"])
    return m, fam, spec, module, flat, spec.prepare(
        module, weights.as_variables(flat, fam.template(spec, module)))


def _reference():
    return (loader.reference("nemotron3_super_stream"),
            loader.reference("vision_transformer"))


def _layer(flat, i):
    """Layer ``i``'s mixer weights of a flat draw, by their names there."""
    pre = f"head/layer{i}/mixer/"
    return {k[len(pre):]: np.asarray(v) for k, v in flat.items()
            if k.startswith(pre)}


def _cfg(sizes, **over):
    return dict(loader.frozen(dict(sizes, **over)))


# -- (a) the latent expert layer ----------------------------------------------

MOE = dict(dim=32, mlp_dim=24, num_experts=16, top_k=4,
           routed_scaling_factor=5.0, shared_mlp_dim=40)


def _moe_weights(seed, latent, gated, held=16):
    rng = np.random.default_rng(seed)
    def f(*s):
        scale = np.sqrt(s[-2] if len(s) > 1 else 1)
        return (rng.normal(size=s) / scale).astype(np.float32)
    de = latent or 32
    out = {"gate": f(32, 16), "expert_bias": (0.2 * rng.normal(size=16)
                                              ).astype(np.float32),
           "w1": f(held, de, 24), "w2": f(held, 24, de),
           "shared_w1": f(32, 40), "shared_w2": f(40, 32)}
    if gated:
        out.update(w3=f(held, de, 24), shared_w3=f(32, 40))
    if latent:
        out.update(fc1_latent_proj=f(32, latent),
                   fc2_latent_proj=f(latent, 32))
    return out


def _dense_loop(p, x, latent, gated):
    """Every expert on every token in float64, weighted by the router's
    weight where the token chose it and 0 where not; W_down before and
    W_up after where there is a latent; the shared expert beside."""
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    x = np.asarray(x, np.float64)
    s = 1.0 / (1.0 + np.exp(-(x @ p["gate"])))
    sel = np.argsort(-(s + p["expert_bias"]), axis=-1, kind="stable")[:, :4]
    w = np.take_along_axis(s, sel, -1)
    w = 5.0 * w / (w.sum(-1, keepdims=True) + 1e-20)
    u = x @ p["fc1_latent_proj"] if latent else x
    relu2 = lambda a: np.maximum(a, 0.0) ** 2  # noqa: E731
    silu = lambda a: a / (1.0 + np.exp(-a))  # noqa: E731
    y = np.zeros_like(u)
    for e in range(16):
        a = (silu(u @ p["w1"][e]) * (u @ p["w3"][e]) if gated
             else relu2(u @ p["w1"][e]))
        y += (w * (sel == e)).sum(-1, keepdims=True) * (a @ p["w2"][e])
    if latent:
        y = y @ p["fc2_latent_proj"]
    a = (silu(x @ p["shared_w1"]) * (x @ p["shared_w3"]) if gated
         else relu2(x @ p["shared_w1"]))
    return y + a @ p["shared_w2"]


@pytest.mark.parametrize("activation,latent", [
    ("relu2", 16), ("relu2", 0), ("swiglu", 16)])
def test_the_expert_layer_is_a_dense_loop(activation, latent):
    """Squared ReLU or SwiGLU, in a latent narrower than the width or at
    it: the dropless layer with every expert held is the dense loop over
    them, to float32 rounding; the counts are every token's top-4."""
    gated = activation == "swiglu"
    p = _moe_weights(1, latent, gated)
    x = jax.random.normal(jax.random.PRNGKey(3), (37, 32))
    layer = TopKMoeMlp(TopKMoeConfig(activation=activation,
                                     latent_dim=latent, **MOE),
                       dtype=jnp.float32)
    y, counts = layer.apply({"params": p}, x, method=TopKMoeMlp.routed)
    np.testing.assert_allclose(np.asarray(y), _dense_loop(p, x, latent, gated),
                               rtol=2e-4, atol=2e-4)
    assert int(counts["pairs"]) == int(counts["held"].sum()) == 37 * 4
    # the other activation is another layer
    other = _dense_loop(_moe_weights(1, latent, True), x, latent, not gated)
    assert float(np.abs(np.asarray(y) - other).max()) > 0.1


def test_the_latent_layer_moves_rows_at_the_latent_width():
    """The rungs and the way back's kernel are chosen by the width the
    experts work at: a latent of 1,024 under a width of 4,096 at the cell's
    chunk (4 streams: 3,136 tokens x top-22 of 512, 16 held)."""
    cfg = nemotron_h.NemotronHConfig().moe
    assert (cfg.expert_dim, cfg.activation) == (1024, "relu2")
    assert nemotron_h.StreamHeadConfig().prefill_chunk == 4
    rungs = pair_rungs(cfg, 3136)
    assert rungs == (68992, 34560, 17280, 8704, 4352)
    assert rungs[-1] >= 2 * 68992 * 16 / 512
    assert pair_rungs(cfg, 64) == (64 * 22,)        # decode: one rung
    # a latent no kernel takes (the twin's) has the one rung of all pairs
    tiny = nemotron_h.tiny_stream_head_config().head.moe
    assert pair_rungs(tiny, 100) == (400,)


def test_the_latent_expert_shares_sum_to_the_uncut_layer():
    """Four holders of 4 of the 16 experts, each with the whole router,
    W_down, W_up and shared expert: their outputs, with the shared expert
    counted once, sum to the plain reference's layer with all 16 held; the
    held loads sum to every routed pair."""
    m = _tiny()
    sizes = m["sizes"]
    p = _moe_weights(2, 16, False)
    x = jax.random.normal(jax.random.PRNGKey(5), (40, 32))
    ref, vt = _reference()
    cfg = _cfg(sizes, experts_held=list(range(16)))
    want = ref.experts({"m/" + k: v for k, v in p.items()}, "m/", x, cfg,
                       vt._einsum(""))
    shared = np.asarray(
        jnp.square(jax.nn.relu(x @ p["shared_w1"])) @ p["shared_w2"])
    total, local = 0.0, 0
    for first in range(0, 16, 4):
        held = tuple(range(first, first + 4))
        ids = np.asarray(held)
        share = dict(p, w1=p["w1"][ids], w2=p["w2"][ids])
        layer = TopKMoeMlp(TopKMoeConfig(
            activation="relu2", latent_dim=16, experts_held=held, **MOE),
            dtype=jnp.float32)
        y, counts = layer.apply({"params": share}, x,
                                method=TopKMoeMlp.routed)
        total, local = total + y, local + int(counts["held"].sum())
    assert local == 40 * 4
    np.testing.assert_allclose(np.asarray(total) - 3 * shared,
                               np.asarray(want), rtol=2e-4, atol=2e-4)
    # one holder alone is not the layer
    assert float(np.abs(np.asarray(y) - np.asarray(want)).max()) > 0.05


# -- (b) the mixer's and the attention's shares -------------------------------

def _mixer_share(p, group, sizes):
    """The slices of the uncut tiny mixer that hold group ``group``: its
    heads' channels of z, x and dt, its B and C, its convolution channels,
    its norm and its rows of W_out."""
    nh, g = sizes["mamba_num_heads"], sizes["n_groups"]
    hp, n = sizes["mamba_head_dim"], sizes["ssm_state_size"]
    e = nh * hp
    per = nh // g
    mine = np.arange(group * per * hp, (group + 1) * per * hp)
    bc = np.arange(group * n, (group + 1) * n)
    heads = np.arange(group * per, (group + 1) * per)
    cols = np.concatenate([mine, e + mine, 2 * e + bc, 2 * e + g * n + bc,
                           2 * e + 2 * g * n + heads])
    conv = np.concatenate([mine, e + bc, e + g * n + bc])
    return {"in_proj": p["in_proj"][:, cols],
            "conv_kernel": p["conv_kernel"][:, conv],
            "conv_bias": p["conv_bias"][conv], "dt_bias": p["dt_bias"][heads],
            "A_log": p["A_log"][heads], "D": p["D"][heads],
            "norm_scale": p["norm_scale"][mine],
            "out_proj": p["out_proj"][mine]}


def test_the_mixer_group_shares_sum_to_the_uncut_mixer():
    """Two holders of one of the two groups each (two heads, their B and
    C, their gated norm whole): the sum of their partial outputs is the
    plain reference's uncut mixer, token by token from a zero state, and
    one holder alone is not."""
    m = _tiny()
    sizes = m["sizes"]
    flat = weights.generate(11, m["family"], sizes)
    p = _layer(flat, 0)
    ref, vt = _reference()
    t = 21
    n = jax.random.normal(jax.random.PRNGKey(4), (t, sizes["hidden_size"]))
    want = np.asarray(ref.mixer({"m/" + k: v for k, v in p.items()}, "m/",
                                n, _cfg(sizes), vt._einsum("")))
    half = dataclasses.replace(
        nemotron_h.tiny_stream_head_config().head, mamba_heads=2,
        mamba_groups=1).mixer
    mixer = mamba2.Mamba2Mixer(half, jnp.float32)
    got = [np.asarray(mixer.apply(
        {"params": _mixer_share(p, g, sizes)}, n[None],
        jnp.zeros((1, 3, half.conv_dim)),
        jnp.zeros((1, 2, 8, 8)))[0][0]) for g in (0, 1)]
    np.testing.assert_allclose(got[0] + got[1], want, rtol=2e-4, atol=2e-4)
    assert float(np.abs(got[0] - want).max()) > 0.05


@pytest.mark.parametrize("kv", [0, 1])
def test_the_attention_kv_head_shares_sum_to_the_uncut_attention(kv):
    """Two holders of one key-value head each, with the two query heads
    that read it: the sum of their partial outputs (W_o's rows) is the
    plain reference's uncut attention, with no rotation; one holder alone
    is not."""
    m = _tiny()
    sizes = m["sizes"]
    flat = weights.generate(12, m["family"], sizes)
    layer = nemotron_h.tiny_stream_head_config().head.kinds.index(("*", 0))
    p = _layer(flat, layer)
    ref, vt = _reference()
    t = 13
    n = jax.random.normal(jax.random.PRNGKey(6), (t, sizes["hidden_size"]))
    want = np.asarray(ref.attention({"m/" + k: v for k, v in p.items()},
                                    "m/", n, _cfg(sizes), vt._einsum("")))
    cfg = dataclasses.replace(nemotron_h.tiny_stream_head_config().head,
                              num_heads=2, num_kv_heads=1).attn
    attn = gqa.CachedAttention(cfg, 0, jnp.float32)

    def share(h):
        q = np.arange(2 * h * 8, 2 * (h + 1) * 8)
        k = np.arange(h * 8, (h + 1) * 8)
        params = {"q_proj": p["q_proj"][:, q], "k_proj": p["k_proj"][:, k],
                  "v_proj": p["v_proj"][:, k], "out_proj": p["out_proj"][q]}
        pool = gqa.empty_pool(cfg, 1, 1, 0, jnp.float32)
        rbuf = tuple(r[0] for r in gqa.round_buffer(cfg, 1, 1, t,
                                                   jnp.float32))
        y, _ = attn.apply({"params": params}, n[None], pool, rbuf,
                          jnp.zeros((1,), jnp.int32),
                          jnp.zeros((1,), jnp.int32), 0)
        return np.asarray(y[0])

    got = [share(h) for h in (0, 1)]
    np.testing.assert_allclose(got[0] + got[1], want, rtol=2e-4, atol=2e-4)
    assert float(np.abs(got[kv] - want).max()) > 0.05


def test_the_attention_applies_no_position():
    """The head's attention, uncut, is the plain reference's causal
    attention with no position embedding; the same weights with rotary
    positions are not."""
    m = _tiny()
    sizes = m["sizes"]
    flat = weights.generate(13, m["family"], sizes)
    p = _layer(flat, 3)
    ref, vt = _reference()
    t = 11
    n = jax.random.normal(jax.random.PRNGKey(8), (t, sizes["hidden_size"]))
    want = np.asarray(ref.attention({"m/" + k: v for k, v in p.items()},
                                    "m/", n, _cfg(sizes), vt._einsum("")))
    base = nemotron_h.tiny_stream_head_config().head.attn
    assert not base.rotary

    def served(cfg):
        attn = gqa.CachedAttention(cfg, 0, jnp.float32)
        pool = gqa.empty_pool(cfg, 1, 1, 0, jnp.float32)
        rbuf = tuple(r[0] for r in gqa.round_buffer(cfg, 1, 1, t,
                                                   jnp.float32))
        return np.asarray(attn.apply(
            {"params": p}, n[None], pool, rbuf, jnp.zeros((1,), jnp.int32),
            jnp.zeros((1,), jnp.int32), 0)[0][0])

    np.testing.assert_allclose(served(base), want, rtol=2e-4, atol=2e-4)
    turned = dataclasses.replace(base, rotary=True, rope_theta=10000.0)
    assert float(np.abs(served(turned) - want).max()) > 0.01


# -- (c) the whole stack through the pool against one full forward ------------

def _rounds(module, spec, variables, flat, m, fam, rounds):
    """Two streams, the second started a round later, ``rounds`` rounds
    through the ``stream`` step and the pool; yields (round, ids, plan,
    out, the reference's logits a stream)."""
    c = module.cfg
    step = jax.jit(runner.build_serving_step(module, spec),
                   donate_argnums=(2,))
    pool = StreamStatePool(module, grow=2)
    ref = loader.reference(m["reference"]).jitted(
        m["family"], loader.frozen(m["sizes"]))
    rng = np.random.default_rng(0)
    n = c.video.num_frames
    frames = {d: rng.integers(0, 255, (n + rounds, H, W, 3), dtype=np.uint8)
              for d in ("cam_a", "cam_b")}
    for r in range(rounds):
        ids = ["cam_a"] + (["cam_b"] if r >= 1 else [])
        k = {d: r - (d == "cam_b") for d in ids}
        batch = np.zeros((2, n, H, W, 3), np.uint8)
        for i, d in enumerate(ids):
            batch[i] = frames[d][k[d]:k[d] + n]
        plan = pool.plan(ids, 2)
        out = step(variables, batch, pool.state, plan["idx"], plan["pos0"],
                   plan["reset"], plan["rounds"])
        pool.state = out.pop("state")
        buf = np.zeros((fam.REFERENCE_BLOCK, n + c.max_rounds - 1, H, W, 3),
                       np.uint8)
        wins = []
        for i, d in enumerate(ids):
            got = int(out["rounds"][i])
            assert (got, int(out["positions"][i])) == fam.expected_state(
                d, k[d] + 1, m["sizes"])
            hist = [int(t) for t in np.asarray(out["history"][i]) if t >= 0]
            assert hist[-c.decode_steps:] == out["tokens"][i].tolist()
            start = k[d] - (got - 1)
            window = frames[d][start:start + n + got - 1]
            buf[i, :len(window)] = window
            wins.append(fam.Window(range(len(window)), got, hist))
        logits = np.asarray(ref(flat, *fam.reference_args(
            buf, wins + wins[:1] * (fam.REFERENCE_BLOCK - len(wins)),
            m["sizes"])))
        yield r, ids, plan, out, logits


def _gaps(out, logits, i):
    lp = np.asarray(jax.nn.log_softmax(logits[i], axis=-1))
    got = np.log(np.asarray(out["top_probs"][i], np.float64))
    want = np.take_along_axis(lp, np.asarray(out["top_ids"][i]), -1)
    return np.abs(got - want)


def test_rounds_through_the_pool_match_one_full_forward():
    """Two streams, started a round apart, six rounds through the
    ``stream`` step and the state pool (a reset when the context is full
    and the cut first context in between): every round's logits are the
    reference's, which runs the whole context at once with its
    state-space layer token by token, its experts in a dense loop and no
    rotation. Tolerance 0.01 on log-probabilities: the float32 twin reads
    ~2e-3 (the preprocess's resize and the encoder's order of sums differ
    from the reference's, as in the sibling heads' tests); the same twin
    served in bfloat16, as the configuration does not state, reads above
    it (the next test)."""
    m, fam, spec, module, flat, variables = _variables(7)
    c = module.cfg
    first = first_context_rounds("cam_a", c.max_rounds)
    history, resets, worst = [], {"cam_a": 0, "cam_b": 0}, 0.0
    for r, ids, plan, out, logits in _rounds(module, spec, variables, flat,
                                             m, fam, 6):
        assert int(out["moe_pairs_total"]) > 0
        for i, d in enumerate(ids):
            resets[d] += int(plan["reset"][i])
            gaps = _gaps(out, logits, i)
            worst = max(worst, float(gaps.max()))
            assert gaps.max() < 0.01, (r, d, gaps.max())
        history.append(int(out["rounds"][0]))
    assert history[:first + 1] == list(range(1, first + 1)) + [1]
    assert resets["cam_a"] >= 2 and resets["cam_b"] >= 1
    assert max(history) >= 3        # >= 3 rounds carried in a context
    assert worst > 0.0


def test_the_tolerance_fails_bfloat16_where_float32_is_stated():
    """The twin served in bfloat16 (its matrices cast at load) against the
    float32 reference: the first two rounds read gaps above the 0.01 the
    float32 twin is held to."""
    m, fam, spec, module, flat, variables = _variables(
        7, dtype=jnp.bfloat16)
    worst = 0.0
    for r, ids, plan, out, logits in _rounds(module, spec, variables, flat,
                                             m, fam, 2):
        for i in range(len(ids)):
            worst = max(worst, float(_gaps(out, logits, i).max()))
    assert worst > 0.01, worst


def test_a_first_round_without_a_pool_is_the_steps_first_token():
    _, _, spec, module, _, variables = _variables(8)
    clips = jax.random.normal(jax.random.PRNGKey(2), (2, 4, 40, 40, 3))
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


@pytest.mark.parametrize("kind", ["ssm", "conv", "kv"])
def test_each_kind_of_state_is_carried(kind):
    """A round that continues the pool's state and one that starts from
    the same state with one kind zeroed serve other logits: the mixers'
    SSM and conv state and the attention's keys and values each reach the
    next round."""
    _, _, spec, module, _, variables = _variables(9)
    c = module.cfg
    clips = jax.random.normal(jax.random.PRNGKey(3), (2, 4, 40, 40, 3))
    state, _ = module.empty_state(2)
    pos0 = jnp.full((2,), len(c.instruction_ids), jnp.int32)
    one = module.serve_round(variables, clips, state, jnp.arange(2), pos0,
                             jnp.ones((2,), bool))
    if kind == "ssm":
        ssm = np.asarray(one["state"]["ssm"])
        assert ssm.dtype == np.float32
        ins = np.asarray(variables["instruction"]["ssm"])
        assert np.abs(ssm - ins).max() > 1e-3      # written, not kept
    pos1 = pos0 + c.round_positions
    two = module.serve_round(variables, clips, one["state"], jnp.arange(2),
                             pos1, jnp.zeros((2,), bool))
    lost = module.serve_round(variables, clips,
                              dict(one["state"], **{kind: state[kind]}),
                              jnp.arange(2), pos1, jnp.zeros((2,), bool))
    assert float(jnp.abs(two["top_probs"] - lost["top_probs"]).max()) > 1e-4


# -- the pool, by kind ------------------------------------------------------

def test_the_pool_indexes_state_by_a_layers_place_among_its_kind():
    """Keys and values for the attention layers only, conv and SSM state
    for the mixers only, the expert layers none; the cell's pool of 64
    streams: 134 MB of keys and values, 336 MB of float32 SSM state."""
    tiny = nemotron_h.tiny_stream_head_config().head
    assert tiny.kinds == (("M", 0), ("E", 0), ("M", 1), ("*", 0), ("E", 1))
    module = registry.get(TINY).build()
    state, axes = module.empty_state(4)
    assert axes == {"conv": 0, "ssm": 0, "kv": 1}
    assert state["conv"].shape == (4, 2, 3, 64)
    assert state["ssm"].shape == (4, 2, 4, 8, 8)
    assert state["kv"][0].shape == (1, 4, 2, 8, 224)
    cfg = nemotron_h.NemotronHConfig()
    assert cfg.kinds[7] == ("*", 0) and cfg.kinds[10] == ("E", 4)
    assert [cfg.count(k) for k in "ME*"] == [5, 5, 1]
    big = jax.eval_shape(lambda: nemotron_h.VideoMAENemotronH(
        nemotron_h.StreamHeadConfig()).empty_state(64)[0])
    assert big["ssm"].shape == (64, 5, 32, 64, 128)
    assert big["ssm"].dtype == jnp.float32
    assert big["conv"].shape == (64, 5, 3, 2560)
    assert big["kv"][0].shape == (1, 64, 1, 128, 4096)
    nbytes = lambda a: int(np.prod(a.shape)) * a.dtype.itemsize  # noqa: E731
    assert round(sum(nbytes(a) for a in big["kv"]) / 1e6) == 134
    assert round(nbytes(big["ssm"]) / 1e6) == 336
    pool = StreamStatePool(module, grow=4)
    pool.plan([f"cam{i}" for i in range(4)], 4)
    assert set(pool.state) == {"conv", "ssm", "kv", "tokens"}


def test_the_published_sizes_are_the_chips_share():
    """One chip's share of Nemotron-3-Super's layers: an expert layer
    142.61M (16 experts 88.08M, the shared expert 44.04M, W_down and W_up
    8.39M, the router 2.10M), a mixer 27.41M, the attention 9.44M."""
    spec = registry.get("videomae_b_nemotron3_super")
    model = spec.build()
    shapes = jax.eval_shape(lambda: spec.init_params(
        jax.random.PRNGKey(0))[1])
    flat = flax.traverse_util.flatten_dict(
        flax.linen.meta.unbox(shapes)["params"], sep="/")
    count = lambda pre: sum(int(np.prod(v.shape))  # noqa: E731
                            for k, v in flat.items() if k.startswith(pre))
    c = model.cfg.head
    assert c.pattern == "MEMEMEM*EME"
    assert flat["head/layer1/mixer/w1"].shape == (16, 1024, 2688)
    assert flat["head/layer1/mixer/gate"].shape == (4096, 512)
    assert flat["head/layer1/mixer/fc1_latent_proj"].shape == (4096, 1024)
    assert flat["head/layer1/mixer/shared_w1"].shape == (4096, 5376)
    assert "head/layer1/mixer/w3" not in flat
    assert flat["head/layer0/mixer/in_proj"].shape == (4096, 4640)
    assert flat["head/layer7/mixer/q_proj"].shape == (4096, 1024)
    assert flat["head/layer7/mixer/k_proj"].shape == (4096, 128)
    assert round(count("head/layer1/") / 1e6, 2) == 142.61
    assert round(count("head/layer1/mixer/w") / 1e6, 2) == 88.08
    assert round(count("head/layer0/") / 1e6, 2) == 27.41
    assert round(count("head/layer7/") / 1e6, 2) == 9.44
    total = sum(int(np.prod(v.shape)) for v in flat.values())
    assert 1.100e9 < total < 1.101e9
    assert max(nemotron_h.INSTRUCTION_IDS) < c.vocab_size
    assert len(set(nemotron_h.INSTRUCTION_IDS)) == 32
    # the router stays float32 through the cast, the experts do not
    cast = jax.eval_shape(stream_head.cast_for_serving, shapes)
    cast = flax.traverse_util.flatten_dict(
        flax.linen.meta.unbox(cast)["params"], sep="/")
    assert cast["head/layer1/mixer/gate"].dtype == jnp.float32
    assert cast["head/layer1/mixer/w1"].dtype == jnp.bfloat16


# -- through the engine, on the bus ------------------------------------------

def test_engine_serves_the_head_through_the_stream_kind(monkeypatch):
    """The tiny twin through ``InferenceEngine``'s ``stream`` kind: every
    result carries its head's answer, the batches carry the expert layers'
    counts over every routed pair, and a stream that leaves frees its slot
    of the three-kind pool."""
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
    eng.start()
    rng = np.random.default_rng(0)
    c = nemotron_h.tiny_stream_head_config()

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
            assert r.head.positions == len(c.instruction_ids) + (
                r.head.rounds_since_reset * c.round_positions)
        recs = [r for r in eng.stage_records if "moe_pairs_total" in r]
        assert recs
        # every token of the 4-row batch through both expert layers,
        # prefill and decode, top-4 a token
        per_row = (c.visual_tokens + c.decode_steps) * 2 * 4
        for rec in recs:
            assert rec["moe_pairs_total"] == 4 * per_row
            assert rec["moe_pairs_routed"] == rec["moe_pairs_total"]
            assert 0 < rec["moe_pairs_local"] <= rec["moe_pairs_total"]
            assert "mtp_drafted" not in rec
        pool = eng._head_pools[TINY]
        assert len(pool) == 3
        assert set(pool.state) == {"conv", "ssm", "kv", "tokens"}
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
