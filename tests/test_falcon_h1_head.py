"""The fourth streaming head (``models/falcon_h1.py``): the chunked
state-space scan (``ops/ssd.py``) against the token-by-token recurrence, a
layer's two shares against the uncut layer, its three kinds of state in the
pool (``engine/stream_state.py``; the state-space state float32 beside
bfloat16 kinds) through the ``stream`` step kind and the engine, against the
benchmark's plain reference (``benchmark/reference/falcon_h1_stream.py``,
loaded by path) on seeded weights at tiny sizes; and the attention it
shares with the first head (``models/gqa.py``), whose move left the other
heads' programs as they were. CPU, float32: results and counts only."""

import hashlib
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
    falcon_h1, gqa, registry)
from video_edge_ai_proxy_tpu.ops import ssd  # noqa: E402
from video_edge_ai_proxy_tpu.uplink.queue import AnnotationQueue  # noqa: E402
from video_edge_ai_proxy_tpu.utils.config import EngineConfig  # noqa: E402

TINY = "tiny_videomae_b_falcon_h1"
H, W = 48, 64


def _tiny_sizes():
    with open(os.path.join(BENCH, "tests", "data",
                           "tiny_falcon_h1.json")) as f:
        return loader.models(json.load(f))[0]


def _variables(seed):
    m = _tiny_sizes()
    fam = loader.family(m["family"])
    spec = registry.get(TINY)
    module = spec.build()
    assert fam.check_sizes(module, m["sizes"]) == {}
    flat = weights.generate(seed, m["family"], m["sizes"])
    return m, fam, spec, module, flat, spec.prepare(
        module, weights.as_variables(flat, fam.template(spec, module)))


# -- the chunked scan against the recurrence ---------------------------------

def _recurrence(x, dt, a, b, c, d, h):
    """Token by token in float64: the published one-step update."""
    x, dt, a, b, c, d, h = (np.asarray(v, np.float64)
                            for v in (x, dt, a, b, c, d, h))
    per = x.shape[2] // b.shape[2]
    ys = []
    for t in range(x.shape[1]):
        bt, ct = (np.repeat(v[:, t], per, axis=1) for v in (b, c))
        h = h * np.exp(dt[:, t] * a)[..., None, None] \
            + (dt[:, t, :, None] * x[:, t])[..., None] * bt[:, :, None, :]
        ys.append(np.einsum("shpn,shn->shp", h, ct) + d[:, None] * x[:, t])
    return np.stack(ys, 1), h


def _scan_inputs(t, seed, s=2, heads=4, p=3, n=5, g=2):
    rng = np.random.default_rng(seed)
    f = lambda *shape: rng.normal(size=shape).astype(np.float32)  # noqa: E731
    dt = np.log1p(np.exp(f(s, t, heads) - 1.0)).astype(np.float32)
    return (f(s, t, heads, p), dt, -np.exp(f(heads)), f(s, t, g, n),
            f(s, t, g, n), f(heads), f(s, heads, p, n))


@pytest.mark.parametrize("carried", [False, True], ids=["zero", "carried"])
@pytest.mark.parametrize("t", [5, 8, 13, 35])
def test_the_chunked_scan_is_the_recurrence(t, carried):
    """Chunks of 8 over lengths that are and are not multiples of it, from
    a zero state and from a carried one: the outputs and the state it ends
    in are the token-by-token recurrence's, to 1e-5 in float32."""
    x, dt, a, b, c, d, h0 = _scan_inputs(t, seed=t)
    if not carried:
        h0 = np.zeros_like(h0)
    y, h = jax.jit(ssd.ssd_scan, static_argnums=7)(x, dt, a, b, c, d, h0, 8)
    want_y, want_h = _recurrence(x, dt, a, b, c, d, h0)
    np.testing.assert_allclose(np.asarray(y), want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h), want_h, rtol=1e-5, atol=1e-5)


def test_padding_positions_leave_the_state_as_it_was():
    """A position whose dt is 0 adds nothing and decays nothing: 13
    positions followed by 11 of dt 0 end in the 13's state, and the 13's
    outputs do not change."""
    x, dt, a, b, c, d, h0 = _scan_inputs(13, seed=3)
    widen = lambda v: np.concatenate(  # noqa: E731
        [v, np.random.default_rng(9).normal(
            size=(v.shape[0], 11) + v.shape[2:]).astype(np.float32)], 1)
    padded = (widen(x), np.concatenate([dt, np.zeros((2, 11, 4), np.float32)],
                                       1), a, widen(b), widen(c), d, h0)
    y, h = ssd.ssd_scan(x, dt, a, b, c, d, h0, 8)
    yp, hp = ssd.ssd_scan(*padded, 8)
    np.testing.assert_allclose(np.asarray(hp), np.asarray(h), rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(yp)[:, :13], np.asarray(y),
                               rtol=1e-6, atol=1e-6)


def test_the_decode_step_repeated_is_the_chunked_scan():
    x, dt, a, b, c, d, h0 = _scan_inputs(19, seed=5)
    y, h = ssd.ssd_scan(x, dt, a, b, c, d, h0, 8)
    state, ys = jnp.asarray(h0), []
    for t in range(19):
        yt, state = ssd.ssd_step(x[:, t], dt[:, t], a, b[:, t], c[:, t], d,
                                 state)
        ys.append(yt)
    np.testing.assert_allclose(np.stack(ys, 1), np.asarray(y), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(state), np.asarray(h), rtol=1e-5,
                               atol=1e-5)


# -- a layer's two shares add up to the uncut layer -------------------------

def _share(flat, heads, kv_heads, group, cols, sizes):
    """One chip's slices of layer 0 of the uncut tiny layer: query heads
    ``heads``, key-value heads ``kv_heads``, mixer group ``group`` (its
    heads' channels of z, x and dt, its B and C, its norm, its rows of
    W_out), the SwiGLU's columns ``cols``."""
    hd, nh, g = sizes["head_dim"], sizes["mamba_n_heads"], \
        sizes["mamba_n_groups"]
    p, n = sizes["mamba_d_head"], sizes["mamba_d_state"]
    e = nh * p
    per, ch = nh // g, nh // g * p
    cols_of = lambda ids, w: np.concatenate(  # noqa: E731
        [np.arange(i * w, (i + 1) * w) for i in ids])
    mine = np.arange(group * ch, (group + 1) * ch)
    bc = np.arange(group * n, (group + 1) * n)
    heads_m = np.arange(group * per, (group + 1) * per)
    z, x = mine, e + mine
    b_, c_ = 2 * e + bc, 2 * e + g * n + bc
    dt = 2 * e + 2 * g * n + heads_m
    conv = np.concatenate([mine, e + bc, e + g * n + bc])
    L = {k[len("head/layer0/"):]: np.asarray(v) for k, v in flat.items()
         if k.startswith("head/layer0/")}
    q = cols_of(heads, hd)
    kv = cols_of(kv_heads, hd)
    out = {
        "input_norm/scale": L["input_norm/scale"],
        "ffn_norm/scale": L["ffn_norm/scale"],
        "mamba/in_proj": L["mamba/in_proj"][:, np.concatenate(
            [z, x, b_, c_, dt])],
        "mamba/conv_kernel": L["mamba/conv_kernel"][:, conv],
        "mamba/conv_bias": L["mamba/conv_bias"][conv],
        "mamba/dt_bias": L["mamba/dt_bias"][heads_m],
        "mamba/A_log": L["mamba/A_log"][heads_m],
        "mamba/D": L["mamba/D"][heads_m],
        "mamba/norm_scale": L["mamba/norm_scale"][mine],
        "mamba/out_proj": L["mamba/out_proj"][mine],
        "attn/q_proj": L["attn/q_proj"][:, q],
        "attn/k_proj": L["attn/k_proj"][:, kv],
        "attn/v_proj": L["attn/v_proj"][:, kv],
        "attn/out_proj": L["attn/out_proj"][q],
        "mlp/gate_proj": L["mlp/gate_proj"][:, cols],
        "mlp/up_proj": L["mlp/up_proj"][:, cols],
        "mlp/down_proj": L["mlp/down_proj"][cols]}
    return flax.traverse_util.unflatten_dict(out, sep="/")


def test_the_two_chips_shares_add_up_to_the_uncut_layer():
    """Chip one holds query heads 0-1 (key-value head 0), mixer group 0
    and SwiGLU columns 0-39; chip two the rest. Each computes its partial
    sums of the mixer, the attention and the SwiGLU (the row-parallel
    products); with the residual counted once they are the plain
    reference's uncut layer, and one share alone is not."""
    m = _tiny_sizes()
    s = m["sizes"]
    flat = weights.generate(11, m["family"], s)
    ref = loader.reference("falcon_h1_stream")
    vt = loader.reference("vision_transformer")
    t = 21
    x = jax.random.normal(jax.random.PRNGKey(4), (t, s["hidden_size"]))
    want = np.asarray(ref.decoder(
        {k: v for k, v in flat.items()}, x,
        dict(loader.frozen(dict(s, num_hidden_layers=1))), vt._einsum("")))
    whole = falcon_h1.tiny_stream_head_config().head
    import dataclasses

    half = dataclasses.replace(whole, num_heads=2, num_kv_heads=1,
                               mamba_heads=2, mamba_groups=1, mlp_held=40)
    shares = [_share(flat, (0, 1), (0,), 0, np.arange(40), s),
              _share(flat, (2, 3), (1,), 1, np.arange(40, 80), s)]
    block = falcon_h1.FalconH1Block(half, 0, jnp.float32)

    def parts(params, x):
        mix = falcon_h1.Mamba2Mixer(half, jnp.float32)
        attn = gqa.CachedAttention(half.attn, 0, jnp.float32)
        n = block.bind({"params": params}).input_norm(x[None])
        mx, _, _ = mix.apply({"params": params["mamba"]}, n,
                             jnp.zeros((1, 3, half.conv_dim)),
                             jnp.zeros((1, 2, 8, 8)))
        pool = gqa.empty_pool(half.attn, 1, 1, 0, jnp.float32)
        a, _ = attn.apply({"params": params["attn"]}, n, pool,
                          tuple(r[0] for r in gqa.round_buffer(
                              half.attn, 1, 1, t, jnp.float32)),
                          jnp.zeros((1,), jnp.int32),
                          jnp.zeros((1,), jnp.int32), 0)
        return mx[0], a[0] * half.attention_out_multiplier

    got = [parts(p, x) for p in shares]
    xp = x + sum(m_ + a_ for m_, a_ in got)
    mlp = falcon_h1.FalconMlp(half, jnp.float32)
    u = [block.bind({"params": p}).ffn_norm(xp) for p in shares]
    np.testing.assert_allclose(np.asarray(u[0]), np.asarray(u[1]))
    total = xp + sum(mlp.apply({"params": p["mlp"]}, u[0]) for p in shares)
    np.testing.assert_allclose(np.asarray(total), want, rtol=2e-4, atol=2e-4)
    alone = x + got[0][0] + got[0][1]
    alone = alone + mlp.apply({"params": shares[0]["mlp"]},
                              block.bind({"params": shares[0]}).ffn_norm(
                                  alone))
    assert float(np.abs(np.asarray(alone) - want).max()) > 0.05


# -- the whole stack through the pool against one full forward ---------------

def test_rounds_through_the_pool_match_one_full_forward():
    """Two streams, started a round apart, six rounds through the
    ``stream`` step and the state pool (a reset when the context is full
    and the cut first context in between): every round's logits are the
    reference's, which runs the whole context at once with its
    state-space layer token by token. Tolerance 0.01 on log-probabilities:
    the float32 twin reads ~2e-3 (the preprocess's resize and the
    encoder's order of sums differ from the reference's; the sibling heads'
    tests hold 0.03); a state that is not carried reads ~0.1 and more."""
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
        k = {d: r - (d == "cam_b") for d in ids}
        batch = np.zeros((2, n, H, W, 3), np.uint8)
        for i, d in enumerate(ids):
            batch[i] = frames[d][k[d]:k[d] + n]
        plan = pool.plan(ids, 2)
        out = step(variables, batch, pool.state, plan["idx"], plan["pos0"],
                   plan["reset"], plan["rounds"])
        pool.state = out.pop("state")
        assert set(pool.state) == {"conv", "ssm", "kv", "tokens"}
        assert not [k_ for k_ in out if k_.startswith("moe_")]
        buf = np.zeros((fam.REFERENCE_BLOCK, n + c.max_rounds - 1, H, W, 3),
                       np.uint8)
        wins = []
        for i, d in enumerate(ids):
            rounds = int(out["rounds"][i])
            resets[d] += int(plan["reset"][i])
            assert (rounds, int(out["positions"][i])) == fam.expected_state(
                d, k[d] + 1, m["sizes"])
            hist = [int(t) for t in np.asarray(out["history"][i]) if t >= 0]
            assert hist[-c.decode_steps:] == out["tokens"][i].tolist()
            start = k[d] - (rounds - 1)
            window = frames[d][start:start + n + rounds - 1]
            buf[i, :len(window)] = window
            wins.append(fam.Window(range(len(window)), rounds, hist))
            history[d].append(rounds)
        logits = np.asarray(ref(flat, *fam.reference_args(
            buf, wins + wins[:1] * (fam.REFERENCE_BLOCK - len(wins)),
            m["sizes"])))
        for i in range(len(ids)):
            lp = np.asarray(jax.nn.log_softmax(logits[i], axis=-1))
            got = np.log(np.asarray(out["top_probs"][i]))
            want = np.take_along_axis(lp, np.asarray(out["top_ids"][i]), -1)
            np.testing.assert_allclose(got, want, atol=0.01)
    assert history["cam_a"][:first["cam_a"] + 1] == list(
        range(1, first["cam_a"] + 1)) + [1]
    assert resets["cam_a"] >= 2 and resets["cam_b"] >= 1
    assert max(history["cam_a"]) >= 3      # >= 3 rounds carried in a context


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


def test_the_state_space_state_is_carried_and_overwritten():
    """The round ends by writing each row's state over its slot's: a slot
    holds what the round left, and a round that starts from another state
    serves other logits."""
    _, _, spec, module, _, variables = _variables(9)
    c = module.cfg
    clips = jax.random.normal(jax.random.PRNGKey(3), (2, 4, 40, 40, 3))
    state, _ = module.empty_state(2)
    pos0 = jnp.full((2,), len(c.instruction_ids), jnp.int32)
    one = module.serve_round(variables, clips, state, jnp.arange(2), pos0,
                             jnp.ones((2,), bool))
    ssm = np.asarray(one["state"]["ssm"])
    assert ssm.dtype == np.float32 and np.abs(ssm).max() > 0
    ins = np.asarray(variables["instruction"]["ssm"])
    assert np.abs(ssm - ins).max() > 1e-3          # written, not kept
    pos1 = pos0 + c.round_positions
    two = module.serve_round(variables, clips, one["state"], jnp.arange(2),
                             pos1, jnp.zeros((2,), bool))
    lost = module.serve_round(variables, clips,
                              dict(one["state"], ssm=state["ssm"]),
                              jnp.arange(2), pos1, jnp.zeros((2,), bool))
    assert float(jnp.abs(two["top_probs"] - lost["top_probs"]).max()) > 1e-3


# -- a pool of three kinds, two dtypes ---------------------------------------

def test_the_pool_holds_a_float32_kind_beside_bfloat16_kinds():
    """Alloc, growth and the round of a head whose kinds differ in dtype:
    each kind keeps its dtype and what the streams hold through a growth,
    along its own slot axis; the round buffer takes the key-value kind's
    dtype, not the first leaf's."""
    module = falcon_h1.VideoMAEFalconH1(falcon_h1.tiny_stream_head_config(),
                                        dtype=jnp.bfloat16)
    c = module.cfg
    state, axes = module.empty_state(4)
    assert axes == {"conv": 0, "ssm": 0, "kv": 1}
    assert state["ssm"].dtype == jnp.float32
    assert state["conv"].dtype == state["kv"][0].dtype == jnp.bfloat16
    assert state["ssm"].shape == (4, c.head.num_layers, 4, 8, 8)
    pool = StreamStatePool(module, grow=4)
    ids = [f"cam{i}" for i in range(4)]
    pool.plan(ids, 4)
    assert set(pool.state) == {"conv", "ssm", "kv", "tokens"}
    bytes0 = pool.nbytes()
    pool.state = jax.tree_util.tree_map(
        lambda a: a + (jnp.arange(a.size, dtype=jnp.float32).reshape(
            a.shape) % 7).astype(a.dtype), pool.state)
    held = jax.tree_util.tree_map(np.asarray, pool.state)
    pool.ensure(5)
    assert pool.capacity == 8 and pool.nbytes() == 2 * bytes0
    assert pool.state["ssm"].dtype == jnp.float32
    assert pool.state["kv"][0].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(pool.state["ssm"])[:4],
                                  held["ssm"])
    np.testing.assert_array_equal(np.asarray(pool.state["conv"])[:4],
                                  held["conv"])
    np.testing.assert_array_equal(np.asarray(pool.state["kv"][1])[:, :4],
                                  held["kv"][1])
    # the round: the state-space state stays float32, the buffer bfloat16
    spec = registry.get(TINY)
    variables = jax.eval_shape(lambda: falcon_h1.stream_head.cast_for_serving(
        spec.init_params(jax.random.PRNGKey(0))[1]))
    variables = dict(variables, instruction=jax.eval_shape(
        lambda v: module.instruction_state(v), variables))
    assert module.round_buffer(8, pool.state["kv"][0].dtype)[0].dtype \
        == jnp.bfloat16
    out = jax.eval_shape(
        lambda v, s: module.serve_round(
            v, jnp.zeros((8, 4, 40, 40, 3), jnp.bfloat16),
            {k: s[k] for k in ("conv", "ssm", "kv")}, jnp.arange(8),
            jnp.full((8,), 5, jnp.int32), jnp.ones((8,), bool)),
        variables, pool.state)
    assert out["state"]["ssm"].dtype == jnp.float32
    assert out["state"]["kv"][0].dtype == jnp.bfloat16
    assert out["state"]["conv"].dtype == jnp.bfloat16


def test_the_published_sizes_are_the_chips_share():
    """One chip's share of a Falcon-H1-34B layer: 215.07M parameters (the
    attention 15.73M, the mixer 34.18M, the SwiGLU 165.15M); the state a
    stream holds at this share."""
    c = falcon_h1.FalconH1Config()
    assert (c.ssm_dim, c.conv_dim, c.in_proj_dim) == (2048, 2560, 4624)
    assert c.mamba_heads * c.mamba_head_dim * 2 == c.mamba_d_ssm
    assert c.mlp_held * 2 == c.mlp_dim
    d = c.dim
    attn = d * c.num_heads * 128 * 2 + d * c.num_kv_heads * 128 * 2
    mixer = (d * c.in_proj_dim + c.d_conv * c.conv_dim + c.conv_dim
             + 3 * c.mamba_heads + c.ssm_dim + c.ssm_dim * d)
    mlp = 3 * d * c.mlp_held
    assert round(attn / 1e6, 2) == 15.73 and round(mixer / 1e6, 2) == 34.18
    assert round(mlp / 1e6, 2) == 165.15
    assert round((attn + mixer + mlp + 2 * d) / 1e6, 2) == 215.07
    state = jax.eval_shape(lambda: falcon_h1.VideoMAEFalconH1(
        falcon_h1.StreamHeadConfig()).empty_state(64)[0])
    assert state["ssm"].shape == (64, 4, 16, 128, 256)
    per = int(np.prod(state["ssm"].shape[2:])) * 4
    assert abs(per / 1e6 - 2.1) < 0.01              # a stream a layer
    # as many bytes as two key-value heads' keys and values of 2,048
    assert per == 2 * 2 * 128 * 2048 * 2
    assert abs(64 * 4 * per / 1e9 - 0.537) < 0.001
    kv = sum(int(np.prod(a.shape)) * 2 for a in state["kv"])
    assert abs(kv / 1e9 - 1.074) < 0.001
    assert max(falcon_h1.INSTRUCTION_IDS) < c.vocab_size
    assert len(set(falcon_h1.INSTRUCTION_IDS)) == 32


# -- the attention both heads share -------------------------------------------

# sha256 of the lowered (StableHLO) text of each head's serving step, taken
# before the attention moved out of models/lfm2.py and the round buffer's
# dtype was taken from the cache's kind: the moves change no program. The
# expert heads' were taken anew when the expert layer's way back came to
# walk only the held rows (ops/combine_rows.py, under ``moe_combine``)
STEPS = {
    ("videomae_b_lfm2", 64, 360, 640):
        "ea8e322cc3a92d180fad45e1d0126a5e792220b3309377e8a7dbd0c2cc22cb83",
    ("tiny_videomae_lfm2", 2, 48, 64):
        "6d9f73dd571d1a3ade8ba535965b10cdb0fb18735a29b27c5bdc8cdf77fee872",
    ("tiny_videomae_xing4", 2, 48, 64):
        "f22be9a1ccc27aa3b49f6e4709df14e93c685bbf658cf3a8db4aeaddd1da9210",
    ("tiny_videomae_dsv2", 2, 48, 64):
        "3078f3d5bf7b2a2ea8f0616492bf76fd0af59a8b242b161005eb5fbb1b66b800",
    # this head's own, taken before its mixer moved to models/mamba2.py and
    # the expert layer learned a latent and squared ReLU for the fifth head
    ("videomae_b_falcon_h1", 64, 360, 640):
        "d9dedcc78c145ac66c01a021429d913d2bd5e224d2819fb7c265146d46c4dea7",
    ("tiny_videomae_b_falcon_h1", 2, 48, 64):
        "732ec2cdbb8d3b009bda018794c5156b126cea19d89abc8b145cc9f88dd8d132",
}


@pytest.mark.parametrize("key", sorted(STEPS), ids=lambda k: k[0])
def test_the_other_heads_steps_are_what_they_were(key):
    name, b, h, w = key
    spec = registry.get(name)
    model = spec.build()
    variables = jax.eval_shape(lambda: spec.prepare(
        model, spec.init_params(jax.random.PRNGKey(0))[1]))
    state, _ = jax.eval_shape(lambda: model.empty_state(b))
    c = model.cfg
    state = dict(state, tokens=jax.ShapeDtypeStruct(
        (b, c.max_rounds * c.decode_steps), jnp.int32))
    ints = jax.ShapeDtypeStruct((b,), jnp.int32)
    text = jax.jit(runner.build_serving_step(model, spec),
                   donate_argnums=(2,)).lower(
        variables, jax.ShapeDtypeStruct((b, spec.clip_len, h, w, 3),
                                        jnp.uint8),
        state, ints, ints, jax.ShapeDtypeStruct((b,), bool), ints).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == STEPS[key]


def test_the_shared_attention_is_told_what_differs():
    """Without the per-head norm it has no norm scales; the key scale
    multiplies the keys; the query width is the held heads', not the
    model's."""
    import flax.linen as nn

    cfg = falcon_h1.FalconH1Config(dim=32, num_heads=2, num_kv_heads=1,
                                   head_dim=8).attn
    attn = gqa.CachedAttention(cfg, 0, jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 5, 32))
    pool = gqa.empty_pool(cfg, 1, 1, 0, jnp.float32)
    rbuf = tuple(r[0] for r in gqa.round_buffer(cfg, 1, 1, 5, jnp.float32))
    args = (pool, rbuf, jnp.zeros((1,), jnp.int32),
            jnp.zeros((1,), jnp.int32), 0)
    params = nn.meta.unbox(attn.init(jax.random.PRNGKey(0), h, *args))
    shapes = jax.tree_util.tree_map(lambda a: a.shape, params["params"])
    assert shapes == {"q_proj": (32, 16), "k_proj": (32, 8),
                      "v_proj": (32, 8), "out_proj": (16, 32)}
    _, (keys, _) = attn.apply(params, h, *args)
    one = gqa.CachedAttention(
        gqa.GqaConfig(**dict(cfg.__dict__, key_multiplier=1.0)), 0,
        jnp.float32)
    _, (plain, _) = one.apply(params, h, *args)
    np.testing.assert_allclose(np.asarray(keys),
                               np.asarray(plain) * cfg.key_multiplier,
                               rtol=1e-5, atol=1e-7)


# -- through the engine, on the bus ------------------------------------------

def test_engine_serves_the_head_through_the_stream_kind(monkeypatch):
    """The tiny twin through ``InferenceEngine``'s ``stream`` kind: every
    result carries its head's answer, no expert counts are asked of a head
    without experts, and a stream that leaves frees its slot of the
    three-kind pool."""
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
    c = falcon_h1.tiny_stream_head_config()

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
        recs = [r for r in eng.stage_records if "head_prefill_tokens" in r]
        assert recs
        for rec in recs:
            assert not [k_ for k_ in rec if k_.startswith("moe_")]
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
