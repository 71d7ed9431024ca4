"""The streaming head (``models/lfm2.py``), its expert layer
(``models/transformer.py`` ``TopKMoeMlp``), its state pool
(``engine/stream_state.py``) and the ``stream`` step kind, against the
benchmark's plain reference (``benchmark/reference/lfm2_stream.py``, loaded
by path) on seeded weights at tiny sizes. CPU: results and counts only."""

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
from video_edge_ai_proxy_tpu.models import lfm2, registry  # noqa: E402
from video_edge_ai_proxy_tpu.obs import tracer  # noqa: E402
from video_edge_ai_proxy_tpu.obs.spans import STAGES  # noqa: E402
from video_edge_ai_proxy_tpu.models.transformer import (  # noqa: E402
    TopKMoeConfig, TopKMoeMlp)
from video_edge_ai_proxy_tpu.uplink.queue import AnnotationQueue  # noqa: E402
from video_edge_ai_proxy_tpu.utils.config import EngineConfig  # noqa: E402

TINY = "tiny_videomae_lfm2"
H, W = 48, 64


def _tiny_sizes():
    import json

    with open(os.path.join(BENCH, "tests", "data", "tiny_stream.json")) as f:
        return loader.models(json.load(f))[0]


def _nest(flat, prefix=""):
    """{"params": tree} of the weights under ``prefix``."""
    tree = {k[len(prefix):]: v for k, v in flat.items()
            if k.startswith(prefix)}
    return {"params": flax.traverse_util.unflatten_dict(tree, sep="/")}


def _reference():
    return loader.reference("lfm2_stream"), loader.reference(
        "vision_transformer")


# -- each kind of layer against the plain reference ------------------------

@pytest.mark.parametrize("layer_types,dense", [
    (("conv",), 1), (("full_attention",), 1), (("conv",), 0),
    (("full_attention", "conv"), 0)],
    ids=["conv+dense", "attention+dense", "conv+experts",
         "attention+conv+experts"])
def test_layers_match_the_reference(layer_types, dense):
    m = _tiny_sizes()
    sizes = dict(m["sizes"], layer_types=list(layer_types),
                 num_hidden_layers=len(layer_types), num_dense_layers=dense)
    flat = weights.generate(11, m["family"], sizes)
    ref, vt = _reference()
    t = 13
    x = jax.random.normal(jax.random.PRNGKey(3), (t, sizes["hidden_size"]))
    want = ref.decoder(flat, x, sizes, vt._einsum(""))
    base = lfm2.tiny_stream_head_config().head
    cfg = lfm2.Lfm2Config(**dict(
        base.__dict__, layer_types=tuple(layer_types),
        num_dense_layers=dense))
    stack = lfm2.Lfm2Stack(cfg, dtype=jnp.float32)
    conv, pool = lfm2.empty_state(cfg, 1, 0, jnp.float32)
    conv = jnp.zeros((1, max(cfg.conv_layers, 1)) + conv.shape[2:])
    got, _, _, load = stack.apply(
        _nest(flat, "head/"), x[None], conv, pool,
        lfm2.round_buffer(cfg, 1, t, jnp.float32), jnp.arange(1),
        jnp.zeros((1,), jnp.int32))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=2e-4, atol=2e-4)
    if not dense:
        # every token's top-2 of 8 experts, of which 4 are held
        assert 0 < int(load.sum()) <= t * 2 * (len(layer_types))


# -- the expert layer: shares and droplessness ------------------------------

def _moe(held, n_experts=16, top_k=4, dim=32, width=24):
    return TopKMoeMlp(TopKMoeConfig(
        dim=dim, mlp_dim=width, num_experts=n_experts, top_k=top_k,
        experts_held=tuple(held)), dtype=jnp.float32)


def _full_moe_params(seed, n_experts=16, dim=32, width=24):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return {"gate": jax.random.normal(k[0], (dim, n_experts)) * dim ** -0.5,
            "expert_bias": jax.random.normal(k[1], (n_experts,)) * 0.2,
            "w1": jax.random.normal(k[2], (n_experts, dim, width)) * 0.2,
            "w3": jax.random.normal(k[3], (n_experts, dim, width)) * 0.2,
            "w2": jax.random.normal(k[4], (n_experts, width, dim)) * 0.2}


def _share(full, held):
    ids = np.asarray(held)
    return {"params": dict(full, w1=full["w1"][ids], w3=full["w3"][ids],
                           w2=full["w2"][ids])}


def _dense_moe(full, x, top_k):
    """Every expert on every token, weighted: the layer written plainly."""
    s = jax.nn.sigmoid(x @ full["gate"])
    _, sel = jax.lax.top_k(s + full["expert_bias"], top_k)
    w = s * jnp.sum(jax.nn.one_hot(sel, s.shape[-1]), axis=1)
    w = w / w.sum(-1, keepdims=True)
    a = jax.nn.silu(jnp.einsum("td,edm->etm", x, full["w1"])) \
        * jnp.einsum("td,edm->etm", x, full["w3"])
    return jnp.einsum("etd,te->td", jnp.einsum("etm,emd->etd", a, full["w2"]),
                      w)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The guide's share test: each of 4 holders routes over all 16
    experts and computes its own 4; the partial outputs sum to the whole
    layer's (nothing is computed alike by all, so nothing counts twice)."""
    full = _full_moe_params(5)
    x = jax.random.normal(jax.random.PRNGKey(9), (37, 32))
    want = _dense_moe(full, x, 4)
    parts, loads = [], []
    for s in range(4):
        held = range(4 * s, 4 * s + 4)
        y, load = _moe(held).apply(_share(full, held), x)
        parts.append(y)
        loads.append(int(load.sum()))
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    assert sum(loads) == 37 * 4           # every pair computed exactly once
    whole, load = _moe(range(16)).apply(_share(full, range(16)), x)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    # an unheld holder's part is not a stand-in for anything
    assert float(jnp.abs(parts[0] - want).max()) > 1e-3


def test_no_token_is_dropped_when_all_choose_one_expert():
    """Skew: the bias sends every token's first choice to expert 2 (and
    the second to expert 9, held elsewhere): expert 2 takes all N pairs,
    and the output is the plain layer's."""
    full = _full_moe_params(6)
    full["expert_bias"] = full["expert_bias"].at[2].set(50.0).at[9].set(40.0)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 32))
    held = (0, 1, 2, 3)
    y, load = _moe(held, top_k=2).apply(_share(full, held), x)
    assert load.tolist() == [0, 0, 64, 0]
    s = jax.nn.sigmoid(x @ full["gate"])
    w2 = s[:, 2] / (s[:, 2] + s[:, 9])
    e2 = (jax.nn.silu(x @ full["w1"][2]) * (x @ full["w3"][2])) @ full["w2"][2]
    np.testing.assert_allclose(np.asarray(y), np.asarray(e2 * w2[:, None]),
                               rtol=1e-4, atol=1e-5)


def test_the_expert_layer_keeps_the_shared_expert_plumbing():
    """One definition of the expert stacks for every MoE variant: the
    'expert' logical axis is on all three of the dropless layer's."""
    m = _moe(range(4))
    v = m.init(jax.random.PRNGKey(0), jnp.zeros((2, 32)))["params"]
    for name in ("w1", "w3", "w2"):
        assert v[name].names[0] == "expert" and v[name].value.shape[0] == 4
    assert v["gate"].value.shape == (32, 16)     # the router's full width
    assert v["expert_bias"].shape == (16,)


# -- the whole stack through the pool against one full forward -------------

def _variables(seed):
    m = _tiny_sizes()
    fam = loader.family(m["family"])
    spec = registry.get(TINY)
    module = spec.build()
    assert fam.check_sizes(module, m["sizes"]) == {}
    flat = weights.generate(seed, m["family"], m["sizes"])
    return m, fam, spec, module, flat, spec.prepare(
        module, weights.as_variables(flat, fam.template(spec, module)))


def test_rounds_through_the_pool_match_one_full_forward():
    """Two streams, started a round apart, five rounds each through the
    ``stream`` step and the state pool (prefill, decode, a reset when the
    context is full and the cut first context): every round's logits are
    the reference's, which sees the whole context at once."""
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
    history = {d: [] for d in frames}      # [(clip start, rounds, tokens)]
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
        for i, d in enumerate(ids):
            rounds = int(out["rounds"][i])
            resets[d] += int(plan["reset"][i])
            answered = k[d] + 1
            assert (rounds, int(out["positions"][i])) == fam.expected_state(
                d, answered, m["sizes"])
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
            lp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
            got = np.log(np.asarray(out["top_probs"][i]))
            want = np.take_along_axis(lp, np.asarray(out["top_ids"][i]), -1)
            np.testing.assert_allclose(got, want, atol=0.03)
            history[d].append(rounds)
        # a padded row (round 0 has one stream) wrote nothing
    assert history["cam_a"][:first["cam_a"] + 1] == list(
        range(1, first["cam_a"] + 1)) + [1]
    assert resets["cam_a"] >= 2 and resets["cam_b"] >= 1
    assert max(history["cam_a"]) <= c.max_rounds


def test_the_stream_step_through_the_window_gives_the_tokens_of_whole_clips():
    """The windowed form of the ``stream`` step (one new frame a row, the
    window on the device) against the step fed whole clips, both through
    a state pool of their own: the same tokens, probabilities and state
    every round, with a camera that joins late and one that sits out."""
    from video_edge_ai_proxy_tpu.engine.stream_state import ClipWindowPool

    _, _, spec, module, _, variables = _variables(7)
    n = module.cfg.video.num_frames
    geom = (H, W, 3)
    plain = jax.jit(runner.build_serving_step(module, spec),
                    donate_argnums=(2,))
    windowed = jax.jit(runner.build_serving_step(module, spec, window=True),
                       donate_argnums=(2, 5))
    heads = StreamStatePool(module, grow=2), StreamStatePool(module, grow=2)
    wpool = ClipWindowPool(n, (1, 2))
    rng = np.random.default_rng(1)
    seen = {"cam_a": [], "cam_b": []}
    compared = 0
    for r in range(n + 5):
        ids = ["cam_a"] if r == 0 or r == n + 2 else ["cam_a", "cam_b"]
        single = np.zeros((2,) + geom, np.uint8)
        for i, d in enumerate(ids):
            single[i] = rng.integers(0, 255, geom, dtype=np.uint8)
            seen[d].append(single[i])
        wplan = wpool.plan(ids, geom, 2)
        emit = wplan["emit"]
        full = [ids[j] for j in emit]
        hw = heads[1].plan(full, 2, rows=emit)
        out_w = dict(windowed(
            variables, single, wpool.window(geom), wplan["idx"],
            wplan["pos"], heads[1].state, hw["idx"], hw["pos0"],
            hw["reset"], hw["rounds"]))
        wpool.put(geom, out_w.pop("window"))
        heads[1].state = out_w.pop("state")
        if not emit:
            continue
        clips = np.zeros((2, n) + geom, np.uint8)
        for j in emit:
            clips[j] = np.stack(seen[ids[j]][-n:])
        hp = heads[0].plan(full, 2, rows=emit)
        for k in hp:
            np.testing.assert_array_equal(hp[k], hw[k])
        out = dict(plain(variables, clips, heads[0].state, hp["idx"],
                         hp["pos0"], hp["reset"], hp["rounds"]))
        heads[0].state = out.pop("state")
        for j in emit:
            for k in ("tokens", "top_ids", "history", "rounds", "positions"):
                np.testing.assert_array_equal(
                    np.asarray(out_w[k][j]), np.asarray(out[k][j]), err_msg=k)
            np.testing.assert_allclose(
                np.asarray(out_w["top_probs"][j]),
                np.asarray(out["top_probs"][j]), rtol=0, atol=1e-6)
            compared += 1
    # each from its 4th read on: cam_a read 9 frames, cam_b 7
    assert compared == 6 + 4


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


def test_the_heads_matrices_are_cast_once_for_serving():
    spec = registry.get("videomae_b_lfm2")
    assert spec.prepare is lfm2.prepare_for_serving and spec.kind == "stream"
    module = registry.get(TINY).build()
    v = jax.jit(module.init)(jax.random.PRNGKey(0),
                             jnp.zeros((1, 4, 32, 32, 3)))
    flat = flax.traverse_util.flatten_dict(
        flax.linen.meta.unbox(lfm2.cast_for_serving(v))["params"], sep="/")
    for name, a in flat.items():
        bf16 = (name.startswith(("head/", "connector/")) and a.ndim >= 2
                and not name.endswith("/gate"))
        assert a.dtype == (jnp.bfloat16 if bf16 else jnp.float32), name
    assert flat["head/layer1_moe/w1"].dtype == jnp.bfloat16
    assert flat["video/encoder/block0/mlp/fc1/kernel"].dtype == jnp.float32
    # the logical axis names survive the cast (ep sharding of the experts)
    boxed = lfm2.cast_for_serving(v)["params"]["head"]["layer1_moe"]["w1"]
    assert boxed.names[0] == "expert" and boxed.value.dtype == jnp.bfloat16


# -- the pool's bookkeeping --------------------------------------------------

def test_pool_slots_are_given_freed_and_reset_by_the_policy():
    module = registry.get(TINY).build()
    c = module.cfg
    pool = StreamStatePool(module, grow=4)
    ids = [f"cam{i}" for i in range(4)]
    plan = pool.plan(ids, 4)
    assert sorted(plan["idx"].tolist()) == [0, 1, 2, 3]
    assert plan["reset"].all() and (plan["pos0"] == 4).all()
    bytes0 = pool.nbytes()
    assert bytes0 > 0 and pool.capacity == 4
    # de-phased: the first contexts end after different numbers of rounds
    firsts = [first_context_rounds(d, c.max_rounds) for d in ids]
    seen = {d: [] for d in ids}
    for _ in range(8):
        plan = pool.plan(ids, 4)
        for i, d in enumerate(ids):
            seen[d].append(bool(plan["reset"][i]))
    for d, f in zip(ids, firsts):
        assert seen[d].index(True) == f - 1      # round f + 1 resets
    assert len({tuple(v) for v in seen.values()}) > 1
    # a stream that leaves frees its slot; the next newcomer takes it and
    # starts from a reset; the pool does not grow
    slot = pool._slots["cam1"]
    pool.pop("cam1")
    assert len(pool) == 3
    plan = pool.plan(["cam0", "new"], 4)
    assert plan["idx"][1] == slot and plan["reset"][1]
    assert plan["idx"][2:].tolist() == [pool.capacity] * 2   # padded rows
    assert pool.nbytes() == bytes0
    # a fifth stream at once grows the pool by one step
    pool.plan(["cam0", "cam2", "cam3", "new", "more"], 8)
    assert pool.capacity == 8 and pool.nbytes() == 2 * bytes0


# -- through the engine, on the bus ----------------------------------------

def _publish(bus, device_id, packet, rng):
    meta = FrameMeta(width=W, height=H, channels=3, packet=packet,
                     timestamp_ms=int(time.time() * 1000), is_keyframe=True)
    bus.publish(device_id, rng.integers(0, 255, (H, W, 3), dtype=np.uint8),
                meta)


@pytest.mark.parametrize("home", ["host", "device"])
def test_engine_serves_the_head_one_result_a_read(monkeypatch, home):
    """``host``: as the engine serves the kind (its windows stay on the
    host, ``InferenceEngine._window_on_device``). ``device``: the same
    reads with that one decision turned, through the windowed step, a
    window slot beside the head's: the same results."""
    monkeypatch.setattr(InferenceEngine, "_TRACKER_GC_GRACE_S", 0.2)
    if home == "device":
        monkeypatch.setattr(
            InferenceEngine, "_window_on_device",
            lambda self, model: self._device_windows)
    bus = MemoryFrameBus()
    cams = [f"clip{i}" for i in range(3)]
    for cam in cams:
        bus.create_stream(cam, H * W * 3)
    cfg = EngineConfig(model=TINY, batch_buckets=(4,), tick_ms=5,
                       stage_trace=True, ladder=False, hbm=True)
    eng = InferenceEngine(bus, cfg,
                          annotations=AnnotationQueue(handler=lambda b: True))
    eng.warmup()
    got = []
    spans_were = (tracer.enabled, tracer.sample_every)
    tracer.clear()
    tracer.configure(enabled=True, sample_every=1)

    def subscriber():
        for res in eng.subscribe():
            got.append(res)

    threading.Thread(target=subscriber, daemon=True).start()
    eng.start()
    rng = np.random.default_rng(0)
    clip_len = registry.get(TINY).clip_len
    try:
        def wait(n):
            deadline = time.time() + 60
            while len(got) < n and time.time() < deadline:
                time.sleep(0.01)
            assert len(got) >= n, (len(got), n)

        def wait_read(k):
            """Every camera's frame k has been read (the bus is
            latest-wins: a frame published over an unread one is lost)."""
            deadline = time.time() + 60
            while time.time() < deadline:
                reads = [sum(1 for e in tracer.events(cam)
                             if e["stage"] == "collect") for cam in cams]
                if min(reads) >= k + 1:
                    return
                time.sleep(0.005)
            raise AssertionError(f"frame {k} unread: {reads}")

        rounds = 9
        for k in range(rounds):
            for cam in cams:
                _publish(bus, cam, k + 1, rng)
            wait_read(k)
            wait(max(0, k + 2 - clip_len) * len(cams))
        # one result a read from the fourth read on, in order, each with
        # its answer and its state
        per_cam = {c: [r for r in got if r.device_id == c] for c in cams}
        c = lfm2.tiny_stream_head_config()
        for cam, rs in per_cam.items():
            assert [r.frame_packet for r in rs] == list(
                range(clip_len, rounds + 1))
            first = first_context_rounds(cam, c.max_rounds)
            for answered, r in enumerate(rs, 1):
                want = (answered if answered <= first
                        else (answered - first - 1) % c.max_rounds + 1)
                assert r.model == TINY
                assert r.head.rounds_since_reset == want
                assert r.head.positions == 4 + want * c.round_positions
                assert len(r.head.token_ids) == want * c.decode_steps
                assert len(r.head.steps) == c.decode_steps
                assert [d.class_id for d in r.detections] == list(
                    r.head.token_ids)[-c.decode_steps:]
                for s, d in zip(r.head.steps, r.detections):
                    assert len(s.token_ids) == 5 and s.token_ids[0] == d.class_id
                    assert 0 < sum(s.probs) <= 1.001
        # the batch trace carries the head's fields
        # (a round in which all three were read by one tick: on a loaded
        # machine a camera's frame can land a tick later)
        rec = max(eng.stage_records, key=lambda r: r["head_prefill_tokens"])
        assert rec["head_prefill_tokens"] == 3 * c.visual_tokens
        assert rec["head_decode_steps"] == c.decode_steps
        assert rec["pool_s"] >= 0 and rec["moe_pairs_local"] > 0
        # the wait for the predecessor step is a phase of its own, before
        # the step call
        assert rec["state_wait_s"] >= 0
        assert rec["t_step0"] <= rec["t_step1"]
        stages = {e["stage"] for e in tracer.events("engine.tick")}
        assert {"pool", "state_wait", "step_call"} <= stages <= set(STAGES)
        assert rec["moe_load_max"] >= rec["moe_load_mean"] > 0
        pool = eng._head_pools[TINY]
        assert len(pool) == 3 and pool.capacity == 4
        held = pool.nbytes()
        assert eng.hbm.pools()["pools"]["stream_state"]["bytes"] == held
        # a stream leaves: its slot is freed (debounced GC); the others go on
        n0 = len(got)
        bus.drop_stream("clip2")
        deadline = time.time() + 30
        k = rounds
        while len(pool) == 3 and time.time() < deadline:
            k += 1
            for cam in cams[:2]:
                _publish(bus, cam, k + 1, rng)
            time.sleep(0.1)
        assert len(pool) == 2 and "clip2" not in list(pool)
        assert len(got) > n0 and pool.nbytes() == held
        # where the windows were: a pool slot a stream on the device (freed
        # with the head's), a ring a stream on the host
        wpool = eng._window_pools.get(TINY)
        if home == "device":
            assert set(wpool) == set(cams[:2]) and not eng._collector._clips
        else:
            assert wpool is None and set(eng._collector._clips) >= set(cams[:2])
    finally:
        eng.stop()
        bus.close()
        tracer.configure(enabled=spans_were[0], sample_every=spans_were[1])
        tracer.clear()
