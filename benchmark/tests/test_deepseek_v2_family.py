"""The third streaming head's family (``families/deepseek_v2_stream.py``)
and its plain reference: the tiny configuration comes in by files under
``data/`` and entries alone; the CPU rehearsal of its cell is ``correct``
and its two new per-layer metrics read the program's counters; and with the
router, the shared experts, a head's slice or the state broken underneath
it is not. Counts and comparisons only, never a time."""

import json
import os

import numpy as np
import pytest

import run as vrun
from test_run_rehearsal import DEVICE_METRICS, bench_with
from vbench import loader

DATA = os.path.join(os.path.dirname(__file__), "data")
MODEL = "tiny_videomae_dsv2"


def dsv2_bench():
    return bench_with("tiny_dsv2", "tiny_dsv2.free", "tiny_stream_free")


def _model(path):
    with open(path) as f:
        return loader.models(json.load(f))[0]


def _run(seed, trace=False):
    return vrun.run("tiny_dsv2.free", seed, 3.0, trace, require_chip=False,
                    bench=dsv2_bench())


@pytest.fixture(scope="module")
def traced():
    return _run(2**31 + 93, trace=True)


def test_the_cell_comes_in_by_entries_and_is_correct(traced):
    assert traced["correct"] is True, traced["checks"]
    assert traced["failed"] == 0 and traced["notes"]["sampled"] == 32
    assert set(traced["checks"]) == {
        "misrouted", "window_compiles", "state_errors",
        f"logprob_mean_{MODEL}", f"logprob_carry_{MODEL}"}
    assert traced["checks"]["state_errors"]["value"] == 0
    for name in ("mean", "carry"):
        c = traced["checks"][f"logprob_{name}_{MODEL}"]
        assert 0.0 < c["value"] < c["limit"] / 2, (name, c)
    assert not DEVICE_METRICS & set(traced["metrics"])


def test_the_heads_metrics_read_the_batch_trace(traced):
    m = traced["metrics"]
    assert m["head_tokens_per_s"]["value"] > 0
    assert m["head_pool_ms"]["value"] > 0
    assert m["moe_load_ratio"]["value"] >= 1.0
    # one position an iteration: D iterations a round, and nothing drafted
    assert m["head_decode_iters"]["value"] == 3
    assert "mtp_accept_pct" not in m
    # 2 of 16 experts held, half of one of 4 groups of which 2 are kept:
    # 12.5 and 50 under an even router; this one's tokens are alike
    assert 0.0 <= m["moe_held_pair_pct"]["value"] <= 100.0 * 2 / 3
    assert m["moe_held_pair_pct"]["value"] <= m["moe_group_hit_pct"]["value"] \
        * 2 / 3 + 1e-9
    assert 0.0 <= m["moe_group_hit_pct"]["value"] <= 100.0


def test_the_new_metrics_are_the_counters_ratios_and_silent_without_them():
    """6 of 96 routed pairs on the held experts, 12 of 32 tokens with the
    held group kept; on a cell whose head does not count them, and on the
    parent commit, the readers find nothing to read and return None."""
    cell = {"config": {"num_experts_per_tok": 3}}
    base = {"batch": (1, 0), "tick": 1, "t_emitted": 1.0,
            "device_id": "clip000", "moe_pairs_local": 6}
    counted = dict(base, moe_pairs_total=96, moe_group_hits=12)
    held = loader.layer_metric("moe_held_pair_pct")
    hit = loader.layer_metric("moe_group_hit_pct")
    ctx = {"stage": [counted], "seconds": 2.0, "cell": cell}
    assert held.read(ctx) == pytest.approx(6.25)
    assert hit.read(ctx) == pytest.approx(37.5)
    for stage in ([base], []):
        ctx = {"stage": stage, "seconds": 2.0, "cell": cell}
        assert held.read(ctx) is None and hit.read(ctx) is None
    assert hit.read({"stage": [counted], "seconds": 2.0,
                     "cell": {"config": {}}}) is None


# -- planted faults -----------------------------------------------------------

def _no_group_limit(monkeypatch):
    """The plain top-k of all experts: every group is kept."""
    import jax.numpy as jnp

    from video_edge_ai_proxy_tpu.models import transformer

    monkeypatch.setattr(
        transformer, "kept_groups",
        lambda pick, cfg: jnp.ones((pick.shape[0], cfg.n_group), bool))


def _scaling_left_out(monkeypatch):
    """``routed_scaling_factor`` (x 16) left out."""
    from video_edge_ai_proxy_tpu.models import transformer

    real = transformer.topk_route

    def unscaled(scores, bias, cfg):
        sel, w = real(scores, bias, cfg)
        return sel, w / cfg.routed_scaling_factor

    monkeypatch.setattr(transformer, "topk_route", unscaled)


def _drop_shared_experts(monkeypatch):
    """The routed experts alone: the shared experts' output is left out."""
    import flax.linen as nn
    import jax

    from video_edge_ai_proxy_tpu.models import transformer

    real = transformer.TopKMoeMlp.routed

    def routed_only(self, x):
        y, *counts = real(self, x)
        w1, w3, w2 = (nn.meta.unbox(self.get_variable("params", name)).astype(
            x.dtype) for name in ("shared_w1", "shared_w3", "shared_w2"))
        return (y - (jax.nn.silu(x @ w1) * (x @ w3)) @ w2, *counts)

    monkeypatch.setattr(transformer.TopKMoeMlp, "routed", routed_only)


_HELD_HEADS = {16: 2, 512: 32}     # by kv_lora_rank: the twin, the cell


def _wrong_heads_slice(monkeypatch):
    """The first held head's slice of ``W_kvb`` is another head's (the
    program's alone: the reference keeps the weights as drawn), in every
    block."""
    from vbench import weights

    real = weights.as_variables

    def swapped(flat, template):
        flat = dict(flat)
        for name in [n for n in flat if n.endswith("attn/kv_b")]:
            w = flat[name]
            per = w.shape[1] // _HELD_HEADS[w.shape[0]]
            flat[name] = w.at[:, :per].set(w[:, per:2 * per])
        return real(flat, template)

    monkeypatch.setattr(weights, "as_variables", swapped)


def _lose_latent_carry(monkeypatch):
    """The round's latent rows never reach the pool (the flush at the
    round's end is lost)."""
    from video_edge_ai_proxy_tpu.models import deepseek_v2

    monkeypatch.setattr(deepseek_v2, "flush_round",
                        lambda pool, rbuf, slots, pos0, keep, main: pool)


FAULTS = [
    (_no_group_limit, f"logprob_mean_{MODEL}"),
    (_scaling_left_out, f"logprob_mean_{MODEL}"),
    (_drop_shared_experts, f"logprob_mean_{MODEL}"),
    (_wrong_heads_slice, f"logprob_mean_{MODEL}"),
    (_lose_latent_carry, f"logprob_carry_{MODEL}")]


@pytest.mark.parametrize("fault,number", FAULTS)
def test_a_broken_head_is_not_correct(fault, number, monkeypatch):
    fault(monkeypatch)
    out = _run(2**31 + 94)
    assert out["correct"] is False, out["checks"]
    c = out["checks"][number]
    assert c["value"] > 2 * c["limit"], out["checks"]
    assert out["checks"]["misrouted"]["value"] == 0


# -- the family's answers -----------------------------------------------------

def test_param_spec_is_the_programs_tree_and_an_edited_file_is_refused():
    import jax

    from video_edge_ai_proxy_tpu.models import registry

    for path, model in ((os.path.join(DATA, "tiny_dsv2.json"), MODEL),
                        (os.path.join(loader.HERE, "configs",
                                      "deepseek_v2_stream.json"),
                         "videomae_b_dsv2")):
        m = _model(path)
        fam, s = loader.family(m["family"]), m["sizes"]
        base = registry.get(model)
        module = base.build()
        assert fam.check_sizes(module, s) == {}
        leaves = jax.tree_util.tree_flatten_with_path(
            fam.template(base, module),
            is_leaf=lambda x: hasattr(x, "unbox"))[0]
        program = {"/".join(getattr(k, "key", str(k)) for k in path[1:]):
                   tuple((leaf.unbox() if hasattr(leaf, "unbox")
                          else leaf).shape) for path, leaf in leaves}
        spec = {name: tuple(shape) for name, shape, _, _ in fam.param_spec(s)}
        assert spec == program
        assert not [n for n in spec if "expert_bias" in n]      # no bias
        for key, other in (("hidden_size", 1024), ("kv_lora_rank", 256),
                           ("n_group", 1), ("topk_group", 1),
                           ("routed_scaling_factor", 1),
                           ("norm_topk_prob", True),
                           ("scoring_func", "sigmoid"),
                           ("heads_total", 64),
                           ("heads_held", list(s["heads_held"])[::-1]),
                           ("experts_held", [e + 1 for e in
                                             s["experts_held"]]),
                           ("n_shared_experts", 1),
                           ("instruction_ids", [1] * len(
                               s["instruction_ids"]))):
            assert key in fam.check_sizes(module, dict(s, **{key: other}))
        assert "rope_scaling.mscale" in fam.check_sizes(module, dict(
            s, rope_scaling=dict(s["rope_scaling"], mscale=1)))
        # a registry model of another family is refused too
        other = registry.get("tiny_videomae_xing4").build()
        with pytest.raises(AttributeError):
            fam.check_sizes(other, s)


def test_sizes_of_the_cell_are_the_published_widths():
    path = os.path.join(loader.HERE, "configs", "deepseek_v2_stream.json")
    m = _model(path)
    s = m["sizes"]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "DeepSeek-V2")
    reduced = {"num_hidden_layers": 5, "n_routed_experts": 10,
               "num_attention_heads": 32, "vocab_size": 12800,
               "max_position_embeddings": 4096}
    with open(path) as f:
        whole = json.load(f)
    assert whole["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert whole[key] == reduced.get(key, value), key
    assert whole["published"] == {k: row["config"][k] for k in reduced}
    assert set(whole["reduced_why"]) == set(reduced) | {"engine"}
    bench = loader.benchmark()
    entry = next(c for c in bench["configs"]
                 if c["name"] == "deepseek_v2_stream")
    assert set(entry["reduced"]) == set(whole["reduced_why"])
    xing = _model(os.path.join(loader.HERE, "configs", "xing4_stream.json"))
    assert whole["engine"] == json.load(open(os.path.join(
        loader.HERE, "configs", "xing4_stream.json")))["engine"]
    assert xing["sizes"]["kv_lora_rank"] == s["kv_lora_rank"] == 512
    assert (s["num_routed_experts"], s["experts_held"]) == (
        160, list(range(10)))
    assert (s["heads_total"], s["heads_held"]) == (128, list(range(32)))
    assert len(s["instruction_ids"]) == 32
    assert max(s["instruction_ids"]) < s["vocab_size"]
    fam = loader.family(m["family"])
    n = sum(int(np.prod(shape)) for _, shape, _, _ in fam.param_spec(s))
    # ISSUE 36's sum: encoder + connector 116.3M, embedding + head 131.1M,
    # dense block 234.1M, four routed blocks 1,317.3M (biases and norms
    # beside them)
    assert 1.797e9 < n < 1.802e9
    assert fam.max_rounds(s) == 5 and fam.mean_context(s) == 32 + 792 * 2.5
    # the cell: the existing traffic file, one chip, and every metric it
    # reports names it
    cell = loader.cell("dsv2_64_360p")
    assert cell["workload"]["traffic"] == "clip64_360p_5fps"
    assert cell["workload"]["chips"] == 1
    names = {m["name"] for m in cell["per_layer"]}
    assert {"moe_held_pair_pct", "moe_group_hit_pct", "moe_load_ratio",
            "head_decode_iters", "head_tokens_per_s", "head_pool_ms",
            "step_mfu_pct"} <= names
    assert "mtp_accept_pct" not in names and len(names) == 26


def test_sample_flops_is_the_hand_count_at_the_tiny_size():
    m = _model(os.path.join(DATA, "tiny_dsv2.json"))
    fam, s = loader.family(m["family"]), m["sizes"]
    from vbench import flops

    from families import _encoder

    enc = dict(s["encoder"], num_labels=1)
    v, steps, d = 32, 3, 32                      # visual tokens, D, C
    front = (4 * flops.resize_flops(80, 120, 32) + 2 * v * (2 * 8 * 8 * 3) * 64
             + _encoder.encoder_flops(v, enc) + 2 * v * (64 * d + d * d))
    ctx = 4 + 35 * 1.5 + 17.5                    # 4 rounds of 35 a context
    before = ctx - 17.5
    # two held heads: q_a, q_b (2 x 16), kv_a, o (2 x 8 rows)
    project = 2 * (d * 24 + 24 * 2 * 16 + d * 24 + 2 * 8 * d)
    up = 2 * 16 * 2 * 16
    dense = 3 * 2 * d * 80
    # router over 16, two shared experts, 3 x 2 / 16 of a held expert
    routed = 2 * d * 16 + (3 * 2 / 16 + 2) * 3 * 2 * d * 24
    plain, latent = 2 * 2 * 24 * ctx, 2 * 2 * 40 * ctx
    prefill = 3 * (project + up + plain + before * up / v) + dense + 2 * routed
    decode = 3 * (project + up + latent) + dense + 2 * routed + 2 * d * 96
    assert fam.sample_flops(s, 80, 120) == int(
        front + v * prefill + steps * decode)
    # the held share only: with all four heads it would be more
    assert fam.sample_flops(dict(s, heads_held=[0, 1, 2, 3]), 80, 120) \
        > fam.sample_flops(s, 80, 120)


def test_the_window_and_what_is_compared_are_the_first_heads():
    m = _model(os.path.join(DATA, "tiny_dsv2.json"))
    fam, s = loader.family(m["family"]), m["sizes"]
    assert (fam.sample_frames(s), fam.max_rounds(s)) == (4, 4)
    assert fam.expected_state("clip001", 1, s) == (1, 4 + 35)
    reads = list(range(10, 30))
    rounds, positions = fam.expected_state("clip001", 2, s)
    kept = {"tokens": list(range(rounds * 3)), "rounds": rounds,
            "positions": positions, "steps": []}
    w = fam.window({"device_id": "clip001", "packet": reads[4],
                    "kept": kept}, reads, s)
    assert kept["state_ok"] is True and w.rounds == rounds
    assert list(w) == reads[2 - rounds:5]
    # what the control would serve: the D tokens' rows, no draft
    row = np.random.default_rng(0).normal(size=(3, 96))
    served = fam.as_served(row)
    assert len(served["steps"]) == 3 and "draft" not in served
    same = fam.compare([served], [row], MODEL)
    assert same[f"logprob_mean_{MODEL}"] < 1e-9
    assert set(same) == {f"logprob_err_{MODEL}", f"logprob_mean_{MODEL}",
                         f"logprob_med_{MODEL}", "state_errors"}
