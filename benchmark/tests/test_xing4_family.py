"""The second streaming head's family (``families/xing4_stream.py``) and its
plain reference: the tiny configuration comes in by files under ``data/``
and entries alone; the CPU rehearsal of its cell is ``correct``; and with
the state, the residual, the expert layer or the prediction module broken
underneath it is not. Counts and comparisons only, never a time."""

import json
import os

import numpy as np
import pytest

import run as vrun
from test_run_rehearsal import DEVICE_METRICS, bench_with
from vbench import loader

DATA = os.path.join(os.path.dirname(__file__), "data")
MODEL = "tiny_videomae_xing4"


def xing_bench():
    return bench_with("tiny_xing4", "tiny_xing4.free", "tiny_stream_free")


def _model(path):
    with open(path) as f:
        return loader.models(json.load(f))[0]


def _run(seed, trace=False):
    return vrun.run("tiny_xing4.free", seed, 3.0, trace, require_chip=False,
                    bench=xing_bench())


@pytest.fixture(scope="module")
def traced():
    return _run(2**31 + 93, trace=True)


def test_the_cell_comes_in_by_entries_and_is_correct(traced):
    assert traced["correct"] is True, traced["checks"]
    assert traced["failed"] == 0 and traced["notes"]["sampled"] == 32
    assert set(traced["checks"]) == {
        "misrouted", "window_compiles", "state_errors",
        f"logprob_mean_{MODEL}", f"logprob_carry_{MODEL}",
        f"logprob_draft_{MODEL}"}
    assert traced["checks"]["state_errors"]["value"] == 0
    for name in ("mean", "carry", "draft"):
        c = traced["checks"][f"logprob_{name}_{MODEL}"]
        assert 0.0 < c["value"] < c["limit"] / 2, (name, c)
    assert not DEVICE_METRICS & set(traced["metrics"])


def test_the_heads_metrics_read_the_batch_trace(traced):
    m = traced["metrics"]
    assert m["head_tokens_per_s"]["value"] > 0
    assert m["head_pool_ms"]["value"] > 0
    assert m["moe_load_ratio"]["value"] >= 1.0
    # 3 tokens a round: 2 or 3 iterations, a draft of 96 ids rarely right
    assert 2 <= m["head_decode_iters"]["value"] <= 3
    assert 0.0 <= m["mtp_accept_pct"]["value"] < 50.0


def test_the_new_metrics_are_silent_where_the_program_does_not_draft():
    """On a cell whose head has no prediction module, and on the parent
    commit, the readers find nothing to read and return None."""
    stage = [{"batch": (1, 0), "tick": 1, "t_emitted": 1.0,
              "device_id": "clip000", "head_prefill_tokens": 96,
              "head_decode_steps": 3}]
    for name in ("mtp_accept_pct", "head_decode_iters"):
        reader = loader.layer_metric(name)
        assert reader.read({"stage": stage, "seconds": 2.0}) is None
        assert reader.read({"stage": [], "seconds": 2.0}) is None


def _lose_latent_carry(monkeypatch):
    """The round's latent rows never reach the pool (the flush at the
    round's end is lost)."""
    from video_edge_ai_proxy_tpu.models import xing4

    monkeypatch.setattr(xing4, "flush_round",
                        lambda pool, rbuf, slots, pos0, keep, main: pool)


def _drop_shared_expert(monkeypatch):
    """The routed experts alone: the shared expert's output is left out."""
    from video_edge_ai_proxy_tpu.models import transformer

    import flax.linen as nn
    import jax

    real = transformer.TopKMoeMlp.__call__

    def routed_only(self, x):
        y, load = real(self, x)
        w1, w3, w2 = (nn.meta.unbox(self.get_variable("params", name)).astype(
            x.dtype) for name in ("shared_w1", "shared_w3", "shared_w2"))
        return y - (jax.nn.silu(x @ w1) * (x @ w3)) @ w2, load

    monkeypatch.setattr(transformer.TopKMoeMlp, "__call__", routed_only)


def _scaling_one_for_two(monkeypatch):
    """``routed_scaling_factor`` taken as 1."""
    from video_edge_ai_proxy_tpu.models import transformer

    real = transformer.topk_route

    def unscaled(scores, bias, cfg):
        sel, w = real(scores, bias, cfg)
        return sel, w / cfg.routed_scaling_factor

    monkeypatch.setattr(transformer, "topk_route", unscaled)


def _no_sinkhorn(monkeypatch):
    """H_res left unnormalised: the exponentials as they are."""
    import jax.numpy as jnp

    from video_edge_ai_proxy_tpu.models import xing4

    monkeypatch.setattr(xing4, "sinkhorn", lambda logits, cfg: jnp.exp(
        jnp.clip(logits, cfg.hc_clamp_min, cfg.hc_clamp_max)))


def _swap_eh_halves(monkeypatch):
    """The module's ``eh_proj`` takes [e | h] for [h | e]."""
    from video_edge_ai_proxy_tpu.models import xing4

    real = xing4.Xing4Stack._mtp_input
    monkeypatch.setattr(xing4.Xing4Stack, "_mtp_input",
                        lambda self, h, e: real(self, e, h))


def _lose_exit_state(monkeypatch):
    """The exit state of a stream's last position is not carried to the
    next round (the pool forgets it): the module's first row of a
    continuing round is made from zeros."""
    from video_edge_ai_proxy_tpu.engine import stream_state

    real = stream_state.StreamStatePool.plan

    def forgetful(self, *a, **kw):
        out = real(self, *a, **kw)
        self.state["exit"] = self.state["exit"] * 0
        return out

    monkeypatch.setattr(stream_state.StreamStatePool, "plan", forgetful)


@pytest.mark.parametrize("fault,number", [
    (_lose_latent_carry, f"logprob_carry_{MODEL}"),
    (_drop_shared_expert, f"logprob_mean_{MODEL}"),
    (_scaling_one_for_two, f"logprob_mean_{MODEL}"),
    (_no_sinkhorn, f"logprob_mean_{MODEL}"),
    (_swap_eh_halves, f"logprob_draft_{MODEL}"),
    (_lose_exit_state, f"logprob_draft_{MODEL}")])
def test_a_broken_head_is_not_correct(fault, number, monkeypatch):
    fault(monkeypatch)
    out = _run(2**31 + 94)
    assert out["correct"] is False, out["checks"]
    c = out["checks"][number]
    assert c["value"] > 2 * c["limit"], out["checks"]
    assert out["checks"]["misrouted"]["value"] == 0


def test_param_spec_is_the_programs_tree_and_an_edited_file_is_refused():
    import jax

    from video_edge_ai_proxy_tpu.models import registry

    for path, model in ((os.path.join(DATA, "tiny_xing4.json"), MODEL),
                        (os.path.join(loader.HERE, "configs",
                                      "xing4_stream.json"),
                         "videomae_b_xing4")):
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
        for key, other in (("hidden_size", 1024), ("kv_lora_rank", 256),
                           ("hc_mult", 2), ("routed_scaling_factor", 1),
                           ("n_shared_experts", 0),
                           ("instruction_ids", [1] * len(
                               s["instruction_ids"]))):
            assert key in fam.check_sizes(module, dict(s, **{key: other}))
        assert "rope_scaling.factor" in fam.check_sizes(module, dict(
            s, rope_scaling=dict(s["rope_scaling"], factor=32)))
        # a registry model of another family is refused too
        other = registry.get("tiny_videomae_lfm2").build()
        with pytest.raises(AttributeError):
            fam.check_sizes(other, s)


def test_sizes_of_the_cell_are_the_published_widths():
    m = _model(os.path.join(loader.HERE, "configs", "xing4_stream.json"))
    s = m["sizes"]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Xing4.0-29B-A4B")
    reduced = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
               "n_routed_experts": 16, "vocab_size": 32768,
               "max_position_embeddings": 4096}
    with open(os.path.join(loader.HERE, "configs",
                           "xing4_stream.json")) as f:
        whole = json.load(f)
    for key, value in row["config"].items():
        assert whole[key] == reduced.get(key, value), key
    assert whole["published"] == {k: row["config"][k] for k in reduced}
    assert set(whole["reduced_why"]) == set(reduced) | {"engine"}
    assert s["num_routed_experts"] == 64 and len(s["experts_held"]) == 16
    assert len(s["instruction_ids"]) == 32
    assert max(s["instruction_ids"]) < s["vocab_size"]
    fam = loader.family(m["family"])
    n = sum(int(np.prod(shape)) for _, shape, _, _ in fam.param_spec(s))
    # ISSUE 34's sum: encoder 86.2M + connector 15.6M + embedding and head
    # 117.4M each + dense block 128.2M + 4 routed 866.1M + module 242.2M
    assert 1.570e9 < n < 1.578e9
    assert fam.max_rounds(s) == 5 and fam.mean_context(s) == 32 + 792 * 2.5


def test_sample_flops_is_the_hand_count_at_the_tiny_size():
    m = _model(os.path.join(DATA, "tiny_xing4.json"))
    fam, s = loader.family(m["family"]), m["sizes"]
    from vbench import flops

    from families import _encoder

    enc = dict(s["encoder"], num_labels=1)
    v, steps, d = 32, 3, 32                      # visual tokens, D, C
    front = (4 * flops.resize_flops(80, 120, 32) + 2 * v * (2 * 8 * 8 * 3) * 64
             + _encoder.encoder_flops(v, enc) + 2 * v * (64 * d + d * d))
    ctx = 4 + 35 * 1.5 + 17.5                    # 4 rounds of 35 a context
    before = ctx - 17.5
    project = 2 * (d * 24 + 24 * 4 * 16 + d * 24 + 4 * 8 * d)
    up = 2 * 16 * 4 * 16
    a_map = 2 * 4 * d * 24 + 2 * 4 * d + 2 * 16 * d + 2 * 4 * d
    dense, routed = 3 * 2 * d * 80, 2 * d * 8 + (1 + 1) * 3 * 2 * d * 24
    plain, latent = 2 * 4 * 24 * ctx, 2 * 4 * 40 * ctx
    prefill = (3 * (project + up + plain + 2 * a_map + before * up / v)
               + dense + 2 * routed + 2 * 2 * d * d + a_map + 2 * d * 24)
    decode = (3 * (project + up + latent + 2 * a_map) + dense + 2 * routed
              + 2 * 2 * d * d + project + up + latent + 2 * a_map + routed
              + 2 * 2 * d * 96)
    assert fam.sample_flops(s, 80, 120) == int(
        front + v * prefill + steps * decode)


def test_the_window_and_the_state_are_the_sibling_heads():
    m = _model(os.path.join(DATA, "tiny_xing4.json"))
    fam, s = loader.family(m["family"]), m["sizes"]
    assert (fam.sample_frames(s), fam.max_rounds(s)) == (4, 4)
    assert fam.expected_state("clip001", 1, s) == (1, 4 + 35)
    reads = list(range(10, 30))
    rounds, positions = fam.expected_state("clip001", 2, s)
    kept = {"tokens": list(range(rounds * 3)), "rounds": rounds,
            "positions": positions, "steps": [], "draft": []}
    w = fam.window({"device_id": "clip001", "packet": reads[4],
                    "kept": kept}, reads, s)
    assert kept["state_ok"] is True and w.rounds == rounds
    assert list(w) == reads[2 - rounds:5]
    # what the control would serve: the D tokens' rows and the draft's
    row = np.random.default_rng(0).normal(size=(4, 96))
    served = fam.as_served(row)
    assert len(served["steps"]) == 3 and len(served["draft"]) == 5
    same = fam.compare([served], [row], MODEL)
    assert same[f"logprob_draft_{MODEL}"] < 1e-9
    assert same[f"logprob_mean_{MODEL}"] < 1e-9
    other = fam.compare([dict(served, draft=served["steps"][0])], [row],
                        MODEL)
    assert other[f"logprob_draft_{MODEL}"] > 0.1
