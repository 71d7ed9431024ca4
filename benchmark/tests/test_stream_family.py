"""The streaming head's family (``families/lfm2_stream.py``) and its plain
reference: the tiny configuration comes in by files under ``data/`` and
entries alone, as the toy family does; the CPU rehearsal of its cell is
``correct``; and with the state or the routing broken underneath it is
not. Counts and comparisons only, never a time."""

import json
import os

import numpy as np
import pytest

import control
import run as vrun
from test_run_rehearsal import DEVICE_METRICS, bench_with
from vbench import loader

DATA = os.path.join(os.path.dirname(__file__), "data")
MODEL = "tiny_videomae_lfm2"


def stream_bench():
    return bench_with("tiny_stream", "tiny_stream.free", "tiny_stream_free")


def _model(path):
    with open(path) as f:
        return loader.models(json.load(f))[0]


def _run(seed, trace=False):
    return vrun.run("tiny_stream.free", seed, 3.0, trace, require_chip=False,
                    bench=stream_bench())


@pytest.fixture(scope="module")
def traced():
    return _run(2**31 + 91, trace=True)


def test_the_cell_comes_in_by_entries_and_is_correct(traced):
    assert traced["correct"] is True, traced["checks"]
    assert traced["failed"] == 0 and traced["notes"]["sampled"] == 32
    assert set(traced["checks"]) == {
        "misrouted", "window_compiles", "state_errors",
        f"logprob_mean_{MODEL}", f"logprob_carry_{MODEL}"}
    assert traced["checks"]["state_errors"]["value"] == 0
    assert 0.0 < traced["checks"][f"logprob_mean_{MODEL}"]["value"] < 0.02
    assert 0.0 < traced["checks"][f"logprob_carry_{MODEL}"]["value"] < 0.002
    assert {f"logprob_err_{MODEL}", f"logprob_med_{MODEL}"} <= set(
        traced["notes"]["unjudged"])
    assert not DEVICE_METRICS & set(traced["metrics"])


def test_the_heads_metrics_read_the_batch_trace(traced):
    m = traced["metrics"]
    # 3 cameras x (32 visual tokens + 3 decoded) a round
    assert m["head_tokens_per_s"]["value"] > 0
    assert m["head_pool_ms"]["value"] > 0
    assert m["moe_load_ratio"]["value"] >= 1.0


def test_the_heads_metrics_are_silent_where_the_program_has_no_head():
    """On a cell without the head (as on the parent commit) the three
    readers find nothing to read and return None; they do not raise."""
    stage = [{"batch": (1, 0), "tick": 1, "t_emitted": 1.0,
              "device_id": "clip000"}]
    for name in ("head_tokens_per_s", "head_pool_ms", "moe_load_ratio"):
        reader = loader.layer_metric(name)
        assert reader.read({"stage": stage, "seconds": 2.0}) is None
        assert reader.read({"stage": [], "seconds": 2.0}) is None


def _zero_conv_state(monkeypatch):
    """The conv state is zeroed between rounds (the pool forgets it)."""
    from video_edge_ai_proxy_tpu.engine import stream_state

    real = stream_state.StreamStatePool.plan

    def forgetful(self, *a, **kw):
        out = real(self, *a, **kw)
        self.state["conv"] = self.state["conv"] * 0
        return out

    monkeypatch.setattr(stream_state.StreamStatePool, "plan", forgetful)


def _lose_kv_carry(monkeypatch):
    """The round's keys and values never reach the pool (the flush at the
    round's end is lost): a later round attends to what the slot held
    before."""
    from video_edge_ai_proxy_tpu.models import lfm2

    monkeypatch.setattr(lfm2, "flush_round",
                        lambda pool, rbuf, slots, pos0: tuple(pool))


def _drop_expert_bias(monkeypatch):
    """The router picks its experts without the published bias."""
    from video_edge_ai_proxy_tpu.models import transformer

    real = transformer.topk_route
    monkeypatch.setattr(transformer, "topk_route",
                        lambda scores, bias, cfg: real(scores, None, cfg))


def _never_reset(monkeypatch):
    """A stream's first context is not cut: a fleet-wide sawtooth, and the
    state the results name is not what the policy gives."""
    from video_edge_ai_proxy_tpu.engine import stream_state

    monkeypatch.setattr(stream_state, "first_context_rounds",
                        lambda device_id, mod: 10**6)


@pytest.mark.parametrize("fault,number", [
    (_zero_conv_state, f"logprob_carry_{MODEL}"),
    (_lose_kv_carry, f"logprob_carry_{MODEL}"),
    (_drop_expert_bias, f"logprob_mean_{MODEL}"),
    (_never_reset, "state_errors")])
def test_a_broken_head_is_not_correct(fault, number, monkeypatch):
    fault(monkeypatch)
    out = _run(2**31 + 92)
    assert out["correct"] is False, out["checks"]
    c = out["checks"][number]
    assert c["value"] > 2 * c["limit"], out["checks"]
    assert out["checks"]["misrouted"]["value"] == 0


def test_the_window_is_every_read_since_the_reset_and_carries_the_tokens():
    m = _model(os.path.join(DATA, "tiny_stream.json"))
    fam = loader.family("lfm2_stream")
    sizes = m["sizes"]
    n, steps = fam.sample_frames(sizes), sizes["decode_steps"]
    assert (n, fam.max_rounds(sizes)) == (4, 4)
    reads = list(range(10, 30))
    cam = "clip001"
    first = fam.expected_state(cam, 1, sizes)
    assert first == (1, 4 + 35)
    # answers 1.. follow the policy: the first context is cut, the others
    # hold max_rounds rounds
    seen = [fam.expected_state(cam, k, sizes)[0] for k in range(1, 12)]
    cut = seen.index(1, 1)
    assert 1 <= cut <= fam.max_rounds(sizes)
    assert seen[cut:cut + 5] == [1, 2, 3, 4, 1]
    for answered in (1, cut, cut + 3):
        rounds, positions = fam.expected_state(cam, answered, sizes)
        packet = reads[answered + n - 2]
        kept = {"tokens": list(range(rounds * steps)), "rounds": rounds,
                "positions": positions, "steps": []}
        res = {"device_id": cam, "packet": packet, "kept": kept}
        w = fam.window(res, reads, sizes)
        assert list(w) == reads[answered - rounds:answered + n - 1]
        assert len(w) == n + rounds - 1 and w.rounds == rounds
        assert w.tokens == kept["tokens"] and kept["state_ok"] is True
        # a result that names another state is marked
        wrong = dict(kept, positions=positions + 1)
        fam.window(dict(res, kept=wrong), reads, sizes)
        assert wrong["state_ok"] is False
    # the control's result has only its packet: one round, fixed ids
    w = control.first_window(m, 9)
    assert list(w) == [9, 46, 83, 120] and w.rounds == 1
    assert len(w.tokens) == steps
    buf = np.zeros((1, 4, 8, 8, 3), np.uint8)
    _, rounds, tokens = fam.reference_args(buf, [w], sizes)
    assert rounds.tolist() == [1] and tokens.shape == (1, 4 * steps)
    assert tokens[0, :steps].tolist() == w.tokens


def test_the_float8_control_is_not_correct_at_the_tiny_size():
    """``control.py``'s comparison: the reference at float8 in the
    program's place, against the exact one, on the control's own windows
    (one round, fixed ids on both sides)."""
    import jax

    from vbench import correct, weights
    from vbench import traffic as traffic_mod

    m = _model(os.path.join(DATA, "tiny_stream.json"))
    fam = loader.family("lfm2_stream")
    models = {MODEL: m}
    traffic = traffic_mod.load(os.path.join(DATA, "tiny_stream_free.json"))
    cams = traffic_mod.cameras(traffic, 5)
    flat = {MODEL: weights.generate(5, m["family"], m["sizes"])}
    sample = [{"device_id": c[1], "model": MODEL,
               "window": control.first_window(m, 9 + c[0])} for c in cams]
    exact = correct.reference_rows(sample, cams, 5, models, flat,
                                   loader.reference)
    low = correct.reference_rows(sample, cams, 5, models, flat,
                                 loader.reference, quant="fp8")
    assert np.asarray(exact[0]).shape == (m["sizes"]["decode_steps"],
                                          m["sizes"]["vocab_size"])
    numbers = correct.compare([fam.as_served(r) for r in low], exact,
                              [MODEL] * len(sample), models)
    assert numbers["state_errors"] == 0
    assert numbers[f"logprob_mean_{MODEL}"] > 0.02     # the tiny limit
    same = correct.compare([fam.as_served(r) for r in exact], exact,
                           [MODEL] * len(sample), models)
    assert same[f"logprob_mean_{MODEL}"] < 1e-5
    del jax


def test_sizes_of_the_cell_are_the_published_widths():
    m = _model(os.path.join(loader.HERE, "configs", "lfm2_stream.json"))
    s = m["sizes"]
    published = {
        "hidden_size": 2048, "intermediate_size": 11776,
        "moe_intermediate_size": 1536, "num_attention_heads": 32,
        "num_key_value_heads": 8, "vocab_size": 65536, "conv_L_cache": 3,
        "norm_eps": 1e-5, "num_experts_per_tok": 4,
        "num_routed_experts": 64, "use_expert_bias": True,
        "norm_topk_prob": True, "routed_scaling_factor": 1}
    assert {k: s[k] for k in published} == published
    assert s["rope_parameters"]["rope_theta"] == 1e6
    assert len(s["instruction_ids"]) == 32 and len(s["experts_held"]) == 16
    from video_edge_ai_proxy_tpu.models import registry

    module = registry.get(m["registry_model"]).build()
    fam = loader.family(m["family"])
    assert fam.check_sizes(module, s) == {}
    spec = fam.param_spec(s)
    n = sum(int(np.prod(shape)) for _, shape, _, _ in spec)
    # 1,554M of the head + 5.8M connector + 86.2M encoder
    assert 1.64e9 < n < 1.65e9
    assert len(s["layer_types"]) == s["num_hidden_layers"] == 9
    assert fam.max_rounds(s) == 5 and fam.mean_context(s) == 32 + 792 * 2.5
    # an edited file is refused
    assert "hidden_size" in fam.check_sizes(module, dict(s, hidden_size=1024))
    assert "instruction_ids" in fam.check_sizes(
        module, dict(s, instruction_ids=[1] * 32))
