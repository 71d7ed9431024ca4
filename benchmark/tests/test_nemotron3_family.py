"""The fifth streaming head's family (``families/nemotron3_super_stream.py``)
and its plain reference: the tiny configuration comes in by files under
``data/`` and entries alone; the CPU rehearsal of its cell is ``correct``;
with the state-space state not carried, the round's keys and values not
flushed, the routed experts' products lost, the experts' activation
changed or the attention rotated it is not; the configuration file's sizes
are the registry's and the published widths; the counts the new per-layer
metrics read. Counts and comparisons only, never a time."""

import json
import os

import numpy as np
import pytest

import run as vrun
from test_run_rehearsal import DEVICE_METRICS, bench_with
from vbench import loader

DATA = os.path.join(os.path.dirname(__file__), "data")
MODEL = "tiny_videomae_nemotron3_super"
SOURCE = ("https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-"
          "BF16/blob/main/config.json")
# the published config.json, every key of it
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 4096,
    "hybrid_override_pattern": (
        "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
        "EMEMEMEMEM*EMEMEMEM*EMEMEMEME"),
    "intermediate_size": 2688, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu",
    "mamba_num_heads": 128, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376,
    "moe_shared_expert_overlap": False, "mtp_hybrid_override_pattern": "*E",
    "n_group": 1, "n_groups": 8, "n_routed_experts": 512,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 22,
    "num_hidden_layers": 88, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}
REDUCED = {"num_hidden_layers": 11, "n_routed_experts": 16,
           "mamba_num_heads": 32, "n_groups": 2, "num_attention_heads": 8,
           "num_key_value_heads": 1, "vocab_size": 16384,
           "max_position_embeddings": 4096, "num_nextn_predict_layers": 0}
CELL = os.path.join(loader.HERE, "configs", "nemotron3_super_stream.json")


def nemotron_bench():
    return bench_with("tiny_nemotron3", "tiny_nemotron3.free",
                      "tiny_stream_free")


def _model(path):
    with open(path) as f:
        return loader.models(json.load(f))[0]


def _run(seed, trace=False):
    return vrun.run("tiny_nemotron3.free", seed, 3.0, trace,
                    require_chip=False, bench=nemotron_bench())


@pytest.fixture(scope="module")
def traced():
    return _run(2**31 + 97, trace=True)


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
    """The expert counts reach the batch trace: every routed pair is
    counted (the twin's one rung moves all of them) and a quarter of them
    fall on the 4 of 16 held experts, about."""
    m = traced["metrics"]
    assert m["head_tokens_per_s"]["value"] > 0
    assert m["head_pool_ms"]["value"] > 0
    assert m["moe_pair_rows_pct"]["value"] == pytest.approx(100.0)
    assert 5.0 < m["moe_held_pair_pct"]["value"] < 60.0
    assert m["moe_load_ratio"]["value"] >= 1.0
    # one router group: every token's kept group holds the held experts
    # (the benchmark does not list this metric in the cell)
    assert m["moe_group_hit_pct"]["value"] == pytest.approx(100.0)
    for name in ("mtp_accept_pct", "head_decode_iters"):
        assert name not in m
    # without a device trace the scope readers have nothing to read
    for name in ("moe_latent_ms", "moe_layer_roofline_pct", "head_moe_ms",
                 "head_ssm_ms"):
        assert name not in m


def _ctx(paths_ms, streams=64):
    """A run's context with one tick of ``streams`` results whose stage
    map gave ``paths_ms``: {scope path: device ms}."""
    from vbench import stage_trace

    m = _model(CELL)
    stage = [{"batch": (1, 0), "tick": 1, "t_emitted": 1.0,
              "device_id": f"clip{i:03d}"} for i in range(streams)]
    ctx = {"stage": stage, "seconds": 2.0, "model_of": {"m": m},
           "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    ctx[stage_trace._KEY] = [{k: v / 1e3 for k, v in paths_ms.items()}]
    return ctx, m


def test_the_new_metrics_read_the_scopes_and_are_silent_without_them():
    """``moe_latent_ms`` is the prefill's two latent projections outside
    the decode loop; ``moe_layer_roofline_pct`` the prefill's expert layers
    against their least time: at 64 streams their 29.3 TFLOP take 148.5 ms
    at the bf16 peak, more than their 31.2 GB take at 819 GB/s (38.1 ms:
    the weights read once a chunk of 4 streams are 23.1 GB of them).
    A program without the scopes reads None."""
    latent = loader.layer_metric("moe_latent_ms")
    roof = loader.layer_metric("moe_layer_roofline_pct")
    ctx, m = _ctx({("head_prefill", "head_moe", "moe_latent"): 20.0,
                   ("head_prefill", "head_moe", "moe_shared"): 150.0,
                   ("head_prefill", "head_moe"): 30.0,
                   ("head_decode", "head_moe", "moe_latent"): 3.0,
                   ("head_prefill", "head_ssm"): 99.0})
    assert latent.read(ctx) == pytest.approx(20.0)
    fam = loader.family(m["family"])
    ops = fam.moe_layer_ops(m["sizes"], 64)
    assert ops / 1e12 == pytest.approx(29.26, abs=0.01)
    assert 64 * 784 * 5 * 116.6e6 == pytest.approx(ops, rel=1e-3)
    least = ops / 197e12
    assert fam.moe_layer_bytes(m["sizes"], 64) / 1e9 == pytest.approx(
        31.24, abs=0.01)
    assert fam.moe_layer_bytes(m["sizes"], 64) / 819e9 < least / 3
    assert roof.read(ctx) == pytest.approx(100.0 * least / 0.200)
    ctx, _ = _ctx({("head_prefill", "head_ssm"): 99.0})
    assert latent.read(ctx) is None and roof.read(ctx) is None
    ctx, _ = _ctx({("head_prefill", "head_moe"): 99.0})
    assert latent.read(ctx) is None and roof.read(ctx) is not None
    assert roof.read(dict(ctx, peak=None)) is None


# -- planted faults -----------------------------------------------------------

def _ssm_state_not_written(monkeypatch):
    """The round's state-space state never reaches the pool."""
    from video_edge_ai_proxy_tpu.models import nemotron_h

    real = nemotron_h.VideoMAENemotronH.commit_round

    def keep(self, state, kv, rows, rbuf, slots, pos0):
        return dict(real(self, state, kv, rows, rbuf, slots, pos0),
                    ssm=state["ssm"])

    monkeypatch.setattr(nemotron_h.VideoMAENemotronH, "commit_round", keep)


def _kv_not_flushed(monkeypatch):
    """The round's keys and values never reach the pool."""
    from video_edge_ai_proxy_tpu.models import nemotron_h

    monkeypatch.setattr(nemotron_h, "flush_round",
                        lambda pool, rbuf, slots, pos0: pool)


def _routed_experts_lost(monkeypatch):
    """The held experts' grouped products give nothing: W_up takes back a
    zero latent, and the layer is its shared expert alone."""
    import jax
    import jax.numpy as jnp

    def nothing(lhs, rhs, group_sizes, preferred_element_type=None, **kw):
        return jnp.zeros(lhs.shape[:1] + rhs.shape[2:],
                         preferred_element_type or lhs.dtype)

    monkeypatch.setattr(jax.lax, "ragged_dot", nothing)


def _activation_silu(monkeypatch):
    """The experts' activation silu(x)^2, a gated unit's SiLU, in place of
    the published relu(x)^2 (in the program alone)."""
    from video_edge_ai_proxy_tpu.models import transformer

    monkeypatch.setattr(transformer.nn, "relu", transformer.nn.silu)


def _attention_rotated(monkeypatch):
    """Rotary positions (theta 10,000, the config's unread rope_theta) on
    the queries and keys the published attention leaves unrotated."""
    import dataclasses

    from video_edge_ai_proxy_tpu.models import nemotron_h

    real = nemotron_h.NemotronHConfig.attn.fget
    monkeypatch.setattr(
        nemotron_h.NemotronHConfig, "attn", property(
            lambda self: dataclasses.replace(real(self), rotary=True,
                                             rope_theta=10000.0)))


FAULTS = [
    (_ssm_state_not_written, f"logprob_carry_{MODEL}"),
    (_kv_not_flushed, f"logprob_carry_{MODEL}"),
    (_routed_experts_lost, f"logprob_mean_{MODEL}"),
    (_activation_silu, f"logprob_mean_{MODEL}"),
    (_attention_rotated, f"logprob_mean_{MODEL}")]


@pytest.mark.parametrize("fault,number", FAULTS,
                         ids=["ssm_not_written", "kv_not_flushed",
                              "routed_lost", "silu_experts", "rotated"])
def test_a_broken_head_is_not_correct(fault, number, monkeypatch):
    fault(monkeypatch)
    out = _run(2**31 + 98)
    assert out["correct"] is False, out["checks"]
    c = out["checks"][number]
    assert c["value"] > 2 * c["limit"], out["checks"]
    assert out["checks"]["misrouted"]["value"] == 0


# -- the family's answers -----------------------------------------------------

def test_param_spec_is_the_programs_tree_and_an_edited_file_is_refused():
    import jax

    from video_edge_ai_proxy_tpu.models import registry

    for path, model in ((os.path.join(DATA, "tiny_nemotron3.json"), MODEL),
                        (CELL, "videomae_b_nemotron3_super")):
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
        for key, other in (("hidden_size", 1024), ("ssm_state_size", 64),
                           ("n_groups", 3), ("mamba_num_heads", 8),
                           ("chunk_size", 256), ("moe_latent_size", 512),
                           ("num_experts_per_tok", 8),
                           ("mlp_hidden_act", "silu"),
                           ("hybrid_override_pattern", "EMEMEM*EMEM"),
                           ("prefill_chunk", 16),
                           ("instruction_ids", [1] * len(
                               s["instruction_ids"]))):
            assert key in fam.check_sizes(module, dict(s, **{key: other}))
        other = registry.get("tiny_videomae_b_falcon_h1").build()
        with pytest.raises(AttributeError):
            fam.check_sizes(other, s)


def test_sizes_of_the_cell_are_the_published_widths():
    m = _model(CELL)
    s = m["sizes"]
    with open(CELL) as f:
        whole = json.load(f)
    assert whole["source"] == SOURCE
    for key, value in PUBLISHED.items():
        assert whole[key] == REDUCED.get(key, value), key
    assert whole["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert set(whole["reduced_why"]) == set(REDUCED) | {"engine"}
    entry = next(c for c in loader.benchmark()["configs"]
                 if c["name"] == "nemotron3_super_stream")
    assert entry["source"] == SOURCE
    assert set(entry["reduced"]) == set(whole["reduced_why"])
    # the share: a quarter of the mixer's heads in whole groups, a quarter
    # of the query heads on one key-value head, 16 of 512 experts
    assert (s["heads_total"], s["kv_heads_total"]) == (32, 2)
    assert (s["mamba_heads_total"], s["n_groups_total"]) == (128, 8)
    assert s["num_routed_experts"] == 512
    assert s["experts_held"] == list(range(16))
    assert s["mamba_num_heads"] * s["mamba_head_dim"] * 4 \
        == s["expand"] * s["hidden_size"]
    assert s["hybrid_override_pattern"][:11] == "MEMEMEM*EME"
    assert len(s["instruction_ids"]) == 32
    assert max(s["instruction_ids"]) < s["vocab_size"]
    fam = loader.family(m["family"])
    n = sum(int(np.prod(shape)) for _, shape, _, _ in fam.param_spec(s))
    # five expert layers of 142.6M, five mixers of 27.4M, the attention
    # 9.4M, embedding and head 134.2M, encoder 86.2M, connector 19.9M
    assert 1.100e9 < n < 1.101e9
    assert fam.max_rounds(s) == 5
    cell = loader.cell("nemotron3s_64_360p")
    assert cell["workload"]["traffic"] == "clip64_360p_5fps"
    assert cell["workload"]["chips"] == 1
    names = {m["name"] for m in cell["per_layer"]}
    assert {"moe_latent_ms", "moe_layer_roofline_pct", "head_moe_ms",
            "head_ssm_ms", "ssm_scan_roofline_pct", "moe_load_ratio",
            "moe_pair_rows_pct", "moe_held_pair_pct", "head_tokens_per_s",
            "head_pool_ms", "head_prefill_attn_ms", "head_decode_ms",
            "head_flush_ms", "step_mfu_pct"} <= names
    assert not names & {"mtp_accept_pct", "head_decode_iters",
                        "moe_group_hit_pct", "mhc_maps_ms",
                        "stage_window_ms", "attn_live_block_pct"}
    assert len(names) == 37


def test_the_expert_layers_counts_by_hand():
    """Per visual token an expert layer needs 116.6 MFLOP (the shared
    expert 88.1, W_down and W_up 16.8, the router 4.2, the held pairs
    22 x 16 / 512 x 11.0 = 7.6); the five make about half a round's
    operations; the weights read once a chunk of 4 streams."""
    m = _model(CELL)
    fam, s = loader.family(m["family"]), m["sizes"]
    assert fam._expert_layer_ops(s) / 1e6 == pytest.approx(116.62, abs=0.01)
    assert fam._held_pairs(s) == pytest.approx(0.6875)
    share = 5 * 784 * fam._expert_layer_ops(s) / fam.sample_flops(s, 360, 640)
    assert 0.49 < share < 0.53
    # a chunk's weights: twice as many chunks, twice the weight bytes
    one, two = (fam.moe_layer_bytes(dict(s, prefill_chunk=c), 64)
                for c in (8, 4))
    weights = 5 * (4 * 4096 * 512 + 2 * (2 * 4096 * 1024 + 2 * 4096 * 5376
                                         + 16 * 2 * 1024 * 2688))
    assert two - one == 8 * weights


def test_the_scans_counts_are_the_fourth_heads_at_these_sizes():
    """The scan's counts are ``falcon_h1_stream``'s, at this head's head
    width, state, groups and chunk, over its five mixer layers."""
    m = _model(CELL)
    fam, s = loader.family(m["family"]), m["sizes"]
    falcon = loader.family("falcon_h1_stream")
    same = {"mamba_chunk_size": 128, "mamba_d_head": 64,
            "mamba_d_state": 128, "mamba_n_heads": 32, "mamba_n_groups": 2,
            "decode_steps": 8, "num_hidden_layers": 5}
    assert fam.ssm_scan_ops(s, 784) == falcon._scan_counts(same, 784)[0]
    assert fam.ssm_scan_bytes(s, 784) == falcon._scan_counts(same, 784)[1]
