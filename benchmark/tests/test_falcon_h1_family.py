"""The fourth streaming head's family (``families/falcon_h1_stream.py``) and
its plain reference: the tiny configuration comes in by files under
``data/`` and entries alone; the CPU rehearsal of its cell is ``correct``;
with the state-space state not carried, the round's keys and values not
flushed, the mixer's output multiplier taken as 1 or its gated norm's order
flipped it is not; and the scan's counted operations and bytes are no more
than what XLA compiles. Counts and comparisons only, never a time."""

import json
import os

import numpy as np
import pytest

import run as vrun
from test_run_rehearsal import DEVICE_METRICS, bench_with
from vbench import loader

DATA = os.path.join(os.path.dirname(__file__), "data")
MODEL = "tiny_videomae_b_falcon_h1"
SOURCE = ("https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/"
          "config.json")
# the published config.json, every key the configuration file carries
PUBLISHED = {
    "attention_bias": False,
    "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375,
    "attn_layer_indices": None,
    "embedding_multiplier": 5.656854249492381,
    "head_dim": 128,
    "hidden_act": "silu",
    "hidden_size": 5120,
    "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804,
    "lm_head_multiplier": 0.0078125,
    "mamba_chunk_size": 128,
    "mamba_conv_bias": True,
    "mamba_d_conv": 4,
    "mamba_d_head": 128,
    "mamba_d_ssm": 4096,
    "mamba_d_state": 256,
    "mamba_expand": 2,
    "mamba_n_groups": 2,
    "mamba_n_heads": 32,
    "mamba_norm_before_gate": False,
    "mamba_proj_bias": False,
    "mamba_rms_norm": True,
    "mamba_use_mlp": True,
    "max_position_embeddings": 262144,
    "mlp_bias": False,
    "mlp_expansion_factor": 8,
    "mlp_multipliers": [
        0.1767766952966369,
        0.011160714285714284
    ],
    "model_type": "falcon_h1",
    "num_attention_heads": 20,
    "num_hidden_layers": 72,
    "num_key_value_heads": 4,
    "num_logits_to_keep": 1,
    "projectors_bias": False,
    "rms_norm_eps": 1e-05,
    "rope_scaling": None,
    "rope_theta": 100000000000,
    "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [
        0.3535533905932738,
        0.25,
        0.1767766952966369,
        0.5,
        0.3535533905932738
    ],
    "ssm_out_multiplier": 0.08838834764831845,
    "tie_word_embeddings": False,
    "vocab_size": 261120
}


def falcon_bench():
    return bench_with("tiny_falcon_h1", "tiny_falcon_h1.free",
                      "tiny_stream_free")


def _model(path):
    with open(path) as f:
        return loader.models(json.load(f))[0]


def _run(seed, trace=False):
    return vrun.run("tiny_falcon_h1.free", seed, 3.0, trace,
                    require_chip=False, bench=falcon_bench())


@pytest.fixture(scope="module")
def traced():
    return _run(2**31 + 95, trace=True)


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
    # no experts, no drafts: their readers find nothing
    for name in ("moe_load_ratio", "moe_pair_rows_pct", "mtp_accept_pct",
                 "head_decode_iters"):
        assert name not in m
    # without a device trace the new readers have nothing to read
    assert "head_ssm_ms" not in m and "ssm_scan_roofline_pct" not in m


def _ctx(paths_ms, streams=64):
    """A run's context with one tick of ``streams`` results whose stage
    map gave ``paths_ms``: {scope path: device ms}."""
    from vbench import stage_trace

    m = _model(os.path.join(loader.HERE, "configs", "falcon_h1_stream.json"))
    stage = [{"batch": (1, 0), "tick": 1, "t_emitted": 1.0,
              "device_id": f"clip{i:03d}"} for i in range(streams)]
    ctx = {"stage": stage, "seconds": 2.0, "model_of": {"m": m},
           "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    ctx[stage_trace._KEY] = [{k: v / 1e3 for k, v in paths_ms.items()}]
    return ctx, m


def test_the_new_metrics_read_the_scopes_and_are_silent_without_them():
    """``head_ssm_ms`` is the prefill's mixer outside the decode loop;
    ``ssm_scan_roofline_pct`` the scan's least time from its counts over
    its device time: at 64 streams the prefill and the decode steps move
    28.9 GB (35.3 ms at 819 GB/s), more than their 625 GFLOP take at the
    bf16 peak (3.2 ms). A program without the scopes (an older build, a
    head without a mixer) reads None."""
    ssm = loader.layer_metric("head_ssm_ms")
    roof = loader.layer_metric("ssm_scan_roofline_pct")
    ctx, m = _ctx({("head_prefill", "head_ssm"): 40.0,
                   ("head_prefill", "head_ssm", "ssm_scan"): 50.0,
                   ("head_decode", "head_ssm", "ssm_scan"): 20.0,
                   ("head_decode", "head_ssm"): 5.0,
                   ("head_prefill", "head_attn"): 99.0})
    assert ssm.read(ctx) == pytest.approx(90.0)
    fam = loader.family(m["family"])
    least = 64 * fam.ssm_scan_bytes(m["sizes"], 784) / 819e9
    assert least == pytest.approx(0.0353, abs=1e-4)
    assert 64 * fam.ssm_scan_ops(m["sizes"], 784) / 197e12 < least / 10
    assert roof.read(ctx) == pytest.approx(100.0 * least / 0.070)
    ctx, _ = _ctx({("head_prefill", "head_attn"): 99.0})
    assert ssm.read(ctx) is None and roof.read(ctx) is None
    assert roof.read(dict(ctx, peak=None)) is None


# -- planted faults -----------------------------------------------------------

def _ssm_state_not_written(monkeypatch):
    """The round's state-space state never reaches the pool: the slot
    keeps what it held before the round."""
    from video_edge_ai_proxy_tpu.models import falcon_h1

    real = falcon_h1.VideoMAEFalconH1.commit_round

    def keep(self, state, kv, rows, rbuf, slots, pos0):
        return dict(real(self, state, kv, rows, rbuf, slots, pos0),
                    ssm=state["ssm"])

    monkeypatch.setattr(falcon_h1.VideoMAEFalconH1, "commit_round", keep)


def _kv_not_flushed(monkeypatch):
    """The round's keys and values never reach the pool."""
    from video_edge_ai_proxy_tpu.models import falcon_h1

    monkeypatch.setattr(falcon_h1, "flush_round",
                        lambda pool, rbuf, slots, pos0: pool)


def _ssm_out_multiplier_one(monkeypatch):
    """The mixer's output taken without ``ssm_out_multiplier`` (in the
    program alone: the configuration still states it)."""
    from video_edge_ai_proxy_tpu.models import falcon_h1

    real = falcon_h1.Mamba2Mixer.__call__

    def unscaled(self, n, conv, ssm):
        m, conv, ssm = real(self, n, conv, ssm)
        return m / self.cfg.ssm_out_multiplier, conv, ssm

    monkeypatch.setattr(falcon_h1.Mamba2Mixer, "__call__", unscaled)


def _norm_before_gate_flipped(monkeypatch):
    """The gated norm normalises before it gates."""
    from video_edge_ai_proxy_tpu.models import falcon_h1

    real = falcon_h1.gated_norm
    monkeypatch.setattr(
        falcon_h1, "gated_norm",
        lambda y, z, scale, groups, eps, before: real(
            y, z, scale, groups, eps, not before))


FAULTS = [
    (_ssm_state_not_written, f"logprob_carry_{MODEL}"),
    (_kv_not_flushed, f"logprob_carry_{MODEL}"),
    (_ssm_out_multiplier_one, f"logprob_mean_{MODEL}"),
    (_norm_before_gate_flipped, f"logprob_mean_{MODEL}")]


@pytest.mark.parametrize("fault,number", FAULTS,
                         ids=["ssm_not_written", "kv_not_flushed",
                              "ssm_out_one", "norm_before_gate"])
def test_a_broken_head_is_not_correct(fault, number, monkeypatch):
    fault(monkeypatch)
    out = _run(2**31 + 96)
    assert out["correct"] is False, out["checks"]
    c = out["checks"][number]
    assert c["value"] > 2 * c["limit"], out["checks"]
    assert out["checks"]["misrouted"]["value"] == 0


# -- the family's answers -----------------------------------------------------

def test_param_spec_is_the_programs_tree_and_an_edited_file_is_refused():
    import jax

    from video_edge_ai_proxy_tpu.models import registry

    for path, model in ((os.path.join(DATA, "tiny_falcon_h1.json"), MODEL),
                        (os.path.join(loader.HERE, "configs",
                                      "falcon_h1_stream.json"),
                         "videomae_b_falcon_h1")):
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
        for key, other in (("hidden_size", 1024), ("mamba_d_state", 128),
                           ("mamba_n_groups", 3), ("mamba_n_heads", 8),
                           ("mamba_chunk_size", 256),
                           ("mamba_norm_before_gate", True),
                           ("ssm_out_multiplier", 1.0),
                           ("key_multiplier", 1.0),
                           ("ssm_multipliers", [1.0] * 5),
                           ("intermediate_held", 7),
                           ("rope_theta", 10000),
                           ("instruction_ids", [1] * len(
                               s["instruction_ids"]))):
            assert key in fam.check_sizes(module, dict(s, **{key: other}))
        # a registry model of another family is refused too
        other = registry.get("tiny_videomae_dsv2").build()
        with pytest.raises(AttributeError):
            fam.check_sizes(other, s)


def test_sizes_of_the_cell_are_the_published_widths():
    path = os.path.join(loader.HERE, "configs", "falcon_h1_stream.json")
    m = _model(path)
    s = m["sizes"]
    reduced = {"num_hidden_layers": 4, "num_attention_heads": 10,
               "num_key_value_heads": 2, "mamba_n_heads": 16,
               "mamba_n_groups": 1, "vocab_size": 32640,
               "max_position_embeddings": 4096}
    with open(path) as f:
        whole = json.load(f)
    assert whole["source"] == SOURCE
    for key, value in PUBLISHED.items():
        assert whole[key] == reduced.get(key, value), key
    assert whole["published"] == {k: PUBLISHED[k] for k in reduced}
    assert set(whole["reduced_why"]) == set(reduced) | {"engine"}
    bench = loader.benchmark()
    entry = next(c for c in bench["configs"]
                 if c["name"] == "falcon_h1_stream")
    assert set(entry["reduced"]) == set(whole["reduced_why"])
    # the share: half the heads, one of two groups, half the columns
    assert (s["heads_total"], s["kv_heads_total"]) == (20, 4)
    assert (s["mamba_heads_total"], s["mamba_groups_total"]) == (32, 2)
    assert s["intermediate_held"] * 2 == s["intermediate_size"] == 21504
    assert s["mamba_n_heads"] * s["mamba_d_head"] * 2 == s["mamba_d_ssm"]
    assert len(s["instruction_ids"]) == 32
    assert max(s["instruction_ids"]) < s["vocab_size"]
    fam = loader.family(m["family"])
    n = sum(int(np.prod(shape)) for _, shape, _, _ in fam.param_spec(s))
    # encoder 86.2M, connector 30.2M, embedding and head 2 x 167.1M, four
    # layers of 215.07M (their norms and biases beside them)
    assert 1.310e9 < n < 1.312e9
    assert fam.max_rounds(s) == 5
    # the cell: the existing traffic file, one chip, and every metric it
    # reports names it
    cell = loader.cell("falconh1_64_360p")
    assert cell["workload"]["traffic"] == "clip64_360p_5fps"
    assert cell["workload"]["chips"] == 1
    names = {m["name"] for m in cell["per_layer"]}
    assert {"head_ssm_ms", "ssm_scan_roofline_pct", "head_tokens_per_s",
            "head_pool_ms", "head_prefill_attn_ms", "head_decode_ms",
            "head_flush_ms", "step_mfu_pct"} <= names
    assert not {n for n in names if n.startswith("moe_")}
    assert not names & {"head_moe_ms", "mtp_accept_pct", "head_decode_iters",
                        "attn_live_block_pct", "mhc_maps_ms",
                        "stage_window_ms"}
    assert len(names) == 31


def test_the_scans_counts_are_no_more_than_what_xla_compiles():
    """``ssm_scan_ops`` and ``ssm_scan_bytes`` of the twin's round against
    ``cost_analysis()`` of the chunked scan and the decode steps compiled at
    the twin's shapes (one stream, one layer): the operations counted are
    the compiled ones less the elementwise work XLA counts besides (at
    least 60% of them), and the bytes no more than it counts (XLA counts
    every fusion's operands, so the least bytes are fewer)."""
    import functools

    import jax
    import jax.numpy as jnp

    from video_edge_ai_proxy_tpu.ops import ssd

    m = _model(os.path.join(DATA, "tiny_falcon_h1.json"))
    s = dict(m["sizes"], num_hidden_layers=1)
    fam = loader.family(m["family"])
    t = fam._visual_tokens(s)
    h, p, n, g = (s["mamba_n_heads"], s["mamba_d_head"], s["mamba_d_state"],
                  s["mamba_n_groups"])
    f = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    scan = jax.jit(functools.partial(ssd.ssd_scan, chunk=s[
        "mamba_chunk_size"])).lower(
        f(1, t, h, p), f(1, t, h), f(h), f(1, t, g, n), f(1, t, g, n), f(h),
        f(1, h, p, n)).compile().cost_analysis()
    step = jax.jit(ssd.ssd_step).lower(
        f(1, h, p), f(1, h), f(h), f(1, g, n), f(1, g, n), f(h),
        f(1, h, p, n)).compile().cost_analysis()
    scan, step = (c[0] if isinstance(c, list) else c for c in (scan, step))
    steps = s["decode_steps"]
    xla_ops = scan["flops"] + steps * step["flops"]
    xla_bytes = scan["bytes accessed"] + steps * step["bytes accessed"]
    ops, nbytes = fam.ssm_scan_ops(s, t), fam.ssm_scan_bytes(s, t)
    assert 0.6 * xla_ops <= ops <= xla_ops, (ops, xla_ops)
    assert nbytes <= xla_bytes, (nbytes, xla_bytes)


def test_the_window_and_what_is_compared_are_the_first_heads():
    m = _model(os.path.join(DATA, "tiny_falcon_h1.json"))
    fam, s = loader.family(m["family"]), m["sizes"]
    assert (fam.sample_frames(s), fam.max_rounds(s)) == (4, 4)
    assert fam.expected_state("clip001", 1, s) == (1, 5 + 53)
    reads = list(range(10, 30))
    rounds, positions = fam.expected_state("clip001", 2, s)
    kept = {"tokens": list(range(rounds * 3)), "rounds": rounds,
            "positions": positions, "steps": []}
    w = fam.window({"device_id": "clip001", "packet": reads[4],
                    "kept": kept}, reads, s)
    assert kept["state_ok"] is True and w.rounds == rounds
    row = np.random.default_rng(0).normal(size=(3, 96))
    served = fam.as_served(row)
    same = fam.compare([served], [row], MODEL)
    assert same[f"logprob_mean_{MODEL}"] < 1e-9
