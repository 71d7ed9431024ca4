"""The readers of the per-stage device metrics (``layer_metrics/stage_*``,
``head_*_ms``, ``mhc_maps_ms``, ``stage_unscoped_pct``,
``h2d_put_call_ms``) on a hand-written ``ctx`` fragment: a batch trace
that names its programs, two device lines, and the stage maps the engine
would have left in ``obs.stages``. Nothing from a run without a device
trace, a batch trace without ``program`` or a program without a map."""

import pytest

from vbench import loader, stage_trace
from video_edge_ai_proxy_tpu.obs import stages

W = 1000.0                      # wall clock minus the trace's clock
HEAD = "videomae_b_xing4/360x640/64"
CLIP = "videomae_b/1080x1920/64"

# the optimised HLO of two tiny "programs": what their instructions'
# op_names say is all the stage map reads
HLO = {
    HEAD: """HloModule jit_stream_step, is_scheduled=true

%body (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(stream_step)/head_prefill/while/body/pre_resize/dot"}
  %fusion.2 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(stream_step)/head_prefill/while/body/enc/encoder_block/add"}
  %attn.3 = f32[8]{0} custom-call(%p), metadata={op_name="jit(stream_step)/head_prefill/while/body/head/mla_prefill/k"}
  %fusion.4 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(stream_step)/head_prefill/while/body/head/head_moe/moe_experts/dot"}
  ROOT %fusion.5 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(stream_step)/head_prefill/while/body/head/mhc_maps/mul"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %fusion.6 = f32[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(stream_step)/head_seed/scatter"}
  %while.7 = f32[8]{0} while(%a), condition=%c, body=%body, metadata={op_name="jit(stream_step)/head_prefill/while"}
  %fusion.8 = f32[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(stream_step)/while/body/head_decode/head/head_moe/dot"}
  %fusion.9 = f32[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(stream_step)/while/body/head_decode/head/mhc_maps/mul"}
  %copy.10 = f32[8]{0} copy(%a)
  ROOT %fusion.11 = f32[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(stream_step)/head_flush/scatter"}
}
""",
    CLIP: """HloModule jit_raw, is_scheduled=true

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(raw)/window_write/scatter"}
  %while.2 = f32[8]{0} while(%a), condition=%c, body=%b, metadata={op_name="jit(raw)/window_copy/while"}
  %fusion.3 = f32[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(raw)/pre_normalize/sub"}
  ROOT %fusion.4 = f32[8]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(raw)/VideoMAE/cls_head/head/dot"}
}
""",
}


class _Step:
    """What the engine registers: something that holds an executable."""

    def __init__(self, text):
        self.compiled = self
        self._text = text

    def as_text(self):
        return self._text


def _head_ops(t0):
    """One run of HEAD's program from ``t0`` (monotonic s): 100 ms."""
    ms = 1e-3
    return [("%fusion.6", t0, 2 * ms),                  # head_seed
            ("%while.7", t0 + 2 * ms, 60 * ms),         # prefill: 4 self
            ("%fusion.1", t0 + 3 * ms, 5 * ms),         # preprocess
            ("%fusion.2", t0 + 8 * ms, 20 * ms),        # encoder
            ("%attn.3", t0 + 28 * ms, 15 * ms),         # prefill attention
            ("%fusion.4", t0 + 43 * ms, 10 * ms),       # experts
            ("%fusion.5", t0 + 53 * ms, 6 * ms),        # maps, prefill
            ("%fusion.8", t0 + 62 * ms, 20 * ms),       # decode: experts
            ("%fusion.9", t0 + 82 * ms, 4 * ms),        # decode: maps
            ("%copy.10", t0 + 86 * ms, 10 * ms),        # no scope
            ("%fusion.11", t0 + 96 * ms, 4 * ms)]       # head_flush


def _clip_ops(t0):
    ms = 1e-3
    return [("%fusion.1", t0, 2 * ms), ("%while.2", t0 + 2 * ms, 10 * ms),
            ("%fusion.3", t0 + 12 * ms, 3 * ms),
            ("%fusion.4", t0 + 15 * ms, 5 * ms)]


def _records(tick, group, program, t_step0, put_call_s=0.004, n=2):
    return [{"tick": tick, "batch": (tick, group), "program": program,
             "t_step0": t_step0 + W, "t_drained": t_step0 + W + 0.2,
             "t_emitted": t_step0 + W + 0.21, "put_call_s": put_call_s}
            for _ in range(n)]


@pytest.fixture()
def ctx():
    stages.clear()
    keep = [_Step(HLO[HEAD]), _Step(HLO[CLIP])]
    stages.register(HEAD, keep[0])
    stages.register(CLIP, keep[1])
    mods, ops, stage = [], [], []
    for tick, t0 in ((1, 10.0), (2, 11.0), (3, 12.0)):
        mods.append(("jit_stream_step(5)", t0 + 0.002, 0.100))
        ops += _head_ops(t0 + 0.002)
        stage += _records(tick, 0, HEAD, t0)
    # tick 3 has a second batch, of another program
    mods.append(("jit_raw(9)", 12.3, 0.020))
    ops += _clip_ops(12.3)
    stage += _records(3, 1, CLIP, 12.25, put_call_s=0.010)
    yield {"cell": {"config": {"step_modules": ["jit_stream_step",
                                                "jit_raw"]}},
           "wall_minus_mono": W, "t_start": 9.0, "seconds": 51.0,
           "stage": stage,
           "trace": {"ops": ops, "module_events": mods}}
    stages.clear()


def _read(name, ctx):
    return loader.layer_metric(name).read(ctx)


# per tick: ticks 1 and 2 run the head's step, tick 3 that and the clip's
EXPECTED = {
    "stage_preprocess_ms": 5.0,         # 5, 5, 5 + 3
    "stage_window_ms": 0.0,             # 0, 0, 2 + 10
    "stage_encoder_ms": 20.0,           # 20, 20, 20 + 5
    "head_prefill_attn_ms": 15.0,
    "head_moe_ms": 10.0,                # the decode loop's left out
    "head_decode_ms": 24.0,
    "head_flush_ms": 6.0,               # head_seed 2 + head_flush 4
    "mhc_maps_ms": 10.0,                # prefill 6 + decode 4
    "stage_unscoped_pct": 100.0 * 30 / 320,
    "h2d_put_call_ms": 4.0,             # 4, 4, 4 + 10
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_reader_reads_its_scopes_per_tick(name, ctx):
    assert _read(name, ctx) == pytest.approx(EXPECTED[name], abs=1e-6)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_reader_reads_nothing_without_what_it_needs(name, ctx):
    bare = dict(ctx, trace=None)
    old = dict(ctx, stage=[
        {k: v for k, v in s.items() if k not in ("program", "put_call_s")}
        for s in ctx["stage"]])
    if name == "h2d_put_call_ms":       # a host stamp: no trace needed
        assert _read(name, bare) == pytest.approx(4.0)
    else:
        assert _read(name, bare) is None
    assert _read(name, old) is None
    assert _read(name, dict(ctx, stage=[])) is None


def test_the_reduction_runs_once_and_conserves(ctx, monkeypatch):
    calls = []
    real = stages.run_stage_seconds
    monkeypatch.setattr(stages, "run_stage_seconds",
                        lambda *a: calls.append(1) or real(*a))
    for name in EXPECTED:
        _read(name, ctx)
    assert calls == [1]
    ticks = stage_trace.per_tick(ctx)
    assert [sum(t.values()) for t in ticks] == pytest.approx(
        [0.100, 0.100, 0.120], abs=1e-9)
    # the loop's own time is what its body's ops leave of it
    assert ticks[0][("head_prefill",)] == pytest.approx(0.004)


def test_a_program_without_a_map_is_left_out(ctx):
    stages.clear()
    stages.register(CLIP, _Step(HLO[CLIP]))     # the head: never registered
    assert _read("head_decode_ms", ctx) == 0.0
    assert _read("stage_window_ms", ctx) == pytest.approx(12.0)
    assert len(stage_trace.per_tick(ctx)) == 1


def test_a_step_cut_at_the_windows_end_is_left_out(ctx):
    cut = dict(ctx, seconds=12.102 - ctx["t_start"])    # inside tick 3's step
    assert len(stage_trace.per_tick(cut)) == 2
    assert _read("stage_window_ms", cut) == 0.0


def test_only_the_ticks_nearest_the_median_are_reduced(ctx, monkeypatch):
    monkeypatch.setattr(stage_trace, "TICK_CAP", 2)
    assert len(stage_trace.per_tick(ctx)) == 2
    assert _read("stage_window_ms", ctx) == 0.0     # tick 3 is the outlier
