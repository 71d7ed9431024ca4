"""Device time by stage (obs/stages.py, ISSUE 38): the stage map of a
compiled program, the one reducer, the registry that builds nothing until
asked, and the taps that feed them (``program`` and ``put_call_s`` in the
batch trace, ``stages.json`` in a profile bundle).

CPU backend, tiny programs: names, counts and hand-written events only.
"""

import json
import os
import time

import numpy as np
import pytest

from video_edge_ai_proxy_tpu.bus.interface import FrameMeta
from video_edge_ai_proxy_tpu.bus.memory_bus import MemoryFrameBus
from video_edge_ai_proxy_tpu.engine import InferenceEngine
from video_edge_ai_proxy_tpu.obs import stages, tracer
from video_edge_ai_proxy_tpu.obs.prof import MANIFEST, STAGES, Profiler
from video_edge_ai_proxy_tpu.uplink.queue import AnnotationQueue
from video_edge_ai_proxy_tpu.utils.config import EngineConfig

H, W = 48, 64

# A step as a device line shows it: a loop that encloses its body's ops,
# an op after it, idle time between them. %fusion.1 belongs to another
# stage in each of the two programs.
LOOP = {"%while.1": ("head_prefill",),
        "%fusion.1": ("head_prefill", "head_attn"),
        "%fusion.2": ("head_prefill", "head_moe", "moe_experts"),
        "%sort.3": ("head_flush",)}
FLAT = {"%fusion.1": ("pre_resize",), "%fusion.9": ("encoder_block",)}


def _step(t0):
    """Ops of one run of LOOP's program starting at ``t0`` (10 ms)."""
    return [("%while.1", t0, 0.006),
            ("%fusion.1", t0 + 0.001, 0.002),   # inside the loop
            ("%fusion.2", t0 + 0.003, 0.0025),  # ends with... 0.5 ms early
            ("%copy.77", t0 + 0.0065, 0.001),   # unknown to the map
            ("%sort.3", t0 + 0.008, 0.002)]


def test_self_time_goes_to_the_innermost_event():
    acc, = stages.run_stage_seconds(_step(1.0), [(1.0, 1.010, LOOP)])
    assert acc[("head_prefill",)] == pytest.approx(0.0015)   # loop, self
    assert acc[("head_prefill", "head_attn")] == pytest.approx(0.002)
    assert acc[("head_prefill", "head_moe", "moe_experts")] \
        == pytest.approx(0.0025)
    assert acc[()] == pytest.approx(0.001)      # an unknown name
    assert acc[("head_flush",)] == pytest.approx(0.002)
    # conservation: the union of the op intervals, idle gaps left out
    assert sum(acc.values()) == pytest.approx(0.006 + 0.001 + 0.002,
                                              abs=1e-9)


def test_two_programs_that_share_an_op_name_are_told_apart_by_their_runs():
    flat = [("%fusion.1", 2.0, 0.004), ("%fusion.9", 2.004, 0.003)]
    ops = _step(1.0) + flat + _step(3.0)
    got = stages.stage_seconds(ops, [
        (1.0, 1.010, "lfm2/48x64/2", LOOP),
        (2.0, 2.007, "vit/48x64/2", FLAT),
        (3.0, 3.010, "lfm2/48x64/2", LOOP),
        (4.0, 4.010, "dsv2/48x64/2", None)])
    assert got["vit/48x64/2"] == {
        "runs": 1, ("pre_resize",): pytest.approx(0.004),
        ("encoder_block",): pytest.approx(0.003)}
    head = got["lfm2/48x64/2"]
    assert head["runs"] == 2
    assert head[("head_prefill", "head_attn")] == pytest.approx(0.004)
    assert ("pre_resize",) not in head
    assert got["dsv2/48x64/2"] is None          # no map: nothing guessed
    for program in ("vit/48x64/2", "lfm2/48x64/2"):
        runs = got[program].pop("runs")
        union = 0.007 if program.startswith("vit") else 0.009 * runs
        assert sum(got[program].values()) == pytest.approx(union, abs=1e-9)


def test_events_out_of_order_and_overlapping_still_conserve():
    # a child listed before its parent, and one that outlives it
    ops = [("%fusion.1", 1.001, 0.002), ("%while.1", 1.0, 0.006),
           ("%fusion.2", 1.005, 0.003)]
    acc, = stages.run_stage_seconds(ops, [(1.0, 1.010, LOOP)])
    assert sum(acc.values()) == pytest.approx(0.008, abs=1e-9)
    assert acc[("head_prefill", "head_moe", "moe_experts")] \
        == pytest.approx(0.003)


def test_calls_take_their_module_events_in_order():
    mods = [("jit_raw(7)", 1.00, 0.02), ("jit_gather(1)", 1.03, 0.001),
            ("jit_raw(9)", 1.05, 0.4), ("jit_raw(7)", 1.50, 0.02)]
    calls = [(0.99, 1.03, "tag"), (1.00, 1.46, "clip"), (1.49, None, "tag")]
    got = stages.pair_runs(mods, calls, {"tag": "jit_raw", "clip": "jit_raw",
                                         "none": "jit_other"})
    assert [(round(s, 2), p) for s, _, p in got] == [
        (1.0, "tag"), (1.05, "clip"), (1.5, "tag")]
    # an event that starts after the batch was fetched is not its run
    assert stages.pair_runs(mods[2:], [(0.9, 1.0, "tag")],
                            {"tag": "jit_raw"}) == []


def _scoped(x, w):
    import jax
    import jax.numpy as jnp

    with jax.named_scope("head_prefill"):
        def body(i, c):
            with jax.named_scope("head_attn"):
                c = jnp.tanh(c @ w)
            with jax.named_scope("head_moe"):
                with jax.named_scope("moe_experts"):
                    c = jnp.cos(c @ w) + 1.0
            with jax.named_scope("not_declared"):
                return jnp.sin(c)
        x = jax.lax.fori_loop(0, 3, body, x)
    with jax.named_scope("head_flush"):
        return jnp.sort(x, axis=-1)


def test_the_stage_map_finds_scopes_inside_a_loop_and_nested():
    import jax
    import jax.numpy as jnp

    x = jnp.ones((8, 8), jnp.float32)
    compiled = jax.jit(_scoped).lower(x, x).compile()
    paths = stages.stage_map(compiled)
    assert all(k.startswith("%") and " = " not in k for k in paths)
    assert all(set(p) <= set(stages.SCOPES) for p in paths.values())
    found = set(paths.values())
    assert {("head_prefill", "head_attn"),
            ("head_prefill", "head_moe", "moe_experts"),
            ("head_prefill",), ("head_flush",)} <= found
    # the loop itself carries the scope it was called under
    loops = {k: p for k, p in paths.items() if k.startswith("%while")}
    assert loops and set(loops.values()) == {("head_prefill",)}
    module, again = stages._parse(compiled.as_text())
    assert module == "jit__scoped" and again == paths


def test_the_latent_projections_are_a_stage_of_the_expert_layer():
    """An expert layer whose experts live in a latent (models/transformer.py
    ``TopKMoeMlp`` with ``latent_dim``), compiled inside a prefill loop:
    its two projections map to ``head_moe`` / ``moe_latent``, beside the
    route, the experts and the shared expert, and nothing of it is left
    unscoped by a name the map does not declare."""
    import jax
    import jax.numpy as jnp

    from video_edge_ai_proxy_tpu.models.transformer import (
        TopKMoeConfig, TopKMoeMlp)

    layer = TopKMoeMlp(TopKMoeConfig(
        dim=32, mlp_dim=24, num_experts=8, top_k=2, shared_mlp_dim=40,
        activation="relu2", latent_dim=16), dtype=jnp.float32)
    x = jnp.ones((6, 32), jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), x)

    def prefill(params, x):
        with jax.named_scope("head_prefill"):
            def body(i, x):
                with jax.named_scope("head_moe"):
                    return x + layer.apply(params, x)[0]
            return jax.lax.fori_loop(0, 2, body, x)

    assert "moe_latent" in stages.SCOPES
    paths = set(stages.stage_map(jax.jit(prefill).lower(params, x)
                                 .compile()).values())
    assert ("head_prefill", "head_moe", "moe_latent") in paths
    assert {("head_prefill", "head_moe", "moe_route"),
            ("head_prefill", "head_moe", "moe_shared")} <= paths


def test_what_the_compiler_made_takes_a_scope_from_its_root_or_operands():
    text = """HloModule jit_f, is_scheduled=true

%fused_computation (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %mul.1 = f32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(f)/embed/mul"}
  ROOT %add.1 = f32[8]{0} add(%mul.1, %p0), metadata={op_name="jit(f)/encoder_block/add"}
}

%scatter_body (t: (s32[], f32[8])) -> (s32[], f32[8]) {
  %t = (s32[], f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%t), index=1
  %fusion.7 = f32[8]{0} fusion(%gte.1), kind=kLoop, calls=%no_metadata
  ROOT %tuple.8 = (s32[], f32[8]{0}) tuple(%gte.1, %fusion.7)
}

ENTRY %main.1 (a: f32[8], w: (f32[8], s32[])) -> f32[8] {
  %a = f32[8]{0:T(8,128)} parameter(0), metadata={op_name="a"}
  %while.4 = (s32[], f32[8]{0}) while(%w), condition=%cond, body=%scatter_body, metadata={op_name="jit(f)/window_write/scatter"}
  %w = (f32[8]{0:T(8,128)}, s32[]{:T(128)}) parameter(1), metadata={op_name="w"}
  %copy.9 = f32[8]{0} copy(%a)
  %fusion.3 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation
  %take.4 = f32[8]{0} gather(%fusion.3, %a), metadata={op_name="jit(f)/jit(_take)/gather"}
  %sel.5 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/head_moe/moe_experts/select"}
  %ragged-dot-none.6 = f32[8]{0:T(8,128)S(1)} custom-call(%w, %copy.9, /*index=2*/%sel.5, %fusion.3), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  ROOT %copy.2 = f32[8]{0} copy(%ragged-dot-none.6)
}
"""
    module, paths = stages._parse(text)
    assert module == "jit_f"
    assert paths == {
        "%a": (), "%w": (),
        # what a scatter was expanded into: the loop its body belongs to
        "%t": ("window_write",), "%gte.1": ("window_write",),
        "%fusion.7": ("window_write",), "%tuple.8": ("window_write",),
        "%while.4": ("window_write",),
        "%copy.9": (),                          # of a parameter: no stage
        "%fusion.3": ("encoder_block",),        # by its root
        "%take.4": (),      # traced by the program outside every scope
        "%sel.5": ("head_moe", "moe_experts"),
        # the compiler's own: the first operand that has a scope
        "%ragged-dot-none.6": ("head_moe", "moe_experts"),
        "%copy.2": ("head_moe", "moe_experts")}


def _publish(bus, device_id, value):
    meta = FrameMeta(width=W, height=H, channels=3,
                     timestamp_ms=int(time.time() * 1000), is_keyframe=True)
    bus.publish(device_id, np.full((H, W, 3), value, np.uint8), meta)


def _serve(stage_trace, results=3):
    """A tiny engine that answers one camera ``results`` times, stopped."""
    bus = MemoryFrameBus()
    bus.create_stream("cam0", H * W * 3)
    eng = InferenceEngine(
        bus, EngineConfig(model="tiny_vit", batch_buckets=(1,), tick_ms=5,
                          stage_trace=stage_trace, ladder=False),
        annotations=AnnotationQueue(handler=lambda b: True))
    eng.warmup()
    got = []
    eng.start()
    try:
        import threading

        def sub():
            for res in eng.subscribe():
                got.append(res)

        threading.Thread(target=sub, daemon=True).start()
        k, deadline = 0, time.time() + 60
        while len(got) < results and time.time() < deadline:
            k += 1
            _publish(bus, "cam0", k % 250)
            time.sleep(0.02)
        assert len(got) >= results, "the camera went unanswered"
    finally:
        eng.stop()
        bus.close()
    return eng


@pytest.fixture()
def fresh_registry():
    stages.clear()
    yield
    stages.clear()


def test_a_traced_engine_names_its_program_and_leaves_plain_maps(
        fresh_registry):
    eng = _serve(stage_trace=True)
    records = list(eng.stage_records)
    program = f"tiny_vit/{H}x{W}/1"
    assert {r["program"] for r in records} == {program}
    for r in records:
        assert 0.0 <= r["put_call_s"] <= r["t_placed"] - r["t_place0"] + 1e-6
    # stop() built the map of the program that ran; what it left is plain
    # data, and the registry holds nothing that leads to the executable
    assert program not in stages._steps
    entry = stages._built[program]
    assert entry["module"] == "jit_with_stats"  # the quality plane's step
    assert type(entry["ops"]) is dict and entry["ops"]
    assert all(type(k) is str and type(v) is tuple
               and all(type(p) is str for p in v)
               for k, v in entry["ops"].items())
    # (names the step has carried since PR 25: a program loaded from a
    # compile cache keeps the scope names of the build that wrote it)
    assert {"pre_resize", "encoder_block", "softmax_topk"} <= {
        p for path in entry["ops"].values() for p in path}
    del eng
    assert stages.built([program])[program] is entry


def test_without_stage_trace_no_text_is_made_and_no_map_built(
        fresh_registry, monkeypatch):
    made = []
    monkeypatch.setattr(stages, "_parse",
                        lambda text: made.append(len(text)) or ("", {}))
    eng = _serve(stage_trace=False)
    assert made == [] and stages._built == {}
    # the one dict entry a compile is there, for a capture to ask about
    assert f"tiny_vit/{H}x{W}/1" in stages._steps
    assert stages.built(["never/1x1/1"]) == {"never/1x1/1": None}
    del eng


def test_a_program_on_the_jit_fallback_has_no_map(fresh_registry):
    class Step:
        compiled = None                 # _TimedStep after an avals drift

    step = Step()
    stages.register("m/1x1/1", step)
    assert stages.built(["m/1x1/1"]) == {"m/1x1/1": None}
    assert "m/1x1/1" not in stages._steps


class _StubDeviceTracer:
    """What tests/test_prof.py's stub leaves: a Perfetto JSON, no
    ``.xplane.pb``."""

    def __call__(self, log_dir, ms):
        run = os.path.join(log_dir, "plugins", "profile", "run01")
        os.makedirs(run, exist_ok=True)
        with open(os.path.join(run, "x.trace.json"), "w") as f:
            json.dump({"traceEvents": []}, f)


def test_a_capture_without_a_device_plane_writes_no_stages_and_says_why(
        tmp_path):
    from video_edge_ai_proxy_tpu.obs.metrics import Registry

    prof = Profiler(str(tmp_path), device_tracer=_StubDeviceTracer(),
                    registry=Registry())
    manifest = prof.capture(10)
    assert manifest["stages"] is None
    assert "xplane" in manifest["stages_missing"]
    assert not os.path.exists(os.path.join(manifest["path"], STAGES))
    with open(os.path.join(manifest["path"], MANIFEST)) as f:
        assert json.load(f)["stages_missing"] == manifest["stages_missing"]


def test_a_bundle_reduces_its_own_trace_by_the_programs_its_spans_name(
        tmp_path, monkeypatch, fresh_registry):
    """The bundle's ``stages.json``: the device lines of its ``.xplane.pb``
    (stood in for here) paired with the ``step_call`` spans' programs."""
    from video_edge_ai_proxy_tpu.obs.metrics import Registry

    t0 = 5000.0

    class XplaneTracer:
        def __call__(self, log_dir, ms):
            run = os.path.join(log_dir, "plugins", "profile", "run01")
            os.makedirs(run, exist_ok=True)
            open(os.path.join(run, "host.xplane.pb"), "wb").close()

    class Spans:
        def events(self):
            step = dict(stream="engine.tick", stage="step_call", frame=1,
                        tick=1, dur_ms=2.0, program="lfm2/48x64/2")
            return [# launched while the profiler started: its head is
                    # missing from the trace, no run of the bundle's either
                    dict(step, ts=t0 + 0.101, batch=[0, 1], tick=0),
                    dict(step, ts=t0 + 1.001, batch=[1, 0]),
                    dict(stream="engine.drain", stage="fetch", frame=1,
                         ts=t0 + 1.012, batch=[1, 0], dur_ms=1.0),
                    dict(step, ts=t0 + 3.001, batch=[2, 0], tick=2),
                    # a step the trace's end cut: no run of the bundle's
                    dict(step, ts=t0 + 5.001, batch=[3, 0], tick=3),
                    dict(step, ts=t0 + 0.5, batch=[0, 0], tick=0,
                         program=None)]

    monkeypatch.setattr(stages, "read_device_lines", lambda path: {
        "ops": _step(0.1)[3:] + _step(1.0) + _step(3.0) + _step(5.0)[:2],
        "modules": [("jit_stream_step(3)", 0.1065, 0.0035),
                    ("jit_stream_step(3)", 1.0, 0.010),
                    ("jit_stream_step(3)", 3.0, 0.010),
                    ("jit_stream_step(3)", 5.0, 0.003)]})
    stages._built["lfm2/48x64/2"] = {"module": "jit_stream_step",
                                     "ops": LOOP}
    walls = iter([t0, t0 + 6.0])        # the capture's two ends
    prof = Profiler(str(tmp_path), device_tracer=XplaneTracer(),
                    tracer=Spans(), registry=Registry(),
                    wall_clock=lambda: next(walls), clock=lambda: 0.0)
    manifest = prof.capture(10)
    assert manifest["stages"] == STAGES and manifest["stages_missing"] is None
    with open(os.path.join(manifest["path"], STAGES)) as f:
        got = json.load(f)
    head = got["programs"]["lfm2/48x64/2"]
    assert head["runs"] == 2
    assert head["device_ms_per_run"] == pytest.approx(9.0)
    assert head["stage_ms_per_run"]["head_prefill/head_attn"] \
        == pytest.approx(2.0)
    assert head["stage_ms_per_run"]["unscoped"] == pytest.approx(1.0)
    assert sum(head["stage_ms_per_run"].values()) == pytest.approx(9.0)
    assert "by root" in got["attribution"] and got["no_map"] == []


def test_the_step_call_span_names_the_program(fresh_registry):
    prev = (tracer.enabled, tracer.sample_every)
    tracer.clear()
    tracer.configure(enabled=True, sample_every=1)
    try:
        _serve(stage_trace=True)
        events = tracer.events()
    finally:
        tracer.configure(enabled=prev[0], sample_every=prev[1])
        tracer.clear()
    calls = [e for e in events if e["stage"] == "step_call"]
    assert calls and {e["program"] for e in calls} == {f"tiny_vit/{H}x{W}/1"}
    places = [e for e in events if e["stage"] == "place"]
    assert places and all(0.0 <= e["put_call_ms"] <= e["dur_ms"] + 1e-3
                          for e in places)
