"""The plain reference against the package's models at tiny widths (CPU),
the float8 control against the limits, and the weights' plumbing."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from vbench import correct, loader, weights
from vbench import traffic as traffic_mod

TINY = os.path.join(os.path.dirname(__file__), "data", "tiny_fleet.json")


def _tiny_models():
    with open(TINY) as f:
        return {m["registry_model"]: m for m in loader.models(json.load(f))}


def _program_float32(name, flat, frames):
    """The package's model, computing in float32, on the same weights."""
    from video_edge_ai_proxy_tpu.models import registry
    from video_edge_ai_proxy_tpu.ops import preprocess as pp

    spec = registry.get(name)
    model = spec.build().clone(dtype=jnp.float32)
    tmpl = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros(spec.example_shape(1), jnp.float32))
    variables = weights.as_variables(flat, tmpl)
    pre = pp.preprocess_clip if spec.clip_len else pp.preprocess_classify
    x = pre(jnp.asarray(frames), (spec.input_size,) * 2,
            out_dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        return np.asarray(model.apply(variables, x))


@pytest.mark.parametrize("name", ["tiny_vit", "tiny_videomae"])
def test_reference_equals_the_package_model_in_float32(name):
    m = _tiny_models()[name]
    flat = weights.generate(2**31 + 5, m["family"], m["sizes"])
    ref = loader.reference(m["reference"])
    n_frames = m["sizes"].get("num_frames", 0)
    shape = (3, n_frames, 80, 120, 3) if n_frames else (3, 80, 120, 3)
    frames = np.stack([traffic_mod.make_frame(7, 0, k, 80, 120)
                       for k in range(3 * max(n_frames, 1))]).reshape(shape)
    got = np.asarray(ref.jitted(
        m["family"], tuple(sorted(m["sizes"].items())))(flat, frames))
    want = _program_float32(name, flat, frames)
    # tanh-GELU (program) vs erf-GELU (published, reference) is the gap
    assert np.abs(got - want).max() < 2e-2
    assert np.abs(got).max() > 1.0      # logits are not all alike


def test_as_variables_refuses_a_tree_the_file_does_not_describe():
    m = _tiny_models()["tiny_vit"]
    flat = weights.generate(1, m["family"], m["sizes"])
    from video_edge_ai_proxy_tpu.models import registry

    spec = registry.get("tiny_videomae")
    tmpl = jax.eval_shape(spec.build().init, jax.random.PRNGKey(0),
                          jnp.zeros(spec.example_shape(1), jnp.bfloat16))
    with pytest.raises(ValueError):
        weights.as_variables(flat, tmpl)


def test_seeds_past_2_31_differ_and_repeat():
    m = _tiny_models()["tiny_vit"]
    a = weights.generate(2**31 + 9, m["family"], m["sizes"])
    b = weights.generate(2**31 + 9, m["family"], m["sizes"])
    c = weights.generate(2**32 + 2**31 + 9, m["family"], m["sizes"])
    k = "encoder/block0/attn/qkv/kernel"
    assert np.array_equal(np.asarray(a[k]), np.asarray(b[k]))
    assert not np.array_equal(np.asarray(a[k]), np.asarray(c[k]))


@pytest.mark.parametrize("config", ["videomae_b.json"])
def test_float8_control_is_not_correct(config):
    """The control — the reference at float8 in the program's place — has
    to fail a limit, at the published widths (two clips at a small source
    geometry, which is what a test run can hold)."""
    with open(os.path.join(loader.HERE, "configs", config)) as f:
        cfg = json.load(f)
    m = loader.models(cfg)[0]
    flat = weights.generate(31337, m["family"], m["sizes"])
    ref = loader.reference(m["reference"])
    clips = np.stack([np.stack([traffic_mod.make_frame(31337, c, k, 80, 120)
                                for k in range(8)]) for c in range(2)])
    key = tuple(sorted(m["sizes"].items()))
    exact = np.asarray(ref.jitted(m["family"], key)(flat, clips))
    low = np.asarray(ref.jitted(m["family"], key, "fp8")(flat, clips))
    names = [m["registry_model"]] * len(low)
    model_of = {m["registry_model"]: m}
    as_served = loader.family(m["family"]).as_served
    numbers = correct.compare([as_served(r) for r in low], list(exact),
                              names, model_of)
    # judged on the numbers this comparison gives, and on no other: it is a
    # log-probability limit that the control has to fail
    limits = {k: v for k, v in cfg["limits"].items() if k in numbers}
    assert limits and all(k.startswith("logprob_") for k in limits)
    ok, checks = correct.verdict(numbers, limits)
    assert not ok, numbers
    assert any(c["value"] > c["limit"] for c in checks.values())
    same = correct.compare([as_served(r) for r in exact], list(exact),
                           names, model_of)
    assert correct.verdict(same, limits)[0]


def _ev(stream, stage, frame, ts):
    return {"stream": stream, "stage": stage, "frame": frame, "ts": ts}


def test_windows_follow_the_frames_the_collector_read():
    """Latest-wins: a clip's window is the camera's last n READ frames, as
    the collect spans give them; a result whose camera had read fewer, or
    whose frame the spans do not show, is not compared."""
    events = [_ev("c", "collect", k, 10.0 + i)
              for i, k in enumerate((3, 9, 14, 20, 27, 31, 38, 44, 52, 57))]
    events += [_ev("t", "collect", k, 10.5 + i) for i, k in enumerate((4, 8))]
    events += [_ev("c", "emit", 52, 30.0)]
    reads = correct.reads_by_camera(events[::-1])       # any order in
    got = [{"device_id": "c", "packet": k} for k in (38, 44, 52, 57, 60)]
    got += [{"device_id": "t", "packet": 8}]
    models = {"c": {"family": "videomae", "sizes": {"num_frames": 8}},
              "t": {"family": "vit", "sizes": {}}}
    out = {(r["device_id"], r["packet"]): r["window"]
           for r in correct.eligible(got, reads, models)}
    assert out[("c", 44)] == [3, 9, 14, 20, 27, 31, 38, 44]
    assert out[("c", 57)] == [14, 20, 27, 31, 38, 44, 52, 57]
    assert ("c", 38) not in out                 # its window was not full
    assert ("c", 60) not in out                 # no span shows that read
    assert out[("t", 8)] == [8]


def test_unanswered_counts_what_the_engine_took_and_lost():
    cams = [(0, "a", "tag", 80, 120, 0.0), (1, "b", "tag", 80, 120, 0.01),
            (2, "p", "tag", 80, 120, 0.02)]
    sample_frames = {"a": 1, "b": 1, "p": 1}
    events, results = [], []
    for i in range(10):                         # one read a second, 1 s each
        for cam in ("a", "b"):
            events.append(_ev(cam, "collect", i, 100.0 + i))
            if not (cam == "b" and i == 4):     # b's frame 4 is never answered
                results.append({"device_id": cam, "packet": i,
                                "t": 1.0 + i + 1.0})
    args = (cams[:2], sample_frames, 1.0, 10.5, 99.0)
    assert correct.unanswered(events, results, *args) == 1
    # the last reads are in flight at the close: not counted
    late = [r for r in results if r["t"] < 10.5]
    assert correct.unanswered(events, late, *args) == 1
    # a camera the collector never read (paused) counts once
    assert correct.unanswered(events, results, cams, sample_frames, 1.0,
                              10.5, 99.0) == 2
    events.append(_ev("a", "dropped", 7, 106.5))
    assert correct.unanswered(events, results, *args) == 2


def test_every_seed_gives_the_same_arrivals_in_another_order():
    t = {"fps": 5, "groups": [{"role": "clip", "prefix": "clip", "cameras": 8,
                               "height": 80, "width": 120}]}
    a = traffic_mod.cameras(t, 2**31 + 5)
    b = traffic_mod.cameras(t, 2**31 + 6)
    assert a == traffic_mod.cameras(t, 2**31 + 5)
    assert sorted(c[5] for c in a) == sorted(c[5] for c in b) \
        == [j / 40 for j in range(8)]
    assert [c[5] for c in a] != [c[5] for c in b]
    assert traffic_mod.due_time(t, a[3], 100.0, 7) == 100.0 + a[3][5] + 1.4
