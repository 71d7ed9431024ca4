"""``run.py`` end to end on the CPU at a tiny size: the line names ``cpu``
and carries no device metric; a new configuration, cell, traffic mix,
per-layer metric and model family (with its reference) come in as new files
and entries only (``data/``); and with the timed path broken underneath
``correct`` comes out false."""

import os

import pytest

import run as vrun
from vbench import loader

DEVICE_METRICS = {"step_device_ms", "step_mfu_pct", "device_idle_pct"}


def bench_with(config: str, workload: str, traffic: str) -> dict:
    """The benchmark plus one cell of the tests' own, by entries alone."""
    bench = loader.benchmark()
    before = {k: list(v) if isinstance(v, list) else v
              for k, v in bench.items()}
    bench["configs"].append({
        "name": config, "source": "tests: the registry's tiny twins",
        "file": f"benchmark/tests/data/{config}.json", "reduced": [],
        "why": "tests"})
    bench["workloads"].append({
        "name": workload, "config": config,
        "traffic": f"../tests/data/{traffic}", "chips": 1, "why": "tests"})
    for m in bench["per_layer"]:
        m["workloads"] = m["workloads"] + [workload]
    bench["per_layer"].append({
        "name": "../tests/data/tiny_batches", "unit": "batches",
        "better": "lower", "source": "program_span", "layer": "collector",
        "moves": "latency_p95_ms", "workloads": [workload]})
    assert before["configs"] == bench["configs"][:-1]   # entries only added
    return bench


def tiny_bench():
    return bench_with("tiny_fleet", "tiny.free", "tiny_free")


def toy_bench():
    """A cell whose model is of a family the harness does not have:
    ``data/toy_family.py``, named by the configuration's ``family`` key."""
    return bench_with("toy_fleet", "toy.free", "toy_free")


@pytest.fixture(scope="module")
def traced():
    return vrun.run("tiny.free", 2**31 + 77, 2.0, True,
                    require_chip=False, bench=tiny_bench())


def test_end_to_end_line_names_cpu():
    out = vrun.run("tiny.free", 2**31 + 78, 2.0, False,
                   require_chip=False, bench=tiny_bench())
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0
    # free-running at 20 fps for 2 s, and a tiny model keeps up: every
    # camera is answered some tens of times
    assert out["attempted"] == out["notes"]["results_in_window"] > 5 * 20
    assert out["device"]["platform"] == "cpu"
    assert set(out["metrics"]) == {"latency_p50_ms", "latency_p95_ms",
                                   "setup_s"}
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == {
        "misrouted", "window_compiles", "logprob_err_tiny_vit",
        "logprob_mean_tiny_vit", "logprob_err_tiny_videomae",
        "logprob_mean_tiny_videomae"}


def test_traced_line_carries_no_device_metric(traced):
    assert traced["correct"] is True, traced["checks"]
    assert traced["device"]["platform"] == "cpu"
    assert not DEVICE_METRICS & set(traced["metrics"])
    assert "busy_s" not in traced["device"]
    assert "breakdown" not in traced
    for name in ("generator_late_p95_ms", "round_results_per_s",
                 "collect_ms", "dispatch_ms", "h2d_gbps", "drain_ms"):
        assert traced["metrics"][name]["value"] > 0


def test_a_new_metric_is_a_new_file_and_entry(traced):
    assert traced["metrics"]["../tests/data/tiny_batches"]["value"] >= 4


def _break_answer(monkeypatch):
    """An answer altered where it is produced: the drain swaps the first
    two classes of every result."""
    from video_edge_ai_proxy_tpu.engine import runner

    real = runner.InferenceEngine._to_detections

    def swapped(self, host, i, spec=None):
        host = dict(host)
        if "top_ids" in host:
            host["top_ids"] = host["top_ids"][:, [1, 0, 2, 3, 4]]
        return real(self, host, i, spec)

    monkeypatch.setattr(runner.InferenceEngine, "_to_detections", swapped)


def _break_clip_order(monkeypatch):
    """Clip assembly hands the model the window's frames newest first."""
    from video_edge_ai_proxy_tpu.engine import collector

    real = collector._ClipRing.copy_to

    def newest_first(self, row):
        real(self, row)
        row[:] = row[::-1].copy()

    monkeypatch.setattr(collector._ClipRing, "copy_to", newest_first)


def _break_routing(monkeypatch):
    """Results leave under the neighbouring camera's name."""
    from video_edge_ai_proxy_tpu.engine import runner

    real = runner.InferenceEngine._publish

    def misrouted(self, result):
        if result.device_id.endswith("000"):
            result.device_id = result.device_id[:-3] + "001"
        elif result.device_id.endswith("001"):
            result.device_id = result.device_id[:-3] + "000"
        return real(self, result)

    monkeypatch.setattr(runner.InferenceEngine, "_publish", misrouted)


def _break_half_the_fleet(monkeypatch):
    """Every other camera's results stop coming after its third."""
    from video_edge_ai_proxy_tpu.engine import runner

    real = runner.InferenceEngine._publish
    sent = {}

    def dropped(self, result):
        sent[result.device_id] = sent.get(result.device_id, 0) + 1
        if int(result.device_id[-3:]) % 2 == 0 or sent[result.device_id] <= 3:
            return real(self, result)

    monkeypatch.setattr(runner.InferenceEngine, "_publish", dropped)


@pytest.mark.parametrize("fault", [_break_answer, _break_clip_order,
                                   _break_routing, _break_half_the_fleet])
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    out = vrun.run("tiny.free", 2**31 + 79, 2.0, False,
                   require_chip=False, bench=tiny_bench())
    assert out["correct"] is False, out["checks"]


def test_a_new_family_is_new_files_and_entries():
    """The toy family's cell runs through the same harness: its sizes hold
    a list, its window is three reads long, and ``correct`` is decided by
    its own number."""
    out = vrun.run("toy.free", 2**31 + 83, 2.0, True, require_chip=False,
                   bench=toy_bench())
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["notes"]["sampled"] == 32
    assert set(out["checks"]) == {"misrouted", "window_compiles",
                                  "top1_prob_err_tiny_vit"}
    assert 0.0 < out["checks"]["top1_prob_err_tiny_vit"]["value"] < 0.1
    assert out["metrics"]["round_results_per_s"]["value"] > 0
    assert not DEVICE_METRICS & set(out["metrics"])


def test_a_new_family_with_its_timed_path_broken_is_not_correct(monkeypatch):
    _break_answer(monkeypatch)
    out = vrun.run("toy.free", 2**31 + 84, 2.0, False, require_chip=False,
                   bench=toy_bench())
    assert out["correct"] is False, out["checks"]
    assert out["checks"]["top1_prob_err_tiny_vit"]["value"] > 0.1


def test_cli_refuses_to_run_without_a_chip():
    import subprocess
    import sys

    p = subprocess.run(
        [sys.executable, os.path.join(loader.HERE, "run.py"), "--workload",
         "clip64_1080p", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
