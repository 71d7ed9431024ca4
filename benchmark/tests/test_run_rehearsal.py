"""``run.py`` end to end on the CPU at a tiny size: the line names ``cpu``
and carries no device metric; a new configuration, cell, traffic mix and
per-layer metric come in as new files and entries only (``data/``); and
with the timed path broken underneath ``correct`` comes out false."""

import os

import numpy as np
import pytest

import run as vrun
from vbench import loader

DEVICE_METRICS = {"step_device_ms", "step_mfu_pct", "device_idle_pct"}


def tiny_bench():
    bench = loader.benchmark()
    before = {k: list(v) if isinstance(v, list) else v
              for k, v in bench.items()}
    bench["configs"].append({
        "name": "tiny_fleet", "source": "tests: the registry's tiny twins",
        "file": "benchmark/tests/data/tiny_fleet.json", "reduced": [],
        "why": "tests"})
    bench["workloads"].append({
        "name": "tiny.free", "config": "tiny_fleet",
        "traffic": "../tests/data/tiny_free", "chips": 1, "why": "tests"})
    for m in bench["per_layer"]:
        m["workloads"] = m["workloads"] + ["tiny.free"]
    bench["per_layer"].append({
        "name": "../tests/data/tiny_batches", "unit": "batches",
        "better": "lower", "source": "program_span", "layer": "collector",
        "moves": "latency_p95_ms", "workloads": ["tiny.free"]})
    assert before["configs"] == bench["configs"][:-1]   # entries only added
    return bench


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

    real = np.stack

    class _Np:
        def __getattr__(self, k):
            return getattr(np, k)

        @staticmethod
        def stack(arrs, *a, **kw):
            arrs = list(arrs)
            return real(arrs[::-1] if len(arrs) == 4 else arrs, *a, **kw)

    monkeypatch.setattr(collector, "np", _Np())


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
