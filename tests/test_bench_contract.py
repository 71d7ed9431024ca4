"""Driver-contract tests for bench.py — a broken bench means no recorded
score at round end, so its output contract, its timing helpers and its
refusal to run without a TPU get real coverage (SURVEY.md §4(e):
benchmarks as tests)."""

import io
import json
import time
from contextlib import redirect_stdout

import numpy as np
import pytest

import bench


class TestTimedBest:
    def test_best_of_three_and_last_checksum(self):
        calls = {"n": 0}

        def run():
            calls["n"] += 1
            return np.int32(calls["n"])

        best, tot = bench.timed_best(run)
        assert calls["n"] == 3          # best-of-3, nothing retried
        assert tot == 3                 # the LAST run's checksum
        assert best > 0

    def test_best_is_the_minimum_not_the_mean(self):
        delays = iter([0.05, 0.0, 0.05])

        def run():
            time.sleep(next(delays))
            return np.int32(1)

        best, _ = bench.timed_best(run)
        assert best < 0.04

    def test_fetch_ends_the_timed_region(self):
        """The timed region ends at the host fetch of run()'s result, so
        an async dispatch is waited for, not just enqueued."""
        fetched = []

        class Lazy:
            def __array__(self, dtype=None, copy=None):
                fetched.append(1)
                return np.asarray(5, dtype=dtype or np.int32)

        _, tot = bench.timed_best(Lazy, repeats=2)
        assert fetched == [1, 1] and tot == 5


class TestTimedMin:
    def test_minimum_of_three(self):
        vals = iter([0.3, 0.001, 0.2])
        assert bench.timed_min(lambda: next(vals)) == 0.001

    def test_repeats(self):
        calls = []
        assert bench.timed_min(lambda: calls.append(1) or 9.0,
                               repeats=5) == 9.0
        assert len(calls) == 5


def _cpu_rehearsal(monkeypatch):
    """Run bench.main() on the CPU twin at a toy size: everything the
    hard no-TPU failure guards is patched HERE, in the test — bench.py
    has no flag or environment variable that makes it shrink."""
    import jax

    from video_edge_ai_proxy_tpu.models import registry
    from video_edge_ai_proxy_tpu.obs import perf

    cpu = jax.devices()[0]
    monkeypatch.setattr(bench, "require_tpu", lambda: cpu)
    monkeypatch.setitem(perf.PEAK_TFLOPS_BY_DEVICE_KIND,
                        cpu.device_kind, 1.0)
    monkeypatch.setattr(bench, "STREAMS", 2)
    monkeypatch.setattr(bench, "ITERS", 2)
    monkeypatch.setattr(bench, "SRC_H", 270)
    monkeypatch.setattr(bench, "SRC_W", 480)
    monkeypatch.setattr(bench, "CAPACITY_BUCKET", 4)
    real_get = registry.get
    monkeypatch.setattr(
        registry, "get", lambda name: real_get("tiny_yolov8"))


class TestIntegrity:
    def test_zero_class_prior_zeroes_only_head_bias(self):
        import jax

        from video_edge_ai_proxy_tpu.models import registry

        spec = registry.get("tiny_yolov8")
        _, variables = spec.init_params(jax.random.PRNGKey(0))
        out = bench.zero_class_prior(variables)

        def find(tree, pred, path=()):
            hits = []
            if isinstance(tree, dict):
                for k, v in tree.items():
                    hits += find(v, pred, path + (k,))
            elif pred(path):
                hits.append((path, tree))
            return hits

        cls_bias = find(out, lambda p: any(
            isinstance(s, str) and s.startswith("cls") and s.endswith("_out")
            for s in p) and p[-1] == "bias")
        assert cls_bias, "no class-head bias found"
        for _, arr in cls_bias:
            assert not np.asarray(arr).any()     # prior neutralized
        # everything else untouched (e.g. some conv kernel is nonzero)
        kernels = find(out, lambda p: p[-1] == "kernel")
        assert any(np.asarray(a).any() for _, a in kernels)

    def test_zero_checksum_fails_loudly(self, monkeypatch):
        """The r4 failure mode (all scores below the NMS threshold ->
        checksum 0) must abort the bench, not record a meaningless
        artifact."""
        import pytest

        _cpu_rehearsal(monkeypatch)
        monkeypatch.setattr(bench, "timed_best", lambda *a, **k: (1.0, 0))
        with pytest.raises(SystemExit, match="integrity"):
            bench.main()


@pytest.mark.slow
class TestProfileMfu:
    def test_tiny_config_decomposes(self):
        """profile_mfu's prefix-timing machinery (capture_intermediates +
        DCE) on the CPU twin: every milestone resolves, stage rows carry
        the contract fields, and FLOPs grow monotonically with prefix
        depth (times are too noisy to assert on a shared CPU)."""
        from tools.profile_mfu import run_config

        out = run_config("tiny_resnet_x2")
        assert out["config"] == "tiny_resnet_x2"
        stages = out["stages"]
        assert [s["stage"] for s in stages] == [
            "preprocess", "stem", "stage1", "head"]
        for s in stages:
            for key in ("prefix_ms", "prefix_gflop", "stage_ms",
                        "stage_gflop"):
                assert key in s
        gf = [s["prefix_gflop"] for s in stages]
        assert gf == sorted(gf)          # DCE prefixes: flops accumulate
        assert out["total_ms"] > 0

    def test_tiny_detect_config_decomposes(self):
        """The detect route: letterbox preprocess, backbone milestone,
        decode ("__model__") and the exact serving step with NMS
        ("__full__") all resolve and accumulate FLOPs."""
        from tools.profile_mfu import run_config

        out = run_config("tiny_yolo_x2", rounds=2)
        stages = out["stages"]
        assert [s["stage"] for s in stages] == [
            "preprocess", "P3", "decode", "nms"]
        gf = [s["prefix_gflop"] for s in stages]
        assert gf == sorted(gf)
        assert out["total_ms"] > 0


class TestBenchOutputContract:
    def test_main_prints_one_json_line_with_required_keys(self, monkeypatch):
        """The driver parses exactly this contract; run main() end-to-end
        on the CPU backend with the tiny detector substituted so the test
        stays fast."""
        _cpu_rehearsal(monkeypatch)
        buf = io.StringIO()
        with redirect_stdout(buf):
            bench.main()
        lines = [l for l in buf.getvalue().splitlines() if l.strip()]
        assert len(lines) == 1, f"expected ONE JSON line, got: {lines}"
        out = json.loads(lines[0])
        for key in ("metric", "value", "unit", "vs_baseline"):
            assert key in out, f"driver contract key missing: {key}"
        assert out["unit"] == "frames/sec"
        assert out["value"] > 0
        assert out["device"]["platform"] == "cpu"   # stamped, not assumed
        assert "upload_step_fetch_ms" in out

    def test_main_without_a_tpu_exits_nonzero_and_prints_nothing(self):
        """Unpatched, on the CPU backend: no shrink-and-print, a
        SystemExit that names the missing device."""
        buf = io.StringIO()
        with redirect_stdout(buf), \
                pytest.raises(SystemExit, match="TPU") as exc:
            bench.main()
        assert exc.value.code not in (0, None)
        assert buf.getvalue() == ""

    def test_peak_lookup_is_by_device_kind(self):
        from video_edge_ai_proxy_tpu.obs.perf import (
            peak_tflops_for, require_peak_tflops,
        )

        assert peak_tflops_for("TPU v5 lite") == 197.0
        assert peak_tflops_for("cpu") is None
        with pytest.raises(SystemExit, match="cpu"):
            require_peak_tflops("cpu")
