"""Persistent AOT prewarm cache tests (engine/aot_cache.py, r19): the
manifest round-trip and its version/jaxlib fallback contract (mismatch
means clean compile, never a crash), the engine ``start()`` manifest
union + ``prewarm_status`` surface, the cross-process round-trip (one
process seeds the cache, a FRESH subprocess prewarms from the manifest
and serves its first dispatch as a step-cache hit with the
``vep_compile_*`` families flat), and the ``aot_cache=False``
default-off bit-identical replay pin (the capacity/roi/cascade
kill-switch pin, applied to the cache)."""

import json
import os
import queue
import subprocess
import sys
import time

import numpy as np
import pytest

from video_edge_ai_proxy_tpu.bus.interface import FrameMeta
from video_edge_ai_proxy_tpu.bus.memory_bus import MemoryFrameBus
from video_edge_ai_proxy_tpu.engine import aot_cache
from video_edge_ai_proxy_tpu.uplink.queue import AnnotationQueue
from video_edge_ai_proxy_tpu.utils.config import EngineConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _meta(side=32):
    return FrameMeta(width=side, height=side, channels=3,
                     timestamp_ms=int(time.time() * 1000),
                     is_keyframe=True)


# ---------------------------------------------------------------------------
# manifest round-trip + fallback contract (pure file I/O)


class TestManifest:
    def test_record_then_load_round_trip(self, tmp_path):
        d = str(tmp_path)
        aot_cache.record_program(d, model="tiny_yolov8", stem="classic",
                                 src_hw=(96, 128), bucket=8)
        aot_cache.record_program(d, model=None, stem="classic",
                                 src_hw=(64, 64), bucket=2)
        # Idempotent merge: the duplicate never lands twice.
        aot_cache.record_program(d, model="tiny_yolov8", stem="classic",
                                 src_hw=(96, 128), bucket=8)
        progs = aot_cache.load_manifest(d)
        assert progs is not None and len(progs) == 2
        by_model = {p["model"]: p for p in progs}
        assert by_model["tiny_yolov8"] == {
            "model": "tiny_yolov8", "stem": "classic",
            "h": 96, "w": 128, "bucket": 8}
        assert by_model[None]["bucket"] == 2
        entries = aot_cache.prewarm_entries(progs)
        assert sorted(entries) == sorted([
            [96, 128, 8, "tiny_yolov8", "classic"],
            [64, 64, 2, "", "classic"]])

    def test_missing_and_corrupt_manifest_ignored(self, tmp_path):
        d = str(tmp_path)
        assert aot_cache.load_manifest(d) is None
        with open(aot_cache.manifest_path(d), "w") as fh:
            fh.write("{not json")
        assert aot_cache.load_manifest(d) is None
        with open(aot_cache.manifest_path(d), "w") as fh:
            json.dump(["not", "a", "mapping"], fh)
        assert aot_cache.load_manifest(d) is None

    def _write(self, d, **overrides):
        body = {
            "version": aot_cache.MANIFEST_VERSION,
            "jaxlib": aot_cache._jaxlib_stamp(),
            "programs": [{"model": "tiny_yolov8", "stem": "classic",
                          "h": 96, "w": 128, "bucket": 8}],
        }
        body.update(overrides)
        with open(aot_cache.manifest_path(d), "w") as fh:
            json.dump(body, fh)

    def test_version_mismatch_means_clean_compile(self, tmp_path):
        d = str(tmp_path)
        self._write(d, version=aot_cache.MANIFEST_VERSION + 1)
        assert aot_cache.load_manifest(d) is None

    def test_jaxlib_mismatch_means_clean_compile(self, tmp_path):
        d = str(tmp_path)
        self._write(d, jaxlib="0.0.0-somewhere-else")
        assert aot_cache.load_manifest(d) is None

    def test_malformed_programs_filtered_not_fatal(self, tmp_path):
        d = str(tmp_path)
        self._write(d, programs=[
            {"model": "m", "stem": "classic", "h": 1, "w": 1, "bucket": 0},
            "not a dict",
            {"model": "m", "stem": "classic", "h": 32, "w": 32, "bucket": 1},
            {"model": "m", "stem": "classic", "h": 32, "w": 32, "bucket": 1},
        ])
        progs = aot_cache.load_manifest(d)
        assert progs == [{"model": "m", "stem": "classic",
                          "h": 32, "w": 32, "bucket": 1}]

    def test_record_replaces_stale_manifest(self, tmp_path):
        # A mismatched manifest on disk is replaced on the next record,
        # not merged into: its cache entries are guaranteed misses.
        d = str(tmp_path)
        self._write(d, version=aot_cache.MANIFEST_VERSION + 1)
        aot_cache.record_program(d, model="fresh", stem="classic",
                                 src_hw=(32, 32), bucket=1)
        progs = aot_cache.load_manifest(d)
        assert [p["model"] for p in progs] == ["fresh"]


# ---------------------------------------------------------------------------
# engine integration: start() union + prewarm_status surface


class _StubJit:
    """Duck-typed jitted function for _TimedStep: ``lower().compile()``
    hands back ``aot`` as the executable; calling the object itself is
    the plain-jit path."""

    def __init__(self, aot, on_jit=None):
        self._aot = aot
        self._on_jit = on_jit

    def lower(self, *a):
        return self

    def compile(self):
        return self._aot

    def __call__(self, *a):
        if self._on_jit is None:
            raise AssertionError("jit path not expected")
        return self._on_jit(*a)


def _restore_jax_cache_config():
    import jax

    return (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs)


def _apply_jax_cache_config(saved):
    import jax

    jax.config.update("jax_compilation_cache_dir", saved[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      saved[1])


class TestEnginePrewarm:
    def test_status_defaults_complete_without_cache(self):
        from video_edge_ai_proxy_tpu.engine.runner import InferenceEngine

        bus = MemoryFrameBus()
        try:
            eng = InferenceEngine(bus, EngineConfig(
                model="tiny_mobilenet_v2", batch_buckets=(1,), tick_ms=5))
            # A member with nothing to prewarm is complete from boot —
            # the fleet tier must never read it as warming.
            assert eng.prewarm_status() == {
                "required": 0, "done": 0, "complete": True,
                "aot_cache": False}
        finally:
            bus.close()

    def test_aot_boot_reports_warming_until_start_computes_the_set(
            self, tmp_path):
        # REST binds before engine.start(): with the AOT cache on, the
        # program set is unknown until start() unions the manifest in —
        # a scrape during the (potentially long) warmup must read the
        # member as warming even with cfg.prewarm empty (the harness's
        # spawn path boots with no --prewarm flags), or the router
        # places/migrates onto a mid-compile-ramp member.
        from video_edge_ai_proxy_tpu.engine.runner import InferenceEngine

        d = str(tmp_path / "aot")
        saved = _restore_jax_cache_config()
        bus = MemoryFrameBus()
        try:
            eng = InferenceEngine(bus, EngineConfig(
                model="tiny_mobilenet_v2", batch_buckets=(1,), tick_ms=5,
                prefetch=False, aot_cache=True, aot_cache_dir=d))
            status = eng.prewarm_status()
            assert status["complete"] is False and status["aot_cache"]
            eng.start()
            try:
                assert eng.prewarm_status()["complete"] is True
            finally:
                eng.stop()
        finally:
            bus.close()
            _apply_jax_cache_config(saved)

    def test_failing_program_never_recorded_in_manifest(self, tmp_path):
        # The manifest records a program only after its first call
        # compiled AND executed successfully — a (geometry, bucket,
        # model) whose compile reliably fails must not be replayed (and
        # re-fail) on every future spawn's boot.
        from video_edge_ai_proxy_tpu.engine.runner import _TimedStep
        from video_edge_ai_proxy_tpu.obs.perf import PerfTracker

        d = str(tmp_path / "aot")

        def record():
            aot_cache.record_program(d, model="broken", stem="classic",
                                     src_hw=(32, 32), bucket=1)

        class BoomJit:
            def lower(self, *a):
                raise RuntimeError("compile failed")

            def __call__(self, *a):
                raise AssertionError("a failed compile is not retried")

        step = _TimedStep(BoomJit(), PerfTracker(), "broken", (32, 32), 1,
                          on_first_success=record)
        for _ in range(3):   # reliably failing: every retry re-raises
            with pytest.raises(RuntimeError, match="compile failed"):
                step(None)
        assert aot_cache.load_manifest(d) is None

        fired = []
        ok = _TimedStep(_StubJit(lambda *a: 42), PerfTracker(), "ok",
                        (32, 32), 1,
                        on_first_success=lambda: fired.append(1))
        assert ok(None) == 42
        assert ok(None) == 42
        assert fired == [1]   # once, on the first success only

    def test_runtime_error_from_aot_call_is_not_swallowed(self):
        """A device/runtime error out of the AOT executable propagates as
        it is — to the dispatch site and the fault plane — with no second
        attempt through jit (the frames argument may already have been
        donated) and nothing counted as a fallback."""
        from video_edge_ai_proxy_tpu.engine.runner import _TimedStep
        from video_edge_ai_proxy_tpu.obs.perf import PerfTracker

        class XlaRuntimeError(RuntimeError):
            pass

        def boom(*a):
            raise XlaRuntimeError("INTERNAL: core halted unexpectedly")

        jit_calls = []
        perf = PerfTracker()
        step = _TimedStep(
            _StubJit(boom, on_jit=lambda *a: jit_calls.append(a)),
            perf, "m", (32, 32), 1)
        for _ in range(2):
            with pytest.raises(XlaRuntimeError, match="core halted"):
                step(None)
        assert jit_calls == []                   # never retried via jit
        assert perf.snapshot()["aot_fallbacks"] == 0
        assert step.compiled is not None         # AOT path still armed

    @pytest.mark.parametrize("exc", [TypeError, ValueError])
    def test_avals_drift_falls_back_to_jit_and_is_counted(self, exc,
                                                          monkeypatch):
        """jax's argument check raises TypeError (avals/pytree) or
        ValueError (sharding) BEFORE anything runs: that one case falls
        back to plain jit for good, logged and counted."""
        from video_edge_ai_proxy_tpu.engine import runner
        from video_edge_ai_proxy_tpu.engine.runner import _TimedStep
        from video_edge_ai_proxy_tpu.obs.perf import PerfTracker

        aot_calls, logged = [], []
        monkeypatch.setattr(runner.log, "warning",
                            lambda msg, *a: logged.append(msg % a))

        def reject(*a):
            aot_calls.append(1)
            raise exc("Argument types differ from the types for which "
                      "this computation was compiled")

        perf = PerfTracker()
        step = _TimedStep(_StubJit(reject, on_jit=lambda *a: "via-jit"),
                          perf, "m", (32, 32), 1)
        assert step(None) == "via-jit"
        assert step(None) == "via-jit"
        assert aot_calls == [1]                  # AOT dropped after one try
        assert perf.snapshot()["aot_fallbacks"] == 1
        assert step.compiled is None
        assert len(logged) == 1 and "rejected its arguments" in logged[0]
        assert "Argument types differ" in logged[0]   # with the exception

    def test_real_aval_mismatch_is_the_fallback_case(self):
        """The real thing, not a stub: an executable compiled for one
        shape, called with another, raises what the wrapper catches."""
        import jax
        import jax.numpy as jnp

        from video_edge_ai_proxy_tpu.engine.runner import _TimedStep
        from video_edge_ai_proxy_tpu.obs.perf import PerfTracker

        perf = PerfTracker()
        step = _TimedStep(jax.jit(lambda v, x: x * 2), perf, "m",
                          (32, 32), 1)
        assert int(step(None, jnp.ones((2,), jnp.int32)).sum()) == 4
        assert step.compiled is not None
        assert int(step(None, jnp.ones((3,), jnp.int32)).sum()) == 6
        assert perf.snapshot()["aot_fallbacks"] == 1

    def test_start_prewarms_manifest_programs(self, tmp_path):
        from video_edge_ai_proxy_tpu.engine.runner import InferenceEngine

        d = str(tmp_path / "aot")
        aot_cache.record_program(d, model="tiny_mobilenet_v2",
                                 stem="classic", src_hw=(32, 32), bucket=1)
        saved = _restore_jax_cache_config()
        bus = MemoryFrameBus()
        try:
            bus.create_stream("cam0", 32 * 32 * 3)
            # NO cfg.prewarm: the program set must come from the manifest.
            eng = InferenceEngine(bus, EngineConfig(
                model="tiny_mobilenet_v2", batch_buckets=(1,), tick_ms=5,
                prefetch=False, aot_cache=True, aot_cache_dir=d))
            eng.start()
            try:
                status = eng.prewarm_status()
                assert status == {"required": 1, "done": 1,
                                  "complete": True, "aot_cache": True}
                key = ("tiny_mobilenet_v2", "classic", (32, 32), 1)
                assert key in eng._step_cache
            finally:
                eng.stop()
        finally:
            bus.close()
            _apply_jax_cache_config(saved)

    def test_mismatched_manifest_boots_and_serves_clean(self, tmp_path):
        from video_edge_ai_proxy_tpu.engine.runner import InferenceEngine

        d = str(tmp_path / "aot")
        os.makedirs(d)
        with open(aot_cache.manifest_path(d), "w") as fh:
            json.dump({"version": aot_cache.MANIFEST_VERSION + 1,
                       "jaxlib": aot_cache._jaxlib_stamp(),
                       "programs": [{"model": "tiny_mobilenet_v2",
                                     "stem": "classic", "h": 32, "w": 32,
                                     "bucket": 1}]}, fh)
        saved = _restore_jax_cache_config()
        bus = MemoryFrameBus()
        try:
            bus.create_stream("cam0", 32 * 32 * 3)
            eng = InferenceEngine(
                bus,
                EngineConfig(model="tiny_mobilenet_v2", batch_buckets=(1,),
                             tick_ms=5, prefetch=False, aot_cache=True,
                             aot_cache_dir=d),
                annotations=AnnotationQueue(handler=lambda batch: True))
            eng.start()
            try:
                # Mismatch = empty union: nothing prewarmed, no crash.
                assert eng.prewarm_status()["required"] == 0
                results = []
                sub = eng.subscribe(timeout=0.1)
                deadline = time.time() + 60
                while not results and time.time() < deadline:
                    bus.publish("cam0",
                                np.full((32, 32, 3), 7, np.uint8), _meta())
                    try:
                        results.append(next(sub))
                    except StopIteration:
                        break
                assert results, "engine did not serve past a mismatched " \
                                "manifest"
            finally:
                eng.stop()
        finally:
            bus.close()
            _apply_jax_cache_config(saved)


# ---------------------------------------------------------------------------
# cross-process round-trip: serialize in one process, hit in a fresh one


_ROUNDTRIP_SCRIPT = r"""
import json, sys, time

import jax

jax.config.update("jax_platforms", "cpu")
cache_dir, phase = sys.argv[1], sys.argv[2]

import numpy as np

from video_edge_ai_proxy_tpu.bus.interface import FrameMeta
from video_edge_ai_proxy_tpu.bus.memory_bus import MemoryFrameBus
from video_edge_ai_proxy_tpu.engine.runner import InferenceEngine
from video_edge_ai_proxy_tpu.obs import registry
from video_edge_ai_proxy_tpu.uplink.queue import AnnotationQueue
from video_edge_ai_proxy_tpu.utils.config import EngineConfig


def family_total(name):
    total = 0.0
    for line in registry.render().splitlines():
        if line.startswith(name) and not line.startswith("# "):
            total += float(line.rsplit(" ", 1)[1])
    return total


cfg = EngineConfig(
    model="tiny_mobilenet_v2", batch_buckets=(1,), tick_ms=5,
    prefetch=False, aot_cache=True, aot_cache_dir=cache_dir,
    prewarm=[[32, 32, 1]] if phase == "seed" else [])
bus = MemoryFrameBus()
bus.create_stream("cam0", 32 * 32 * 3)
eng = InferenceEngine(bus, cfg,
                      annotations=AnnotationQueue(handler=lambda b: True))
t0 = time.monotonic()
eng.start()
out = {
    "phase": phase,
    "boot_s": round(time.monotonic() - t0, 3),
    "prewarm": eng.prewarm_status(),
    "compiles_after_start": family_total("vep_compile_programs_total"),
    "compile_s_after_start": family_total("vep_compile_seconds_sum"),
}
meta = FrameMeta(width=32, height=32, channels=3,
                 timestamp_ms=int(time.time() * 1000), is_keyframe=True)
results = []
sub = eng.subscribe(timeout=0.1)
deadline = time.time() + 60
while not results and time.time() < deadline:
    bus.publish("cam0", np.full((32, 32, 3), 7, np.uint8), meta)
    try:
        results.append(next(sub))
    except StopIteration:
        break
out["served"] = bool(results)
out["compiles_after_dispatch"] = family_total("vep_compile_programs_total")
out["step_hits"] = family_total("vep_step_cache_hits_total")
out["step_misses"] = family_total("vep_step_cache_misses_total")
eng.stop()
bus.close()
print(json.dumps(out))
"""


class TestCrossProcessRoundTrip:
    def _run(self, cache_dir, phase):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, "-c", _ROUNDTRIP_SCRIPT, cache_dir, phase],
            capture_output=True, text=True, timeout=420, cwd=REPO, env=env)
        assert proc.returncode == 0, proc.stderr[-2000:]
        return json.loads(proc.stdout.strip().splitlines()[-1])

    def test_fresh_process_prewarms_with_zero_dispatch_compiles(
            self, tmp_path):
        d = str(tmp_path / "aot")
        # Process A seeds: explicit prewarm geometry, records the
        # manifest next to the XLA payload.
        seed = self._run(d, "seed")
        assert seed["served"], seed
        assert seed["prewarm"]["complete"] and \
            seed["prewarm"]["aot_cache"], seed
        progs = aot_cache.load_manifest(d)
        assert progs is not None and [p["model"] for p in progs] == [
            "tiny_mobilenet_v2"]

        # Process B is FRESH (new interpreter, empty step cache) and has
        # NO prewarm config: the manifest supplies the program set, and
        # the first dispatch is a step-cache hit — the vep_compile_*
        # families do not move between start() and first-frame-served.
        warm = self._run(d, "warm")
        assert warm["served"], warm
        assert warm["prewarm"] == {"required": 1, "done": 1,
                                   "complete": True, "aot_cache": True}
        assert warm["compiles_after_start"] >= 1.0
        assert warm["compiles_after_dispatch"] == \
            warm["compiles_after_start"], warm
        assert warm["step_hits"] >= 1.0
        assert warm["step_misses"] == 1.0, warm   # the prewarm itself


# ---------------------------------------------------------------------------
# default-off bit-identical pin (the r9 kill-switch stance)


class TestAotCacheChecksumPin:
    def test_aot_cache_off_default_bit_identical(self, tmp_path):
        """The cache is pure compile plumbing: the device outputs an
        engine emits must fold the SAME checksum with aot_cache=True as
        with the default aot_cache=False — persistence may move compile
        cost, never change what a program computes."""
        from video_edge_ai_proxy_tpu.engine.runner import InferenceEngine
        from video_edge_ai_proxy_tpu.replay.checksum import (
            CHECKSUM_MASK,
            device_checksum,
            finalize_checksum,
        )

        saved = _restore_jax_cache_config()

        def run(aot):
            b = MemoryFrameBus()
            try:
                b.create_stream("cam1", 64 * 64 * 3)
                eng = InferenceEngine(
                    b, EngineConfig(model="tiny_blob_gauge",
                                    batch_buckets=(1, 2, 4), tick_ms=5,
                                    prefetch=False, aot_cache=aot,
                                    aot_cache_dir=(
                                        str(tmp_path / "aot") if aot
                                        else "")),
                    annotations=AnnotationQueue(handler=lambda batch: True))
                eng.warmup()
                eng._drain_q = queue.Queue(maxsize=8)
                carry = 0
                for value in (15, 60, 105, 150):
                    b.publish("cam1",
                              np.full((64, 64, 3), value, np.uint8),
                              _meta(64))
                    groups = eng._collector.collect()
                    eng._dispatch(groups, time.perf_counter())
                    inflight = eng._drain_q.get(timeout=10)
                    part = int(np.asarray(
                        device_checksum(inflight.outputs)))
                    carry = (carry + part) & CHECKSUM_MASK
                    eng._emit(inflight)
                    eng._collector.release(inflight.group)
                    eng._drain_q.task_done()
                if aot:
                    # The dispatch-side record hook ran: the manifest now
                    # carries the program the drive compiled.
                    progs = aot_cache.load_manifest(str(tmp_path / "aot"))
                    assert progs and progs[0]["model"] == "tiny_blob_gauge"
                return finalize_checksum(carry)
            finally:
                b.close()

        try:
            assert run(aot=True) == run(aot=False)
        finally:
            _apply_jax_cache_config(saved)
