"""Replay subsystem tests (ISSUE r6): trace round-trip, the ``replay://``
source, worker flight-recorder tap, record->replay lockstep determinism,
seeded-numerics-fault checksum divergence, fault plans, and a mini chaos
soak on the in-process harness."""

import json
import os

import numpy as np
import pytest

from video_edge_ai_proxy_tpu.bus.interface import FrameMeta
from video_edge_ai_proxy_tpu.bus.memory_bus import MemoryFrameBus
from video_edge_ai_proxy_tpu.ingest import IngestWorker, WorkerConfig, open_source
from video_edge_ai_proxy_tpu.replay import trace as trace_mod
from video_edge_ai_proxy_tpu.replay.checksum import (
    CHECKSUM_MASK,
    check_golden,
    device_checksum,
    golden_lookup,
)
from video_edge_ai_proxy_tpu.replay.faults import FaultEvent, FaultPlan
from video_edge_ai_proxy_tpu.replay.player import ReplaySource, TracePlayer
from video_edge_ai_proxy_tpu.replay.recorder import (
    RecordingBus,
    TraceRecorder,
    record_synthetic_trace,
)


def _meta(w=64, h=48, ts=1_700_000_000_000, packet=0, key=True):
    return FrameMeta(
        width=w, height=h, channels=3, timestamp_ms=ts, pts=packet * 3000,
        dts=packet * 3000, packet=packet, is_keyframe=key,
        frame_type="I" if key else "P",
    )


class TestTraceFormat:
    def test_synthetic_trace_roundtrip(self, tmp_path):
        path = str(tmp_path / "t.vtrace")
        record_synthetic_trace(
            path, ["cam0", "cam1"], width=64, height=48, fps=30.0,
            gop=5, frames=12)
        header, events = trace_mod.read_trace(path)
        assert header["magic"] == trace_mod.TRACE_MAGIC
        assert header["version"] == trace_mod.TRACE_VERSION
        assert trace_mod.trace_devices(events) == ["cam0", "cam1"]
        frames = list(trace_mod.iter_frames(events, "cam0"))
        assert len(frames) == 12
        assert [e["key"] for e in frames[:6]] == [
            True, False, False, False, False, True]
        # Decoding is pure: two decodes of the same event are byte-equal.
        a, b = trace_mod.decode_frame(frames[3]), trace_mod.decode_frame(frames[3])
        assert a.shape == (48, 64, 3) and a.dtype == np.uint8
        np.testing.assert_array_equal(a, b)

    def test_payload_frames_roundtrip_losslessly(self, tmp_path):
        path = str(tmp_path / "p.vtrace")
        rng = np.random.default_rng(7)
        frames = [rng.integers(0, 256, (8, 10, 3), dtype=np.uint8)
                  for _ in range(3)]
        w = trace_mod.TraceWriter(path)
        w.stream_event("camP", width=10, height=8, fps=30.0, gop=1,
                       kind="packet")
        for i, f in enumerate(frames):
            w.frame_event("camP", pts=i, dts=i, is_keyframe=True, packet=i,
                          timestamp_ms=1000 + i, time_base=1 / 90000,
                          frame=f)
        w.close()
        _, events = trace_mod.read_trace(path)
        assert events[-1]["ev"] == "end"
        got = [trace_mod.decode_frame(e)
               for e in trace_mod.iter_frames(events, "camP")]
        for a, b in zip(frames, got):
            np.testing.assert_array_equal(a, b)

    def test_torn_tail_is_tolerated(self, tmp_path):
        """A crash mid-append leaves a torn final line; the reader must
        keep every complete event instead of refusing the trace."""
        path = str(tmp_path / "torn.vtrace")
        record_synthetic_trace(path, ["cam0"], width=32, height=24,
                               fps=30.0, frames=5)
        with open(path, "a", encoding="utf-8") as fh:
            fh.write('{"ev": "frame", "device": "cam0", "trunc')
        _, events = trace_mod.read_trace(path)
        assert len(list(trace_mod.iter_frames(events, "cam0"))) == 5


class TestRecorder:
    def test_recording_bus_taps_publishes(self, tmp_path):
        path = str(tmp_path / "bus.vtrace")
        bus = MemoryFrameBus()
        rec = TraceRecorder(path)
        rbus = RecordingBus(bus, rec)
        rbus.create_stream("cam0", 64 * 48 * 3)
        frame = np.full((48, 64, 3), 7, np.uint8)
        for i in range(3):
            rbus.publish("cam0", frame, _meta(packet=i))
        assert bus.head("cam0") == 3          # delegation reached the bus
        rec.close()
        _, events = trace_mod.read_trace(path)
        recorded = list(trace_mod.iter_frames(events, "cam0"))
        assert len(recorded) == 3
        np.testing.assert_array_equal(trace_mod.decode_frame(recorded[0]), frame)
        # stream event recorded exactly once despite three publishes
        assert sum(1 for e in events if e.get("ev") == "stream") == 1

    def test_worker_flight_recorder_tap(self, tmp_path):
        """cfg.trace_dir turns the stock ingest worker into a recorder:
        the trace re-delivers byte-identical frames through replay://."""
        src_url = "test://pattern?w=64&h=48&fps=30&gop=5&pace=0&frames=10"
        bus = MemoryFrameBus()
        cfg = WorkerConfig(
            rtsp_endpoint=src_url, device_id="cam1", bus_backend="memory",
            max_frames=10, trace_dir=str(tmp_path))
        w = IngestWorker(cfg, bus=bus)
        bus.touch_query("cam1")     # decode everything, not just keyframes
        w.run()
        trace_path = str(tmp_path / "cam1.vtrace")
        assert os.path.exists(trace_path)
        player = TracePlayer(trace_path)
        assert player.devices == ["cam1"]
        replayed = [f for _, f, _ in player.iter_frames("cam1")]
        assert len(replayed) == w._published == 10

        # Byte identity vs the original source, frame for frame.
        src = open_source(src_url)
        src.open()
        originals = []
        while src.grab() is not None:
            originals.append(src.retrieve())
        for a, b in zip(originals, replayed):
            np.testing.assert_array_equal(a, b)


class TestReplaySource:
    def test_url_scheme_routes_to_replay_source(self, tmp_path):
        path = str(tmp_path / "r.vtrace")
        record_synthetic_trace(path, ["cam0"], width=32, height=24,
                               fps=30.0, frames=4)
        src = open_source(f"replay://{path}?device=cam0&pace=0")
        assert isinstance(src, ReplaySource)

    def test_delivers_recorded_bytes_then_eof(self, tmp_path):
        path = str(tmp_path / "r.vtrace")
        record_synthetic_trace(path, ["cam0"], width=32, height=24,
                               fps=30.0, frames=6)
        src = open_source(f"replay://{path}?device=cam0&pace=0")
        src.open()
        assert (src.width, src.height) == (32, 24)
        got = []
        while (pkt := src.grab()) is not None:
            got.append((pkt.packet, src.retrieve()))
        assert len(got) == 6                      # loop=0: bounded
        want = [f for _, f, _ in TracePlayer(path).iter_frames("cam0")]
        for (_, a), b in zip(got, want):
            np.testing.assert_array_equal(a, b)

    def test_start_resumes_mid_gop_with_keyframe_entry(self, tmp_path):
        """Migration resume leg: ``start=N`` slices to the handoff cursor
        and must report the FIRST remaining packet as a keyframe even
        mid-GOP — trace events decode standalone, and a fresh worker's
        lazy-decode valve would otherwise skip exactly the cursor packet
        (no client-activity stamp exists yet on the destination)."""
        path = str(tmp_path / "r.vtrace")
        record_synthetic_trace(path, ["cam0"], width=32, height=24,
                               fps=30.0, gop=8, frames=12)
        src = open_source(f"replay://{path}?device=cam0&pace=0&start=5")
        src.open()
        pkts = []
        while (pkt := src.grab()) is not None:
            pkts.append(pkt)
        assert [p.packet for p in pkts] == list(range(5, 12))
        assert pkts[0].is_keyframe            # cursor packet promoted
        assert not pkts[2].is_keyframe        # packet 7: recorded flag kept
        assert pkts[3].is_keyframe            # packet 8: real gop boundary

    def test_start_zero_keeps_recorded_keyframe_flags(self, tmp_path):
        path = str(tmp_path / "r.vtrace")
        record_synthetic_trace(path, ["cam0"], width=32, height=24,
                               fps=30.0, gop=8, frames=4)
        src = open_source(f"replay://{path}?device=cam0&pace=0")
        src.open()
        flags = []
        while (pkt := src.grab()) is not None:
            flags.append(pkt.is_keyframe)
        assert flags == [True, False, False, False]

    def test_ambiguous_device_errors(self, tmp_path):
        path = str(tmp_path / "multi.vtrace")
        record_synthetic_trace(path, ["a", "b"], width=32, height=24,
                               fps=30.0, frames=2)
        src = ReplaySource(f"replay://{path}?pace=0")
        with pytest.raises(ConnectionError, match="device"):
            src.open()

    def test_missing_trace_errors(self, tmp_path):
        src = ReplaySource(f"replay://{tmp_path}/absent.vtrace")
        with pytest.raises(ConnectionError):
            src.open()


@pytest.fixture(scope="module")
def lockstep_env(tmp_path_factory):
    """One small trace + one baseline lockstep run, shared by the
    determinism and divergence tests (the replay itself is the expensive
    part: each run compiles the bucket-1 serving program)."""
    from video_edge_ai_proxy_tpu.replay.harness import lockstep_checksum

    path = str(tmp_path_factory.mktemp("lockstep") / "d.vtrace")
    record_synthetic_trace(path, ["cam0"], width=64, height=48,
                           fps=30.0, frames=8)
    baseline = lockstep_checksum(path, model="tiny_yolov8")
    return path, baseline


class TestLockstepDeterminism:
    def test_two_replays_are_bit_identical(self, lockstep_env):
        from video_edge_ai_proxy_tpu.replay.harness import lockstep_checksum

        path, baseline = lockstep_env
        again = lockstep_checksum(path, model="tiny_yolov8")
        assert baseline["frames"] == again["frames"] == 8
        assert baseline["checksum"] == again["checksum"]
        assert 0 <= baseline["checksum"] <= CHECKSUM_MASK

    def test_seeded_numerics_fault_diverges(self, lockstep_env):
        """Negative control: nudging ONE weight element must move the
        content checksum — proof it hashes the numerics, not the shapes
        (the r4/r5 valid.sum() could not see a box-decode bug)."""
        from video_edge_ai_proxy_tpu.replay.harness import lockstep_checksum

        path, baseline = lockstep_env

        def perturb(variables):
            import jax.numpy as jnp

            state = {"done": False}

            def walk(node):
                if isinstance(node, dict):
                    return {k: walk(v) for k, v in node.items()}
                if not state["done"] and getattr(node, "ndim", 0) >= 2:
                    state["done"] = True
                    flat = node.reshape(-1)
                    flat = flat.at[0].add(0.25)
                    return flat.reshape(node.shape)
                return node

            out = walk(variables)
            assert state["done"], "no weight tensor found to perturb"
            return out

        bad = lockstep_checksum(path, model="tiny_yolov8", perturb=perturb)
        assert bad["checksum"] != baseline["checksum"]


class TestLockstepMesh:
    """The dp-mesh replay against one device (chip_smoke.py --chips 4 runs
    the same comparison on real chips): the mesh step is a shard_map over
    dp, every slice running the single-chip program on its own rows."""

    @pytest.mark.parametrize("dp", [1, 4])
    def test_mesh_equals_one_device_running_the_shards_in_turn(
            self, tmp_path, dp):
        import jax

        from video_edge_ai_proxy_tpu.engine.collector import stream_shard
        from video_edge_ai_proxy_tpu.parallel import make_mesh
        from video_edge_ai_proxy_tpu.replay.harness import lockstep_checksum

        names, i = [], 0          # one stream on every shard
        while len({stream_shard(n, dp) for n in names}) < dp:
            names.append(f"cam{i}")
            i += 1
        path = str(tmp_path / "m.vtrace")
        record_synthetic_trace(path, names, width=64, height=48,
                               fps=30.0, frames=4)
        one = lockstep_checksum(path, model="tiny_yolov8", shards=dp)
        mesh = lockstep_checksum(
            path, model="tiny_yolov8",
            mesh=make_mesh(dp=dp, devices=jax.devices()[:dp]))
        assert one["frames"] == mesh["frames"] == 4 * len(names)
        assert one["checksum"] == mesh["checksum"] != 0

    def test_dp_mesh_step_is_a_shard_map_only_when_the_mesh_is_dp_only(self):
        """dp-only: manual over dp (a Pallas kernel cannot be partitioned
        by the TPU compiler, and rows are independent). With a model axis
        the compiler's partitioning stays, as before."""
        import jax

        from video_edge_ai_proxy_tpu.engine.runner import build_serving_step
        from video_edge_ai_proxy_tpu.models import registry
        from video_edge_ai_proxy_tpu.parallel import make_mesh

        spec = registry.get("tiny_yolov8")
        model = spec.build()
        plain = build_serving_step(model, spec)
        dp_only = build_serving_step(
            model, spec, mesh=make_mesh(dp=2, devices=jax.devices()[:2]))
        with_tp = build_serving_step(
            model, spec,
            mesh=make_mesh(dp=2, tp=2, devices=jax.devices()[:4]))
        assert dp_only.__name__ == "sharded"
        assert with_tp.__code__ is plain.__code__


class TestFaultPlan:
    def test_unknown_kind_rejected(self):
        with pytest.raises(ValueError, match="unknown fault kind"):
            FaultEvent(at_s=1.0, kind="meteor_strike")

    def test_pop_due_is_monotone_and_ordered(self):
        plan = FaultPlan([
            FaultEvent(at_s=5.0, kind="bus_stall", duration_s=1.0),
            FaultEvent(at_s=1.0, kind="camera_kill", device_id="c0"),
            FaultEvent(at_s=3.0, kind="camera_restore", device_id="c0"),
        ])
        assert [e.kind for e in plan.pop_due(1.5)] == ["camera_kill"]
        assert plan.pop_due(1.5) == []            # cursor advanced
        assert [e.kind for e in plan.pop_due(10.0)] == [
            "camera_restore", "bus_stall"]
        plan.reset()
        assert len(plan.pop_due(10.0)) == 3

    def test_json_roundtrip(self):
        plan = FaultPlan.default_churn([f"d{i}" for i in range(4)], 100.0)
        clone = FaultPlan.from_json(plan.to_json())
        assert [(e.at_s, e.kind, e.device_id, e.duration_s)
                for e in clone.events] == \
               [(e.at_s, e.kind, e.device_id, e.duration_s)
                for e in plan.events]

    def test_default_churn_shape(self):
        plan = FaultPlan.default_churn(["a", "b", "c"], 120.0)
        kinds = [e.kind for e in plan.events]
        assert kinds == ["camera_kill", "frame_gap", "camera_restore",
                         "bus_stall", "slow_subscriber"]
        kill = next(e for e in plan.events if e.kind == "camera_kill")
        restore = next(e for e in plan.events if e.kind == "camera_restore")
        assert kill.device_id == restore.device_id == "a"
        assert kill.at_s < restore.at_s <= 120.0


class TestChecksum:
    def _detect_out(self, x1=10.0):
        import jax.numpy as jnp

        return {
            "boxes": jnp.asarray([[[x1, 20.0, 30.0, 40.0]]], jnp.float32),
            "scores": jnp.asarray([[0.9]], jnp.float32),
            "classes": jnp.asarray([[3]], jnp.int32),
            "valid": jnp.asarray([[1]], jnp.int32),
        }

    def test_detect_checksum_sees_box_coordinates(self):
        a = int(np.asarray(device_checksum(self._detect_out(x1=10.0))))
        b = int(np.asarray(device_checksum(self._detect_out(x1=11.0))))
        assert a != b                      # 1 px box move -> different hash

    def test_invalid_rows_do_not_contribute(self):
        import jax.numpy as jnp

        out = self._detect_out()
        out["valid"] = jnp.zeros_like(out["valid"])
        assert int(np.asarray(device_checksum(out))) == 0

    def test_golden_lookup_and_drift(self, tmp_path):
        path = str(tmp_path / "goldens.json")
        with open(path, "w") as f:
            json.dump({"bench:m:cpu:2x2": 123}, f)
        assert golden_lookup("bench:m:cpu:2x2", path) == 123
        assert golden_lookup("bench:other:cpu:2x2", path) is None
        assert check_golden("bench:m:cpu:2x2", 123, tool="t", path=path) == 123
        with pytest.raises(SystemExit, match="drift"):
            check_golden("bench:m:cpu:2x2", 124, tool="t", path=path)
        # missing golden: record-only, never fatal
        assert check_golden("bench:new:cpu:2x2", 9, tool="t", path=path) is None


class TestFleetSoakMini:
    def test_churn_soak_routes_and_recovers(self):
        """4-stream, 2-family mini soak with a kill/re-add cycle: results
        flow, nothing crosses model families, and the artifact carries the
        acceptance fields (the >=120 s run is tools/soak_replay.py)."""
        from video_edge_ai_proxy_tpu.replay.harness import run_fleet_soak

        plan = FaultPlan([
            FaultEvent(at_s=1.0, kind="camera_kill", device_id="fleet00"),
            FaultEvent(at_s=2.5, kind="camera_restore", device_id="fleet00"),
        ])
        out = run_fleet_soak(
            duration_s=5.0, fleet={"tiny_yolov8": 2, "tiny_resnet": 2},
            src_hw=(48, 64), fault_plan=plan, sample_every_s=1.0,
            timeline_bin_s=2.0)
        assert out["streams"] == 4
        assert out["misrouted_results"] == 0
        assert [f["kind"] for f in out["faults_applied"]] == [
            "camera_kill", "camera_restore"]
        assert sum(out["published"].values()) > 0
        for key in ("per_family_latency_ms", "bucket_fill_timeline",
                    "step_cache", "subscriber_drops"):
            assert key in out
        assert out["step_cache"]["final"] >= 1
        # the killed camera kept suppressing while down
        assert out["suppressed"]["fleet00"] > 0
