"""A clip camera's window on the device (ISSUE 30): the windowed form of
the clip step (``runner._windowed``), the pool that holds the windows
(``engine/stream_state.py`` ``ClipWindowPool``) and the one-device engine
that serves through both, against the two-argument clip step on the same
reads. CPU backend, tiny models: results and counts only."""

import time

import jax
import numpy as np
import pytest

from video_edge_ai_proxy_tpu.bus.interface import FrameMeta
from video_edge_ai_proxy_tpu.bus.memory_bus import MemoryFrameBus
from video_edge_ai_proxy_tpu.engine import Collector, InferenceEngine
from video_edge_ai_proxy_tpu.engine.runner import build_serving_step
from video_edge_ai_proxy_tpu.engine.stream_state import ClipWindowPool
from video_edge_ai_proxy_tpu.models import registry as models
from video_edge_ai_proxy_tpu.obs import registry
from video_edge_ai_proxy_tpu.uplink.queue import AnnotationQueue
from video_edge_ai_proxy_tpu.utils.config import EngineConfig

MODEL = "tiny_videomae"
H, W = 24, 32
GEOM = (H, W, 3)
F = H * W * 3
KEYS = ("top_probs", "top_ids")


@pytest.fixture(scope="module")
def steps():
    spec = models.get(MODEL)
    module, variables = spec.init_params(jax.random.PRNGKey(0))
    plain = jax.jit(build_serving_step(module, spec))
    windowed = jax.jit(build_serving_step(module, spec, window=True),
                       donate_argnums=(2,))
    return spec, variables, plain, windowed


class _Sim:
    """Cameras, a window pool and both steps: every round the cameras named
    publish one frame each, the windowed step runs on the single frames,
    and every row it would emit is compared, bit for bit, with the
    two-argument step on that camera's last ``clip_len`` frames stacked."""

    def __init__(self, steps, buckets=(1, 2, 4)):
        self.spec, self.variables, self.plain, self.windowed = steps
        self.L = self.spec.clip_len
        self.pool = ClipWindowPool(self.L, buckets)
        self.rng = np.random.default_rng(3)
        self.seen = {}
        self.emitted = []           # [(round, camera)]
        self.rounds = 0

    def round(self, cams, bucket, rows=None):
        frames = np.zeros((bucket,) + GEOM, np.uint8)
        for i, cam in enumerate(cams):
            f = self.rng.integers(0, 256, GEOM, dtype=np.uint8)
            self.seen.setdefault(cam, []).append(f)
            frames[i if rows is None else rows[i]] = f
        plan = self.pool.plan(cams, GEOM, bucket, rows=rows)
        out = dict(self.windowed(self.variables, frames,
                                 self.pool.window(GEOM), plan["idx"],
                                 plan["pos"]))
        self.pool.put(GEOM, out.pop("window"))
        clips = np.zeros((bucket, self.L) + GEOM, np.uint8)
        at = {}
        for j in plan["emit"]:
            r = j if rows is None else rows[j]
            clips[r] = np.stack(self.seen[cams[j]][-self.L:])
            at[cams[j]] = r
        want = self.plain(self.variables, clips)
        for cam, r in at.items():
            for k in KEYS:
                np.testing.assert_array_equal(
                    np.asarray(out[k][r]), np.asarray(want[k][r]),
                    err_msg=f"round {self.rounds} {cam} {k}")
            self.emitted.append((self.rounds, cam))
        self.rounds += 1
        return [cams[j] for j in plan["emit"]], plan

    def forget(self, cam):
        """The camera leaves: its slot is freed, its frames forgotten."""
        self.pool.pop(cam)
        del self.seen[cam]


def _one_pos(sim):
    cams = ["a", "b", "c", "d"]
    for k in range(2 * sim.L + 1):           # the ring wraps twice
        emit, plan = sim.round(cams, 4)
        assert len(set(plan["pos"])) == 1     # every row the same position
        assert emit == (cams if k >= sim.L - 1 else [])


def _own_pos(sim):
    cams = ["a", "b", "c"]
    for k in range(2 * sim.L + 2):            # camera i joins in round i
        here = cams[:k + 1]
        emit, plan = sim.round(here, 4)
        if k >= len(cams):
            assert len(set(plan["pos"][:3])) == 3
        assert emit == [c for i, c in enumerate(cams[:k + 1])
                        if k - i >= sim.L - 1]


def _pad_rows(sim):
    for k in range(sim.L + 2):
        emit, plan = sim.round(["a", "b", "c"], 4)
        assert plan["idx"][3] == sim.pool.capacity(GEOM)    # dropped
    for k in range(2):                        # and a batch that is mostly pad
        emit, plan = sim.round(["b"], 4)
        assert emit == ["b"]
        assert list(plan["idx"][1:]) == [sim.pool.capacity(GEOM)] * 3
    # rows placed apart, as a shard-segmented group places them
    emit, plan = sim.round(["a", "c"], 4, rows=[1, 3])
    assert emit == ["a", "c"]
    assert plan["idx"][0] == plan["idx"][2] == sim.pool.capacity(GEOM)


def _absent_row(sim):
    for k in range(sim.L + 1):
        sim.round(["a", "b"], 2)
    for k in range(3):                        # b has no new frame
        assert sim.round(["a"], 1)[0] == ["a"]
    # b's window was left as it was: its last L frames, read before and now
    assert sim.round(["a", "b"], 2)[0] == ["a", "b"]
    assert sim.round(["b"], 1)[0] == ["b"]


def _slot_re_owned(sim):
    for k in range(sim.L + 1):
        sim.round(["a", "b"], 2)
    slot_b = sim.pool._streams["b"][1]
    sim.forget("b")
    for k in range(sim.L + 2):
        emit, plan = sim.round(["a", "c"], 2)
        assert sim.pool._streams["c"][1] == slot_b          # b's old slot
        # c is not emitted from a window that still holds b's frames
        assert emit == (["a", "c"] if k >= sim.L - 1 else ["a"])
    assert sim.pool.capacity(GEOM) == 2


def _pool_grown(sim):
    for k in range(sim.L + 1):
        sim.round(["a", "b"], 2)
    assert sim.pool.capacity(GEOM) == 2
    for k in range(sim.L + 1):                # two more: the buffer doubles
        emit, plan = sim.round(["a", "b", "c", "d"], 4)
        assert sim.pool.capacity(GEOM) == 4
        # the windows held before the growth are emitted straight on
        assert emit == (["a", "b", "c", "d"] if k >= sim.L - 1
                        else ["a", "b"])
    sim.round(["e"], 1)                       # a fifth: two buckets of four
    assert sim.pool.capacity(GEOM) == 8
    assert sim.round(["a", "d"], 2)[0] == ["a", "d"]


@pytest.mark.parametrize("case", [
    _one_pos, _own_pos, _pad_rows, _absent_row, _slot_re_owned, _pool_grown])
def test_windowed_step_is_the_two_argument_step_on_the_same_reads(
        steps, case):
    sim = _Sim(steps)
    case(sim)
    assert sim.emitted                        # something was compared


def test_a_reversed_window_is_another_result(steps):
    """The fault the benchmark's rehearsal plants (frames handed over
    newest first) belongs in the windowed step's ordering now: the same
    window read backwards gives other numbers."""
    sim = _Sim(steps)
    for k in range(sim.L):
        emit, _ = sim.round(["a"], 1)
    clips = np.stack(sim.seen["a"][-sim.L:])[None]
    fwd = sim.plain(sim.variables, clips)
    rev = sim.plain(sim.variables, clips[:, ::-1])
    assert not np.array_equal(np.asarray(fwd["top_probs"]),
                              np.asarray(rev["top_probs"]))


class TestPool:
    def _pool(self, buckets=(1, 2, 4)):
        noted = []
        return ClipWindowPool(
            3, buckets, note_restart=lambda r, n: noted.append((r, n))), noted

    def test_capacity_is_a_bucket_then_multiples_of_the_largest(self):
        pool, _ = self._pool()
        for slots, cap in ((1, 1), (2, 2), (3, 4), (4, 4), (5, 8), (9, 12)):
            pool.ensure(GEOM, slots)
            assert pool.capacity(GEOM) == cap
            assert pool.window(GEOM).shape == (cap, 3) + GEOM
        assert pool.nbytes() == 12 * 3 * F

    def test_restart_counts_windows_that_held_a_frame(self):
        pool, noted = self._pool()
        pool.plan(["a", "b"], GEOM, 2)
        assert pool.restart(["a", "c"], "shed") == 1      # c is unknown
        assert pool.restart(["a"], "shed") == 0           # already empty
        assert noted == [("shed", 1)]
        assert (pool.held("a"), pool.held("b")) == (0, 1)
        # a restarted window is full again after clip_len frames
        emits = [pool.plan(["a"], GEOM, 1)["emit"] for _ in range(3)]
        assert emits == [[], [], [0]]

    def test_another_geometry_is_another_window(self):
        pool, noted = self._pool()
        for _ in range(3):
            plan = pool.plan(["a"], GEOM, 1)
        assert plan["emit"] == [0]
        other = (H // 2, W, 3)
        assert pool.plan(["a"], other, 1)["emit"] == []
        assert noted == [("geometry", 1)]
        assert pool.held("a") == 1 and pool.capacity(other) == 1
        # the slot it left is free for the next stream of that geometry
        assert pool.plan(["b"], GEOM, 1)["idx"][0] == 0

    def test_a_lost_buffer_restarts_every_stream_of_its_geometry(self):
        pool, noted = self._pool()
        other = (H // 2, W, 3)
        pool.plan(["a", "b"], GEOM, 2)
        pool.plan(["c"], other, 1)
        pool.lost(GEOM, "step_error")
        assert noted == [("step_error", 2)]
        assert (pool.held("a"), pool.held("b"), pool.held("c")) == (0, 0, 1)
        assert pool.nbytes() == 3 * other[0] * other[1] * 3
        pool.plan(["a"], GEOM, 1)                 # a new, zeroed buffer
        assert pool.window(GEOM).shape == (2, 3) + GEOM
        assert not np.asarray(pool.window(GEOM)).any()

    def test_pop_frees_the_slot(self):
        pool, _ = self._pool()
        pool.plan(["a", "b"], GEOM, 2)
        assert len(pool) == 2 and set(pool) == {"a", "b"}
        pool.pop("a")
        pool.pop("nobody")
        assert list(pool) == ["b"] and pool.held("a") == 0
        assert pool.plan(["c"], GEOM, 1)["idx"][0] == 0


def _restarts():
    fam = {f.name: f for f in registry.families()}
    return fam["vep_clip_window_restarts_total"]


def _rows(home):
    fam = {f.name: f for f in registry.families()}
    return fam["vep_clip_window_rows_total"].labels(home).value


class _Drive:
    """An engine stepped by hand (collect -> dispatch -> drain), so that a
    test says which frames a tick reads. ``results`` holds, per emitted
    row, (camera, the step's top-5 for it, the batch's bucket, the row)."""

    def __init__(self, bus, cams, size=F, **cfg_kw):
        self.bus, self.cams = bus, list(cams)
        for cam in self.cams:
            bus.create_stream(cam, size)
        cfg = EngineConfig(model=MODEL, batch_buckets=(1, 2, 4), tick_ms=5,
                           prefetch=False, ladder=False, **cfg_kw)
        self.eng = InferenceEngine(
            bus, cfg, annotations=AnnotationQueue(handler=lambda b: True))
        self.eng.warmup()
        self.rng = np.random.default_rng(11)
        self.seen = {cam: [] for cam in self.cams}
        self.results = []
        self.traces = []

    def publish(self, cams=None, h=H, w=W):
        for cam in cams or self.cams:
            f = self.rng.integers(0, 256, (h, w, 3), dtype=np.uint8)
            self.bus.publish(cam, f, FrameMeta(
                width=w, height=h, channels=3,
                timestamp_ms=int(time.time() * 1000), is_keyframe=True))
            self.seen[cam].append(f)

    def tick(self):
        """One tick's collect, dispatch and drain; returns the cameras
        emitted."""
        eng = self.eng
        groups = eng._collector.collect()
        self.traces.append(dict(eng._collector.last_trace))
        eng._dispatch(groups, time.perf_counter())
        out = []
        while not eng._drain_q.empty():
            inflight = eng._drain_q.get()
            try:
                host = {k: np.asarray(v) for k, v in inflight.outputs.items()}
                g = inflight.group
                emit = range(len(g.device_ids)) if inflight.emit is None \
                    else inflight.emit
                for i in emit:
                    r = i if g.rows is None else g.rows[i]
                    self.results.append(
                        (g.device_ids[i], {k: host[k][r] for k in KEYS},
                         g.bucket, r))
                    out.append(g.device_ids[i])
                eng._emit(inflight)
            finally:
                eng._collector.release(inflight.group)
                eng._drain_q.task_done()
        return out

    def round(self, cams=None, **kw):
        self.publish(cams, **kw)
        return self.tick()

    def expect_last(self, steps, cam):
        """The newest result of ``cam`` is the two-argument step's on its
        last clip_len frames, bit for bit (in a batch of the same bucket,
        at the same row: the arithmetic of a row follows the batch's
        shape)."""
        spec, variables, plain, _ = steps
        got, bucket, row = [r[1:] for r in self.results if r[0] == cam][-1]
        clip = np.stack(self.seen[cam][-spec.clip_len:])
        clips = np.zeros((bucket,) + clip.shape, np.uint8)
        clips[row] = clip
        want = plain(variables, clips)
        for k in KEYS:
            np.testing.assert_array_equal(got[k], np.asarray(want[k][row]))


@pytest.fixture()
def bus():
    b = MemoryFrameBus()
    yield b
    b.close()


class TestEngine:
    L = 4

    def test_first_result_follows_exactly_clip_len_reads(self, bus, steps):
        d = _Drive(bus, ["a", "b"], hbm=True)
        rows0 = _rows("device")
        for k in range(self.L + 2):
            emitted = d.round()
            assert emitted == (["a", "b"] if k >= self.L - 1 else [])
        assert d.eng._collector._clips == {}      # no window on the host
        assert _rows("device") - rows0 == 2 * (self.L + 2)
        for cam in d.cams:
            d.expect_last(steps, cam)
        # the window is the pool's, under the memory ledger
        pool = d.eng._window_pools[MODEL]
        assert len(pool) == 2 and pool.capacity(GEOM) == 2
        assert pool.nbytes() == 2 * self.L * F
        assert d.eng.hbm.pools()["pools"]["clip_windows"]["bytes"] \
            == pool.nbytes()

    def test_a_batch_with_no_full_window_only_writes_its_frames(self, bus):
        """Rounds 1 .. clip_len-1: the frames go into the windows by the
        write alone (no model step runs, nothing reaches the drain), and
        the fault ledger counts the rows out as still filling."""
        d = _Drive(bus, ["a", "b"], fault=True)
        eng = d.eng
        for k in range(self.L - 1):
            assert d.round() == []
            assert eng._drain_q.empty()
            kinds = {k[4:] for k in eng._step_cache}
            assert kinds == {(2, "write")}        # the write, no step yet
            with eng._collector._pool_lock:       # the lease went back
                assert all(not slot["leased"]
                           for slot in eng._collector._pool.values())
        window = np.asarray(eng._window_pools[MODEL].window(GEOM))
        for slot, cam in enumerate(d.cams):
            np.testing.assert_array_equal(
                window[slot, :self.L - 1], np.stack(d.seen[cam]))
        assert d.round() == ["a", "b"]
        assert {k[4:] for k in eng._step_cache} == {(2, "write"), (2,)}
        bal = eng.faults.ledger.balance()
        assert bal["dropped"] == {"window_filling": 2 * (self.L - 1)}
        assert bal["emitted"] == 2 and bal["lost"] == 0

    def test_prewarm_compiles_the_windowed_step_and_the_write(self, bus):
        cfg = EngineConfig(model=MODEL, batch_buckets=(1, 2, 4), tick_ms=5,
                           ladder=False, prewarm=[[H, W, 4]])
        eng = InferenceEngine(bus, cfg)
        eng.start()
        try:
            keys = set(eng._step_cache)
            assert (MODEL, "classic", (H, W), 4, 4) in keys
            assert (MODEL, "classic", (H, W), 4, 4, "write") in keys
            assert not any(len(k) == 4 for k in keys)   # no two-argument one
            pool = eng._window_pools[MODEL]
            assert pool.capacity(GEOM) == 4 and len(pool) == 0
            assert sorted(c["model"] for c in
                          eng.perf.snapshot()["compiles"]) \
                == [MODEL, MODEL + "/window_write"]
        finally:
            eng.stop()

    def test_a_fast_path_clip_frame_is_copied_once(self, bus):
        d = _Drive(bus, ["a", "b", "c"])
        d.round()                                 # first sight: generic path
        for _ in range(self.L + 1):
            d.round()
            tr = d.traces[-1]
            assert tr["bytes_read"] == 3 * F
            # ring of the bus -> the camera's row of a pooled batch, and a
            # bucket of four zeroes no row again: at most 2, here 1
            assert tr["bytes_copied"] / tr["bytes_read"] <= 2
            assert tr["bytes_copied"] == 3 * F

    @pytest.mark.parametrize("home", ["device", "host"])
    def test_results_are_those_of_the_other_home(self, bus, steps, home):
        """The same reads through the window on the device and through the
        host rings (a dp mesh of one chip keeps them): each is the
        two-argument step on the camera's last frames."""
        d = _Drive(bus, ["a", "b", "c"],
                   **({"mesh": {"dp": 1}} if home == "host" else {}))
        assert d.eng._device_windows == (home == "device")
        schedule = [None] * self.L + [["a"], ["a", "c"], None, ["b"], None]
        n0 = _rows(home)
        for cams in schedule:
            for cam in d.round(cams):
                d.expect_last(steps, cam)
        assert [r[0] for r in d.results].count("a") == 5
        assert _rows(home) > n0
        assert bool(d.eng._collector._clips) == (home == "host")

    def test_a_geometry_drift_restarts_the_window(self, bus, steps):
        d = _Drive(bus, ["a", "b"], size=4 * F)
        n0 = _restarts().labels("geometry").value
        for _ in range(self.L):
            d.round()
        assert d.round() == ["a", "b"]
        # a's camera is reconfigured: nothing from a until clip_len reads
        # at the new geometry
        for k in range(self.L + 1):
            d.publish(["a"], h=2 * H, w=W)
            d.publish(["b"])
            emitted = d.tick()
            assert ("a" in emitted) == (k >= self.L - 1)
            assert "b" in emitted
        assert _restarts().labels("geometry").value - n0 == 1
        d.expect_last(steps, "a")
        d.expect_last(steps, "b")
        pool = d.eng._window_pools[MODEL]
        assert pool.capacity((2 * H, W, 3)) == 1

    def test_a_dropped_batch_restarts_the_window(self, bus, steps,
                                                 monkeypatch):
        d = _Drive(bus, ["a", "b"])
        n0 = _restarts().labels("dropped").value
        for _ in range(self.L):
            d.round()
        # the placement fails after the frames were read: the batch is
        # dropped, and with it a frame of every window
        monkeypatch.setattr(d.eng, "_place", lambda frames: 1 / 0)
        d.publish()
        with pytest.raises(ZeroDivisionError):
            d.tick()
        monkeypatch.undo()
        assert _restarts().labels("dropped").value - n0 == 2
        for k in range(self.L + 1):
            assert d.round() == (["a", "b"] if k >= self.L - 1 else [])
        d.expect_last(steps, "a")

    def test_a_step_that_raised_loses_the_buffer_and_restarts(
            self, bus, steps, monkeypatch):
        d = _Drive(bus, ["a", "b"])
        n0 = _restarts().labels("step_error").value
        for _ in range(self.L):
            d.round()
        key = next(k for k in d.eng._step_cache if len(k) == 5)
        real = d.eng._step_cache[key]

        def broken(*args):
            for a in args:        # what a failed execution leaves: donated
                if hasattr(a, "delete"):
                    a.delete()
            raise RuntimeError("device says no")

        d.eng._step_cache[key] = broken
        d.publish()
        with pytest.raises(RuntimeError, match="device says no"):
            d.tick()
        d.eng._step_cache[key] = real
        assert _restarts().labels("step_error").value - n0 == 2
        for k in range(self.L + 1):
            assert d.round() == (["a", "b"] if k >= self.L - 1 else [])
        d.expect_last(steps, "b")

    def test_a_stream_that_leaves_frees_its_slot(self, bus, monkeypatch):
        monkeypatch.setattr(InferenceEngine, "_TRACKER_GC_GRACE_S", 0.05)
        for cam in ("a", "b"):
            bus.create_stream(cam, F)
        cfg = EngineConfig(model=MODEL, batch_buckets=(1, 2, 4), tick_ms=5,
                           ladder=False)
        eng = InferenceEngine(
            bus, cfg, annotations=AnnotationQueue(handler=lambda b: True))
        eng.start()
        try:
            def publish(cams):
                for cam in cams:
                    bus.publish(cam, np.zeros(GEOM, np.uint8), FrameMeta(
                        width=W, height=H, channels=3,
                        timestamp_ms=int(time.time() * 1000),
                        is_keyframe=True))

            deadline = time.time() + 60
            while len(eng._window_pools.get(MODEL, ())) < 2 \
                    and time.time() < deadline:
                publish(["a", "b"])
                time.sleep(0.02)
            pool = eng._window_pools[MODEL]
            assert set(pool) == {"a", "b"}
            bus.drop_stream("b")
            while "b" in set(pool) and time.time() < deadline:
                publish(["a"])
                time.sleep(0.02)
            assert set(pool) == {"a"} and "b" not in eng._window_home
        finally:
            eng.stop()


class TestCollectorHome:
    """What the collector hands on, by where it was told the window is."""

    def _collector(self, bus, **kw):
        return Collector(bus, buckets=(1, 2, 4), clip_len=3, **kw)

    def _publish(self, bus, cam, h=H, w=W, value=7):
        bus.publish(cam, np.full((h, w, 3), value, np.uint8), FrameMeta(
            width=w, height=h, channels=3,
            timestamp_ms=int(time.time() * 1000), is_keyframe=True))

    def test_device_windows_hand_on_single_frames_from_the_first_read(
            self, bus):
        bus.create_stream("a", 4 * F)
        col = self._collector(bus, device_windows=True)
        self._publish(bus, "a", value=1)
        (g,) = col.collect()                      # first sight: generic path
        assert g.window == 3 and g.frames.shape == (1,) + GEOM
        assert g.lease is None and (g.frames == 1).all()
        self._publish(bus, "a", value=2)
        (g,) = col.collect()                      # fast path: a pooled row
        assert g.window == 3 and g.frames.shape == (1,) + GEOM
        assert (g.frames == 2).all()
        self._publish(bus, "a", h=2 * H, value=3)   # drift: spilled, whole
        (g,) = col.collect()
        assert g.window == 3 and g.frames.shape == (1, 2 * H, W, 3)
        assert col._clips == {} and col.take_window_breaks() == []

    def test_without_the_argument_a_group_carries_whole_clips(self, bus):
        bus.create_stream("a", F)
        col = self._collector(bus)
        for k in range(3):
            self._publish(bus, "a", value=k)
            groups = col.collect()
        (g,) = groups
        assert g.window == 0 and g.frames.shape == (1, 3) + GEOM
        assert set(col._clips) == {"a"}

    def test_a_sharded_collector_keeps_host_rings(self, bus):
        col = Collector(bus, buckets=(2, 4), clip_len=3, shards=2,
                        device_windows=True)
        assert not col._device_windows

    def test_a_frame_that_cannot_be_handed_on_is_reported(self, bus):
        bus.create_stream("a", F)
        col = self._collector(bus, device_windows=True)
        bus.publish("a", np.zeros(17, np.uint8), FrameMeta(
            width=17, height=1, channels=1,
            timestamp_ms=int(time.time() * 1000), is_keyframe=True))
        assert col.collect() == []
        assert col.take_window_breaks() == ["a"]
        assert col.take_window_breaks() == []


def _compile_counts():
    """vep_compile_programs_total by (model, geometry, bucket)."""
    fam = {f.name: f for f in registry.families()}
    return {labels: child.value
            for labels, child in fam["vep_compile_programs_total"].children()}


class _Counted:
    """A step-cache entry that counts its calls."""

    def __init__(self, fn):
        self.fn, self.calls = fn, 0

    def __call__(self, *args):
        self.calls += 1
        return self.fn(*args)


class TestSetUp:
    """The set-up, pinned by counts (ISSUE 31): what the prewarm loads,
    what the rounds before a first full window run, and that loading side
    by side changes neither."""

    L = 4
    TAG = "tiny_vit"

    def test_no_full_step_runs_before_some_window_is_full(self, bus):
        """One clip prewarm entry, empty windows: the prewarm calls the
        windowed step once (every row padded); the clip_len - 1 rounds
        after it run the write alone, one call a round, and the step's
        second call is the round that fills the windows."""
        d = _Drive(bus, ["a", "b"])
        eng = d.eng
        eng.compile_for((H, W), 2)
        step_key = (MODEL, "classic", (H, W), 2, 2)
        assert set(eng._step_cache) == {step_key, step_key + ("write",)}
        counted = {k: _Counted(fn) for k, fn in eng._step_cache.items()}
        eng._step_cache.update(counted)
        step, write = counted[step_key], counted[step_key + ("write",)]
        for k in range(self.L - 1):
            assert d.round() == []
            assert (step.calls, write.calls) == (0, k + 1)
        assert d.round() == ["a", "b"]
        assert (step.calls, write.calls) == (1, self.L - 1)
        assert set(eng._step_cache) == set(counted)     # nothing compiled

    @pytest.mark.parametrize("cell", ["clip", "fleet", "ladder"])
    def test_start_loads_every_program_the_first_rounds_use(
            self, bus, cell):
        """``start()`` with a cell's prewarm list (benchmark/run.py
        ``prewarm_entries``: every bucket up to the one the cameras fill)
        leaves nothing to compile in the first 20 rounds: the harness's
        ``window_compiles`` 0. ``ladder``: three buckets of one model, all
        keyed by the window's serving capacity."""
        from video_edge_ai_proxy_tpu.obs import tracer

        clips = ["clip0", "clip1", "clip2"]
        tags = ["tag0", "tag1"] if cell == "fleet" else []
        buckets = (1, 2, 4) if cell == "ladder" else (4,)
        for cam in clips + tags:
            bus.create_stream(cam, F)
        prewarm = [[H, W, b, MODEL] for b in buckets]
        if tags:
            prewarm += [[H, W, 4, self.TAG]]
        cfg = EngineConfig(model=MODEL, batch_buckets=buckets, tick_ms=5,
                           ladder=False, prewarm=prewarm)
        eng = InferenceEngine(
            bus, cfg, annotations=AnnotationQueue(handler=lambda b: True),
            model_resolver=lambda d: self.TAG if d.startswith("tag")
            else MODEL)
        spans_were = (tracer.enabled, tracer.sample_every)
        tracer.clear()
        tracer.configure(enabled=True, sample_every=1)
        rows0 = _rows("device")
        eng.start()
        try:
            keys0 = set(eng._step_cache)
            compiles0 = sum(c["programs"]
                            for c in eng.perf.snapshot()["compiles"])
            assert {k[3:] for k in keys0 if k[0] == MODEL} == {
                t for b in buckets for t in ((b, 4), (b, 4, "write"))}
            assert compiles0 == len(keys0) == 2 * len(buckets) + bool(tags)
            assert eng.prewarm_status()["complete"]
            rng = np.random.default_rng(5)
            for k in range(20):
                for cam in clips + tags:
                    bus.publish(
                        cam, rng.integers(0, 256, GEOM, dtype=np.uint8),
                        FrameMeta(width=W, height=H, channels=3,
                                  timestamp_ms=int(time.time() * 1000),
                                  is_keyframe=True))
                deadline = time.time() + 60
                while time.time() < deadline and min(
                        sum(1 for e in tracer.events(cam)
                            if e["stage"] == "collect")
                        for cam in clips + tags) < k + 1:
                    time.sleep(0.002)
            while time.time() < deadline \
                    and _rows("device") - rows0 < 20 * len(clips):
                time.sleep(0.002)     # the last read's dispatch
            assert _rows("device") - rows0 == 20 * len(clips)
            assert set(eng._step_cache) == keys0
            snap = eng.perf.snapshot()
            assert sum(c["programs"] for c in snap["compiles"]) == compiles0
            # what was loaded from shapes is what the batches run
            assert snap["aot_fallbacks"] == 0
        finally:
            eng.stop()
            tracer.clear()
            tracer.configure(enabled=spans_were[0],
                             sample_every=spans_were[1])

    ENTRIES = [[H, W, 2, MODEL], [H, W, 4, MODEL], [H, W, 4, TAG],
               [H // 2, W, 4, MODEL]]

    def _prewarmed(self, bus, sized):
        """(step-cache keys, this engine's compiles, the registry's
        counts it added) after ENTRIES were compiled in the list's order
        with the windows sized first, as ``start()`` does | one by one,
        the largest bucket first."""
        cfg = EngineConfig(model=MODEL, batch_buckets=(1, 2, 4), tick_ms=5,
                           ladder=False)
        eng = InferenceEngine(bus, cfg)
        eng.warmup()
        before = _compile_counts()
        entries = self.ENTRIES
        if sized:
            eng._size_windows(entries)
        else:
            entries = sorted(entries, key=lambda e: -e[2])
        for h, w, bucket, model in entries:
            eng.compile_for((h, w), bucket, model)
        added = {k: v - before.get(k, 0)
                 for k, v in _compile_counts().items()
                 if v != before.get(k, 0)}
        compiles = sorted((c["model"], c["geometry"], c["bucket"],
                           c["programs"])
                          for c in eng.perf.snapshot()["compiles"])
        return set(eng._step_cache), compiles, added

    def test_a_sized_list_is_its_entries_largest_first(self, bus):
        """``start()`` sizes the windows for its whole list first, so in
        any order it loads what ``compile_for`` loads entry by entry when
        the largest bucket comes first: the same step-cache keys, the same
        ``vep_compile_programs_total``; no program at a capacity nobody
        serves."""
        sized = self._prewarmed(bus, True)
        assert sized == self._prewarmed(bus, False)
        keys, compiles, added = sized
        assert len(keys) == 7                 # 3 steps + 3 writes + the tag's
        assert sum(c[3] for c in compiles) == sum(added.values()) == 7
        assert {k[4] for k in keys if len(k) > 4} == {4}    # the capacity

    def test_a_bad_entry_is_reported_and_the_others_load(self, bus):
        cfg = EngineConfig(model=MODEL, batch_buckets=(1, 2, 4), tick_ms=5,
                           ladder=False,
                           prewarm=[[H, W, 2, "no_such_model"], [H, W, 2]])
        eng = InferenceEngine(bus, cfg)
        eng.start()                   # logged, and the boot goes on
        try:
            assert {k[3:] for k in eng._step_cache} == {
                (2, 2), (2, 2, "write")}
            assert eng.prewarm_status()["complete"]
            with pytest.raises(KeyError):
                eng.compile_for((H, W), 2, "no_such_model")
        finally:
            eng.stop()

    def test_a_prewarm_call_waits_for_no_result(self, bus, monkeypatch):
        """The programs are called once each and nothing of what they
        return is fetched or blocked on: the pool takes the handle."""
        from jax._src.array import ArrayImpl

        blocked = []
        real = ArrayImpl.block_until_ready

        def spy(self):
            blocked.append(self.shape)
            return real(self)

        cfg = EngineConfig(model=MODEL, batch_buckets=(4,), tick_ms=5,
                           ladder=False)
        eng = InferenceEngine(bus, cfg)
        eng.warmup()
        try:
            ArrayImpl.block_until_ready = spy
            monkeypatch.setattr(jax, "block_until_ready",
                                lambda x: blocked.append("tree") or x)
            monkeypatch.setattr(jax, "device_get",
                                lambda x: blocked.append("get") or x)
            eng.compile_for((H, W), 4)
        finally:
            ArrayImpl.block_until_ready = real
        assert blocked == []
        assert eng._window_pools[MODEL].window(GEOM).shape \
            == (4, self.L) + GEOM


class TestHomeByKind:
    """Where a window lives is the engine's to say, a model (ISSUE 31,
    point 8): on one device the ``stream`` kind keeps the host ring, every
    other clip-taking kind gets the device window, in one engine."""

    STREAM = "tiny_videomae_lfm2"

    def _engine(self, bus, cams, **kw):
        for cam in cams:
            bus.create_stream(cam, F)
        cfg = EngineConfig(model=MODEL, batch_buckets=(1, 2, 4), tick_ms=5,
                           prefetch=False, ladder=False, **kw)
        eng = InferenceEngine(
            bus, cfg, annotations=AnnotationQueue(handler=lambda b: True),
            model_resolver=lambda d: self.STREAM if d.startswith("s")
            else MODEL)
        eng.warmup()
        return eng

    @pytest.mark.parametrize("mesh", [None, {"dp": 1}])
    def test_the_engine_says_by_device_and_kind(self, bus, mesh):
        eng = self._engine(bus, [], **({"mesh": mesh} if mesh else {}))
        assert eng._window_on_device(MODEL) == (mesh is None)
        assert eng._window_on_device("") == (mesh is None)    # the default
        assert not eng._window_on_device(self.STREAM)
        assert not eng._window_on_device("tiny_vit")          # no window

    def test_both_homes_in_one_engine(self, bus):
        eng = self._engine(bus, ["a", "s"])
        L = models.get(MODEL).clip_len
        assert models.get(self.STREAM).clip_len == L
        eng.compile_for((H, W), 1, self.STREAM)
        assert {k[3:] for k in eng._step_cache} == {(1,)}   # two-argument
        rows0 = {h: _rows(h) for h in ("device", "host")}
        rng = np.random.default_rng(2)
        homes = []
        for k in range(L + 1):
            for cam in ("a", "s"):
                bus.publish(
                    cam, rng.integers(0, 256, GEOM, dtype=np.uint8),
                    FrameMeta(width=W, height=H, channels=3,
                              timestamp_ms=int(time.time() * 1000),
                              is_keyframe=True))
            groups = eng._collector.collect()
            homes.append({g.model: (g.window, g.frames.ndim)
                          for g in groups})
            eng._dispatch(groups, time.perf_counter())
            while not eng._drain_q.empty():
                inflight = eng._drain_q.get()
                eng._emit(inflight)
                eng._collector.release(inflight.group)
                eng._drain_q.task_done()
        # a's frames go on one by one from the first read; s's ring hands
        # on whole clips from the clip_len-th
        assert homes[0] == {MODEL: (L, 4)}
        assert homes[-1] == {MODEL: (L, 4), self.STREAM: (0, 5)}
        assert set(eng._window_pools) == {MODEL}
        assert set(eng._window_pools[MODEL]) == {"a"}
        assert set(eng._collector._clips) == {"s"}
        assert _rows("device") - rows0["device"] == L + 1
        assert _rows("host") - rows0["host"] == 2

    def test_a_stream_moved_to_a_host_home_leaves_its_slot(self, bus):
        home = {"x": MODEL}
        bus.create_stream("x", F)
        cfg = EngineConfig(model=MODEL, batch_buckets=(1, 2, 4), tick_ms=5,
                           prefetch=False, ladder=False)
        eng = InferenceEngine(
            bus, cfg, annotations=AnnotationQueue(handler=lambda b: True),
            model_resolver=lambda d: home[d])
        eng.warmup()
        n0 = _restarts().labels("model").value
        L = models.get(MODEL).clip_len
        rng = np.random.default_rng(4)

        def round_():
            bus.publish(
                "x", rng.integers(0, 256, GEOM, dtype=np.uint8),
                FrameMeta(width=W, height=H, channels=3,
                          timestamp_ms=int(time.time() * 1000),
                          is_keyframe=True))
            groups = eng._collector.collect()
            eng._dispatch(groups, time.perf_counter())
            while not eng._drain_q.empty():
                inflight = eng._drain_q.get()
                eng._collector.release(inflight.group)
                eng._drain_q.task_done()

        for _ in range(2):
            round_()
        assert set(eng._window_pools[MODEL]) == {"x"}
        home["x"] = self.STREAM           # re-added under the stream head
        for _ in range(L + 1):
            round_()
        assert set(eng._window_pools[MODEL]) == set()
        assert "x" not in eng._window_home
        assert _restarts().labels("model").value - n0 == 1
