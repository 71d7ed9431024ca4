import json
import os
import pathlib
import time

import grpc
import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent

from video_edge_ai_proxy_tpu.bus import MemoryFrameBus, open_bus
from video_edge_ai_proxy_tpu.proto import pb, pb_grpc
from video_edge_ai_proxy_tpu.serve import (
    NotFound,
    ProcessError,
    ProcessManager,
    SettingsManager,
    Storage,
    StreamProcess,
)
from video_edge_ai_proxy_tpu.utils.config import Config


class TestStorage:
    """Parity with the reference's only Go tests (storage_test.go:27-94):
    Put/Get roundtrip and prefix scan over a real embedded store."""

    def test_put_get_roundtrip(self, tmp_path):
        s = Storage(str(tmp_path / "t.db"))
        s.put("/rtspprocess/", "cam1", b"hello")
        assert s.get("/rtspprocess/", "cam1") == b"hello"
        s.close()

    def test_prefix_scan(self, tmp_path):
        s = Storage(str(tmp_path / "t.db"))
        for i in range(10):
            s.put("/rtspprocess/", f"cam{i}", str(i).encode())
        s.put("/settings/", "default", b"x")
        found = s.list("/rtspprocess/")
        assert len(found) == 10 and found["cam3"] == b"3"
        s.close()

    def test_missing_raises(self, tmp_path):
        s = Storage(str(tmp_path / "t.db"))
        with pytest.raises(NotFound):
            s.get("/p/", "nope")
        s.close()

    def test_delete(self, tmp_path):
        s = Storage(str(tmp_path / "t.db"))
        s.put("/p/", "k", b"v")
        s.delete("/p/", "k")
        assert s.get_or_none("/p/", "k") is None
        s.close()

    def test_persistence_across_reopen(self, tmp_path):
        path = str(tmp_path / "t.db")
        s = Storage(path)
        s.put("/p/", "k", b"v")
        s.close()
        s2 = Storage(path)
        assert s2.get("/p/", "k") == b"v"
        s2.close()


class TestSettings:
    def test_default_then_overwrite(self, tmp_path):
        s = Storage(str(tmp_path / "t.db"))
        mgr = SettingsManager(s)
        assert mgr.edge_credentials() == ("", "")
        mgr.overwrite("key1", "secret1")
        assert mgr.edge_credentials() == ("key1", "secret1")
        # Fresh manager reads persisted record.
        assert SettingsManager(s).edge_credentials() == ("key1", "secret1")
        s.close()


def synth_url(frames=0):
    extra = f"&frames={frames}" if frames else ""
    return f"test://pattern?w=64&h=48&fps=30&gop=5{extra}"


@pytest.fixture()
def pm(tmp_path, shm_dir):
    bus = open_bus("shm", shm_dir)
    storage = Storage(str(tmp_path / "reg.db"))
    manager = ProcessManager(storage, bus, shm_dir=shm_dir)
    yield manager, bus, storage
    manager.close()
    bus.close()
    storage.close()


def _logs_grew(rest: str, cursor: int, name: str = "cam1") -> bool:
    import urllib.request

    with urllib.request.urlopen(
        rest + f"/api/v1/process/{name}/logs?since={cursor}"
    ) as resp:
        out = json.loads(resp.read())
    return out["total"] > cursor and bool(out["lines"])


def wait_for(cond, timeout=20.0, interval=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return False


class TestProcessManager:
    def test_start_spawns_worker_and_publishes(self, pm):
        manager, bus, _ = pm
        manager.start(StreamProcess(name="cam1", rtsp_endpoint=synth_url()))
        bus.touch_query("cam1")  # decode everything
        assert wait_for(lambda: bus.read_latest("cam1") is not None)
        record = manager.info("cam1")
        assert record.state.running and record.state.pid > 0
        manager.stop("cam1")
        assert manager.list() == []

    def test_worker_resource_limits_applied(self, pm):
        """Reference caps each camera container (CPUShares/log limits,
        rtsp_process_manager.go:71-78); the subprocess runner applies an
        RLIMIT_AS + niceness in the spawn path and surfaces them in Info."""
        manager, bus, _ = pm
        manager.start(StreamProcess(name="cam1", rtsp_endpoint=synth_url()))
        record = manager.info("cam1")
        assert record.limits["mem_limit_mb"] == manager._mem_limit_mb
        assert record.limits["nice"] == manager._nice
        pid = record.state.pid
        with open(f"/proc/{pid}/limits") as fh:
            line = next(l for l in fh if l.startswith("Max address space"))
        assert str(manager._mem_limit_mb << 20) in line
        with open(f"/proc/{pid}/stat") as fh:
            nice = int(fh.read().split()[18])
        assert nice == manager._nice

    def test_runaway_worker_is_contained(self, tmp_path):
        """A worker that tries to eat the host's memory hits RLIMIT_AS and
        dies (MemoryError) instead of stalling the machine — the supervisor
        restart policy then owns it. Under the limit the workers really
        run with, and 4 GiB asked for over it: a limit made small for the
        test's sake (it was 256 MB) is under what ``import numpy`` itself
        maps on a host of 8 cores or more (OpenBLAS's buffers, one set a
        thread), and a child that runs out of address space half way
        through that import does not die, it hangs at exit."""
        import subprocess
        import sys as _sys

        from video_edge_ai_proxy_tpu.serve.process_manager import (
            WORKER_MEM_LIMIT_MB, _worker_preexec,
        )

        assert WORKER_MEM_LIMIT_MB << 20 < 8 << 29      # the 4 GiB below
        proc = subprocess.run(
            [_sys.executable, "-c",
             "import numpy; numpy.ones((1 << 29,), dtype=numpy.float64)"],
            preexec_fn=lambda: _worker_preexec(
                mem_limit_mb=WORKER_MEM_LIMIT_MB, nice=0),
            capture_output=True, text=True, timeout=60,
        )
        assert proc.returncode != 0
        assert "MemoryError" in proc.stderr or "Cannot allocate" in proc.stderr

    def test_duplicate_start_conflicts(self, pm):
        manager, _, _ = pm
        manager.start(StreamProcess(name="cam1", rtsp_endpoint=synth_url()))
        with pytest.raises(ProcessError):
            manager.start(StreamProcess(name="cam1", rtsp_endpoint=synth_url()))

    def test_stop_unknown_raises(self, pm):
        manager, _, _ = pm
        with pytest.raises(ProcessError):
            manager.stop("ghost")

    def test_default_name_is_md5(self, pm):
        import hashlib

        manager, _, _ = pm
        url = synth_url()
        record = manager.start(StreamProcess(rtsp_endpoint=url))
        assert record.name == hashlib.md5(url.encode()).hexdigest()

    def test_restart_policy_always(self, pm, monkeypatch):
        """Worker exits (bounded lifetime) -> supervisor restarts it
        (Docker RestartPolicy-always parity, rtsp_process_manager.go:76)."""
        monkeypatch.setenv("vep_max_frames", "5")
        manager, bus, _ = pm
        manager.start(
            StreamProcess(name="cam1", rtsp_endpoint=synth_url())
        )
        assert wait_for(
            lambda: manager.info("cam1").state.failing_streak >= 1, timeout=30
        )

    def test_failing_streak_backoff_resets_after_stability(
            self, pm, monkeypatch):
        """ISSUE satellite: repeated worker exits grow a decorrelated-
        jitter restart backoff (RetryPolicy, bounded by
        RESTART_BACKOFF_MAX_S); once the worker stays up past the
        stability window, streak AND backoff reset so the next failure
        starts from base again."""
        import video_edge_ai_proxy_tpu.serve.process_manager as pmmod

        monkeypatch.setenv("vep_max_frames", "5")  # worker dies after 5
        manager, bus, _ = pm
        monkeypatch.setattr(manager, "STABLE_AFTER_S", 2.0)
        manager.start(StreamProcess(name="cam1", rtsp_endpoint=synth_url()))
        assert wait_for(
            lambda: manager.info("cam1").state.failing_streak >= 2,
            timeout=60,
        )
        entry = manager._entries["cam1"]
        assert 0.0 < entry.backoff_s <= pmmod.RESTART_BACKOFF_MAX_S
        # Source heals: respawned workers inherit the env WITHOUT the
        # frame cap, run stable past the window, and the streak resets.
        monkeypatch.delenv("vep_max_frames")
        assert wait_for(
            lambda: manager.info("cam1").state.failing_streak == 0,
            timeout=60,
        )
        assert entry.backoff_s == 0.0

    def test_sigkill_exit_surfaces_oom_flag(self, pm):
        """SIGKILL exit (the kernel OOM killer's signature for a subprocess
        runner) must surface as oom_killed in the process state — the
        reference reads Docker's OOMKilled for this (grpc_api.go:102-117)."""
        import os
        import signal as _signal

        manager, bus, _ = pm
        manager.start(StreamProcess(name="cam1", rtsp_endpoint=synth_url()))
        assert wait_for(
            lambda: manager.info("cam1").state.running, timeout=30
        )
        pid = manager.info("cam1").state.pid
        os.kill(pid, _signal.SIGKILL)
        # Sticky across the restart: the flag must be visible even after
        # the supervisor has already respawned the worker.
        assert wait_for(
            lambda: manager.info("cam1").state.oom_killed, timeout=30
        )

    def test_eof_reconnect_forever(self, pm):
        """A source that runs dry does NOT kill the worker — it loops waiting
        for the camera to return (reference rtsp_to_rtmp.py:186-187)."""
        manager, bus, _ = pm
        manager.start(
            StreamProcess(name="cam1", rtsp_endpoint=synth_url(frames=5))
        )
        assert wait_for(lambda: bus.read_latest("cam1") is not None)
        time.sleep(2.5)  # several EOF/reopen cycles
        record = manager.info("cam1")
        assert record.state.running and record.state.failing_streak == 0

    def test_registry_resume(self, pm, shm_dir, tmp_path):
        manager, bus, storage = pm
        manager.start(StreamProcess(name="cam1", rtsp_endpoint=synth_url()))
        manager.shutdown_workers()
        # New manager over the same storage: resume re-spawns.
        manager2 = ProcessManager(storage, bus, shm_dir=shm_dir)
        try:
            assert manager2.resume() == 1
            assert wait_for(lambda: manager2.info("cam1").state.running)
        finally:
            manager2.close()

    def test_worker_readoption_across_manager_restart(self, shm_dir, tmp_path):
        """Reference parity rtsp_process_manager.go:191-233: a server
        restart re-attaches to still-running workers — same pid, frames
        keep flowing, no respawn."""
        bus = open_bus("shm", shm_dir)
        storage = Storage(str(tmp_path / "reg.db"))
        log_dir = str(tmp_path / "wlogs")
        m1 = ProcessManager(storage, bus, shm_dir=shm_dir, log_dir=log_dir)
        try:
            m1.start(StreamProcess(name="cam1", rtsp_endpoint=synth_url()))
            bus.touch_query("cam1")
            assert wait_for(lambda: bus.read_latest("cam1") is not None)
            pid1 = m1.info("cam1").state.pid
            rec = m1.info("cam1")
            assert rec.runtime and rec.runtime["pid"] == pid1
            assert rec.runtime["starttime"]
            # Control-plane restart: detach (workers keep running).
            m1.detach()
            assert os.path.exists(f"/proc/{pid1}")
            m2 = ProcessManager(storage, bus, shm_dir=shm_dir, log_dir=log_dir)
            try:
                assert m2.resume() == 1
                info = m2.info("cam1")
                assert info.state.running and info.state.pid == pid1  # ADOPTED
                # Frames keep flowing through the restart: a publish NEWER
                # than adoption time arrives.
                t_adopt = int(time.time() * 1000)
                bus.touch_query("cam1")
                assert wait_for(
                    lambda: (f := bus.read_latest("cam1")) is not None
                    and f.meta.timestamp_ms >= t_adopt
                )
                # Adopted log tail follows the file the worker still owns.
                assert wait_for(
                    lambda: m2.info("cam1").logs is not None
                    and m2.info("cam1").logs["total"] > 0
                )
                # stop() through the adopted handle really kills it.
                m2.stop("cam1")
                assert wait_for(
                    lambda: not os.path.exists(f"/proc/{pid1}")
                    or open(f"/proc/{pid1}/stat").read().split(") ")[1][0] == "Z"
                )
            finally:
                m2.close()
        finally:
            m1.close()
            bus.close()
            storage.close()

    def test_readoption_contract_mismatch_respawns(self, shm_dir, tmp_path):
        """A live worker whose env contract no longer matches the persisted
        record is killed and respawned (kill only on mismatch)."""
        import json as _json

        from video_edge_ai_proxy_tpu.serve.models import PREFIX_RTSP_PROCESS

        bus = open_bus("shm", shm_dir)
        storage = Storage(str(tmp_path / "reg.db"))
        log_dir = str(tmp_path / "wlogs")
        m1 = ProcessManager(storage, bus, shm_dir=shm_dir, log_dir=log_dir)
        try:
            m1.start(StreamProcess(name="cam1", rtsp_endpoint=synth_url()))
            pid1 = m1.info("cam1").state.pid
            m1.detach()
            # Operator edited the record while the server was down.
            raw = _json.loads(storage.get(PREFIX_RTSP_PROCESS, "cam1"))
            raw["rtsp_endpoint"] = synth_url(frames=99999)
            storage.put(PREFIX_RTSP_PROCESS, "cam1",
                        _json.dumps(raw).encode())
            m2 = ProcessManager(storage, bus, shm_dir=shm_dir, log_dir=log_dir)
            try:
                assert m2.resume() == 1
                pid2 = m2.info("cam1").state.pid
                assert pid2 != pid1  # respawned under the new contract
                assert wait_for(
                    lambda: not os.path.exists(f"/proc/{pid1}")
                    or open(f"/proc/{pid1}/stat").read().split(") ")[1][0] == "Z"
                )
            finally:
                m2.close()
        finally:
            m1.close()
            bus.close()
            storage.close()

    def test_adoption_disabled_restart_kills_orphan(self, shm_dir, tmp_path):
        """worker_adoption turned OFF between restarts: the surviving
        worker must be killed before the respawn, or two publishers would
        fight over one ring."""
        bus = open_bus("shm", shm_dir)
        storage = Storage(str(tmp_path / "reg.db"))
        log_dir = str(tmp_path / "wlogs")
        m1 = ProcessManager(storage, bus, shm_dir=shm_dir, log_dir=log_dir)
        try:
            m1.start(StreamProcess(name="cam1", rtsp_endpoint=synth_url()))
            pid1 = m1.info("cam1").state.pid
            m1.detach()
            assert os.path.exists(f"/proc/{pid1}")
            m2 = ProcessManager(storage, bus, shm_dir=shm_dir)  # no log_dir
            try:
                assert m2.resume() == 1
                pid2 = m2.info("cam1").state.pid
                assert pid2 != pid1
                assert wait_for(
                    lambda: not os.path.exists(f"/proc/{pid1}")
                    or open(f"/proc/{pid1}/stat").read().split(") ")[1][0] == "Z"
                )
            finally:
                m2.close()
        finally:
            m1.close()
            bus.close()
            storage.close()

    def test_dead_worker_resume_respawns(self, shm_dir, tmp_path):
        """Adoption only claims LIVE processes: a worker that died while the
        server was down is respawned, and a reused-looking pid with the
        wrong birth cookie is never touched."""
        import signal as _signal

        bus = open_bus("shm", shm_dir)
        storage = Storage(str(tmp_path / "reg.db"))
        log_dir = str(tmp_path / "wlogs")
        m1 = ProcessManager(storage, bus, shm_dir=shm_dir, log_dir=log_dir)
        try:
            m1.start(StreamProcess(name="cam1", rtsp_endpoint=synth_url()))
            pid1 = m1.info("cam1").state.pid
            m1.detach()
            os.kill(pid1, _signal.SIGKILL)
            try:
                os.waitpid(pid1, 0)  # reap so /proc entry clears
            except ChildProcessError:
                pass
            m2 = ProcessManager(storage, bus, shm_dir=shm_dir, log_dir=log_dir)
            try:
                assert m2.resume() == 1
                assert wait_for(lambda: m2.info("cam1").state.running)
                assert m2.info("cam1").state.pid != pid1
            finally:
                m2.close()
        finally:
            m1.close()
            bus.close()
            storage.close()

    def test_info_includes_log_tail(self, pm):
        manager, bus, _ = pm
        manager.start(StreamProcess(name="cam1", rtsp_endpoint=synth_url()))
        assert wait_for(
            lambda: manager.info("cam1").logs is not None
            and any("ingest worker up" in l for l in manager.info("cam1").logs["stdout"])
        )


def _boot_server(tmp_path, shm_dir, **cfg_overrides):
    """One bootstrapping path for every server-needing test (ephemeral
    ports, shm dir, no-egress annotation endpoint)."""
    from video_edge_ai_proxy_tpu.serve.server import Server

    cfg = Config()
    cfg.bus.shm_dir = shm_dir
    cfg.annotation.endpoint = "http://127.0.0.1:1/annotate"  # fail fast, no egress
    # Tests default adoption OFF so a stopped server never leaks synthetic
    # workers; the adoption tests turn it on and clean up explicitly.
    cfg.worker_adoption = False
    for key, value in cfg_overrides.items():
        section, _, field = key.partition("__")
        if field:
            setattr(getattr(cfg, section), field, value)
        else:
            setattr(cfg, section, value)
    srv = Server(cfg, data_dir=str(tmp_path), grpc_port=0, rest_port=0)
    srv.start()
    return srv


@pytest.fixture()
def server(tmp_path, shm_dir):
    srv = _boot_server(tmp_path, shm_dir)
    yield srv
    srv.stop()


def test_server_restart_keeps_frames_flowing(tmp_path, shm_dir):
    """Full-server restart with worker_adoption on (the default config):
    stop() detaches, the next boot re-adopts, frames never stop
    (reference rtsp_process_manager.go:191-233 availability parity)."""
    srv = _boot_server(tmp_path, shm_dir, worker_adoption=True)
    srv.process_manager.start(
        StreamProcess(name="cam1", rtsp_endpoint=synth_url())
    )
    srv.bus.touch_query("cam1")
    assert wait_for(lambda: srv.bus.read_latest("cam1") is not None)
    pid1 = srv.process_manager.info("cam1").state.pid
    srv.stop()  # detaches: worker must still be alive
    assert os.path.exists(f"/proc/{pid1}")
    srv2 = _boot_server(tmp_path, shm_dir, worker_adoption=True)
    try:
        assert srv2.process_manager.info("cam1").state.pid == pid1
        t_adopt = int(time.time() * 1000)
        srv2.bus.touch_query("cam1")
        assert wait_for(
            lambda: (f := srv2.bus.read_latest("cam1")) is not None
            and f.meta.timestamp_ms >= t_adopt
        )
    finally:
        # Kill workers before stopping or the detach path would leak the
        # synthetic worker past the test.
        srv2.process_manager.shutdown_workers()
        srv2.stop()


def test_storage_toggle_signed_put(tmp_path, shm_dir):
    """Storage RPC success path (reference grpc_storage_api.go:63-88 +
    edge_service.go:39-49): the server derives the stream key from the
    camera's RTMP endpoint and issues a signed PUT
    /api/v1/edge/storage/<key> the cloud can verify — captured here by a
    local HTTP server and checked with the shared secret."""
    import http.server
    import threading

    from video_edge_ai_proxy_tpu.utils.signing import verify_signature

    captured = {}

    class Capture(http.server.BaseHTTPRequestHandler):
        def do_PUT(self):
            body = self.rfile.read(int(self.headers.get("Content-Length", 0)))
            captured.update(
                method="PUT", path=self.path, body=body,
                headers={k: v for k, v in self.headers.items()},
            )
            self.send_response(200)
            self.end_headers()
            self.wfile.write(b"{}")

        def log_message(self, *_a):  # keep pytest output clean
            pass

    httpd = http.server.HTTPServer(("127.0.0.1", 0), Capture)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    srv = None
    try:
        srv = _boot_server(
            tmp_path, shm_dir,
            api__endpoint=f"http://127.0.0.1:{httpd.server_port}",
        )
        srv.settings.overwrite("edgekey", "edgesecret")
        srv.process_manager.start(StreamProcess(
            name="storcam", rtsp_endpoint=synth_url(),
            rtmp_endpoint="rtmp://cloud.example/live/streamKey123",
        ))
        channel = grpc.insecure_channel(f"127.0.0.1:{srv.bound_grpc_port}")
        stub = pb_grpc.ImageStub(channel)
        resp = stub.Storage(pb.StorageRequest(device_id="storcam", start=True))
        assert resp.start is True
        # The wire call the reference cloud expects:
        assert captured["method"] == "PUT"
        assert captured["path"] == "/api/v1/edge/storage/streamKey123"
        # urllib title-cases header names on the wire; verify_signature
        # expects the reference's exact names — canonicalize first.
        low = {k.lower(): v for k, v in captured["headers"].items()}
        canon = {
            "X-ChrysEdge-Auth": low.get("x-chrysedge-auth", ""),
            "X-Chrys-Date": low.get("x-chrys-date", ""),
            "Content-MD5": low.get("content-md5", ""),
        }
        assert verify_signature(captured["body"], canon, "edgesecret")
        # ...and the control-plane/persistence side effects:
        assert srv.bus.hget("last_access_time_storcam", "store") == "true"
        assert srv.process_manager.info(
            "storcam").rtmp_stream_status.storing is True
        channel.close()
    finally:
        if srv is not None:
            srv.stop()
        httpd.shutdown()
        httpd.server_close()


class TestEndToEnd:
    """M0 slice (SURVEY.md §7): synthetic source -> ingest worker ->
    shm bus -> gRPC VideoLatestImage -> client sees frames."""

    def test_full_slice(self, server):
        import urllib.request

        rest = f"http://127.0.0.1:{server._rest.bound_port}"

        # settings (REST) — needed for Annotate edge-key check
        req = urllib.request.Request(
            rest + "/api/v1/settings",
            data=json.dumps({"edge_key": "k", "edge_secret": "s"}).encode(),
            method="POST",
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req) as resp:
            assert resp.status == 200

        # start a camera (REST)
        req = urllib.request.Request(
            rest + "/api/v1/process",
            data=json.dumps(
                {"name": "cam1", "rtsp_endpoint": synth_url()}
            ).encode(),
            method="POST",
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req) as resp:
            assert resp.status == 200

        with urllib.request.urlopen(rest + "/api/v1/processlist") as resp:
            processes = json.loads(resp.read())
        assert [p["name"] for p in processes] == ["cam1"]

        channel = grpc.insecure_channel(f"127.0.0.1:{server.bound_grpc_port}")
        stub = pb_grpc.ImageStub(channel)

        # ListStreams — incl. the source-kind surface (VERDICT r2 weak
        # #6: a fleet must SEE which cameras run fabricated packet
        # semantics; this synthetic camera must say so).
        assert wait_for(
            lambda: any(
                s.name == "cam1" and s.running and s.source == "synthetic"
                for s in stub.ListStreams(pb.ListStreamRequest())
            )
        )
        # REST info carries the same field for the portal detail card.
        with urllib.request.urlopen(rest + "/api/v1/process/cam1") as resp:
            assert json.loads(resp.read())["source"] == "synthetic"

        # VideoLatestImage: the reference example pattern
        # (examples/basic_usage.py / opencv_display.py:43-53).
        def requests(n=40):
            for _ in range(n):
                yield pb.VideoFrameRequest(device_id="cam1")
                time.sleep(0.02)

        got = None
        for frame in stub.VideoLatestImage(requests()):
            got = frame
            break
        assert got is not None
        assert got.width == 64 and got.height == 48
        assert len(got.data) == 64 * 48 * 3
        dims = [(d.name, d.size) for d in got.shape.dim]
        assert dims == [("height", 48), ("width", 64), ("channels", 3)]

        # Annotate: ack-on-enqueue
        resp = stub.Annotate(
            pb.AnnotateRequest(
                device_name="cam1",
                type="moving",
                start_timestamp=int(time.time() * 1000),
            )
        )
        assert resp.device_name == "cam1" and resp.type == "moving"
        assert server.annotations.published == 1

        # Annotate outside the ±7d window is rejected (grpc_annotation_api.go:26-33)
        with pytest.raises(grpc.RpcError) as err:
            stub.Annotate(
                pb.AnnotateRequest(device_name="cam1", type="x", start_timestamp=1)
            )
        assert err.value.code() == grpc.StatusCode.INVALID_ARGUMENT

        # Proxy toggle writes the control key the worker polls
        resp = stub.Proxy(pb.ProxyRequest(device_id="cam1", passthrough=True))
        assert resp.passthrough
        assert server.bus.proxy_rtmp("cam1")

        # Storage toggle requires an RTMP endpoint -> FAILED_PRECONDITION here
        with pytest.raises(grpc.RpcError) as err:
            stub.Storage(pb.StorageRequest(device_id="cam1", start=True))
        assert err.value.code() == grpc.StatusCode.FAILED_PRECONDITION

        # live log follow (REST): cursor 0 returns the startup lines;
        # re-asking at the tip returns nothing new (incremental contract —
        # reference xterm streaming, process-details.component.ts:58-73)
        assert wait_for(lambda: _logs_grew(rest, 0))
        with urllib.request.urlopen(
            rest + "/api/v1/process/cam1/logs?since=0"
        ) as resp:
            first = json.loads(resp.read())
        with urllib.request.urlopen(
            rest + f"/api/v1/process/cam1/logs?since={first['total']}"
        ) as resp:
            tip = json.loads(resp.read())
        assert len(tip["lines"]) <= tip["total"] - first["total"]

        # stop camera (REST)
        req = urllib.request.Request(
            rest + "/api/v1/process/cam1", method="DELETE"
        )
        with urllib.request.urlopen(req) as resp:
            assert resp.status == 200
        with urllib.request.urlopen(rest + "/api/v1/processlist") as resp:
            assert json.loads(resp.read()) == []
        channel.close()

    def test_reference_example_runs_unchanged(self, server):
        """The compatibility bar made executable: examples/basic_usage.py —
        the reference's client pattern — runs as a real subprocess against
        a live server and sees frames (SURVEY.md §7: "so examples/*.py run
        unchanged")."""
        import subprocess
        import sys as _sys

        server.process_manager.start(
            StreamProcess(name="excam", rtsp_endpoint=synth_url())
        )
        try:
            host = f"127.0.0.1:{server.bound_grpc_port}"
            env = dict(os.environ, PYTHONPATH=str(REPO))
            listing = subprocess.run(
                [_sys.executable, "examples/basic_usage.py", "--list",
                 "--host", host],
                cwd=str(REPO), env=env, capture_output=True, text=True,
                timeout=60,
            )
            assert listing.returncode == 0, listing.stderr
            assert 'name: "excam"' in listing.stdout
            watch = subprocess.run(
                [_sys.executable, "examples/basic_usage.py",
                 "--device", "excam", "--frames", "3", "--host", host],
                cwd=str(REPO), env=env, capture_output=True, text=True,
                timeout=60,
            )
            assert watch.returncode == 0, watch.stderr
            frames = [l for l in watch.stdout.splitlines()
                      if l.startswith("excam: ")]
            assert len(frames) == 3
            assert "64x48" in frames[0]
        finally:
            server.process_manager.stop("excam")

    def test_log_follow_incremental(self, server):
        """?since=cursor hands back only new lines; unknown camera 400s."""
        import urllib.error
        import urllib.request

        rest = f"http://127.0.0.1:{server._rest.bound_port}"
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(rest + "/api/v1/process/ghost/logs")
        assert exc.value.code == 400
        # Bounded source: EOF->reconnect warnings keep appending lines, so
        # live growth is observable, not just the startup banner.
        server.process_manager.start(
            StreamProcess(name="camlog", rtsp_endpoint=synth_url(frames=5))
        )
        try:
            assert wait_for(lambda: _logs_grew(rest, 0, name="camlog"))
            with urllib.request.urlopen(
                rest + "/api/v1/process/camlog/logs?since=0"
            ) as resp:
                snap = json.loads(resp.read())
            assert snap["lines"]
            # the reconnect loop keeps producing NEW lines past the cursor
            assert wait_for(
                lambda: _logs_grew(rest, snap["total"], name="camlog")
            )
        finally:
            server.process_manager.stop("camlog")

    def test_per_connection_cursors(self, server):
        """Two clients on one camera each get frames — the reference's shared
        deviceMap cursor race (grpc_api.go:42,182) is fixed by design."""
        import urllib.request

        rest = f"http://127.0.0.1:{server._rest.bound_port}"
        req = urllib.request.Request(
            rest + "/api/v1/process",
            data=json.dumps({"name": "c2", "rtsp_endpoint": synth_url()}).encode(),
            method="POST",
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req) as resp:
            assert resp.status == 200

        channel = grpc.insecure_channel(f"127.0.0.1:{server.bound_grpc_port}")
        stub = pb_grpc.ImageStub(channel)

        def fetch_one():
            def gen():
                for _ in range(80):
                    yield pb.VideoFrameRequest(device_id="c2")
                    time.sleep(0.02)

            for frame in stub.VideoLatestImage(gen()):
                return frame
            return None

        f1 = fetch_one()
        f2 = fetch_one()
        assert f1 is not None and f2 is not None
        channel.close()


def test_supervisor_config_wires_decision_loop_and_endpoint(
        tmp_path, shm_dir):
    """supervisor.enabled=true in a config file must actually run the
    decision loop (advisory — no spawner is configurable from YAML) and
    answer /api/v1/supervisor, not silently do nothing (r19 review)."""
    import urllib.request

    srv = _boot_server(
        tmp_path, shm_dir,
        supervisor__enabled=True,
        # Port 1 refuses instantly: a dead member is fine — the router
        # scrapes it down; the supervisor holds at min_members.
        router__members=("m0=http://127.0.0.1:1",),
    )
    try:
        assert srv.supervisor is not None and srv.router is not None
        body = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{srv._rest.bound_port}/api/v1/supervisor",
            timeout=5).read())
        assert body["acting"] is False
        assert body["bounds"] == {"min": 1, "max": 4}
        assert "m0" in body["members"]
    finally:
        srv.stop()


def test_supervisor_enabled_without_members_stays_off(tmp_path, shm_dir):
    srv = _boot_server(tmp_path, shm_dir, supervisor__enabled=True)
    try:
        assert srv.supervisor is None
        import urllib.error
        import urllib.request

        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv._rest.bound_port}"
                "/api/v1/supervisor", timeout=5)
        assert err.value.code == 400
    finally:
        srv.stop()


@pytest.fixture()
def engine_server(tmp_path, shm_dir):
    """Full stack WITH the TPU engine: the flagship serving path."""
    from video_edge_ai_proxy_tpu.serve.server import Server

    cfg = Config()
    cfg.bus.shm_dir = shm_dir
    cfg.annotation.endpoint = "http://127.0.0.1:1/annotate"
    cfg.engine.model = "tiny_mobilenet_v2"
    cfg.engine.tick_ms = 20
    cfg.engine.batch_buckets = (1, 2, 4)
    srv = Server(cfg, data_dir=str(tmp_path), grpc_port=0, rest_port=0,
                 enable_engine=True)
    srv.start()
    yield srv
    srv.stop()


class TestInferenceEndToEnd:
    """Flagship path: synthetic camera -> ingest -> bus -> engine ->
    gRPC Inference stream (the loop the reference never closes)."""

    def test_inference_stream(self, engine_server):
        import urllib.request

        rest = f"http://127.0.0.1:{engine_server._rest.bound_port}"
        req = urllib.request.Request(
            rest + "/api/v1/process",
            data=json.dumps(
                {"name": "cam1",
                 "rtsp_endpoint": "test://pattern?w=32&h=32&fps=30&gop=10"}
            ).encode(),
            method="POST",
        )
        with urllib.request.urlopen(req) as resp:
            assert resp.status == 200

        channel = grpc.insecure_channel(
            f"127.0.0.1:{engine_server.bound_grpc_port}"
        )
        stub = pb_grpc.ImageStub(channel)
        results = []
        for r in stub.Inference(pb.InferenceRequest(), timeout=60):
            results.append(r)
            if len(results) >= 3:
                break
        assert len(results) >= 3
        for r in results:
            assert r.device_id == "cam1"
            assert r.model == "tiny_mobilenet_v2"
            assert len(r.detections) == 5          # top-5 classification
            assert r.batch_size >= 1
        # engine stats visible over REST
        with urllib.request.urlopen(rest + "/api/v1/stats") as resp:
            stats = json.loads(resp.read())
        assert stats["engine"]["streams"]["cam1"]["frames"] >= 3

        # InferenceRequest.model filter: a REGISTERED model that no
        # stream runs yields nothing until the deadline (and ONLY a
        # deadline — any other status is a regression)...
        got_other = []
        with pytest.raises(grpc.RpcError) as exc:
            for r in stub.Inference(
                pb.InferenceRequest(model="tiny_yolov8"), timeout=2
            ):
                got_other.append(r)
        assert exc.value.code() == grpc.StatusCode.DEADLINE_EXCEEDED
        assert got_other == []
        # ...an UNKNOWN name fails fast instead of hanging forever...
        with pytest.raises(grpc.RpcError) as exc:
            next(iter(stub.Inference(
                pb.InferenceRequest(model="yolov8m_typo"), timeout=5
            )))
        assert exc.value.code() == grpc.StatusCode.INVALID_ARGUMENT
        # ...and the matching name streams normally.
        for r in stub.Inference(
            pb.InferenceRequest(model="tiny_mobilenet_v2"), timeout=60
        ):
            assert r.model == "tiny_mobilenet_v2"
            break
