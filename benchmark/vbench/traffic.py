"""The one traffic generator: free-running camera fleets.

A traffic mix is a JSON file of parameters under ``benchmark/traffic/``:
one frame rate and groups of cameras (count, geometry, role). Every camera
publishes frame ``k`` at ``t0 + phase + k / fps`` whether or not anybody
reads it, as an ingest worker does: the frame bus is latest-wins, and the
engine takes each camera's newest frame when its tick comes round. The
load is open loop: a publisher never waits for the engine, and every
latency is timed from the frame's due time.

Cameras are not synchronised: their phases are the even ladder
``j / (n * fps)`` over one frame period, dealt to the cameras by the seed.
So every seed gives the same arrivals in another order, and the seed never
changes how much work there is.

This module never imports jax (publishers must not touch the chip) and
nothing from the program except its frame bus.
"""

from __future__ import annotations

import json
import os
import select
import time

import numpy as np

BLOCK = 40          # source pixels per pattern cell (1080p -> 27 x 48 cells)
LEAD_S = 0.008      # a frame is rendered this long before it is due


def load(path: str) -> dict:
    with open(path) as f:
        t = json.load(f)
    for key in ("fps", "groups"):
        if key not in t:
            raise ValueError(f"{path}: traffic file lacks {key!r}")
    for g in t["groups"]:
        if g["height"] % BLOCK or g["width"] % BLOCK:
            raise ValueError(f"{path}: geometry must be a multiple of {BLOCK}")
    return t


def cameras(traffic: dict, seed: int) -> list:
    """[(index, device_id, role, height, width, phase_s)] in file order."""
    out = []
    for g in traffic["groups"]:
        for i in range(int(g["cameras"])):
            out.append([len(out), f"{g['prefix']}{i:03d}", g["role"],
                        int(g["height"]), int(g["width"])])
    n = len(out)
    ladder = np.arange(n) / (n * float(traffic["fps"]))
    phase = np.random.default_rng([int(seed), 1 << 40]).permutation(ladder)
    return [tuple(c) + (float(p),) for c, p in zip(out, phase)]


def due_time(traffic: dict, cam: tuple, t0: float, k: int) -> float:
    """Monotonic due time of camera ``cam``'s frame ``k`` in a fleet whose
    clock started at ``t0``."""
    return t0 + cam[5] + k / float(traffic["fps"])


def stamp_ms(due_mono: float, wall_minus_mono: float) -> int:
    """The ``timestamp_ms`` a frame due at ``due_mono`` carries."""
    return int(round((due_mono + wall_minus_mono) * 1000.0))


def frame_cells(seed: int, cam: int, k: int, gh: int, gw: int) -> np.ndarray:
    """The [gh, gw, 3] uint8 pattern of camera ``cam``'s frame ``k``: a base
    colour, a contrast, and coarse plus fine structure, all drawn from
    (seed, cam, k) — so that a clip built from another camera's frames, or
    from the right frames in the wrong order, moves the logits by far more
    than any rounding does."""
    r = np.random.default_rng([int(seed), int(cam), int(k)])
    base = r.integers(32, 224, (1, 1, 3)).astype(np.float32)
    amp = r.uniform(10.0, 100.0)
    coarse = r.normal(0.0, 1.0, (3, 4, 3))
    coarse = np.repeat(np.repeat(coarse, -(-gh // 3), 0), -(-gw // 4), 1)
    fine = r.normal(0.0, 1.0, (gh, gw, 3))
    x = base + amp * (0.7 * coarse[:gh, :gw] + 0.5 * fine)
    return np.clip(x, 0.0, 255.0).astype(np.uint8)


def fill_frame(out: np.ndarray, seed: int, cam: int, k: int) -> np.ndarray:
    """Write frame (seed, cam, k) into the [H, W, 3] uint8 buffer ``out``."""
    h, w = out.shape[:2]
    gh, gw = h // BLOCK, w // BLOCK
    rows = np.repeat(frame_cells(seed, cam, k, gh, gw), BLOCK, axis=1)
    out.reshape(gh, BLOCK, w * 3)[...] = rows.reshape(gh, 1, w * 3)
    return out


def make_frame(seed: int, cam: int, k: int, h: int, w: int) -> np.ndarray:
    return fill_frame(np.empty((h, w, 3), np.uint8), seed, cam, k)


def _sleep_until(t: float) -> None:
    while True:
        d = t - time.monotonic()
        if d <= 0:
            return
        time.sleep(d - 0.0004 if d > 0.0008 else 0)


def publisher_main(shm_dir: str, traffic: dict, seed: int, mine: list,
                   inp, out) -> None:
    """Child process: own the rings of cameras ``mine`` (indices) and run
    them, one JSON line each way: ["run", t0, wall_minus_mono] starts the
    cameras; ["halt"] stops them and is answered by ["done", [[due, late_s]
    of every frame published]]; ["stop"] (or end of input) -> exit. A frame
    whose slot has passed before the one ahead of it was out is skipped, as
    a camera's would be."""
    from video_edge_ai_proxy_tpu.bus import FrameMeta
    from video_edge_ai_proxy_tpu.bus.shm_bus import ShmFrameBus

    def say(msg):
        out.write(json.dumps(msg) + "\n")
        out.flush()

    cams = cameras(traffic, seed)
    period = 1.0 / float(traffic["fps"])
    bus = ShmFrameBus(shm_dir)
    try:
        bufs = {}
        for i in mine:
            _, dev, _, h, w, _ = cams[i]
            bus.create_stream(dev, h * w * 3)
            bufs[i] = np.empty((h, w, 3), np.uint8)
        say(["ready", os.getpid()])
        order = sorted(mine, key=lambda i: cams[i][5])
        t0 = wall_minus_mono = None
        late, k, at = [], 0, 0
        while True:
            wait = None
            if t0 is not None:
                i = order[at]
                due = due_time(traffic, cams[i], t0, k)
                wait = due - LEAD_S - time.monotonic()
                if wait < -period:          # the slot has passed
                    k = int((time.monotonic() - t0 - cams[i][5])
                            / period) + 1
                    continue
            if select.select([inp], [], [],
                             wait if wait is None else max(wait, 0.0))[0]:
                line = inp.readline()
                cmd = json.loads(line) if line else ["stop"]
                if cmd[0] == "run":
                    _, t0, wall_minus_mono = cmd
                elif cmd[0] == "halt":
                    t0 = None
                    say(["done", late])
                    late, k, at = [], 0, 0
                else:
                    return
                continue
            if wait is None or wait > 0:
                continue
            _, dev, _, h, w, _ = cams[i]
            fill_frame(bufs[i], seed, i, k)
            _sleep_until(due)
            bus.publish(dev, bufs[i], FrameMeta(
                width=w, height=h, channels=3, packet=k,
                timestamp_ms=stamp_ms(due, wall_minus_mono),
                is_keyframe=True))
            late.append([round(due, 6), round(time.monotonic() - due, 6)])
            at += 1
            if at == len(order):
                at, k = 0, k + 1
    finally:
        bus.close()


class Publishers:
    """The fleet's publisher processes: plain children of this file, which
    never import jax, commanded over their stdin."""

    def __init__(self, shm_dir: str, traffic_path: str, seed: int):
        import subprocess
        import sys

        traffic = load(traffic_path)
        n_cams = sum(int(g["cameras"]) for g in traffic["groups"])
        n_proc = max(1, min(int(traffic.get("publisher_processes", 4)),
                            n_cams))
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        env = dict(os.environ, PYTHONPATH=os.pathsep.join(
            [root] + [p for p in [os.environ.get("PYTHONPATH")] if p]))
        self._procs = []
        for p in range(n_proc):
            mine = [i for i in range(n_cams) if i % n_proc == p]
            self._procs.append(subprocess.Popen(
                [sys.executable, os.path.abspath(__file__), shm_dir,
                 traffic_path, str(int(seed)),
                 ",".join(str(i) for i in mine)],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
                bufsize=1, env=env))

    def _read(self, proc, want: str):
        line = proc.stdout.readline()
        if not line:
            raise RuntimeError(
                f"publisher {proc.pid} ended (code {proc.poll()}) before "
                f"it said {want!r}")
        msg = json.loads(line)
        if msg[0] != want:
            raise RuntimeError(f"publisher said {msg[0]!r}, not {want!r}")
        return msg[1]

    def _tell(self, msg: list) -> None:
        for proc in self._procs:
            proc.stdin.write(json.dumps(msg) + "\n")
            proc.stdin.flush()

    def wait_ready(self) -> None:
        for proc in self._procs:
            self._read(proc, "ready")

    def run(self, t0: float, wall_minus_mono: float) -> None:
        """Every camera free-runs from ``t0`` (monotonic) on."""
        self._tell(["run", t0, wall_minus_mono])

    def halt(self) -> list:
        """Stop the cameras; [[due, late_s]] of every frame published (a
        publisher that dies raises; one that hangs is the run's own time
        limit's to end)."""
        self._tell(["halt"])
        late = []
        for proc in self._procs:
            late.extend(self._read(proc, "done"))
        return late

    def stop(self) -> None:
        for proc in self._procs:
            try:
                proc.stdin.write('["stop"]\n')
                proc.stdin.close()
            except (BrokenPipeError, OSError, ValueError):
                pass
        for proc in self._procs:
            try:
                proc.wait(timeout=10)
            except Exception:
                proc.kill()
                proc.wait(timeout=10)
            proc.stdout.close()


if __name__ == "__main__":
    import sys

    publisher_main(sys.argv[1], load(sys.argv[2]), int(sys.argv[3]),
                   [int(i) for i in sys.argv[4].split(",")],
                   sys.stdin, sys.stdout)
