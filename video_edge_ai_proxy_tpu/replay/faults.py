"""Scripted fault plans for chaos soaks (ISSUE r6 tentpole part 3).

A FaultPlan is a deterministic, time-ordered script of pipeline faults —
the chaos in a soak run is part of the experiment's inputs, not a random
draw, so a failing soak replays exactly. Fault kinds and what injects
them (replay/harness.py):

- ``camera_kill`` / ``camera_restore`` — stop a camera's publisher and
  drop its bus stream mid-run / re-add it (collector churn: cursors,
  geometry cache, tracker + _ann_state GC must all survive).
- ``frame_gap`` — suppress one camera's publishes for ``duration_s``
  (burst loss: the latest-wins collector must idle the stream, not stall
  the batch).
- ``bus_stall`` — delay EVERY publish for ``duration_s`` (a wedged shm
  writer / slow Redis: the engine tick must degrade, not deadlock).
- ``slow_subscriber`` — stop draining the result subscription for
  ``duration_s`` (backpressure: the engine must drop-and-count via
  subscriber_drops, never block the drain thread).
- ``uplink_down`` — the annotation cloud endpoint fails every POST for
  ``duration_s`` (resilience wiring: retries back off, the breaker
  opens, batches land in the dead-letter spool and re-drain on
  recovery — zero annotations lost).
- ``bus_flap`` — publishes raise ``ConnectionError`` for ``duration_s``
  (a flapping link: cameras tolerate it, the bus breaker and resp
  idempotency-aware resync keep readers degraded, not wedged).
- ``device_stall`` — every device step call slows for ``duration_s``
  (a thermal-throttled or otherwise slowed chip: sustained tick-budget overrun
  must walk the engine's degradation ladder, then recover).
- ``black_frame`` — one camera publishes all-zero frames for
  ``duration_s`` (lens cap / dead sensor: obs/quality.py must verdict
  the stream "black" within the hysteresis bound, then recover it).
- ``frozen_frame`` — one camera republishes the same frame for
  ``duration_s`` (a wedged decoder/DVR loop: the device diff-energy
  signal must drive a "frozen" verdict, then recover).
- ``score_drift`` — every detect step's scores are scaled down for
  ``duration_s`` (silent model/numerics regression: the drift scorer
  must move and the canary checksum must mismatch while it lasts).
- ``shard_fault`` — ONE mesh shard's step execution fails hard (or, with
  ``duration_s`` > 0, stalls its drain fetch for that long) from ``at_s``
  on (``device_id`` carries the shard index as a string — the device-
  fault domain's chaos kind, injected by tools/fault_smoke.py as a
  per-shard failing/stalling step wrapper; the engine must detect,
  fail over to the survivor mesh, and prove frame conservation).

JSON round-trip so plans can be committed next to artifacts.
"""

from __future__ import annotations

import json
from dataclasses import asdict, dataclass, field

KINDS = (
    "camera_kill", "camera_restore", "frame_gap", "bus_stall",
    "slow_subscriber", "uplink_down", "bus_flap", "device_stall",
    "black_frame", "frozen_frame", "score_drift", "shard_fault",
)

#: Schedule template for the resilience kinds (fraction of the soak
#: window: start, duration) — disjoint windows, each with recovery slack
#: before the next, so the artifact attributes effects to causes.
_RESILIENCE_WINDOWS = {
    "uplink_down": (0.15, 0.20),
    "bus_flap": (0.50, 0.06),
    "device_stall": (0.62, 0.15),
}

#: The kinds `tools/soak_replay.py --faults` may select (the churn kinds
#: need per-device scheduling and run via default_churn instead).
RESILIENCE_KINDS = tuple(_RESILIENCE_WINDOWS)

#: Schedule template for the output-quality kinds (ISSUE r10): black and
#: frozen run on DISTINCT cameras (per-device targeting), drift is
#: global (a step-wrapper perturbation), so their windows may overlap —
#: but they stay disjoint anyway so the detection-latency gate in
#: tools/soak_replay.py attributes each verdict to one cause, and each
#: window leaves recovery slack for the exit-hysteresis to clear.
#: score_drift gets the widest slot: the canary judges integrity one
#: full checksum cycle at a time (loop_len / canary fps ≈ 3 s in the
#: soak harness), so the drift must stay up long enough for at least
#: one complete cycle — ideally two — to close inside it.
_QUALITY_WINDOWS = {
    "black_frame": (0.10, 0.20),
    "frozen_frame": (0.35, 0.20),
    "score_drift": (0.58, 0.35),
}

QUALITY_KINDS = tuple(_QUALITY_WINDOWS)


@dataclass(order=True)
class FaultEvent:
    at_s: float                 # seconds from soak start
    kind: str = field(compare=False)
    device_id: str = field(default="", compare=False)
    duration_s: float = field(default=0.0, compare=False)

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(f"unknown fault kind {self.kind!r}")


class FaultPlan:
    """Time-ordered fault script with a cursor (pop_due)."""

    def __init__(self, events=()):
        self.events = sorted(events)
        self._i = 0

    def reset(self) -> None:
        self._i = 0

    def pop_due(self, now_s: float) -> list[FaultEvent]:
        """Events whose time has come since the last call (monotone)."""
        due = []
        while self._i < len(self.events) and \
                self.events[self._i].at_s <= now_s:
            due.append(self.events[self._i])
            self._i += 1
        return due

    def to_json(self) -> str:
        return json.dumps([asdict(e) for e in self.events], indent=2)

    @classmethod
    def from_json(cls, text: str) -> "FaultPlan":
        return cls([FaultEvent(**e) for e in json.loads(text)])

    @classmethod
    def default_churn(
        cls, device_ids, duration_s: float,
    ) -> "FaultPlan":
        """The acceptance-run script, scaled to the soak window: one
        camera killed at 25% and re-added at 55% (churn across a long gap
        — its collector/tracker state must GC and rebuild), a frame-gap
        burst on a second camera, one global bus stall, and one
        slow-subscriber window — each in its own quiet period so the
        artifact attributes effects to causes."""
        devs = sorted(device_ids)
        ev = []
        if devs:
            ev += [
                FaultEvent(at_s=duration_s * 0.25, kind="camera_kill",
                           device_id=devs[0]),
                FaultEvent(at_s=duration_s * 0.55, kind="camera_restore",
                           device_id=devs[0]),
            ]
        if len(devs) > 1:
            ev.append(FaultEvent(
                at_s=duration_s * 0.35, kind="frame_gap",
                device_id=devs[-1],
                duration_s=max(2.0, duration_s * 0.05)))
        ev.append(FaultEvent(
            at_s=duration_s * 0.70, kind="bus_stall",
            duration_s=max(1.0, duration_s * 0.02)))
        ev.append(FaultEvent(
            at_s=duration_s * 0.85, kind="slow_subscriber",
            duration_s=max(2.0, duration_s * 0.05)))
        return cls(ev)

    @classmethod
    def resilience(
        cls, duration_s: float, kinds=("uplink_down", "bus_flap",
                                       "device_stall"),
    ) -> "FaultPlan":
        """The chaos-smoke script: the three resilience fault kinds in
        disjoint windows scaled to the soak length (``make chaos-smoke``
        runs all three; ``tools/soak_replay.py --faults`` selects)."""
        ev = []
        for kind in kinds:
            if kind not in _RESILIENCE_WINDOWS:
                raise ValueError(
                    f"not a resilience fault kind: {kind!r} "
                    f"(choose from {sorted(_RESILIENCE_WINDOWS)})"
                )
            frac, dur = _RESILIENCE_WINDOWS[kind]
            ev.append(FaultEvent(
                at_s=duration_s * frac, kind=kind,
                duration_s=max(1.0, duration_s * dur),
            ))
        return cls(ev)

    @classmethod
    def quality(
        cls, duration_s: float, device_ids,
        kinds=QUALITY_KINDS,
    ) -> "FaultPlan":
        """The quality-smoke script: black on the first camera, frozen
        on the second (distinct targets — both verdicts must fire
        independently), score_drift global, each in its _QUALITY_WINDOWS
        slot scaled to the soak length."""
        devs = sorted(device_ids)
        if not devs:
            raise ValueError("quality fault plan needs at least one camera")
        target = {
            "black_frame": devs[0],
            "frozen_frame": devs[1 % len(devs)],
            "score_drift": "",
        }
        ev = []
        for kind in kinds:
            if kind not in _QUALITY_WINDOWS:
                raise ValueError(
                    f"not a quality fault kind: {kind!r} "
                    f"(choose from {sorted(_QUALITY_WINDOWS)})"
                )
            frac, dur = _QUALITY_WINDOWS[kind]
            ev.append(FaultEvent(
                at_s=duration_s * frac, kind=kind,
                device_id=target[kind],
                duration_s=max(1.0, duration_s * dur),
            ))
        return cls(ev)
