"""Just-in-time reads (ISSUE 33): when a tick may start its ``collect()``.

The bus is latest-wins, so a frame read before the device can take it only
grows stale on the host. Where the device sets the pace the tick thread
used to run ahead until a backstop held it (the depth-2 drain queue, a
stream head's wait for its predecessor step), both of them AFTER the
read and the placement. ``ReadPacer`` moves that wait to before the read:
the tick thread enters ``collect()`` at

    predicted time the device is free of everything already dispatched
      -  predicted time from collect() entry to the tick's first step call

Both terms are what the engine stamps anyway. *Device free*: the tick
thread reports every batch with device work as it hands it to the drain
thread (``launched``), the drain thread as its outputs reach the host
(``drained``); a program's step time is ``drained - max(launched, the
previous batch's drained)``, and the estimate is the median of the last
few per program, so one compile-length or stalled step does not move it.
The backlog is the running batch's remaining time plus the queued
batches' step times. *Host lead*: ``t_collect0 -> t_step0`` of a tick's
first batch (read, fill, placement wait, pool plan), a high reading of
the last few ticks: their largest once gross outliers are set aside (a
pool buffer's first touch, a stalled host: one such reading taken at its
word switches the pacing off for as many rounds as the history is long,
measured in PERF.md 6, PR 33), plus a few ms of slack. A placement that
ends early costs a few ms of staleness, one that ends late idles the
chip.

No setting: with nothing in flight, no history yet, or a backlog shorter
than the lead (the host sets the pace) the tick reads at once. A wrong
prediction cannot hold the tick thread for long: the wait re-reckons at
every ``drained``, ends when nothing is left in flight, and wakes every
``SLICE_S`` for the engine's stop event. The drain queue and the state
pool's wait stay behind it as the hard limits.

jax-free; the clock is injectable so the arithmetic is tested on fake
time.
"""

from __future__ import annotations

import statistics
import threading
import time
from collections import deque
from typing import Callable, Deque, Dict, Hashable, Optional

__all__ = ["ReadPacer"]


class ReadPacer:
    STEP_HISTORY = 9      # step times kept a program; their median is used
    LEAD_HISTORY = 8      # host leads kept; the largest typical one is used
    LEAD_OUTLIER = 1.5    # a lead over this many medians is not typical
    # Aim to be ready this long before the device is reckoned free: that
    # reckoning is anchored on outputs reaching the host, a fetch (3-6 ms)
    # after the step ended, and a step starts 1-2 ms after its call.
    SLACK_S = 0.008
    MIN_WAIT_S = 0.001    # a shorter wait is no wait (and no back-pressure)
    SLICE_S = 0.05        # longest sleep between looks at the stop event

    def __init__(self, clock: Callable[[], float] = time.monotonic):
        self._clock = clock
        self._cond = threading.Condition()
        # dispatched and not yet drained, oldest first: [token, key, t]
        self._inflight: Deque[list] = deque()
        self._steps: Dict[Hashable, Deque[float]] = {}
        self._leads: Deque[float] = deque(maxlen=self.LEAD_HISTORY)
        self._last_drained = float("-inf")

    def launched(self, token, key: Hashable,
                 now: Optional[float] = None) -> None:
        """Tick thread: a batch with device work goes to the drain thread.
        ``key`` names its program (model, geometry, bucket). Groups that
        cost the device nothing (coasted ROI groups, write-only rounds)
        are never reported: they count for nothing in the backlog."""
        with self._cond:
            self._inflight.append(
                [token, key, self._clock() if now is None else now])

    def drained(self, token, now: Optional[float] = None) -> None:
        """Drain thread: the batch's outputs are on the host. Records the
        program's step time and wakes a waiting tick thread."""
        now = self._clock() if now is None else now
        with self._cond:
            entry = self._pop(token)
            if entry is None:
                return
            _, key, t_launch = entry
            self._steps.setdefault(
                key, deque(maxlen=self.STEP_HISTORY)).append(
                now - max(t_launch, self._last_drained))
            self._last_drained = now
            self._cond.notify_all()

    def forget(self, token) -> None:
        """Drain thread: the batch left the pipeline whether or not its
        outputs arrived (a failed fetch). No-op after ``drained``."""
        with self._cond:
            if self._pop(token) is not None:
                self._cond.notify_all()

    def _pop(self, token) -> Optional[list]:
        for i, entry in enumerate(self._inflight):
            if entry[0] is token:
                # the drain is in order: what was ahead of it is gone too
                for _ in range(i + 1):
                    self._inflight.popleft()
                return entry
        return None

    def note_lead(self, seconds: float) -> None:
        """Tick thread: this tick's collect() entry -> first step call."""
        with self._cond:
            self._leads.append(max(0.0, seconds))

    def in_flight(self) -> int:
        with self._cond:
            return len(self._inflight)

    def read_at(self, now: Optional[float] = None) -> Optional[float]:
        """When collect() should be entered so that the placement ends as
        the device frees; None where nothing can be predicted (nothing in
        flight, no lead measured yet): read at once."""
        with self._cond:
            return self._read_at(self._clock() if now is None else now)

    def _read_at(self, now: float) -> Optional[float]:
        if not self._inflight or not self._leads:
            return None
        free = None
        for _, key, t_launch in self._inflight:
            history = self._steps.get(key)
            # a program never timed yet predicts nothing for its batch
            step = statistics.median(history) if history else 0.0
            if free is None:
                # the running batch began at its launch or as its
                # predecessor ended; one that is overdue ends now
                free = max(max(t_launch, self._last_drained) + step, now)
            else:
                free += step
        typical = self.LEAD_OUTLIER * statistics.median(self._leads)
        lead = max(s for s in self._leads if s <= typical)
        return free - lead - self.SLACK_S

    def wait(self, stop: threading.Event) -> float:
        """Tick thread, before collect(): block until ``read_at``. Returns
        the seconds waited, 0.0 where the wait did not engage."""
        t0 = self._clock()
        with self._cond:
            due = self._read_at(t0)
            if due is None or due - t0 < self.MIN_WAIT_S:
                return 0.0
            while not stop.is_set():
                now = self._clock()
                due = self._read_at(now)
                if due is None or due <= now:
                    break
                self._cond.wait(min(due - now, self.SLICE_S))
        return self._clock() - t0
