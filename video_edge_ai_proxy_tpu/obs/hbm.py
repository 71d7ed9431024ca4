"""HBM attribution plane: per-program and per-pool device-memory ledger
with OOM forecasting.

The memory mirror of :mod:`.capacity` (r18 made device TIME a conserved,
forecastable resource; this round does the same for device BYTES —
ISSUE 18, the byte-budget prerequisite for ROADMAP item 3's
device-resident KV/state caches and item 5's per-tenant economics).
No reference counterpart: the reference proxy keeps no device state at
all (frames live in per-camera shm rings, ``rtsp_to_rtmp.py:144-145``);
a fused TPU serving plane accumulates compiled-program footprints,
grow-by-8 clip rings, thumb pools, prefetch double-buffers and canvas
buffers that nothing accounted for until now — the fleet could forecast
running out of time but not running out of HBM.

Three tiers, one object (``HbmTracker``, engine-owned like
``CapacityTracker``):

- **Static program footprints.** Captured once per compiled program at
  the engine's single step-cache-miss site (the same ``_TimedStep``
  success path obs/perf.py taps for compile time + FLOPs):
  ``compiled.memory_analysis()`` argument/output/temp/generated-code
  bytes per ``(model, stem, geometry, bucket, mesh)`` program, with
  donated-argument aliasing credited (``alias_bytes``) so
  ``donate_frames`` shows up as saved bytes. Programs execute serially,
  so the resident model is Σ code bytes (executables persist) plus the
  MAX single-program workspace (argument+output+temp−alias), not the
  sum of every workspace.
- **Dynamic pool accounting.** A ``register_pool(name, nbytes_fn)``
  protocol: each device-resident pool (thumb pools, track-state clip
  rings, stream-head state: ``stream_state``, whatever kinds a head
  declares (LFM2's conv states and key-value caches, Xing4's latent cache)
  in engine/stream_state.py, prefetch slots, collector
  host batch buffers) registers a
  zero-argument callable returning its CURRENT bytes — an int, or a
  ``{shard: int}`` mapping for per-chip pools under ``engine.mesh``.
  Reading the pool's own ``.nbytes`` at call time makes the exactness
  invariant (tracked bytes == Σ constituent ``.nbytes``) hold by
  construction; tools/hbm_smoke.py and the dp=2 test pin it anyway.
  Re-registering a name replaces the callable (the engine's sharded
  warmup swaps stay tracked with no unregister dance).
- **Budget + forecast.** Device capacity from ``device.memory_stats()``
  on the real TPU (the engine resolves it at warmup and calls
  :meth:`set_budget`) with a configurable synthetic budget on the CPU
  twin. ``evaluate`` (throttled, engine-tick driven) samples used =
  pools + code + peak workspace, EWMA-smooths the utilization slope and
  extrapolates ``time_to_oom_s`` in the exact r18 forecast shape; burn
  rates follow the SRE fast/slow recipe over window PEAKS (memory is a
  level, not a rate — the windows carry high-water marks). The
  aggregate ``pressure()`` verdict (burning, or OOM forecast inside
  ``pressure_horizon_s``) feeds the resilience ladder so the engine
  sheds/stretches BEFORE the allocator fails.

Metric families (gauges unless noted):

- ``vep_hbm_budget_bytes`` / ``vep_hbm_used_bytes`` — the budget model
- ``vep_hbm_pool_bytes{pool}`` — per registered pool, live
- ``vep_hbm_program_code_bytes`` / ``vep_hbm_program_workspace_bytes``
  — resident executables + the single largest program workspace
- ``vep_hbm_donated_saved_bytes`` — donated-argument aliasing credit
- ``vep_hbm_programs_total`` (counter) — programs footprinted
- ``vep_hbm_utilization{window}`` — window-peak used over budget
- ``vep_hbm_burn_rate{window}`` — utilization over the sustainable
  objective (>1 = trending to OOM faster than sustainable)
- ``vep_hbm_headroom_bytes`` — budget minus used
- ``vep_hbm_time_to_oom_seconds`` — EWMA-slope forecast (-1 = not
  trending toward OOM)

jax-free by design (CLAUDE.md): importable from control-plane code; the
``nbytes_fn`` callables touch device arrays' ``.nbytes`` metadata only,
never their contents — no transfer, no sync.
"""

from __future__ import annotations

import math
import threading
import time
from typing import Callable, Dict, Optional, Tuple, Union

from . import metrics

# CPU-twin fallback budget when the engine resolves no real device
# budget (device.memory_stats() absent) and the config pins none: big
# enough that the tiny twins never read as pressured, small enough that
# a runaway pool still trips the forecast in soaks.
DEFAULT_SYNTHETIC_BUDGET_BYTES = 4 << 30

PoolBytes = Union[int, Dict[str, int]]


class _PeakRing:
    """Per-bin HIGH-WATER marks over the slow window (the
    obs/capacity.py ``_BusyRing`` idiom with max instead of sum):
    memory is a level, not a rate, so a window total is meaningless —
    the window's peak is what OOM cares about. O(1) record, O(n_bins)
    peak scan at evaluate time."""

    __slots__ = ("_bin_s", "_n", "_peak", "_epochs")

    def __init__(self, span_s: float, bin_s: float):
        self._bin_s = float(bin_s)
        self._n = max(int(math.ceil(span_s / bin_s)) + 1, 2)
        self._peak = [0.0] * self._n
        self._epochs = [-1] * self._n

    def record(self, value: float, now: float) -> None:
        epoch = int(now // self._bin_s)
        i = epoch % self._n
        if self._epochs[i] != epoch:
            self._epochs[i] = epoch
            self._peak[i] = 0.0
        if value > self._peak[i]:
            self._peak[i] = value

    def peak(self, window_s: float, now: float) -> float:
        """Max recorded value across bins younger than ``window_s``."""
        lo_epoch = int((now - window_s) // self._bin_s)
        now_epoch = int(now // self._bin_s)
        peak = 0.0
        for i in range(self._n):
            e = self._epochs[i]
            if lo_epoch < e <= now_epoch and self._peak[i] > peak:
                peak = self._peak[i]
        return peak


class _Program:
    """One compiled program's memory footprint (bytes, from
    ``compiled.memory_analysis()`` via obs/perf.py memory_summary)."""

    __slots__ = ("argument", "output", "temp", "code", "alias", "count")

    def __init__(self, summary: Dict[str, int]):
        self.argument = int(summary.get("argument_bytes", 0))
        self.output = int(summary.get("output_bytes", 0))
        self.temp = int(summary.get("temp_bytes", 0))
        self.code = int(summary.get("code_bytes", 0))
        self.alias = int(summary.get("alias_bytes", 0))
        self.count = 1      # recompiles of the same key overwrite

    @property
    def workspace(self) -> int:
        """Live bytes while THIS program executes: arguments + outputs
        + XLA temp, minus donated-argument aliasing (a donated input
        plane is the output's storage — the credit that makes
        ``donate_frames`` visible as saved bytes)."""
        return max(0, self.argument + self.output + self.temp - self.alias)


class HbmTracker:
    """Engine-owned HBM plane: program footprints + pool ledger +
    budget forecast.

    ``note_program`` is the compile-site tap (drain thread, once per
    step-cache miss); ``register_pool`` arms the dynamic ledger;
    ``evaluate`` is the forecast step (tick thread, throttled to
    ``eval_interval_s``); ``snapshot`` is the read surface. The clock is
    injectable so ramp/forecast math tests run sleep-free.
    """

    def __init__(self, *, budget_bytes: int = 0,
                 fast_window_s: float = 60.0,
                 slow_window_s: float = 1800.0,
                 bin_s: float = 1.0,
                 util_objective: float = 0.9,
                 slope_alpha: float = 0.3,
                 eval_interval_s: float = 1.0,
                 pressure_horizon_s: float = 120.0,
                 clock=time.monotonic,
                 registry: Optional[metrics.Registry] = None):
        if not 0.0 < util_objective <= 1.0:
            raise ValueError(
                f"util_objective must be in (0, 1], got {util_objective}")
        if fast_window_s >= slow_window_s:
            raise ValueError(
                f"fast window ({fast_window_s}s) must be shorter than the "
                f"slow window ({slow_window_s}s)")
        if budget_bytes < 0:
            raise ValueError(f"budget_bytes must be >= 0, got {budget_bytes}")
        self.budget_bytes = (int(budget_bytes) if budget_bytes
                             else DEFAULT_SYNTHETIC_BUDGET_BYTES)
        #: True once set_budget() installed a device-reported budget
        #: (the snapshot distinguishes measured from synthetic).
        self.budget_measured = False
        self.fast_window_s = float(fast_window_s)
        self.slow_window_s = float(slow_window_s)
        self.bin_s = float(bin_s)
        self.util_objective = float(util_objective)
        self.slope_alpha = float(slope_alpha)
        self.eval_interval_s = float(eval_interval_s)
        self.pressure_horizon_s = float(pressure_horizon_s)
        self._clock = clock
        self._lock = threading.Lock()
        self._programs: Dict[Tuple[str, str, str, int, str], _Program] = {}
        self._pools: Dict[str, Callable[[], PoolBytes]] = {}
        self._ring = _PeakRing(slow_window_s, bin_s)
        # Forecast state (updated only in evaluate()).
        self._next_eval = 0.0
        self._prev_util: Optional[float] = None
        self._prev_eval_t: Optional[float] = None
        self._slope_ema: Optional[float] = None   # utilization / second
        self._last: dict = {
            "used_bytes": 0,
            "utilization": {"fast": 0.0, "slow": 0.0},
            "burn": {"fast": 0.0, "slow": 0.0},
            "burning": False,
            "headroom_bytes": self.budget_bytes,
            "slope_per_s": None,
            "time_to_oom_s": None,
            "pressure": False,
        }
        reg = registry if registry is not None else metrics.registry
        self._m_budget = reg.gauge(
            "vep_hbm_budget_bytes",
            "Device memory budget (measured via device.memory_stats() "
            "or the configured synthetic twin budget)").labels()
        self._m_used = reg.gauge(
            "vep_hbm_used_bytes",
            "Modeled resident bytes: pools + program code + peak single-"
            "program workspace").labels()
        self._m_pool = reg.gauge(
            "vep_hbm_pool_bytes",
            "Live bytes per registered device/host pool", ("pool",))
        self._m_code = reg.gauge(
            "vep_hbm_program_code_bytes",
            "Generated-code bytes summed over resident compiled programs"
        ).labels()
        self._m_workspace = reg.gauge(
            "vep_hbm_program_workspace_bytes",
            "Largest single-program execution workspace (arguments + "
            "outputs + temp - donated aliasing)").labels()
        self._m_saved = reg.gauge(
            "vep_hbm_donated_saved_bytes",
            "Bytes saved by donated-argument aliasing across resident "
            "programs (donate_frames evidence)").labels()
        self._m_programs = reg.counter(
            "vep_hbm_programs_total",
            "Compiled programs footprinted at the step-cache-miss site"
        ).labels()
        self._m_util = reg.gauge(
            "vep_hbm_utilization",
            "Window-peak used bytes over the budget", ("window",))
        self._m_burn = reg.gauge(
            "vep_hbm_burn_rate",
            "HBM burn multiple per window (utilization over the "
            "sustainable objective)", ("window",))
        self._m_headroom = reg.gauge(
            "vep_hbm_headroom_bytes",
            "Budget minus modeled used bytes").labels()
        self._m_tto = reg.gauge(
            "vep_hbm_time_to_oom_seconds",
            "EWMA-slope OOM forecast (-1 = not trending toward OOM)"
        ).labels()
        self._m_budget.set(self.budget_bytes)
        self._m_headroom.set(self.budget_bytes)
        self._m_tto.set(-1.0)

    # -- budget ----------------------------------------------------------

    def set_budget(self, budget_bytes: int, *, measured: bool = True) -> None:
        """Install the device-reported budget (engine warmup calls this
        with ``device.memory_stats()['bytes_limit']`` on the real TPU;
        the CPU twin keeps the configured/synthetic budget)."""
        if budget_bytes <= 0:
            return
        with self._lock:
            self.budget_bytes = int(budget_bytes)
            self.budget_measured = bool(measured)
        self._m_budget.set(self.budget_bytes)

    # -- static program footprints (drain thread, once per compile) ------

    def note_program(self, model: str, src_hw: Tuple[int, int], bucket: int,
                     summary: Dict[str, int], *, stem: str = "classic",
                     mesh: str = "") -> None:
        """Record one compiled program's ``memory_analysis()`` summary
        (obs/perf.py ``memory_summary`` dict) under its
        ``(model, stem, geometry, bucket, mesh)`` key. A recompile of
        the same key (engine restart of a bucket) overwrites — the model
        is RESIDENT programs, not compile history."""
        if not summary:
            return
        geometry = f"{src_hw[0]}x{src_hw[1]}"
        key = (str(model), str(stem), geometry, int(bucket), str(mesh))
        with self._lock:
            prev = self._programs.get(key)
            prog = _Program(summary)
            if prev is not None:
                prog.count = prev.count + 1
            self._programs[key] = prog
            code = sum(p.code for p in self._programs.values())
            workspace = max(
                (p.workspace for p in self._programs.values()), default=0)
            saved = sum(p.alias for p in self._programs.values())
        self._m_programs.inc()
        self._m_code.set(code)
        self._m_workspace.set(workspace)
        self._m_saved.set(saved)

    # -- dynamic pool ledger ---------------------------------------------

    def register_pool(self, name: str,
                      nbytes_fn: Callable[[], PoolBytes]) -> None:
        """Arm live byte accounting for one pool. ``nbytes_fn()`` returns
        the pool's CURRENT bytes — an int, or ``{shard: int}`` for
        per-chip pools under a dp mesh. Called at evaluate/snapshot time
        only (metadata reads; keep it cheap and lock-safe). Registering
        an existing name replaces the callable."""
        with self._lock:
            self._pools[str(name)] = nbytes_fn

    def pools(self) -> dict:
        """Live per-pool bytes: ``{"total": int, "pools": {name:
        {"bytes": int, "shards": {shard: int} | None}}}``. A pool whose
        callable raises reads as 0 bytes with ``"error"`` set — the
        forecast degrades, the tick loop never dies."""
        with self._lock:
            fns = list(self._pools.items())
        out: Dict[str, dict] = {}
        total = 0
        for name, fn in fns:
            row: dict = {"bytes": 0, "shards": None}
            try:
                val = fn()
            except Exception as exc:  # noqa: BLE001 — live tap must survive
                row["error"] = f"{type(exc).__name__}: {exc}"
                out[name] = row
                continue
            if isinstance(val, dict):
                shards = {str(k): int(v) for k, v in val.items()}
                row["shards"] = shards
                row["bytes"] = sum(shards.values())
            else:
                row["bytes"] = int(val)
            total += row["bytes"]
            out[name] = row
        return {"total": total, "pools": out}

    # -- forecast (tick thread, throttled) -------------------------------

    def _used(self) -> Tuple[int, dict, int, int, int]:
        """(used, pools, code, workspace, saved) — the budget model."""
        pools = self.pools()
        with self._lock:
            code = sum(p.code for p in self._programs.values())
            workspace = max(
                (p.workspace for p in self._programs.values()), default=0)
            saved = sum(p.alias for p in self._programs.values())
        used = pools["total"] + code + workspace
        return used, pools, code, workspace, saved

    def evaluate(self, now: Optional[float] = None,
                 force: bool = False) -> dict:
        """Sample used bytes, update the forecast + burn state; throttled
        to ``eval_interval_s`` unless forced. Returns the live state dict
        (also retained for snapshot())."""
        now = self._clock() if now is None else now
        if not force and now < self._next_eval:
            return self._last
        self._next_eval = now + self.eval_interval_s
        used, pools, code, workspace, saved = self._used()
        budget = self.budget_bytes
        self._ring.record(float(used), now)
        u_now = used / budget if budget else 0.0
        u_fast = self._ring.peak(self.fast_window_s, now) / budget \
            if budget else 0.0
        u_slow = self._ring.peak(self.slow_window_s, now) / budget \
            if budget else 0.0
        # EWMA utilization slope (per second) on the INSTANT level — the
        # same forecast shape as obs/capacity.py: ramps register within
        # an eval interval, the EMA keeps one allocation burst from
        # whipsawing the OOM estimate.
        if self._prev_util is not None and self._prev_eval_t is not None \
                and now > self._prev_eval_t:
            slope = (u_now - self._prev_util) / (now - self._prev_eval_t)
            self._slope_ema = (
                slope if self._slope_ema is None
                else self.slope_alpha * slope
                + (1.0 - self.slope_alpha) * self._slope_ema)
        self._prev_util = u_now
        self._prev_eval_t = now
        headroom_frac = max(0.0, 1.0 - u_now)
        headroom_bytes = max(0, budget - used)
        tto: Optional[float] = None
        if self._slope_ema is not None and self._slope_ema > 1e-9:
            tto = headroom_frac / self._slope_ema
        burn_fast = u_fast / self.util_objective
        burn_slow = u_slow / self.util_objective
        burning = burn_fast > 1.0 and burn_slow > 1.0
        pressure = burning or (
            tto is not None and tto <= self.pressure_horizon_s)
        self._last = {
            "used_bytes": used,
            "utilization": {"fast": u_fast, "slow": u_slow},
            "burn": {"fast": burn_fast, "slow": burn_slow},
            "burning": burning,
            "headroom_bytes": headroom_bytes,
            "slope_per_s": self._slope_ema,
            "time_to_oom_s": tto,
            "pressure": pressure,
        }
        self._m_used.set(used)
        self._m_code.set(code)
        self._m_workspace.set(workspace)
        self._m_saved.set(saved)
        self._m_util.labels("fast").set(u_fast)
        self._m_util.labels("slow").set(u_slow)
        self._m_burn.labels("fast").set(burn_fast)
        self._m_burn.labels("slow").set(burn_slow)
        self._m_headroom.set(headroom_bytes)
        self._m_tto.set(tto if tto is not None else -1.0)
        for name, row in pools["pools"].items():
            self._m_pool.labels(name).set(row["bytes"])
        return self._last

    def pressure(self) -> bool:
        """The resilience ladder's aggregate verdict from the last
        evaluate: burning on both windows, or forecast to OOM inside
        ``pressure_horizon_s``. One dict read — the per-tick cost."""
        return bool(self._last["pressure"])

    # -- read surfaces ----------------------------------------------------

    def programs(self) -> Dict[str, dict]:
        """Per-program footprint rows (copies), keyed
        ``model|stem|geometry|bucket|mesh``."""
        with self._lock:
            return {
                "|".join((model, stem, geometry, str(bucket), mesh or "-")): {
                    "argument_bytes": p.argument,
                    "output_bytes": p.output,
                    "temp_bytes": p.temp,
                    "code_bytes": p.code,
                    "alias_bytes": p.alias,
                    "workspace_bytes": p.workspace,
                    "compiles": p.count,
                }
                for (model, stem, geometry, bucket, mesh), p
                in self._programs.items()
            }

    def snapshot(self) -> dict:
        """JSON-able HBM state for /api/v1/hbm, the /api/v1/stats obs
        embed, and the fleet scrape. Runs a (throttled) evaluate so a
        read-only consumer still sees a live forecast."""
        state = self.evaluate()
        used, pools, code, workspace, saved = self._used()
        return {
            "budget_bytes": self.budget_bytes,
            "budget_measured": self.budget_measured,
            "util_objective": self.util_objective,
            "windows_s": {"fast": self.fast_window_s,
                          "slow": self.slow_window_s},
            "used_bytes": used,
            "utilization": {k: round(v, 9)
                            for k, v in state["utilization"].items()},
            "burn": {k: round(v, 9) for k, v in state["burn"].items()},
            "burning": state["burning"],
            "headroom_bytes": state["headroom_bytes"],
            "slope_per_s": state["slope_per_s"],
            "time_to_oom_s": state["time_to_oom_s"],
            "pressure": state["pressure"],
            "program_code_bytes": code,
            "program_workspace_bytes": workspace,
            "donated_saved_bytes": saved,
            "programs": self.programs(),
            "pools": pools,
        }
