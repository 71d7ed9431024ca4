"""Observability plane: unified metrics registry, frame-lineage tracing,
stall/watermark detection (ISSUE r7 tentpole), live device-performance
attribution and SLO burn-rate evaluation (ISSUE r9 tentpole).

Pure-Python, jax-free at import, importable from control-plane and worker
code alike. Modules:

- :mod:`metrics` — process-wide counters/gauges/log2-histograms, rendered
  once by ``/metrics`` (Prometheus 0.0.4) and ``/api/v1/stats`` (JSON).
- :mod:`spans` — sampled per-frame lineage span events (ingest -> bus ->
  batch -> device -> emit), per-stream ring buffers, Chrome trace-event
  export (``tools/obs_export.py``) and ``/api/v1/trace``.
- :mod:`watch` — threshold-crossing detection (drain backpressure, batch
  occupancy, recompilation storms, frame drops) logged once per episode.
- :mod:`perf` — XLA compile cost + wall-time per (model, geometry,
  bucket), per-batch device time, padded-slot waste, live MFU /
  aggregate-fps gauges (``vep_perf_*`` / ``vep_compile_*``).
- :mod:`slo` — declarative SLOs (p50 detect latency, aggregate fps,
  stream availability) with multi-window burn-rate episodes, served at
  ``/api/v1/slo`` and feeding the resilience degradation ladder.
- :mod:`prof` — duration-bounded jax.profiler captures (on-demand via
  ``/api/v1/profile`` + gRPC admin mirror, or fired automatically when an
  SLO episode opens / the degradation ladder escalates) written as
  self-contained bundles into a byte-bounded retention ring.
- :mod:`stages` — device time by stage from the programs' own
  ``jax.named_scope`` names: the stage map of a compiled step (built only
  when asked for), the one reducer behind a bundle's ``stages.json`` and
  the benchmark's per-stage metrics.
- :mod:`quality` — output-quality observability: per-stream black /
  frozen / flatline verdict state machines fed by device-computed frame
  statistics, detection drift scores vs committed baselines, and the
  canary golden-replay integrity check (``vep_quality_*`` /
  ``/api/v1/quality``), feeding the degradation ladder's first-shed set
  and the ``canary_integrity`` SLO.
- :mod:`fleet` — the cross-process tier (ISSUE r14 tentpole): scrapes N
  member engines' ``/metrics`` + ``/api/v1/stats`` + ``/api/v1/slo``,
  merges counters (sum) / gauges (last-write + staleness flag) /
  histograms (bucket merge) under an ``instance`` label, and ranks
  member health (``vep_fleet_*``, ``/api/v1/fleet/stats``).
- :mod:`capacity` — the forward-looking tier (ISSUE r18 tentpole): the
  per-stream device-time ledger (conservation-gated attribution of every
  measured batch back to its occupant streams), per-(model, geometry,
  bucket) utilization rings with an EWMA-slope ``time_to_saturation_s``
  forecast, and SRE-style fast/slow capacity burn rates
  (``vep_capacity_*``, ``/api/v1/capacity``) — the signal
  ``StreamRouter.admit`` consumes for headroom-aware placement.
- :mod:`journal` — the decision audit trail (ISSUE r23 tentpole): a
  process-wide bounded ring of causally-linked control-plane decision
  events (actor/action/subject/quantitative trigger/cause link) with
  ``why()`` backward chain walks, fleet merge via monotone per-member
  seqs, and ``vep_journal_*`` counters (``/api/v1/journal`` +
  ``/api/v1/why``).
- :mod:`hbm` — the memory mirror of :mod:`capacity` (ISSUE r21
  tentpole): static per-program footprints from ``memory_analysis()``
  at AOT-compile time, dynamic per-pool byte accounting via registered
  ``nbytes`` callables, a window-peak utilization model over the
  device's HBM budget, and an EWMA-slope ``time_to_oom_s`` forecast
  (``vep_hbm_*``, ``/api/v1/hbm``) feeding the degradation ladder,
  memory-aware admission, and the supervisor's scale-out decision.
"""

from .capacity import CapacityTracker
from .hbm import HbmTracker
from .metrics import Registry, registry
from .perf import PerfTracker, cost_summary, mfu_pct
from .prof import Profiler
from .quality import CanaryChecker, QualityTracker
from .slo import BurnRateSLO, SLOEngine, SLOSpec, default_slos, integrity_slo
from .fleet import FleetAggregator
from .journal import DecisionJournal, format_event, merge_journals
from .spans import (
    SpanRecorder, stage_breakdown, to_chrome_trace, trace_id_for, tracer,
)
from .watch import Watchdog

__all__ = [
    "CapacityTracker",
    "HbmTracker",
    "Registry",
    "registry",
    "PerfTracker",
    "Profiler",
    "CanaryChecker",
    "QualityTracker",
    "cost_summary",
    "mfu_pct",
    "BurnRateSLO",
    "SLOEngine",
    "SLOSpec",
    "default_slos",
    "integrity_slo",
    "FleetAggregator",
    "DecisionJournal",
    "format_event",
    "merge_journals",
    "SpanRecorder",
    "stage_breakdown",
    "to_chrome_trace",
    "trace_id_for",
    "tracer",
    "Watchdog",
]
