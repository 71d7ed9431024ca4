"""HBM smoke: exercise the r21 device-memory attribution plane end to
end and gate the ISSUE 18 acceptance criteria.

Four parts, one JSON line (``--out`` additionally writes the artifact,
committed as HBM_r01.json; tools/bench_gate.py carries it
informationally):

A. **Pool-byte exactness under track churn** — a hand-stepped cascade
   engine (the tests/test_cascade.py ``_tick`` convention) with the HBM
   plane armed, soaked through a track-churn schedule that GROWS the
   clip ring (enough live tracks to force a grow-by-8 reallocation) and
   then SHRINKS the live set (streams go dark, tracks TTL out, slots
   return to the free list). Gates: at EVERY sample the tracked
   ``track_state``/``thumbs`` bytes equal the constituent device
   arrays' ``.nbytes`` exactly (max_abs_delta_bytes == 0), ring bytes
   grew at least once, and live slots shrank after the churn-out. The
   same soak runs again on a dp=2 mesh engine where the per-shard rows
   must each match their sub-ring exactly and sum to the aggregate.
B. **Deterministic ramp forecast** — a fake-clock ``HbmTracker`` with a
   linearly growing registered pool. Gates: ``time_to_oom_s`` falls
   strictly monotonically once the forecast is established and headroom
   bytes never go negative.
C. **Memory-aware admission storm** — a scripted-fleet StreamRouter
   admitting a storm of new streams against one byte-exhausted member
   that still has plenty of TIME headroom. Gates: the byte-exhausted
   member takes ZERO placements, every admission lands on the member
   with memory headroom.
D. **Kill-switch replay** — the engine's emitted device-output checksum
   with ``hbm=True`` must be bit-identical to the default ``hbm=False``
   run (attribution may account for memory, never change results).

Runs in ~30 s on the CPU twin; wired as ``make hbm-smoke``. Exits
non-zero on any gate breach.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

STORM = 24          # part C admission storm size
HORIZON_S = 60.0    # router oom-exclusion horizon under test


def _meta(side):
    from video_edge_ai_proxy_tpu.bus.interface import FrameMeta

    _meta.ts = max(int(time.time() * 1000), getattr(_meta, "ts", 0) + 1)
    return FrameMeta(width=side, height=side, channels=3,
                     timestamp_ms=_meta.ts, is_keyframe=True)


def _blob_frame(side, key, flick):
    import numpy as np

    from video_edge_ai_proxy_tpu.models.blob import blob_color

    f = np.full((side, side, 3), 150 if flick else 78, np.uint8)
    f[20:36, 20:34] = blob_color(key)
    return f


def _tick(eng):
    """One engine tick by hand: collect -> dispatch -> drain/emit ->
    cascade tick (the tests/test_cascade.py convention)."""
    import queue as _queue

    groups = eng._collector.collect()
    eng._dispatch(groups, time.perf_counter())
    while True:
        try:
            inflight = eng._drain_q.get_nowait()
        except _queue.Empty:
            break
        try:
            eng._emit(inflight)
        finally:
            eng._collector.release(inflight.group)
            eng._drain_q.task_done()
    if eng._cascade is not None:
        eng._cascade_tick()


def _expected_track_bytes(sched):
    """Σ constituent ``.nbytes`` of the live clip ring(s), read from the
    device arrays themselves — the independent side of the exactness
    invariant."""
    pool = sched._pool
    if pool is None:
        return 0, {}
    arrs = pool.array
    if isinstance(arrs, list):                    # sharded: one per shard
        shards = {str(s): (int(a.nbytes) if a is not None else 0)
                  for s, a in enumerate(arrs)}
        return sum(shards.values()), shards
    return (int(arrs.nbytes) if arrs is not None else 0), {}


def _soak(mesh=None):
    """Track-churn soak on a cascade engine with the HBM plane armed:
    grow the ring past a grow-by-8 boundary, then let tracks TTL out."""
    import queue as _queue

    from video_edge_ai_proxy_tpu.bus.memory_bus import MemoryFrameBus
    from video_edge_ai_proxy_tpu.engine import InferenceEngine
    from video_edge_ai_proxy_tpu.uplink.queue import AnnotationQueue
    from video_edge_ai_proxy_tpu.utils.config import EngineConfig

    side = 64
    # Stream names chosen so crc32 pinning spreads them across a dp=2
    # mesh (cam0 -> shard 0, cam4 -> shard 1, the test_cascade.py pair);
    # enough single-track streams to push the ring past its first
    # grow-by-8 capacity (rows 1..9 need 10 > 8).
    names = [f"cam{i}" for i in range(9)] if mesh is None \
        else ["cam0", "cam4"]
    # Stagger onset: a simultaneous first scatter of all 9 tracks would
    # size the ring's *initial* capacity at 16 rows (ceil(10/8)*8) and
    # never cross a grow-by-8 reallocation.  Wave 1 (rows 1..4) makes it
    # materialize at cap 8; wave 2 (rows 5..9 -> need 10) forces the
    # device-side jnp.pad regrow to 16 that the exactness gate must
    # survive.  Tick 1 publishes nothing so the first sample is the
    # unmaterialized (0-byte) ring and both transitions count as growth.
    start_at = {n: 2 for n in names}
    for n in names[4:]:
        start_at[n] = 5
    dark_after = {n: 14 for n in names}
    for n in names[len(names) // 2:]:
        dark_after[n] = 8                  # churn out: streams go dark
    bus = MemoryFrameBus()
    try:
        eng = InferenceEngine(
            bus,
            EngineConfig(
                model="tiny_blob_gauge", batch_buckets=(1, 2, 4, 8, 16),
                tick_ms=10, prefetch=False, track=True,
                cascade=True, cascade_model="tiny_videomae",
                cascade_every_n=2, cascade_track_ttl_ticks=3,
                hbm=True, mesh=mesh,
            ),
            annotations=AnnotationQueue(handler=lambda batch: True))
        eng.warmup()
        assert eng.hbm is not None, "hbm plane failed to arm"
        eng._drain_q = _queue.Queue(maxsize=8)
        for n in names:
            bus.create_stream(n, side * side * 3)

        samples = 0
        max_delta = 0
        shard_max_delta = 0
        byte_series = []
        slot_series = []
        for tick in range(1, 21):
            for i, n in enumerate(names):
                if start_at[n] <= tick <= dark_after[n]:
                    # Color keys stay inside the gauge's 8 class bins;
                    # duplicate keys across streams are fine (tracks are
                    # per-stream).
                    bus.publish(n,
                                _blob_frame(side, (i % 7) + 1,
                                            tick % 2 == 0),
                                _meta(side))
            _tick(eng)
            pools = eng.hbm.pools()
            tracked = pools["pools"].get("track_state", {"bytes": 0})
            expect, expect_shards = _expected_track_bytes(eng._cascade)
            max_delta = max(max_delta, abs(tracked["bytes"] - expect))
            if expect_shards:
                got_shards = tracked.get("shards") or {}
                for s, want in expect_shards.items():
                    shard_max_delta = max(
                        shard_max_delta, abs(got_shards.get(s, 0) - want))
                # Aggregate row must be the shard sum, nothing else.
                max_delta = max(max_delta, abs(
                    tracked["bytes"] - sum(expect_shards.values())))
            thumbs = pools["pools"].get("thumbs", {"bytes": 0})
            if eng._thumbs is not None:
                max_delta = max(max_delta, abs(
                    thumbs["bytes"] - _thumb_nbytes(eng._thumbs)))
            byte_series.append(tracked["bytes"])
            slot_series.append(eng._cascade._pool.slots_in_use()
                               if eng._cascade._pool is not None else 0)
            samples += 1
        eng.hbm.evaluate(force=True)
        snap = eng.hbm.snapshot()
    finally:
        bus.close()
    return {
        "mesh": mesh or None,
        "samples": samples,
        "max_abs_delta_bytes": max_delta,
        "shard_max_abs_delta_bytes": shard_max_delta if mesh else None,
        "ring_bytes_first": byte_series[0],
        "ring_bytes_last": byte_series[-1],
        "ring_grew": any(b > a for a, b in zip(byte_series,
                                               byte_series[1:])),
        # Distinct growth events: materialization plus at least one
        # grow-by-8 reallocation proves the exactness held across a
        # device-side jnp.pad, not just a static ring.
        "ring_growth_events": sum(
            1 for a, b in zip(byte_series, byte_series[1:]) if b > a),
        "slots_peak": max(slot_series),
        "slots_last": slot_series[-1],
        "slots_shrank": slot_series[-1] < max(slot_series),
        "used_bytes": snap["used_bytes"],
        "programs": len(snap["programs"]),
        "pool_names": sorted(snap["pools"]["pools"]),
    }


def _thumb_nbytes(thumbs):
    """Σ constituent ``.nbytes`` of the quality thumb pool(s)."""
    subs = getattr(thumbs, "_subs", None)
    if subs is not None:                          # sharded thumb pool
        return sum(int(s._pool.nbytes) for s in subs
                   if s._pool is not None)
    return int(thumbs._pool.nbytes) if thumbs._pool is not None else 0


def _part_a():
    out = {"aggregate": _soak(mesh=None), "dp2": _soak(mesh={"dp": 2})}
    out["max_abs_delta_bytes"] = max(
        out["aggregate"]["max_abs_delta_bytes"],
        out["dp2"]["max_abs_delta_bytes"],
        out["dp2"]["shard_max_abs_delta_bytes"] or 0)
    return out


def _part_b():
    """Fake-clock ramp: time_to_oom_s must fall monotonically."""
    from video_edge_ai_proxy_tpu.obs.hbm import HbmTracker
    from video_edge_ai_proxy_tpu.obs.metrics import Registry

    clock = types.SimpleNamespace(now=0.0)
    budget = 1_000_000
    tracker = HbmTracker(
        budget_bytes=budget, fast_window_s=60.0, slow_window_s=1800.0,
        util_objective=0.9, eval_interval_s=0.0,
        clock=lambda: clock.now, registry=Registry())
    holder = [0]
    tracker.register_pool("ramp", lambda: holder[0])
    series = []
    headrooms = []
    for t in range(1, 161):
        clock.now = float(t)
        holder[0] = 4000 * t                 # linear allocation ramp
        state = tracker.evaluate(now=clock.now, force=True)
        headrooms.append(state["headroom_bytes"])
        if t >= 10:                          # forecast established
            series.append((t, state["time_to_oom_s"]))
    return {
        "ramp_bytes_per_s": 4000,
        "budget_bytes": budget,
        "samples": len(series),
        "tto_first_s": series[0][1],
        "tto_last_s": series[-1][1],
        "tto_series_defined": all(v is not None for _, v in series),
        "tto_monotone_decreasing": all(
            a[1] is not None and b[1] is not None and b[1] < a[1] + 1e-9
            for a, b in zip(series, series[1:])),
        "min_headroom_bytes": min(headrooms),
        "final_pressure": tracker.pressure(),
    }


def _make_router(rows):
    """Scripted-fleet StreamRouter (the tools/capacity_smoke.py fakes):
    no sockets, breaker always closed, fixed health rows."""
    from video_edge_ai_proxy_tpu.serve.router import StreamRouter

    names = [r["instance"] for r in rows]
    fleet = types.SimpleNamespace(
        _members=[types.SimpleNamespace(name=n, base_url=f"http://{n}")
                  for n in names],
        rows={r["instance"]: r for r in rows},
        scrape_once=lambda: None,
        health=lambda: [dict(r) for r in rows],
    )
    started = {n: [] for n in names}

    def factory(name, url):
        return types.SimpleNamespace(
            name=name,
            breaker=types.SimpleNamespace(state="closed"),
            start_stream=lambda s, u, m="", p="",
            _n=name: started[_n].append(s),
            stop_stream=lambda s: None,
            attach_router=lambda r, u="": {},
            detach_router=lambda: None,
            stream_frames=lambda s: 0,
        )

    clock = types.SimpleNamespace(now=0.0)
    router = StreamRouter(
        [f"{n}=http://{n}" for n in names], fleet=fleet,
        client_factory=factory, clock=lambda: clock.now,
        sleep=lambda s: None, admit_saturation_horizon_s=HORIZON_S,
        admit_oom_horizon_s=HORIZON_S)
    router.run_pass()
    return router, started


def _row(name, headroom, tts, hbm_headroom_bytes, tto):
    return {"instance": name, "up": True, "stale": False, "healthy": True,
            "score": 0.9, "score_ema": 0.9, "healthy_since_s": 100.0,
            "ladder_rung": 0.0, "slo_burning": False, "streams": 0,
            "capacity": True, "headroom": headroom,
            "capacity_utilization": (1.0 - headroom
                                     if headroom is not None else None),
            "time_to_saturation_s": tts,
            "hbm": True, "hbm_headroom_bytes": hbm_headroom_bytes,
            "hbm_utilization": (None if hbm_headroom_bytes is None
                                else 0.99 if hbm_headroom_bytes <= 0
                                else 0.3),
            "time_to_oom_s": tto}


def _part_c():
    """Admission storm: byte-exhausted member with plenty of TIME
    headroom must take zero placements."""
    # m1 has the best compute headroom in the fleet but zero HBM
    # headroom; m2 is forecast to OOM inside the horizon; m0 has memory
    # room. Memory-blind admission would put the whole storm on m1.
    rows = [_row("m0", 0.60, None, 8 << 30, None),
            _row("m1", 0.90, None, 0, None),
            _row("m2", 0.70, None, 4 << 30, 20.0)]
    router, started = _make_router(rows)
    placements = [router.admit(f"storm{i}", f"rtsp://storm{i}")
                  for i in range(STORM)]
    storm_by_member = {n: len(s) for n, s in started.items()}
    return {
        "storm_size": STORM,
        "storm_by_member": storm_by_member,
        "exhausted_member_placements": storm_by_member["m1"],
        "oom_forecast_member_placements": storm_by_member["m2"],
        "all_on_memory_headroom_member": set(placements) == {"m0"},
    }


def _part_d():
    """hbm=True emitted checksum must be bit-identical to hbm=False."""
    import queue as _queue

    import numpy as np

    from video_edge_ai_proxy_tpu.bus.interface import FrameMeta
    from video_edge_ai_proxy_tpu.bus.memory_bus import MemoryFrameBus
    from video_edge_ai_proxy_tpu.engine import InferenceEngine
    from video_edge_ai_proxy_tpu.replay.checksum import (
        CHECKSUM_MASK,
        device_checksum,
        finalize_checksum,
    )
    from video_edge_ai_proxy_tpu.uplink.queue import AnnotationQueue
    from video_edge_ai_proxy_tpu.utils.config import EngineConfig

    def run(hbm):
        b = MemoryFrameBus()
        try:
            b.create_stream("cam1", 64 * 64 * 3)
            eng = InferenceEngine(
                b, EngineConfig(model="tiny_blob_gauge",
                                batch_buckets=(1, 2, 4), tick_ms=5,
                                prefetch=False, hbm=hbm),
                annotations=AnnotationQueue(handler=lambda batch: True))
            eng.warmup()
            eng._drain_q = _queue.Queue(maxsize=8)
            carry = 0
            last_ts = 0
            # Blob frames (not flat fills): flat frames yield zero valid
            # detections and device_checksum folds only over valid rows,
            # which would make the bit-exactness pin vacuously 0 == 0.
            for tick, key in enumerate((1, 3, 5, 7)):
                last_ts = max(int(time.time() * 1000), last_ts + 1)
                b.publish("cam1", _blob_frame(64, key, tick % 2 == 0),
                          FrameMeta(width=64, height=64, channels=3,
                                    timestamp_ms=last_ts,
                                    is_keyframe=True))
                groups = eng._collector.collect()
                eng._dispatch(groups, time.perf_counter())
                inflight = eng._drain_q.get(timeout=10)
                part = int(np.asarray(device_checksum(inflight.outputs)))
                carry = (carry + part) & CHECKSUM_MASK
                eng._emit(inflight)
                eng._collector.release(inflight.group)
                eng._drain_q.task_done()
            if hbm:
                assert eng.hbm is not None
            else:
                assert eng.hbm is None
            return finalize_checksum(carry)
        finally:
            b.close()

    on, off = run(True), run(False)
    return {"checksum_hbm_on": on, "checksum_hbm_off": off,
            "hbm_off_bitexact": on == off}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--native", action="store_true",
                    help="use the environment's real backend instead of "
                         "forcing CPU")
    ap.add_argument("--out", default="",
                    help="also write the artifact JSON here")
    args = ap.parse_args(argv)

    import jax

    if not args.native:
        jax.config.update("jax_platforms", "cpu")
        # 8 virtual CPU devices for the dp=2 mesh leg: XLA_FLAGS is read
        # when the backend initializes, which nothing has done yet.
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
    backend = jax.default_backend()

    t0 = time.monotonic()
    part_a = _part_a()
    part_b = _part_b()
    part_c = _part_c()
    part_d = _part_d()
    out = {
        "tool": "hbm_smoke",
        "backend": backend,
        "wall_s": round(time.monotonic() - t0, 2),
        "pools": part_a,
        "forecast": part_b,
        "admission": part_c,
        "replay": part_d,
        "gates": {
            "pool_max_abs_delta_bytes_max": 0,
            "ring_grew_and_slots_shrank": True,
            "tto_monotone_decreasing": True,
            "exhausted_member_placements_max": 0,
            "hbm_off_bitexact": True,
            "checksum_nonzero": True,
        },
    }
    print(json.dumps(out), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=2)
            f.write("\n")

    if part_a["max_abs_delta_bytes"] != 0:
        raise SystemExit(
            "hbm_smoke: pool-byte exactness broken (max delta "
            f"{part_a['max_abs_delta_bytes']} bytes)")
    if part_a["aggregate"]["ring_growth_events"] < 2:
        raise SystemExit(
            "hbm_smoke: aggregate ring never crossed a grow-by-8 "
            f"reallocation ({part_a['aggregate']['ring_growth_events']} "
            "growth events; expected materialize + regrow)")
    for leg in ("aggregate", "dp2"):
        if not part_a[leg]["ring_grew"]:
            raise SystemExit(f"hbm_smoke: {leg} ring never grew")
        if not part_a[leg]["slots_shrank"]:
            raise SystemExit(
                f"hbm_smoke: {leg} live slots never shrank after churn")
        if "track_state" not in part_a[leg]["pool_names"]:
            raise SystemExit(
                f"hbm_smoke: {leg} track_state pool unregistered "
                f"({part_a[leg]['pool_names']})")
        if part_a[leg]["programs"] == 0:
            raise SystemExit(
                f"hbm_smoke: {leg} footprinted no compiled programs")
    if not part_b["tto_series_defined"]:
        raise SystemExit("hbm_smoke: OOM forecast never established "
                         "under ramped allocation")
    if not part_b["tto_monotone_decreasing"]:
        raise SystemExit(
            "hbm_smoke: time_to_oom_s not monotone under a linear ramp "
            f"({part_b['tto_first_s']} -> {part_b['tto_last_s']})")
    if part_b["min_headroom_bytes"] < 0:
        raise SystemExit(
            f"hbm_smoke: negative headroom {part_b['min_headroom_bytes']}")
    if part_c["exhausted_member_placements"] != 0:
        raise SystemExit(
            f"hbm_smoke: {part_c['exhausted_member_placements']} "
            "admissions on the byte-exhausted member (expected 0)")
    if part_c["oom_forecast_member_placements"] != 0:
        raise SystemExit(
            f"hbm_smoke: {part_c['oom_forecast_member_placements']} "
            "admissions on the OOM-forecast member (expected 0)")
    if not part_c["all_on_memory_headroom_member"]:
        raise SystemExit(
            "hbm_smoke: storm admissions left the memory-headroom "
            f"member: {part_c['storm_by_member']}")
    if not part_d["hbm_off_bitexact"]:
        raise SystemExit(
            "hbm_smoke: hbm=True changed the emitted checksum "
            f"({part_d['checksum_hbm_on']} != "
            f"{part_d['checksum_hbm_off']})")
    if part_d["checksum_hbm_on"] == 0:
        raise SystemExit(
            "hbm_smoke: replay checksum is 0 — no valid detections, the "
            "bit-exactness pin is vacuous")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
