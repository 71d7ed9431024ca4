"""r14 fleet telemetry plane: cross-process trace ids, merge rules,
member health, and the two-process aggregation conformance test.

The conformance test is the first multihost-flavored test that does NOT
skip on the CPU backend: it boots two REAL serve processes (control
plane only — no engine, so no backend init) on ephemeral ports, scrapes
them with a FleetAggregator, and asserts merged counters equal the sum
of the members plus the staleness flag on a killed member.
"""

import json
import os
import subprocess
import sys
import textwrap
import time

import pytest

from video_edge_ai_proxy_tpu.bus.interface import FrameMeta
from video_edge_ai_proxy_tpu.obs.fleet import (
    FleetAggregator,
    MemberState,
    parse_exposition,
    _strip_label,
    _with_instance,
)
from video_edge_ai_proxy_tpu.obs.metrics import Registry, lint_exposition
from video_edge_ai_proxy_tpu.obs.spans import (
    SpanRecorder,
    stage_breakdown,
    to_chrome_trace,
    trace_id_for,
    trace_id_of,
)


# ---------------------------------------------------------------------------
# Trace-context ids (obs/spans.py)


class TestTraceIds:
    def test_deterministic_and_nonzero(self):
        a = trace_id_for("cam1", 7)
        assert a == trace_id_for("cam1", 7)     # content-derived: replay-
        assert a != trace_id_for("cam1", 8)     # checksum safe by design
        assert a != trace_id_for("cam2", 7)
        assert a != 0

    def test_63_bit_range(self):
        # int64-safe on the wire (proto int64 / ctypes c_int64): never
        # negative, never zero (0 = unstamped sentinel).
        for i in range(200):
            tid = trace_id_for(f"cam{i}", i * 37)
            assert 0 < tid <= 0x7FFF_FFFF_FFFF_FFFF

    def test_trace_id_of_prefers_wire_value(self):
        meta = FrameMeta(packet=5, trace_id=12345)
        assert trace_id_of(meta, "cam1") == 12345

    def test_trace_id_of_falls_back_to_hash(self):
        meta = FrameMeta(packet=5)          # unstamped (trace_id=0)
        assert trace_id_of(meta, "cam1") == trace_id_for("cam1", 5)

    def test_meta_defaults_ride_the_bus_struct(self):
        meta = FrameMeta()
        assert meta.trace_id == 0 and meta.parent_span == 0


# ---------------------------------------------------------------------------
# Dropped-stage lineage closure (the r14 bugfix: drops used to orphan
# their spans silently)


class TestDroppedSpans:
    def test_breakdown_accounts_drops_by_reason(self):
        rec = SpanRecorder(enabled=True, sample_every=1)
        rec.record("cam1", "collect", 1, ts=1.0)
        rec.record("cam1", "dropped", 1, ts=1.01, reason="stale_shed")
        rec.record("cam1", "dropped", 2, ts=1.02, reason="stale_shed")
        rec.record("cam1", "dropped", 3, ts=1.03, reason="shutdown_drain")
        br = stage_breakdown(rec.events())
        assert br["drops"]["count"] == 3
        assert br["drops"]["by_reason"] == {
            "shutdown_drain": 1, "stale_shed": 2}

    def test_dropped_events_export_to_chrome_trace(self):
        rec = SpanRecorder(enabled=True, sample_every=1)
        rec.record("cam1", "dropped", 1, ts=1.0, reason="stale_shed",
                   trace_id=trace_id_for("cam1", 1))
        obj = to_chrome_trace(rec.events())
        assert any(ev.get("name") == "dropped"
                   for ev in obj["traceEvents"])


# ---------------------------------------------------------------------------
# Render-time const labels (obs/metrics.py)


class TestConstLabels:
    def test_instance_label_on_every_sample(self):
        r = Registry()
        r.set_const_labels(instance="m7")
        r.counter("vep_x_total", "x").inc(2)
        r.gauge("vep_g", "g", ("stream",)).labels("cam1").set(1.5)
        h = r.histogram("vep_h_ms", "h")
        h.observe(3.0)
        text = r.render()
        assert lint_exposition(text) == []
        for line in text.splitlines():
            if line.startswith("#") or not line.strip():
                continue
            assert 'instance="m7"' in line, line

    def test_per_sample_label_wins_on_collision(self):
        r = Registry()
        r.set_const_labels(instance="outer")
        r.counter("vep_c_total", "c", ("instance",)).labels("inner").inc()
        text = r.render()
        assert 'instance="inner"' in text
        assert 'instance="outer"' not in text

    def test_snapshot_stays_const_label_free(self):
        # The ISSUE pins render-time labeling: the JSON snapshot (and the
        # hot-path sample maps behind it) must not grow per-sample label
        # churn.
        r = Registry()
        r.set_const_labels(instance="m0")
        r.counter("vep_c_total", "c").inc()
        snap = r.snapshot()
        assert "instance" not in json.dumps(snap["vep_c_total"]["samples"])


# ---------------------------------------------------------------------------
# Exposition parsing + merge rules (obs/fleet.py)


def _member_page(instance: str, count: float, rung: float) -> str:
    r = Registry()
    r.set_const_labels(instance=instance)
    r.counter("vep_frames_total", "frames", ("stream",)).labels(
        "cam1").inc(count)
    r.gauge("vep_ladder_rung", "rung").set(rung)
    h = r.histogram("vep_lat_ms", "lat")
    h.observe(1.0)
    h.observe(100.0)
    return r.render()


def _seed_member(m: MemberState, page: str, *, streams=0, burning=False):
    m.families = parse_exposition(page)
    m.stats = {"engine": {"streams": {f"c{i}": {} for i in range(streams)}}}
    m.slo = {"burning": burning}
    m.alive = True
    m.last_ok = time.monotonic()
    m.scrapes += 1


class TestMergeRules:
    def _agg(self):
        agg = FleetAggregator(
            ["m0=http://127.0.0.1:1", "m1=http://127.0.0.1:1"],
            scrape_interval_s=0.2)
        _seed_member(agg._members[0], _member_page("m0", 3, 0), streams=2)
        _seed_member(agg._members[1], _member_page("m1", 5, 2),
                     streams=1, burning=True)
        return agg

    def test_parse_roundtrip_families(self):
        fams = parse_exposition(_member_page("m0", 3, 0))
        kinds = {f["name"]: f["kind"] for f in fams}
        assert kinds["vep_frames_total"] == "counter"
        assert kinds["vep_ladder_rung"] == "gauge"
        assert kinds["vep_lat_ms"] == "histogram"
        hist = next(f for f in fams if f["name"] == "vep_lat_ms")
        assert any(n.endswith("_bucket") for n, _, _ in hist["samples"])

    def test_counters_sum_across_members(self):
        fs = self._agg().fleet_stats()
        row = fs["counters"]["vep_frames_total"]['stream="cam1"']
        assert row["value"] == 8.0
        assert row["instances"] == {"m0": 3.0, "m1": 5.0}

    def test_histograms_bucket_merge(self):
        fs = self._agg().fleet_stats()
        row = fs["histograms"]["vep_lat_ms"][""]
        assert row["count"] == 4                   # 2 observations x 2
        assert row["buckets"]["+Inf"] == 4.0
        # Cumulative bucket counts stay monotone after the merge.
        finite = [(float(le), v) for le, v in row["buckets"].items()
                  if le != "+Inf"]
        ordered = [v for _, v in sorted(finite)]
        assert ordered == sorted(ordered)

    def test_gauges_last_write_with_staleness(self):
        fs = self._agg().fleet_stats()
        row = fs["gauges"]["vep_ladder_rung"][""]
        assert row["stale"] is False
        assert row["instances"]["m0"]["value"] == 0.0
        assert row["instances"]["m1"]["value"] == 2.0

    def test_health_folds_burn_rung_and_streams(self):
        health = self._agg().health()
        assert [h["instance"] for h in health] == ["m0", "m1"]  # ranked
        m0, m1 = health
        assert m0["score"] > m1["score"]
        assert m1["slo_burning"] and m1["ladder_rung"] == 2.0
        assert m0["streams"] == 2 and m1["streams"] == 1

    def test_merged_exposition_lint_clean_with_instances(self):
        text = self._agg().merged_exposition()
        assert lint_exposition(text) == []
        assert 'vep_frames_total{instance="m0",stream="cam1"} 3' in text
        assert 'vep_frames_total{instance="m1",stream="cam1"} 5' in text
        assert "vep_fleet_member_health_score" in text
        assert "vep_fleet_members 2" in text

    def test_dead_member_scores_zero_and_flags_stale(self):
        agg = self._agg()
        m1 = agg._members[1]
        m1.alive = False
        m1.last_ok = time.monotonic() - 10 * agg.stale_after_s
        health = {h["instance"]: h for h in agg.health()}
        assert health["m1"]["stale"] is True
        assert health["m1"]["score"] == 0.0
        assert health["m0"]["stale"] is False

    def test_label_helpers(self):
        assert _strip_label('a="1",instance="m0",b="2"', "instance") == \
            'a="1",b="2"'
        assert _with_instance("", "m0") == 'instance="m0"'
        assert _with_instance('k="v"', "m0") == 'instance="m0",k="v"'
        # A member that already self-labels keeps its own identity.
        assert _with_instance('instance="self",k="v"', "m0") == \
            'instance="self",k="v"'


# ---------------------------------------------------------------------------
# Capacity plane in the fleet merge (r18 satellite)


def _capacity_member_page(instance: str) -> str:
    """A member exposition that includes live vep_capacity_* families
    (registered and driven by a real CapacityTracker, not hand-written
    text — the lint check covers what the plane actually renders)."""
    from video_edge_ai_proxy_tpu.obs.capacity import CapacityTracker

    r = Registry()
    r.set_const_labels(instance=instance)
    r.counter("vep_frames_total", "frames", ("stream",)).labels(
        "cam1").inc(2)
    cap = CapacityTracker(fast_window_s=10.0, slow_window_s=100.0,
                          eval_interval_s=0.0, clock=lambda: 1000.0,
                          registry=r)
    cap.note_batch("det", (64, 64), 4, 20.0, ["cam1", "cam2"])
    cap.note_batch("det", (64, 64), 1, 5.0, ["cam1"], weights=[1.0],
                   kind="roi")
    cap.evaluate(force=True)
    return r.render()


def _capacity_snapshot():
    return {"headroom": 0.75, "utilization": {"fast": 0.25, "slow": 0.1},
            "burn": {"fast": 0.3125, "slow": 0.125}, "burning": False,
            "time_to_saturation_s": 120.0}


class TestCapacityFleetMerge:
    def _agg(self):
        """m0 reports the capacity plane, m1 does not (pre-r18 member /
        capacity=False): the mixed-version fleet must merge cleanly."""
        agg = FleetAggregator(
            ["m0=http://127.0.0.1:1", "m1=http://127.0.0.1:1"],
            scrape_interval_s=0.2)
        _seed_member(agg._members[0], _capacity_member_page("m0"),
                     streams=2)
        agg._members[0].capacity = _capacity_snapshot()
        _seed_member(agg._members[1], _member_page("m1", 5, 0), streams=1)
        return agg

    def test_mixed_version_health_rows(self):
        health = {h["instance"]: h for h in self._agg().health()}
        m0, m1 = health["m0"], health["m1"]
        assert m0["capacity"] is True
        assert m0["headroom"] == pytest.approx(0.75)
        assert m0["capacity_utilization"] == pytest.approx(0.25)
        assert m0["time_to_saturation_s"] == pytest.approx(120.0)
        # The capacity-less peer merges with None signals, never a
        # KeyError or a fake zero that would read as "saturated".
        assert m1["capacity"] is False
        assert m1["headroom"] is None
        assert m1["capacity_utilization"] is None
        assert m1["time_to_saturation_s"] is None

    def test_merged_exposition_capacity_families_lint_clean(self):
        text = self._agg().merged_exposition()
        assert lint_exposition(text) == []
        # Member-side vep_capacity_* samples survive the merge with
        # their instance label...
        assert ('vep_capacity_stream_device_ms_total{instance="m0",'
                'stream="cam1",kind="full"}') in text
        assert "vep_capacity_headroom" in text
        assert "vep_capacity_cell_utilization" in text
        # ...and the fleet-level member-capacity gauges render with the
        # -1 unreported sentinel for the capacity-less peer.
        assert 'vep_fleet_member_headroom{instance="m0"} 0.75' in text
        assert 'vep_fleet_member_headroom{instance="m1"} -1' in text
        assert ('vep_fleet_member_time_to_saturation_seconds'
                '{instance="m1"} -1') in text

    def test_scrape_tolerates_missing_capacity_endpoint(self):
        """A member whose /api/v1/capacity answers 400 (plane disabled)
        keeps scraping clean: metrics/stats/slo land, capacity stays
        empty."""
        agg = FleetAggregator(["m0=http://127.0.0.1:1"],
                              scrape_interval_s=0.2)
        pages = {
            "/metrics": _member_page("m0", 1, 0).encode(),
            "/api/v1/stats": json.dumps(
                {"engine": {"streams": {}}}).encode(),
            "/api/v1/slo": json.dumps({"burning": False}).encode(),
        }

        def fetch(url):
            for suffix, body in pages.items():
                if url.endswith(suffix):
                    return body
            raise OSError("HTTP 400: capacity plane disabled")

        agg._fetch = fetch
        agg.scrape_once()
        m0 = agg._members[0]
        assert m0.alive is True
        assert m0.capacity == {}
        row = {h["instance"]: h for h in agg.health()}["m0"]
        assert row["up"] is True and row["headroom"] is None


# ---------------------------------------------------------------------------
# HBM plane in the fleet merge (r21 satellite)


def _hbm_member_page(instance: str) -> str:
    """A member exposition with live vep_hbm_* families (registered and
    driven by a real HbmTracker — the lint check covers what the plane
    actually renders, including the sharded pool label)."""
    from video_edge_ai_proxy_tpu.obs.hbm import HbmTracker

    r = Registry()
    r.set_const_labels(instance=instance)
    r.counter("vep_frames_total", "frames", ("stream",)).labels(
        "cam1").inc(2)
    hbm = HbmTracker(budget_bytes=1_000_000, fast_window_s=10.0,
                     slow_window_s=100.0, eval_interval_s=0.0,
                     clock=lambda: 1000.0, registry=r)
    hbm.register_pool("thumbs", lambda: 4096)
    hbm.register_pool("track_state", lambda: {"0": 100, "1": 300})
    hbm.note_program("det", (64, 64), 4, {
        "argument_bytes": 100, "output_bytes": 50, "temp_bytes": 30,
        "code_bytes": 10, "alias_bytes": 20})
    hbm.evaluate(force=True)
    return r.render()


def _hbm_snapshot():
    return {"budget_bytes": 1_000_000, "used_bytes": 300_000,
            "utilization": {"fast": 0.3, "slow": 0.3},
            "burn": {"fast": 0.333, "slow": 0.333}, "burning": False,
            "headroom_bytes": 700_000, "time_to_oom_s": 240.0,
            "pressure": False}


class TestHbmFleetMerge:
    def _agg(self):
        """m0 reports the HBM plane, m1 does not (pre-r21 member /
        hbm=False): the mixed-version fleet must merge cleanly with -1
        sentinels, never a fake zero that would read as OOM-now."""
        agg = FleetAggregator(
            ["m0=http://127.0.0.1:1", "m1=http://127.0.0.1:1"],
            scrape_interval_s=0.2)
        _seed_member(agg._members[0], _hbm_member_page("m0"), streams=2)
        agg._members[0].hbm = _hbm_snapshot()
        _seed_member(agg._members[1], _member_page("m1", 5, 0), streams=1)
        return agg

    def test_mixed_version_health_rows(self):
        health = {h["instance"]: h for h in self._agg().health()}
        m0, m1 = health["m0"], health["m1"]
        assert m0["hbm"] is True
        assert m0["hbm_headroom_bytes"] == 700_000
        assert m0["hbm_utilization"] == pytest.approx(0.3)
        assert m0["time_to_oom_s"] == pytest.approx(240.0)
        # The hbm-less peer merges with None signals: the router treats
        # it as memory-blind (admitting on time alone), never as full.
        assert m1["hbm"] is False
        assert m1["hbm_headroom_bytes"] is None
        assert m1["hbm_utilization"] is None
        assert m1["time_to_oom_s"] is None

    def test_merged_exposition_hbm_families_lint_clean(self):
        text = self._agg().merged_exposition()
        assert lint_exposition(text) == []
        # Member-side vep_hbm_* samples survive the merge with their
        # instance label...
        assert ('vep_hbm_pool_bytes{instance="m0",pool="track_state"}'
                ' 400') in text
        assert 'vep_hbm_used_bytes{instance="m0"}' in text
        assert 'vep_hbm_donated_saved_bytes{instance="m0"} 20' in text
        # ...and the fleet-level member-HBM gauges render with the -1
        # unreported sentinel for the hbm-less peer.
        assert ('vep_fleet_member_hbm_headroom_bytes{instance="m0"} '
                '700000') in text
        assert ('vep_fleet_member_hbm_headroom_bytes{instance="m1"} '
                '-1') in text
        assert ('vep_fleet_member_time_to_oom_seconds{instance="m1"} '
                '-1') in text

    def test_scrape_tolerates_missing_hbm_endpoint(self):
        """A member whose /api/v1/hbm answers 400 (plane disabled) or
        404 (pre-r21 build) keeps scraping clean: metrics/stats/slo
        land, hbm stays empty."""
        agg = FleetAggregator(["m0=http://127.0.0.1:1"],
                              scrape_interval_s=0.2)
        pages = {
            "/metrics": _member_page("m0", 1, 0).encode(),
            "/api/v1/stats": json.dumps(
                {"engine": {"streams": {}}}).encode(),
            "/api/v1/slo": json.dumps({"burning": False}).encode(),
            "/api/v1/capacity": json.dumps({"headroom": 0.5}).encode(),
        }

        def fetch(url):
            for suffix, body in pages.items():
                if url.endswith(suffix):
                    return body
            raise OSError("HTTP 400: hbm plane disabled")

        agg._fetch = fetch
        agg.scrape_once()
        m0 = agg._members[0]
        assert m0.alive is True
        assert m0.hbm == {}
        row = {h["instance"]: h for h in agg.health()}["m0"]
        assert row["up"] is True
        assert row["hbm"] is False and row["hbm_headroom_bytes"] is None
        # The capacity plane it DOES report still lands.
        assert row["headroom"] == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# Warming member state (r19): scraped-alive but prewarm incomplete


class TestWarmingState:
    def _member(self, *, alive=True, prewarm="unset"):
        m = MemberState("m0", "http://127.0.0.1:1")
        m.alive = alive
        m.last_ok = time.monotonic()
        engine = {"streams": {}}
        if prewarm != "unset":
            engine["prewarm"] = prewarm
        m.stats = {"engine": engine}
        return m

    def test_state_table(self):
        # (alive, prewarm payload) -> warming. A member is warming ONLY
        # while scraped-alive with a reported-incomplete program set;
        # engine-less / pre-r19 members (no prewarm dict) never are.
        table = [
            (True, {"required": 2, "done": 1, "complete": False}, True),
            (True, {"required": 2, "done": 2, "complete": True}, False),
            (True, {"required": 0, "done": 0, "complete": True}, False),
            (False, {"required": 2, "done": 1, "complete": False}, False),
            (True, "unset", False),           # pre-r19 member
            (True, None, False),              # explicit null
            (True, "not-a-dict", False),      # malformed payload
            (True, {}, False),                # complete defaults True
        ]
        for alive, prewarm, want in table:
            m = self._member(alive=alive, prewarm=prewarm)
            assert m.warming() is want, (alive, prewarm)

    def _agg_with_warming(self):
        agg = FleetAggregator(
            ["m0=http://127.0.0.1:1", "m1=http://127.0.0.1:1"],
            scrape_interval_s=0.2)
        _seed_member(agg._members[0], _member_page("m0", 1, 0), streams=1)
        _seed_member(agg._members[1], _member_page("m1", 1, 0))
        agg._members[1].stats["engine"]["prewarm"] = {
            "required": 3, "done": 1, "complete": False,
            "aot_cache": True}
        return agg

    def test_health_rows_carry_warming(self):
        health = {h["instance"]: h for h in self._agg_with_warming()
                  .health()}
        assert health["m0"]["warming"] is False
        assert health["m1"]["warming"] is True
        # Warming is not unhealth: the member answers scrapes and must
        # keep its up/score standing (the supervisor distinguishes
        # "don't route to it yet" from "it is broken").
        assert health["m1"]["up"] is True

    def test_warming_gauge_in_merged_exposition(self):
        text = self._agg_with_warming().merged_exposition()
        assert lint_exposition(text) == []
        assert 'vep_fleet_member_warming{instance="m0"} 0' in text
        assert 'vep_fleet_member_warming{instance="m1"} 1' in text


# ---------------------------------------------------------------------------
# Runtime membership (r19 supervisor hooks)


class TestRuntimeMembership:
    def test_auto_names_are_monotonic_never_reused(self):
        # add(m0,m1), remove(m0), add(bare) must yield a FRESH name —
        # naming by list length would collide with m1 and raise.
        agg = FleetAggregator(["http://a:1", "http://b:1"])
        assert [m.name for m in agg._members] == ["m0", "m1"]
        agg.remove_member("m0")
        assert agg.add_member("http://c:1") == "m2"
        assert agg.add_member("http://d:1") == "m3"

    def test_auto_names_skip_operator_claimed_slots(self):
        agg = FleetAggregator(["m1=http://a:1"])
        assert agg.add_member("http://b:1") == "m2"
        assert agg.add_member("http://c:1") == "m3"

    def test_named_duplicates_still_raise(self):
        agg = FleetAggregator(["m0=http://a:1"])
        with pytest.raises(ValueError):
            agg.add_member("m0=http://b:1")


# ---------------------------------------------------------------------------
# Feature-disabled notice (satellite 1)


class TestFeatureDisabledGauge:
    def test_gauge_set_and_log_once(self):
        import logging

        from video_edge_ai_proxy_tpu.engine import runner
        from video_edge_ai_proxy_tpu.obs import registry as obs_registry

        # The vep_tpu root logger does not propagate (utils/logging.py),
        # so capture with a handler on the runner's own logger.
        records: list = []

        class _Capture(logging.Handler):
            def emit(self, record):
                records.append(record.getMessage())

        handler = _Capture()
        logger = logging.getLogger("vep_tpu.engine.runner")
        logger.addHandler(handler)
        try:
            runner._FEATURES_NOTED.discard(("roi", "test_reason"))
            runner._note_feature_disabled("roi", "test_reason")
            runner._note_feature_disabled("roi", "test_reason")
        finally:
            logger.removeHandler(handler)
        notices = [m for m in records if "test_reason" in m]
        assert len(notices) == 1          # once per process, not per tick
        text = obs_registry.render()
        assert ('vep_engine_feature_disabled{feature="roi",'
                'reason="test_reason"} 1' in text)


# ---------------------------------------------------------------------------
# Multi-engine trace merge (tools/obs_export.py --merge --member)


class TestMultiEngineMerge:
    def _spans_file(self, tmp_path, name, stream):
        rec = SpanRecorder(enabled=True, sample_every=1)
        tid = trace_id_for(stream, 1)
        rec.record(stream, "collect", 1, ts=1.0, trace_id=tid)
        rec.record(stream, "emit", 1, ts=1.01, trace_id=tid)
        path = tmp_path / f"{name}.json"
        path.write_text(json.dumps({"events": rec.events()}))
        return str(path)

    def test_member_pid_namespaces(self, tmp_path):
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
        from tools.obs_export import merge_traces

        members = []
        for i in range(3):
            with open(self._spans_file(tmp_path, f"m{i}", f"cam{i}")) as f:
                members.append((f"m{i}", json.load(f)["events"]))
        trace = merge_traces(None, None, members=members)
        pids = {ev["pid"] for ev in trace["traceEvents"]}
        assert pids == {1, 2, 3}
        names = {ev["args"]["name"] for ev in trace["traceEvents"]
                 if ev.get("name") == "process_name"}
        assert names == {"m0", "m1", "m2"}
        assert trace["metadata"]["merge"]["members"] == ["m0", "m1", "m2"]

    def test_cli_member_flags(self, tmp_path):
        out = tmp_path / "fleet_trace.json"
        env = dict(os.environ)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        cmd = [sys.executable, os.path.join(root, "tools", "obs_export.py"),
               "--merge", "--check", "-o", str(out)]
        for i in range(2):
            cmd += ["--member",
                    f"m{i}={self._spans_file(tmp_path, f'cli{i}', f'cam{i}')}"]
        res = subprocess.run(cmd, capture_output=True, text=True, env=env,
                             timeout=60)
        assert res.returncode == 0, res.stderr
        assert json.loads(res.stdout)["check"] == "ok"
        trace = json.loads(out.read_text())
        assert {ev["pid"] for ev in trace["traceEvents"]} == {1, 2}


# ---------------------------------------------------------------------------
# Two-process aggregation conformance (satellite 3): real serve
# processes, real HTTP scrapes, CPU backend, no skips.


_MEMBER_SCRIPT = textwrap.dedent("""
    import json, os, sys
    sys.path.insert(0, {root!r})
    from video_edge_ai_proxy_tpu.obs import registry
    from video_edge_ai_proxy_tpu.serve.server import Server
    from video_edge_ai_proxy_tpu.utils.config import Config

    instance, inc, workdir = sys.argv[1], float(sys.argv[2]), sys.argv[3]
    registry.counter(
        "vep_fleettest_total", "fleet conformance counter", ("k",)
    ).labels("x").inc(inc)
    cfg = Config()
    cfg.bus.shm_dir = os.path.join("/dev/shm", f"vep_ft_{{os.getpid()}}")
    cfg.annotation.endpoint = "http://127.0.0.1:1/annotate"
    cfg.obs.instance = instance
    srv = Server(cfg, data_dir=workdir, grpc_port=0, rest_port=0,
                 enable_engine=False)
    srv.start()
    print(json.dumps({{"rest_port": srv._rest.bound_port}}), flush=True)
    sys.stdin.readline()
    srv.stop()
    import shutil
    shutil.rmtree(cfg.bus.shm_dir, ignore_errors=True)
""")


class TestTwoProcessConformance:
    def test_merged_counters_and_kill_staleness(self, tmp_path):
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        script = tmp_path / "member.py"
        script.write_text(_MEMBER_SCRIPT.format(root=root))
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"   # control plane never inits jax,
        # but a child that did must not reach for an attached chip
        procs = []
        ports = []
        try:
            for i, inc in enumerate((3.0, 5.0)):
                wd = tmp_path / f"m{i}"
                wd.mkdir()
                p = subprocess.Popen(
                    [sys.executable, str(script), f"m{i}", str(inc),
                     str(wd)],
                    stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                    stderr=subprocess.PIPE, text=True, env=env)
                procs.append(p)
            for p in procs:
                # Server logs share stdout with the ready line — skim
                # until the JSON message (same protocol run_fleet_obs
                # speaks with its members).
                port = None
                deadline = time.time() + 60
                while port is None and time.time() < deadline:
                    line = p.stdout.readline()
                    assert line, p.stderr.read()
                    try:
                        port = json.loads(line)["rest_port"]
                    except (ValueError, KeyError):
                        continue
                assert port is not None
                ports.append(port)

            agg = FleetAggregator(
                [f"m{i}=http://127.0.0.1:{port}"
                 for i, port in enumerate(ports)],
                scrape_interval_s=0.5)
            agg.scrape_once()

            # Both members present + fresh.
            health = {h["instance"]: h for h in agg.health()}
            assert set(health) == {"m0", "m1"}
            assert all(h["up"] and not h["stale"]
                       for h in health.values())

            # Merged counters == sum of members; per-instance parts kept.
            fs = agg.fleet_stats()
            row = fs["counters"]["vep_fleettest_total"]['k="x"']
            assert row["value"] == 8.0
            assert row["instances"] == {"m0": 3.0, "m1": 5.0}

            # Merged exposition lint-clean with both instances labeled.
            merged = agg.merged_exposition()
            assert lint_exposition(merged) == []
            assert 'vep_fleettest_total{instance="m0",k="x"} 3' in merged
            assert 'vep_fleettest_total{instance="m1",k="x"} 5' in merged

            # Kill m1 (by PID via the Popen handle); the NEXT scrape
            # pass must flag it stale — within one scrape interval.
            procs[1].kill()
            procs[1].wait(timeout=10)
            agg.scrape_once()
            health = {h["instance"]: h for h in agg.health()}
            assert health["m1"]["stale"] is True
            assert health["m1"]["up"] is False
            assert health["m0"]["stale"] is False
            assert health["m0"]["score"] > health["m1"]["score"]
            # The survivor's counter still serves from the last scrape.
            merged = agg.merged_exposition()
            assert lint_exposition(merged) == []
            assert 'vep_fleet_member_stale{instance="m1"} 1' in merged
        finally:
            for p in procs:
                if p.poll() is None:
                    try:
                        p.stdin.write("exit\n")
                        p.stdin.flush()
                    except (BrokenPipeError, OSError):
                        pass
                    try:
                        p.wait(timeout=10)
                    except subprocess.TimeoutExpired:
                        p.kill()   # by PID via the handle, never pkill


# ---------------------------------------------------------------------------
# REST fleet routes (serve/rest_api.py)


class TestFleetRoutes:
    def test_disabled_returns_400(self):
        # No fleet_members configured -> both routes refuse with the
        # standard kill-switch message instead of serving empties.
        from aiohttp.test_utils import TestClient, TestServer
        import asyncio

        from video_edge_ai_proxy_tpu.serve.rest_api import build_app

        class _PM:
            def list(self):
                return []

        async def run():
            app = build_app(_PM(), settings=None, fleet=None)
            async with TestClient(TestServer(app)) as client:
                r1 = await client.get("/api/v1/fleet/stats")
                r2 = await client.get("/api/v1/fleet/metrics")
                return r1.status, r2.status

        s1, s2 = asyncio.new_event_loop().run_until_complete(run())
        assert s1 == 400 and s2 == 400

    def test_enabled_serves_merged_plane(self):
        from aiohttp.test_utils import TestClient, TestServer
        import asyncio

        from video_edge_ai_proxy_tpu.serve.rest_api import build_app

        agg = FleetAggregator(["m0=http://127.0.0.1:1"],
                              scrape_interval_s=0.2)
        _seed_member(agg._members[0], _member_page("m0", 4, 1))

        class _PM:
            def list(self):
                return []

        async def run():
            app = build_app(_PM(), settings=None, fleet=agg)
            async with TestClient(TestServer(app)) as client:
                stats = await (await client.get("/api/v1/fleet/stats")).json()
                page = await (await client.get(
                    "/api/v1/fleet/metrics")).text()
                return stats, page

        stats, page = asyncio.new_event_loop().run_until_complete(run())
        assert stats["members"] == 1
        assert stats["counters"]["vep_frames_total"][
            'stream="cam1"']["value"] == 4.0
        assert lint_exposition(page) == []
        assert "vep_fleet_member_health_score" in page
