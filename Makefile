# Build / codegen targets (reference Makefile parity: proto codegen was its
# whole build; ours adds the native bus lib and test/bench shortcuts).

.PHONY: all proto native install test bench graft clean redis-conformance \
	obs-smoke chaos-smoke prof-smoke quality-smoke perf-gate h2d-smoke \
	roi-smoke fleet-obs-smoke stem-smoke router-smoke cascade-smoke \
	capacity-smoke autoscale-smoke multichip-serve-smoke hbm-smoke \
	fault-smoke journal-smoke

all: proto native

# Regenerate gRPC stubs after editing proto/video_streaming.proto
# (reference Makefile:5-17 — one schema, generated bindings checked in).
# Prefer grpc_tools (generator and Python runtime ship from the same wheel,
# so no gencode/runtime version skew); fall back to the system `protoc` for
# message-only edits where grpcio-tools isn't installed — then verify the
# regenerated stub actually imports against the local runtime.
proto:
	@if python -c "import grpc_tools" 2>/dev/null; then \
		python -m grpc_tools.protoc \
			-I video_edge_ai_proxy_tpu/proto \
			--python_out=video_edge_ai_proxy_tpu/proto \
			--grpc_python_out=video_edge_ai_proxy_tpu/proto \
			video_edge_ai_proxy_tpu/proto/video_streaming.proto \
		&& sed -i 's/^import video_streaming_pb2/from . import video_streaming_pb2/' \
			video_edge_ai_proxy_tpu/proto/video_streaming_pb2_grpc.py; \
	else \
		echo "grpcio-tools not installed; regenerating MESSAGES ONLY with" \
			"system protoc — a service-definition change still needs" \
			"'make install' + rerun"; \
		protoc -I video_edge_ai_proxy_tpu/proto \
			--python_out=video_edge_ai_proxy_tpu/proto \
			video_edge_ai_proxy_tpu/proto/video_streaming.proto; \
	fi
	python -c "from video_edge_ai_proxy_tpu.proto import pb, pb_grpc; pb.VideoFrame(); pb_grpc.ImageStub"

# Force-rebuild the native libs (normally built+cached on first import):
# the C++ shm bus core and the libav demux/mux shim.
native:
	rm -rf ~/.cache/vep_tpu
	python -c "from video_edge_ai_proxy_tpu.bus.native.build import build_library; print(build_library())"
	python -c "from video_edge_ai_proxy_tpu.utils.cbuild import build_library; import video_edge_ai_proxy_tpu.ingest.av as av; print(build_library(av._SRC, 'vepav', av._LDFLAGS))"

# Tooling for the proto target (reference Makefile:20-24).
install:
	pip install -U grpcio grpcio-tools

test:
	python -m pytest tests/ -x -q

bench:
	python bench.py

# Observability smoke: a short instrumented replay soak (CPU backend,
# tiny twins), exporting the sampled frame-lineage spans as Chrome trace
# JSON and schema-validating the export. Proves one replay run yields the
# stage-segmented latency breakdown + a loadable trace (ISSUE obs
# acceptance). ~1 min.
obs-smoke:
	python tools/soak_replay.py --duration 15 --no-e2e \
		--out /tmp/vep_obs_smoke.json --trace-out /tmp/vep_obs_trace.json
	python tools/obs_export.py /tmp/vep_obs_trace.json --check
	@python -c "import json; d=json.load(open('/tmp/vep_obs_smoke.json')); \
		print(json.dumps(d['soak']['obs']['stage_breakdown'], indent=2))"

# Resilience chaos smoke: a short replay soak (CPU backend, tiny twins)
# under the three scripted resilience faults — annotation uplink down,
# bus flap, device stall — gated on zero deadlocks (uplink fully drains),
# zero lost annotations (delivered + explicit spool evictions ==
# published), and bounded subscriber drops. Deterministic fault schedule
# (replay/faults.py windows); the gates live in tools/soak_replay.py and
# exit non-zero on breach. ~1 min.
chaos-smoke:
	python tools/soak_replay.py --duration 20 --no-e2e \
		--faults uplink_down,bus_flap,device_stall \
		--out /tmp/vep_chaos_smoke.json
	@python -c "import json; d=json.load(open('/tmp/vep_chaos_smoke.json')); \
		print(json.dumps(d['soak']['resilience'], indent=2))"

# Triggered-profiling smoke: a short chaos soak (CPU backend) with
# --profile-on-burn armed — the device_stall fault escalates the ladder,
# which must fire a real bounded jax.profiler capture (hard gate in
# soak_replay.py: an intact triggered bundle exists on disk). Then merge
# the newest bundle's device trace with its concurrent lineage-span
# window into ONE Perfetto timeline (obs_export.py --merge --check) and
# assert both the host span track and >=1 profiler device track are
# present. ~1 min.
prof-smoke:
	rm -rf /tmp/vep_prof_smoke && mkdir -p /tmp/vep_prof_smoke
	python tools/soak_replay.py --duration 20 --no-e2e \
		--faults device_stall --profile-on-burn \
		--prof-dir /tmp/vep_prof_smoke \
		--out /tmp/vep_prof_smoke.json
	@python -c "import os; \
		d='/tmp/vep_prof_smoke'; \
		bs=sorted(p for p in os.listdir(d) if os.path.isdir(os.path.join(d,p))); \
		assert bs, 'no capture bundles in '+d; \
		print('bundle:', bs[-1]); \
		open('/tmp/vep_prof_bundle.txt','w').write(os.path.join(d,bs[-1]))"
	python tools/obs_export.py $$(cat /tmp/vep_prof_bundle.txt) --merge \
		--check -o /tmp/vep_prof_merged.json
	@python -c "import json; \
		t=json.load(open('/tmp/vep_prof_merged.json')); \
		pids={e['pid'] for e in t['traceEvents'] if 'pid' in e}; \
		assert 1 in pids, 'host span track (pid 1) missing'; \
		dev=sorted(p for p in pids if p >= 1000); \
		assert dev, 'no profiler device track in the merged timeline'; \
		m=t['metadata']['merge']; \
		print(json.dumps({'host_events': m['host_events'], \
			'device_events': m['device_events'], \
			'device_pids': m['device_pids'], \
			'clock_anchor': m['anchor']}))"

# Output-quality smoke: a short replay soak (CPU backend, tiny twins)
# under the three scripted quality faults — lens-cap black frames, a
# frozen decoder, and a silent score drift — gated on every fault being
# DETECTED (verdict transition within the latency bound; canary
# checksum mismatch + watchdog episode for the drift) with ZERO false
# positives over the clean remainder of the window. Deterministic
# schedule (replay/faults.py _QUALITY_WINDOWS); gates in
# tools/soak_replay.py exit non-zero on breach; writes the
# QUALITY_r07.json attribution artifact. ~1 min.
quality-smoke:
	python tools/soak_replay.py --duration 20 --no-e2e \
		--faults black_frame,frozen_frame,score_drift \
		--out /tmp/vep_quality_smoke.json \
		--quality-out /tmp/vep_quality_r07.json
	@python -c "import json; d=json.load(open('/tmp/vep_quality_r07.json')); \
		assert all(f['detected'] for f in d['faults']), d['faults']; \
		assert not d['false_positives'], d['false_positives']; \
		print(json.dumps(d['faults'], indent=2))"

# H2D prefetch overlap smoke: a short two-geometry lockstep serve on a
# MemoryFrameBus (CPU backend, tiny twin) proving the transfer stage
# hides copy time behind dispatch/compute. Gates (in tools/h2d_smoke.py,
# exit non-zero on breach): >=3 served batches per geometry, aggregate
# h2d_hidden_pct > 0, and the vep_h2d_* metric families (including the
# round-8 vep_h2d_hidden_seconds counter) render lint-clean Prometheus
# exposition. ~15 s.
h2d-smoke:
	python tools/h2d_smoke.py | tee /tmp/vep_h2d_smoke.json
	@python -c "import json; \
		lines=[l for l in open('/tmp/vep_h2d_smoke.json') if l.startswith('{')]; \
		d=json.loads(lines[-1]); \
		assert d['h2d_hidden_pct'] and d['h2d_hidden_pct'] > 0, d; \
		assert not d['exposition_problems'], d['exposition_problems']; \
		print('h2d overlap: %.1f%% of transfer wall hidden (%d batches/geometry)' \
			% (d['h2d_hidden_pct'], d['batches_per_geometry']))"

# MOSAIC ROI serving smoke: two lockstep serves over a color-keyed
# synthetic fleet (3 moving + 3 static streams, blob-gauge model),
# roi=False baseline vs roi=True packed path. Gates (in
# tools/roi_smoke.py, exit non-zero on breach): mean IoU vs analytic
# ground truth >= 0.9, ZERO misrouted/unrouted detections, the motion
# gate engaged (idle+roi stream-ticks, >=1 canvas), and >= 2x
# full-frame-equivalent throughput per device frame. The committed
# ROI_r01.json artifact is a pinned run of this tool. ~30 s.
# r14 fleet telemetry: 3 member Server subprocesses replaying through
# real workers/buses/engines, one FleetAggregator scraping them. The
# tool hard-gates (exit nonzero): merged exposition lint-clean, every
# member present + fresh, >=1 fully-stitched cross-process trace
# (worker -> bus -> engine -> client via the on-wire trace_id), and
# merged counters == sum of per-member scrapes. Commits FLEETOBS_r01.json.
fleet-obs-smoke:
	python tools/soak_replay.py --fleet 3 --fleet-out FLEETOBS_r01.json \
		| tee /tmp/vep_fleet_obs.json
	@python -c "import json; \
		lines=[l for l in open('/tmp/vep_fleet_obs.json') if l.startswith('{')]; \
		d=json.loads(lines[-1]); g=d['gates']; \
		print('fleet obs: %d members, %d stitched traces, lint_clean=%s, conserved=%s' \
			% (d['members'], g['stitched_traces'], \
			   g['merged_lint_clean'], g['counters_conserved']))"

# Detect-stem smoke (round 12): CPU tiny twin of the s2d/int8 detect
# path. Gates (in tools/stem_smoke.py, exit non-zero on breach): fused
# letterbox+s2d preprocess matches the two-pass reference to bf16
# rounding, the classic->s2d stem kernel fold is lossless at the model
# level (1e-3 px), the calibrated int8 activation path stays within its
# committed mAP50 self-consistency tolerance, and an engine configured
# stem="s2d" + quantize="int8_act" warms up and serves through a real
# bus. ~30 s.
stem-smoke:
	python tools/stem_smoke.py | tee /tmp/vep_stem_smoke.json
	@python -c "import json; \
		lines=[l for l in open('/tmp/vep_stem_smoke.json') if l.startswith('{')]; \
		d=json.loads(lines[-1]); \
		print('stem: fold maxdiff %.2g px, fused maxdiff %.2g, int8 mAP50 %.3f, %d engine frames' \
			% (d['fold_box_maxdiff_px'], d['fused_vs_two_pass_maxdiff'], \
			   d['int8_act_map50_vs_fp'], d['engine_frames_served']))"

# Fleet-router acceptance (round 13 = r16): 3 serve-only members, 6
# replay streams placed by serve/router.py's consistent-hash ring, then
# two fault legs. Gates (in tools/router_smoke.py, exit non-zero on
# breach): burn leg — the forced-burn member's ladder reaches
# shed_to_fleet and the router migrates its streams BEFORE the local
# ladder hits bucket_downshift; kill leg — every stream of a SIGKILLed
# member re-placed, detect->resumed within one scrape interval; the
# frame-conservation ledger balances for every stream (zero lost, zero
# duplicated across the drain->cutover->resume handoffs); every
# migration has a stitched worker->bus->engine->client lineage chain;
# and vep_router_* exposition is lint-clean. Commits ROUTER_r01.json.
router-smoke:
	python tools/router_smoke.py | tee /tmp/vep_router_smoke.json
	@python -c "import json; \
		lines=[l for l in open('/tmp/vep_router_smoke.json') if l.startswith('{')]; \
		d=json.loads(lines[-1]); \
		print('router: %d members / %d streams, burn handoff %.1fs, kill detect->resumed %.2fs (wall %.2fs), ledger lost=%d dup=%d' \
			% (d['members'], d['streams'], d['burn_migrate_s'], \
			   d['kill_replace_detect_s'], d['kill_replace_wall_s'], \
			   d['ledger']['lost'], d['ledger']['duplicated']))"

capacity-smoke:
	python tools/capacity_smoke.py | tee /tmp/vep_capacity_smoke.json
	@python -c "import json; \
		lines=[l for l in open('/tmp/vep_capacity_smoke.json') if l.startswith('{')]; \
		d=json.loads(lines[-1]); \
		print('capacity: ledger conserves (drift %.1e), kinds %s, tap %.1fus (%.2f%% of tick budget), tts %.0fs->%.0fs monotone, storm %s (saturating member: %d admissions)' \
			% (d['ledger']['conservation']['rel_drift'], \
			   '+'.join(d['ledger']['kinds']), \
			   d['ledger']['ledger_tap_mean_us'], \
			   d['ledger']['ledger_tap_pct_of_tick_budget'], \
			   d['forecast']['tts_first_s'], d['forecast']['tts_last_s'], \
			   d['admission']['storm_by_member'], \
			   d['admission']['saturating_member_admissions']))"

# HBM attribution acceptance (round 21): track-churn pool exactness
# (aggregate + per-shard under dp=2) across a grow-by-8 ring
# reallocation, fake-clock OOM forecast monotonicity, a memory-blind
# admission storm the byte-exhausted member must survive untouched, and
# the hbm=False bit-exactness replay pin. Gates live in
# tools/hbm_smoke.py and exit non-zero on breach; the committed
# HBM_r01.json artifact is a pinned run of this tool. ~30 s.
hbm-smoke:
	python tools/hbm_smoke.py | tee /tmp/vep_hbm_smoke.json
	@python -c "import json; \
		lines=[l for l in open('/tmp/vep_hbm_smoke.json') if l.startswith('{')]; \
		d=json.loads(lines[-1]); \
		print('hbm: pool delta %d B (shard %s), ring %d growth events, tto %.0fs->%.0fs monotone=%s, storm %s (exhausted member: %d admissions), hbm-off bitexact=%s' \
		% (d['pools']['max_abs_delta_bytes'], \
		   d['pools']['dp2']['shard_max_abs_delta_bytes'], \
		   d['pools']['aggregate']['ring_growth_events'], \
		   d['forecast']['tto_first_s'], d['forecast']['tto_last_s'], \
		   d['forecast']['tto_monotone_decreasing'], \
		   d['admission']['storm_by_member'], \
		   d['admission']['exhausted_member_placements'], \
		   d['replay']['hbm_off_bitexact']))"

# Device-fault acceptance (round 22): hard-error shard loss dp4->dp3 on
# the 8-virtual-device CPU twin (detect <=2 ticks, failover inside
# budget with AOT survivor-variant prewarm, deterministic stream
# evacuation, >=90% pin retention), an informational stall leg dp3->dp2
# (hysteresis + probe quorum), and the frame-conservation ledger: zero
# lost / zero duplicated outside the declared failover windows. Gates
# live in tools/fault_smoke.py and exit non-zero on breach; the
# committed FAULT_r01.json artifact is a pinned run of this tool. ~30 s.
fault-smoke:
	python tools/fault_smoke.py --out FAULT_r01.json | tee /tmp/vep_fault_smoke.json
	@python -c "import json; \
		lines=[l for l in open('/tmp/vep_fault_smoke.json') if l.startswith('{')]; \
		d=json.loads(lines[-1]); h=d['hard_fault']; led=d['ledger']; \
		print('fault: hard dp4->dp3 detect %d ticks, failover %.0fms (aot %d/%d), evac %.0fms, pin retention %.2f; stall dp3->dp2 %.0fms composes=%s; ledger lost=%d dup=%d outside-window=%d (excused device_fault=%d)' \
		% (h['detect_ticks'], h['failover']['failover_ms'], \
		   h['failover']['aot']['recorded'], h['failover']['aot']['prewarmed'], \
		   h['evac_first_result_ms'], h['pin_retention'], \
		   d['stall_fault']['failover']['failover_ms'], \
		   d['stall_fault']['repin_composes'], \
		   led['lost'], led['duplicated'], led['lost_outside_window'], \
		   led['dropped'].get('device_fault', 0)))"

# Decision-journal acceptance (round 23): CPU-twin engine degraded
# through a REAL SLO burn, gating that /api/v1/why?stream=S resolves
# the complete slo episode_open -> ladder escalate -> per-stream
# cascade_stretch chain with quantitative triggers, ladder-transition /
# journal-event conservation, deterministic fleet merge, record() mean
# < 50us (0.5% of the 10ms tick), and journal=False bit-identical
# serving. Gates live in tools/journal_smoke.py and exit non-zero on
# breach; the committed JOURNAL_r01.json artifact is a pinned run. ~1 min.
journal-smoke:
	python tools/journal_smoke.py --out JOURNAL_r01.json | tee /tmp/vep_journal_smoke.json
	@python -c "import json; \
		lines=[l for l in open('/tmp/vep_journal_smoke.json') if l.startswith('{')]; \
		d=json.loads(lines[-1]); c=d['chain']; o=d['overhead']; \
		print('journal: why(%s) %d-link chain in %.1fs, %d/%d ladder transitions journaled, merge deterministic=%s, record mean %.1fus (< %.0fus), journal-off identical=%s' \
		% (c['stream'], c['why']['links'], c['stretched_at_s'], \
		   d['conservation']['ladder_journaled'], \
		   d['conservation']['ladder_transitions'], \
		   d['merge']['deterministic'], o['record_mean_us'], \
		   o['budget_us'], d['kill_switch']['bit_identical']))"

autoscale-smoke:
	python tools/autoscale_smoke.py | tee /tmp/vep_autoscale_smoke.json
	@python -c "import json; \
		lines=[l for l in open('/tmp/vep_autoscale_smoke.json') if l.startswith('{')]; \
		d=json.loads(lines[-1]); \
		print('autoscale: boots cold %.1fs / warm %.1fs / spawn %.1fs, spawn->first-frame %.2fs, storm p99 %.2fs, ledger lost=%d dup=%d' \
			% (d['boots']['m0'], d['boots']['m1'], \
			   d['boots'].get('a0', float('nan')), \
			   d['spawn_first_frame_s'], d['storm_p99_s'], \
			   d['ledger']['lost'], d['ledger']['duplicated']))"

cascade-smoke:
	python tools/cascade_smoke.py | tee /tmp/vep_cascade_smoke.json
	@python -c "import json; \
		lines=[l for l in open('/tmp/vep_cascade_smoke.json') if l.startswith('{')]; \
		d=json.loads(lines[-1]); \
		print('cascade: head cadence 1/%d exact, enter latency %d ticks (<= %d), %d/%d enter/exit uplinked, slot high water %d' \
			% (d['cascade_every_n'], d['cascade_event_latency_ticks'], \
			   d['gates']['max_event_latency_ticks'], d['uplink_enter_requests'], \
			   d['uplink_exit_requests'], d['slot_high_water']))"

# Mesh-native serving acceptance (round 17): lockstep replay fleet on
# dp=1/2/4 CPU meshes (8 virtual devices). Gates (in
# tools/multichip_serve_smoke.py, exit non-zero on breach): dp=1 mesh
# replay checksum bit-identical to the single-chip path (plus a
# subprocess anchor of the committed 1-device golden — the
# host-device-count flag changes XLA CPU codegen numerics, see the tool
# docstring), ZERO misrouted and ZERO unrouted ROI scatter-backs on
# every leg, per-shard capacity conservation drift exactly 0.0, cascade
# live on-mesh, vep_*_shard exposition lint-clean, and aggregate fps at
# dp=4 >= 3.2x dp=1. The committed MULTICHIP_SERVE_r01.json artifact is
# a pinned run of this tool. ~2 min.
multichip-serve-smoke:
	python tools/multichip_serve_smoke.py | tee /tmp/vep_multichip_smoke.json
	@python -c "import json; \
		lines=[l for l in open('/tmp/vep_multichip_smoke.json') if l.startswith('{')]; \
		d=json.loads(lines[-1]); s=d['serve']; \
		print('multichip serving: dp1 %.0f / dp2 %.0f / dp4 %.0f fps (scale %.2fx), lockstep bit_identical=%s, misrouted=%d unrouted=%d' \
			% (s['dp1']['fps'], s['dp2']['fps'], s['dp4']['fps'], \
			   d['fps_scale_dp4_over_dp1'], d['lockstep']['bit_identical'], \
			   sum(l['misrouted'] for l in s.values()), \
			   sum(l['unrouted'] for l in s.values())))"

roi-smoke:
	python tools/roi_smoke.py | tee /tmp/vep_roi_smoke.json
	@python -c "import json; \
		lines=[l for l in open('/tmp/vep_roi_smoke.json') if l.startswith('{')]; \
		d=json.loads(lines[-1]); \
		print('roi serving: %.2fx equivalent fps, IoU mean %.4f, %d crops on %d canvases' \
			% (d['equivalent_fps_gain'], d['roi']['iou_mean'], \
			   d['roi']['perf_roi']['crops'], d['roi']['perf_roi']['canvases']))"

# Performance regression gate: run the bench, then compare its JSON line
# against the committed BENCH_r*.json trajectory (tools/bench_gate.py;
# fails below best-committed minus 5%). Metric-matched: a metric with no
# committed baseline records and passes (first-run semantics). Needs the
# chip: bench.py exits non-zero when jax finds no TPU.
perf-gate:
	python bench.py | tee /tmp/vep_bench_latest.json
	python tools/bench_gate.py /tmp/vep_bench_latest.json

# One-command genuine-Redis conformance run (VERDICT r3 #8): on any host
# with redis-server on PATH, re-runs every Redis-plane test against the
# real server and records the result to REDIS_CONFORMANCE.json. This
# image ships no redis-server (the run requires one and says so loudly);
# the runbook lives in BASELINE.md.
redis-conformance:
	@command -v redis-server >/dev/null || \
		{ echo "redis-server not on PATH - install it, then re-run"; exit 1; }
	python tools/redis_conformance.py --record REDIS_CONFORMANCE.json

graft:
	python __graft_entry__.py

clean:
	rm -rf .jax_cache .pytest_cache
	find . -name __pycache__ -type d -exec rm -rf {} +
