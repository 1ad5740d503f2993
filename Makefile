# Developer entry points (CI parity with the reference's Jenkinsfile stages:
# lint, local tests, distributed tests, benchmarks).
PY ?= python

.PHONY: test test-all test-dist native proto lint clean mosaic-aot aot-fused-norm verify audit telemetry-check timeline-check monitor-check chaos perf-gate serve-check postmortem-check fleet-check check

test:
	$(PY) -m pytest tests/ -x -q

test-all:
	$(PY) -m pytest tests/ -q --run-integration

test-dist:
	$(PY) -m pytest tests/integration/ -q --run-integration

native:
	$(MAKE) -C native

proto:
	bash autodist_tpu/proto/gen.sh

# Pallas surface through the REAL Mosaic/XLA:TPU compiler, no chip needed
# (libtpu deviceless topology compile); writes MOSAIC_AOT.json
mosaic-aot:
	$(PY) tools/mosaic_aot_check.py

# model x strategy sweep compiled for v5e targets (XLA cost/memory stats
# + roofline ranking); writes records/v5e_aot/summary.json
aot-sweep:
	$(PY) tools/aot_sweep.py

# HBM capacity proof for ResNet-50 B=256 and GPT-2-small S=1024 (several minutes);
# writes records/v5e_aot/capacity.json
aot-capacity:
	$(PY) tools/aot_capacity.py

# GPT flagship batch/remat lever sweep for v5e (minutes per variant);
# writes records/v5e_aot/gpt_levers.json
aot-gpt-levers:
	$(PY) tools/aot_gpt_levers.py

# fused-normalization lever proof (the F008 remediation): the fused
# Pallas batch norm's deviceless Mosaic compile for v5e vs the unfused
# reference lowering at the same norm site — >= 30% fewer XLA-counted
# HBM bytes asserted; writes records/v5e_aot/fused_norm_lever.json
aot-fused-norm:
	$(PY) tools/aot_fused_norm.py

lint:
	$(PY) tools/lint.py
	$(PY) -m compileall -q autodist_tpu tests examples

# static strategy verification, no TPU needed (docs/analysis.md): every
# recorded sweep strategy must verify clean, and the canonical rejected
# case (--selftest) must still produce its three ERROR findings
verify:
	$(PY) tools/verify_strategy.py records/cpu_mesh/*.json
	$(PY) tools/verify_strategy.py --selftest

# HLO audits (docs/analysis.md): lower every recorded strategy's step
# and diff the REALIZED program against the strategy's plan — the
# communication audit (X-codes: an implicit-reshard all_to_all or a
# dropped sync collective fails the gate; the seeded reshard case must
# be caught as X001) and the compute audit (F-codes: every target must
# emit its F006 FLOP table with zero F001 realized-FLOP blowups AND a
# precision-aware contraction_flops_by_dtype table that reconciles
# against realized FLOPs — bf16 contractions counted exactly once, no
# double-count against jaxpr_flops; the seeded remat case must be
# caught as F002, the seeded all-f32 case as F003, the seeded
# dropped-donation case as F004, and --suggest must map each to its
# documented strategy/engine delta; every target must also emit its
# F007 HBM-traffic table — per-region bytes, arithmetic intensity,
# roofline legs — with F008 flagging any genuinely memory-bound step
# toward the fused-norm/GroupNorm byte levers) plus the cross-rank
# LOCKSTEP
# verifier (L-codes: every strategy's step expanded into per-rank
# rendezvous traces and proven deadlock-free with its L006 trace table;
# the seeded broken-ring case must fire exactly L003 and the seeded
# divergent-cond case exactly L001) plus the DETERMINISM tier (N-codes:
# every strategy's PRNG key lineage, batch-shard coverage, and lowered
# order-hazard scatters audited — every target must emit its N006
# key-lineage table with its determinism class and zero N001-N003; the
# seeded replicated-dropout case must fire exactly N001 and the seeded
# shard-overlap case exactly N003)
audit:
	$(PY) tools/verify_strategy.py --hlo records/cpu_mesh/*.json
	$(PY) tools/verify_strategy.py --hlo --selftest
	$(PY) tools/verify_strategy.py --compute records/cpu_mesh/*.json
	$(PY) tools/verify_strategy.py --compute --suggest --selftest
	$(PY) tools/verify_strategy.py --lockstep records/cpu_mesh/*.json
	$(PY) tools/verify_strategy.py --lockstep --selftest
	$(PY) tools/verify_strategy.py --determinism records/cpu_mesh/*.json
	$(PY) tools/verify_strategy.py --determinism --selftest

# live telemetry gate (docs/observability.md): a 5-step CPU-mesh session
# with telemetry on must emit a schema-valid JSONL manifest with per-step
# walls / throughput / MFU / memory snapshots, render through
# tools/telemetry_report.py, and calibrate from its RuntimeRecord
telemetry-check:
	$(PY) tools/telemetry_check.py

# runtime timeline gate (docs/observability.md): every records/cpu_mesh
# strategy runs 5 live CPU-mesh steps with the last captured under
# jax.profiler.trace and audited by the RUNTIME tier — every strategy
# must emit its T006 three-way table with zero T001 (exposed comm); the
# golden fixtures must fire T001 (exposed-comm trace), T002 (skewed
# two-worker pair) and reconcile the overlapped trace with
# CostEstimate.overlapped_s (--runtime --selftest)
timeline-check:
	$(PY) tools/timeline_check.py
	$(PY) tools/verify_strategy.py --runtime --selftest

# live control-plane gate (docs/observability.md "Live control plane"):
# a telemetry-enabled CPU-mesh session streams frames to a chief-side
# TelemetryCollector over the length-prefixed-JSON socket, the mirrored
# cluster event log folds into the schema-v3 manifest with a clean E005
# causality table, tools/monitor.py --once and telemetry_report --follow
# render the run dir, and a dead collector degrades to file-only with
# counted drops; the E-code fixtures must fire E001 (unacted signal) and
# E002 (blown MTTR budget) with a clean control (--events --selftest)
monitor-check:
	$(PY) tools/monitor_check.py
	$(PY) tools/verify_strategy.py --events --selftest

# fault-injection gate (docs/elasticity.md): CPU-mesh chaos drills —
# kill-one-worker (drain -> manifest checkpoint -> AutoStrategy re-plan on
# the shrunk topology -> R->R' reshard incl. sharded opt state -> Y/X
# verify gate -> loss-continuous resume), SIGTERM preempt + bitwise
# same-topology resume, and straggler-delay injection
chaos:
	$(PY) tools/chaos_check.py

# cross-run regression gate (docs/observability.md): the golden fixtures
# must fire R001 (seeded slow manifest) and R002 (NaN manifest) with a
# clean control (--selftest), then every records/cpu_mesh strategy is
# re-measured on the CPU mesh and diffed against its blessed baseline in
# records/baselines — every strategy must emit its R006 run-vs-baseline
# table with zero R001/R004 (bless an intentional perf change with
# --update-baseline and commit the rewritten files)
perf-gate:
	$(PY) tools/perf_gate.py --selftest
	$(PY) tools/perf_gate.py

# serving gate (docs/serving.md): a live CPU-mesh continuous-batching
# run (staggered admissions over the slot-sharded mesh, plus a
# disaggregated prefill/decode split) must bit-match generate(), leave
# a schema-v5 manifest whose serving block passes the Q-code audit with
# Q004 only, and the seeded over-budget decode case must fire Q001
# while the clean fixture stays Q004-only (--serving --selftest)
serve-check:
	$(PY) tools/serve_check.py
	$(PY) tools/verify_strategy.py --serving --selftest

# postmortem gate (docs/observability.md "Postmortem tier"): a live
# CPU-mesh chaos run (nan@2) must leave a flight-recorder bundle whose
# P-code audit fires P001 naming the injected worker+step, the operator
# views (tools/postmortem.py, monitor --postmortem) must reconstruct
# it, and the golden bundle fixtures must fire P001 (NaN cascade) and
# P002 (stall death) with a clean control (--postmortem --selftest)
postmortem-check:
	$(PY) tools/postmortem_check.py
	$(PY) tools/verify_strategy.py --postmortem --selftest

# fleet-scale gate (docs/observability.md "Fleet tier"): a 512-worker
# simulated cluster (production StreamPublisher per worker over the real
# length-prefixed-JSON socket) drives the selectors-based chief — the
# pending queue must stay bounded with zero dropped frames, snapshot p99
# must hold within 4x the same-machine 8-worker baseline (the O(top_k)
# read path), and the scripted cascading straggler must surface in
# ClusterView + fire on_straggler within the MTTR budget, with a clean
# W005-only audit; the W-code fixtures must fire W001 (saturated chief)
# and W002 (slow detection) with a clean 512-worker control
# (--fleet --selftest)
fleet-check:
	$(PY) tools/fleet_check.py
	$(PY) tools/verify_strategy.py --fleet --selftest

# the pre-merge gate: lint + strategy verification + HLO audit + live
# telemetry + runtime timeline + live control plane + chaos drills + the
# cross-run perf gate + the serving gate + the postmortem gate + the
# fleet-scale gate (tests/test_analysis.py + test_telemetry.py +
# test_timeline.py + test_elastic.py + test_regression_audit.py +
# test_stream.py + test_reaction_audit.py + test_serving.py +
# test_flight_recorder.py + test_postmortem_audit.py + test_sketch.py +
# test_fleet.py run the same chains, so tier-1 exercises it)
check: lint verify audit telemetry-check timeline-check monitor-check chaos perf-gate serve-check postmortem-check fleet-check

clean:
	$(MAKE) -C native clean
	find . -name __pycache__ -type d -exec rm -rf {} +
