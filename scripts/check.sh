#!/usr/bin/env bash
# Round gate: the full test suite + the multi-chip dryrun must BOTH pass
# before a round ends (round 4 shipped a red suite because nothing
# forced a final full run).  Reference analog: the CircleCI gate
# running `./gradlew clean build` (.circleci/config.yml:16).
#
# Usage: scripts/check.sh [pytest-args...]
# Exit: nonzero if the suite or the dryrun fails.
set -u
cd "$(dirname "$0")/.."

echo "== check.sh: pytest tests/ -q $* =="
python -m pytest tests/ -q "$@"
suite_rc=$?

echo "== check.sh: dryrun_multichip(8) on virtual CPU mesh =="
JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
python - <<'EOF'
import __graft_entry__ as g
g.dryrun_multichip(8)
print("dryrun_multichip(8): OK")
EOF
dryrun_rc=$?

echo "== check.sh: single-chip entry compile check =="
JAX_PLATFORMS=cpu python - <<'EOF'
import jax, __graft_entry__ as g
fn, args = g.entry()
out = jax.jit(fn)(*args)
jax.block_until_ready(out)
print("entry(): OK")
EOF
entry_rc=$?

echo "== check.sh: bench.py --smoke (fused vs legacy perf path, CPU) =="
JAX_PLATFORMS=cpu python bench.py --smoke
smoke_rc=$?

echo "== check.sh: bench.py --mesh-smoke (1-vs-8-device mesh parity, CPU) =="
# named gate: a 1-device and an 8-virtual-device run of the same seeded
# anneal must reproduce the plain engine's placements byte-for-byte, and
# the per-round collective payload must match the gather-candidates-only
# schedule (0 bytes at n=1) — the mesh engine layer's core invariants
JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
python bench.py --mesh-smoke
mesh_rc=$?

echo "== check.sh: bench.py --mesh --smoke (sharded-model mesh at 25k/2M, CPU) =="
# named gate: the sharded-MODEL mode must (a) reproduce the plain engine's
# placements byte-for-byte at small geometry alongside the replicated
# mesh, and (b) hold <= 1/4 of the replicated model footprint per device
# at the 25k-broker / 2M-partition scale-out north star (full geometry,
# shrunken search) — scaling efficiency + collective bytes are recorded
# in BENCH_mesh_r01.json
JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
python bench.py --mesh --smoke
mesh_model_rc=$?

echo "== check.sh: bench.py --churn --smoke (shape-bucketed serving, CPU) =="
JAX_PLATFORMS=cpu python bench.py --churn --smoke
churn_rc=$?

echo "== check.sh: bench.py --scenarios --smoke (batched what-if evaluation, CPU) =="
# named gate: one batched N-scenario evaluation must be no slower than N
# sequential runs AND produce bit-identical per-scenario objectives —
# batching is an execution detail of the planner, never a numerics change
JAX_PLATFORMS=cpu python bench.py --scenarios --smoke
scenarios_rc=$?

echo "== check.sh: bench.py --streaming --smoke (incremental controller replay, CPU) =="
# named gate: a multi-window streaming replay must show (a) the COLD
# controller cycle reproduces today's flatten-and-anneal byte-for-byte,
# (b) warm-started incremental anneals converge in measurably fewer
# rounds at equal goal quality, (c) zero full re-flattens across
# metric-only windows (the in-place delta contract, asserted via
# sensors), and (d) the fused-cycle latency/dispatch contract: every
# steady-state delta cycle after the fused program compiles runs FUSED
# at <= 2 device dispatches (one program launch + one host extraction,
# proved by the dispatch meter) with a sub-second
# window-roll-to-publish p99 (cold-compile cycles excluded via their
# one-shot sensors)
JAX_PLATFORMS=cpu python bench.py --streaming --smoke
streaming_rc=$?

echo "== check.sh: streaming controller gate (prior parity, warm start, delta path) =="
# named gate: cold-prior byte parity, warm-start carry (fused==legacy,
# no donated-buffer corruption), move-acceptance prior fitting/decay,
# WindowedHistory delta extraction under topic churn + partial windows,
# LiveState in-place updates, publish/supersede
python -m pytest tests/test_controller.py -q
controller_rc=$?

echo "== check.sh: bench.py --coldstart --smoke (restart SLO: manifest+AOT prewarm, CPU) =="
# named gate: one child process per restart phase (truly cold /
# XLA-cache-only / manifest+AOT); the manifest+AOT phase must report
# ZERO fresh engine traces for manifest-listed buckets, a strictly
# lower cold-start-to-first-proposal wall than truly-cold, and the
# identical objective (the AOT path must never change results)
JAX_PLATFORMS=cpu python bench.py --coldstart --smoke
coldstart_rc=$?

echo "== check.sh: cold-start prewarm gate (manifest, AOT fallback ladder, warm pool) =="
# named gate: manifest round-trip + fingerprint rejection, corrupt/
# truncated AOT artifact -> plain-jit fallback (no crash, sensor
# incremented), aval-drift fallback (the r4 regression class),
# never-on-the-request-path, warm-pool priority ordering, fleet
# manifest merging
python -m pytest tests/test_prewarm.py -q
prewarm_rc=$?

echo "== check.sh: bench.py --fleet-smoke (shared-engine fleet economics, CPU) =="
# named gate: a 3-cluster fleet (2 sharing a shape bucket) must end with
# FEWER compiled engines than clusters (the shared AnalyzerCore is real)
# and each cluster's warm proposal wall within 1.5x a single-cluster
# baseline — multi-tenancy must not tax steady-state serving
JAX_PLATFORMS=cpu python bench.py --fleet-smoke
fleet_smoke_rc=$?

echo "== check.sh: device scheduler gate (QoS classes, preemption, shed/brownout, parity) =="
# named gate: segmented-vs-unsegmented anneal byte parity (placements,
# objectives, trajectories), urgent queue-to-dispatch wait <= one slice
# budget under a device_slowdown x 20-cluster burst with BACKGROUND
# shedding counted (zero URGENT sheds), aging (background delayed but
# never starved), brownout after sustained overload, FLEET_OVERLOAD
# once per episode, Retry-After on both 429 paths, and the
# scheduler-off byte-for-byte default
python -m pytest tests/test_scheduler.py -q
scheduler_rc=$?

echo "== check.sh: fleet HA gate (leases, fencing, kill-and-takeover) =="
# named gate: the chaos invariants — at most one lease holder per cluster
# at any instant (audit-trail-proven, incl. under seeded store partitions
# + clock skew), zero duplicate submissions across a kill-and-takeover,
# zero leaked throttles, a fenced zombie can neither journal nor mutate,
# and fleet.ha.enabled=false stays byte-for-byte classic
python -m pytest tests/test_fleet_ha.py -q
fleet_ha_rc=$?

echo "== check.sh: bench.py --ha-smoke (lease takeover SLO, CPU) =="
# named gate: 2 instances over 3 synthetic clusters sharing one lease
# store — kill one, time-to-takeover-to-first-proposal under budget and
# the single-holder invariant checked from the lease-store audit trail
JAX_PLATFORMS=cpu python bench.py --ha-smoke
ha_smoke_rc=$?

echo "== check.sh: fleet controller gate (N clusters, shared core, isolation) =="
# named gate: shared engine-cache hits across same-bucket clusters,
# per-cluster journal namespacing with zero cross-adoption on restart,
# cluster= routing + per-tenant 429 admission, N-cluster /metrics lint,
# and the 3-FakeKafkaCluster live-socket acceptance story
python -m pytest tests/test_fleet.py -q
fleet_rc=$?

echo "== check.sh: scenario planner gate (what-if parity, forecaster, rightsizer) =="
# named gate: the identity-scenario byte parity, dead-rack/broker-add
# semantics, engine-cache reuse across a scenario batch, and the
# /simulate & /rightsize surfaces — regressions here mislead capacity
# decisions silently
python -m pytest tests/test_planner.py -q
planner_rc=$?

echo "== check.sh: fault supervision gate (degraded mode, breaker, harness) =="
# named gate: every breaker transition / degraded proposal is pinned by
# deterministic fault injection (testing/faults.py), never by a real TPU
# misbehaving on cue.  Runs standalone so a fault-supervision regression
# is named in the summary even when the full suite was skipped via args.
python -m pytest tests/test_faults.py -q
faults_rc=$?

echo "== check.sh: mesh fault-tolerance gate (device loss, carry checkpoints, degrade-and-resume) =="
# named gate: probe fan-out attribution (DEVICE_LOST / COLLECTIVE_STALL
# naming the suspect chip), segmented-vs-unsegmented mesh byte parity,
# reduced-width resume from a slice-boundary carry checkpoint, per-width
# breakers that never open the single-device breaker, scoped parallel
# purge, and the once-per-episode MESH_DEGRADED surface
python -m pytest tests/test_mesh_ft.py -q
mesh_ft_rc=$?

echo "== check.sh: bench.py --mesh-chaos --smoke (mid-anneal device loss, CPU) =="
# named gate: inject a device loss mid-anneal on an 8-virtual-device
# mesh — the optimizer must resume at width 4 from the last checkpoint
# with placements BYTE-EQUAL to a clean uninterrupted run, checkpoint-off
# must keep the dispatch stream byte-for-byte with zero extra dispatches,
# and exactly one MESH_DEGRADED event must arm per degrade episode
JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
python bench.py --mesh-chaos --smoke
mesh_chaos_rc=$?

echo "== check.sh: crash-safe execution gate (journal recovery, reaper, adaptive) =="
# named gate: the kill-and-restart matrix (process crash mid-move /
# mid-leadership / mid-logdir-copy, truncated-journal replay, stuck-move
# reaper, adaptive-concurrency backoff) must hold regardless of what the
# full suite ran — a regression here strands real reassignments.
python -m pytest tests/test_executor_recovery.py -q
recovery_rc=$?

echo "== check.sh: /metrics exposition lint gate (live scrape) =="
# named gate: boot the simulated service, scrape GET /metrics over HTTP,
# and lint the body with the strict exposition parser (TYPE lines, label
# escaping, counter monotonicity, histogram bucket structure) — a
# malformed exposition breaks every dashboard silently
JAX_PLATFORMS=cpu python - <<'EOF'
import urllib.request

from cruise_control_tpu.common.exposition import parse_exposition
from cruise_control_tpu.service.main import build_simulated_service
from cruise_control_tpu.service.progress import OperationProgress

app, fetcher, admin, sampler = build_simulated_service(seed=1)
app.start()
try:
    # one proposal run so the analyzer/device sensor surface registers
    app.cc.proposals(OperationProgress())
    url = f"http://{app.host}:{app.port}{app.prefix}/metrics"
    with urllib.request.urlopen(url, timeout=30) as resp:
        assert resp.headers["Content-Type"].startswith("text/plain"), (
            resp.headers["Content-Type"]
        )
        families = parse_exposition(resp.read().decode())
    for fam in (
        "cruisecontrol_analyzer_proposal_computation_timer_seconds",
        "cruisecontrol_analyzer_proposal_computation_seconds",
        "cruisecontrol_tpu_device_live_buffers",
    ):
        assert fam in families, f"missing family {fam}"
    print(f"exposition lint: OK ({len(families)} families)")
finally:
    app.stop()
EOF
metrics_rc=$?

echo "== check.sh: trace overhead gate (tracing-on adds <2% to a smoke run) =="
# named gate: the flight recorder is ON by default on the hot proposal
# path, so its cost is pinned by measurement, not assumption
JAX_PLATFORMS=cpu python bench.py --trace-overhead
overhead_rc=$?

echo "== check.sh: black-box overhead gate (spool-on adds <2%, disabled path writes nothing) =="
# named gate: the crash-durable dispatch spool is ON by default wherever
# a durable dir exists; its per-dispatch write+flush must stay
# unmeasurable beside an engine run, recording must not perturb results
# (byte-identical placements), and the disabled path must write zero bytes
JAX_PLATFORMS=cpu python bench.py --blackbox-overhead
blackbox_overhead_rc=$?

echo "== check.sh: ledger overhead gate (diagnostics+ledger on adds <2%, byte-identical placements) =="
# named gate: convergence diagnostics + the decision ledger are ON by
# default; the per-run decision record and the diagnostics-on fused
# program must stay unmeasurable beside an engine run, placements must be
# byte-identical on vs off, and the disabled path must write zero bytes
JAX_PLATFORMS=cpu python bench.py --ledger-overhead
ledger_overhead_rc=$?

echo "== check.sh: decision ledger gate (durability, joins, calibration, /explain) =="
# named gate: torn-tail append-after-truncate, retention never pruning a
# pending-outcome episode, fleet two-cluster ledger isolation,
# disabled-path zero bytes, diagnostics byte-parity across
# plain/segmented/mesh, and the decision→outcome→calibration→/explain
# acceptance story
python -m pytest tests/test_ledger.py -q
ledger_rc=$?

echo "== check.sh: black-box gate (crash-durable spool, kill/hang post-mortems) =="
# named gate: a process killed -9 (or hang-timed-out) mid-anneal must
# leave a spool that replays to the exact in-flight dispatch (bucket,
# slice index, wait class), the dryrun timeout verdict must embed
# structured last-dispatch records, and the torn-tail/ring-rotation
# reader invariants must hold
python -m pytest tests/test_blackbox.py -q
blackbox_rc=$?

echo "== check.sh: SLO gate (burn-rate windows, once-per-episode alerting, /slo) =="
# named gate: multi-window burn-rate math on injected clocks, a
# sustained freshness breach fires SLO_BURN exactly once per episode
# (twice across two episodes), burn gauges render in a lint-clean
# /metrics scrape, and GET /slo serves the registry state
python -m pytest tests/test_slo.py -q
slo_rc=$?

echo "== check.sh: flight-recorder unit gate (trace model, exposition parser) =="
python -m pytest tests/test_trace.py -q
trace_rc=$?

echo
echo "check.sh summary: suite=$suite_rc dryrun=$dryrun_rc entry=$entry_rc smoke=$smoke_rc mesh=$mesh_rc mesh_model=$mesh_model_rc churn=$churn_rc streaming=$streaming_rc controller=$controller_rc coldstart=$coldstart_rc prewarm=$prewarm_rc fleet_smoke=$fleet_smoke_rc fleet=$fleet_rc fleet_ha=$fleet_ha_rc ha_smoke=$ha_smoke_rc scheduler=$scheduler_rc scenarios=$scenarios_rc planner=$planner_rc faults=$faults_rc mesh_ft=$mesh_ft_rc mesh_chaos=$mesh_chaos_rc recovery=$recovery_rc metrics=$metrics_rc overhead=$overhead_rc blackbox_overhead=$blackbox_overhead_rc ledger_overhead=$ledger_overhead_rc ledger=$ledger_rc blackbox=$blackbox_rc slo=$slo_rc trace=$trace_rc"
[ "$suite_rc" -eq 0 ] && [ "$dryrun_rc" -eq 0 ] && [ "$entry_rc" -eq 0 ] && [ "$smoke_rc" -eq 0 ] && [ "$mesh_rc" -eq 0 ] && [ "$mesh_model_rc" -eq 0 ] && [ "$churn_rc" -eq 0 ] && [ "$streaming_rc" -eq 0 ] && [ "$controller_rc" -eq 0 ] && [ "$coldstart_rc" -eq 0 ] && [ "$prewarm_rc" -eq 0 ] && [ "$fleet_smoke_rc" -eq 0 ] && [ "$fleet_rc" -eq 0 ] && [ "$fleet_ha_rc" -eq 0 ] && [ "$ha_smoke_rc" -eq 0 ] && [ "$scheduler_rc" -eq 0 ] && [ "$scenarios_rc" -eq 0 ] && [ "$planner_rc" -eq 0 ] && [ "$faults_rc" -eq 0 ] && [ "$mesh_ft_rc" -eq 0 ] && [ "$mesh_chaos_rc" -eq 0 ] && [ "$recovery_rc" -eq 0 ] && [ "$metrics_rc" -eq 0 ] && [ "$overhead_rc" -eq 0 ] && [ "$blackbox_overhead_rc" -eq 0 ] && [ "$ledger_overhead_rc" -eq 0 ] && [ "$ledger_rc" -eq 0 ] && [ "$blackbox_rc" -eq 0 ] && [ "$slo_rc" -eq 0 ] && [ "$trace_rc" -eq 0 ]
