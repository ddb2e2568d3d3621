"""Benchmark ladder: the five BASELINE.md configs, headline last.

Prints one JSON line per benchmark config, with the north-star line
(config 4: 2,600-broker / 200k-partition full-default-goals proposal,
target < 10 s on one TPU chip) printed LAST so drivers that parse the
final line get the headline metric.  `vs_baseline` on the headline is
wall / 10s (the fraction of the north-star budget used; < 1.0 beats it).

Configs (BASELINE.md "Benchmark configs to implement" + additions):
  1 deterministic 3-broker parity oracle vs reference-style greedy
  2 RandomCluster 50/5k, ResourceDistribution+ReplicaCapacity goals
  3 JBOD 500/50k, DiskCapacity+RackAware goals
  4 north-star 2600/200k, full default.goals          <- headline
  5 broker-decommission self-healing on the 2600/200k model
  6 cluster-model generation wall-clock at north-star scale
  7 ShardedEngine (model-sharded scale-out path) at north-star scale

Greedy comparisons (configs 1,2,3,5) run the CPU oracle
(cruise_control_tpu/analyzer/greedy.py) under a wall-clock budget — the
reference's sequential search runs minutes at scale (SURVEY §6); the
budgeted objective is what it achieves in comparable time.

Env: BENCH_CONFIGS="1,2,3,4,5" to select (default all);
BENCH_SCALE=north_star|mid|small retained for the headline fixture size.

`bench.py --churn [--smoke]` runs the topology-churn scenario instead:
N generations with partition creates (+ a broker add) served bucketed vs
exact, gating on "churned generations compile zero engines" (see churn()).

`bench.py --coldstart [--smoke]` runs the restart-SLO ladder instead: a
child process per phase (truly cold / XLA-cache-only / manifest+AOT)
measures cold-start-to-first-proposal and gates the manifest+AOT phase
on zero fresh traces for manifest buckets (see coldstart()).

warmup_s on the headline is the FIRST optimize() call in a fresh process
with a warm persistent XLA cache: engine statics build + program
trace/lower + cache-hit compile + one full proposal computation.  It is
the operator's honest time-to-first-proposal — and that first pass
already yields a complete usable proposal set (the service's precompute
loop caches it), not discarded warm-up work.  Cold cache (first process
ever) adds ~60s of XLA compilation on top.
"""

import json
import os
import sys
import time

import numpy as np

NORTH_STAR_SPEC = dict(
    num_brokers=2600,
    num_racks=52,
    num_topics=200,
    num_partitions=200_000,
    min_replication=2,
    max_replication=3,
    skew=0.5,
    broker_capacity=(100.0, 500_000.0, 500_000.0, 5_000_000.0),
    mean_cpu=0.15,
    mean_nw_in=400.0,
    mean_nw_out=500.0,
    mean_disk=4000.0,
)
MID_SPEC = dict(
    num_brokers=500,
    num_racks=20,
    num_topics=100,
    num_partitions=50_000,
    skew=0.5,
    broker_capacity=(100.0, 300_000.0, 300_000.0, 3_000_000.0),
    mean_cpu=0.2,
    mean_nw_in=500.0,
    mean_nw_out=600.0,
    mean_disk=5000.0,
)
SMALL_SPEC = dict(num_brokers=50, num_partitions=5000, num_racks=5, num_topics=20, skew=0.8)

SEARCH = dict(
    num_candidates=16384,
    leadership_candidates=4096,
    steps_per_round=int(os.environ.get("BENCH_STEPS", "64")),
    num_rounds=8,
    seed=0,
)
SEARCH_SMALL = dict(
    num_candidates=2048,
    leadership_candidates=512,
    steps_per_round=64,
    num_rounds=8,
    seed=0,
)


def _emit(**kv):
    print(json.dumps(kv), flush=True)


def _run_tpu(opt, state, chain):
    """Warm (compile) + measured run; returns (result, wall_s, warm_s)."""
    warm = opt.optimize(state)
    t0 = time.monotonic()
    res = opt.optimize(state)
    return res, time.monotonic() - t0, warm.wall_seconds


def _baseline_fingerprint(state, chain) -> str:
    """Cheap identity of (cluster, goal chain) a greedy baseline was built
    for — a changed spec/seed/fixture/chain must invalidate the committed
    number LOUDLY instead of silently comparing different clusters."""
    import hashlib

    s = state.shape
    n_valid = int(np.asarray(state.replica_valid).sum())
    # dead-broker topology is part of the problem (config5 decommission):
    # changing WHICH brokers die must invalidate the baseline
    alive = np.asarray(state.broker_valid) & np.asarray(state.broker_alive)
    n_alive = int(alive.sum())
    alive_sig = int(np.nonzero(~alive)[0].sum())
    # 4 significant digits: fixtures built partly on-device differ CPU vs
    # TPU in the last f32 bits, and the baseline is generated on CPU while
    # the bench checks on TPU — the signature must survive that noise while
    # still catching real spec/seed changes
    load_sig = float(np.asarray(state.replica_load_leader, np.float64).sum())
    names = ",".join(g.name for g in chain.goals)
    raw = f"{s.B}x{s.P}x{n_valid}|{n_alive}|{alive_sig}|{load_sig:.4g}|{names}"
    return hashlib.sha256(raw.encode()).hexdigest()[:16]


def _greedy_objective(config_name, state, chain, budget_s, *, moves=400, dests=8, seed=0):
    """Greedy-oracle comparison numbers for one bench config.

    Prefers the committed CONVERGED baseline (BASELINE_GREEDY.json, built by
    scripts/gen_greedy_baselines.py) — comparing against a budget-truncated
    oracle understates the bar.  Falls back to an
    in-bench budgeted run, honestly labeled converged=False when cut off.
    Returns (objective, seconds, converged).
    """
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "BASELINE_GREEDY.json")
    if os.path.exists(path):
        with open(path) as f:
            entry = json.load(f).get(config_name)
        if entry is not None:
            fp = _baseline_fingerprint(state, chain)
            if entry.get("fingerprint") not in (None, fp):
                print(
                    f"greedy baseline {config_name} is STALE "
                    f"(fingerprint {entry.get('fingerprint')} != {fp}); "
                    "re-run scripts/gen_greedy_baselines.py — falling back "
                    "to in-bench greedy",
                    file=sys.stderr,
                )
            else:
                return float(entry["objective"]), float(entry["seconds"]), bool(
                    entry["converged"]
                )
    from cruise_control_tpu.analyzer.greedy import greedy_optimize

    final, info = greedy_optimize(
        state, chain, max_moves_per_goal=moves, candidate_dests=dests, seed=seed,
        time_budget_s=budget_s, return_info=True,
    )
    obj, _, _ = chain.evaluate(final)
    return float(obj), info["seconds"], info["converged"]


def config_1():
    """Deterministic 3-broker parity oracle (DeterministicCluster analog)."""
    from cruise_control_tpu.analyzer import GoalOptimizer, OptimizerConfig
    from cruise_control_tpu.analyzer.objective import DEFAULT_CHAIN
    from cruise_control_tpu.testing.fixtures import small_cluster

    state = small_cluster()
    opt = GoalOptimizer(config=OptimizerConfig(**SEARCH_SMALL))
    res, wall, _ = _run_tpu(opt, state, DEFAULT_CHAIN)
    greedy_obj, greedy_s, greedy_conv = _greedy_objective(
        "config1", state, DEFAULT_CHAIN, budget_s=120
    )
    _emit(
        metric="config1_deterministic_parity",
        value=round(wall, 3),
        unit="s",
        vs_baseline=round(res.objective_after / max(greedy_obj, 1e-12), 4),
        tpu_objective=round(res.objective_after, 6),
        greedy_objective=round(greedy_obj, 6),
        greedy_seconds=round(greedy_s, 1),
        greedy_converged=greedy_conv,
        tpu_beats_greedy=bool(res.objective_after <= greedy_obj * (1 + 1e-4) + 1e-9),
        balancedness_after=round(res.balancedness_after, 2),
    )


def config_2():
    """RandomCluster 50/5k, ResourceDistribution + ReplicaCapacity goals."""
    from cruise_control_tpu.analyzer import GoalOptimizer, OptimizerConfig
    from cruise_control_tpu.analyzer.objective import GoalChain
    from cruise_control_tpu.testing.fixtures import RandomClusterSpec, random_cluster_fast

    chain = GoalChain.from_names([
        "ReplicaCapacityGoal",
        "DiskUsageDistributionGoal",
        "NetworkInboundUsageDistributionGoal",
        "NetworkOutboundUsageDistributionGoal",
        "CpuUsageDistributionGoal",
    ])
    state = random_cluster_fast(RandomClusterSpec(**SMALL_SPEC), seed=42)
    opt = GoalOptimizer(chain=chain, config=OptimizerConfig(**SEARCH_SMALL))
    res, wall, warm = _run_tpu(opt, state, chain)
    greedy_obj, greedy_s, greedy_conv = _greedy_objective(
        "config2", state, chain, budget_s=60
    )
    _emit(
        metric="config2_random_50_5k",
        value=round(wall, 3),
        unit="s",
        vs_baseline=round(res.objective_after / max(greedy_obj, 1e-12), 4),
        tpu_objective=round(res.objective_after, 6),
        greedy_objective=round(greedy_obj, 6),
        greedy_seconds=round(greedy_s, 1),
        greedy_converged=greedy_conv,
        tpu_beats_greedy=bool(res.objective_after <= greedy_obj * (1 + 1e-4) + 1e-9),
        balancedness_before=round(res.balancedness_before, 2),
        balancedness_after=round(res.balancedness_after, 2),
        num_replica_moves=res.num_inter_broker_moves,
        warmup_s=round(warm, 1),
    )


def config_3():
    """JBOD 500-broker/50k-partition, DiskCapacity + RackAware goals."""
    from cruise_control_tpu.analyzer import GoalOptimizer, OptimizerConfig
    from cruise_control_tpu.analyzer.objective import GoalChain
    from cruise_control_tpu.testing.fixtures import RandomClusterSpec, random_cluster_fast

    chain = GoalChain.from_names([
        "RackAwareGoal",
        "DiskCapacityGoal",
        "IntraBrokerDiskCapacityGoal",
        "IntraBrokerDiskUsageDistributionGoal",
    ])
    state = random_cluster_fast(
        RandomClusterSpec(**{**MID_SPEC, "disks_per_broker": 4}), seed=42
    )
    opt = GoalOptimizer(chain=chain, config=OptimizerConfig(**SEARCH))
    res, wall, warm = _run_tpu(opt, state, chain)
    greedy_obj, greedy_s, greedy_conv = _greedy_objective(
        "config3", state, chain, budget_s=60
    )
    _emit(
        metric="config3_jbod_500_50k",
        value=round(wall, 3),
        unit="s",
        vs_baseline=round(res.objective_after / max(greedy_obj, 1e-12), 4),
        tpu_objective=round(res.objective_after, 6),
        greedy_objective=round(greedy_obj, 6),
        greedy_seconds=round(greedy_s, 1),
        greedy_converged=greedy_conv,
        tpu_beats_greedy=bool(res.objective_after <= greedy_obj * (1 + 1e-4) + 1e-9),
        balancedness_before=round(res.balancedness_before, 2),
        balancedness_after=round(res.balancedness_after, 2),
        num_replica_moves=res.num_inter_broker_moves,
        warmup_s=round(warm, 1),
    )


def config_6():
    """Cluster-model generation wall-clock at north-star scale.

    The monitor half of time-to-proposal: synthetic 2600-broker/200k-
    partition topology + a filled 4-window aggregator, timed through
    LoadMonitor.cluster_model() (aggregate -> columnar join ->
    build_state_columnar -> device arrays).  The reference meters this as
    its cluster-model-creation-timer sensor (monitor/LoadMonitor.java:100,510);
    target <= 1s warm.
    """
    from cruise_control_tpu.monitor import (
        KAFKA_METRIC_DEF,
        FixedCapacityResolver,
        LoadMonitor,
        ModelCompletenessRequirements,
        WindowedMetricSampleAggregator,
    )
    from cruise_control_tpu.monitor.sampling import PartitionEntity
    from cruise_control_tpu.monitor.topology import StaticMetadataProvider
    from cruise_control_tpu.testing.synthetic import synthetic_topology

    t_fx = time.monotonic()
    topo = synthetic_topology(
        num_brokers=NORTH_STAR_SPEC["num_brokers"],
        topics={f"t{i:03d}": 1000 for i in range(200)},  # 200k partitions
        seed=42,
    )
    cols = topo.columns()
    ents = [
        PartitionEntity(int(t), int(p))
        for t, p in zip(cols.part_topic, cols.part_num)
    ]
    agg = WindowedMetricSampleAggregator(
        4, 1000, 1, KAFKA_METRIC_DEF, initial_capacity=len(ents)
    )
    rng = np.random.default_rng(0)
    M = KAFKA_METRIC_DEF.num_metrics
    for w in range(5):
        agg.add_samples_columnar(
            ents, w * 1000 + 5, rng.uniform(1, 10, (len(ents), M)).astype(np.float32)
        )
    monitor = LoadMonitor(
        StaticMetadataProvider(topo),
        FixedCapacityResolver(list(NORTH_STAR_SPEC["broker_capacity"])),
        agg,
    )
    req = ModelCompletenessRequirements(min_required_num_windows=2)
    fixture_s = time.monotonic() - t_fx
    t0 = time.monotonic()
    state = monitor.cluster_model(req)
    first = time.monotonic() - t0
    walls = []
    for _ in range(3):
        t0 = time.monotonic()
        state = monitor.cluster_model(req)
        walls.append(time.monotonic() - t0)
    wall = sorted(walls)[1]  # median of 3
    _emit(
        metric="cluster_model_creation_north_star",
        value=round(wall, 3),
        unit="s",
        vs_baseline=round(wall / 1.0, 4),  # fraction of the 1s target
        first_call_s=round(first, 2),
        fixture_gen_s=round(fixture_s, 1),
        brokers=state.shape.B,
        partitions=state.shape.P,
        replicas=int(np.asarray(state.replica_valid).sum()),
        monitored_partitions=agg.num_entities(),
    )


def config_7():
    """ShardedEngine at NORTH-STAR scale on the available mesh (1 real
    device on the bench host), measured AGAINST the plain engine on the
    same fixture/config: proves the mesh-layer program — the multi-host
    scale-out path — compiles, fits in HBM, improves the objective at
    2600x200k, and emits the two driver-capturable targets: warm_start_s
    (time to first sharded proposal, < 30 s target) and
    shard_overhead_pct (sharded n=1 wall vs plain engine wall, < 10%
    target — the mesh layer's n=1 program traces to the plain fused
    program)."""
    import jax

    from cruise_control_tpu.analyzer import Engine, OptimizerConfig
    from cruise_control_tpu.analyzer.objective import DEFAULT_CHAIN
    from cruise_control_tpu.parallel.sharded import ShardedEngine, model_mesh

    state = _headline_state("north_star")
    cfg = OptimizerConfig(**{**SEARCH, "num_rounds": 4})
    n_dev = len(jax.devices())

    def timed_run(engine):
        t0 = time.monotonic()
        final, _history = engine.run()
        jax.block_until_ready(final.replica_broker)
        return final, time.monotonic() - t0

    # plain single-device reference: same fixture, same search config
    plain = Engine(state, DEFAULT_CHAIN, config=cfg)
    _, plain_warm = timed_run(plain)
    _, plain_wall = timed_run(plain)

    se = ShardedEngine(state, DEFAULT_CHAIN, mesh=model_mesh(), config=cfg)
    final, warm = timed_run(se)
    final, wall = timed_run(se)
    obj0, _, _ = DEFAULT_CHAIN.evaluate(state)
    obj1, _, _ = DEFAULT_CHAIN.evaluate(final)
    overhead_pct = (wall - plain_wall) / max(plain_wall, 1e-9) * 100.0
    _emit(
        metric="sharded_proposal_wall_clock_north_star",
        value=round(wall, 3),
        unit="s",
        vs_baseline=round(wall / 10.0, 4),
        n_devices=n_dev,
        brokers=state.shape.B,
        partitions=state.shape.P,
        objective_before=round(float(obj0), 5),
        objective_after=round(float(obj1), 5),
        improved=bool(float(obj1) < float(obj0)),
        warmup_s=round(warm, 1),
        warm_start_s=round(warm, 3),
        plain_wall_s=round(plain_wall, 3),
        plain_warm_start_s=round(plain_warm, 3),
        shard_overhead_pct=round(overhead_pct, 2),
        shard_overhead_ok=bool(overhead_pct < 10.0),
        collective_bytes_per_round=int(se.collective_bytes_per_round),
    )


def _headline_state(scale):
    from cruise_control_tpu.testing.fixtures import RandomClusterSpec, random_cluster_fast

    specs = {
        "north_star": NORTH_STAR_SPEC,
        "mid": MID_SPEC,
        "small": SMALL_SPEC,
    }
    return random_cluster_fast(RandomClusterSpec(**specs[scale]), seed=42)


def config_5(opt, scale):
    """Broker decommission + offline-replica self-healing at headline scale.

    Reuses the headline optimizer/engine: same shape + config -> zero
    recompilation (statics rebind), the steady-state self-healing path.
    """
    import dataclasses as dc

    import jax.numpy as jnp

    from cruise_control_tpu.analyzer.objective import DEFAULT_CHAIN

    state = _headline_state(scale)
    # decommission 1% of brokers (>= 2): their replicas go offline
    B = state.shape.B
    n_dead = max(2, B // 100)
    alive = np.asarray(state.broker_alive).copy()
    dead_ids = np.arange(B - n_dead, B)
    alive[dead_ids] = False
    offline = np.asarray(state.replica_offline) | ~alive[np.asarray(state.replica_broker)]
    state = dc.replace(
        state,
        broker_alive=jnp.asarray(alive),
        disk_alive=jnp.asarray(alive[:, None] & np.asarray(state.disk_alive)),
        replica_offline=jnp.asarray(offline),
    )
    res, wall, _ = _run_tpu(opt, state, DEFAULT_CHAIN)
    after = res.state_after
    remaining = int(
        (
            np.asarray(after.replica_valid)
            & ~np.asarray(after.broker_alive)[np.asarray(after.replica_broker)]
        ).sum()
    )
    # the committed config5 baseline is generated at north-star scale ONLY —
    # after a scale fallback the entry would compare apples to oranges
    baseline_key = "config5" if scale == "north_star" else f"config5_{scale}"
    greedy_obj, greedy_s, greedy_conv = _greedy_objective(
        baseline_key, state, DEFAULT_CHAIN, budget_s=90, moves=100, dests=6
    )
    _emit(
        metric="config5_decommission_self_healing",
        value=round(wall, 3),
        unit="s",
        vs_baseline=round(res.objective_after / max(greedy_obj, 1e-12), 4),
        scale=scale,
        dead_brokers=int(n_dead),
        offline_replicas_before=int(offline.sum()),
        offline_replicas_after=remaining,
        evacuated=bool(remaining == 0),
        tpu_objective=round(res.objective_after, 6),
        greedy_objective=round(greedy_obj, 6),
        greedy_seconds=round(greedy_s, 1),
        greedy_converged=greedy_conv,
        tpu_beats_greedy=bool(res.objective_after <= greedy_obj * (1 + 1e-4) + 1e-9),
        balancedness_before=round(res.balancedness_before, 2),
        balancedness_after=round(res.balancedness_after, 2),
        violated_goals_after=res.violated_goals_after(1e-6),
        num_replica_moves=res.num_inter_broker_moves,
        num_leader_moves=res.num_leadership_moves,
    )


def config_4(scale_order):
    """North-star headline: full default.goals proposal wall-clock.

    Returns (optimizer, scale) so config 5 can reuse the compiled engine.
    """
    from cruise_control_tpu.analyzer import GoalOptimizer, OptimizerConfig

    result = None
    opt = None
    used = None
    for sc in scale_order:
        try:
            t_gen = time.monotonic()
            state = _headline_state(sc)
            gen_s = time.monotonic() - t_gen
            cfg = OptimizerConfig(**SEARCH)
            from cruise_control_tpu.common.sensors import REGISTRY

            opt = GoalOptimizer(config=cfg, sensors=REGISTRY)
            # warm-up run compiles the engine for this cluster shape; the
            # measured run rebinds the cached engine (zero recompilation) —
            # steady-state service behavior, where the proposal precompute
            # loop reuses the compiled program (reference GoalOptimizer
            # proposal cache, analyzer/GoalOptimizer.java:276).
            warm = opt.optimize(state)
            t0 = time.monotonic()
            res = opt.optimize(state)
            wall = time.monotonic() - t0
            # device/host split from the history timing record: localizes a
            # wall-clock regression to device search vs host extraction.
            # The split is meaningful under async (TPU) dispatch only — on a
            # synchronous CPU backend device compute folds into dispatch
            # time and device_s is near zero (see Engine._run_fused).
            timing = next((h for h in res.history if h.get("timing")), {})
            result = dict(
                metric=f"proposal_wall_clock_{sc}",
                value=round(wall, 3),
                unit="s",
                vs_baseline=round(wall / 10.0, 4),
                device_s=timing.get("device_s"),
                host_extract_s=timing.get("host_extract_s"),
                blocking_syncs=timing.get("blocking_syncs"),
                scale=sc,
                brokers=state.shape.B,
                partitions=state.shape.P,
                replicas=int(np.asarray(state.replica_valid).sum()),
                balancedness_before=round(res.balancedness_before, 2),
                balancedness_after=round(res.balancedness_after, 2),
                objective_before=round(res.objective_before, 5),
                objective_after=round(res.objective_after, 5),
                num_replica_moves=res.num_inter_broker_moves,
                num_leader_moves=res.num_leadership_moves,
                violated_goals_after=res.violated_goals_after(1e-6),
                fixture_gen_s=round(gen_s, 1),
                warmup_s=round(warm.wall_seconds, 1),
                device=str(__import__("jax").devices()[0]),
                # flight-recorder per-stage rollup + sensor catalog: the
                # committed BENCH_*.json records where the wall went
                # (model build vs optimize vs device op), not just totals
                stage_summary=__import__(
                    "cruise_control_tpu.common.trace", fromlist=["TRACER"]
                ).TRACER.summarize(),
                sensors=REGISTRY.snapshot(),
            )
            used = sc
            break
        except Exception as e:  # noqa: BLE001 — fall back to a smaller scale
            print(f"bench scale {sc} failed: {e!r}", file=sys.stderr)
            continue
    if result is None:
        result = dict(metric="proposal_wall_clock", value=-1.0, unit="s", vs_baseline=-1.0)
    return opt, used, result


def smoke() -> int:
    """`bench.py --smoke`: CI-grade CPU check of the perf path in seconds.

    Runs the fused (default) and legacy round loops on a small fixture at
    T=0 (init_temperature_scale=0 makes the trajectories deterministic and
    comparable) and emits one JSON line with both wall-clocks, objectives,
    and the blocking-sync counts from the history timing split.  Exit is
    nonzero when the fused path's final objective regresses vs legacy or
    its O(1)-blocking-sync contract is broken — catching fused-round-loop
    regressions without a chip.  Wall-clocks are reported (and
    only grossly gated) because CPU CI timing is noisy.
    """
    import dataclasses as dc

    from cruise_control_tpu.analyzer import GoalOptimizer, OptimizerConfig
    from cruise_control_tpu.common.sensors import REGISTRY
    from cruise_control_tpu.common.trace import TRACER
    from cruise_control_tpu.testing.fixtures import RandomClusterSpec, random_cluster_fast

    state = random_cluster_fast(
        RandomClusterSpec(
            num_brokers=24, num_partitions=1500, num_racks=6, num_topics=12, skew=1.0
        ),
        seed=7,
    )
    base = OptimizerConfig(
        num_candidates=512, leadership_candidates=128, swap_candidates=64,
        steps_per_round=16, num_rounds=4, init_temperature_scale=0.0, seed=0,
    )
    out: dict = {}
    for name, cfg in (
        ("fused", dc.replace(base, fused_rounds=True)),
        ("legacy", dc.replace(base, fused_rounds=False)),
    ):
        opt = GoalOptimizer(config=cfg, sensors=REGISTRY)
        opt.optimize(state)  # warm-up: compile once, measure the steady state
        walls = []
        res = None
        for _ in range(3):
            t0 = time.monotonic()
            res = opt.optimize(state)
            walls.append(time.monotonic() - t0)
        timing = next((h for h in res.history if h.get("timing")), {})
        out[name] = dict(
            wall_s=round(min(walls), 3),
            objective=res.objective_after,
            blocking_syncs=timing.get("blocking_syncs"),
            device_s=timing.get("device_s"),
            host_extract_s=timing.get("host_extract_s"),
        )
    obj_ok = out["fused"]["objective"] <= out["legacy"]["objective"] * (1 + 1e-6) + 1e-9
    syncs_ok = (
        out["fused"]["blocking_syncs"] == 1
        and out["legacy"]["blocking_syncs"] >= base.num_rounds
    )
    ratio = out["fused"]["wall_s"] / max(out["legacy"]["wall_s"], 1e-9)
    wall_ok = ratio <= 1.5  # gross-regression tripwire only: CPU CI is noisy
    ok = obj_ok and syncs_ok and wall_ok
    _emit(
        metric="smoke_fused_vs_legacy",
        value=out["fused"]["wall_s"],
        unit="s",
        vs_baseline=round(ratio, 4),
        fused=out["fused"],
        legacy=out["legacy"],
        objective_parity=obj_ok,
        sync_contract=syncs_ok,
        ok=ok,
        # where the wall time went (flight-recorder per-stage rollup) and
        # the sensor catalog the run registered — the perf trajectory
        # records stage breakdowns, not just totals
        stage_summary=TRACER.summarize(),
        sensors=REGISTRY.snapshot(),
    )
    return 0 if ok else 1


def mesh_smoke() -> int:
    """`bench.py --mesh-smoke`: the mesh engine layer on a virtual
    8-device CPU mesh, in seconds.

    Gates the layer's core invariant — a 1-device and an 8-device run of
    the same seeded anneal reproduce the PLAIN engine's placements
    byte-for-byte (parallel/mesh.py: replicated RNG + full-K draws +
    gather-candidates-only), hence identical objectives — and reports the
    per-round collective payload bytes so the perf trajectory records
    what cross-shard candidate exchange actually costs.  Wall-clocks are
    reported but not gated (CPU CI timing is noisy; the n=1 overhead
    gate lives in config 7 on the bench host).

    Self-provisions the mesh: with fewer than 8 visible devices it
    re-execs itself in a child with JAX_PLATFORMS=cpu +
    --xla_force_host_platform_device_count=8 (the platform is pinned at
    first backend use — same mechanism as __graft_entry__'s dryrun).
    """
    import jax

    if len(jax.devices()) < 8:
        if os.environ.get("MESH_SMOKE_CHILD"):
            print(
                "mesh-smoke: forced-CPU child still has "
                f"{len(jax.devices())} devices, need 8",
                file=sys.stderr,
            )
            return 1
        import subprocess

        env = dict(os.environ)
        env.update(
            MESH_SMOKE_CHILD="1",
            JAX_PLATFORMS="cpu",
            XLA_FLAGS=(
                env.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8"
            ).strip(),
        )
        return subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--mesh-smoke"],
            env=env,
        ).returncode

    from cruise_control_tpu.analyzer import Engine, OptimizerConfig
    from cruise_control_tpu.analyzer.objective import DEFAULT_CHAIN
    from cruise_control_tpu.parallel.sharded import ShardedEngine, model_mesh
    from cruise_control_tpu.testing.fixtures import RandomClusterSpec, random_cluster_fast

    state = random_cluster_fast(
        RandomClusterSpec(
            num_brokers=24, num_partitions=1500, num_racks=6, num_topics=12, skew=1.0
        ),
        seed=7,
    )
    cfg = OptimizerConfig(
        num_candidates=512, leadership_candidates=128, swap_candidates=64,
        steps_per_round=16, num_rounds=4, seed=0,
    )
    devices = jax.devices()

    def timed_run(engine):
        t0 = time.monotonic()
        final, _history = engine.run()
        jax.block_until_ready(final.replica_broker)
        return final, round(time.monotonic() - t0, 3)

    plain_final, plain_wall = timed_run(Engine(state, DEFAULT_CHAIN, config=cfg))
    out: dict = {}
    parity = True
    for n in (1, 8):
        se = ShardedEngine(
            state, DEFAULT_CHAIN, mesh=model_mesh(devices[:n]), config=cfg
        )
        final, wall = timed_run(se)
        obj, _, _ = DEFAULT_CHAIN.evaluate(final)
        same = all(
            bool(
                (
                    np.asarray(getattr(plain_final, f))
                    == np.asarray(getattr(final, f))
                ).all()
            )
            for f in ("replica_broker", "replica_is_leader", "replica_disk")
        )
        parity = parity and same
        out[f"n{n}"] = dict(
            wall_s=wall,
            objective=float(obj),
            byte_parity_vs_plain=same,
            collective_bytes_per_round=int(se.collective_bytes_per_round),
        )
    obj_plain, _, _ = DEFAULT_CHAIN.evaluate(plain_final)
    obj_ok = out["n1"]["objective"] == out["n8"]["objective"] == float(obj_plain)
    coll_ok = (
        out["n1"]["collective_bytes_per_round"] == 0
        and out["n8"]["collective_bytes_per_round"] > 0
    )
    ok = parity and obj_ok and coll_ok
    _emit(
        metric="mesh_smoke",
        value=out["n8"]["wall_s"],
        unit="s",
        vs_baseline=round(out["n8"]["wall_s"] / max(plain_wall, 1e-9), 4),
        n_devices=8,
        plain=dict(wall_s=plain_wall, objective=float(obj_plain)),
        **out,
        byte_parity=parity,
        objective_parity=obj_ok,
        collective_accounting=coll_ok,
        ok=ok,
    )
    return 0 if ok else 1


def mesh_chaos(smoke_mode: bool = False) -> int:
    """`bench.py --mesh-chaos [--smoke]`: the mesh fault-tolerance gate —
    device loss injected MID-ANNEAL on a virtual 8-device CPU mesh.

    Exercises the full degrade-and-resume ladder (analyzer/optimizer.py
    `_optimize_mesh_ft` + parallel/ft.py): a DEVICE_LOST-shaped failure
    surfaces at a slice boundary two slices into a supervised sharded
    anneal, the per-device probe fan-out pins it on the injected chip,
    and the run resumes on the 4 survivors from the last slice-boundary
    carry checkpoint.  Gates:

      * the chaos run completes NON-degraded at reduced width, resumed
        (not restarted) from the checkpointed round, with the lost chip
        named in the result's mesh_ft history record;
      * its placements are byte-identical to a clean full-width run —
        the replicated mesh's width-independence (full-K draws before
        slicing) makes reduced-width resume exact, so this one equality
        subsumes "byte-equal a clean reduced-width run from that
        checkpoint";
      * exactly ONE MESH_DEGRADED event per degrade episode (drained via
        poll_event; the episode stays open at reduced width);
      * the checkpoint-OFF path (tpu.mesh.ft.checkpoint.every.slices=0)
        is byte-for-byte the pre-FT behavior with an IDENTICAL dispatch
        stream — zero snapshot dispatches, zero extra anything.

    Checkpoint overhead (snapshot wall vs anneal wall) is reported, not
    gated — CPU CI timing is noise; the correctness gates above are not.
    Self-provisions 8 virtual devices exactly like `--mesh-smoke`.
    """
    import jax

    if len(jax.devices()) < 8:
        if os.environ.get("MESH_CHAOS_CHILD"):
            print(
                "mesh-chaos: forced-CPU child still has "
                f"{len(jax.devices())} devices, need 8",
                file=sys.stderr,
            )
            return 1
        import subprocess

        env = dict(os.environ)
        env.update(
            MESH_CHAOS_CHILD="1",
            JAX_PLATFORMS="cpu",
            XLA_FLAGS=(
                env.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8"
            ).strip(),
        )
        argv = ["--mesh-chaos"] + (["--smoke"] if smoke_mode else [])
        return subprocess.run(
            [sys.executable, os.path.abspath(__file__)] + argv, env=env
        ).returncode

    import threading

    from cruise_control_tpu.analyzer import GoalOptimizer, OptimizerConfig
    from cruise_control_tpu.analyzer.engine import SegmentContext, segmented_execution
    from cruise_control_tpu.common.device_watchdog import DeviceSupervisor
    from cruise_control_tpu.common.dispatch import dispatch_meter
    from cruise_control_tpu.common.sensors import SensorRegistry
    from cruise_control_tpu.parallel.ft import MeshFtController
    from cruise_control_tpu.testing import faults
    from cruise_control_tpu.testing.fixtures import RandomClusterSpec, random_cluster_fast

    spec = (
        RandomClusterSpec(
            num_brokers=24, num_partitions=1500, num_racks=6, num_topics=12, skew=1.0
        )
        if smoke_mode
        else RandomClusterSpec(
            num_brokers=48, num_partitions=6000, num_racks=6, num_topics=24, skew=1.0
        )
    )
    state = random_cluster_fast(spec, seed=7)
    cfg = OptimizerConfig(
        num_candidates=512, leadership_candidates=128, swap_candidates=64,
        steps_per_round=16, num_rounds=4 if smoke_mode else 6, seed=0,
    )

    def make_opt(ft, sensors=None):
        return GoalOptimizer(
            config=cfg,
            parallel_mode="sharded",
            supervisor=DeviceSupervisor(
                op_timeout_s=600.0, max_retries=0, sensors=sensors
            ),
            mesh_ft=ft,
            sensors=sensors,
        )

    def timed(opt, run_state):
        t0 = time.monotonic()
        res = opt.optimize(run_state)
        return res, round(time.monotonic() - t0, 3)

    def same_result(a, b) -> bool:
        return float(a.objective_after) == float(b.objective_after) and all(
            bool(
                (
                    np.asarray(getattr(a.state_after, f))
                    == np.asarray(getattr(b.state_after, f))
                ).all()
            )
            for f in ("replica_broker", "replica_is_leader", "replica_disk")
        )

    out: dict = {}

    # -- baseline: FT disabled = the pre-FT supervised mesh path --------
    opt_pre = make_opt(MeshFtController(enabled=False))
    with dispatch_meter() as m_pre:
        base, base_wall = timed(opt_pre, state)
    out["baseline"] = dict(
        wall_s=base_wall, objective=float(base.objective_after),
        dispatches=dict(m_pre.counts),
    )

    # -- checkpoint-off parity: FT on, snapshots off — byte-for-byte ----
    opt_off = make_opt(MeshFtController(checkpoint_every_slices=0))
    with dispatch_meter() as m_off:
        off, off_wall = timed(opt_off, state)
    off_parity = same_result(base, off)
    off_dispatch_parity = m_off.counts == m_pre.counts
    off_zero_snapshots = (
        m_off.counts.get("mesh.snapshot", 0) == 0
        and m_off.counts.get("engine.snapshot", 0) == 0
    )
    out["checkpoint_off"] = dict(
        wall_s=off_wall, byte_parity=off_parity,
        dispatch_parity=off_dispatch_parity,
        zero_snapshot_dispatches=off_zero_snapshots,
        dispatches=dict(m_off.counts),
    )

    # -- segmented clean run, checkpoints ON: overhead report ----------
    reg_clean = SensorRegistry()
    opt_ckpt = make_opt(
        MeshFtController(checkpoint_every_slices=1, sensors=reg_clean),
        sensors=reg_clean,
    )
    with segmented_execution(SegmentContext(0.0)):
        ckpt, ckpt_wall = timed(opt_ckpt, state)
    ckpt_timing = next(
        (h for h in ckpt.history if h.get("timing") and h.get("segmented")), {}
    )
    ckpt_parity = same_result(base, ckpt)
    snapshots_taken = int(ckpt_timing.get("snapshots", 0))
    snapshot_s = float(ckpt_timing.get("snapshot_s", 0.0))
    out["checkpoint_on"] = dict(
        wall_s=ckpt_wall, byte_parity=ckpt_parity,
        segments=ckpt_timing.get("segments"),
        snapshots=snapshots_taken,
        snapshot_s=snapshot_s,
        overhead_vs_baseline=round(ckpt_wall / max(base_wall, 1e-9), 4),
    )

    # -- chaos: device 6 dies at the second slice boundary -------------
    LOST = 6
    reg = SensorRegistry()
    ft = MeshFtController(checkpoint_every_slices=1, sensors=reg)
    opt = make_opt(ft, sensors=reg)
    tripped = threading.Event()
    boundaries = {"n": 0}

    def chk():
        # the scheduler's between-slice pause callback doubles as the
        # injection point: two slices in, the next mesh dispatch would
        # fail — surface the backend's DEVICE_LOST shape right here
        boundaries["n"] += 1
        if boundaries["n"] == 2:
            tripped.set()
            raise faults.device_lost_error("mesh.run", LOST)

    def probe_effect(op, fn, args, kwargs):
        # latched like testing.faults.device_loss: once the chip is gone
        # its attribution probe fails too, every other chip's passes
        if tripped.is_set() and getattr(args[0], "id", None) == LOST:
            raise faults.device_lost_error(op, LOST)
        return fn(*args, **kwargs)

    with faults.device_fault(
        probe_effect, ops=(faults.DEVICE_PROBE_OP,)
    ) as plog, segmented_execution(SegmentContext(0.0, chk)):
        chaos, chaos_wall = timed(opt, state)

    ft_rec = next(
        (h for h in reversed(chaos.history) if h.get("mesh_ft")), {}
    )
    chaos_timing = next(
        (h for h in chaos.history if h.get("timing") and h.get("segmented")), {}
    )
    event = ft.poll_event()
    event_drained_once = event is not None and ft.poll_event() is None
    resumes = getattr(reg.get("analyzer.mesh-ft.resumes"), "count", 0)
    device_lost = getattr(reg.get("analyzer.mesh-ft.device-lost"), "count", 0)
    chaos_ok = (
        not chaos.degraded
        and ft_rec.get("resumed") is True
        and ft_rec.get("width") == 4
        and ft_rec.get("full_width") == 8
        and ft_rec.get("lost_devices") == [LOST]
        and int(ft_rec.get("resumed_from_round") or 0) >= 1
        and chaos_timing.get("resumed_from_round") == ft_rec.get("resumed_from_round")
        and ft.episodes == 1
        and event_drained_once
        and event.get("failure_class") == "device_lost"
        and ft.episode_open  # still at reduced width: not healed yet
        and resumes == 1
        and device_lost >= 1
    )
    chaos_parity = same_result(base, chaos)
    out["chaos"] = dict(
        wall_s=chaos_wall,
        byte_parity_vs_clean=chaos_parity,
        resumed_from_round=ft_rec.get("resumed_from_round"),
        lost_devices=ft_rec.get("lost_devices"),
        width=ft_rec.get("width"),
        episodes=ft.episodes,
        event=event,
        probes=dict(plog.fired),
        degrade_contract=chaos_ok,
        mesh_ft_state=ft.state_json(),
        sensors=reg.snapshot(),
    )

    ok = (
        off_parity and off_dispatch_parity and off_zero_snapshots
        and ckpt_parity and snapshots_taken >= 1
        and chaos_ok and chaos_parity
    )
    _emit(
        metric="mesh_chaos",
        value=chaos_wall,
        unit="s",
        vs_baseline=round(chaos_wall / max(base_wall, 1e-9), 4),
        n_devices=8,
        **out,
        ok=ok,
    )
    return 0 if ok else 1


MESH_NORTH_STAR_SPEC = dict(
    num_brokers=25_000,
    num_racks=100,
    num_topics=400,
    num_partitions=2_000_000,
    min_replication=2,
    max_replication=3,
    skew=0.5,
    broker_capacity=(100.0, 500_000.0, 500_000.0, 5_000_000.0),
    mean_cpu=0.15,
    mean_nw_in=400.0,
    mean_nw_out=500.0,
    mean_disk=4000.0,
)


def _per_device_model_bytes(statics) -> dict:
    """Bytes of the PLACED engine statics resident per device id —
    replicated leaves bill their full copy to every device, sharded
    leaves bill each device its own row block."""
    import jax

    out: dict = {}
    for leaf in jax.tree_util.tree_leaves(statics):
        if hasattr(leaf, "addressable_shards"):
            for sh in leaf.addressable_shards:
                out[sh.device.id] = out.get(sh.device.id, 0) + int(sh.data.nbytes)
    return out


def mesh(smoke_mode: bool) -> int:
    """`bench.py --mesh [--smoke]`: the sharded-MODEL mesh mode at the
    scale-out north star — 25k brokers / 2M partitions on 8 chips
    (virtual CPU devices under check.sh; real chips on a device host).

    Two gates plus a scaling report, written to BENCH_mesh_r01.json:

      1. PARITY (small geometry): plain engine, replicated mesh and
         sharded-model mesh runs of one seeded anneal must produce
         byte-identical placements and equal objectives.  The state is
         pre-padded to the shard multiple so every mode normalizes by
         the same padded partition count, and loads are integer-quantized
         so the sharded mode's psum'd partial sums are exact
         (parallel/model_shard.py "Byte parity").
      2. MEMORY (north-star shape): the sharded run's per-device placed
         model bytes must be <= 1/4 of the replicated footprint (the
         whole point of sharding the model axis: 8 chips hold ~1/8 each).

    Scaling efficiency = plain 1-device wall / (n * sharded n-device
    wall) over the warm (post-compile) runs — reported, not gated: on
    the virtual CPU mesh all 8 "devices" share the host's cores, so CI
    efficiency is meaningless; the number is the record a device host
    fills in.  Per-device peak live bytes ride along from
    common/profiling.per_device_live_bytes (the scraped counterpart is
    the `tpu.device.peak-live-bytes-by-bucket` collector).

    Smoke mode shrinks the SEARCH (2 steps, 1 round, 256 candidates) but
    keeps the full 25k/2M geometry — the memory claim is about the model
    arrays, which exist at full scale either way.
    """
    import dataclasses

    import jax

    if len(jax.devices()) < 8:
        if os.environ.get("MESH_BENCH_CHILD"):
            print(
                "mesh: forced-CPU child still has "
                f"{len(jax.devices())} devices, need 8",
                file=sys.stderr,
            )
            return 1
        import subprocess

        env = dict(os.environ)
        env.update(
            MESH_BENCH_CHILD="1",
            JAX_PLATFORMS="cpu",
            XLA_FLAGS=(
                env.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8"
            ).strip(),
        )
        argv = ["--mesh"] + (["--smoke"] if smoke_mode else [])
        return subprocess.run(
            [sys.executable, os.path.abspath(__file__)] + argv, env=env
        ).returncode

    import jax.numpy as jnp

    from cruise_control_tpu.analyzer import Engine, OptimizerConfig
    from cruise_control_tpu.analyzer.objective import DEFAULT_CHAIN
    from cruise_control_tpu.common.profiling import per_device_live_bytes
    from cruise_control_tpu.models.builder import pad_state
    from cruise_control_tpu.models.sharding import shard_multiple_shape
    from cruise_control_tpu.parallel.mesh import MeshEngine, grid_mesh
    from cruise_control_tpu.testing.fixtures import RandomClusterSpec, random_cluster_fast

    n_dev = 8
    devices = jax.devices()[:n_dev]
    record: dict = dict(
        metric="mesh_model_sharded_north_star",
        mode="smoke" if smoke_mode else "full",
        n_devices=n_dev,
        platform=devices[0].platform,
    )

    def timed_run(engine):
        t0 = time.monotonic()
        final, history = engine.run()
        jax.block_until_ready(final.replica_broker)
        return final, history, round(time.monotonic() - t0, 3)

    # ---- gate 1: 3-way byte parity at small geometry --------------------
    small = random_cluster_fast(
        RandomClusterSpec(num_brokers=12, num_partitions=160, skew=1.5), seed=21
    )
    # integer-quantized loads: psum partial sums add exactly in f32
    small = dataclasses.replace(
        small,
        replica_load_leader=jnp.round(small.replica_load_leader * 8),
        replica_load_follower=jnp.round(small.replica_load_follower * 8),
    )
    # pre-pad so all three modes normalize by the same padded shape
    small = pad_state(small, shard_multiple_shape(small.shape, n_dev))
    small_cfg = OptimizerConfig(
        num_candidates=60, leadership_candidates=16, swap_candidates=8,
        steps_per_round=6, num_rounds=3, seed=3,
    )
    mesh2d = grid_mesh(1, n_dev, devices)
    finals = {}
    for name, eng in (
        ("plain", Engine(small, DEFAULT_CHAIN, config=small_cfg)),
        ("replicated", MeshEngine(small, DEFAULT_CHAIN, mesh=mesh2d, config=small_cfg)),
        ("sharded", MeshEngine(
            small, DEFAULT_CHAIN, mesh=mesh2d, config=small_cfg,
            model_shard_min_partitions=1,
        )),
    ):
        if name == "sharded" and not eng.model_sharded:
            print("mesh: sharded engine fell back to replicated", file=sys.stderr)
            return 1
        final, _, _ = timed_run(eng)
        obj, viol, _ = DEFAULT_CHAIN.evaluate(final)
        finals[name] = (final, float(obj), np.asarray(viol))
    parity = True
    for f in ("replica_broker", "replica_is_leader", "replica_disk"):
        vals = [np.asarray(getattr(finals[n][0], f)) for n in ("plain", "replicated", "sharded")]
        parity &= bool((vals[0] == vals[1]).all()) and bool((vals[1] == vals[2]).all())
    objs = [finals[n][1] for n in ("plain", "replicated", "sharded")]
    viols = [finals[n][2] for n in ("plain", "replicated", "sharded")]
    parity &= objs[0] == objs[1] == objs[2]
    parity &= bool((viols[0] == viols[1]).all()) and bool((viols[1] == viols[2]).all())
    record["small_geometry_parity"] = dict(
        byte_identical=bool(parity), objective=objs[0],
        shape=dict(B=small.shape.B, P=small.shape.P, R=small.shape.R),
    )
    del finals

    # ---- gate 2 + scaling: the 25k / 2M north-star shape ----------------
    t0 = time.monotonic()
    state = random_cluster_fast(RandomClusterSpec(**MESH_NORTH_STAR_SPEC), seed=11)
    record["fixture"] = dict(
        brokers=state.shape.B, partitions=state.shape.P, replicas=state.shape.R,
        gen_s=round(time.monotonic() - t0, 1),
    )
    search = (
        dict(num_candidates=256, leadership_candidates=64, swap_candidates=32,
             steps_per_round=2, num_rounds=1, seed=0)
        if smoke_mode
        else {**SEARCH, "num_rounds": 4}
    )
    cfg = OptimizerConfig(**search)

    sharded = MeshEngine(
        state, DEFAULT_CHAIN, mesh=grid_mesh(1, n_dev, devices), config=cfg,
        model_shard_min_partitions=500_000,
    )
    if not sharded.model_sharded:
        print("mesh: north-star engine fell back to replicated", file=sys.stderr)
        return 1
    dev_bytes = _per_device_model_bytes(sharded.statics)
    replicated_bytes = sum(
        int(getattr(leaf, "nbytes", 0))
        for leaf in jax.tree_util.tree_leaves(sharded.engine.statics)
    )
    max_dev_bytes = max(dev_bytes.values())
    mem_ok = max_dev_bytes <= replicated_bytes / 4
    final, hist, cold_wall = timed_run(sharded)
    _, _, warm_wall = timed_run(sharded)
    obj0, _, _ = DEFAULT_CHAIN.evaluate(state)
    obj1, _, _ = DEFAULT_CHAIN.evaluate(final)
    timing = next((h for h in hist if h.get("timing")), hist[-1] if hist else {})
    peak = per_device_live_bytes()
    record["north_star"] = dict(
        sharded_wall_s=warm_wall,
        sharded_wall_incl_compile_s=cold_wall,
        objective_before=round(float(obj0), 6),
        objective_after=round(float(obj1), 6),
        improved=bool(float(obj1) < float(obj0)),
        per_device_model_bytes={str(k): v for k, v in sorted(dev_bytes.items())},
        replicated_model_bytes=replicated_bytes,
        max_device_fraction_of_replicated=round(max_dev_bytes / replicated_bytes, 4),
        model_bytes_quarter_gate=bool(mem_ok),
        collective_bytes_per_round=int(timing.get("collective_bytes") or 0),
        model_psum_bytes_per_round=int(timing.get("model_psum_bytes") or 0),
        per_device_peak_live_bytes={str(k): int(v) for k, v in sorted(peak.items())},
    )
    del final, sharded

    plain = Engine(state, DEFAULT_CHAIN, config=cfg)
    _, _, plain_cold = timed_run(plain)
    _, _, plain_warm = timed_run(plain)
    del plain
    efficiency = plain_warm / (n_dev * max(warm_wall, 1e-9))
    record["scaling"] = dict(
        plain_n1_wall_s=plain_warm,
        plain_n1_wall_incl_compile_s=plain_cold,
        sharded_n8_wall_s=warm_wall,
        scaling_efficiency=round(efficiency, 4),
        note="virtual CPU devices share host cores; efficiency is the "
             "record a real 8-chip host fills in",
    )
    ok = parity and mem_ok
    record.update(value=warm_wall, unit="s", vs_baseline=round(warm_wall / 10.0, 4), ok=ok)
    _emit(**record)
    out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_mesh_r01.json")
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
        f.write("\n")
    return 0 if ok else 1


def trace_overhead() -> int:
    """`bench.py --trace-overhead`: tracing is ON by default on the hot
    proposal path, so its cost is gated, not assumed.  Runs the smoke
    workload with the flight recorder enabled vs disabled (same compiled
    engine, min-of-N walls) and fails when tracing adds more than 2%.
    A small absolute epsilon keeps sub-millisecond CPU timing noise from
    failing runs whose spans cost nothing."""
    import jax

    from cruise_control_tpu.analyzer import GoalOptimizer, OptimizerConfig
    from cruise_control_tpu.common.trace import Tracer
    from cruise_control_tpu.testing.fixtures import RandomClusterSpec, random_cluster_fast

    state = random_cluster_fast(
        RandomClusterSpec(
            num_brokers=24, num_partitions=1500, num_racks=6, num_topics=12, skew=1.0
        ),
        seed=7,
    )
    cfg = OptimizerConfig(
        num_candidates=512, leadership_candidates=128, swap_candidates=64,
        steps_per_round=16, num_rounds=4, init_temperature_scale=0.0, seed=0,
    )
    reps = 7
    walls: dict[str, float] = {}
    n_spans = 0
    for mode in ("traced", "untraced"):
        tracer = Tracer(enabled=(mode == "traced"))
        opt = GoalOptimizer(config=cfg, tracer=tracer)
        opt.optimize(state)  # warm: compile outside the measurement
        best = float("inf")
        for _ in range(reps):
            t0 = time.monotonic()
            opt.optimize(state)
            best = min(best, time.monotonic() - t0)
        walls[mode] = best
        if mode == "traced":
            n_spans = len(tracer._all_spans())
    overhead = walls["traced"] / max(walls["untraced"], 1e-9) - 1.0
    ok = walls["traced"] <= walls["untraced"] * 1.02 + 0.002
    _emit(
        metric="trace_overhead_smoke",
        value=round(walls["traced"], 4),
        unit="s",
        vs_baseline=round(overhead, 4),
        traced_wall_s=round(walls["traced"], 4),
        untraced_wall_s=round(walls["untraced"], 4),
        overhead_pct=round(overhead * 100, 2),
        spans_recorded=n_spans,
        ok=ok,
    )
    return 0 if ok else 1


def blackbox_overhead() -> int:
    """`bench.py --blackbox-overhead`: the black-box dispatch spool is ON
    by default wherever a durable directory exists, so its cost is gated
    by measurement, not assumption — same shape as --trace-overhead.

    Runs the smoke workload with the recorder spooling to a temp
    directory vs disabled (same compiled engine, min-of-N walls) and
    fails past 2% overhead; also pins the DISABLED path leaves no spool
    file and that recording changes NOTHING about results (byte-identical
    placements) — observation must never perturb the optimization."""
    import os as _os
    import tempfile

    import jax
    import numpy as np

    from cruise_control_tpu.analyzer import GoalOptimizer, OptimizerConfig
    from cruise_control_tpu.common.blackbox import RECORDER
    from cruise_control_tpu.testing.fixtures import RandomClusterSpec, random_cluster_fast

    state = random_cluster_fast(
        RandomClusterSpec(
            num_brokers=24, num_partitions=1500, num_racks=6, num_topics=12, skew=1.0
        ),
        seed=7,
    )
    cfg = OptimizerConfig(
        num_candidates=512, leadership_candidates=128, swap_candidates=64,
        steps_per_round=16, num_rounds=4, init_temperature_scale=0.0, seed=0,
    )
    reps = 7
    walls: dict[str, float] = {}
    placements: dict[str, object] = {}
    spool_dir = tempfile.mkdtemp(prefix="blackbox-bench-")
    records_written = 0

    def _spool_bytes() -> int:
        return sum(
            _os.path.getsize(_os.path.join(spool_dir, f))
            for f in _os.listdir(spool_dir)
        )

    try:
        for mode in ("recorded", "disabled"):
            if mode == "recorded":
                RECORDER.configure(
                    _os.path.join(spool_dir, f"spool-{_os.getpid()}.jsonl")
                )
            else:
                RECORDER.configure(None)
            opt = GoalOptimizer(config=cfg)
            result = opt.optimize(state)  # warm: compile outside the measurement
            placements[mode] = np.asarray(result.state_after.replica_broker)
            best = float("inf")
            for _ in range(reps):
                t0 = time.monotonic()
                opt.optimize(state)
                best = min(best, time.monotonic() - t0)
            walls[mode] = best
            if mode == "recorded":
                records_written = RECORDER.state_json()["recordsWritten"]
                bytes_after_recorded = _spool_bytes()
    finally:
        RECORDER.configure(None)
    overhead = walls["recorded"] / max(walls["disabled"], 1e-9) - 1.0
    parity = bool((placements["recorded"] == placements["disabled"]).all())
    # the disabled pin: the whole disabled run wrote ZERO spool bytes
    no_writes_when_disabled = _spool_bytes() == bytes_after_recorded
    ok = (
        walls["recorded"] <= walls["disabled"] * 1.02 + 0.002
        and parity
        and records_written > 0
        and no_writes_when_disabled
    )
    _emit(
        metric="blackbox_overhead_smoke",
        value=round(walls["recorded"], 4),
        unit="s",
        vs_baseline=round(overhead, 4),
        recorded_wall_s=round(walls["recorded"], 4),
        disabled_wall_s=round(walls["disabled"], 4),
        overhead_pct=round(overhead * 100, 2),
        records_written=records_written,
        disabled_parity=parity,
        ok=ok,
    )
    return 0 if ok else 1


def ledger_overhead() -> int:
    """`bench.py --ledger-overhead`: convergence diagnostics + the
    decision ledger are ON by default, so their cost is gated by
    measurement, not assumption — same shape as --blackbox-overhead.

    Runs the smoke workload with diagnostics compiled in AND one ledger
    decision record written per run, vs both off (min-of-N walls), and
    fails past 2% overhead; also pins that the diagnostics-on engine
    produces BYTE-IDENTICAL placements to diagnostics-off (observation
    must never perturb the search) and that the disabled path writes
    ZERO ledger bytes."""
    import dataclasses as _dc
    import os as _os
    import tempfile

    import jax
    import numpy as np

    from cruise_control_tpu.analyzer import GoalOptimizer, OptimizerConfig
    from cruise_control_tpu.analyzer.ledger import (
        DecisionLedger,
        build_decision_record,
    )
    from cruise_control_tpu.testing.fixtures import RandomClusterSpec, random_cluster_fast

    state = random_cluster_fast(
        RandomClusterSpec(
            num_brokers=24, num_partitions=1500, num_racks=6, num_topics=12, skew=1.0
        ),
        seed=7,
    )
    base_cfg = OptimizerConfig(
        num_candidates=512, leadership_candidates=128, swap_candidates=64,
        steps_per_round=16, num_rounds=4, init_temperature_scale=0.0, seed=0,
    )
    reps = 7
    walls: dict[str, float] = {}
    placements: dict[str, object] = {}
    ledger_dir = tempfile.mkdtemp(prefix="ledger-bench-")
    records_written = 0
    conv_rounds = None

    def _dir_bytes() -> int:
        return sum(
            _os.path.getsize(_os.path.join(ledger_dir, f))
            for f in _os.listdir(ledger_dir)
        )

    for mode in ("recorded", "disabled"):
        cfg = _dc.replace(base_cfg, diagnostics=(mode == "recorded"))
        led = (
            DecisionLedger(_os.path.join(ledger_dir, "decision-ledger.jsonl"))
            if mode == "recorded"
            else None
        )

        def run_once(opt=GoalOptimizer(config=cfg), led=led):
            result = opt.optimize(state)
            if led is not None:
                led.record_decision(
                    build_decision_record(result, source="bench")
                )
            return result

        result = run_once()  # warm: compile outside the measurement
        placements[mode] = np.asarray(result.state_after.replica_broker)
        if mode == "recorded":
            timing = next(h for h in result.history if h.get("timing"))
            conv_rounds = timing["convergence"]["rounds"]
        best = float("inf")
        for _ in range(reps):
            t0 = time.monotonic()
            run_once()
            best = min(best, time.monotonic() - t0)
        walls[mode] = best
        if mode == "recorded":
            records_written = led.records_written
            bytes_after_recorded = _dir_bytes()
            led.close()
    overhead = walls["recorded"] / max(walls["disabled"], 1e-9) - 1.0
    parity = bool((placements["recorded"] == placements["disabled"]).all())
    # the disabled pin: the whole disabled run wrote ZERO ledger bytes
    no_writes_when_disabled = _dir_bytes() == bytes_after_recorded
    ok = (
        walls["recorded"] <= walls["disabled"] * 1.02 + 0.002
        and parity
        and records_written > 0
        and conv_rounds is not None
        and conv_rounds >= 1
        and no_writes_when_disabled
    )
    _emit(
        metric="ledger_overhead_smoke",
        value=round(walls["recorded"], 4),
        unit="s",
        vs_baseline=round(overhead, 4),
        recorded_wall_s=round(walls["recorded"], 4),
        disabled_wall_s=round(walls["disabled"], 4),
        overhead_pct=round(overhead * 100, 2),
        decisions_recorded=records_written,
        convergence_rounds=conv_rounds,
        diagnostics_parity=parity,
        disabled_zero_bytes=no_writes_when_disabled,
        ok=ok,
    )
    return 0 if ok else 1


def fleet_smoke() -> int:
    """`bench.py --fleet-smoke`: the fleet controller's economics gate.

    Boots a 3-cluster simulated fleet (east/west share a bucketed shape,
    south has its own) behind ONE shared AnalyzerCore and gates:

      * compiled-engine count < cluster count (same-bucket clusters rebind
        one engine — the whole point of the shared core), with at least
        one engine-cache HIT recorded on the shared registry;
      * per-cluster WARM proposal wall within 1.5x a single-cluster
        baseline of the same geometry — multi-tenancy must not tax the
        steady-state serving path (compiles excluded: both sides measure
        after their first run).
    """
    import jax

    from cruise_control_tpu.service.main import (
        build_simulated_fleet,
        build_simulated_service,
    )
    from cruise_control_tpu.service.progress import OperationProgress

    reps = 3

    def warm_wall(fn) -> float:
        fn()  # first run pays compile/cache-load; the gate is steady state
        best = float("inf")
        for _ in range(reps):
            t0 = time.monotonic()
            fn()
            best = min(best, time.monotonic() - t0)
        return best

    # single-cluster baselines, one per fleet geometry (the default
    # build_simulated_service matches east/west; south is the bigger one)
    geometries = {
        "small": dict(num_brokers=6, topics={"T0": 12, "T1": 12}),
        "large": dict(num_brokers=12, topics={"T0": 48, "T1": 48}),
    }
    baselines = {}
    for name, geo in geometries.items():
        app, fetcher, admin, sampler = build_simulated_service(seed=31, **geo)
        baselines[name] = warm_wall(
            lambda cc=app.cc: cc.proposals(OperationProgress(), ignore_cache=True)
        )
        app.stop()

    app, fleet = build_simulated_fleet(seed=31)
    opt = fleet.core.optimizer
    per_cluster = {}
    for cid in fleet.contexts:
        per_cluster[cid] = warm_wall(
            lambda cc=fleet.facade(cid): cc.proposals(
                OperationProgress(), ignore_cache=True
            )
        )
    engines = opt.cache_size
    hits = opt.engine_cache_hits
    ratios = {
        cid: per_cluster[cid]
        / max(baselines["large" if cid == "south" else "small"], 1e-9)
        for cid in per_cluster
    }
    # 1.5x + a small absolute epsilon: these are ~100ms CPU walls and a
    # scheduler hiccup must not flake the gate
    ok_wall = all(
        per_cluster[cid]
        <= 1.5 * baselines["large" if cid == "south" else "small"] + 0.05
        for cid in per_cluster
    )
    ok_engines = engines < len(fleet.contexts) and hits >= 1
    sched_report = _scheduler_burst()
    ok = ok_wall and ok_engines and sched_report["ok"]
    _emit(
        metric="fleet_smoke",
        value=round(max(per_cluster.values()), 4),
        unit="s",
        vs_baseline=round(max(ratios.values()), 3),
        clusters=len(fleet.contexts),
        compiled_engines=engines,
        engine_cache_hits=hits,
        per_cluster_wall_s={k: round(v, 4) for k, v in per_cluster.items()},
        baseline_wall_s={k: round(v, 4) for k, v in baselines.items()},
        wall_ratio={k: round(v, 3) for k, v in ratios.items()},
        ok_engines=ok_engines,
        ok_wall=ok_wall,
        scheduler=sched_report,
        ok=ok,
    )
    fleet.shutdown()
    return 0 if ok else 1


def _scheduler_burst(n_clusters: int = 20, duration_s: float = 2.0) -> dict:
    """Device-scheduler overload gate (stepping toward the ROADMAP
    `bench.py --fleet` 100-cluster freshness-SLO gate): a 20-cluster
    synthetic burst of BACKGROUND drift cycles under `device_slowdown`,
    with URGENT broker-failure-fix dispatches injected throughout.

    Reports per-class p50/p99 queue-to-dispatch wait + deadline-miss
    ratio and GATES: urgent p99 wait <= one slice budget, zero urgent
    sheds, every shed counted in fleet.scheduler.shed-total.  Synthetic
    device work (sleep-shaped slices through the @device_op seam) keeps
    the burst deterministic and CPU-cheap — the engine-level parity and
    preemption mechanics are pinned by tests/test_scheduler.py."""
    import threading

    from cruise_control_tpu.common.device_watchdog import device_op
    from cruise_control_tpu.fleet.scheduler import (
        BackgroundShedError,
        DeviceScheduler,
        WorkClass,
    )
    from cruise_control_tpu.testing import faults

    slice_s = 0.05
    slowdown = 3.0
    sched = DeviceScheduler(
        slice_budget_s=slice_s * slowdown * 1.5,
        freshness_slo_s=1.0,
        aging_s=0.5,
        shed_queue_depth=max(4, n_clusters // 3),
        brownout_after_s=duration_s / 2,
    )

    @device_op("engine.run")
    def device_slice():
        time.sleep(slice_s)

    from cruise_control_tpu.analyzer.engine import current_segment_context

    def background_cycle():
        ctx = current_segment_context()
        for i in range(3):
            device_slice()
            if ctx is not None and ctx.checkpoint is not None and i < 2:
                ctx.checkpoint()

    stop = threading.Event()
    count_lock = threading.Lock()
    shed_count = [0]
    brownout_runs = [0]

    def cluster_loop(cid):
        while not stop.is_set():
            try:
                if sched.brownout_active:
                    with count_lock:
                        brownout_runs[0] += 1
                sched.run(
                    WorkClass.BACKGROUND, background_cycle,
                    cluster_id=f"c{cid}", op="controller-cycle",
                )
            except BackgroundShedError:
                # locked: 20 threads race this count, and the gate below
                # compares it for EXACT equality with the scheduler's own
                # lock-protected shed counter
                with count_lock:
                    shed_count[0] += 1
                time.sleep(0.02)

    urgent_waits: list[float] = []
    urgent_device_s = slice_s * slowdown
    with faults.device_slowdown(slowdown) as log:
        threads = [
            threading.Thread(target=cluster_loop, args=(i,), daemon=True)
            for i in range(n_clusters)
        ]
        for t in threads:
            t.start()
        time.sleep(0.3)  # let the burst pile up
        deadline = time.monotonic() + duration_s
        while time.monotonic() < deadline:
            t0 = time.monotonic()
            sched.run(
                WorkClass.URGENT, device_slice, cluster_id="cX",
                op="fix:broker-failure",
            )
            urgent_waits.append(time.monotonic() - t0 - urgent_device_s)
            time.sleep(0.1)
        stop.set()
        for t in threads:
            t.join(5.0)

    def pct(xs, p):
        if not xs:
            return 0.0
        xs = sorted(xs)
        return xs[min(len(xs) - 1, int(round(p * (len(xs) - 1))))]

    st = sched.state_json()
    dispatches = sum(st["dispatches"].values())
    misses = st["deadlineMisses"]
    per_class = {
        cls: dict(
            p50=round(pct([w for w in waits], 0.50), 4),
            p99=round(pct([w for w in waits], 0.99), 4),
            missRatio=round(
                misses[cls] / max(1, st["dispatches"][cls]), 3
            ),
        )
        for cls, waits in (("urgent", urgent_waits),)
    }
    urgent_p99 = pct(urgent_waits, 0.99)
    ok_urgent = urgent_p99 <= sched.slice_budget_s
    ok_sheds = (
        st["shedTotal"]["urgent"] == 0
        and st["shedTotal"]["background"] == shed_count[0]
        and shed_count[0] >= 1
    )
    return dict(
        clusters=n_clusters,
        sliceBudgetS=sched.slice_budget_s,
        urgentInjected=len(urgent_waits),
        urgentWait=per_class["urgent"],
        waitSeconds=st.get("waitSeconds"),
        deadlineMissRatioByClass={
            c: round(misses[c] / max(1, st["dispatches"][c]), 3)
            for c in misses
        },
        dispatches=st["dispatches"],
        totalDispatches=dispatches,
        shedTotal=st["shedTotal"],
        preemptions=st["preemptions"],
        overloadEpisodes=st["overloadEpisodes"],
        brownoutRuns=brownout_runs[0],
        deviceOpCalls=log.total_calls,
        ok_urgent_p99=ok_urgent,
        ok_sheds_counted=ok_sheds,
        ok=ok_urgent and ok_sheds,
    )


def ha_smoke() -> int:
    """`bench.py --ha-smoke`: the fleet-HA takeover SLO gate.

    Two in-process instances (A, B) share ONE lease/journal directory and
    one set of 3 simulated clusters — the exact coordination surface real
    instances share.  A starts first and owns everything; B stands by,
    heart-beating but unable to steal a live lease.  Then A is killed
    (heartbeats stop, nothing released — a crash, not a shutdown) and the
    gate holds that:

      * B acquires every cluster and serves its first post-takeover
        proposal within the budget (lease expiry + heartbeat + CPU
        compile headroom) — the measured time-to-takeover SLO;
      * the lease store's audit trail proves at most one holder per
        cluster at any instant across the whole run (the single-holder
        invariant, checked mechanically, not trusted).
    """
    import tempfile

    import jax

    from cruise_control_tpu.executor.admin import SimulatedClusterAdmin
    from cruise_control_tpu.fleet.leases import single_holder_violations
    from cruise_control_tpu.monitor.topology import StaticMetadataProvider
    from cruise_control_tpu.service.main import build_simulated_fleet
    from cruise_control_tpu.service.progress import OperationProgress
    from cruise_control_tpu.testing.synthetic import (
        SyntheticWorkloadSampler,
        synthetic_topology,
    )

    ttl, renew, slack = 1.5, 0.4, 0.2
    journal_dir = tempfile.mkdtemp(prefix="cc-ha-smoke-")
    backends = {}
    for i, cid in enumerate(("c1", "c2", "c3")):
        topo = synthetic_topology(
            num_brokers=6, topics={"T0": 12, "T1": 12}, seed=41 + i
        )
        meta = StaticMetadataProvider(topo)
        backends[cid] = (
            meta,
            SimulatedClusterAdmin(meta, link_rate_bytes_per_s=1e12),
            SyntheticWorkloadSampler(topo, seed=41 + i),
        )

    def instance(iid):
        return build_simulated_fleet({
            "fleet.clusters": "c1,c2,c3",
            "fleet.ha.enabled": "true",
            "fleet.ha.instance.id": iid,
            "fleet.ha.lease.ttl.s": ttl,
            "fleet.ha.renew.s": renew,
            "fleet.ha.skew.slack.s": slack,
            "executor.journal.dir": journal_dir,
            "anomaly.detection.interval.ms": 3_600_000,
            "tpu.prewarm.enabled": "false",
        }, backends=backends)

    app_a, fleet_a = instance("A")
    app_b, fleet_b = instance("B")
    lm_a, lm_b = fleet_a.lease_manager, fleet_b.lease_manager

    fleet_a.start_up()
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and len(lm_a.owned_clusters()) < 3:
        time.sleep(0.02)
    owned_a = sorted(lm_a.owned_clusters())

    fleet_b.start_up()  # stands by: a live lease cannot be stolen
    time.sleep(3 * renew)
    stolen = sorted(lm_b.owned_clusters())

    t_kill = time.monotonic()
    lm_a.kill()  # crash: no release — B must wait out the TTL
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline and len(lm_b.owned_clusters()) < 3:
        time.sleep(0.02)
    takeover_s = time.monotonic() - t_kill
    owned_b = sorted(lm_b.owned_clusters())
    fleet_b.facade("c1").proposals(OperationProgress(), ignore_cache=True)
    first_proposal_s = time.monotonic() - t_kill

    violations = single_holder_violations(lm_b.store.audit_events())
    # lease expiry (ttl + slack past A's last renewal, found within one
    # heartbeat) + the takeover's reconciliation/activation + one cold
    # CPU engine compile for the first proposal
    budget = ttl + slack + 2 * renew + 45.0
    ok = (
        owned_a == ["c1", "c2", "c3"]
        and stolen == []
        and owned_b == ["c1", "c2", "c3"]
        and first_proposal_s <= budget
        and violations == []
    )
    _emit(
        metric="ha_smoke",
        value=round(first_proposal_s, 3),
        unit="s",
        vs_baseline=round(first_proposal_s / budget, 3),
        takeover_s=round(takeover_s, 3),
        time_to_first_proposal_s=round(first_proposal_s, 3),
        budget_s=budget,
        lease_ttl_s=ttl,
        owned_before_kill=owned_a,
        stolen_while_alive=stolen,
        owned_after_takeover=owned_b,
        single_holder_violations=violations,
        audit_events=len(lm_b.store.audit_events()),
        ok=ok,
    )
    fleet_b.shutdown()
    fleet_a.shutdown()
    return 0 if ok else 1


def _churn_states(n_gens, *, brokers, partitions, parts_per_gen, broker_add_at, seed):
    """One synthetic churn stream: generation g has `partitions + g*delta`
    partitions (partition creates) and one broker added at broker_add_at —
    the monitor's view of a live cluster between proposal calls."""
    from cruise_control_tpu.testing.fixtures import RandomClusterSpec, random_cluster_fast

    states = []
    for g in range(n_gens):
        b = brokers + (1 if broker_add_at is not None and g >= broker_add_at else 0)
        states.append(random_cluster_fast(
            RandomClusterSpec(
                num_brokers=b,
                num_partitions=partitions + g * parts_per_gen,
                num_racks=6,
                num_topics=12,
                skew=1.0,
            ),
            seed=seed,
        ))
    return states


def churn(smoke_mode: bool) -> int:
    """`bench.py --churn [--smoke]`: serve a stream of churned generations.

    N model generations with partitions created every generation (and one
    broker add mid-stream) are served twice: with shape bucketing (states
    padded to ShapeBucketPolicy buckets, the service default) and exact.
    Emits one JSON line with p50/p95 proposal wall-clock and the engine
    compile count for each mode.  Gate (--smoke, wired into
    scripts/check.sh): every bucketed generation whose shape matches the
    previous one must hit the engine cache — churned generations compile
    ZERO engines — while the exact mode recompiles per generation.
    """
    import jax

    from cruise_control_tpu.analyzer import GoalOptimizer, OptimizerConfig
    from cruise_control_tpu.models.builder import pad_state
    from cruise_control_tpu.models.state import DEFAULT_BUCKET_POLICY

    if smoke_mode:
        scale = dict(brokers=24, partitions=1200, parts_per_gen=9,
                     broker_add_at=3, seed=11)
        n_gens = 6
        cfg = OptimizerConfig(
            num_candidates=512, leadership_candidates=128, swap_candidates=64,
            steps_per_round=16, num_rounds=3, seed=0,
        )
    else:
        scale = dict(brokers=500, partitions=50_000, parts_per_gen=250,
                     broker_add_at=4, seed=11)
        n_gens = 8
        cfg = OptimizerConfig(**SEARCH)

    states = _churn_states(n_gens, **scale)
    out: dict = {}
    in_bucket_compiles = 0
    in_bucket_gens = 0
    for mode in ("bucketed", "exact"):
        if mode == "bucketed":
            served = [
                pad_state(s, DEFAULT_BUCKET_POLICY.bucket_shape(s.shape))
                for s in states
            ]
        else:
            served = states
        opt = GoalOptimizer(config=cfg)
        walls, compiles = [], []
        for g, s in enumerate(served):
            misses0 = opt.engine_cache_misses
            t0 = time.monotonic()
            res = opt.optimize(s)
            walls.append(time.monotonic() - t0)
            compiled = opt.engine_cache_misses - misses0
            compiles.append(compiled)
            if mode == "bucketed" and g > 0:
                if served[g].shape == served[g - 1].shape:
                    in_bucket_gens += 1
                    in_bucket_compiles += compiled
            del res
        ws = sorted(walls[1:] or walls)  # steady state: drop the cold gen 0

        def pct(p):
            return round(ws[min(len(ws) - 1, int(p * len(ws)))], 3)

        out[mode] = dict(
            p50_wall_s=pct(0.50), p95_wall_s=pct(0.95),
            first_gen_s=round(walls[0], 3),
            compiles=int(sum(compiles)), per_gen_compiles=compiles,
            cache_hits=opt.engine_cache_hits,
        )
    # the scenario must actually exercise in-bucket churn, and those
    # generations must be compile-free (the acceptance gate)
    scenario_ok = in_bucket_gens >= 3
    zero_ok = in_bucket_compiles == 0
    exact_recompiles = out["exact"]["compiles"] >= max(2, n_gens - 2)
    ok = scenario_ok and zero_ok and exact_recompiles
    _emit(
        metric="churn_bucketed_vs_exact",
        value=out["bucketed"]["p50_wall_s"],
        unit="s",
        vs_baseline=round(
            out["bucketed"]["p50_wall_s"] / max(out["exact"]["p50_wall_s"], 1e-9), 4
        ),
        generations=n_gens,
        in_bucket_generations=in_bucket_gens,
        churned_generation_compiles=in_bucket_compiles,
        bucketed=out["bucketed"],
        exact=out["exact"],
        ok=ok,
    )
    return 0 if ok else 1


def scenarios_bench(smoke_mode: bool) -> int:
    """`bench.py --scenarios [--smoke]`: batched what-if evaluation gate.

    Builds one base cluster and N what-if scenarios (rack loss, broker
    adds, broker removals, topic load scaling) of ONE planned shape, then
    scores them two ways: (a) ONE batched vmap program over the stacked
    states — the planner's serving path — and (b) N sequential
    single-state evaluations of the same jitted program.  Gate (--smoke,
    wired into scripts/check.sh): the batched pass must be no slower than
    the sequential pass (steady state, both warmed) and must produce
    IDENTICAL per-scenario objectives — batching is a pure execution
    detail, never a numerics change.
    """
    import jax

    from cruise_control_tpu.analyzer.scenario_eval import ScenarioEvaluator
    from cruise_control_tpu.planner.scenario import (
        BrokerAdd,
        Scenario,
        apply_scenario,
        plan_shape,
    )
    from cruise_control_tpu.testing.fixtures import (
        RandomClusterSpec,
        random_cluster_fast,
    )

    if smoke_mode:
        spec = RandomClusterSpec(
            num_brokers=24, num_partitions=1500, num_racks=6, num_topics=12,
            skew=0.8,
        )
        n_scenarios = 12
        reps = 5
    else:
        spec = RandomClusterSpec(
            num_brokers=500, num_partitions=50_000, num_racks=20,
            num_topics=100, skew=0.5,
        )
        n_scenarios = 32
        reps = 3
    state = random_cluster_fast(spec, seed=7)
    scenarios = []
    for i in range(n_scenarios):
        kind = i % 4
        if kind == 0:
            scenarios.append(Scenario(name=f"kill-rack-{i}", kill_racks=(i % spec.num_racks,)))
        elif kind == 1:
            scenarios.append(Scenario(name=f"add-{i}", add_brokers=(BrokerAdd(count=1 + i % 3),)))
        elif kind == 2:
            scenarios.append(Scenario(
                name=f"remove-{i}", remove_brokers=(i % spec.num_brokers,)
            ))
        else:
            scenarios.append(Scenario(
                name=f"scale-{i}", topic_load_factors={i % spec.num_topics: 1.0 + 0.25 * (i % 5)}
            ))
    shape = plan_shape(state, scenarios)
    if shape != state.shape:
        from cruise_control_tpu.models.builder import pad_state

        state = pad_state(state, shape)  # pad once: scenario states alias it
    states = [apply_scenario(state, sc, shape=shape) for sc in scenarios]

    ev = ScenarioEvaluator(max_scenarios=max(32, n_scenarios))
    # warm both programs (compile outside the measurement: the gate is
    # about serving, and one batch program amortizes like any engine)
    ev.evaluate_states(states)
    obj_seq_warm, _ = ev._evaluate_cpu(states[:1])  # noqa: F841 — warm cpu jit
    t0 = time.monotonic()
    for _ in range(reps):
        batched_obj, batched_viol, _ = ev.evaluate_states(states)
    batched_s = (time.monotonic() - t0) / reps

    # sequential twin: same chain/constraint, one jitted single-state
    # program reused across scenarios (its own best case)
    ev._single_eval(states[0])  # warm
    t0 = time.monotonic()
    for _ in range(reps):
        seq = [ev._single_eval(s) for s in states]
    sequential_s = (time.monotonic() - t0) / reps
    seq_obj = np.asarray([o for o, _ in seq])

    identical = bool(np.array_equal(batched_obj, seq_obj))
    ok = identical and batched_s <= sequential_s
    _emit(
        metric="scenario_batched_vs_sequential",
        value=round(batched_s, 4),
        unit="s",
        vs_baseline=round(batched_s / max(sequential_s, 1e-9), 4),
        scenarios=n_scenarios,
        batched_wall_s=round(batched_s, 4),
        sequential_wall_s=round(sequential_s, 4),
        identical_objectives=identical,
        max_objective_delta=float(np.abs(batched_obj - seq_obj).max()),
        shape=dict(R=shape.R, B=shape.B, P=shape.P),
        ok=ok,
    )
    return 0 if ok else 1


def streaming(smoke_mode: bool) -> int:
    """`bench.py --streaming [--smoke]`: the streaming controller's gate —
    a multi-window replay of always-on incremental rebalancing.

    Replays N metric windows of a drifting synthetic workload through two
    controller configurations:

      * WARM — the production path: device-resident model, in-place
        window deltas (no re-flatten while the shape bucket holds),
        warm-start carry from the previous accepted placement, learned
        move-acceptance prior mixed into the destination draws;
      * COLD — warm starts off, delta path off (full re-flatten per
        window), prior mix 0: byte-for-byte today's
        flatten-and-anneal-from-scratch pipeline.

    Gates:
      * parity: the COLD controller's final-window placement is
        byte-identical to a direct `optimizer.optimize` over a freshly
        built model (cold prior + full re-flatten == today's results);
      * rounds: WARM anneals converge in measurably fewer rounds than
        COLD at equal-or-better objective;
      * in-place contract (sensors): across N metric-only windows the
        WARM controller re-flattens exactly once (the initial build) and
        delta-applies N-1 times.
    Also reports sustained proposals/sec for the trajectory record.
    """
    import jax

    from cruise_control_tpu.config.app_config import CruiseControlConfig
    from cruise_control_tpu.service.main import build_simulated_service

    n_windows = 12 if smoke_mode else 100
    geometry = (
        dict(num_brokers=6, topics={"T0": 12, "T1": 12})
        if smoke_mode
        else dict(num_brokers=24, topics={"T0": 96, "T1": 96, "T2": 48})
    )
    base_props = {
        "partition.metrics.window.ms": 1000,
        "min.samples.per.partition.metrics.window": 1,
        "num.partition.metrics.windows": 3,
        "execution.progress.check.interval.ms": 100,
        "webserver.http.port": 0,
        "tpu.num.candidates": 256,
        "tpu.leadership.candidates": 64,
        "tpu.steps.per.round": 24,
        "tpu.num.rounds": 4,
        "controller.enabled": True,
        # the prior warms mid-replay so the tail windows run prior-mixed
        "controller.prior.min.observations": 16,
    }

    def replay(mode_props, *, drift=1.03, seed=5):
        app, fetcher, admin, sampler = build_simulated_service(
            CruiseControlConfig({**base_props, **mode_props}), seed=seed,
            **geometry,
        )
        cc = app.cc
        ctl = cc.controller
        parts = sampler.all_partition_entities()
        wms = 1000
        rounds, objectives, violations = [], [], []
        fused, dispatches = [], []
        last_result = None
        t0 = time.monotonic()
        for w in range(4, 4 + n_windows):
            sampler.drift(drift)
            fetcher.fetch_once(parts, w * wms, (w + 1) * wms - 1)
            info = ctl.run_once()
            assert info is not None, f"window {w} produced no cycle"
            rounds.append(info["rounds"])
            objectives.append(info["objective"])
            violations.append(float(np.max(info["result"].violations_after)))
            fused.append(bool(info.get("fused")))
            dispatches.append(sum(info.get("dispatches", {}).values()))
            last_result = info["result"]
        wall = time.monotonic() - t0
        stats = ctl.state_json()
        app.stop()
        return dict(
            rounds=rounds, objectives=objectives, violations=violations,
            fused=fused, dispatches=dispatches,
            wall_s=wall, stats=stats, cc=cc, last_result=last_result,
        )

    warm = replay({})
    cold = replay({
        "controller.warm.start.enabled": False,
        "controller.delta.enabled": False,
        "controller.prior.mix": 0.0,
    })

    # parity: over the cold replay's final window, run the plain
    # request-path optimizer on a freshly built model — identical
    # placements prove the controller's cold cycle IS today's pipeline
    cc = cold["cc"]
    fresh = cc.monitor.cluster_model()
    direct = cc.optimizer.optimize(fresh, options=cc._build_options(fresh))
    ctl_after = cold["last_result"].state_after
    parity = all(
        bool(
            (
                np.asarray(getattr(ctl_after, f))
                == np.asarray(getattr(direct.state_after, f))
            ).all()
        )
        for f in ("replica_broker", "replica_is_leader", "replica_disk")
    )

    # steady-state rounds (drop the cold-start window both sides pay)
    warm_rounds = warm["rounds"][1:]
    cold_rounds = cold["rounds"][1:]
    warm_mean = sum(warm_rounds) / max(1, len(warm_rounds))
    cold_mean = sum(cold_rounds) / max(1, len(cold_rounds))
    rounds_ok = warm_mean <= cold_mean - 1.0
    # "equal objective": every warm window either clears the goal chain
    # to the early-stop tolerance (the point at which more rounds only
    # polish the noise-level dispersion tiebreaker cold's extra rounds
    # keep shaving) or matches cold's objective outright
    tol = 1e-6
    obj_ok = all(
        wv <= tol or wo <= co * (1 + 1e-6) + 1e-9
        for wo, co, wv in zip(
            warm["objectives"][1:], cold["objectives"][1:],
            warm["violations"][1:],
        )
    )
    inplace_ok = (
        warm["stats"]["fullReflattens"] == 1
        and warm["stats"]["deltaApplies"] == n_windows - 1
        and cold["stats"]["fullReflattens"] == n_windows
    )
    # the headline latency metric (ROADMAP item 4): window-roll-to-
    # published-proposal p50/p99 from the controller's histogram.  The
    # first published cycle (XLA cold compile) and the first FUSED cycle
    # (fused-program compile) are excluded — each reports through its own
    # one-shot sensor — so the histogram holds n_windows - 2 steady-state
    # samples and the p99 is an honest steady-state claim
    hist = warm["cc"].sensors.get("controller.window-roll-to-publish-seconds")
    publish_p50 = publish_p99 = None
    hist_ok = hist is not None and hist.count == n_windows - 2
    if hist is not None and hist.count:
        # None (JSON null), never NaN, when empty: the failing run's
        # record must stay parseable by strict JSON consumers
        publish_p50 = round(hist.quantile(0.5), 4)
        publish_p99 = round(hist.quantile(0.99), 4)
    # the fusion contract (tentpole gate): every steady-state delta
    # cycle after the fused program compiles runs FUSED, and a fused
    # cycle costs exactly one program dispatch + one host extraction —
    # proved by the controller's dispatch meter, not assumed.  Window 0
    # is the reflatten, window 1 goes staged while the warm engine cache
    # fills; everything after must fuse.
    fused_ok = all(warm["fused"][2:]) and not warm["fused"][0]
    dispatch_ok = all(
        d <= 2 for d, f in zip(warm["dispatches"], warm["fused"]) if f
    )
    sub_second_ok = publish_p99 is not None and publish_p99 < 1.0
    ok = (
        parity and rounds_ok and obj_ok and inplace_ok and hist_ok
        and fused_ok and dispatch_ok and sub_second_ok
    )
    rec = dict(
        metric="streaming_warm_vs_cold",
        value=round(warm["wall_s"], 3),
        unit="s",
        vs_baseline=round(warm["wall_s"] / max(cold["wall_s"], 1e-9), 4),
        windows=n_windows,
        window_roll_to_publish_p50_s=publish_p50,
        window_roll_to_publish_p99_s=publish_p99,
        publish_histogram_ok=hist_ok,
        fused_cycles=warm["stats"]["fusedCycles"],
        fused_ok=fused_ok,
        dispatches_per_fused_cycle_max=max(
            (d for d, f in zip(warm["dispatches"], warm["fused"]) if f),
            default=None,
        ),
        dispatch_ok=dispatch_ok,
        sub_second_ok=sub_second_ok,
        cold_cycle_s=warm["stats"]["coldCycleSeconds"],
        fused_cold_cycle_s=warm["stats"]["fusedColdCycleSeconds"],
        plan_sized_cycles=warm["stats"]["planSizedCycles"],
        reflattens_by_reason=warm["stats"]["fullReflattensByReason"],
        proposals_per_sec=round(n_windows / max(warm["wall_s"], 1e-9), 3),
        cold_proposals_per_sec=round(n_windows / max(cold["wall_s"], 1e-9), 3),
        warm_rounds_mean=round(warm_mean, 3),
        cold_rounds_mean=round(cold_mean, 3),
        warm_rounds=warm["rounds"],
        cold_rounds=cold["rounds"],
        warm_violations_max=max(warm["violations"]),
        cold_violations_max=max(cold["violations"]),
        warm_reflattens=warm["stats"]["fullReflattens"],
        warm_delta_applies=warm["stats"]["deltaApplies"],
        cold_reflattens=cold["stats"]["fullReflattens"],
        prior=warm["stats"]["prior"],
        cold_parity=parity,
        rounds_ok=rounds_ok,
        objective_ok=obj_ok,
        inplace_ok=inplace_ok,
        ok=ok,
    )
    _emit(**rec)
    if not smoke_mode:
        # the committed trajectory record: one JSON file per full
        # streaming run
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_streaming_r01.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
            f.write("\n")
    return 0 if ok else 1


def _coldstart_child() -> int:
    """`bench.py --coldstart-child` (internal): ONE restart phase in a
    truly fresh process.  Builds the simulated service against the
    parent's cache/manifest directories, runs start_up (the boot-prewarm
    path under test), serves one proposal, and emits the honest
    cold-start-to-first-proposal wall + the compile-cache boot report
    (fresh-trace vs AOT-load counts per bucket)."""
    t0 = time.monotonic()
    import jax

    from cruise_control_tpu.common import compilation_cache
    from cruise_control_tpu.config.app_config import CruiseControlConfig
    from cruise_control_tpu.service.main import build_simulated_service
    from cruise_control_tpu.service.progress import OperationProgress

    phase = os.environ["COLDSTART_PHASE"]
    smoke = bool(os.environ.get("COLDSTART_SMOKE"))
    props = {
        "partition.metrics.window.ms": 1000,
        "min.samples.per.partition.metrics.window": 1,
        "num.partition.metrics.windows": 3,
        "webserver.http.port": 0,
        "tpu.compile.cache.dir": os.environ["COLDSTART_CACHE_DIR"],
        "tpu.prewarm.manifest.dir": os.environ["COLDSTART_MANIFEST_DIR"],
        # the xla-cache-only phase is PR 9's slice: persistent compile
        # cache on, no manifest, no AOT — tracing is paid again
        "tpu.prewarm.enabled": phase != "xla-cache",
        "anomaly.detection.interval.ms": 3_600_000,
    }
    if smoke:
        props.update({
            # candidates >= engine.AOT_MIN_CANDIDATES: the smoke engine
            # must be AOT-worthy or phase 1 writes no artifact to gate on
            "tpu.num.candidates": 1024, "tpu.leadership.candidates": 128,
            "tpu.swap.candidates": 64, "tpu.steps.per.round": 16,
            "tpu.num.rounds": 3,
        })
        geometry = dict(num_brokers=6, topics={"T0": 12, "T1": 12})
    else:
        props.update({
            "tpu.num.candidates": 2048, "tpu.leadership.candidates": 512,
            "tpu.steps.per.round": 64, "tpu.num.rounds": 6,
        })
        geometry = dict(num_brokers=24, topics={"T0": 96, "T1": 96, "T2": 48})
    app, fetcher, admin, sampler = build_simulated_service(
        CruiseControlConfig(props), seed=3, **geometry
    )
    cc = app.cc
    cc.start_up(detection_interval_s=3600)
    # deterministic gate: wait for the manifest replay to ENQUEUE its
    # engines (compiles continue on the warm pool; the request below
    # waits per-program exactly like any warm start)
    cc._boot_prewarm_done.wait(timeout=300)
    prewarm_wait_s = time.monotonic() - t0
    res = cc.proposals(OperationProgress(), ignore_cache=True)
    wall = time.monotonic() - t0
    report = compilation_cache.boot_report() or {}
    store = cc.core.prewarm_store
    manifest_buckets = []
    if store is not None:
        # flush background AOT exports so the NEXT phase finds artifacts
        # (after the measurement — exports are never on the serving path)
        store.drain(300)
        manifest_buckets = sorted(set(store.manifest_bucket_keys()))
    from cruise_control_tpu.analyzer.prewarm import bucket_key

    _emit(
        metric="coldstart_phase",
        phase=phase,
        value=round(wall, 3),
        unit="s",
        cold_start_to_first_proposal_s=round(wall, 3),
        boot_prewarm_wait_s=round(prewarm_wait_s, 3),
        served_bucket=bucket_key(res.state_before.shape),
        manifest_buckets=manifest_buckets,
        engine_traces=report.get("engineTraces", {}),
        xla_entries_at_boot=report.get("entriesAtBoot"),
        xla_new_compiles=report.get("newCompiles"),
        objective_after=res.objective_after,
        num_proposals=len(res.proposals),
        prewarmed_buckets=int(
            cc.sensors.snapshot()
            .get("analyzer.boot-prewarm-buckets", {})
            .get("count", 0)
        ),
    )
    cc.shutdown()
    return 0


def coldstart(smoke_mode: bool) -> int:
    """`bench.py --coldstart [--smoke]`: the restart SLO gate.

    Spawns a CHILD PROCESS per phase against one shared on-disk
    cache/manifest directory — process boundaries are the only honest way
    to measure cold starts (jit caches, tracing, and module imports are
    all per-process):

      1. cold         — empty disk: full trace + XLA compile bill
                        (manifest + AOT artifacts are WRITTEN here, off
                        the serving path);
      2. xla-cache    — PR 9's slice: compile skipped, tracing paid,
                        nothing prewarmed until the request asks;
      3. manifest-aot — this PR: boot prewarm replays the manifest and
                        deserializes the fused program, so the request
                        hits a compiling-or-compiled engine with ZERO
                        fresh traces for manifest buckets.

    Gates (--smoke, wired into scripts/check.sh): the manifest-aot phase
    reports zero fresh traces for every manifest-listed bucket, its
    cold-start-to-first-proposal wall is strictly below the truly-cold
    phase, and all three phases produce the identical objective (the AOT
    path must not change results).  Headline mode reports the three walls
    without the CPU-noise-sensitive wall gate.
    """
    import subprocess
    import tempfile

    tmp = tempfile.mkdtemp(prefix="cc-coldstart-")
    cache_dir = os.path.join(tmp, "xla")
    manifest_dir = os.path.join(tmp, "prewarm")
    phases = ("cold", "xla-cache", "manifest-aot")
    out: dict[str, dict] = {}
    try:
        for phase in phases:
            env = dict(os.environ)
            env.update(
                COLDSTART_PHASE=phase,
                COLDSTART_CACHE_DIR=cache_dir,
                COLDSTART_MANIFEST_DIR=manifest_dir,
            )
            if smoke_mode:
                env.update(COLDSTART_SMOKE="1", JAX_PLATFORMS="cpu")
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--coldstart-child"],
                env=env, capture_output=True, text=True, timeout=1800,
            )
            line = next(
                (ln for ln in reversed(proc.stdout.splitlines())
                 if ln.startswith("{")),
                None,
            )
            if proc.returncode != 0 or line is None:
                print(f"coldstart phase {phase} failed (rc={proc.returncode}):\n"
                      f"{proc.stderr[-4000:]}", file=sys.stderr)
                _emit(metric="coldstart_to_first_proposal", value=-1.0,
                      unit="s", vs_baseline=-1.0, failed_phase=phase, ok=False)
                return 1
            out[phase] = json.loads(line)
    finally:
        import shutil

        shutil.rmtree(tmp, ignore_errors=True)

    aot = out["manifest-aot"]
    cold = out["cold"]
    # zero fresh traces for every manifest-listed bucket on the AOT phase
    traces = aot["engine_traces"]
    fresh_by_bucket = {
        b: traces.get(b, {}).get("fresh", 0) for b in aot["manifest_buckets"]
    }
    traces_ok = bool(aot["manifest_buckets"]) and all(
        v == 0 for v in fresh_by_bucket.values()
    )
    aot_loads = sum(
        traces.get(b, {}).get("aot", 0) for b in aot["manifest_buckets"]
    )
    wall_ok = aot["cold_start_to_first_proposal_s"] < cold[
        "cold_start_to_first_proposal_s"
    ]
    # vs the xla-cache phase: reported, not gated — the acceptance gate
    # is vs truly-cold (at smoke scale the two warm phases sit within
    # CPU-scheduler noise of each other; the trace-skip proof is the
    # zero-fresh-traces count, which cannot be noise)
    wall_below_xla = aot["cold_start_to_first_proposal_s"] < out["xla-cache"][
        "cold_start_to_first_proposal_s"
    ]
    obj_ok = (
        out["cold"]["objective_after"]
        == out["xla-cache"]["objective_after"]
        == aot["objective_after"]
    )
    prewarm_ok = aot["prewarmed_buckets"] >= 1
    ok = traces_ok and obj_ok and prewarm_ok and (wall_ok or not smoke_mode)
    _emit(
        metric="coldstart_to_first_proposal",
        value=aot["cold_start_to_first_proposal_s"],
        unit="s",
        vs_baseline=round(
            aot["cold_start_to_first_proposal_s"]
            / max(cold["cold_start_to_first_proposal_s"], 1e-9),
            4,
        ),
        cold_start_to_first_proposal_s={
            p: out[p]["cold_start_to_first_proposal_s"] for p in phases
        },
        xla_new_compiles={p: out[p]["xla_new_compiles"] for p in phases},
        manifest_buckets=aot["manifest_buckets"],
        fresh_traces_manifest_buckets=fresh_by_bucket,
        aot_loads_manifest_buckets=aot_loads,
        prewarmed_buckets=aot["prewarmed_buckets"],
        zero_fresh_traces=traces_ok,
        wall_below_cold=wall_ok,
        wall_below_xla_cache=wall_below_xla,
        objective_parity=obj_ok,
        ok=ok,
    )
    return 0 if ok else 1


def main():
    if "--coldstart-child" in sys.argv:
        sys.exit(_coldstart_child())
    if "--coldstart" in sys.argv:
        sys.exit(coldstart("--smoke" in sys.argv))
    if "--streaming" in sys.argv:
        sys.exit(streaming("--smoke" in sys.argv))
    if "--fleet-smoke" in sys.argv:
        sys.exit(fleet_smoke())
    if "--ha-smoke" in sys.argv:
        sys.exit(ha_smoke())
    if "--mesh-smoke" in sys.argv:
        sys.exit(mesh_smoke())
    if "--mesh-chaos" in sys.argv:
        sys.exit(mesh_chaos("--smoke" in sys.argv))
    if "--mesh" in sys.argv:
        sys.exit(mesh("--smoke" in sys.argv))
    if "--trace-overhead" in sys.argv:
        sys.exit(trace_overhead())
    if "--blackbox-overhead" in sys.argv:
        sys.exit(blackbox_overhead())
    if "--ledger-overhead" in sys.argv:
        sys.exit(ledger_overhead())
    if "--scenarios" in sys.argv:
        sys.exit(scenarios_bench("--smoke" in sys.argv))
    if "--churn" in sys.argv:
        sys.exit(churn("--smoke" in sys.argv))
    if "--smoke" in sys.argv:
        sys.exit(smoke())

    from cruise_control_tpu.common.compilation_cache import (
        DEFAULT_CACHE_DIR,
        enable_persistent_cache,
    )
    # shared accelerator liveness gate (also run by __graft_entry__'s
    # dryrun): a wedged backend yields a diagnosable record, not an opaque
    # process-timeout kill
    from cruise_control_tpu.common.device_watchdog import device_watchdog

    device_error = device_watchdog()
    if device_error is not None:
        _emit(
            metric="proposal_wall_clock",
            value=-1.0,
            unit="s",
            vs_baseline=-1.0,
            error=device_error,
        )
        os._exit(1)  # daemon probe thread may be wedged in the runtime

    # persistent XLA cache: repeat bench runs skip the ~70s warm-up compile,
    # making warmup_s the honest time-to-first-proposal of a restarted
    # service with a warm cache
    enable_persistent_cache(os.environ.get("BENCH_COMPILE_CACHE", DEFAULT_CACHE_DIR))
    scale = os.environ.get("BENCH_SCALE", "auto")
    scale_order = [scale] if scale != "auto" else ["north_star", "mid", "small"]
    wanted = set(
        (os.environ.get("BENCH_CONFIGS") or "1,2,3,4,5,6,7").replace(" ", "").split(",")
    )

    for n, fn in (("1", config_1), ("2", config_2), ("3", config_3),
                  ("6", config_6), ("7", config_7)):
        if n in wanted:
            try:
                fn()
            except Exception as e:  # noqa: BLE001 — one config must not sink the rest
                print(f"bench config {n} failed: {e!r}", file=sys.stderr)

    headline = dict(metric="proposal_wall_clock", value=-1.0, unit="s", vs_baseline=-1.0)
    opt = used = None
    if "4" in wanted:
        opt, used, headline = config_4(scale_order)
    if "5" in wanted:
        if opt is None or used is None:
            print(
                "bench config 5 skipped: it reuses config 4's compiled engine — "
                "include 4 in BENCH_CONFIGS",
                file=sys.stderr,
            )
        else:
            try:
                config_5(opt, used)
            except Exception as e:  # noqa: BLE001
                print(f"bench config 5 failed: {e!r}", file=sys.stderr)
    if "4" in wanted:
        _emit(**headline)  # headline LAST: drivers parse the final line


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    main()
