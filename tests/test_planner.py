"""Scenario planner tests: what-if edits, batched evaluation, forecasting,
rightsizing, and the /simulate + /rightsize REST surface.

The headline pins (acceptance criteria of the planner subsystem):
  * identity-scenario parity — applying `Scenario()` produces BYTE-identical
    engine trajectories to the unmutated state (the pinning style of
    tests/test_bucketing.py)
  * a scenario batch of one planned shape reuses ONE compiled engine for
    the optimize pass (asserted via the analyzer.engine-cache-* counters)
  * POST /simulate with a 3-scenario batch and GET /rightsize return
    correct, schema-conforming results over the simulated service
"""

import dataclasses
import json
import time
import urllib.request

import numpy as np
import pytest

from cruise_control_tpu.analyzer import (
    DEFAULT_CHAIN,
    GoalChain,
    GoalOptimizer,
    OptimizerConfig,
    ScenarioEvaluator,
)
from cruise_control_tpu.common.sensors import SensorRegistry
from cruise_control_tpu.models.builder import (
    BrokerSpec,
    ClusterModelBuilder,
    PartitionSpec,
)
from cruise_control_tpu.models.state import ShapeBucketPolicy, validate
from cruise_control_tpu.planner import (
    BrokerAdd,
    LoadForecaster,
    Rightsizer,
    Scenario,
    apply_scenario,
    plan_shape,
)

FAST = OptimizerConfig(
    num_candidates=128, leadership_candidates=32, swap_candidates=16,
    steps_per_round=8, num_rounds=2, max_extra_rounds=2, seed=3,
)

POLICY = ShapeBucketPolicy(growth=1.25, floor=8)

_COMPACT_CHAIN = GoalChain.from_names([
    "OfflineReplicaGoal", "RackAwareGoal", "ReplicaCapacityGoal",
    "DiskCapacityGoal", "ReplicaDistributionGoal",
    "LeaderReplicaDistributionGoal", "NetworkInboundUsageDistributionGoal",
])


def _catalogued_cluster():
    """small_cluster topology rebuilt so the catalog is kept (rack/topic
    names resolve through it)."""
    b = ClusterModelBuilder()
    cap = np.array([100.0, 1000.0, 1000.0, 10000.0], np.float32)
    for i in range(3):
        b.add_broker(BrokerSpec(i, rack=f"r{i}", capacity=cap))
    loads = {
        ("T1", 0): [18.0, 90.0, 100.0, 750.0],
        ("T1", 1): [15.0, 80.0, 90.0, 650.0],
        ("T2", 0): [12.0, 70.0, 80.0, 550.0],
        ("T2", 1): [10.0, 60.0, 70.0, 450.0],
    }
    b.add_partition(PartitionSpec("T1", 0, [0, 1], np.array(loads[("T1", 0)], np.float32)))
    b.add_partition(PartitionSpec("T1", 1, [0, 1], np.array(loads[("T1", 1)], np.float32)))
    b.add_partition(PartitionSpec("T2", 0, [0, 2], np.array(loads[("T2", 0)], np.float32)))
    b.add_partition(PartitionSpec("T2", 1, [0, 1], np.array(loads[("T2", 1)], np.float32)))
    return b.build(), b.catalog


# ----------------------------------------------------------------------
# scenario spec: JSON round trip + validation
# ----------------------------------------------------------------------


def test_scenario_json_round_trip():
    sc = Scenario(
        name="storm",
        add_brokers=(BrokerAdd(count=2, rack="r1", capacity=(100.0, 1e3, 1e3, 1e4)),),
        remove_brokers=(0,),
        demote_brokers=(1,),
        kill_racks=("r2",),
        topic_load_factors={"T1": 2.0, "T2": (1.0, 2.0, 2.0, 1.5)},
        load_factor=1.1,
        load_delta=(0.0, 5.0, 5.0, 10.0),
    )
    rt = Scenario.from_json(sc.to_json())
    assert rt.to_json() == sc.to_json()
    assert rt.brokers_added == 2 and not rt.is_identity
    assert Scenario().is_identity
    assert Scenario.from_json({"name": "x"}).is_identity


def test_scenario_unknown_fields_rejected():
    with pytest.raises(ValueError, match="unknown scenario fields"):
        Scenario.from_json({"removeBrokres": [1]})


# ----------------------------------------------------------------------
# identity parity: byte-identical trajectories (tests/test_bucketing.py style)
# ----------------------------------------------------------------------


def _proposal_keys(proposals):
    return sorted(
        (p.partition, p.topic, p.old_leader, p.new_leader,
         p.old_replicas, p.new_replicas, p.disk_moves)
        for p in proposals
    )


def test_identity_scenario_byte_parity():
    """apply_scenario(state, Scenario()) must be invisible: every array
    byte-identical, every engine trajectory byte-identical."""
    state, catalog = _catalogued_cluster()
    ident = apply_scenario(state, Scenario(), catalog)
    assert ident.shape == state.shape
    for f in dataclasses.fields(type(state)):
        if f.name == "shape":
            continue
        a, b = np.asarray(getattr(state, f.name)), np.asarray(getattr(ident, f.name))
        assert np.array_equal(a, b) and a.dtype == b.dtype, f.name

    r1 = GoalOptimizer(chain=DEFAULT_CHAIN, config=FAST).optimize(state)
    r2 = GoalOptimizer(chain=DEFAULT_CHAIN, config=FAST).optimize(ident)
    assert r1.objective_after == r2.objective_after
    assert np.array_equal(r1.violations_after, r2.violations_after)
    assert np.array_equal(
        np.asarray(r1.state_after.replica_broker),
        np.asarray(r2.state_after.replica_broker),
    )
    assert np.array_equal(
        np.asarray(r1.state_after.replica_is_leader),
        np.asarray(r2.state_after.replica_is_leader),
    )
    assert _proposal_keys(r1.proposals) == _proposal_keys(r2.proposals)


def test_identity_parity_survives_shape_planning():
    """Even when the batch shape pads the base (a sibling scenario adds
    brokers), the identity member must score exactly like the padded base."""
    state, catalog = _catalogued_cluster()
    scenarios = [Scenario(name="id"), Scenario(name="add", add_brokers=(BrokerAdd(6),))]
    shape = plan_shape(state, scenarios, bucket=POLICY)
    assert shape.num_brokers > state.shape.num_brokers
    from cruise_control_tpu.models.builder import pad_state

    padded = pad_state(state, shape)
    ident = apply_scenario(padded, scenarios[0], catalog, shape=shape)
    for f in dataclasses.fields(type(padded)):
        if f.name == "shape":
            continue
        assert np.array_equal(
            np.asarray(getattr(padded, f.name)), np.asarray(getattr(ident, f.name))
        ), f.name


# ----------------------------------------------------------------------
# topology scenarios: dead rack, broker add, demote
# ----------------------------------------------------------------------


def test_dead_rack_scenario_marks_offline_and_fix_evacuates():
    state, catalog = _catalogued_cluster()
    sc = Scenario(name="lose-r0", kill_racks=("r0",))
    mutated = apply_scenario(state, sc, catalog)
    assert validate(mutated) == []
    alive = np.asarray(mutated.broker_alive) & np.asarray(mutated.broker_valid)
    assert not alive[0] and alive[1] and alive[2]  # broker 0 is rack r0
    offline = np.asarray(mutated.replica_offline) & np.asarray(mutated.replica_valid)
    on_b0 = np.asarray(mutated.replica_broker) == 0
    valid = np.asarray(mutated.replica_valid)
    assert (offline[valid & on_b0]).all()  # every replica on the dead broker

    # the anneal must evacuate the dead broker entirely
    opt = GoalOptimizer(chain=_COMPACT_CHAIN, config=FAST)
    res = opt.optimize(mutated)
    after_brokers = np.asarray(res.state_after.replica_broker)[
        np.asarray(res.state_after.replica_valid)
    ]
    assert 0 not in after_brokers
    assert res.num_inter_broker_moves > 0


def test_broker_add_scenario_activates_padding_rows():
    state, catalog = _catalogued_cluster()
    sc = Scenario(name="add2", add_brokers=(BrokerAdd(count=2),))
    mutated = apply_scenario(state, sc, catalog, bucket=POLICY)
    assert validate(mutated) == []
    bv = np.asarray(mutated.broker_valid)
    alive = np.asarray(mutated.broker_alive)
    new = np.asarray(mutated.broker_new)
    assert int(bv.sum()) == 5 and int((bv & alive).sum()) == 5
    assert int(new[bv].sum()) == 2  # the added brokers are NEW brokers
    # median capacity profile cloned onto the added rows
    caps = np.asarray(mutated.broker_capacity)
    for b in np.nonzero(new & bv)[0]:
        assert np.allclose(caps[b], [100.0, 1000.0, 1000.0, 10000.0])
    # rack round-robin keeps added brokers on existing rack ids
    assert np.asarray(mutated.broker_rack)[bv].max() < mutated.shape.num_racks


def test_add_more_brokers_than_padding_raises_without_plan():
    state, catalog = _catalogued_cluster()
    sc = Scenario(name="add99", add_brokers=(BrokerAdd(count=99),))
    # planned shape accommodates...
    mutated = apply_scenario(state, sc, catalog, bucket=POLICY)
    assert int(np.asarray(mutated.broker_valid).sum()) == 102
    # ...but a deliberately tight shape fails loudly
    with pytest.raises(ValueError, match="no padding broker rows"):
        apply_scenario(state, sc, catalog, shape=state.shape)


def test_demote_scenario_moves_leadership():
    state, catalog = _catalogued_cluster()
    sc = Scenario(name="demote-0", demote_brokers=(0,))
    mutated = apply_scenario(state, sc, catalog)
    assert validate(mutated) == []
    lead = np.asarray(mutated.replica_is_leader) & np.asarray(mutated.replica_valid)
    brokers = np.asarray(mutated.replica_broker)
    assert 0 not in set(brokers[lead])  # no leader left on broker 0


def test_load_scenarios_scale_and_delta():
    state, catalog = _catalogued_cluster()
    doubled = apply_scenario(
        state, Scenario(name="x2", topic_load_factors={"T1": 2.0}), catalog
    )
    t1 = np.asarray(state.replica_topic) == catalog.topic_id("T1")
    valid = np.asarray(state.replica_valid)
    assert np.allclose(
        np.asarray(doubled.replica_load_leader)[t1 & valid],
        2.0 * np.asarray(state.replica_load_leader)[t1 & valid],
    )
    other = valid & ~t1
    assert np.array_equal(
        np.asarray(doubled.replica_load_leader)[other],
        np.asarray(state.replica_load_leader)[other],
    )
    # absolute delta: leader gets all 4; follower only NW_IN + DISK
    delta = apply_scenario(
        state, Scenario(name="d", load_delta=(1.0, 10.0, 20.0, 30.0)), catalog
    )
    dl = np.asarray(delta.replica_load_leader) - np.asarray(state.replica_load_leader)
    df = np.asarray(delta.replica_load_follower) - np.asarray(state.replica_load_follower)
    assert np.allclose(dl[valid], [1.0, 10.0, 20.0, 30.0])
    assert np.allclose(df[valid], [0.0, 10.0, 0.0, 30.0])


# ----------------------------------------------------------------------
# batched evaluation: one program, one engine
# ----------------------------------------------------------------------


def test_batched_matches_sequential_objectives():
    state, catalog = _catalogued_cluster()
    scenarios = [
        Scenario(name="id"),
        Scenario(name="lose-r0", kill_racks=("r0",)),
        Scenario(name="t1x2", topic_load_factors={"T1": 2.0}),
        Scenario(name="add1", add_brokers=(BrokerAdd(1),)),
    ]
    ev = ScenarioEvaluator(chain=_COMPACT_CHAIN)
    shape = plan_shape(state, scenarios, bucket=POLICY)
    from cruise_control_tpu.models.builder import pad_state

    base = pad_state(state, shape) if shape != state.shape else state
    states = [apply_scenario(base, sc, catalog, shape=shape) for sc in scenarios]
    obj, viol, degraded = ev.evaluate_states(states)
    assert not degraded and obj.shape == (4,)
    # sequential twin must agree EXACTLY (the bench gate's contract:
    # batching is an execution detail, never a numerics change)
    for i, s in enumerate(states):
        o, v = ev._single_eval(s)
        assert float(o) == obj[i], (i, float(o), obj[i])
        assert np.array_equal(np.asarray(v, np.float64), viol[i])


def test_objective_f64_resolves_soft_goals_under_a_hard_violation():
    """One hard goal 12.5% violated puts the objective near 625, where the
    f32 ulp (6.1e-5) exceeds the lowest goals' weights: a change in those
    goals vanishes from an f32 sum but not from the host f64 one, which
    otherwise agrees with `evaluate`."""
    chain = DEFAULT_CHAIN
    names = chain.names()
    v = np.zeros(len(names))
    v[names.index("RackAwareGoal")] = 0.125
    v[names.index("DiskUsageDistributionGoal")] = 0.075
    worse = v.copy()
    worse[names.index("LeaderBytesInDistributionGoal")] += 0.1
    scores = np.zeros(len(names))
    w32 = np.asarray(chain.weights, np.float32)
    assert np.float32((w32 * v.astype(np.float32)).sum()) == np.float32(
        (w32 * worse.astype(np.float32)).sum()
    )
    assert chain.objective_f64(worse, scores) > chain.objective_f64(v, scores)

    state, _ = _catalogued_cluster()
    obj, viol, sc = chain.evaluate(state)
    assert np.isclose(chain.objective_f64(viol, sc), float(obj), rtol=1e-6)


def test_evaluate_reuses_one_engine_across_batch():
    """The optimize pass over a scenario batch must compile ONE engine and
    rebind it for every other scenario (analyzer.engine-cache-* counters —
    the planner acceptance criterion)."""
    state, catalog = _catalogued_cluster()
    sensors = SensorRegistry()
    opt = GoalOptimizer(chain=_COMPACT_CHAIN, config=FAST, sensors=sensors)
    ev = ScenarioEvaluator(chain=_COMPACT_CHAIN, optimizer=opt, sensors=sensors)
    scenarios = [
        Scenario(name="id"),
        Scenario(name="lose-r0", kill_racks=("r0",)),
        Scenario(name="add2", add_brokers=(BrokerAdd(2),)),
        Scenario(name="t2x3", topic_load_factors={"T2": 3.0}),
    ]
    outcomes = ev.evaluate(state, scenarios, catalog, optimize=True, bucket=POLICY)
    assert len(outcomes) == 4
    assert all(o.fix is not None for o in outcomes)
    assert opt.engine_cache_misses == 1, "scenario batch recompiled the engine"
    assert opt.engine_cache_hits == len(scenarios) - 1
    snap = sensors.snapshot()
    assert snap["analyzer.engine-cache-misses"]["count"] == 1
    assert snap["analyzer.engine-cache-hits"]["count"] == 3
    assert snap["planner.scenarios-evaluated"]["count"] == 4


def test_evaluate_rejects_oversized_batch():
    state, catalog = _catalogued_cluster()
    ev = ScenarioEvaluator(chain=_COMPACT_CHAIN, max_scenarios=2)
    with pytest.raises(ValueError, match="planner.max.scenarios"):
        ev.evaluate(state, [Scenario(name=str(i)) for i in range(3)], catalog)


def test_degraded_cpu_fallback_matches_device_numbers():
    """A breaker-open supervisor must not change the answers — only the
    route (sequential CPU) and the degraded flag."""
    from cruise_control_tpu.common.device_watchdog import DeviceSupervisor

    state, catalog = _catalogued_cluster()
    scenarios = [Scenario(name="id"), Scenario(name="lose-r0", kill_racks=("r0",))]
    ev_direct = ScenarioEvaluator(chain=_COMPACT_CHAIN)
    direct = ev_direct.evaluate(state, scenarios, catalog, bucket=POLICY)

    sup = DeviceSupervisor(
        op_timeout_s=30.0, breaker_failure_threshold=1, probe_interval_s=3600.0
    )
    sup.breaker.record_failure()  # breaker open: device path forbidden
    assert not sup.available()
    ev_degraded = ScenarioEvaluator(
        chain=_COMPACT_CHAIN, supervisor=sup, sensors=SensorRegistry()
    )
    degraded = ev_degraded.evaluate(state, scenarios, catalog, bucket=POLICY)
    assert all(o.degraded for o in degraded)
    for d, o in zip(degraded, direct):
        assert np.isclose(d.objective, o.objective, rtol=1e-6)
        assert d.violated_goals == o.violated_goals


# ----------------------------------------------------------------------
# forecasting
# ----------------------------------------------------------------------


def _history(n_topics=2, parts_per_topic=3, n_windows=5, slope=10.0):
    """Synthetic WindowedHistory: each topic's per-partition NW_IN grows
    `slope` per window; other resources flat."""
    from cruise_control_tpu.monitor import KAFKA_METRIC_DEF, WindowedMetricSampleAggregator
    from cruise_control_tpu.monitor.sampling import PartitionEntity

    agg = WindowedMetricSampleAggregator(n_windows, 1000, 1, KAFKA_METRIC_DEF)
    ents = [
        PartitionEntity(t, p) for t in range(n_topics) for p in range(parts_per_topic)
    ]
    nwin = KAFKA_METRIC_DEF.metric_id("LEADER_BYTES_IN")
    cpu = KAFKA_METRIC_DEF.metric_id("CPU_USAGE")
    for w in range(n_windows):
        vals = np.zeros((len(ents), KAFKA_METRIC_DEF.num_metrics), np.float32)
        vals[:, nwin] = 100.0 + slope * w
        vals[:, cpu] = 5.0
        agg.add_samples_columnar(ents, w * 1000 + 5, vals)
    # one more sample opens window n_windows so all n_windows complete
    agg.add_samples_columnar(ents, n_windows * 1000 + 5, vals)
    return agg, KAFKA_METRIC_DEF


@pytest.mark.parametrize("method", ["linear", "holt"])
def test_forecaster_fits_growing_trend(method):
    agg, mdef = _history(slope=10.0)
    history = agg.history_snapshot()
    fc = LoadForecaster(method=method, min_windows=3)
    trends = fc.fit(history, mdef, {0: "A", 1: "B"})
    assert sorted(t.topic for t in trends) == ["A", "B"]
    tr = trends[0]
    # per-partition NW_IN at newest window = 100 + 10*(W-1) = 140; topic
    # total = 3 * 140 = 420, growing 30/window
    assert tr.level[1] == pytest.approx(420.0, rel=0.05)
    assert tr.slope[1] == pytest.approx(30.0, rel=0.15)
    # 2 windows out -> (420 + 60) / 420
    sc = fc.scenario_at(trends, horizon_ms=2000, window_ms=1000)
    f = sc.topic_load_factors["A"]
    assert f[1] == pytest.approx(480.0 / 420.0, rel=0.05)
    # flat resources stay ~1.0, zero-load resources exactly 1.0
    assert f[0] == pytest.approx(1.0, abs=0.05)
    assert f[2] == 1.0  # NW_OUT never observed -> no change


def test_forecaster_clamps_runaway_factors():
    agg, mdef = _history(slope=500.0)
    fc = LoadForecaster(method="linear", min_windows=3, max_factor=3.0)
    scs = fc.scenarios(agg.history_snapshot(), mdef, [100_000])
    for f in scs[0].topic_load_factors.values():
        assert max(f) <= 3.0


def test_forecaster_skips_underobserved_topics():
    agg, mdef = _history(n_windows=3)
    fc = LoadForecaster(min_windows=5)
    assert fc.fit(agg.history_snapshot(), mdef) == []


# ----------------------------------------------------------------------
# aggregator history snapshot (satellite)
# ----------------------------------------------------------------------


def test_history_snapshot_windows_and_rolling():
    from cruise_control_tpu.monitor import KAFKA_METRIC_DEF, WindowedMetricSampleAggregator

    agg = WindowedMetricSampleAggregator(3, 1000, 2, KAFKA_METRIC_DEF)
    nwin = KAFKA_METRIC_DEF.metric_id("LEADER_BYTES_IN")

    def sample(e, t, v):
        vals = np.zeros(KAFKA_METRIC_DEF.num_metrics, np.float32)
        vals[nwin] = v
        agg.add_sample(e, t, vals)

    sample("a", 500, 10.0)
    sample("a", 600, 20.0)  # window 0 complete (2 samples), avg 15
    sample("a", 1500, 99.0)  # window 1: 1 sample -> incomplete
    sample("a", 2500, 7.0)  # window 2 opens; windows 0..1 completed
    h = agg.history_snapshot()
    assert list(h.window_indices) == [1, 0]  # newest -> oldest
    assert h.values[0, 1, nwin] == pytest.approx(15.0)  # AVG divided
    assert h.values[0, 0, nwin] == pytest.approx(99.0)
    assert bool(h.complete[0, 1]) and not bool(h.complete[0, 0])
    assert h.sample_counts[0, 1] == 2 and h.sample_counts[0, 0] == 1
    assert h.entities == ("a",)

    # entity growth mid-stream: new entity appears with zero history
    sample("b", 2600, 42.0)
    sample("b", 3500, 1.0)  # roll again
    h2 = agg.history_snapshot()
    assert h2.entities == ("a", "b")
    assert list(h2.window_indices) == [2, 1, 0]
    bi = h2.entities.index("b")
    assert h2.sample_counts[bi, 0] == 1  # only window 2 sampled for b
    assert h2.sample_counts[bi, 1] == 0 and h2.sample_counts[bi, 2] == 0

    # rolling far forward evicts: the snapshot only covers live windows
    sample("a", 10_500, 3.0)
    h3 = agg.history_snapshot()
    assert list(h3.window_indices) == [9, 8, 7]
    assert h3.sample_counts.sum() == 0  # all old cells were recycled

    # snapshot is a copy: mutating it cannot corrupt the ring
    h3.values[:] = -1.0
    assert agg.history_snapshot().values.min() >= 0.0


# ----------------------------------------------------------------------
# rightsizer
# ----------------------------------------------------------------------


def _rightsize_fixture(num_brokers=6, num_parts=12):
    b = ClusterModelBuilder()
    cap = np.array([100.0, 1000.0, 1000.0, 10000.0], np.float32)
    for i in range(num_brokers):
        b.add_broker(BrokerSpec(i, rack=f"r{i % 3}", capacity=cap))
    load = np.array([2.0, 20.0, 25.0, 100.0], np.float32)
    for p in range(num_parts):
        b.add_partition(
            PartitionSpec("T0", p, [p % num_brokers, (p + 1) % num_brokers], load)
        )
    return b.build(), b.catalog


def test_rightsizer_overprovisioned_cluster():
    state, catalog = _rightsize_fixture()
    opt = GoalOptimizer(chain=_COMPACT_CHAIN, config=FAST)
    ev = ScenarioEvaluator(chain=_COMPACT_CHAIN, optimizer=opt, max_scenarios=64)
    rs = Rightsizer(ev, max_broker_factor=1.5)
    out = rs.rightsize(state, catalog)
    assert out["provisionStatus"] == "OVER_PROVISIONED"
    assert out["minBrokers"] is not None and out["minBrokers"] < out["currentBrokers"]
    assert out["minBrokers"] >= 2  # replication-factor floor
    assert not out["undecided"]
    # the boundary is real: min is feasible, min-1 (if annealed) is not
    by_count = {c["brokers"]: c for c in out["candidates"]}
    assert by_count[out["minBrokers"]]["feasible"]
    # the screening curve covers the searched range endpoints
    lo, hi = out["searchedRange"]
    assert str(lo) in map(str, out["preMoveViolations"]) or lo in out["preMoveViolations"]


def test_rightsizer_underprovisioned_under_load():
    """Scaling every topic far past total capacity must demand MORE
    brokers than the cluster has (or prove even the ceiling infeasible)."""
    state, catalog = _rightsize_fixture(num_brokers=4, num_parts=8)
    chain = GoalChain.from_names([
        "OfflineReplicaGoal", "RackAwareGoal", "DiskCapacityGoal",
        "ReplicaDistributionGoal",
    ])
    opt = GoalOptimizer(chain=chain, config=FAST)
    ev = ScenarioEvaluator(chain=chain, optimizer=opt, max_scenarios=64)
    rs = Rightsizer(ev, max_broker_factor=2.0)
    # 8 parts x RF2 x 100 disk x 30 = 48000 total disk over usable 8000
    # per broker (10000 x 0.8 threshold): >= 6 brokers required, 4 exist
    heavy = Scenario(name="x30", load_factor=30.0)
    out = rs.rightsize(state, catalog, load_scenario=heavy)
    assert out["provisionStatus"] in ("UNDER_PROVISIONED", "UNDECIDED")
    if out["minBrokers"] is not None:
        assert out["minBrokers"] > out["currentBrokers"]
    assert out["loadScenario"]["loadFactor"] == 30.0


def test_rightsizer_exhausted_budget_reports_undecided_with_upper_bound():
    """A search whose anneal budget dies mid-bracket must say UNDECIDED
    (minBrokers null) and report the proven feasible count only as an
    UPPER bound — never as 'the minimum' (that could flip an
    over-provisioned cluster's verdict to UNDER_PROVISIONED)."""
    state, catalog = _rightsize_fixture()
    opt = GoalOptimizer(chain=_COMPACT_CHAIN, config=FAST)
    ev = ScenarioEvaluator(chain=_COMPACT_CHAIN, optimizer=opt, max_scenarios=64)
    rs = Rightsizer(ev, max_broker_factor=1.5)
    out = rs.rightsize(state, catalog, max_anneals=1)  # only check(hi) runs
    assert out["undecided"] and out["provisionStatus"] == "UNDECIDED"
    assert out["minBrokers"] is None
    assert out["minBrokersUpperBound"] == out["searchedRange"][1]
    assert out["annealsRun"] == 1


def test_rightsizer_monotone_floor_respects_replication():
    state, catalog = _rightsize_fixture()
    ev = ScenarioEvaluator(chain=_COMPACT_CHAIN, optimizer=GoalOptimizer(
        chain=_COMPACT_CHAIN, config=FAST
    ), max_scenarios=64)
    rs = Rightsizer(ev, min_brokers=1)
    assert rs._floor(state, 6) == 2  # max RF is 2


# ----------------------------------------------------------------------
# REST surface on the simulated service
# ----------------------------------------------------------------------


@pytest.fixture(scope="module")
def planner_service():
    from cruise_control_tpu.service.main import build_simulated_service

    app, fetcher, admin, sampler = build_simulated_service(seed=13)
    app.start()
    yield app
    app.stop()


def _request(app, method, endpoint, headers=None, **params):
    import urllib.parse

    q = urllib.parse.urlencode(params)
    url = f"http://{app.host}:{app.port}{app.prefix}/{endpoint}" + (f"?{q}" if q else "")
    req = urllib.request.Request(url, method=method, headers=headers or {})
    with urllib.request.urlopen(req, timeout=60) as resp:
        return resp.status, json.loads(resp.read()), dict(resp.headers)


def _poll(app, method, endpoint, **params):
    status, payload, headers = _request(app, method, endpoint, **params)
    tid = headers.get("User-Task-ID")
    deadline = time.time() + 90
    while status == 202 and time.time() < deadline:
        time.sleep(0.3)
        status, payload, headers = _request(
            app, method, endpoint, headers={"User-Task-ID": tid}, **params
        )
    return status, payload


def test_simulate_endpoint_three_scenario_batch(planner_service):
    from cruise_control_tpu.service.schemas import validate_response

    app = planner_service
    racks = sorted({
        b["rack"]
        for b in _request(app, "GET", "kafka_cluster_state")[1]["KafkaBrokerState"].values()
    })
    scenarios = [
        {"name": "lose-rack", "killRacks": [racks[0]]},
        {"name": "add-3", "addBrokers": [{"count": 3}]},
        {"name": "double-T0", "topicLoadFactors": {"T0": 2.0}},
    ]
    status, payload = _poll(
        app, "POST", "simulate", scenarios=json.dumps(scenarios), optimize="true"
    )
    assert status == 200
    assert validate_response("simulate", payload) == []
    assert [s["name"] for s in payload["scenarios"]] == [
        "lose-rack", "add-3", "double-T0"
    ]
    by_name = {s["name"]: s for s in payload["scenarios"]}
    base_alive = payload["baseline"]["brokersAlive"]
    assert by_name["add-3"]["brokersAlive"] == base_alive + 3
    assert by_name["lose-rack"]["brokersAlive"] < base_alive
    # losing a rack strands replicas: hard goals violated, fix proposed
    assert not by_name["lose-rack"]["hardGoalsSatisfied"]
    assert "OfflineReplicaGoal" in by_name["lose-rack"]["violatedGoals"]
    assert by_name["lose-rack"]["fix"]["numReplicaMovements"] > 0
    # doubling load keeps broker count, raises the objective vs baseline
    assert by_name["double-T0"]["brokersAlive"] == base_alive
    assert by_name["double-T0"]["objective"] > payload["baseline"]["objective"]
    assert payload["degraded"] is False


def test_simulate_endpoint_rejects_bad_scenarios(planner_service):
    import urllib.error

    app = planner_service
    with pytest.raises(urllib.error.HTTPError) as e:
        _request(app, "POST", "simulate", scenarios="not json")
    assert e.value.code == 400
    with pytest.raises(urllib.error.HTTPError) as e:
        _request(app, "POST", "simulate",
                 scenarios=json.dumps([{"removeBrokres": [0]}]))
    assert e.value.code == 400
    with pytest.raises(urllib.error.HTTPError) as e:
        _request(app, "POST", "simulate")  # missing scenarios
    assert e.value.code == 400


def test_simulate_endpoint_full_batch_accepted_oversize_400(planner_service):
    """A batch of exactly planner.max.scenarios must be accepted (the
    internal baseline rider must not eat one slot); one more is a 400
    client error, not a 500 from inside the async task."""
    import urllib.error

    app = planner_service
    cap = app.cc.config.get("planner.max.scenarios")
    full = [{"name": f"s{i}"} for i in range(cap)]
    status, payload = _poll(
        app, "POST", "simulate", scenarios=json.dumps(full, separators=(",", ":"))
    )
    assert status == 200 and len(payload["scenarios"]) == cap
    with pytest.raises(urllib.error.HTTPError) as e:
        _request(app, "POST", "simulate",
                 scenarios=json.dumps(full + [{"name": "extra"}],
                                      separators=(",", ":")))
    assert e.value.code == 400
    assert "planner.max.scenarios" in json.loads(e.value.read())["errorMessage"]


def test_rightsize_endpoint_rejects_bad_bounds(planner_service):
    import urllib.error

    app = planner_service
    for params in (
        {"horizon_ms": "-5"},
        {"min_brokers": "0"},
        {"max_broker_factor": "0.5"},
    ):
        with pytest.raises(urllib.error.HTTPError) as e:
            _request(app, "GET", "rightsize", **params)
        assert e.value.code == 400


def test_rightsize_endpoint(planner_service):
    from cruise_control_tpu.service.schemas import validate_response

    app = planner_service
    status, payload = _poll(app, "GET", "rightsize")
    assert status == 200
    assert validate_response("rightsize", payload) == []
    assert payload["currentBrokers"] == 6
    assert payload["provisionStatus"] in (
        "RIGHT_SIZED", "OVER_PROVISIONED", "UNDER_PROVISIONED", "UNDECIDED"
    )
    if payload["minBrokers"] is not None:
        lo, hi = payload["searchedRange"]
        assert lo <= payload["minBrokers"] <= hi
    # with a horizon the forecast verdict rides along
    status, payload = _poll(app, "GET", "rightsize", horizon_ms="3600000")
    assert status == 200
    assert "forecast" in payload


def test_planner_sensors_exported(planner_service):
    app = planner_service
    status, payload, _ = _request(app, "GET", "state", substates="sensors")
    snap = payload["Sensors"]
    assert "planner.scenarios-evaluated" in snap
    assert "planner.rightsize-timer" in snap
