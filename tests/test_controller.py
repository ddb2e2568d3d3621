"""Streaming controller tests: cold-prior byte parity, warm-start carry,
move-acceptance prior fitting, WindowedHistory delta extraction (topic
add/remove mid-stream, partial windows), LiveState in-place updates, and
the controller loop's publish/supersede contract."""

import dataclasses as dc

import numpy as np
import pytest

from cruise_control_tpu.analyzer import OptimizerConfig
from cruise_control_tpu.analyzer.engine import Engine, build_statics
from cruise_control_tpu.analyzer.objective import DEFAULT_CHAIN
from cruise_control_tpu.analyzer.options import DEFAULT_OPTIONS
from cruise_control_tpu.config.app_config import CruiseControlConfig
from cruise_control_tpu.controller.prior import MoveAcceptancePrior
from cruise_control_tpu.models.whatif import LiveState
from cruise_control_tpu.monitor.aggregator import WindowedMetricSampleAggregator
from cruise_control_tpu.monitor.delta import (
    extract_window_delta,
    reduce_complete_loads,
)
from cruise_control_tpu.monitor.metricdef import KAFKA_METRIC_DEF
from cruise_control_tpu.monitor.sampling import PartitionEntity
from cruise_control_tpu.testing.fixtures import (
    RandomClusterSpec,
    random_cluster_fast,
)

SMALL = RandomClusterSpec(
    num_brokers=12, num_partitions=200, num_racks=4, num_topics=6, skew=1.0
)
CFG = OptimizerConfig(
    num_candidates=128, leadership_candidates=32, swap_candidates=16,
    steps_per_round=8, num_rounds=3, seed=0,
)


def _placements(state):
    return tuple(
        np.asarray(getattr(state, f))
        for f in ("replica_broker", "replica_is_leader", "replica_disk")
    )


def _same_placement(a, b) -> bool:
    return all(bool((x == y).all()) for x, y in zip(_placements(a), _placements(b)))


# ---------------------------------------------------------------- engine


def test_cold_prior_is_byte_identical_to_uniform_draws():
    """prior_enabled=True with a COLD prior (mix 0) must reproduce the
    pre-prior engine's trajectory bit-for-bit — the controller's parity
    guarantee (the uniform branch consumes the same key with the same
    arithmetic; the prior's extra draws ride fold_in-derived keys)."""
    state = random_cluster_fast(SMALL, seed=3)
    base, _ = Engine(state, DEFAULT_CHAIN, config=CFG).run()
    prior_on, hist = Engine(
        state, DEFAULT_CHAIN, config=dc.replace(CFG, prior_enabled=True)
    ).run()
    assert _same_placement(base, prior_on)
    # and the history (accept counts per round) matches too
    base2, hist2 = Engine(state, DEFAULT_CHAIN, config=CFG).run()
    assert [h.get("accepted") for h in hist] == [h.get("accepted") for h in hist2]


def test_warm_prior_biases_destinations_and_stays_valid():
    """A peaked prior changes the draw stream; the anneal still produces
    a valid, improving placement (feasibility masks do not care where a
    candidate came from)."""
    from cruise_control_tpu.models.state import validate

    state = random_cluster_fast(SMALL, seed=3)

    class Peaked:
        mix = 1.0
        weights = np.zeros((state.shape.num_topics, state.shape.B), np.float32)

    Peaked.weights[:, 0] = 1.0
    eng = Engine(
        state, DEFAULT_CHAIN, config=dc.replace(CFG, prior_enabled=True),
        prior=Peaked,
    )
    final, _ = eng.run()
    base, _ = Engine(state, DEFAULT_CHAIN, config=CFG).run()
    assert not _same_placement(final, base)  # the prior actually steers
    assert validate(final, strict=False) == []
    obj0, _, _ = DEFAULT_CHAIN.evaluate(state)
    obj1, _, _ = DEFAULT_CHAIN.evaluate(final)
    assert float(obj1) <= float(obj0)


def test_prior_rebind_is_data_only():
    """Feeding a refreshed prior through rebind must not recompile: same
    engine object, same shape, new statics."""
    state = random_cluster_fast(SMALL, seed=3)
    eng = Engine(
        state, DEFAULT_CHAIN, config=dc.replace(CFG, prior_enabled=True)
    )
    cold_mix = float(np.asarray(eng.statics.prior_mix))
    assert cold_mix == 0.0

    class P:
        mix = 0.25
        weights = np.ones((state.shape.num_topics, state.shape.B), np.float32)

    eng.rebind(state, prior=P)
    assert float(np.asarray(eng.statics.prior_mix)) == 0.25
    assert eng.statics.prior_dst_cdf.shape == (
        state.shape.num_topics, state.shape.B
    )


def test_prior_disabled_statics_carry_placeholder():
    state = random_cluster_fast(SMALL, seed=3)
    sx = build_statics(state, DEFAULT_OPTIONS)
    assert sx.prior_dst_cdf.shape == (1, 1)
    assert float(np.asarray(sx.prior_mix)) == 0.0


def test_warm_start_carry_fused_and_legacy_agree():
    """init_carry_from threads through both round loops; at a fixed seed
    the two produce identical warm-started trajectories (the fused/legacy
    parity contract extends to warm starts)."""
    state = random_cluster_fast(SMALL, seed=3)
    eng = Engine(state, DEFAULT_CHAIN, config=CFG)
    first, _ = eng.run()
    init = (first.replica_broker, first.replica_is_leader, first.replica_disk)
    fused, _ = eng.run(initial_placement=init)
    legacy_eng = Engine(
        state, DEFAULT_CHAIN, config=dc.replace(CFG, fused_rounds=False)
    )
    legacy, _ = legacy_eng.run(initial_placement=init)
    assert _same_placement(fused, legacy)


def test_warm_start_does_not_corrupt_the_source_placement():
    """The fused run donates its carry; the carry is seeded from the
    caller's placement arrays — they must be COPIED first, or the donated
    run scribbles over the published result's state_after."""
    state = random_cluster_fast(SMALL, seed=3)
    eng = Engine(state, DEFAULT_CHAIN, config=CFG)
    first, _ = eng.run()
    before = _placements(first)
    eng.run(initial_placement=(
        first.replica_broker, first.replica_is_leader, first.replica_disk
    ))
    after = _placements(first)  # re-read: still alive, still identical
    assert all(bool((a == b).all()) for a, b in zip(before, after))


# ----------------------------------------------------------------- prior


def _catalog(topics=("A", "B")):
    from cruise_control_tpu.models.builder import ClusterCatalog

    return ClusterCatalog(
        topics=tuple(topics),
        partitions=tuple((t, i) for t in topics for i in range(2)),
    )


def _proposal(topic_id, old, new):
    from cruise_control_tpu.analyzer.proposals import ExecutionProposal

    return ExecutionProposal(
        partition=0, topic=topic_id, old_leader=old[0], new_leader=new[0],
        old_replicas=tuple(old), new_replicas=tuple(new),
    )


def test_prior_fits_accepted_destinations_and_gates_on_observations():
    cat = _catalog()
    prior = MoveAcceptancePrior(mix=0.5, decay=1.0, min_observations=3)
    table = prior.table(cat, _shape(T=2, B=4))
    assert table.mix == 0.0  # cold
    prior.observe_proposals([_proposal(0, (1, 2), (3, 2))], cat)
    assert prior.table(cat, _shape(T=2, B=4)).mix == 0.0  # still < min
    prior.observe_proposals(
        [_proposal(0, (1, 2), (3, 2)), _proposal(1, (0, 1), (2, 1))], cat
    )
    t = prior.table(cat, _shape(T=2, B=4))
    assert t.mix == 0.5
    assert t.weights[0, 3] == pytest.approx(2.0)  # topic A -> broker 3, twice
    assert t.weights[1, 2] == pytest.approx(1.0)
    assert t.weights[0, 2] == 0.0  # broker already held the replica


def test_prior_decay_fades_and_executed_weighs_more():
    cat = _catalog()
    prior = MoveAcceptancePrior(mix=1.0, decay=0.5, min_observations=0)
    prior.observe_proposals([_proposal(0, (1,), (3,))], cat)
    prior.observe_executed([_proposal(1, (0,), (2,))], cat)
    t = prior.table(cat, _shape(T=2, B=4))
    # the first observation decayed once (0.5); the executed one is x4
    assert t.weights[0, 3] == pytest.approx(0.5)
    assert t.weights[1, 2] == pytest.approx(4.0)


def test_prior_survives_topic_churn():
    """Topics deleted from the catalog contribute nothing; unknown broker
    ids are dropped — stale knowledge can never corrupt a fresh table."""
    prior = MoveAcceptancePrior(mix=1.0, decay=1.0, min_observations=0)
    prior.observe_proposals([_proposal(0, (1,), (3,))], _catalog(("OLD", "B")))
    t = prior.table(_catalog(("NEW", "B")), _shape(T=2, B=4))
    assert t.weights.sum() == 0.0  # OLD is gone; nothing maps


def _shape(T, B):
    from cruise_control_tpu.models.state import ClusterShape

    return ClusterShape(
        num_replicas=8, num_brokers=B, num_partitions=4, num_topics=T,
        num_racks=2, num_hosts=B, max_disks_per_broker=1,
    )


def test_proposal_set_destination_pairs():
    """The columnar extraction must report exactly the brokers RECEIVING
    a replica they did not hold."""
    state = random_cluster_fast(SMALL, seed=3)
    eng = Engine(state, DEFAULT_CHAIN, config=CFG)
    final, _ = eng.run()
    from cruise_control_tpu.analyzer.proposals import extract_proposals

    ps = extract_proposals(state, final)
    tids, dsts = ps.destination_pairs()
    assert len(tids) == len(dsts)
    # cross-check against the materialized objects
    expected = []
    for p in ps:
        old = set(p.old_replicas)
        for b in p.new_replicas:
            if b not in old:
                expected.append((int(p.topic), int(b)))
    assert sorted(zip(tids.tolist(), dsts.tolist())) == sorted(expected)


# ------------------------------------------------------- window delta path


def _agg(num_windows=4, window_ms=1000, min_samples=2):
    return WindowedMetricSampleAggregator(
        num_windows=num_windows, window_ms=window_ms,
        min_samples_per_window=min_samples, metric_def=KAFKA_METRIC_DEF,
    )


def _sample(agg, entity, t_ms, cpu=1.0, nwin=10.0, nwout=5.0, disk=100.0):
    m = agg.metric_def
    vals = np.zeros(m.num_metrics, np.float32)
    vals[m.metric_id("CPU_USAGE")] = cpu
    vals[m.metric_id("LEADER_BYTES_IN")] = nwin
    vals[m.metric_id("LEADER_BYTES_OUT")] = nwout
    vals[m.metric_id("DISK_USAGE")] = disk
    agg.add_sample(entity, t_ms, vals)


def test_delta_partial_window_does_not_read_as_traffic_drop():
    """A half-sampled window holds a partial average; the completeness
    mask must keep it out of the reduction so the entity's loads hold
    steady instead of collapsing."""
    agg = _agg(min_samples=2)
    e = PartitionEntity(0, 0)
    for w in range(3):  # windows 0..2 fully sampled (2 samples each)
        _sample(agg, e, w * 1000 + 100, nwin=10.0)
        _sample(agg, e, w * 1000 + 600, nwin=10.0)
    _sample(agg, e, 3500)  # roll to window 3 (windows 0..2 completed)
    prev = agg.history_snapshot()
    # window 3 gets only ONE sample (partial) before window 4 opens
    _sample(agg, e, 4500)
    cur = agg.history_snapshot()
    delta = extract_window_delta(prev, cur, agg.metric_def)
    assert not delta.requires_reflatten
    red = reduce_complete_loads(cur, agg.metric_def)
    from cruise_control_tpu.common.resources import Resource

    i = cur.entities.index(e)
    # the partial window must NOT have dragged the NW_IN mean below 10
    assert red.loads[i][Resource.NW_IN] == pytest.approx(10.0)
    if delta.entities:  # if reported at all, the loads hold steady
        j = delta.entities.index(e)
        assert delta.loads[j][Resource.NW_IN] == pytest.approx(10.0)


def test_delta_entity_with_no_complete_window_is_stale_not_zero():
    agg = _agg(min_samples=3)
    e = PartitionEntity(0, 0)
    for w in range(3):
        _sample(agg, e, w * 1000 + 100)  # 1 sample/window < min_samples=3
    _sample(agg, e, 3500)
    prev = agg.history_snapshot()
    _sample(agg, e, 4500)
    cur = agg.history_snapshot()
    delta = extract_window_delta(prev, cur, agg.metric_def)
    assert e in delta.stale
    assert e not in delta.entities  # never emitted with fabricated zeros


def test_delta_mid_stream_topic_add_and_remove_force_reflatten():
    agg = _agg(min_samples=1)
    a, b = PartitionEntity(0, 0), PartitionEntity(1, 0)
    _sample(agg, a, 100)
    _sample(agg, a, 1100)
    _sample(agg, a, 2100)
    prev = agg.history_snapshot()
    _sample(agg, b, 3100)  # new topic appears mid-stream
    _sample(agg, a, 3200)
    cur = agg.history_snapshot()
    delta = extract_window_delta(prev, cur, agg.metric_def)
    assert delta.added == (b,)
    assert delta.requires_reflatten
    # removal: diff the other direction (an aggregator never forgets rows,
    # but a restarted one would — the delta contract covers both)
    back = extract_window_delta(cur, prev, agg.metric_def)
    assert back.removed == (b,)
    assert back.requires_reflatten


def test_delta_reports_changed_loads_absolute():
    agg = _agg(min_samples=1)
    e0, e1 = PartitionEntity(0, 0), PartitionEntity(0, 1)
    for w in range(3):
        _sample(agg, e0, w * 1000 + 100, nwin=10.0)
        _sample(agg, e1, w * 1000 + 100, nwin=20.0)
    # window 3 opens with e0's spike — still in progress, so invisible
    # to the prev snapshot
    _sample(agg, e0, 3100, nwin=40.0)
    _sample(agg, e1, 3100, nwin=20.0)
    prev = agg.history_snapshot()
    # rolling to window 4 COMPLETES the spike window
    _sample(agg, e0, 4100, nwin=40.0)
    _sample(agg, e1, 4100, nwin=20.0)
    cur = agg.history_snapshot()
    delta = extract_window_delta(prev, cur, agg.metric_def)
    from cruise_control_tpu.common.resources import Resource

    by_e = dict(zip(delta.entities, zip(delta.loads, delta.changed)))
    l0, c0 = by_e[e0]
    l1, c1 = by_e[e1]
    assert bool(c0) and not bool(c1)
    assert l0[Resource.NW_IN] > 10.0  # absolute new value, not an increment
    assert l1[Resource.NW_IN] == pytest.approx(20.0)


# ------------------------------------------------------------- live state


def test_live_state_scatter_matches_host_update_and_preserves_rest():
    state = random_cluster_fast(SMALL, seed=9)
    live = LiveState(state)
    rows = np.asarray([0, 3, 7], np.int32)
    ll = np.full((3, 4), 42.0, np.float32)
    fl = np.full((3, 4), 21.0, np.float32)
    rb_before = np.asarray(state.replica_broker).copy()
    # host copies BEFORE the update: donation invalidates the old device
    # arrays of the rewritten leaves (the ownership contract)
    ll_before = np.asarray(state.replica_load_leader).copy()
    live.set_partition_loads(rows, ll, fl)
    out = np.asarray(live.state.replica_load_leader)
    assert (out[rows] == 42.0).all()
    fout = np.asarray(live.state.replica_load_follower)
    assert (fout[rows] == 21.0).all()
    # untouched rows and placement arrays unchanged
    untouched = np.setdiff1d(np.arange(state.shape.R), rows)
    assert np.array_equal(out[untouched], ll_before[untouched])
    assert np.array_equal(np.asarray(live.state.replica_broker), rb_before)


def test_live_state_broker_liveness_rederives_offline():
    state = random_cluster_fast(SMALL, seed=9)
    live = LiveState(state)
    alive = np.asarray(state.broker_alive).copy()
    victim = int(np.asarray(state.replica_broker)[0])
    alive[victim] = False
    live.set_broker_liveness(alive)
    st = live.state
    off = np.asarray(st.replica_offline)
    rb = np.asarray(st.replica_broker)
    rv = np.asarray(st.replica_valid)
    assert (off[(rb == victim) & rv]).all()


# ------------------------------------------------------- controller loop


def _controller_service(extra=None, seed=5):
    from cruise_control_tpu.service.main import build_simulated_service

    props = {
        "partition.metrics.window.ms": 1000,
        "min.samples.per.partition.metrics.window": 1,
        "num.partition.metrics.windows": 3,
        "execution.progress.check.interval.ms": 100,
        "webserver.http.port": 0,
        "tpu.num.candidates": 128,
        "tpu.leadership.candidates": 32,
        "tpu.steps.per.round": 16,
        "tpu.num.rounds": 2,
        "controller.enabled": True,
        "controller.prior.min.observations": 8,
    }
    props.update(extra or {})
    return build_simulated_service(CruiseControlConfig(props), seed=seed)


def test_controller_replay_delta_path_and_publish():
    app, fetcher, admin, sampler = _controller_service()
    try:
        cc = app.cc
        ctl = cc.controller
        assert ctl is not None
        parts = sampler.all_partition_entities()
        for w in range(4, 9):
            sampler.drift(1.05)
            fetcher.fetch_once(parts, w * 1000, (w + 1) * 1000 - 1)
            info = ctl.run_once()
            assert info is not None
        stats = ctl.state_json()
        assert stats["fullReflattens"] == 1  # only the initial build
        assert stats["deltaApplies"] == 4
        assert stats["proposalsPublished"] == 5
        assert stats["warmStarts"] == 4
        # the published proposal serves /proposals without a rebuild
        assert cc._valid_cache() is not None
        assert cc._cache.source == "controller"
        st = cc.state()
        assert st["ControllerState"]["windowRolls"] == 5
        assert st["AnalyzerState"]["proposalSource"] == "controller"
        # idempotent tick: no new window -> no cycle
        assert ctl.run_once() is None
    finally:
        app.stop()


def test_controller_delta_bridges_first_seen_vs_catalog_topic_ids():
    """Aggregator entities carry FIRST-SEEN topology topic ids; the
    catalog/state ids are name-rank.  With topics first seen out of name
    order ("zeta" before "alpha"), a spike on zeta must land on ZETA's
    replica rows — not alpha's (the id-space bridge in _reflatten)."""
    from cruise_control_tpu.service.main import build_simulated_service

    props = {
        "partition.metrics.window.ms": 1000,
        "min.samples.per.partition.metrics.window": 1,
        "num.partition.metrics.windows": 3,
        "execution.progress.check.interval.ms": 100,
        "webserver.http.port": 0,
        "tpu.num.candidates": 128, "tpu.leadership.candidates": 32,
        "tpu.steps.per.round": 16, "tpu.num.rounds": 2,
        "controller.enabled": True,
    }
    app, fetcher, admin, sampler = build_simulated_service(
        CruiseControlConfig(props), topics={"zeta": 6, "alpha": 6}, seed=5
    )
    try:
        cc = app.cc
        ctl = cc.controller
        parts = sampler.all_partition_entities()
        fetcher.fetch_once(parts, 4000, 4999)
        assert ctl.run_once() is not None  # initial flatten
        catalog = cc.monitor.last_catalog
        assert catalog.topics == ("alpha", "zeta")  # name-rank space
        zeta_id = catalog.topic_id("zeta")
        st0 = ctl._live.state
        topic = np.asarray(st0.replica_topic)
        valid = np.asarray(st0.replica_valid)
        before = np.asarray(st0.replica_load_leader).copy()
        # spike ONLY zeta's traffic; the spiked window must COMPLETE
        # (roll once more) before the delta path may see it — the
        # completeness mask correctly hides the in-progress window
        sampler.drift(4.0, topic="zeta")
        fetcher.fetch_once(parts, 5000, 5999)
        info = ctl.run_once()
        assert info is not None and not info["reflattened"]
        fetcher.fetch_once(parts, 6000, 6999)
        info = ctl.run_once()
        assert info is not None and not info["reflattened"]
        assert info["delta_partitions"] > 0
        after = np.asarray(ctl._live.state.replica_load_leader)
        from cruise_control_tpu.common.resources import Resource

        zeta_rows = valid & (topic == zeta_id)
        alpha_rows = valid & (topic != zeta_id)
        assert (
            after[zeta_rows, Resource.NW_IN] > before[zeta_rows, Resource.NW_IN]
        ).all()
        # alpha's loads must be untouched by zeta's spike (jitter-free
        # check: alpha did not change at all this window beyond sampler
        # noise — compare against a 2x bound, far below the 4x spike)
        assert (
            after[alpha_rows, Resource.NW_IN]
            < 2.0 * np.maximum(before[alpha_rows, Resource.NW_IN], 1e-9)
        ).all()
    finally:
        app.stop()


def test_controller_cold_mode_matches_direct_optimize():
    """Cold parity: warm start off + delta off + prior mix 0 must equal
    today's flatten-and-anneal pipeline byte-for-byte."""
    app, fetcher, admin, sampler = _controller_service({
        "controller.warm.start.enabled": False,
        "controller.delta.enabled": False,
        "controller.prior.mix": 0.0,
    })
    try:
        cc = app.cc
        ctl = cc.controller
        parts = sampler.all_partition_entities()
        info = None
        for w in range(4, 7):
            sampler.drift(1.05)
            fetcher.fetch_once(parts, w * 1000, (w + 1) * 1000 - 1)
            info = ctl.run_once()
        assert ctl.state_json()["fullReflattens"] == 3
        fresh = cc.monitor.cluster_model()
        direct = cc.optimizer.optimize(fresh, options=cc._build_options(fresh))
        assert _same_placement(info["result"].state_after, direct.state_after)
    finally:
        app.stop()


def test_publish_supersede_keeps_freshest_generation():
    app, fetcher, admin, sampler = _controller_service()
    try:
        cc = app.cc
        ctl = cc.controller
        parts = sampler.all_partition_entities()
        sampler.drift(1.05)
        fetcher.fetch_once(parts, 4000, 4999)
        info = ctl.run_once()
        result = info["result"]
        assert cc._valid_cache() is not None
        gen_at_publish = cc._cache.model_generation
        # a fresher publish for the same generation supersedes the cache
        assert cc.publish_proposal(result) is True
        # simulate the cache holding a FRESHER generation than a late,
        # straggling publish: bump the cached generation stamp
        from cruise_control_tpu.monitor.load_monitor import ModelGeneration

        cc._cache.model_generation = ModelGeneration(
            metadata_generation=gen_at_publish.metadata_generation + 1,
            load_generation=gen_at_publish.load_generation,
        )
        assert cc.publish_proposal(result) is False  # stale publish dropped
    finally:
        app.stop()


def test_controller_survives_unrelated_model_builds():
    """An anomaly-detector round (or any cache-miss request) building a
    model bumps the monitor's load generation; that must neither evict
    the controller's published proposal nor sideline its future
    publishes — only a topology change or expiry invalidates them."""
    app, fetcher, admin, sampler = _controller_service()
    try:
        cc = app.cc
        ctl = cc.controller
        parts = sampler.all_partition_entities()
        sampler.drift(1.05)
        fetcher.fetch_once(parts, 4000, 4999)
        assert ctl.run_once() is not None
        assert cc._valid_cache() is not None
        # simulate a detector round: a model build bumps _load_generation
        cc.monitor.cluster_model()
        assert cc._valid_cache() is not None  # controller result survives
        assert cc._cache.source == "controller"
        # and the NEXT controller publish still lands (not judged stale
        # against the detector-bumped generation)
        sampler.drift(1.05)
        fetcher.fetch_once(parts, 5000, 5999)
        info = ctl.run_once()
        assert info is not None and info["published"]
    finally:
        app.stop()


def test_controller_lifecycle_and_precompute_standdown():
    """start_up starts the controller thread (and does NOT start the
    legacy precompute loop beside it); shutdown joins it."""
    app, fetcher, admin, sampler = _controller_service()
    try:
        cc = app.cc
        cc.start_up(precompute=True)
        assert cc.controller.running
        assert cc._precompute_thread is None
        cc.shutdown()
        assert not cc.controller.running
    finally:
        app.stop()


def test_controller_config_keys_parse_and_gate_construction():
    cfg = CruiseControlConfig({})
    assert cfg.get("controller.enabled") is False
    with pytest.raises(Exception):
        CruiseControlConfig({"controller.prior.mix": 1.5})
    # compile-cache key resolution: preferred name wins
    cfg2 = CruiseControlConfig({
        "tpu.compile.cache.dir": "/tmp/a", "tpu.compilation.cache.dir": "/tmp/b",
    })
    assert cfg2.compile_cache_dir() == "/tmp/a"
    assert CruiseControlConfig({}).compile_cache_dir() is not None  # in-checkout default


# ------------------------------------------------------------ fused cycle


def _replay(ctl, fetcher, sampler, windows, drift=1.05):
    parts = sampler.all_partition_entities()
    infos = []
    for w in windows:
        sampler.drift(drift)
        fetcher.fetch_once(parts, w * 1000, (w + 1) * 1000 - 1)
        info = ctl.run_once()
        assert info is not None
        infos.append(info)
    return infos


def test_fused_cycle_matches_staged_path_byte_for_byte():
    """The tentpole parity pin: the fused delta->re-anneal->extract device
    program must publish BYTE-IDENTICAL placements to the staged
    scatter-then-anneal path, every window — fusion is an execution
    detail, never a numerics change.  Also proves the dispatch contract
    (<= 2 device dispatches per fused steady-state cycle) and that
    controller.fusion.enabled=false pins the staged path (zero fused
    cycles)."""
    runs = {}
    for fusion in (True, False):
        app, fetcher, admin, sampler = _controller_service(
            {"controller.fusion.enabled": fusion}
        )
        try:
            ctl = app.cc.controller
            infos = _replay(ctl, fetcher, sampler, range(4, 9))
            runs[fusion] = (
                [_placements(i["result"].state_after) for i in infos],
                [i for i in infos],
                ctl.state_json(),
            )
        finally:
            app.stop()
    on_p, on_i, on_s = runs[True]
    off_p, off_i, off_s = runs[False]
    assert on_s["fusedCycles"] > 0 and off_s["fusedCycles"] == 0
    assert not on_i[0].get("fused")  # the reflatten cycle never fuses
    for a, b in zip(on_p, off_p):
        for x, y in zip(a, b):
            assert (x == y).all()
    for info in on_i:
        if info.get("fused"):
            # one program dispatch + one host extraction, metered — the
            # O(1) host<->device steady-state contract
            assert sum(info["dispatches"].values()) <= 2
    assert on_s["lastCycleDispatches"] <= 2


def test_cold_cycle_histogram_exclusion_and_one_shot_sensors():
    """The first published cycle (XLA cold compile) and the first fused
    cycle (fused-program compile) stay OUT of the steady-state
    window-roll-to-publish histogram; each reports through its own
    one-shot sensor instead."""
    app, fetcher, admin, sampler = _controller_service()
    try:
        ctl = app.cc.controller
        n = 5
        _replay(ctl, fetcher, sampler, range(4, 4 + n))
        stats = ctl.state_json()
        assert stats["proposalsPublished"] == n
        assert stats["coldCycleSeconds"] is not None
        assert stats["fusedColdCycleSeconds"] is not None
        hist = app.cc.sensors.get("controller.window-roll-to-publish-seconds")
        assert hist is not None and hist.count == n - 2
        assert (
            app.cc.sensors.gauge("controller.cold-compile-cycle-seconds").value
            > 0.0
        )
        assert (
            app.cc.sensors.gauge(
                "controller.fused-cold-compile-cycle-seconds"
            ).value
            > 0.0
        )
    finally:
        app.stop()


def test_reflatten_reason_counters():
    """fullReflattens stays the aggregate; fullReflattensByReason breaks
    it down so a reflatten storm is attributable (topology churn vs
    delta-disabled vs mid-stream entity churn) — and the reasons always
    sum to the aggregate."""
    app, fetcher, admin, sampler = _controller_service(
        {"controller.delta.enabled": False}
    )
    try:
        ctl = app.cc.controller
        _replay(ctl, fetcher, sampler, range(4, 7))
        stats = ctl.state_json()
        assert stats["fullReflattens"] == 3
        assert stats["fullReflattensByReason"] == {
            "initial": 1, "delta-disabled": 2,
        }
        assert sum(stats["fullReflattensByReason"].values()) == stats[
            "fullReflattens"
        ]
    finally:
        app.stop()
    app, fetcher, admin, sampler = _controller_service()
    try:
        ctl = app.cc.controller
        _replay(ctl, fetcher, sampler, range(4, 7))
        assert ctl.state_json()["fullReflattensByReason"] == {"initial": 1}
    finally:
        app.stop()


# ------------------------------------------------------- delta-sized plans


def test_plan_config_quantized_ladder():
    """Delta-sized candidate plans quantize to 1/2, 1/4 or 1/8 of full K
    (never an exact per-delta width — bounded compile count, at most
    three extra engine-cache keys per base config), hold the brownout
    floors, and return the SAME config object at full K so the engine
    cache key is stable."""
    app, fetcher, admin, sampler = _controller_service({
        "tpu.num.candidates": 1024,
        "controller.plan.min.candidates": 64,
        "controller.plan.candidates.per.partition": 4,
    })
    try:
        ctl = app.cc.controller
        cfg = ctl._opt_config
        tiny = ctl._plan_config(cfg, 4)  # needed=64 -> 1/8
        assert tiny.num_candidates == 128
        mid = ctl._plan_config(cfg, 64)  # needed=256 -> 1/4
        assert mid.num_candidates == 256
        # quantized: equal deltas map to EQUAL configs (cache-key stable)
        assert ctl._plan_config(cfg, 4) == tiny
        # a big delta needs full K: the identical object comes back
        assert ctl._plan_config(cfg, 600) is cfg
        # floors mirror brownout_config's
        assert tiny.leadership_candidates >= 8
        assert tiny.swap_candidates >= 0
    finally:
        app.stop()


def test_delta_sized_plans_hold_goal_quality():
    """A delta-sized (1/8-width) steady-state plan must land the same
    goal quality as full-K: equal-or-cleaner violations, objective within
    a few percent — the width was sized to the delta, not starved."""
    runs = {}
    for sizing in (True, False):
        app, fetcher, admin, sampler = _controller_service({
            "tpu.num.candidates": 1024,
            # a realistic round budget: the narrow plan trades width for
            # steps, so it needs the steps the production config has
            # (the 2-round harness default starves it into a residual)
            "tpu.num.rounds": 4,
            "tpu.steps.per.round": 24,
            "controller.plan.min.candidates": 64,
            "controller.plan.candidates.per.partition": 4,
            "controller.plan.sizing.enabled": sizing,
            # staged path only: keeps this test to one engine compile per
            # width (plan sizing is orthogonal to fusion)
            "controller.fusion.enabled": False,
        })
        try:
            ctl = app.cc.controller
            infos = _replay(ctl, fetcher, sampler, range(4, 8))
            runs[sizing] = (infos[-1]["result"], ctl.state_json())
        finally:
            app.stop()
    sized_res, sized_stats = runs[True]
    full_res, full_stats = runs[False]
    assert sized_stats["planSizedCycles"] > 0
    assert full_stats["planSizedCycles"] == 0
    sized_viol = float(np.max(sized_res.violations_after))
    full_viol = float(np.max(full_res.violations_after))
    tol = 1e-6
    assert sized_viol <= max(full_viol, tol)
    assert float(sized_res.objective_after) <= float(
        full_res.objective_after
    ) * 1.05 + tol
