"""The anneal's packed per-broker table (`Engine._broker_rows`).

Goal-delta scoring reads every per-broker value of a candidate's source
and destination brokers from one packed row per broker, gathered once per
index vector, instead of one column at a time.  The arithmetic is the
same, so every execution mode reproduces the placements and the per-round
trajectories the column reads gave, byte for byte (the digests below were
recorded from the column-gather engine); and the step program issues a
handful of row gathers where it issued 98 column gathers.
"""

from __future__ import annotations

import collections
import copy
import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jcore

from cruise_control_tpu.analyzer.engine import ANNEAL_PHASES, Engine, OptimizerConfig
from cruise_control_tpu.analyzer.objective import DEFAULT_CHAIN
from cruise_control_tpu.models.builder import BrokerSpec, ClusterModelBuilder, PartitionSpec
from cruise_control_tpu.models.sharding import shard_multiple_shape
from cruise_control_tpu.testing.fixtures import RandomClusterSpec, random_cluster_fast

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = OptimizerConfig(
    num_candidates=192, leadership_candidates=32, swap_candidates=24,
    steps_per_round=8, num_rounds=3, seed=4, diagnostics=True,
)
SPEC = RandomClusterSpec(
    num_brokers=12, num_partitions=180, num_racks=4, num_topics=6, skew=1.5
)


def _digests(final, history) -> tuple[str, str]:
    """(final placement, per-round trajectory) digests of one anneal."""
    place = hashlib.sha256()
    for a in (final.replica_broker, final.replica_is_leader, final.replica_disk):
        place.update(np.asarray(a).tobytes())
    traj = [
        [
            h["round"], h["accepted"], float(h["temperature"]).hex(),
            float(h["objective"]).hex(), h["goal_violations"],
            h.get("extra", False), h.get("repair", False),
        ]
        for h in history
        if "round" in h
    ]
    return place.hexdigest()[:16], hashlib.sha256(
        json.dumps(traj).encode()
    ).hexdigest()[:16]


def _engine_run(state, cfg=CFG):
    return Engine(state, DEFAULT_CHAIN, config=cfg).run()


def _plain():
    return _engine_run(random_cluster_fast(SPEC, seed=11))


def _multi_host_state(jbod: bool = False):
    """12 brokers, two to a host, loaded near the host capacity threshold
    so the host-granularity capacity terms move.  `jbod`: three logdirs a
    broker, one of them failed on broker 3, and broker 5 dead."""
    rng = np.random.default_rng(17)
    b = ClusterModelBuilder()
    cap = np.array([100.0, 20_000.0, 20_000.0, 500_000.0], np.float32)
    for i in range(12):
        extra = {}
        if jbod:
            extra = dict(
                disk_capacities=[150_000.0, 200_000.0, 150_000.0],
                bad_disks=[1] if i == 3 else None, alive=i != 5,
            )
        b.add_broker(
            BrokerSpec(i, rack=f"r{i % 4}", host=f"h{i // 2}", capacity=cap, **extra)
        )
    weights = np.linspace(3.0, 1.0, 12)
    weights /= weights.sum()
    for p in range(150):
        brokers = rng.choice(12, size=3, replace=False, p=weights)
        load = np.array([3.2, 500.0, 700.0, 9000.0], np.float32) * rng.lognormal(
            0.0, 0.4
        )
        b.add_partition(
            PartitionSpec(f"T{p % 5}", p // 5, [int(x) for x in brokers], load.astype(np.float32))
        )
    return b.build()


def _multi_broker_hosts():
    return _engine_run(_multi_host_state())


def _jbod():
    return _engine_run(random_cluster_fast(dataclasses.replace(SPEC, disks_per_broker=3), seed=5))


def _jbod_intra_broker():
    return _engine_run(
        random_cluster_fast(dataclasses.replace(SPEC, disks_per_broker=3), seed=5),
        dataclasses.replace(CFG, intra_broker=True),
    )


def _swap_heavy():
    return _engine_run(
        random_cluster_fast(SPEC, seed=23),
        dataclasses.replace(CFG, swap_candidates=80),
    )


def _mesh(n):
    from cruise_control_tpu.parallel.mesh import MeshEngine, model_mesh

    return MeshEngine(
        random_cluster_fast(SPEC, seed=11), DEFAULT_CHAIN,
        mesh=model_mesh(jax.devices()[:n]), config=CFG,
    ).run()


def _model_sharded():
    from cruise_control_tpu.models.builder import pad_state
    from cruise_control_tpu.parallel.mesh import MeshEngine, grid_mesh

    state = random_cluster_fast(SPEC, seed=11)
    state = pad_state(state, shard_multiple_shape(state.shape, 4))
    return MeshEngine(
        state, DEFAULT_CHAIN, mesh=grid_mesh(1, 4), config=CFG,
        model_shard_min_partitions=1,
    ).run()


def _removal_repair():
    """north2600's deployment cut to 12 brokers, broker 0 removed, and one
    partition planted on two brokers of one rack: the removal plan needs
    one repair round (the service path of tests/test_hard_repair.py)."""
    from benchmark import run
    from benchmark.deployment import make_deployment
    from cruise_control_tpu.service.progress import OperationProgress

    config = copy.deepcopy(run.load_json("benchmark/configs/north2600.json"))
    config["cluster"] = {
        "brokers": 12, "racks": 4, "topics": 4, "partitions_per_topic": 30,
        "replication_factor": 3,
    }
    config["service"].update({
        "tpu.num.candidates": 128, "tpu.leadership.candidates": 32,
        "tpu.steps.per.round": 16, "tpu.num.rounds": 2,
    })
    dep = make_deployment(config, 2**31 + 12345, ROOT)
    replicas = dep.replicas.copy()
    replicas[0] = [1, 5, 2]  # brokers 1 and 5 share rack 1
    dep = dataclasses.replace(dep, replicas=replicas)
    removed = run.removed_brokers(
        run.load_json("benchmark/traffic/remove_broker.json"), dep.num_brokers
    )
    app, _split = run.build(config, dep)
    cc = app.cc
    got = {}
    optimize = cc.optimizer.optimize

    def recorded(*a, **kw):
        got["result"] = optimize(*a, **kw)
        return got["result"]

    cc.optimizer.optimize = recorded
    try:
        cc.remove_brokers(OperationProgress(), removed, dryrun=True)
    finally:
        cc.shutdown()
    res = got["result"]
    timing = next(h for h in res.history if h.get("timing"))
    assert timing["repair_rounds"] == 1
    return res.state_after, res.history


#: case -> (placement digest, trajectory digest), recorded from the engine
#: that gathered each per-broker column separately
CASES = {
    "plain_fused": (_plain, ("cc2390dd6abec1f3", "a59fcffa8bddb893")),
    "removal_repair": (_removal_repair, ("9976c10e8a364088", "1ababd005a0120c1")),
    "multi_broker_hosts": (_multi_broker_hosts, ("577b39ea4241db66", "92efd568828df3b4")),
    "jbod": (_jbod, ("54c1b53cd4c2a882", "04c89780526d7d12")),
    "jbod_intra_broker": (_jbod_intra_broker, ("077661e5a3d56643", "0d2331f8dfc40e13")),
    "swap_heavy": (_swap_heavy, ("79ebd2eaa7146a77", "fb4191ac4747f05d")),
    "mesh_1": (lambda: _mesh(1), ("cc2390dd6abec1f3", "a59fcffa8bddb893")),
    "mesh_4": (lambda: _mesh(4), ("cc2390dd6abec1f3", "a59fcffa8bddb893")),
    "model_sharded": (_model_sharded, ("9568ff13fbd3963e", "4e5132cdb1956fd6")),
}


@pytest.mark.parametrize("case", list(CASES))
def test_rows_reproduce_column_reads(case):
    run, want = CASES[case]
    final, history = run()
    assert _digests(final, history) == want


# --------------------------------------------------------------- structure


@pytest.mark.parametrize("jbod", [False, True], ids=["one_logdir", "jbod"])
def test_table_reads_back_every_column(jbod):
    """Every field of the packed table, read off all brokers, is the value
    it packs: the carry's aggregates and the model's broker columns, with
    host capacity and the multi-broker flag worked out here from scratch."""
    state = _multi_host_state(jbod)
    eng = Engine(state, DEFAULT_CHAIN, config=CFG)
    carry = eng.init_carry(jax.random.PRNGKey(0))
    rows = {k: np.asarray(v) for k, v in eng._all_rows(eng._globals(eng.statics, carry)).items()}
    st = jax.tree.map(np.asarray, state)
    c = jax.tree.map(np.asarray, carry)
    alive = st.broker_valid & st.broker_alive
    same_host = st.broker_host[:, None] == st.broker_host[None, :]
    want = {
        "load": c.broker_load, "pot": c.broker_potential_nw_out,
        "lbin": c.broker_leader_bytes_in, "host_load": c.host_load[st.broker_host],
        "disk_load": c.disk_load, "cap": st.broker_capacity,
        "host_cap": (same_host & alive[None, :]).astype(np.float32) @ st.broker_capacity,
        "disk_cap": st.disk_capacity, "rcount": c.broker_replica_count,
        "lcount": c.broker_leader_count, "alive": alive, "broker_alive": st.broker_alive,
        "host": st.broker_host, "rack": st.broker_rack,
        "host_multi": (same_host & st.broker_valid[None, :]).sum(1) > 1,
        "dest_ok": alive, "lead_ok": alive, "disk_alive": st.disk_alive,
    }
    assert set(rows) == set(want)
    assert rows["disk_alive"].shape[1] == (3 if jbod else 1)
    assert rows["host_multi"].all() and (~rows["alive"]).sum() == (1 if jbod else 0)
    for name, value in want.items():
        np.testing.assert_array_equal(rows[name], value, err_msg=name)
        assert rows[name].dtype == value.dtype, name


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for x in v if isinstance(v, (list, tuple)) else [v]:
            if isinstance(x, jcore.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jcore.Jaxpr):
                yield x


def _gathers_by_phase(jaxpr, outer=()):
    """Counter of (innermost phase, operand shape) over the gather
    equations of a jaxpr; a sub-jaxpr inherits its equation's scopes."""
    out = collections.Counter()
    for eqn in jaxpr.eqns:
        stack = outer + tuple(p for p in str(eqn.source_info.name_stack).split("/") if p)
        phases = [p for p in stack if p in ANNEAL_PHASES]
        if eqn.primitive.name == "gather":
            out[(phases[-1] if phases else None, eqn.invars[0].aval.shape)] += 1
        for sub in _sub_jaxprs(eqn):
            out += _gathers_by_phase(sub, stack)
    return out


def test_step_gathers_broker_rows_not_columns():
    """One `_step` at north2600's search widths: the per-broker tables
    (leading axis B, or the host axis) are gathered at most 12 times under
    `anneal_score`, where the column reads gathered them 98 times."""
    state = random_cluster_fast(
        RandomClusterSpec(num_brokers=13, num_partitions=200, num_racks=4, num_topics=6),
        seed=3,
    )
    eng = Engine(
        state, DEFAULT_CHAIN,
        config=OptimizerConfig(num_candidates=16384, leadership_candidates=4096),
    )
    sh = eng.shape
    assert (eng.K_r, eng.K_s, eng.K_l) == (11776, 512, 4096)
    # the broker and host axes must not coincide with any other table's
    assert len({sh.B, sh.num_topics, sh.P, sh.R}) == 4
    sx_av = eng.statics_avals()
    carry_av = jax.eval_shape(
        eng._init_impl, sx_av, jax.ShapeDtypeStruct((2,), jnp.uint32)
    )
    plan_av = jax.eval_shape(eng._plan_impl, sx_av, carry_av)
    jaxpr = jax.make_jaxpr(eng._step)(sx_av, carry_av, jnp.float32(1.0), plan_av)
    counts = _gathers_by_phase(jaxpr.jaxpr)
    per_broker = sum(
        n for (phase, shape), n in counts.items()
        if phase == "anneal_score" and shape and shape[0] in (sh.B, sh.num_hosts)
    )
    assert per_broker <= 12, counts
