"""`correct` at a tiny size on the CPU: sound runs pass; both controls
fail (the program with its own bfloat16 scoring switched on, and the
reference in bfloat16 put in the program's place); and a run with the
served path broken underneath fails, once per fault each mix can have.
The exchange between chips does not exist in a one-chip cell.  With
every broker alive the reference reads as it did before it knew removed
brokers (readings recorded from that code, data/reference_all_alive.json)."""

import dataclasses
import json
import os

import ml_dtypes
import numpy as np
import pytest

from benchmark import correctness
from benchmark import reference as ref
from benchmark.tests.conftest import tiny

SEED = 2**31 + 777
#: one cell of each mix: fresh proposals, and a removal of brokers (a
#: pending cell, conftest.PENDING_CELLS)
MIXES = ["small50.proposals", "north2600.remove_broker"]


def readings(run, workload="small50.proposals", seed=SEED, control=False, service=None,
             removed=None):
    """Worst compared numbers of a tiny run; `removed` overrides the
    brokers the reference is told the requests removed."""
    _b, _cell, config, traffic = run.resolve(workload)
    config["service"].update(service or {})
    result, dep = run.run_cell(config, traffic, seed, 1.0, False, log=lambda m: None)
    served = run.checked_plans(result, seed, traffic)
    assert served
    if removed is not None:
        result.removed = list(removed)
    dtype = ml_dtypes.bfloat16 if control else None
    worst = correctness.worst(run.compare_plans(result, served, dep, config, control_dtype=dtype))
    return worst, config["limits"]


def correct(worst, limits) -> bool:
    return all(worst[k] <= limits[k] for k in limits)


@pytest.mark.parametrize("workload", MIXES)
def test_sound_run_is_correct(cpu_harness, workload):
    worst, limits = readings(cpu_harness, workload)
    assert correct(worst, limits), worst


@pytest.mark.parametrize("workload", MIXES + ["north2600.proposals"])
def test_program_bfloat16_scoring_is_not_correct(cpu_harness, workload):
    worst, limits = readings(
        cpu_harness, workload, service={"analyzer.precision.score.dtype": "bfloat16"}
    )
    assert worst["objective_gap"] > limits["objective_gap"]


def test_bfloat16_control_is_not_correct(cpu_harness):
    worst, limits = readings(cpu_harness, control=True)
    assert worst["load_gap"] > limits["load_gap"]
    assert worst["violation_gap"] > limits["violation_gap"]


def test_bfloat16_control_of_a_removal_is_not_correct(cpu_harness):
    # the tiny removal plan (4 rounds, conftest.PENDING_CELLS) meets every
    # goal, in bfloat16 too, so only the model's loads tell the control apart
    worst, limits = readings(cpu_harness, "north2600.remove_broker", control=True)
    assert worst["load_gap"] > limits["load_gap"]
    assert not correct(worst, limits)


def test_replica_left_on_removed_broker_is_not_correct(cpu_harness, monkeypatch):
    from cruise_control_tpu.service import facade

    # the removal path forgets to mark the brokers dead: the engine never
    # evacuates them, and reports OfflineReplicaGoal met
    monkeypatch.setattr(facade, "_mark_brokers_dead", lambda state, ids: state)
    worst, limits = readings(cpu_harness, "north2600.remove_broker")
    assert worst["hard_goals_violated"] >= 1
    assert not correct(worst, limits)


def test_reference_blind_to_removal_is_not_correct(cpu_harness):
    # a sound removal plan, judged as if every broker were alive: the
    # program's goals judge the alive brokers alone, so they disagree
    worst, limits = readings(cpu_harness, "north2600.remove_broker", removed=())
    assert worst["violation_gap"] > limits["violation_gap"]


@pytest.mark.parametrize("workload", MIXES)
def test_state_returned_unchanged_is_not_correct(cpu_harness, monkeypatch, workload):
    from cruise_control_tpu.analyzer.engine import Engine

    run_engine = Engine.run

    def unchanged(self, **kw):
        final, history = run_engine(self, **kw)
        # the deployment's initial placement: original brokers, leader first
        return dataclasses.replace(
            final,
            replica_broker=final.replica_orig_broker,
            replica_is_leader=final.replica_valid & (final.replica_pos == 0),
        ), history

    monkeypatch.setattr(Engine, "run", unchanged)
    worst, limits = readings(cpu_harness, workload)
    assert worst["hard_goals_violated"] > limits["hard_goals_violated"]


@pytest.mark.parametrize("workload", MIXES)
def test_half_the_plan_left_out_is_not_correct(cpu_harness, monkeypatch, workload):
    from cruise_control_tpu.analyzer import optimizer

    extract = optimizer.extract_proposals
    monkeypatch.setattr(
        optimizer, "extract_proposals",
        lambda *a, **kw: (lambda ps: list(ps)[: len(ps) // 2])(extract(*a, **kw)),
    )
    worst, limits = readings(cpu_harness, workload)
    assert worst["placement_mismatch"] > limits["placement_mismatch"]


@pytest.mark.parametrize("workload", MIXES)
def test_altered_move_is_not_correct(cpu_harness, monkeypatch, workload):
    from cruise_control_tpu.analyzer import optimizer

    extract = optimizer.extract_proposals

    def altered(*a, **kw):
        ps = list(extract(*a, **kw))
        p = ps[0]
        others = [b for b in range(12) if b not in p.new_replicas]
        new = (p.new_replicas[0], others[0]) + tuple(p.new_replicas[2:])
        ps[0] = dataclasses.replace(p, new_replicas=new)
        return ps

    monkeypatch.setattr(optimizer, "extract_proposals", altered)
    worst, limits = readings(cpu_harness, workload)
    assert worst["placement_mismatch"] > limits["placement_mismatch"]


@pytest.mark.parametrize("workload", MIXES)
def test_altered_model_load_is_not_correct(cpu_harness, monkeypatch, workload):
    from cruise_control_tpu.monitor.load_monitor import LoadMonitor

    follower_loads = LoadMonitor.follower_loads

    def altered(self, loads):
        out = follower_loads(self, loads)
        out[:, 0] *= 1.01  # follower CPU one percent high
        return out

    monkeypatch.setattr(LoadMonitor, "follower_loads", altered)
    worst, limits = readings(cpu_harness, workload)
    assert worst["load_gap"] > limits["load_gap"]


def test_tiny_config_keeps_the_limits():
    from benchmark import run

    _b, _c, config, _t = run.resolve("north2600.proposals")
    assert tiny(config)["limits"] == config["limits"]


@pytest.mark.parametrize("value", [1e-6 * 0.5, 1e-6 * 2])
def test_balancedness_counts_violated_goals(value):
    from benchmark import reference as ref
    from benchmark import run

    _b, _c, config, _t = run.resolve("north2600.proposals")
    sem = ref.Semantics.from_config(config)
    viol = {g: 0.0 for g in sem.goals}
    assert ref.balancedness(viol, sem) == 100.0
    viol[sem.goals[-1]] = value
    got = ref.balancedness(viol, sem)
    assert (got < 100.0) == (value > sem.violated_epsilon)


def recorded_placements(initial: ref.Placement, num_brokers: int) -> dict:
    """The placements data/reference_all_alive.json was recorded on."""
    b = initial.brokers.copy()
    b[::2] = (b[::2] + 1) % num_brokers
    rotated = ref.Placement(b, b[:, 0].copy())
    b = initial.brokers.copy()
    b[:80] = [0, 1, 2]
    piled = ref.Placement(b, initial.brokers[:, 1].copy())
    piled.leader[:80] = 1
    return {"initial": initial, "rotated": rotated, "piled": piled}


@pytest.mark.parametrize("alive", ["default", "given"])
def test_reference_with_every_broker_alive_reads_as_before(alive):
    """Bit for bit, the readings of the reference before it knew removed
    brokers, on placements that violate every goal but OfflineReplicaGoal."""
    from benchmark import run
    from benchmark.deployment import make_deployment

    with open(os.path.join(os.path.dirname(__file__), "data", "reference_all_alive.json")) as f:
        recorded = json.load(f)
    _b, _c, config, _t = run.resolve("north2600.proposals")
    config = tiny(config)
    # thresholds that the piled placement breaks
    config["service"]["max.replicas.per.broker"] = 40
    for r in ("cpu", "network.inbound", "network.outbound", "disk"):
        config["service"][f"{r}.capacity.threshold"] = 0.1
    dep = make_deployment(config, SEED, run.ROOT)
    removed = () if alive == "default" else []
    R = correctness.Reference(dep, config, removed=removed)
    mask = None if alive == "default" else np.ones(dep.num_brokers, bool)
    for name, pl in recorded_placements(R.initial, dep.num_brokers).items():
        want = recorded[name]
        v = ref.goal_violations(
            pl, R.leader, R.follower, dep.capacity, dep.rack_of_broker, dep.part_topic,
            len(dep.topic_names), R.sem, alive=mask,
        )
        s = ref.goal_scores(pl, R.leader, R.follower, dep.capacity, R.sem, alive=mask)
        assert v == want["violations"], name
        assert s == want["scores"], name
        assert R.violations(pl) == v and R.objective(pl, v) == want["objective"], name
        assert ref.balancedness(v, R.sem) == want["balancedness"], name


def test_removed_broker_reads_in_the_reference():
    """Broker 0 removed: its replicas violate OfflineReplicaGoal, and the
    alive brokers alone are held to capacity and balance."""
    from benchmark import run
    from benchmark.deployment import make_deployment

    _b, _c, config, _t = run.resolve("north2600.proposals")
    config = tiny(config)
    dep = make_deployment(config, SEED, run.ROOT)
    everyone = correctness.Reference(dep, config)
    removal = correctness.Reference(dep, config, removed=[0])
    pl = removal.initial
    on_zero = int((pl.brokers == 0).sum())
    assert on_zero > 0
    v = removal.violations(pl)
    assert v["OfflineReplicaGoal"] == on_zero / pl.brokers.size
    assert everyone.violations(pl)["OfflineReplicaGoal"] == 0.0
    # evacuate broker 0 by hand, round robin over the others: offline met,
    # and the empty broker no longer sits under the count and load bands
    b = pl.brokers.copy()
    nxt = 0
    for p in np.nonzero((b == 0).any(1))[0]:
        while (dest := 1 + nxt % (dep.num_brokers - 1)) in b[p]:
            nxt += 1
        b[p, b[p] == 0] = dest
        nxt += 1
    evacuated = ref.Placement(b, b[:, 0].copy())
    assert removal.violations(evacuated)["OfflineReplicaGoal"] == 0.0
    for g in ("ReplicaDistributionGoal", "DiskUsageDistributionGoal"):
        assert everyone.violations(evacuated)[g] > removal.violations(evacuated)[g]
