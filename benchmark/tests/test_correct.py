"""`correct` at a tiny size on the CPU: sound runs pass; both controls
fail (the program with its own bfloat16 scoring switched on, and the
reference in bfloat16 put in the program's place); and a run with the
served path broken underneath fails, once per fault this cell can have.
The exchange between chips does not exist in a one-chip cell."""

import dataclasses

import ml_dtypes
import pytest

from benchmark import correctness
from benchmark.tests.conftest import tiny

SEED = 2**31 + 777


def readings(run, workload="small50.proposals", seed=SEED, control=False, service=None):
    _b, _cell, config, traffic = run.resolve(workload)
    config["service"].update(service or {})
    result, dep = run.run_cell(config, traffic, seed, 1.0, False, log=lambda m: None)
    served = run.checked_plans(result, seed, traffic)
    assert served
    ctl = correctness.Reference(dep, config, dtype=ml_dtypes.bfloat16) if control else None
    worst = correctness.worst(run.compare_plans(served, dep, config, control=ctl))
    return worst, config["limits"]


def correct(worst, limits) -> bool:
    return all(worst[k] <= limits[k] for k in limits)


def test_sound_run_is_correct(cpu_harness):
    worst, limits = readings(cpu_harness)
    assert correct(worst, limits), worst


@pytest.mark.parametrize("workload", ["small50.proposals", "north2600.proposals"])
def test_program_bfloat16_scoring_is_not_correct(cpu_harness, workload):
    worst, limits = readings(
        cpu_harness, workload, service={"analyzer.precision.score.dtype": "bfloat16"}
    )
    assert worst["objective_gap"] > limits["objective_gap"]


def test_bfloat16_control_is_not_correct(cpu_harness):
    worst, limits = readings(cpu_harness, control=True)
    assert worst["load_gap"] > limits["load_gap"]
    assert worst["violation_gap"] > limits["violation_gap"]


def test_state_returned_unchanged_is_not_correct(cpu_harness, monkeypatch):
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
    worst, limits = readings(cpu_harness)
    assert worst["hard_goals_violated"] > limits["hard_goals_violated"]


def test_half_the_plan_left_out_is_not_correct(cpu_harness, monkeypatch):
    from cruise_control_tpu.analyzer import optimizer

    extract = optimizer.extract_proposals
    monkeypatch.setattr(
        optimizer, "extract_proposals",
        lambda *a, **kw: (lambda ps: list(ps)[: len(ps) // 2])(extract(*a, **kw)),
    )
    worst, limits = readings(cpu_harness)
    assert worst["placement_mismatch"] > limits["placement_mismatch"]


def test_altered_move_is_not_correct(cpu_harness, monkeypatch):
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
    worst, limits = readings(cpu_harness)
    assert worst["placement_mismatch"] > limits["placement_mismatch"]


def test_altered_model_load_is_not_correct(cpu_harness, monkeypatch):
    from cruise_control_tpu.monitor.load_monitor import LoadMonitor

    follower_loads = LoadMonitor.follower_loads

    def altered(self, loads):
        out = follower_loads(self, loads)
        out[:, 0] *= 1.01  # follower CPU one percent high
        return out

    monkeypatch.setattr(LoadMonitor, "follower_loads", altered)
    worst, limits = readings(cpu_harness)
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
