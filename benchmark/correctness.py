"""What decides `correct`: a served plan against `benchmark.reference`.

`snapshot` copies, from the program's in-process result of one request,
the model it built, the placement its engine ended on, the whole plan
(the REST answer carries only its first 100 moves) and the goal
violations it reported.  `compare` holds them against the reference
computed from the deployment the benchmark generated:

* `load_gap`: the worst per-broker load of the model, against the loads
  the samples imply, as a share of that resource's mean broker load;
* `placement_mismatch`: partitions where the model's initial placement
  differs from the topology, where the plan applied to the topology
  differs from the engine's final placement, and plan rows whose old
  replicas are not the placement they change (exact: limit 0);
* `violation_gap`: the widest gap between a reported goal violation and
  the reference's violation of the plan-applied placement;
* `objective_gap`: the objective the anneal itself computed on its last
  round, against the reference's objective of the placement the engine
  ended on, as a share of the latter: the one number the anneal's own
  arithmetic produces (the reports above are evaluated apart, in float32);
* `hard_goals_violated`: hard goals the reference finds violated after
  the plan (the deployment's guarantee: limit 0);
* `degraded`: answers served by the CPU fallback, not the chip (limit 0).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from benchmark import reference as ref

#: the numbers compared, in the order they are printed
NUMBERS = (
    "load_gap", "placement_mismatch", "violation_gap", "objective_gap",
    "hard_goals_violated", "degraded",
)


@dataclasses.dataclass
class Served:
    """Host copy of what one request produced."""

    brokers_before: np.ndarray  # [R] per replica row
    leader_before: np.ndarray  # [R] bool
    pid: np.ndarray  # [R] partition id of each replica row
    load_leader: np.ndarray  # [R, 4]
    load_follower: np.ndarray  # [R, 4]
    brokers_after: np.ndarray  # [R]
    leader_after: np.ndarray  # [R] bool
    plan: list  # [(partition id, old replicas, new replicas)], leader first
    violations: dict  # goal -> reported violation after
    objective: float  # the anneal's objective after its last round
    degraded: bool


def snapshot(result) -> dict:
    """Host copy of one OptimizerResult (valid replica rows only), so the
    program's device arrays can be freed; the plan stays columnar."""
    import jax

    sb, sa = result.state_before, result.state_after
    valid, bb, lb, pid, ll, fl = jax.device_get((
        sb.replica_valid, sb.replica_broker, sb.replica_is_leader,
        sb.replica_partition, sb.replica_load_leader, sb.replica_load_follower,
    ))
    ba, la = jax.device_get((sa.replica_broker, sa.replica_is_leader))
    valid = np.asarray(valid, bool)
    rounds = [h for h in result.history if "objective" in h and not h.get("timing")]
    if not rounds:
        raise ValueError(
            "the result carries no per-round objective: the configuration has to "
            "keep analyzer.diagnostics.enabled on"
        )
    return dict(
        brokers_before=np.asarray(bb)[valid],
        leader_before=np.asarray(lb)[valid],
        pid=np.asarray(pid)[valid],
        load_leader=np.asarray(ll, np.float64)[valid],
        load_follower=np.asarray(fl, np.float64)[valid],
        brokers_after=np.asarray(ba)[valid],
        leader_after=np.asarray(la)[valid],
        proposals=result.proposals,
        violations=dict(zip(result.goal_names, map(float, result.violations_after))),
        objective=float(rounds[-1]["objective"]),
        degraded=bool(result.degraded),
    )


def served(snap: dict) -> Served:
    """The snapshot with its whole plan as rows (leader first)."""
    fields = dict(snap)
    fields["plan"] = [
        (p.partition, p.old_replicas, p.new_replicas) for p in fields.pop("proposals")
    ]
    return Served(**fields)


class Reference:
    """The reference side for one deployment, shared by every plan;
    `removed` lists the brokers the requests remove (dead in the reference)."""

    def __init__(self, dep, config: dict, dtype=np.float64, removed=()):
        self.dep = dep
        self.sem = ref.Semantics.from_config(config)
        self.dtype = dtype
        self.alive = np.ones(dep.num_brokers, bool)
        self.alive[list(removed)] = False
        self.leader, self.follower = ref.partition_loads(
            dep.window_values, dep.complete_windows, self.sem, dtype
        )
        self.initial = ref.initial_placement(dep.replicas)
        # partition ids: partitions sorted by (topic name, partition number)
        names = np.asarray(dep.topic_names)[dep.part_topic]
        self.order = np.lexsort((dep.part_num, names))
        self.loads_before = ref.broker_loads(
            self.initial, self.leader, self.follower, dep.num_brokers, dtype
        )

    def violations(self, pl: ref.Placement) -> dict:
        d = self.dep
        return ref.goal_violations(
            pl, self.leader, self.follower, d.capacity, d.rack_of_broker,
            d.part_topic, len(d.topic_names), self.sem, self.dtype, self.alive,
        )

    def objective(self, pl: ref.Placement, violations: dict) -> float:
        d = self.dep
        scores = ref.goal_scores(
            pl, self.leader, self.follower, d.capacity, self.sem, self.dtype, self.alive
        )
        return ref.objective(violations, scores, self.sem)

    def placement(self, brokers, leader, pid) -> ref.Placement:
        """Per-replica rows (partition ids) as a Placement in deployment order."""
        rf = self.dep.replicas.shape[1]
        rows = np.argsort(pid, kind="stable")
        b = brokers[rows].reshape(-1, rf)
        lead_rows = rows.reshape(-1, rf)
        is_lead = leader[lead_rows]
        lead = np.where(is_lead.any(1), b[np.arange(b.shape[0]), is_lead.argmax(1)], -1)
        out_b = np.empty_like(b)
        out_l = np.empty_like(lead)
        out_b[self.order] = b
        out_l[self.order] = lead
        return ref.Placement(out_b, out_l)

    def plan_in_deployment_order(self, plan) -> list:
        return [(int(self.order[p]), tuple(o), tuple(n)) for p, o, n in plan]


def program_broker_loads(s: Served, num_brokers: int) -> np.ndarray:
    loads = np.where(s.leader_before[:, None], s.load_leader, s.load_follower)
    out = np.zeros((num_brokers, 4))
    for r in range(4):
        out[:, r] = np.bincount(s.brokers_before, weights=loads[:, r], minlength=num_brokers)
    return out


def compare(s: Served, reference: Reference, control: Reference | None = None) -> dict:
    """The compared numbers for one plan.  With `control`, the lower-precision
    reference takes the place of the program's loads and violations."""
    dep = reference.dep
    rf = dep.replicas.shape[1]
    if s.pid.size != dep.num_partitions * rf:
        raise ValueError(f"model has {s.pid.size} replicas, the deployment {dep.num_partitions * rf}")
    model0 = reference.placement(s.brokers_before, s.leader_before, s.pid)
    final = reference.placement(s.brokers_after, s.leader_after, s.pid)
    applied, bad_rows = ref.apply_plan(
        reference.initial, reference.plan_in_deployment_order(s.plan)
    )
    ref_viol = reference.violations(applied)
    final_mismatch = ref.placement_mismatch(applied, final)
    ref_obj = reference.objective(
        final, reference.violations(final) if final_mismatch else ref_viol
    )
    if control is None:
        prog_loads = program_broker_loads(s, dep.num_brokers)
        prog_viol = s.violations
        prog_obj = s.objective
    else:
        prog_loads = control.loads_before.astype(np.float64)
        prog_viol = control.violations(applied)
        prog_obj = control.objective(final, control.violations(final))
    mean = reference.loads_before.mean(axis=0)
    load_gap = float(
        (np.abs(prog_loads - reference.loads_before) / np.where(mean > 0, mean, 1)).max()
    )
    return {
        "load_gap": load_gap,
        "placement_mismatch": (
            ref.placement_mismatch(model0, reference.initial)
            + final_mismatch
            + bad_rows
        ),
        "violation_gap": max(abs(prog_viol[g] - ref_viol[g]) for g in reference.sem.goals),
        "objective_gap": abs(prog_obj - ref_obj) / ref_obj,
        "hard_goals_violated": sum(
            ref_viol[g] > reference.sem.violated_epsilon for g in reference.sem.hard
            if g in ref_viol
        ),
        "degraded": int(s.degraded),
        "_balancedness": ref.balancedness(ref_viol, reference.sem),
        "_objective": reference.objective(applied, ref_viol),
        "_moves": len(s.plan),
    }


def worst(readings: list) -> dict:
    """Per number, the worst reading over the plans checked."""
    return {k: max(r[k] for r in readings) for k in NUMBERS}
