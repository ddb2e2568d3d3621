"""Plain reference of what a served plan must be, in float64 numpy.

It imports nothing of the program.  From the deployment the benchmark
generated (the topology and the metric values it fed) and the goal
semantics the configuration file states, it computes:

* `partition_loads`: each partition's leader and follower load, as the
  monitor must derive them from the samples (average over the complete
  windows for CPU and network, the newest complete window for disk;
  followers carry no bytes out and the follower share of CPU);
* `apply_plan`: the placement a plan leaves when it is applied to the
  initial topology;
* `broker_loads`, `goal_violations`, `balancedness`: per-broker loads, the
  violation of every goal of the chain, and the 0-100 balancedness of a
  placement;
* `goal_scores`, `objective`: each distribution goal's dispersion (the
  coefficient of variation across brokers) and the one scalar the
  optimizer's anneal minimises: the violations weighted by priority, hard
  goals boosted, plus the dispersions as a small tiebreaker.

`dtype` selects the arithmetic: float64 is the reference; the control
runs the same code in bfloat16 (see PERF.md, "How correct is decided").
"""

from __future__ import annotations

import dataclasses
from fractions import Fraction

import numpy as np

CPU, NW_IN, NW_OUT, DISK = range(4)
_CAPACITY_GOALS = {
    "CpuCapacityGoal": CPU,
    "NetworkInboundCapacityGoal": NW_IN,
    "NetworkOutboundCapacityGoal": NW_OUT,
    "DiskCapacityGoal": DISK,
}
_DISTRIBUTION_GOALS = {
    "CpuUsageDistributionGoal": CPU,
    "NetworkInboundUsageDistributionGoal": NW_IN,
    "NetworkOutboundUsageDistributionGoal": NW_OUT,
    "DiskUsageDistributionGoal": DISK,
}
_RESOURCE_KEY = {CPU: "cpu", NW_IN: "network.inbound", NW_OUT: "network.outbound", DISK: "disk"}


@dataclasses.dataclass(frozen=True)
class Placement:
    """Replica sets per partition: brokers [P, RF], leader broker [P]."""

    brokers: np.ndarray
    leader: np.ndarray


@dataclasses.dataclass(frozen=True)
class Semantics:
    """The goal chain and thresholds the configuration states."""

    goals: tuple
    hard: frozenset
    capacity_threshold: tuple  # per resource
    balance_threshold: tuple  # per resource
    replica_count_threshold: float
    leader_count_threshold: float
    topic_replica_threshold: float
    max_replicas_per_broker: int
    follower_cpu_weights: tuple  # leader in, leader out, follower in
    priority_weight: float
    strictness_weight: float
    violated_epsilon: float
    hard_boost: float  # the objective's weight factor on a hard goal
    priority_decay: float  # the objective's weight ratio of adjacent goals
    tie_weight: float  # the dispersions' weight over the smallest goal weight

    @staticmethod
    def from_config(config: dict) -> "Semantics":
        s = config["service"]
        g = config["goals"]
        obj = g["objective"]
        return Semantics(
            goals=tuple(s["default.goals"].split(",")),
            hard=frozenset(g["hard"]),
            capacity_threshold=tuple(
                float(s[f"{_RESOURCE_KEY[r]}.capacity.threshold"]) for r in range(4)
            ),
            balance_threshold=tuple(
                float(s[f"{_RESOURCE_KEY[r]}.balance.threshold"]) for r in range(4)
            ),
            replica_count_threshold=float(s["replica.count.balance.threshold"]),
            leader_count_threshold=float(s["leader.replica.count.balance.threshold"]),
            topic_replica_threshold=float(s["topic.replica.count.balance.threshold"]),
            max_replicas_per_broker=int(s["max.replicas.per.broker"]),
            follower_cpu_weights=(
                float(s["leader.network.inbound.weight.for.cpu.util"]),
                float(s["leader.network.outbound.weight.for.cpu.util"]),
                float(s["follower.network.inbound.weight.for.cpu.util"]),
            ),
            priority_weight=float(s["goal.balancedness.priority.weight"]),
            strictness_weight=float(s["goal.balancedness.strictness.weight"]),
            violated_epsilon=float(g["violated_epsilon"]),
            hard_boost=float(obj["hard_boost"]),
            priority_decay=float(obj["priority_decay"]),
            tie_weight=float(obj["tie_weight"]),
        )


def partition_loads(window_values: np.ndarray, complete: int, sem: Semantics, dtype):
    """(leader [P, 4], follower [P, 4]) loads from the sampled windows."""
    w = window_values[:complete].astype(dtype)
    leader = w.mean(axis=0, dtype=dtype)
    leader[:, DISK] = w[complete - 1, :, DISK]
    w_in, w_out, w_follow = (dtype(x) for x in sem.follower_cpu_weights)
    total = w_in * leader[:, NW_IN] + w_out * leader[:, NW_OUT]
    safe = np.where(total > 0, total, dtype(1))
    follower = leader.copy()
    follower[:, NW_OUT] = 0
    follower[:, CPU] = np.where(
        total > 0, leader[:, CPU] * w_follow * leader[:, NW_IN] / safe, 0
    ).astype(dtype)
    return leader, follower


def initial_placement(replicas: np.ndarray) -> Placement:
    return Placement(brokers=replicas.copy(), leader=replicas[:, 0].copy())


def apply_plan(initial: Placement, plan) -> tuple[Placement, int]:
    """Apply every (partition, old replicas, new replicas) of a plan, leader
    first in both lists.  Returns the placement and the number of rows
    whose old replicas do not match the placement they claim to change."""
    brokers = initial.brokers.copy()
    leader = initial.leader.copy()
    bad = 0
    for p, old, new in plan:
        cur = brokers[p]
        if (
            len(old) != cur.size
            or set(old) != set(cur.tolist())
            or old[0] != leader[p]
            or len(new) != cur.size
            or len(set(new)) != len(new)
        ):
            bad += 1
            continue
        brokers[p] = new
        leader[p] = new[0]
    return Placement(brokers, leader), bad


def placement_mismatch(a: Placement, b: Placement) -> int:
    """Partitions whose replica set or leader differ between a and b."""
    sets_differ = (np.sort(a.brokers, 1) != np.sort(b.brokers, 1)).any(1)
    return int((sets_differ | (a.leader != b.leader)).sum())


def broker_loads(pl: Placement, leader_load, follower_load, num_brokers: int, dtype):
    """[B, 4] load per broker: the leader load where it leads, else the follower's."""
    P, rf = pl.brokers.shape
    is_leader = pl.brokers == pl.leader[:, None]
    per_rep = np.where(is_leader[..., None], leader_load[:, None, :], follower_load[:, None, :])
    out = np.zeros((num_brokers, 4), dtype)
    flat_b = pl.brokers.reshape(-1)
    per_rep = per_rep.reshape(-1, 4)
    for r in range(4):
        out[:, r] = _bincount(flat_b, per_rep[:, r], num_brokers, dtype)
    return out


def _bincount(idx, weights, n, dtype):
    if dtype == np.float64:
        return np.bincount(idx, weights=weights, minlength=n)
    # lower-precision control: accumulate in `dtype` itself
    out = np.zeros(n, dtype)
    np.add.at(out, idx, weights.astype(dtype))
    return out


def _band(values, upper, lower, scale):
    over = np.maximum(values - upper, 0)
    under = np.maximum(lower - values, 0)
    return float((over + under).sum()) / (float(scale) + 1e-12)


def _count_band(counts, threshold: float, n_brokers: int):
    """Cruise Control's count band: ceil(avg * t), floor(avg * (2 - t)), with
    avg * t taken exactly (the thresholds are decimal settings)."""
    t = Fraction(str(threshold))
    lo_t = max(Fraction(0), 2 - t)
    total = int(counts.sum())
    avg = Fraction(total, n_brokers)
    upper = -((-avg * t).numerator // (avg * t).denominator)  # ceil
    lower = (avg * lo_t).numerator // (avg * lo_t).denominator  # floor
    return upper, lower


def goal_violations(
    pl: Placement,
    leader_load,
    follower_load,
    capacity,
    rack_of_broker,
    part_topic,
    num_topics: int,
    sem: Semantics,
    dtype=np.float64,
) -> dict:
    """{goal: violation} for every goal of the chain; every broker is alive.

    Violations are dimensionless: capacity excess over total capacity,
    band excursions over the total of what is balanced, replica counts
    over the number of replicas."""
    B = capacity.shape[0]
    P, rf = pl.brokers.shape
    n_rep = P * rf
    load = broker_loads(pl, leader_load, follower_load, B, dtype)
    cap = capacity.astype(dtype)
    flat_b = pl.brokers.reshape(-1)
    rep_count = np.bincount(flat_b, minlength=B)
    lead_count = np.bincount(pl.leader, minlength=B)
    pot_nw_out = _bincount(flat_b, np.repeat(leader_load[:, NW_OUT], rf), B, dtype)
    lead_bytes_in = _bincount(pl.leader, leader_load[:, NW_IN], B, dtype)
    out = {}
    for g in sem.goals:
        if g == "OfflineReplicaGoal":
            v = 0.0  # no broker or disk is dead in these deployments
        elif g == "RackAwareGoal":
            racks = int(rack_of_broker.max()) + 1
            cell = np.repeat(np.arange(P), rf) * racks + rack_of_broker[flat_b]
            per = np.bincount(cell, minlength=P * racks)
            v = float(np.maximum(per - 1, 0).sum()) / n_rep
        elif g == "ReplicaCapacityGoal":
            v = float(np.maximum(rep_count - sem.max_replicas_per_broker, 0).sum()) / n_rep
        elif g in _CAPACITY_GOALS:
            r = _CAPACITY_GOALS[g]
            excess = np.maximum(load[:, r] - dtype(sem.capacity_threshold[r]) * cap[:, r], 0)
            v = float(excess.sum()) / float(cap[:, r].sum())
        elif g == "PotentialNwOutGoal":
            excess = np.maximum(
                pot_nw_out - dtype(sem.capacity_threshold[NW_OUT]) * cap[:, NW_OUT], 0
            )
            v = float(excess.sum()) / float(cap[:, NW_OUT].sum())
        elif g in _DISTRIBUTION_GOALS:
            r = _DISTRIBUTION_GOALS[g]
            t = sem.balance_threshold[r]
            avg_pct = load[:, r].sum() / cap[:, r].sum()
            upper = avg_pct * dtype(t) * cap[:, r]
            lower = avg_pct * dtype(max(0.0, 2.0 - t)) * cap[:, r]
            v = _band(load[:, r], upper, lower, load[:, r].sum())
        elif g in ("ReplicaDistributionGoal", "LeaderReplicaDistributionGoal"):
            counts = rep_count if g == "ReplicaDistributionGoal" else lead_count
            t = (
                sem.replica_count_threshold
                if g == "ReplicaDistributionGoal"
                else sem.leader_count_threshold
            )
            upper, lower = _count_band(counts, t, B)
            v = _band(counts, upper, lower, counts.sum())
        elif g == "TopicReplicaDistributionGoal":
            topic_of_rep = np.repeat(part_topic, rf)
            tc = np.bincount(topic_of_rep * B + flat_b, minlength=num_topics * B)
            tc = tc.reshape(num_topics, B)
            excursion = 0
            for row in tc:
                upper, lower = _count_band(row, sem.topic_replica_threshold, B)
                excursion += int(np.maximum(row - upper, 0).sum())
                excursion += int(np.maximum(lower - row, 0).sum())
            v = excursion / (float(tc.sum()) + 1e-12)
        elif g == "LeaderBytesInDistributionGoal":
            avg = lead_bytes_in.sum() / B
            upper = avg * dtype(sem.balance_threshold[NW_IN])
            v = _band(lead_bytes_in, upper, 0, lead_bytes_in.sum())
        else:
            raise ValueError(f"no reference for goal {g!r}")
        out[g] = v
    return out


def _cv(values) -> float:
    """Coefficient of variation across brokers: population deviation over mean."""
    v = np.asarray(values, np.float64)
    mean = v.mean()
    return float(np.sqrt(((v - mean) ** 2).mean()) / (mean + 1e-12))


def goal_scores(pl: Placement, leader_load, follower_load, capacity, sem: Semantics,
                dtype=np.float64) -> dict:
    """{goal: dispersion}: the coefficient of variation of what a
    distribution goal balances (utilisation for a resource, replica and
    leader counts, leader bytes in); 0 for every other goal."""
    B = capacity.shape[0]
    load = broker_loads(pl, leader_load, follower_load, B, dtype)
    cap = capacity.astype(np.float64)
    counts = {
        "ReplicaDistributionGoal": np.bincount(pl.brokers.reshape(-1), minlength=B),
        "LeaderReplicaDistributionGoal": np.bincount(pl.leader, minlength=B),
        "LeaderBytesInDistributionGoal": _bincount(pl.leader, leader_load[:, NW_IN], B, dtype),
    }
    out = {}
    for g in sem.goals:
        if g in _DISTRIBUTION_GOALS:
            r = _DISTRIBUTION_GOALS[g]
            out[g] = _cv(load[:, r].astype(np.float64) / (cap[:, r] + 1e-12))
        elif g in counts:
            out[g] = _cv(counts[g])
        else:
            out[g] = 0.0
    return out


def objective(violations: dict, scores: dict, sem: Semantics) -> float:
    """The optimizer's scalar objective: goal i of the chain weighs
    priority_decay^i, times hard_boost for a hard goal; the dispersions add
    tie_weight times the smallest goal weight each."""
    weights = [
        sem.priority_decay ** i * (sem.hard_boost if g in sem.hard else 1.0)
        for i, g in enumerate(sem.goals)
    ]
    weighted = sum(w * violations[g] for w, g in zip(weights, sem.goals))
    return weighted + sem.tie_weight * min(weights) * sum(scores[g] for g in sem.goals)


def balancedness(violations: dict, sem: Semantics) -> float:
    """0-100: one minus the weight of the violated goals over all goals'
    weight; a goal's weight is priority_weight^(goals below it), times
    strictness_weight for a hard goal (Cruise Control's balancedness)."""
    n = len(sem.goals)
    weights = np.array(
        [
            sem.priority_weight ** (n - 1 - i)
            * (sem.strictness_weight if g in sem.hard else 1.0)
            for i, g in enumerate(sem.goals)
        ]
    )
    violated = np.array([violations[g] > sem.violated_epsilon for g in sem.goals])
    return float(100.0 * (1.0 - weights[violated].sum() / weights.sum()))
