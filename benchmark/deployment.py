"""A Kafka deployment made from a configuration file and a seed.

Everything here is plain numpy: the topology (brokers, racks, partitions
and their replica lists) and the per-partition metric values of every
sampled window.  The same seed gives the same deployment.  The program
receives only these inputs, through its simulated backend and the
`DeploymentSampler` below; `benchmark.reference` reads the same arrays.
"""

from __future__ import annotations

import dataclasses
import json
import os

import numpy as np

#: column order of every [.., 4] load array here (the program's Resource order)
RESOURCES = ("CPU", "NW_IN", "NW_OUT", "DISK")


@dataclasses.dataclass(frozen=True)
class Deployment:
    """One generated cluster: topology plus the metric values fed to it."""

    num_brokers: int
    rack_of_broker: np.ndarray  # int64 [B]
    topic_names: tuple  # [T] in creation order
    part_topic: np.ndarray  # int64 [P] index into topic_names
    part_num: np.ndarray  # int64 [P] partition number within its topic
    replicas: np.ndarray  # int64 [P, RF] broker ids, preferred (leader) first
    window_values: np.ndarray  # float32 [W + 1, P, 4] leader-side values per window
    window_ms: int
    complete_windows: int  # W: windows 0..W-1 are complete, window W is current
    capacity: np.ndarray  # float64 [B, 4]

    @property
    def num_partitions(self) -> int:
        return int(self.part_topic.size)


def load_capacity(path: str, num_brokers: int) -> np.ndarray:
    """Per-broker [CPU, NW_IN, NW_OUT, DISK] capacities from a Cruise Control
    capacity file (brokerId -1 is the default for brokers not listed)."""
    with open(path) as f:
        doc = json.load(f)
    rows = {int(e["brokerId"]): e["capacity"] for e in doc["brokerCapacities"]}
    out = np.zeros((num_brokers, 4), np.float64)
    for b in range(num_brokers):
        cap = rows.get(b, rows[-1])
        out[b] = [float(cap[r]) for r in RESOURCES]
    return out


def _distinct_brokers(rng, num_partitions: int, num_brokers: int, rf: int):
    """[P, rf] brokers drawn uniformly, distinct within each row."""
    reps = rng.integers(0, num_brokers, size=(num_partitions, rf))
    for j in range(1, rf):
        while True:
            clash = (reps[:, j : j + 1] == reps[:, :j]).any(1)
            if not clash.any():
                break
            reps[clash, j] = rng.integers(0, num_brokers, size=int(clash.sum()))
    return reps


def make_deployment(config: dict, seed: int, root: str) -> Deployment:
    """The cluster of `config` (a file under benchmark/configs) for `seed`.

    The cluster itself is drawn once, from the configuration's
    `instance_seed`: replica placement uniform at random (so racks and
    counts start uneven and RackAwareGoal is violated), loads per-topic
    multipliers (hot topics) times a per-partition lognormal draw, with
    per-window lognormal jitter around it.  `seed` lists its partitions
    in another order, so every seed asks the optimizer for the same work:
    the anneal's round count, and with it a plan's time, changes with any
    change to the cluster, its labels included (PERF.md).
    """
    c = config["cluster"]
    load = config["load"]
    rng = np.random.default_rng(config["instance_seed"])
    B, racks, T, ppt, rf = (
        c["brokers"], c["racks"], c["topics"], c["partitions_per_topic"],
        c["replication_factor"],
    )
    P = T * ppt
    part_topic = np.repeat(np.arange(T), ppt)
    part_num = np.tile(np.arange(ppt), T)
    replicas = _distinct_brokers(rng, P, B, rf)

    # hot topics: a share of the topics carries `hot_topic_factor` times the
    # load of the rest; multipliers are scaled to mean 1 over partitions so
    # the configured means hold
    n_hot = int(round(load["hot_topic_share"] * T))
    mult = np.ones(T)
    mult[rng.choice(T, size=n_hot, replace=False)] = load["hot_topic_factor"]
    mult /= mult.mean()
    sigma = load["partition_sigma"]
    means = np.array([load[f"mean_{r.lower()}"] for r in RESOURCES])
    base = (
        means[None, :]
        * mult[part_topic][:, None]
        * np.exp(rng.normal(-0.5 * sigma**2, sigma, size=(P, 4)))
    )
    W = config["monitor"]["complete_windows"]
    jit = load["window_jitter"]
    noise = np.exp(rng.normal(-0.5 * jit**2, jit, size=(W + 1, P, 4)))
    window_values = (base[None] * noise).astype(np.float32)
    capacity = load_capacity(os.path.join(root, config["capacity_file"]), B)

    # the seed lists the same partitions in another order: the topology's
    # partition list and the order the sampler interns its entities
    rows = np.random.default_rng(seed).permutation(P)
    return Deployment(
        num_brokers=B,
        rack_of_broker=np.arange(B) % racks,
        topic_names=tuple(f"topic{t:04d}" for t in range(T)),
        part_topic=part_topic[rows],
        part_num=part_num[rows],
        replicas=replicas[rows],
        window_values=window_values[:, rows],
        window_ms=config["monitor"]["window_ms"],
        complete_windows=W,
        capacity=capacity,
    )


def cluster_topology(dep: Deployment):
    """The deployment as the program's topology description."""
    from cruise_control_tpu.monitor.topology import (
        BrokerNode,
        ClusterTopology,
        PartitionInfo,
    )

    brokers = tuple(
        BrokerNode(b, rack=f"rack{int(dep.rack_of_broker[b]):03d}", host=f"host{b:05d}")
        for b in range(dep.num_brokers)
    )
    names = dep.topic_names
    parts = tuple(
        PartitionInfo(names[t], p, leader=reps[0], replicas=tuple(reps))
        for t, p, reps in zip(
            dep.part_topic.tolist(), dep.part_num.tolist(), dep.replicas.tolist()
        )
    )
    return ClusterTopology(brokers=brokers, partitions=parts)


class DeploymentSampler:
    """The program's MetricSampler SPI over a Deployment's window values.

    A fetch of [start_ms, end_ms] returns one sample per assigned
    partition, stamped in the middle of the window, carrying that window's
    CPU, leader bytes in/out and disk values.  No broker samples are
    produced, so follower CPU comes from the static coefficients."""

    def __init__(self, dep: Deployment):
        from cruise_control_tpu.monitor.metricdef import KAFKA_METRIC_DEF
        from cruise_control_tpu.monitor.sampling import PartitionEntity

        self.dep = dep
        m = KAFKA_METRIC_DEF
        self._num_metrics = m.num_metrics
        self._cols = [
            m.metric_id(n)
            for n in ("CPU_USAGE", "LEADER_BYTES_IN", "LEADER_BYTES_OUT", "DISK_USAGE")
        ]
        # topic ids in first-seen order: the program's entity key rule
        first_seen: dict = {}
        self._entities = [
            PartitionEntity(first_seen.setdefault(int(t), len(first_seen)), int(p))
            for t, p in zip(dep.part_topic, dep.part_num)
        ]
        self._row = {(e.topic, e.partition): i for i, e in enumerate(self._entities)}

    def all_partition_entities(self):
        return list(self._entities)

    def get_samples(self, assigned_partitions, start_ms: int, end_ms: int):
        from cruise_control_tpu.monitor.sampling import MetricSample, SamplingResult

        w = start_ms // self.dep.window_ms
        ents = list(assigned_partitions)
        rows = np.fromiter(
            (self._row[(e.topic, e.partition)] for e in ents), np.int64, len(ents)
        )
        vals = np.zeros((len(ents), self._num_metrics), np.float32)
        vals[:, self._cols] = self.dep.window_values[w, rows]
        t = (start_ms + end_ms) // 2
        return SamplingResult(
            [MetricSample(e, t, v) for e, v in zip(ents, vals)], []
        )

    def fetch_all_windows(self, fetcher) -> None:
        """Feed every window (the complete ones and the current one)."""
        ms = self.dep.window_ms
        for w in range(self.dep.complete_windows + 1):
            fetcher.fetch_once(self._entities, w * ms, (w + 1) * ms - 1)
