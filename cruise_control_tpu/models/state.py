"""Array-encoded cluster workload state — the TPU-native ClusterModel.

The reference models a cluster as a mutable object graph
Rack -> Host -> Broker -> Disk -> Replica with windowed Load objects
(reference: model/ClusterModel.java:48, model/Replica.java, model/Load.java).
Goals then pointer-chase that graph in a single-threaded greedy loop.

Here the same information is flattened into fixed-shape device arrays so that
goal scores are segment-reductions and candidate moves are gather/scatter
deltas — evaluable for thousands of plans in parallel under vmap/jit.

Encoding (R = padded replica count, B = broker count, D = max disks/broker):

  replica axis [R]:
    replica_broker     i32  current broker id (padding rows point at broker 0
                            but are masked out by replica_valid everywhere)
    replica_partition  i32  global partition id
    replica_topic      i32  topic id of the partition
    replica_pos        i32  position in the partition's replica list (0 =
                            preferred leader; reference model/Partition.java)
    replica_is_leader  bool currently the partition leader
    replica_valid      bool padding mask
    replica_orig_broker i32 broker at model-build time (immigrant tracking,
                            reference model/Replica.java originalBroker)
    replica_offline    bool on a dead broker / bad disk; must be relocated
    replica_disk       i32  disk index within broker (JBOD), 0 if single-disk
    replica_load_leader   f32[R, 4]  expected utilization if this replica
                                     leads its partition
    replica_load_follower f32[R, 4]  expected utilization as a follower
                                     (NW_OUT = 0; CPU = follower share —
                                     reference model/ModelUtils.java:53-67)

  broker axis [B]:
    broker_capacity    f32[B, 4]  per-resource capacity (DISK = sum of disks)
    broker_rack        i32        rack id
    broker_host        i32        host id
    broker_alive       bool       live broker (dead => replicas offline)
    broker_new         bool       newly-added broker (only immigrant replicas
                                  allowed — reference analyzer semantics)
    broker_valid       bool       padding mask
    disk_capacity      f32[B, D]  per-logdir capacity (JBOD)
    disk_alive         bool[B, D] logdir health

Static (non-array) metadata lives in the companion `ClusterShape` so the
pytree leaves are all arrays and jit retraces only when shapes change.

Leadership semantics: the effective load of a replica is
`where(is_leader, load_leader, load_follower)`; relocating leadership between
two replicas of a partition therefore shifts CPU/NW_OUT between their brokers
exactly like reference model/ClusterModel.java:374 (relocateLeadership).
Potential-NW-out (reference model/ClusterModel.java:70,205) is the sum of
`replica_load_leader[:, NW_OUT]` over a broker's replicas — what the broker
would serve if it led everything it hosts.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from cruise_control_tpu.common.resources import NUM_RESOURCES


@dataclasses.dataclass(frozen=True)
class ClusterShape:
    """Static shape/topology metadata for a ClusterState.

    Kept out of the pytree so it can gate jit specialization explicitly.
    """

    num_replicas: int  # padded R
    num_brokers: int  # B
    num_partitions: int  # P
    num_topics: int
    num_racks: int
    num_hosts: int
    max_disks_per_broker: int  # D

    @property
    def R(self) -> int:  # noqa: N802 — math-style aliases
        return self.num_replicas

    @property
    def B(self) -> int:  # noqa: N802
        return self.num_brokers

    @property
    def P(self) -> int:  # noqa: N802
        return self.num_partitions


@dataclasses.dataclass(frozen=True)
class ShapeBucketPolicy:
    """Geometric shape-bucketing policy for engine-cache stability.

    Engines compile per exact ClusterShape (analyzer/engine.py); a Kafka
    cluster creates partitions and adds brokers continuously, so an exact
    shape key makes nearly every model generation under churn a compile
    miss.  Rounding each axis up to the next bucket of the geometric
    series floor·growth^k (the batch/sequence-length bucketing of
    inference serving) makes successive generations land in the SAME
    padded shape: `Engine.rebind()` swaps in the fresh data with zero
    recompilation, and only a bucket overflow (≥ growth× accumulated
    churn) pays a compile.

    The padding this introduces is masked everywhere — `replica_valid`
    for replicas, `broker_valid` for brokers (never alive, zero capacity,
    never a destination, excluded from every goal denominator), and
    shape-only padding for partitions/topics/racks/hosts (no replicas
    reference them) — pinned by the exact-vs-bucketed parity tests.
    """

    enabled: bool = True
    #: bucket growth factor between adjacent buckets (> 1)
    growth: float = 1.25
    #: smallest bucket; also the series base
    floor: int = 8

    def __post_init__(self):
        if self.growth <= 1.0:
            raise ValueError(f"bucket growth must be > 1, got {self.growth}")
        if self.floor < 1:
            raise ValueError(f"bucket floor must be >= 1, got {self.floor}")

    def bucket(self, n: int) -> int:
        """Smallest bucket >= n in the series ceil(floor * growth^k)."""
        if not self.enabled:
            return int(n)
        if n <= self.floor:
            return self.floor
        import math

        # float log gets within one step of the right k; walk to the exact
        # smallest bucket so the series is deterministic and monotone
        k = max(0, int(math.log(n / self.floor) / math.log(self.growth)) - 1)
        b = int(math.ceil(self.floor * self.growth**k))
        while b < n:
            k += 1
            b = int(math.ceil(self.floor * self.growth**k))
        return b

    def bucket_shape(self, shape: ClusterShape) -> ClusterShape:
        """Round every churn-prone axis up to its bucket (D stays exact:
        logdir counts change only on hardware refresh)."""
        if not self.enabled:
            return shape
        return ClusterShape(
            num_replicas=self.bucket(shape.num_replicas),
            num_brokers=self.bucket(shape.num_brokers),
            num_partitions=self.bucket(shape.num_partitions),
            num_topics=self.bucket(shape.num_topics),
            num_racks=self.bucket(shape.num_racks),
            num_hosts=self.bucket(shape.num_hosts),
            max_disks_per_broker=shape.max_disks_per_broker,
        )

    def next_bucket_shape(self, shape: ClusterShape) -> ClusterShape:
        """The shape one partition-churn overflow lands in: the replica and
        partition axes bumped past their current bucket (other axes — topic,
        broker, rack, host — stay at their current bucket; their churn is an
        order of magnitude rarer than partition creates).  Used by the
        service's precompute loop to pre-warm the next engine so a bucket
        overflow hits a warm compile instead of a cold one."""
        return ClusterShape(
            num_replicas=self.bucket(self.bucket(shape.num_replicas) + 1),
            num_brokers=self.bucket(shape.num_brokers),
            num_partitions=self.bucket(self.bucket(shape.num_partitions) + 1),
            num_topics=self.bucket(shape.num_topics),
            num_racks=self.bucket(shape.num_racks),
            num_hosts=self.bucket(shape.num_hosts),
            max_disks_per_broker=shape.max_disks_per_broker,
        )


#: service-default policy (config keys tpu.shape.bucket.*)
DEFAULT_BUCKET_POLICY = ShapeBucketPolicy()


@partial(
    jax.tree_util.register_dataclass,
    data_fields=[
        "replica_broker",
        "replica_partition",
        "replica_topic",
        "replica_pos",
        "replica_is_leader",
        "replica_valid",
        "replica_orig_broker",
        "replica_offline",
        "replica_disk",
        "replica_load_leader",
        "replica_load_follower",
        "broker_capacity",
        "broker_rack",
        "broker_host",
        "broker_alive",
        "broker_new",
        "broker_valid",
        "disk_capacity",
        "disk_alive",
    ],
    meta_fields=["shape"],
)
@dataclasses.dataclass(frozen=True)
class ClusterState:
    # --- replica axis [R] ---
    replica_broker: jax.Array
    replica_partition: jax.Array
    replica_topic: jax.Array
    replica_pos: jax.Array
    replica_is_leader: jax.Array
    replica_valid: jax.Array
    replica_orig_broker: jax.Array
    replica_offline: jax.Array
    replica_disk: jax.Array
    replica_load_leader: jax.Array  # [R, NUM_RESOURCES]
    replica_load_follower: jax.Array  # [R, NUM_RESOURCES]
    # --- broker axis [B] ---
    broker_capacity: jax.Array  # [B, NUM_RESOURCES]
    broker_rack: jax.Array
    broker_host: jax.Array
    broker_alive: jax.Array
    broker_new: jax.Array
    broker_valid: jax.Array
    disk_capacity: jax.Array  # [B, D]
    disk_alive: jax.Array  # [B, D]
    # --- static metadata ---
    shape: ClusterShape

    # ---- derived quantities (cheap, jit-friendly) ----

    @property
    def replica_load(self) -> jax.Array:
        """Effective [R, 4] utilization given current leadership."""
        lead = self.replica_is_leader[:, None]
        load = jnp.where(lead, self.replica_load_leader, self.replica_load_follower)
        return jnp.where(self.replica_valid[:, None], load, 0.0)

    def broker_segment_ids(self) -> jax.Array:
        """Replica→broker ids with padding routed to an overflow bucket B."""
        return jnp.where(self.replica_broker >= 0, self.replica_broker, self.shape.B)

    def with_replicas_moved(
        self, replica_idx: jax.Array, new_broker: jax.Array, new_disk: jax.Array | None = None
    ) -> "ClusterState":
        """Scatter-update replica placement (reference ClusterModel.relocateReplica:347)."""
        rb = self.replica_broker.at[replica_idx].set(new_broker)
        disk = (
            self.replica_disk.at[replica_idx].set(new_disk)
            if new_disk is not None
            else self.replica_disk.at[replica_idx].set(0)
        )
        # offline tracks destination health, not a blanket clear: landing on a
        # dead broker/logdir keeps the replica offline
        dest_ok = self.broker_alive[new_broker] & self.disk_alive[new_broker, disk[replica_idx]]
        off = self.replica_offline.at[replica_idx].set(~dest_ok)
        return dataclasses.replace(self, replica_broker=rb, replica_offline=off, replica_disk=disk)

    def with_leadership_moved(self, from_replica: jax.Array, to_replica: jax.Array) -> "ClusterState":
        """Transfer leadership between two replicas of the same partition
        (reference ClusterModel.relocateLeadership:374)."""
        lead = self.replica_is_leader.at[from_replica].set(False).at[to_replica].set(True)
        return dataclasses.replace(self, replica_is_leader=lead)


#: check names for validate_on_device's count vector, in order
DEVICE_CHECKS = (
    "broker ids out of range",
    "replica on invalid broker",
    "partitions without exactly one leader",
    "duplicate replica of a partition on one broker",
    "non-finite or negative leader loads",
)


@jax.jit
def validate_on_device(state: ClusterState):
    """The same invariants as validate(), computed ON DEVICE and returned
    as a tiny [5] violation-count vector — on a remote device the host
    validate()'s bulk device->host transfer costs more than the checks.
    Decode nonzero entries against DEVICE_CHECKS (then re-run the host
    validate for the detailed message)."""
    valid = state.replica_valid
    B, P, R = state.shape.B, state.shape.P, state.shape.R
    brk = jnp.where(valid, state.replica_broker, 0)
    part = jnp.where(valid, state.replica_partition, 0)
    lead = state.replica_is_leader & valid

    in_range = (state.replica_broker >= 0) & (state.replica_broker < B)
    n_oor = jnp.sum(valid & ~in_range)
    n_invalid_broker = jnp.sum(valid & in_range & ~state.broker_valid[brk])

    leaders_per_part = jnp.zeros(P, jnp.int32).at[part].add(lead.astype(jnp.int32))
    present = jnp.zeros(P, jnp.bool_).at[part].max(valid)
    n_bad_leader = jnp.sum(present & (leaders_per_part != 1))

    # duplicate (partition, broker): lexsort the PAIR and compare adjacent —
    # a combined part*B+brk key would need int64, which jax truncates to
    # int32 without x64 mode (overflow at ~800k partitions x 2600 brokers)
    part_key = jnp.where(valid, part, P)  # padding sorts to the end
    brk_key = jnp.where(valid, brk, -1)
    order = jnp.lexsort((brk_key, part_key))
    ps, bs, vs = part_key[order], brk_key[order], valid[order]
    n_dup = jnp.sum((ps[1:] == ps[:-1]) & (bs[1:] == bs[:-1]) & vs[1:] & vs[:-1])

    loads = jnp.where(valid[:, None], state.replica_load_leader, 0.0)
    n_bad_load = jnp.sum(~jnp.isfinite(loads)) + jnp.sum(loads < 0)

    return jnp.stack(
        [n_oor, n_invalid_broker, n_bad_leader, n_dup, n_bad_load]
    ).astype(jnp.int32)


def validate(state: ClusterState, *, strict: bool = True) -> list[str]:
    """Host-side structural sanity check (reference ClusterModel.sanityCheck:1081).

    Checks (on materialized numpy copies — not for use inside jit):
      * exactly one leader per partition (over valid replicas)
      * replica broker ids within range and pointing at valid brokers
      * no duplicate (partition, broker) placement
      * loads are non-negative and finite
    Returns a list of human-readable problems; raises if strict and non-empty.

    Hot paths use validate_on_device instead (a [5] count vector, no bulk
    device->host transfer) and fall back here for the detailed message.
    """
    problems: list[str] = []
    # one batched device->host transfer (per-array np.asarray syncs five times)
    valid, part, brk, lead, load_l = jax.device_get(
        (
            state.replica_valid,
            state.replica_partition,
            state.replica_broker,
            state.replica_is_leader,
            state.replica_load_leader,
        )
    )
    part, brk, lead = part[valid], brk[valid], lead[valid]
    B, P = state.shape.B, state.shape.P

    if brk.size:
        in_range = (brk >= 0) & (brk < B)
        if not in_range.all():
            problems.append(f"replica broker ids out of range [0,{B}): {brk.min()}..{brk.max()}")
        bvalid = np.asarray(state.broker_valid)
        if not bvalid[brk[in_range]].all():
            problems.append("replica placed on invalid (padding) broker")

    leaders_per_part = np.bincount(part[lead], minlength=P)
    present = np.bincount(part, minlength=P) > 0
    bad = present & (leaders_per_part != 1)
    if bad.any():
        problems.append(f"{int(bad.sum())} partitions without exactly one leader")

    pb = part.astype(np.int64) * B + brk.astype(np.int64)
    if np.unique(pb).size != pb.size:
        problems.append("duplicate replica of a partition on one broker")

    loads = load_l[valid]
    if not np.isfinite(loads).all() or (loads < 0).any():
        problems.append("non-finite or negative leader loads")

    if problems and strict:
        raise ValueError("ClusterState sanity check failed: " + "; ".join(problems))
    return problems
