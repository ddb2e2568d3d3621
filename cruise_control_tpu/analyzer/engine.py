"""Batched simulated-annealing/greedy optimization engine.

This replaces the reference's single-threaded greedy goal loop
(reference analyzer/goals/AbstractGoal.java:66-107: while(!finished)
rebalanceForBroker -> maybeApplyBalancingAction, one move tried at a time
with O(#goals) veto checks) with a TPU-shaped search:

  every step, K candidate moves (replica relocations + leadership
  transfers) are sampled and their exact objective deltas are computed IN
  PARALLEL in O(1) each — gathers against per-broker aggregates plus
  frozen per-step globals — then a maximal non-conflicting subset of
  improving moves is accepted (per-broker/per-partition rank argmin), and
  aggregates are updated by scatter.  Hundreds of moves land per step; the
  whole step is one fused XLA program under `lax.scan`.

Objective semantics match GoalChain (analyzer/objective.py): weighted
lexicographic goal violations + a dispersion tiebreaker.  The delta path
and the full-eval path (goal classes) are kept consistent by unit test
(tests/test_optimizer.py).

Simulated annealing: a candidate is accepted if delta < -T·log(u) — at
T=0 this is pure greedy improvement; early rounds use T>0 to escape the
local optima the reference needs explicit swap moves for (reference
ResourceDistributionGoal.java:502-599; SURVEY §7 hard part (b)).

Compilation model: all cluster data rides in an `EngineStatics` pytree
passed as a runtime ARGUMENT to the jitted programs — never closed over.
Closure-captured arrays become XLA constants, which (a) forces a
recompile per model generation and (b) makes those compiles pathologically
slow at 500k-replica scale.  With statics-as-arguments one Engine per
ClusterShape serves every model generation; `rebind()` swaps in fresh
data with zero recompilation (the TPU analog of the reference's proposal
precompute amortization, GoalOptimizer.java:124-175).

Shape bucketing extends that amortization across TOPOLOGY CHURN: a live
cluster creates partitions and adds brokers continuously, so exact shapes
would make nearly every generation a compile miss anyway.  Model builds
round each ClusterShape axis up to a geometric bucket
(`models.state.ShapeBucketPolicy`, config `tpu.shape.bucket.*`) and mask
the padding (replica_valid / broker_valid); sampling draws are scaled by
the RUNTIME valid counts (`EngineStatics.n_source/n_dest/n_brokers`, not
the padded axis sizes), so an exact and a bucketed build of the same
cluster produce byte-identical move trajectories — bucketing changes the
compile key and nothing else.  `GoalOptimizer` keeps compiled engines in
a bounded LRU (`tpu.engine.cache.size`) whose eviction calls `release()`
to free the evicted generation's HBM.

Execution model (fused rounds, the default): the ENTIRE multi-round
anneal is ONE device-resident XLA program — a `lax.scan` over rounds
whose body is the per-round step scan plus the between-rounds program
(aggregate refresh, sampling-plan rebuild, cheap early-stop signal), with
the temperature schedule, the authoritative full-goal-chain early stop,
and the extra-polish-rounds loop expressed in-graph as cond-masked
rounds.  The host dispatches twice (init, fused run), then performs ONE
blocking device sync to fetch scalar per-round stats; the final carry
stays on device for the result report / proposal diff to consume, so
host-side extraction overlaps the tail of device work.  The EngineCarry
input is donated (`donate_argnums`) so HBM holds a single placement copy
at 500k-replica scale instead of one per dispatch.

The legacy Python round loop (`fused_rounds=False`) dispatches one scan
per round and syncs O(num_rounds) times.  It remains the right tool for
fused-vs-legacy parity testing, per-round host-side debugging (inspect
the carry between rounds), and experimenting with host-driven schedules;
both paths share every traced sub-program, temperatures, and RNG chain,
so at T=0 with a fixed seed they produce identical move trajectories.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import functools
import logging
import threading
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from cruise_control_tpu.analyzer.objective import GoalChain, TIE_WEIGHT
from cruise_control_tpu.analyzer.options import DEFAULT_OPTIONS, OptimizationOptions
from cruise_control_tpu.common import collectives
from cruise_control_tpu.common.blackbox import RECORDER as _BLACKBOX
from cruise_control_tpu.common.device_watchdog import device_op
from cruise_control_tpu.common.dispatch import count_dispatch
from cruise_control_tpu.common.resources import NUM_RESOURCES, Resource
from cruise_control_tpu.config.balancing import BalancingConstraint, DEFAULT_CONSTRAINT
from cruise_control_tpu.models.aggregates import compute_aggregates
from cruise_control_tpu.models.state import (
    ClusterShape,
    ClusterState,
    validate_on_device,
)
from cruise_control_tpu.models.stats import compute_stats


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    """Search knobs (no reference analog — the reference search is greedy)."""

    num_candidates: int = 2048  # K sampled moves per step
    leadership_candidates: int = 512  # of which leadership transfers
    swap_candidates: int = 512  # of which replica swaps (escape local optima,
    # reference ResourceDistributionGoal.java:502-599; clamped so at least
    # one plain relocation candidate remains)
    steps_per_round: int = 64  # jitted scan length
    num_rounds: int = 10  # python-level rounds (aggregates re-derived each round)
    init_temperature_scale: float = 1e-2  # T0 = scale * initial objective
    temperature_decay: float = 0.5  # per-round geometric decay; last round T=0
    seed: int = 0
    #: movement pricing — the reference only moves what a goal demands and
    #: its executor caps concurrent moves (executor/Executor.java:485-510,
    #: ExecutionProposal data-to-move).  SA needs movement priced into the
    #: objective or it random-walks placement for free.  A move away from a
    #: replica's ORIGINAL broker/leader pays the cost; moving back refunds it.
    replica_move_cost: float = 0.5  # per relocated replica, /n_valid
    leadership_move_cost: float = 1.0  # per relocated partition leadership, /n_valid
    #: fraction of replica-move candidates importance-sampled from brokers
    #: with the largest objective contribution (rest stay uniform); the
    #: sampling plan is refreshed every round
    importance_fraction: float = 0.5
    #: intra-broker (JBOD) mode: candidates move replicas between a broker's
    #: own logdirs instead of between brokers (reference rebalance_disk
    #: semantics, AnalyzerConfig.java:236 default.intra.broker.goals);
    #: leadership/swap candidates are disabled
    intra_broker: bool = False
    #: stop annealing once the weighted goal violations (objective minus the
    #: dispersion tiebreaker) fall to this level — remaining rounds could
    #: only polish dispersion, which no goal measures.  Aligned with the
    #: 1e-6 "goal satisfied" tolerance used by balancedness_score and the
    #: bench's violated_goals_after (f32 noise floor at 500k-replica scale
    #: is ~1e-8..1e-7; see analyzer/objective.py).  <0 disables.
    early_stop_violations: float = 1e-6
    #: extra T=0 polish rounds run past num_rounds while the FULL goal chain
    #: still reports violations and each round keeps improving.  The
    #: reference optimizes every goal to completion rather than on a fixed
    #: budget (AbstractGoal.optimize loops until finished); a fixed schedule
    #: tuned for steady-state rebalances runs out on much-worse starts
    #: (mass decommissions).  0 disables.
    max_extra_rounds: int = 8
    #: run the whole multi-round anneal as ONE device-resident program
    #: (scan-of-scans with in-graph aggregate refresh, sampling-plan
    #: rebuild, temperature schedule, early stop, and extra polish rounds;
    #: the EngineCarry input is donated so HBM holds one placement copy).
    #: False selects the legacy Python round loop — one dispatch + one
    #: blocking sync per round — kept for parity testing, per-round
    #: debugging, and host-side schedule experiments.
    fused_rounds: bool = True
    #: learned move-acceptance prior (streaming controller): replica-move
    #: DESTINATION draws mix a per-(source-topic, destination) categorical
    #: fitted from past anneal trajectories / executed proposals
    #: (controller/prior.py) into the uniform draw.  Trace-static: False
    #: (the default) keeps the traced step program byte-identical to the
    #: pre-prior engine; True adds the prior gather/searchsorted ops but a
    #: COLD prior (mix 0) still reproduces the uniform draw stream
    #: bit-for-bit — the uniform branch consumes the same key with the
    #: same arithmetic, and the prior's extra draws ride keys derived via
    #: fold_in that no other stream reads (pinned by tests).
    prior_enabled: bool = False
    #: convergence diagnostics (config analyzer.diagnostics.enabled): the
    #: fused program's per-round outputs additionally carry the full-chain
    #: objective, the per-goal violation vector at each round boundary,
    #: acceptance counts by move kind, and prior-draw usage — riding the
    #: run's existing single host extraction, ZERO extra blocking syncs.
    #: Trace-static: False keeps the traced program and its outputs
    #: byte-identical to today's; True adds only read-only reductions
    #: (no RNG keys are split, no placement arithmetic changes), so
    #: placements are byte-identical to the off path — pinned by
    #: tests/test_ledger.py across plain, segmented, and mesh runs.
    diagnostics: bool = False
    #: mixed-precision goal scoring (config analyzer.precision.score.dtype):
    #: "bfloat16" accumulates the goal-score weighted sums — the
    #: `_broker_terms` inner loop (inlined ~8x into the step program) and
    #: the goal chain's objective reduction — in bf16, halving the hot
    #: loop's accumulation bandwidth.  Parity-safe subset only: threshold
    #: compares, ceil/floor banding, violation vectors, and RNG arithmetic
    #: stay f32.  Trace-static: the default "float32" takes the original
    #: code path so its traced program is byte-identical to the pre-knob
    #: engine (the fp32 fallback pin); the bf16 objective must track f32
    #: within analyzer.precision.tolerance (the tolerance gate, pinned by
    #: tests/test_optimizer.py and the streaming bench).
    score_dtype: str = "float32"

    def __post_init__(self):
        # round-count knobs validated in ONE place: both the in-graph
        # (fused) early stop and the legacy host-side early stop derive
        # their round budgets from these values via `extra_round_budget`
        # and `early_stop_tol`, so the two paths cannot disagree on how
        # many rounds may run
        if self.num_rounds < 1:
            raise ValueError(f"num_rounds must be >= 1, got {self.num_rounds}")
        if self.steps_per_round < 1:
            raise ValueError(
                f"steps_per_round must be >= 1, got {self.steps_per_round}"
            )
        if self.max_extra_rounds < 0:
            raise ValueError(
                f"max_extra_rounds must be >= 0, got {self.max_extra_rounds}"
            )
        if self.num_candidates < 1:
            raise ValueError(
                f"num_candidates must be >= 1, got {self.num_candidates}"
            )
        if self.score_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"score_dtype must be 'float32' or 'bfloat16', got "
                f"{self.score_dtype!r}"
            )

    @property
    def extra_round_budget(self) -> int:
        """Extra T=0 polish rounds actually runnable.  The extra-rounds
        loop is gated on the early-stop violation signal, so disabling
        early stop (early_stop_violations < 0) disables extra rounds with
        it — in BOTH round-loop implementations."""
        return self.max_extra_rounds if self.early_stop_violations >= 0.0 else 0

    @property
    def early_stop_tol(self) -> float:
        """The early-stop threshold as the f32 value both paths compare
        against.  The fused in-graph compare is f32; the legacy host
        compare must use the same quantized constant or the two could
        disagree on round counts at the boundary."""
        return float(np.float32(self.early_stop_violations))


@partial(
    jax.tree_util.register_dataclass,
    data_fields=[
        "replica_broker",
        "replica_is_leader",
        "replica_disk",
        "broker_load",
        "broker_replica_count",
        "broker_leader_count",
        "broker_potential_nw_out",
        "broker_leader_bytes_in",
        "broker_topic_count",
        "part_rack_count",
        "disk_load",
        "host_load",
        "key",
    ],
    meta_fields=[],
)
@dataclasses.dataclass(frozen=True)
class EngineCarry:
    """Mutable placement + incremental aggregates carried through lax.scan."""

    replica_broker: jax.Array
    replica_is_leader: jax.Array
    replica_disk: jax.Array
    broker_load: jax.Array  # f32[B, 4] (includes dead brokers' stranded load)
    broker_replica_count: jax.Array  # i32[B]
    broker_leader_count: jax.Array  # i32[B]
    broker_potential_nw_out: jax.Array  # f32[B]
    broker_leader_bytes_in: jax.Array  # f32[B]
    broker_topic_count: jax.Array  # i32[T, B]
    part_rack_count: jax.Array  # i32[P, num_racks]
    disk_load: jax.Array  # f32[B, D]
    host_load: jax.Array  # f32[H, 4]
    key: jax.Array


@partial(
    jax.tree_util.register_dataclass,
    data_fields=[
        "state",
        "part_replicas",
        "alive",
        "dest_ids",
        "dest_ok",
        "lead_ok",
        "topic_movable",
        "host_multi",
        "host_cap",
        "total_cap",
        "n_alive",
        "n_valid",
        "total_disk_cap",
        "n_source",
        "n_dest",
        "n_brokers",
        "prior_dst_cdf",
        "prior_mix",
    ],
    meta_fields=[],
)
@dataclasses.dataclass(frozen=True)
class EngineStatics:
    """Per-model-generation inputs, passed (not closed over) into jit."""

    state: ClusterState
    part_replicas: jax.Array  # i32[P, max_rf]
    alive: jax.Array  # bool[B] valid & alive
    dest_ids: jax.Array  # i32[B] allowed destination ids, cyclically padded
    dest_ok: jax.Array  # bool[B] allowed-destination mask (swap feasibility)
    lead_ok: jax.Array  # bool[B]
    topic_movable: jax.Array  # bool[T]
    host_multi: jax.Array  # bool[H]
    host_cap: jax.Array  # f32[H, 4]
    total_cap: jax.Array  # f32[4]
    n_alive: jax.Array  # f32 scalar
    n_valid: jax.Array  # f32 scalar
    total_disk_cap: jax.Array  # f32 scalar
    #: i32 scalar — leading replica slots uniform source draws cover (the
    #: valid prefix when replicas are front-packed, else the full padded R).
    #: Sampling ``floor(u * n_source)`` instead of ``randint(0, R)`` makes
    #: candidate streams independent of the PADDED R: an exact and a
    #: shape-bucketed build of the same cluster draw identical candidates,
    #: so bucketing changes nothing but the compile key (and no draws are
    #: wasted on padding rows).
    n_source: jax.Array
    #: i32 scalar — real entries at the head of dest_ids (same role as
    #: n_source for destination draws: padded-B invariance)
    n_dest: jax.Array
    #: i32 scalar — valid (real, front-packed) broker count; clips the
    #: importance sampler's CDF search so a u ~ 1.0 edge draw resolves to
    #: the last REAL broker under any padding
    n_brokers: jax.Array
    #: f32[T, B] per-SOURCE-TOPIC inclusive CDF over destination POSITIONS
    #: (indices into dest_ids' real head), the learned move-acceptance
    #: prior of the streaming controller; positions >= n_dest hold 1.0 so
    #: an edge draw clips onto the last real destination.  A [1, 1] zero
    #: placeholder when the engine's config has prior_enabled=False (the
    #: compile key includes the flag, so avals stay consistent per engine).
    prior_dst_cdf: jax.Array
    #: f32 scalar in [0, 1] — fraction of replica-move destination draws
    #: taken from the prior CDF instead of uniform; 0.0 (cold prior) makes
    #: the destination stream byte-identical to the uniform-only draw
    prior_mix: jax.Array


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["broker_cdf", "order", "start", "count", "replica_cost", "lead_cost"],
    meta_fields=[],
)
@dataclasses.dataclass(frozen=True)
class SamplingPlan:
    """Per-round step context: importance sampling + movement pricing.

    Sampling: uniform source sampling over 500k replicas wastes almost the
    whole candidate budget near convergence (nearly all candidates touch
    already-balanced brokers).  Instead: sample a source broker from a
    categorical proportional to its current objective contribution, then a
    replica uniformly on that broker via a grouped index (order/start/count),
    all frozen at round start so the scan stays a fixed program.

    Pricing: per-move costs scale with the round-start objective — early
    rounds only accept moves with substantial gains; as the objective falls
    the price falls with it, so fine-grained fixes (and refunds for strayed
    replicas returning home) still go through.
    """

    broker_cdf: jax.Array  # f32[B] inclusive cumsum of broker probabilities
    order: jax.Array  # i32[R] replica ids grouped by broker (invalid last)
    start: jax.Array  # i32[B] group offsets into order
    count: jax.Array  # i32[B] replicas per broker
    replica_cost: jax.Array  # f32 scalar: objective price per strayed replica
    lead_cost: jax.Array  # f32 scalar: price per strayed partition leadership


def partition_replica_table(
    state: ClusterState, max_rf: int | None = None, *, host: dict | None = None
) -> np.ndarray:
    """i32[P, max_rf] replica indices per partition, padded with R.

    Membership never changes during optimization (only placement does), so
    this is built once on the host.  Mirrors reference model/Partition.java's
    replica list.  `max_rf` forces a uniform table width (the sharded engine
    needs identical shapes across shards).  `host` supplies already-fetched
    numpy copies (build_statics batches ALL device->host transfers into one
    device_get — per-array np.asarray paid seconds of transfer sync at
    500k-replica scale).
    """
    if host is not None:
        valid, part, pos = host["replica_valid"], host["replica_partition"], host["replica_pos"]
    else:
        valid, part, pos = jax.device_get(
            (state.replica_valid, state.replica_partition, state.replica_pos)
        )
    P, R = state.shape.P, state.shape.R
    if max_rf is None:
        max_rf = 1
        counts = np.bincount(part[valid], minlength=P)
        if counts.size:
            max_rf = max(1, int(counts.max()))
    table = np.full((P, max_rf), R, np.int32)
    idx = np.nonzero(valid)[0]
    slot = np.minimum(pos[idx], max_rf - 1)
    table[part[idx], slot] = idx
    return table


def _prior_fields(prior, T: int, B: int, dest_idx: np.ndarray):
    """(prior_cdf f32[T, B], prior_mix float) from a duck-typed prior
    (`.weights` f32[T, B] in broker-id space, `.mix` float) and the REAL
    destination-position list `dest_idx` — the prior-onto-positions
    conversion factored out of build_statics so the fused streaming cycle
    can refresh ONLY these two statics fields per window
    (Engine.rebind_prior) without build_statics' batched device fetch."""
    n_dest_int = int(dest_idx.size)
    prior_cdf = np.ones((T, B), np.float32)
    w = None if prior is None else getattr(prior, "weights", None)
    if w is not None:
        w = np.asarray(w, np.float32)
        if w.shape != (T, B):
            raise ValueError(
                f"prior weights shape {w.shape} != model (T={T}, B={B})"
            )
        w_pos = np.maximum(w[:, dest_idx], 0.0)  # [T, n_dest]
    else:
        w_pos = np.zeros((T, n_dest_int), np.float32)
    tot = w_pos.sum(1, keepdims=True)
    # unseen topics draw uniformly over the real destination list —
    # still a valid categorical, just a different stream than the
    # uniform branch (the mix gate decides which branch is taken)
    uni = np.full((T, n_dest_int), 1.0 / max(1, n_dest_int), np.float32)
    probs = np.where(tot > 0.0, w_pos / np.maximum(tot, 1e-12), uni)
    prior_cdf[:, :n_dest_int] = np.cumsum(probs, axis=1)
    prior_mix = float(getattr(prior, "mix", 0.0)) if prior is not None else 0.0
    if not 0.0 <= prior_mix <= 1.0:
        raise ValueError(f"prior mix must be in [0, 1], got {prior_mix}")
    return prior_cdf, prior_mix


def build_statics(
    state: ClusterState,
    options: OptimizationOptions,
    *,
    prior=None,
    prior_full_shape: bool = False,
    layout_out: dict | None = None,
) -> EngineStatics:
    """Host-side (numpy) preprocessing of one model generation.

    Every device array this needs comes down in ONE batched device_get —
    at 500k-replica scale, per-array np.asarray syncs cost seconds each
    and dominated engine construction.

    `prior` (duck-typed: `.weights` f32[T, B] in broker-id space keyed by
    this generation's topic ids, `.mix` float) is the learned
    move-acceptance prior; it is converted here onto destination
    POSITIONS because only this function knows the dest_ids layout.  With
    `prior_full_shape` False (prior_enabled=False engines) the statics
    carry a [1, 1] placeholder so the disabled program never pays a
    [T, B] transfer per rebind.
    """
    s = state.shape
    h_keys = (
        "broker_valid", "broker_alive", "broker_capacity", "broker_host",
        "disk_alive", "disk_capacity", "replica_valid", "replica_partition",
        "replica_pos",
    )
    h = dict(zip(h_keys, jax.device_get(tuple(getattr(state, k) for k in h_keys))))
    alive = h["broker_valid"] & h["broker_alive"]
    cap = h["broker_capacity"]
    dest = alive & options.dest_allowed(state)
    dest_idx = np.nonzero(dest)[0].astype(np.int32)
    if dest_idx.size == 0:
        dest_idx = np.nonzero(alive)[0].astype(np.int32)
    if dest_idx.size == 0:
        dest_idx = np.zeros(1, np.int32)
    # cyclic pad to [B]: uniform sampling over the padded list stays uniform
    # over the allowed set while the array shape stays generation-invariant
    dest_pad = dest_idx[np.arange(s.B) % dest_idx.size]
    host = h["broker_host"]
    valid_b = h["broker_valid"]
    bph = np.bincount(host[valid_b], minlength=s.num_hosts)
    host_cap = np.zeros((s.num_hosts, NUM_RESOURCES), np.float32)
    np.add.at(host_cap, host[valid_b & alive], cap[valid_b & alive])
    dmask = h["disk_alive"] & alive[:, None]
    # shape-invariant sampling bounds: uniform draws cover only the valid
    # replica prefix / real destination list, so the padded sizes never
    # leak into the RNG stream (exact-vs-bucketed trajectory parity)
    n_valid_int = int(h["replica_valid"].sum())
    front_packed = bool(h["replica_valid"][:n_valid_int].all())
    n_source = n_valid_int if front_packed else s.R
    n_dest_int = int(dest_idx.size)
    if layout_out is not None:
        # host-side destination layout for data-only statics refreshes
        # (Engine.rebind_prior): the fused cycle path must rebuild the
        # prior CDF without re-fetching these arrays from device
        layout_out["dest_idx"] = dest_idx
    if not prior_full_shape:
        prior_cdf = np.zeros((1, 1), np.float32)
        prior_mix = 0.0
    else:
        prior_cdf, prior_mix = _prior_fields(prior, s.num_topics, s.B, dest_idx)
    return EngineStatics(
        state=state,
        part_replicas=jnp.asarray(partition_replica_table(state, host=h)),
        alive=jnp.asarray(alive),
        dest_ids=jnp.asarray(dest_pad),
        dest_ok=jnp.asarray(dest),
        lead_ok=jnp.asarray(alive & options.leadership_allowed(state)),
        topic_movable=jnp.asarray(options.topic_movable(state)),
        host_multi=jnp.asarray(bph > 1),
        host_cap=jnp.asarray(host_cap),
        total_cap=jnp.asarray((cap * alive[:, None]).sum(0) + 1e-12, dtype=jnp.float32),
        n_alive=jnp.asarray(max(1.0, float(alive.sum())), jnp.float32),
        n_valid=jnp.asarray(
            max(1.0, float(h["replica_valid"].sum())), jnp.float32
        ),
        total_disk_cap=jnp.asarray(
            float((h["disk_capacity"] * dmask).sum() + 1e-12), jnp.float32
        ),
        n_source=jnp.asarray(max(1, n_source), jnp.int32),
        n_dest=jnp.asarray(n_dest_int, jnp.int32),
        n_brokers=jnp.asarray(max(1, int(h["broker_valid"].sum())), jnp.int32),
        prior_dst_cdf=jnp.asarray(prior_cdf),
        prior_mix=jnp.asarray(prior_mix, jnp.float32),
    )


def _weights_by_name(chain: GoalChain) -> dict[str, float]:
    return {g.name: w for g, w in zip(chain.goals, chain.weights)}


_RES_DIST_NAMES = {
    Resource.CPU: "CpuUsageDistributionGoal",
    Resource.NW_IN: "NetworkInboundUsageDistributionGoal",
    Resource.NW_OUT: "NetworkOutboundUsageDistributionGoal",
    Resource.DISK: "DiskUsageDistributionGoal",
}
_CAP_NAMES = {
    Resource.CPU: "CpuCapacityGoal",
    Resource.NW_IN: "NetworkInboundCapacityGoal",
    Resource.NW_OUT: "NetworkOutboundCapacityGoal",
    Resource.DISK: "DiskCapacityGoal",
}


@dataclasses.dataclass(frozen=True)
class _Weights:
    """Per-term weights extracted from a GoalChain (0 = goal not in chain)."""

    offline: float
    rack: float
    replica_cap: float
    cap: tuple[float, float, float, float]  # by Resource index
    pot_nw_out: float
    replica_dist: float
    leader_dist: float
    res_dist: tuple[float, float, float, float]
    topic_dist: float
    lbin_dist: float
    pref_leader: float
    intra_cap: float
    intra_dist: float
    tie: float

    @staticmethod
    def from_chain(chain: GoalChain) -> "_Weights":
        w = _weights_by_name(chain)
        return _Weights(
            offline=w.get("OfflineReplicaGoal", 0.0),
            rack=w.get("RackAwareGoal", 0.0),
            replica_cap=w.get("ReplicaCapacityGoal", 0.0),
            cap=tuple(w.get(_CAP_NAMES[Resource(i)], 0.0) for i in range(4)),
            pot_nw_out=w.get("PotentialNwOutGoal", 0.0),
            replica_dist=w.get("ReplicaDistributionGoal", 0.0),
            leader_dist=w.get("LeaderReplicaDistributionGoal", 0.0),
            res_dist=tuple(w.get(_RES_DIST_NAMES[Resource(i)], 0.0) for i in range(4)),
            topic_dist=w.get("TopicReplicaDistributionGoal", 0.0),
            lbin_dist=w.get("LeaderBytesInDistributionGoal", 0.0),
            pref_leader=w.get("PreferredLeaderElectionGoal", 0.0),
            intra_cap=w.get("IntraBrokerDiskCapacityGoal", 0.0),
            intra_dist=w.get("IntraBrokerDiskUsageDistributionGoal", 0.0),
            tie=TIE_WEIGHT * min(chain.weights),
        )


log = logging.getLogger(__name__)

#: AOT-artifact worthwhileness floor (analyzer/prewarm.py): exporting a
#: fused program costs a second trace + one background compile, which
#: only pays off where tracing is the restart bill — production-scale
#: engines.  Toy engines (unit tests, tiny demo clusters) trace in
#: well under a second and skip the artifact tier entirely; the
#: manifest/boot-prewarm tier is scale-independent and always applies.
AOT_MIN_REPLICAS = 16_384
AOT_MIN_CANDIDATES = 1_024

#: per-round scalar keys of the (non-verbose) fused program's ys output —
#: the ONE definition `_fused_rounds_body` validates its dict against and
#: `Engine._fused_out_def` rebuilds the output treedef from WITHOUT
#: tracing (the AOT-hit path must not pay the trace artifacts skip)
FUSED_YS_KEYS = ("accepted", "ran", "stopped", "temperature", "cheap")

#: the additional per-round keys the diagnostics-on fused program emits
#: (OptimizerConfig.diagnostics): full-chain objective, per-goal violation
#: vector [G], per-kind acceptance counts, and prior-draw usage — all
#: read-only reductions riding the same single host extraction
FUSED_DIAG_YS_KEYS = FUSED_YS_KEYS + (
    "objective", "goal_viol", "acc_replica", "acc_swap", "acc_lead",
    "prior_cands", "prior_acc",
)

#: budget of AUTHORITATIVE (full goal chain) early-stop checks per run when
#: the cheap O(B) gate opens but delta-folded goals still have work — shared
#: by the fused in-graph loop and the legacy host loop so the two can never
#: disagree on how many checks (and therefore rounds) may run
FULL_CHECK_BUDGET = 2

#: cap on the segmented runner's rounds-per-slice growth: bounds the
#: number of distinct slice lengths (and therefore compiled slice
#: programs) per engine to log2(cap)+1
SEGMENT_MAX_ROUNDS = 64


def rounds_ran(ran) -> int:
    """Anneal rounds a run executed, from its fetched per-round `ran`
    flags (the timing record's `rounds`)."""
    return int(np.asarray(ran).sum())


def snapshot_host_tree(tree):
    """Device->host fetch that OWNS its memory.  `jax.device_get` alone is
    not a snapshot: on the CPU backend it returns zero-copy numpy views of
    the device buffers, and the slice programs donate their carry — the
    next slice dispatch reuses that memory and silently rewrites the
    "checkpoint" after capture.  np.array(copy=True) pins the bytes."""
    return jax.tree.map(lambda x: np.array(x, copy=True), jax.device_get(tree))


@dataclasses.dataclass
class CarryCheckpoint:
    """Host-side snapshot of a segmented anneal at a slice boundary —
    everything a resume needs to continue the remaining round schedule
    byte-identically: the next absolute round index, the full scan state
    (carry + seg tuple) as host numpy trees, and the per-round ys rows
    already fetched.  Captured while the device is idle (the slice
    boundary IS a blocking sync), so the copy races nothing; restoring
    onto a DIFFERENT mesh width is just `device_put` under the new mesh's
    shardings — the host trees carry no placement."""

    base: int
    carry: object
    seg: tuple
    ys_parts: list
    n_chains: int = 1
    meta: dict = dataclasses.field(default_factory=dict)


class SegmentContext:
    """Preemptible-execution request for one fused anneal (the device
    scheduler's bounded-wall preemption, fleet/scheduler.py).

    `slice_budget_s` bounds each device dispatch's wall clock
    (`fleet.scheduler.slice.budget.s`): the engine splits the round
    schedule into slices sized so one slice stays within the budget.
    `checkpoint` is called between slices on the dispatching thread — the
    scheduler uses it to pause this run while an URGENT request takes the
    device, so an urgent anneal never waits on more than ONE slice of
    background work.  The callback may block; when it returns, the run
    resumes from the carried scan state, byte-identically.

    Mesh fault tolerance (`tpu.mesh.ft.*`) rides the same boundaries:
    with `snapshot_every` > 0 and a `snapshot_sink`, every Nth slice
    boundary captures a host-side CarryCheckpoint (via the engine-supplied
    `capture` thunk) and hands it to the sink on a background thread —
    bounded to ONE in-flight persist (a due snapshot is skipped, not
    queued, while the previous one is still persisting).  Capture wall
    feeds `checkpoint_clock` so the supervisor excludes it from the hang
    budget like pause clocks.  `snapshot_every=0` (the default) is
    byte-for-byte today's behavior: `offer_snapshot` returns on one
    predicate with zero extra device work."""

    __slots__ = (
        "slice_budget_s", "checkpoint", "snapshot_every", "snapshot_sink",
        "checkpoint_clock", "snapshots_taken", "snapshots_skipped",
        "snapshot_seconds", "_snapshot_boundary", "_snapshot_worker",
        "_snapshot_lock",
    )

    def __init__(
        self,
        slice_budget_s: float,
        checkpoint=None,
        *,
        snapshot_every: int = 0,
        snapshot_sink=None,
        checkpoint_clock=None,
    ):
        self.slice_budget_s = slice_budget_s
        self.checkpoint = checkpoint
        self.snapshot_every = int(snapshot_every)
        self.snapshot_sink = snapshot_sink
        self.checkpoint_clock = checkpoint_clock
        self.snapshots_taken = 0
        self.snapshots_skipped = 0
        self.snapshot_seconds = 0.0
        self._snapshot_boundary = 0
        self._snapshot_worker = None
        self._snapshot_lock = threading.Lock()

    def offer_snapshot(self, capture) -> None:
        """Engine hook at a slice boundary (device idle): maybe capture a
        CarryCheckpoint via `capture()` and persist it in the background."""
        if self.snapshot_every <= 0 or self.snapshot_sink is None:
            return
        with self._snapshot_lock:
            self._snapshot_boundary += 1
            if self._snapshot_boundary % self.snapshot_every:
                return
            worker = self._snapshot_worker
            if worker is not None and worker.is_alive():
                # one in-flight snapshot: skip, never queue — a slow sink
                # must not stack copies of a 500k-replica carry
                self.snapshots_skipped += 1
                return
            t0 = time.monotonic()
            payload = capture()
            sink = self.snapshot_sink

            def persist():
                try:
                    sink(payload)
                except Exception:  # noqa: BLE001 — checkpointing must never
                    # take down the run it protects
                    log.warning("carry snapshot sink failed", exc_info=True)

            worker = threading.Thread(
                target=persist, daemon=True, name="carry-snapshot"
            )
            self._snapshot_worker = worker
            worker.start()
            dt = time.monotonic() - t0
            self.snapshots_taken += 1
            self.snapshot_seconds += dt
            if self.checkpoint_clock is not None:
                self.checkpoint_clock.add(dt)

    def wait_snapshot(self, timeout_s: float = 10.0) -> None:
        """Block until any in-flight persist finishes (run teardown /
        tests) — never raises."""
        worker = self._snapshot_worker
        if worker is not None:
            worker.join(timeout_s)


#: ambient segmented-execution request, set by the device scheduler
#: around a granted non-urgent dispatch.  A contextvar (not a thread
#: local) because the DeviceSupervisor runs the engine body on a worker
#: thread with the caller's context COPIED in — the seam must survive
#: that hop.  None (the default) keeps every run on the plain fused
#: path, byte-for-byte.
_SEGMENT_CTX: contextvars.ContextVar = contextvars.ContextVar(
    "engine_segment_context", default=None
)


def current_segment_context() -> SegmentContext | None:
    return _SEGMENT_CTX.get()


@contextlib.contextmanager
def segmented_execution(ctx: SegmentContext):
    """Run the enclosed dispatches in wall-bounded preemptible slices.
    The single-device fused path and the mesh layer's fused path
    (parallel/mesh.py `_run_segmented`) honor it — a mesh slice is a
    whole shard_map program, never a split collective; everything else
    ignores the context."""
    token = _SEGMENT_CTX.set(ctx)
    try:
        yield
    finally:
        _SEGMENT_CTX.reset(token)


class _FlatCallAdapter:
    """Adapter giving an AOT-deserialized FLAT executable the plain
    fused program's (statics, carry) -> (carry, ys) calling convention.

    The exported artifact is serialized over flat leaf tuples (custom
    pytree registrations do not survive jax.export serialization across
    processes); this adapter re-flattens/unflattens at the boundary.
    Always wrapped in `_WarmedFn`, so any drift between the artifact and
    the live avals falls back to the plain jit path."""

    __slots__ = ("_compiled", "_out_def")

    def __init__(self, compiled, out_def):
        self._compiled = compiled
        self._out_def = out_def

    def __call__(self, sx, carry):
        out = self._compiled(*jax.tree.leaves((sx, carry)))
        return jax.tree.unflatten(self._out_def, list(out))


class _WarmedFn:
    """A precompiled engine program with the plain jit as safety net.

    The compiled executable skips Python re-tracing; any call-time mismatch
    (aval/sharding drift the warm-up avals did not anticipate) falls back
    to the ordinary jit path, which recompiles correctly.  `on_fallback`
    (optional) fires once per fallback call — the engine uses it to keep
    the cold-start trace accounting honest when an AOT-served program
    turns out stale at call time (a trace IS paid then, on the request
    path, and boot_report must say so)."""

    __slots__ = ("_compiled", "_jit", "_on_fallback")

    def __init__(self, compiled, jit_fn, on_fallback=None):
        self._compiled = compiled
        self._jit = jit_fn
        self._on_fallback = on_fallback

    def __call__(self, *args):
        try:
            return self._compiled(*args)
        except Exception:  # noqa: BLE001 — warm path is an optimization only
            if self._on_fallback is not None:
                try:
                    self._on_fallback()
                except Exception:  # noqa: BLE001 — accounting must not block
                    pass
            return self._jit(*args)

    def __getattr__(self, item):  # .trace/.lower passthrough for tooling
        return getattr(self._jit, item)


class _WarmPool:
    """Shared priority warm pool: background compile of engine programs.

    ONE process-wide pool (not one per engine): boot prewarm enqueues
    many engines at once, and the ACTIVE bucket's programs must compile
    before any next-bucket speculation — a heap ordered by (priority,
    submission order) gives exactly that; equal priorities keep today's
    FIFO arrival order.  Lower priority value = compiles earlier.

    Starvation guard: in-flight compiles are not preempted, so a
    FOREGROUND submission (priority <= 0 — a live request's engine, the
    boot prewarm's active bucket) that finds every worker busy spawns an
    extra worker, up to `MAX_WORKERS` — a blocked `run()` must never
    wait minutes behind a speculative bucket's compile.

    DAEMON worker threads, not ThreadPoolExecutor: concurrent.futures
    joins its (non-daemon) workers at interpreter exit, so a compile
    stuck on an unresponsive device would block process shutdown forever.
    Warm-up must never outlive the process.
    """

    #: cap on demand-grown workers (the old per-engine pools ran 2 per
    #: engine; a handful of concurrent foreground engines is the realistic
    #: worst case, and compiles release the GIL in C++ anyway)
    MAX_WORKERS = 8

    #: how long interpreter exit waits for in-flight compiles
    EXIT_WAIT_S = 120.0

    def __init__(self):
        import itertools
        import threading

        self._cond = threading.Condition()
        self._heap: list = []
        self._seq = itertools.count()
        self._workers = 0
        self._busy = 0
        self._exit_hook = False

    def submit(self, thunk, *, priority: int = 0):
        import concurrent.futures as cf
        import heapq

        import threading

        fut = cf.Future()
        spawn = False
        with self._cond:
            heapq.heappush(self._heap, (priority, next(self._seq), fut, thunk))
            if (
                priority <= 0
                and self._busy >= self._workers
                and self._workers < self.MAX_WORKERS
            ):
                # reserve the slot INSIDE the lock: two racing foreground
                # submits must provision two workers, not both observe
                # the same count and spawn one
                self._workers += 1
                spawn = True
            self._cond.notify()
        if spawn:
            self._hook_exit()
            threading.Thread(
                target=self._work, daemon=True, name="engine-warm-grown"
            ).start()
        return fut

    def ensure_workers(self, n: int) -> None:
        import threading

        with self._cond:
            n = min(n, self.MAX_WORKERS)
            spawn = max(0, n - self._workers)
            self._workers += spawn
        if spawn:
            self._hook_exit()
        for i in range(spawn):
            threading.Thread(
                target=self._work, daemon=True, name=f"engine-warm-{i}"
            ).start()

    def _work(self):
        import heapq

        while True:
            with self._cond:
                while not self._heap:
                    self._cond.wait()
                _, _, fut, thunk = heapq.heappop(self._heap)
                self._busy += 1
            if not fut.set_running_or_notify_cancel():
                with self._cond:
                    self._busy -= 1
                continue
            try:
                fut.set_result(thunk())
            except BaseException as e:  # noqa: BLE001 — surface via _fn
                fut.set_exception(e)
            finally:
                with self._cond:
                    self._busy -= 1
                    self._cond.notify_all()

    def wait_idle(self, timeout_s: float) -> bool:
        """Wait until nothing is queued or compiling; False at the timeout."""
        with self._cond:
            return self._cond.wait_for(
                lambda: not self._heap and self._busy == 0, timeout_s
            )

    def _hook_exit(self) -> None:
        import atexit

        with self._cond:
            if self._exit_hook:
                return
            self._exit_hook = True
        atexit.register(self._drain_at_exit)

    def _drain_at_exit(self) -> None:
        """Interpreter exit with a compile in flight aborts the process
        (finalization tears the runtime down under the daemon worker):
        drop the queued work and let in-flight compiles finish, bounded
        so a wedged backend still cannot block exit forever."""
        with self._cond:
            for _, _, fut, _ in self._heap:
                fut.cancel()
            self._heap.clear()
        self.wait_idle(self.EXIT_WAIT_S)


_WARM_POOL = _WarmPool()


def warm_pool_wait_idle(timeout_s: float) -> bool:
    return _WARM_POOL.wait_idle(timeout_s)


def warm_pool_submit(thunk, *, priority: int = 0, workers: int = 2):
    """Run `thunk` on the shared warm pool; returns its Future.  The
    engine variants' compile targets and the AOT export task all ride
    this one queue, so priority ordering holds across engines."""
    _WARM_POOL.ensure_workers(max(1, workers))
    return _WARM_POOL.submit(thunk, priority=priority)


def start_warm_pool(targets, *, workers: int = 2, priority: int = 0):
    """Trace+lower+compile jitted programs on the shared warm pool.

    targets: [(name, jit_fn, avals)]; returns {name: Future[compiled]}.
    The ONE warm-overlap pool every engine variant shares: the plain
    Engine warms its fused/scan programs through it and the mesh layer
    (parallel/mesh.py) warms its shard_map'd whole-anneal program through
    the same helper, so ahead-of-use tracing always overlaps the caller's
    serial prelude the same way.  `priority` orders targets ACROSS
    engines (boot prewarm: the active bucket first, next-bucket
    speculation last); within one call, list order is preserved.
    """
    return {
        name: warm_pool_submit(
            lambda fn=fn, av=av: fn.trace(*av).lower().compile(),
            priority=priority,
            workers=workers,
        )
        for name, fn, av in targets
    }


def _relu(x):
    return jnp.maximum(x, 0.0)


def _uniform_idx(key: jax.Array, shape, n: jax.Array) -> jax.Array:
    """Uniform i32 indices in [0, n) with n a TRACED bound (n >= 1).

    `randint(0, axis_size)` would bake the PADDED axis size into the draw,
    making shape-bucketed and exact builds of the same cluster diverge;
    scaling a unit uniform by the runtime count keeps the candidate stream
    identical across padded shapes (and wastes no draws on padding rows).
    """
    u = jax.random.uniform(key, shape)
    return jnp.minimum((u * n.astype(jnp.float32)).astype(jnp.int32), n - 1)


#: The anneal's phases, as `jax.named_scope`s on the traced code: every
#: op of an engine program carries the innermost phase it was traced
#: under in its name stack (a profiler trace's `tf_op`), so a device trace
#: splits the anneal's time by phase.  A phase nests inside another (the
#: candidate gathers of `anneal_sample` inside a move kind's
#: `anneal_score`) except under the two that own their whole subtree:
#: refresh and eval recompute aggregates, globals and goal terms with the
#: helpers the step's phases use.  Scopes change op metadata only.
ANNEAL_PHASES = (
    "anneal_sample", "anneal_score", "anneal_accept", "anneal_refresh",
    "anneal_eval",
)
_OWNING_PHASES = frozenset({"anneal_refresh", "anneal_eval"})
#: the phase open on this thread while a program traces (warm-pool
#: workers trace concurrently)
_TRACING_PHASE = threading.local()


@contextlib.contextmanager
def anneal_phase(name: str):
    """Trace the enclosed code under the phase scope `name`."""
    outer = getattr(_TRACING_PHASE, "name", None)
    if outer == name or outer in _OWNING_PHASES:
        yield
        return
    _TRACING_PHASE.name = name
    try:
        with jax.named_scope(name):
            yield
    finally:
        _TRACING_PHASE.name = outer


def in_phase(name: str):
    """Decorator: the function traces under `anneal_phase(name)`."""

    def deco(fn):
        @functools.wraps(fn)
        def traced(*args, **kwargs):
            with anneal_phase(name):
                return fn(*args, **kwargs)

        return traced

    return deco


class Engine:
    """Compiled optimization engine bound to one ClusterShape.

    Trace-static: shape, goal weights, constraint thresholds, search
    config.  Runtime: EngineStatics (cluster data) + EngineCarry.  Reuse
    the same Engine across model generations via `rebind(state)`; only a
    changed ClusterShape (padded sizes) triggers recompilation.
    """

    #: mesh axis the replica/partition arrays are sharded over, or None
    #: (replicated model).  A CLASS attribute: the model-sharded twin
    #: (parallel/model_shard.py) shares this engine's __dict__ and
    #: overrides it at class level, so the plain engine's traced programs
    #: never see a collective.
    _model_axis: str | None = None

    def __init__(
        self,
        state: ClusterState,
        chain: GoalChain,
        constraint: BalancingConstraint = DEFAULT_CONSTRAINT,
        options: OptimizationOptions = DEFAULT_OPTIONS,
        config: OptimizerConfig = OptimizerConfig(),
        prior=None,
        prewarm_store=None,
    ):
        self.chain = chain
        self.constraint = constraint
        self.config = config
        self.w = _Weights.from_chain(chain)
        self.shape: ClusterShape = state.shape
        # effective candidate split (leadership + swap carved out of K);
        # swaps never take more than half the non-leadership budget so plain
        # relocations — the workhorse moves — keep a healthy share
        if config.intra_broker:
            # disk rebalancing: only intra-broker disk moves make sense
            self.K_l, self.K_s, self.K_r = 0, 0, config.num_candidates
        else:
            self.K_l = min(config.leadership_candidates, config.num_candidates - 1)
            self.K_s = min(
                config.swap_candidates, max(0, (config.num_candidates - self.K_l) // 2)
            )
            self.K_r = config.num_candidates - self.K_l - self.K_s
        self.d_thresh = float(constraint.capacity_threshold[int(Resource.DISK)])
        #: host-side destination layout of the CURRENT statics (filled by
        #: build_statics) — rebind_prior's no-device-fetch prior refresh
        self._statics_layout: dict = {}
        self.statics = build_statics(
            state, options, prior=prior, prior_full_shape=config.prior_enabled,
            layout_out=self._statics_layout,
        )
        self._scan = jax.jit(self._scan_impl)
        self._jit_refresh = jax.jit(self._refresh_impl)
        self._jit_objective = jax.jit(self._objective_impl)
        self._jit_plan = jax.jit(self._plan_impl)
        self._jit_violations = jax.jit(self._violations_impl)
        self._jit_cheap_violations = jax.jit(self._cheap_violations_impl)
        self._jit_round_prep = jax.jit(self._round_prep_impl)
        self._jit_init = jax.jit(self._init_impl)
        self._jit_init_from = jax.jit(self._init_from_impl)
        self._jit_eval = jax.jit(self._eval_impl)
        # the fused whole-anneal program: the carry is DONATED — its
        # buffers are reused for the output placement, so HBM holds one
        # EngineCarry at 500k-replica scale, not one per dispatch
        self._jit_run_fused = jax.jit(self._run_fused_impl, donate_argnums=(1,))
        self._jit_run_fused_verbose = None  # built lazily (adds per-round eval)
        # the fused STREAMING-CYCLE program (delta scatter + warm re-anneal
        # + reports + extraction payload as ONE dispatch): the live load
        # arrays are donated — the scatter rewrites them in place, exactly
        # like LiveState's standalone scatter program
        self._jit_run_cycle = jax.jit(self._cycle_impl, donate_argnums=(1, 2))
        #: cached (statics, cycle-statics, zero-loads placeholder) triple
        #: backing _cycle_statics
        self._cycle_sx: tuple | None = None
        #: segmented (preemptible) execution programs, built lazily on the
        #: first scheduler-granted slice run: the init program plus one
        #: slice program per rounds-per-slice length (powers of two)
        self._jit_seg_init = None
        self._seg_fns: dict[int, object] = {}
        self._warm_futures: dict | None = None
        #: analyzer/prewarm.py PrewarmStore — when present, precompile
        #: loads/saves the fused program's AOT artifact (warm-pool workers
        #: only; the request path never touches an artifact)
        self._prewarm_store = prewarm_store
        #: one trace-accounting record per engine (the fused program is
        #: jit-cached after its first trace, so later runs are not traces)
        self._fused_trace_recorded = False

    # ------------------------------------------------------------------
    # ahead-of-use compilation (warm start)
    # ------------------------------------------------------------------

    def precompile_async(self, *, priority: int = 0) -> None:
        """Trace+lower+compile every engine program on background threads,
        from abstract shapes only (no cluster data touched).

        The warm-start story: a restarted service pays Python tracing +
        XLA-cache loading before its first proposal (the reference's JVM
        never restarts its compiler — GoalOptimizer.java:124-175 amortizes
        via the precompute loop).  Kicking this off as soon as the engine
        exists lets that work overlap the optimizer's own serial prelude
        (input validation, before-stats report, host fetches): tracing in
        the pool interleaves with main-thread tracing under the GIL, and
        the XLA compile / persistent-cache load phases (GIL-released C++)
        run truly in parallel.  `run()` waits per-program via `_fn`, so
        programs are consumed in the same order they are submitted.
        `priority` orders this engine's compiles against other engines on
        the shared pool (boot prewarm: active bucket first).

        AOT (analyzer/prewarm.py, config tpu.prewarm.*): with a
        PrewarmStore bound, the fused program's serialized jax.export
        artifact is tried FIRST — a warm-disk restart skips Python
        tracing, not just the XLA compile.  The round-4 in-line attempt
        at this regressed warm start and broke multi-device modes
        because deserialization ran on the request path and
        artifacts had no staleness key; now loads run only HERE (a
        warm-pool worker), are keyed strictly on (bucket, config,
        chain/constraint, jax version, platform, exact avals), and any
        drift or corruption falls back to the fresh trace+compile below
        — with `_WarmedFn`'s plain-jit fallback as the last rung, so
        correctness never depends on an artifact.
        """
        if self._warm_futures is not None:
            return
        sx_av = self.statics_avals()
        key_av = jax.ShapeDtypeStruct((2,), jnp.uint32)
        carry_av = jax.eval_shape(self._init_impl, sx_av, key_av)
        plan_av = jax.eval_shape(self._plan_impl, sx_av, carry_av)
        temps_av = jax.ShapeDtypeStruct((self.config.steps_per_round,), jnp.float32)
        if self.config.fused_rounds:
            # the fused run() path touches exactly two programs: init and
            # the whole-anneal scan-of-scans (everything else is inlined
            # into it).  Fused first: it is by far the largest program.
            self._warm_futures = {
                "_jit_run_fused": warm_pool_submit(
                    self._fused_warm_thunk(sx_av, carry_av, priority),
                    priority=priority,
                ),
                **start_warm_pool(
                    [("_jit_init", self._jit_init, (sx_av, key_av))],
                    priority=priority,
                ),
            }
            return
        targets = [
            # scan first: it is by far the largest program and gates the
            # first round's dispatch — worker 1 spends its whole warm-up
            # on it while worker 2 clears the small programs in use order
            ("_scan", (sx_av, carry_av, temps_av, plan_av)),
            ("_jit_init", (sx_av, key_av)),
            ("_jit_plan", (sx_av, carry_av)),
            ("_jit_round_prep", (sx_av, carry_av)),
            ("_jit_eval", (sx_av, carry_av)),
        ]
        self._warm_futures = start_warm_pool(
            [(name, getattr(self, name), av) for name, av in targets],
            priority=priority,
        )

    # ------------------------------------------------------------------
    # AOT-serialized fused program (analyzer/prewarm.py)
    # ------------------------------------------------------------------

    def _bucket_key(self) -> str:
        from cruise_control_tpu.analyzer.prewarm import bucket_key

        return bucket_key(self.shape)

    def _record_fused_trace(self, source: str) -> None:
        """Per-engine, once: count how this engine's fused program came
        to exist ("fresh" Python trace vs "aot" artifact load) — the
        cold-start SLO's observable (compilation_cache.boot_report)."""
        if self._fused_trace_recorded:
            return
        self._fused_trace_recorded = True
        from cruise_control_tpu.common.compilation_cache import record_engine_trace

        record_engine_trace(self._bucket_key(), source=source)

    def _fused_flat_inputs(self, sx_av, carry_av):
        """(leaf avals, input treedef, donated argnums) of the fused
        program over FLAT leaf tuples — the only form jax.export
        artifacts can round-trip across processes (custom pytree
        registrations do not serialize).  The carry's leaves are donated,
        matching the plain program's donate_argnums=(1,).  Pure tree
        bookkeeping: NO tracing happens here — the AOT-hit path must
        never pay the trace the artifact exists to skip."""
        leaves_av, in_def = jax.tree.flatten((sx_av, carry_av))
        n_sx = len(jax.tree.leaves(sx_av))
        donate = tuple(range(n_sx, len(leaves_av)))
        return leaves_av, in_def, donate

    def _ys_keys(self) -> tuple:
        """Per-round ys keys of this engine's (non-verbose) fused program
        — FUSED_YS_KEYS, plus the diagnostics keys when the config
        compiles convergence diagnostics in."""
        return FUSED_DIAG_YS_KEYS if self.config.diagnostics else FUSED_YS_KEYS

    def _fused_out_def(self, carry_av):
        """Output treedef of the (non-verbose) fused program — (carry,
        per-round ys dict) — constructed WITHOUT tracing: dict pytrees
        flatten by sorted key, so the key set (`_ys_keys`, the same
        constant set `_fused_rounds_body` checks its ys against) pins the
        structure.  tests/test_prewarm.py asserts this equals the traced
        structure, and the artifact fingerprint's source digest retires
        artifacts whenever this file changes."""
        ys = {k: 0 for k in self._ys_keys()}
        return jax.tree.structure((carry_av, ys))

    def aot_worthwhile(self) -> bool:
        """Whether this engine's fused program is worth an AOT artifact
        (module thresholds above; tests lower them to exercise the
        ladder at toy scale)."""
        return (
            self.shape.R >= AOT_MIN_REPLICAS
            or self.config.num_candidates >= AOT_MIN_CANDIDATES
        )

    def _fused_warm_thunk(self, sx_av, carry_av, priority: int):
        """Warm-pool thunk for the fused program: AOT artifact first
        (zero Python tracing — inputs/outputs come from tree bookkeeping
        only), fresh trace+compile otherwise (exporting the fresh program
        in the background so the NEXT restart skips the trace)."""
        store = self._prewarm_store
        aot = None
        if store is not None and self.aot_worthwhile():
            try:
                max_rf = int(self.statics.part_replicas.shape[1])
                aot = store.aot_handle(self.shape, max_rf, self.config)
            except Exception:  # noqa: BLE001 — AOT is an optimization only
                aot = None

        def thunk():
            if aot is not None:
                leaves_av, in_def, donate = self._fused_flat_inputs(
                    sx_av, carry_av
                )
                compiled = aot.load(leaves_av, donate)
                if compiled is not None:
                    self._record_fused_trace("aot")
                    return _FlatCallAdapter(
                        compiled, self._fused_out_def(carry_av)
                    )
                self._record_fused_trace("fresh")
                result = (
                    self._jit_run_fused.trace(sx_av, carry_av).lower().compile()
                )

                # named like the program it flattens: the exported
                # module's ops keep `jit(_run_fused_impl)` in their name
                # stack, beside the anneal's phase scopes
                def _run_fused_impl(*leaves):
                    sx, carry = jax.tree.unflatten(in_def, list(leaves))
                    return tuple(jax.tree.leaves(self._run_fused_impl(sx, carry)))

                # persist + compile the exported twin off this (waited-on)
                # path: strictly lower priority than every pending compile
                aot.save_async(
                    _run_fused_impl, leaves_av, donate, priority=priority + 1_000
                )
                return result
            self._record_fused_trace("fresh")
            return self._jit_run_fused.trace(sx_av, carry_av).lower().compile()

        return thunk

    def statics_avals(self):
        """Abstract shapes of the bound statics (warm-up / eval_shape input)."""
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)),
            self.statics,
        )

    def _fn(self, name: str):
        """The program `name`, swapped to its precompiled executable once
        the background compile finishes; plain jit when warm-up is off or
        the compile failed (correctness never depends on the warm path)."""
        futs = self._warm_futures
        if futs is not None and name in futs:
            fut = futs.pop(name)
            # a fused program that falls back AT CALL TIME (stale AOT
            # executable, aval drift under rebind) pays a fresh trace on
            # the request path — record it so the cold-start report can
            # never claim "aot" for a bucket that actually re-traced
            cb = self._record_fused_fallback if name == "_jit_run_fused" else None
            try:
                setattr(
                    self,
                    name,
                    _WarmedFn(fut.result(), getattr(self, name), on_fallback=cb),
                )
            except Exception as e:  # noqa: BLE001 — fall back to lazy jit
                log.warning("engine precompile of %s failed: %r", name, e)
        return getattr(self, name)

    def _record_fused_fallback(self) -> None:
        if getattr(self, "_fused_fallback_recorded", False):
            return
        self._fused_fallback_recorded = True
        from cruise_control_tpu.common.compilation_cache import record_engine_trace

        record_engine_trace(self._bucket_key(), source="fresh")

    # convenience for call sites that held `engine.state`
    @property
    def state(self) -> ClusterState:
        return self.statics.state

    def rebind(
        self,
        state: ClusterState,
        options: OptimizationOptions = DEFAULT_OPTIONS,
        prior=None,
    ) -> "Engine":
        """Swap in a new model generation without recompiling.  `prior`
        (see build_statics) rides the statics, so a refreshed learned
        move-acceptance prior is a data rebind too, never a compile."""
        if state.shape != self.shape:
            raise ValueError(
                f"shape changed {self.shape} -> {state.shape}; build a new Engine"
            )
        self._statics_layout = {}
        self.statics = build_statics(
            state, options, prior=prior,
            prior_full_shape=self.config.prior_enabled,
            layout_out=self._statics_layout,
        )
        return self

    def rebind_prior(self, prior) -> None:
        """Refresh ONLY the learned-prior statics fields (prior_dst_cdf /
        prior_mix) from host-side data — the steady-state fused cycle's
        per-window rebind.  A full rebind() pays build_statics' batched
        device fetch every cycle; between reflattens the placement,
        capacity, and option masks those arrays derive from cannot have
        changed, so the prior (the one statics input that evolves every
        window) is the only field worth touching.  No device_get, no
        recompile (same shapes/dtypes)."""
        if not self.config.prior_enabled or prior is None:
            return
        cdf, mix = _prior_fields(
            prior, self.shape.num_topics, self.shape.B,
            self._statics_layout["dest_idx"],
        )
        self.statics = dataclasses.replace(
            self.statics,
            prior_dst_cdf=jnp.asarray(cdf),
            prior_mix=jnp.asarray(mix, jnp.float32),
        )

    def release(self) -> None:
        """Free this engine's device buffers (engine-cache LRU eviction).

        Deletes the ENGINE-DERIVED statics arrays explicitly — dropping the
        Python reference alone leaves the HBM release to GC timing, and a
        service cycling through cluster shapes would hold every evicted
        model generation until collection.  `statics.state` is the CALLER'S
        ClusterState (also alive as result.state_before, the facade's
        proposal cache, sibling engines under other configs): its arrays
        are never deleted here, only de-referenced so GC can reclaim them
        once the caller lets go.  The engine is unusable afterwards."""
        sx = self.statics
        if sx is not None:
            for f in dataclasses.fields(EngineStatics):
                if f.name == "state":
                    continue  # caller-owned model arrays: drop the ref only
                for leaf in jax.tree.leaves(getattr(sx, f.name)):
                    try:
                        leaf.delete()
                    except Exception:  # noqa: BLE001 — already-deleted/np
                        pass
        self.statics = None
        self._warm_futures = None
        self._seg_fns = {}
        self._jit_seg_init = None

    # ------------------------------------------------------------------
    # state <-> carry
    # ------------------------------------------------------------------

    def init_carry(self, key: jax.Array) -> EngineCarry:
        return self._fn("_jit_init")(self.statics, key)

    def init_carry_from(self, key: jax.Array, placement) -> EngineCarry:
        """Carry seeded from a PRIOR placement — the streaming controller's
        warm start: the previous accepted proposal's (replica_broker,
        replica_is_leader, replica_disk) arrays become the anneal's initial
        state while the statics keep the CURRENT cluster placement, so
        movement pricing still charges strays against what the executor
        would actually have to move."""
        rb, il, dk = placement
        # REAL copies, not views: the init program forwards these arrays
        # into the carry, and the fused run DONATES the carry — without a
        # copy the donated buffers would still be aliased by the caller's
        # placement (typically a published result's state_after), which
        # the run would then scribble over
        return self._fn("_jit_init_from")(
            self.statics, key,
            jnp.array(rb, jnp.int32, copy=True),
            jnp.array(il, bool, copy=True),
            jnp.array(dk, jnp.int32, copy=True),
        )

    def _init_impl(self, sx: EngineStatics, key: jax.Array) -> EngineCarry:
        """Zero carry + aggregate refresh as ONE program (seeded from the
        statics' current placement).  Building the zero arrays eagerly
        cost ~10 tiny jit dispatches whose sub-second compiles are not
        persisted — several seconds of per-process warmup for literal
        zero-fills."""
        st = sx.state
        return self._init_from_impl(
            sx, key, st.replica_broker, st.replica_is_leader, st.replica_disk
        )

    def _init_from_impl(
        self, sx: EngineStatics, key: jax.Array, rb: jax.Array,
        il: jax.Array, dk: jax.Array,
    ) -> EngineCarry:
        """Carry seeded from an arbitrary placement (the statics' own for
        cold starts, a prior accepted placement for warm starts);
        aggregates are refreshed from IT, so the carry is exactly what a
        run that produced this placement would have left.  One program,
        one refresh (the zero aggregates are overwritten by the refresh,
        so none are computed twice)."""
        B = self.shape.B
        zeros = EngineCarry(
            replica_broker=rb,
            replica_is_leader=il,
            replica_disk=dk,
            broker_load=jnp.zeros((B, NUM_RESOURCES), jnp.float32),
            broker_replica_count=jnp.zeros(B, jnp.int32),
            broker_leader_count=jnp.zeros(B, jnp.int32),
            broker_potential_nw_out=jnp.zeros(B, jnp.float32),
            broker_leader_bytes_in=jnp.zeros(B, jnp.float32),
            broker_topic_count=jnp.zeros((self.shape.num_topics, B), jnp.int32),
            part_rack_count=jnp.zeros(self._prc_shape(), jnp.int32),
            disk_load=jnp.zeros((B, self.shape.max_disks_per_broker), jnp.float32),
            host_load=jnp.zeros((self.shape.num_hosts, NUM_RESOURCES), jnp.float32),
            key=key,
        )
        return self._refresh_impl(sx, zeros)

    def carry_to_state(self, carry: EngineCarry, sx: EngineStatics | None = None) -> ClusterState:
        st = (sx or self.statics).state
        offline = ~(
            st.broker_alive[carry.replica_broker]
            & st.disk_alive[carry.replica_broker, carry.replica_disk]
        )
        return dataclasses.replace(
            st,
            replica_broker=carry.replica_broker,
            replica_is_leader=carry.replica_is_leader,
            replica_disk=carry.replica_disk,
            replica_offline=offline & st.replica_valid,
        )

    def _prc_shape(self) -> tuple[int, int]:
        """Rows x racks of the carry's part_rack_count — the model-sharded
        twin overrides the row count with its shard-local partition rows."""
        return (self.shape.P, self.shape.num_racks)

    def _psum_if_sharded(self, x):
        """Finish a replica/partition-axis reduction: psum over the model
        axis when the model is sharded, the identity otherwise."""
        if self._model_axis is None:
            return x
        return jax.lax.psum(x, self._model_axis)

    def _refresh_impl(self, sx: EngineStatics, carry: EngineCarry) -> EngineCarry:
        state = self.carry_to_state(carry, sx)
        with collectives.model_axis_scope(self._model_axis):
            agg = compute_aggregates(state)
        hseg = jnp.where(state.broker_valid, state.broker_host, self.shape.num_hosts)
        host_load = jax.ops.segment_sum(
            agg.broker_load, hseg, num_segments=self.shape.num_hosts + 1
        )[: self.shape.num_hosts]
        return dataclasses.replace(
            carry,
            broker_load=agg.broker_load,
            broker_replica_count=agg.broker_replica_count,
            broker_leader_count=agg.broker_leader_count,
            broker_potential_nw_out=agg.broker_potential_nw_out,
            broker_leader_bytes_in=agg.broker_leader_bytes_in,
            broker_topic_count=agg.broker_topic_count,
            part_rack_count=agg.part_rack_count,
            disk_load=agg.disk_load,
            host_load=host_load,
        )

    def _objective_impl(self, sx: EngineStatics, carry: EngineCarry):
        with collectives.model_axis_scope(self._model_axis):
            obj, _, _ = self.chain.evaluate(
                self.carry_to_state(carry, sx), constraint=self.constraint,
                score_dtype=self.config.score_dtype,
            )
        return obj

    def carry_objective(self, sx: EngineStatics, carry: EngineCarry):
        """Scalar SA objective from carry aggregates (traceable, collective-free).

        Matches the delta-decomposed objective the step optimizes (broker
        terms + rack + offline + tie), NOT the full goal-chain evaluation.
        """
        g = self._globals(sx, carry)
        b = jnp.arange(self.shape.B)
        terms = self._broker_terms(
            sx,
            b,
            carry.broker_load,
            carry.broker_replica_count,
            carry.broker_leader_count,
            carry.broker_potential_nw_out,
            carry.broker_leader_bytes_in,
            g,
        ).sum()
        rack = self._psum_if_sharded(
            jnp.maximum(carry.part_rack_count - 1, 0).sum()
        ).astype(jnp.float32)
        terms += self.w.rack * rack / sx.n_valid
        st = sx.state
        offline = self._psum_if_sharded(
            (
                st.replica_valid
                & ~(
                    st.broker_alive[carry.replica_broker]
                    & st.disk_alive[carry.replica_broker, carry.replica_disk]
                )
            ).sum()
        )
        terms += self.w.offline * offline.astype(jnp.float32) / sx.n_valid
        terms += self._tie_term(sx, g["pct_sum"], g["pct_sumsq"])
        return terms

    def _cheap_violations_impl(self, sx: EngineStatics, carry: EngineCarry):
        """O(B) lower-bound signal: delta-decomposed objective minus the
        dispersion tiebreaker.  Misses goals folded into candidate deltas
        only (topic distribution), so it can read zero with work left —
        used as a gate for the authoritative check below."""
        g = self._globals(sx, carry)
        return self.carry_objective(sx, carry) - self._tie_term(
            sx, g["pct_sum"], g["pct_sumsq"]
        )

    def _violations_impl(self, sx: EngineStatics, carry: EngineCarry):
        """Authoritative early-stop signal: the WORST per-goal violation
        from the full goal chain — evaluated against the carry's incremental
        aggregates, so no O(R) segment-sums are recomputed."""
        return self._eval_impl(sx, carry)[1]

    @in_phase("anneal_eval")
    def _eval_impl(self, sx: EngineStatics, carry: EngineCarry):
        """(full objective, worst per-goal violation) as ONE program.

        run() needs the objective at round start (temperature scaling) and
        the violation max at the early-stop gate; tracing the full goal
        chain once instead of twice halves the chain's share of the
        warm-start trace bill."""
        obj, viol = self._eval_vec_impl(sx, carry)
        return obj, jnp.max(viol)

    @in_phase("anneal_eval")
    def _eval_vec_impl(self, sx: EngineStatics, carry: EngineCarry):
        """(full objective, per-goal violation VECTOR f32[G]) from the
        carry's incremental aggregates — the convergence-diagnostics
        variant of _eval_impl (the ledger's per-round goal trajectory)."""
        from cruise_control_tpu.models.aggregates import BrokerAggregates

        agg = BrokerAggregates(
            broker_load=carry.broker_load,
            broker_replica_count=carry.broker_replica_count,
            broker_leader_count=carry.broker_leader_count,
            broker_potential_nw_out=carry.broker_potential_nw_out,
            broker_leader_bytes_in=carry.broker_leader_bytes_in,
            broker_topic_count=carry.broker_topic_count,
            part_rack_count=carry.part_rack_count,
            disk_load=carry.disk_load,
        )
        with collectives.model_axis_scope(self._model_axis):
            obj, viol, _ = self.chain.evaluate(
                self.carry_to_state(carry, sx), agg=agg, constraint=self.constraint,
                score_dtype=self.config.score_dtype,
            )
        return obj, viol

    def _plan_impl(self, sx: EngineStatics, carry: EngineCarry):
        """Importance-sampling + movement-pricing plan from current aggregates."""
        probs, unit = self._plan_probs(sx, carry)
        return self._plan_build(sx, carry, probs, unit)

    def _plan_probs(self, sx: EngineStatics, carry: EngineCarry):
        """Per-broker sampling probabilities + movement-pricing unit — the
        O(B + T·B) half of the plan, replicated-broker math shared verbatim
        by the plain engine and the model-sharded twin."""
        st = sx.state
        B = self.shape.B
        g = self._globals(sx, carry)
        b = jnp.arange(B)
        w = self._broker_terms(
            sx,
            b,
            carry.broker_load,
            carry.broker_replica_count,
            carry.broker_leader_count,
            carry.broker_potential_nw_out,
            carry.broker_leader_bytes_in,
            g,
        )
        # stranded replicas on dead brokers/disks carry the offline-goal mass
        dead = st.broker_valid & ~sx.alive
        w = w + self.w.offline * jnp.where(
            dead, carry.broker_replica_count.astype(jnp.float32), 0.0
        ) / sx.n_valid
        # topic-distribution violations live in [T, B] cells that
        # _broker_terms cannot see — without this term the sampler goes
        # blind exactly when topic imbalance is the last goal standing
        # (post-decommission tails) and convergence stalls on uniform luck
        if self.w.topic_dist != 0.0:
            tt = self.constraint.topic_replica_count_balance_threshold
            upper = jnp.ceil(g["topic_avg"] * tt)[:, None]
            lower = jnp.floor(g["topic_avg"] * max(0.0, 2.0 - tt))[:, None]
            cnt = carry.broker_topic_count.astype(jnp.float32)
            cells = _relu(cnt - upper) + _relu(lower - cnt)  # [T, B]
            w = w + self.w.topic_dist * jnp.where(
                sx.alive, cells.sum(0), 0.0
            ) / g["total_count"]
        w = jnp.maximum(jnp.where(st.broker_valid, w, 0.0), 0.0)
        total = w.sum()
        uni = jnp.where(st.broker_valid, 1.0, 0.0)
        uni = uni / jnp.maximum(uni.sum(), 1.0)
        probs = jnp.where(total > 1e-12, w / jnp.maximum(total, 1e-12), uni)
        obj = self.carry_objective(sx, carry)
        unit = obj / sx.n_valid
        return probs, unit

    def _plan_build(self, sx: EngineStatics, carry: EngineCarry, probs, unit):
        """The O(R) half of the plan: per-broker replica counts and the
        broker-grouped replica order.  The model-sharded twin overrides
        this with shard-local counts/order + the psum'd global counts."""
        st = sx.state
        B, R = self.shape.B, self.shape.R
        seg = jnp.where(st.replica_valid, carry.replica_broker, B)
        count = jax.ops.segment_sum(
            jnp.ones(R, jnp.int32), seg, num_segments=B + 1
        )[:B]
        start = jnp.concatenate(
            [jnp.zeros(1, jnp.int32), jnp.cumsum(count)[:-1].astype(jnp.int32)]
        )
        return SamplingPlan(
            broker_cdf=jnp.cumsum(probs),
            order=jnp.argsort(seg).astype(jnp.int32),
            start=start,
            count=count,
            replica_cost=self.config.replica_move_cost * unit,
            lead_cost=self.config.leadership_move_cost * unit,
        )

    # ------------------------------------------------------------------
    # objective terms
    # ------------------------------------------------------------------

    @in_phase("anneal_score")
    def _globals(self, sx: EngineStatics, carry: EngineCarry):
        """Per-step frozen global scalars, O(B + T·B) from aggregates."""
        st = sx.state
        am = sx.alive
        load = jnp.where(am[:, None], carry.broker_load, 0.0)
        total_load = load.sum(0)  # [4]
        avg_pct = total_load / sx.total_cap
        counts = jnp.where(am, carry.broker_replica_count, 0)
        total_count = counts.sum()
        lcounts = jnp.where(am, carry.broker_leader_count, 0)
        total_lcount = lcounts.sum()
        lbin = jnp.where(am, carry.broker_leader_bytes_in, 0.0)
        total_lbin = lbin.sum()
        topic_total = jnp.where(am[None, :], carry.broker_topic_count, 0).sum(1)  # [T]
        dmask = st.disk_alive & am[:, None]
        total_disk_load = jnp.where(dmask, carry.disk_load, 0.0).sum()
        # dispersion tiebreaker sufficient statistics (utilization pct)
        pct = jnp.where(am[:, None], carry.broker_load / (st.broker_capacity + 1e-12), 0.0)
        return dict(
            total_load=total_load,
            avg_pct=avg_pct,
            avg_count=total_count.astype(jnp.float32) / sx.n_alive,
            total_count=jnp.maximum(total_count.astype(jnp.float32), 1.0),
            avg_lcount=total_lcount.astype(jnp.float32) / sx.n_alive,
            total_lcount=jnp.maximum(total_lcount.astype(jnp.float32), 1.0),
            avg_lbin=total_lbin / sx.n_alive,
            total_lbin=total_lbin + 1e-12,
            topic_avg=topic_total.astype(jnp.float32) / sx.n_alive,
            total_disk_load=total_disk_load + 1e-12,
            pct_sum=pct.sum(0),  # [4]
            pct_sumsq=(pct * pct).sum(0),  # [4]
        )

    def _broker_terms(self, sx, b, load, rcount, lcount, pot, lbin, g):
        """Weighted objective contribution of broker(s) b given hypothetical
        per-broker stats.  All inputs may carry a leading candidate axis.

        Mirrors (in delta-decomposable form): CapacityGoal (broker
        granularity), ReplicaCapacityGoal, PotentialNwOutGoal,
        ResourceDistributionGoal, Replica/LeaderReplicaDistributionGoal,
        LeaderBytesInDistributionGoal — see the goal classes for the
        reference citations.
        """
        st = sx.state
        w = self.w
        c = self.constraint
        cap = st.broker_capacity[b]  # [..., 4]
        alive = sx.alive[b]
        # mixed-precision accumulation (config analyzer.precision.score.dtype):
        # each goal term is still computed in f32 (the reluses against
        # capacities need the dynamic range), but the running per-broker SUM
        # of terms — the hottest accumulate in the step program, inlined ~8x —
        # may ride bf16.  f32 is the default, and `_acc` is the identity
        # there (same-dtype astype returns the input tracer), so the default
        # traced graph is byte-identical to the pre-flag one: the fp32 pin.
        lowp = self.config.score_dtype != "float32"
        acc_dt = jnp.dtype(self.config.score_dtype)
        _acc = (lambda x: x.astype(acc_dt)) if lowp else (lambda x: x)
        out = jnp.zeros(jnp.shape(b), acc_dt if lowp else jnp.float32)
        # per-resource constants as [4] vectors: one vectorized expression
        # instead of a 4-iteration Python loop — this function is inlined
        # ~8x into the step program, so per-resource unrolling multiplies
        # the traced-graph size (and with it warm-start trace time)
        cth = np.asarray(c.capacity_threshold, np.float32)
        host_res = np.asarray(
            [Resource(r).is_host_resource for r in range(NUM_RESOURCES)]
        )
        w_cap = np.asarray(w.cap, np.float32)

        # capacity goals (broker granularity; host granularity handled in
        # _host_terms for multi-broker hosts)
        single = ~sx.host_multi[st.broker_host[b]]
        excess = _relu(load - cth * cap)  # [..., 4]
        gate = alive[..., None] & (single[..., None] | ~host_res)
        out += _acc((jnp.where(gate, excess, 0.0) * (w_cap / sx.total_cap)).sum(-1))

        # replica capacity
        exc = _relu((rcount - c.max_replicas_per_broker).astype(jnp.float32))
        out += _acc(w.replica_cap * jnp.where(alive, exc, 0.0) / sx.n_valid)

        # potential nw out
        r = int(Resource.NW_OUT)
        exc = _relu(pot - c.capacity_threshold[r] * cap[..., r])
        out += _acc(w.pot_nw_out * jnp.where(alive, exc, 0.0) / sx.total_cap[r])

        # resource distribution bands
        t_bal = np.asarray(c.balance_threshold, np.float32)
        t_low = np.maximum(0.0, 2.0 - t_bal)
        w_dist = np.asarray(w.res_dist, np.float32)
        upper = g["avg_pct"] * t_bal * cap
        lower = g["avg_pct"] * t_low * cap
        term = _relu(load - upper) + _relu(lower - load)
        out += _acc(
            (
                jnp.where(alive[..., None], term, 0.0)
                * (w_dist / (g["total_load"] + 1e-12))
            ).sum(-1)
        )

        # replica count distribution
        t = c.replica_count_balance_threshold
        upper = jnp.ceil(g["avg_count"] * t)
        lower = jnp.floor(g["avg_count"] * max(0.0, 2.0 - t))
        rc = rcount.astype(jnp.float32)
        term = _relu(rc - upper) + _relu(lower - rc)
        out += _acc(w.replica_dist * jnp.where(alive, term, 0.0) / g["total_count"])

        # leader count distribution
        t = c.leader_replica_count_balance_threshold
        upper = jnp.ceil(g["avg_lcount"] * t)
        lower = jnp.floor(g["avg_lcount"] * max(0.0, 2.0 - t))
        lc = lcount.astype(jnp.float32)
        term = _relu(lc - upper) + _relu(lower - lc)
        out += _acc(w.leader_dist * jnp.where(alive, term, 0.0) / g["total_lcount"])

        # leader bytes-in distribution (upper band only)
        t = c.balance_threshold[int(Resource.NW_IN)]
        term = _relu(lbin - g["avg_lbin"] * t)
        out += _acc(w.lbin_dist * jnp.where(alive, term, 0.0) / g["total_lbin"])

        # downstream consumers (plan weights, scalar objective reduction)
        # expect f32; a no-op when the accumulator already is
        return out.astype(jnp.float32)

    def _host_terms(self, sx, h, hload):
        """Host-granularity capacity terms for multi-broker hosts
        (reference CapacityGoal host/broker split)."""
        c = self.constraint
        hcap = sx.host_cap[h]
        multi = sx.host_multi[h]
        # vectorized over resources (see _broker_terms): host resources only
        w_cap = np.asarray(
            [
                self.w.cap[r] if Resource(r).is_host_resource else 0.0
                for r in range(NUM_RESOURCES)
            ],
            np.float32,
        )
        cth = np.asarray(c.capacity_threshold, np.float32)
        excess = _relu(hload - cth * hcap)  # [..., 4]
        return (
            jnp.where(multi[..., None], excess, 0.0) * (w_cap / sx.total_cap)
        ).sum(-1)

    def _disk_terms(self, sx, b, disk_row, broker_disk_load, g):
        """Intra-broker disk goal terms for broker(s) b.

        disk_row: hypothetical f32[..., D] per-logdir load of broker b.
        broker_disk_load: its sum (for the per-broker distribution band).
        """
        st = sx.state
        w = self.w
        if w.intra_cap == 0.0 and w.intra_dist == 0.0:
            return jnp.zeros(jnp.shape(b), jnp.float32)
        dcap = st.disk_capacity[b]  # [..., D]
        dalive = st.disk_alive[b] & sx.alive[b][..., None]
        out = jnp.zeros(jnp.shape(b), jnp.float32)
        # IntraBrokerDiskCapacityGoal
        cap_term = jnp.where(
            dalive, _relu(disk_row - self.d_thresh * dcap), disk_row
        ).sum(-1)
        out += w.intra_cap * cap_term / sx.total_disk_cap
        # IntraBrokerDiskUsageDistributionGoal
        bcap = jnp.where(dalive, dcap, 0.0).sum(-1, keepdims=True)
        avg_pct = broker_disk_load[..., None] / (bcap + 1e-12)
        t = self.constraint.balance_threshold[int(Resource.DISK)]
        upper = avg_pct * t * dcap
        lower = avg_pct * max(0.0, 2.0 - t) * dcap
        dist = jnp.where(dalive, _relu(disk_row - upper) + _relu(lower - disk_row), 0.0).sum(-1)
        out += w.intra_dist * dist / g["total_disk_load"]
        return out

    def _tie_term(self, sx, pct_sum, pct_sumsq):
        """Dispersion tiebreaker: sum over resources of std of utilization pct.

        Inputs may carry a leading candidate axis — reduce ONLY the trailing
        resource axis, or every candidate's delta absorbs the whole batch's
        variance as a constant offset that vetoes small improvements.
        """
        n = sx.n_alive
        var = _relu(pct_sumsq / n - (pct_sum / n) ** 2)
        return self.w.tie * jnp.sqrt(var + 1e-18).sum(-1)

    # ------------------------------------------------------------------
    # candidate generation + delta evaluation
    # ------------------------------------------------------------------

    @in_phase("anneal_sample")
    def _sample_sources(self, sx, key: jax.Array, n: int, plan) -> jax.Array:
        """n source replica ids; `importance_fraction` of them drawn by a
        two-stage plan draw (broker ~ categorical(objective contribution),
        then a replica uniformly on that broker), the rest uniform over the
        valid prefix (sx.n_source — see EngineStatics: padded-R invariance)."""
        k1, k3, k4, k5 = jax.random.split(key, 4)
        n_imp = (
            int(round(n * self.config.importance_fraction)) if plan is not None else 0
        )
        r = _uniform_idx(k1, (n - n_imp,), sx.n_source)
        if n_imp:
            u = jax.random.uniform(k3, (n_imp,))
            bsel = jnp.clip(
                jnp.searchsorted(plan.broker_cdf, u, side="right"), 0, sx.n_brokers - 1
            ).astype(jnp.int32)
            j = (jax.random.uniform(k4, (n_imp,)) * plan.count[bsel]).astype(jnp.int32)
            r_imp = plan.order[jnp.clip(plan.start[bsel] + j, 0, self.shape.R - 1)]
            fallback = _uniform_idx(k5, (n_imp,), sx.n_source)
            r_imp = jnp.where(plan.count[bsel] > 0, r_imp, fallback)
            r = jnp.concatenate([r, r_imp])
        return r

    @in_phase("anneal_sample")
    def _sample_dests(
        self, sx, key: jax.Array, n: int, r: jax.Array, *, with_mask: bool = False
    ):
        """n destination POSITIONS (indices into dest_ids) for the replica
        moves whose sampled sources are `r`.

        Default (prior_enabled=False): the uniform draw over the real
        destination head — today's program, untouched.  With the learned
        move-acceptance prior compiled in, each draw takes the
        per-source-topic prior CDF with probability `prior_mix` and the
        uniform branch otherwise.  The uniform branch consumes the SAME
        key with the SAME arithmetic as the default, and the prior's two
        extra draws ride a fold_in-derived key no other stream reads, so
        a cold prior (mix 0) reproduces the uniform stream bit-for-bit —
        the controller's parity guarantee (tests/test_controller.py).

        `with_mask` (convergence diagnostics) additionally returns the
        per-draw took-the-prior-branch mask — a pure read of the existing
        mix draw, so the destination stream itself is untouched.
        """
        uni = _uniform_idx(key, (n,), sx.n_dest)
        if not self.config.prior_enabled:
            if with_mask:
                return uni, jnp.zeros((n,), bool)
            return uni
        k_m, k_p = jax.random.split(jax.random.fold_in(key, 1))
        t = self._take_rows(
            sx, None, jnp.minimum(r, self.shape.R - 1), ("topic",)
        )["topic"]
        cdf = sx.prior_dst_cdf[t]  # [n, B] per-topic inclusive CDF
        u = jax.random.uniform(k_p, (n,))
        p_idx = jnp.minimum(
            jnp.sum(u[:, None] >= cdf, axis=-1).astype(jnp.int32), sx.n_dest - 1
        )
        use = jax.random.uniform(k_m, (n,)) < sx.prior_mix
        out = jnp.where(use, p_idx, uni)
        if with_mask:
            return out, use
        return out

    @in_phase("anneal_sample")
    def _slice_draws(self, slice_, *arrays):
        """Candidate-axis sharding (parallel/mesh.py): keep only one mesh
        shard's contiguous slice of the full-K draw vectors.

        Drawing the FULL candidate index stream from a replicated key and
        slicing afterwards keeps the stream identical for every mesh size
        — the 1-vs-N-device byte-parity guarantee — while the expensive
        per-candidate evaluation below the draws runs on K/n rows only.
        Arrays are edge-padded to n*ceil(K/n) so the tiled all_gather on
        the far side reassembles the exact full-K order (padding rows are
        discarded after the gather).  slice_=None is the single-device
        identity (the plain engine's path)."""
        if slice_ is None:
            return arrays if len(arrays) > 1 else arrays[0]
        idx, n = slice_
        out = []
        for a in arrays:
            size = -(-a.shape[0] // n)
            pad = n * size - a.shape[0]
            if pad:
                a = jnp.concatenate(
                    [a, jnp.broadcast_to(a[:1], (pad,) + a.shape[1:])]
                )
            out.append(jax.lax.dynamic_slice_in_dim(a, idx * size, size))
        return tuple(out) if len(out) > 1 else out[0]

    # ------------------------------------------------------------------
    # replica-axis row providers (the model-sharding seam)
    #
    # Candidate generation reads per-replica columns at sampled ids and
    # per-partition cells at member/partition ids.  The plain engine (and
    # the replicated mesh) fancy-index the full arrays directly; the
    # model-sharded twin (parallel/model_shard.py) overrides these four
    # methods with ownership-masked local gathers + a psum over MODEL_AXIS
    # (ids are GLOBAL; exactly one shard owns each row, the rest
    # contribute zeros).  Everything above these seams is kind-agnostic
    # replicated math, so the candidate functions themselves are shared
    # verbatim by both modes.
    # ------------------------------------------------------------------

    #: seam field -> (carry | state, attribute).  "orig_*" read the
    #: STATICS placement (movement pricing charges strays against the
    #: pre-optimization cluster, not the evolving carry).
    _ROW_SOURCES = {
        "broker": ("carry", "replica_broker"),
        "is_lead": ("carry", "replica_is_leader"),
        "disk": ("carry", "replica_disk"),
        "part": ("state", "replica_partition"),
        "topic": ("state", "replica_topic"),
        "pos": ("state", "replica_pos"),
        "valid": ("state", "replica_valid"),
        "load_leader": ("state", "replica_load_leader"),
        "load_follower": ("state", "replica_load_follower"),
        "orig_broker": ("state", "replica_broker"),
        "orig_disk": ("state", "replica_disk"),
        "orig_lead": ("state", "replica_is_leader"),
    }

    def _row_source(self, sx, carry, field):
        kind, attr = self._ROW_SOURCES[field]
        return getattr(carry if kind == "carry" else sx.state, attr)

    @in_phase("anneal_sample")
    def _take_rows(self, sx, carry, ids, fields):
        """{field: column[ids]} for (global) replica ids `ids`."""
        return {f: self._row_source(sx, carry, f)[ids] for f in fields}

    @in_phase("anneal_sample")
    def _take_members(self, sx, part):
        """[K, max_rf] partition->replica member table rows at (global)
        partition ids (member entries are global replica ids; >= R pads)."""
        return sx.part_replicas[part]

    @in_phase("anneal_sample")
    def _member_field(self, sx, carry, members, field, fill):
        """Per-member column gather with the table's >= R padding masked
        to `fill` (members carry global replica ids)."""
        src = self._row_source(sx, carry, field)
        vals = src[jnp.minimum(members, self.shape.R - 1)]
        return jnp.where(members < self.shape.R, vals, fill)

    def _rack_cell(self, carry, part, rack):
        """part_rack_count[(global) partition, rack] as f32."""
        return carry.part_rack_count[part, rack].astype(jnp.float32)

    @in_phase("anneal_score")
    def _replica_candidates(
        self, sx, carry: EngineCarry, key: jax.Array, g, plan=None, slice_=None
    ):
        """K_r replica-move candidates -> (delta, src, dst, part, payload)."""
        st = sx.state
        K = self.K_r
        with anneal_phase("anneal_sample"):
            k1, k2 = jax.random.split(key)
            r = self._sample_sources(sx, k1, K, plan)
            if self.config.diagnostics:
                # same draws, plus the took-the-prior-branch mask so
                # per-round prior usage can be counted — placements untouched
                pos, from_prior = self._sample_dests(sx, k2, K, r, with_mask=True)
                dst = sx.dest_ids[pos]
                r, dst, from_prior = self._slice_draws(slice_, r, dst, from_prior)
            else:
                dst = sx.dest_ids[self._sample_dests(sx, k2, K, r)]
                r, dst = self._slice_draws(slice_, r, dst)
                from_prior = None
        fields = ["broker", "part", "disk", "topic", "valid", "is_lead",
                  "load_leader", "load_follower"]
        if self.w.pref_leader != 0.0:
            fields.append("pos")
        if plan is not None and self.config.replica_move_cost:
            fields.append("orig_broker")
        rows = self._take_rows(sx, carry, r, tuple(fields))
        src = rows["broker"]
        part = rows["part"]

        # feasibility (reference GoalUtils.legitMove:153 + exclusions)
        offline = ~(st.broker_alive[src] & st.disk_alive[src, rows["disk"]])
        movable = sx.topic_movable[rows["topic"]] | offline
        feasible = rows["valid"] & movable & (src != dst)
        # no second replica of the partition on dst (reference
        # ClusterModel.relocateReplica precondition)
        members = self._take_members(sx, part)  # [K, max_rf]
        member_broker = self._member_field(sx, carry, members, "broker", -1)
        feasible &= ~(member_broker == dst[:, None]).any(axis=1)

        is_lead = rows["is_lead"]
        load = jnp.where(
            is_lead[:, None], rows["load_leader"], rows["load_follower"]
        )  # [K, 4]
        load = jnp.where(rows["valid"][:, None], load, 0.0)

        # destination logdir: most-free alive disk on dst
        ddst_pct = carry.disk_load[dst] / (st.disk_capacity[dst] + 1e-12)
        ddst_pct = jnp.where(st.disk_alive[dst], ddst_pct, jnp.inf)
        d_dst = jnp.argmin(ddst_pct, axis=1).astype(jnp.int32)
        d_src = rows["disk"]

        pot = rows["load_leader"][:, int(Resource.NW_OUT)]
        lbin = jnp.where(is_lead, rows["load_leader"][:, int(Resource.NW_IN)], 0.0)
        dcount = jnp.ones(r.shape, jnp.int32)
        dlcount = is_lead.astype(jnp.int32)

        delta = self._move_delta(
            sx,
            carry,
            g,
            src=src,
            dst=dst,
            dload_src=-load,
            dload_dst=load,
            dcount=dcount,
            dlcount=dlcount,
            dpot=pot,
            dlbin=lbin,
            d_src=d_src,
            d_dst=d_dst,
            ddisk=load[:, int(Resource.DISK)],
        )

        # rack cells (reference RackAwareGoal)
        rack_s, rack_d = st.broker_rack[src], st.broker_rack[dst]
        c_s = self._rack_cell(carry, part, rack_s)
        c_d = self._rack_cell(carry, part, rack_d)
        drack = (_relu(c_s - 2.0) - _relu(c_s - 1.0)) + (_relu(c_d) - _relu(c_d - 1.0))
        delta += self.w.rack * jnp.where(rack_s != rack_d, drack, 0.0) / sx.n_valid

        # topic cells (reference TopicReplicaDistributionGoal)
        if self.w.topic_dist != 0.0:
            t = rows["topic"]
            tt = self.constraint.topic_replica_count_balance_threshold
            upper = jnp.ceil(g["topic_avg"][t] * tt)
            lower = jnp.floor(g["topic_avg"][t] * max(0.0, 2.0 - tt))

            def cell(cnt):
                return _relu(cnt - upper) + _relu(lower - cnt)

            ct_s = carry.broker_topic_count[t, src].astype(jnp.float32)
            ct_d = carry.broker_topic_count[t, dst].astype(jnp.float32)
            dtop = (cell(ct_s - 1.0) - cell(ct_s)) + (cell(ct_d + 1.0) - cell(ct_d))
            delta += self.w.topic_dist * dtop / g["total_count"]

        # offline-replica term (reference OptimizationVerifier BROKEN_BROKERS)
        dst_ok = st.broker_alive[dst] & st.disk_alive[dst, d_dst]
        doff = (~dst_ok).astype(jnp.float32) - offline.astype(jnp.float32)
        delta += self.w.offline * doff / sx.n_valid

        # preferred-leader eligibility shift (reference PreferredLeaderElectionGoal)
        if self.w.pref_leader != 0.0:
            pref = (rows["pos"] == 0) & rows["valid"] & ~is_lead
            was = pref & ~offline
            now = pref & dst_ok
            delta += (
                self.w.pref_leader
                * (now.astype(jnp.float32) - was.astype(jnp.float32))
                / max(1, self.shape.P)
            )

        # movement pricing: cost to stray from the ORIGINAL broker (statics
        # hold the pre-optimization placement), refunded when moving home —
        # keeps the plan executable (reference ExecutionProposal data-to-move)
        if plan is not None and self.config.replica_move_cost:
            orig = rows["orig_broker"]
            stray = (dst != orig).astype(jnp.float32) - (src != orig).astype(jnp.float32)
            delta += plan.replica_cost * stray

        payload = dict(r=r, dst=dst, d_dst=d_dst, load=load, is_lead=is_lead,
                       pot=pot, lbin=lbin, d_src=d_src)
        if from_prior is not None:
            payload["from_prior"] = from_prior
        return delta, feasible, src, dst, part, payload

    @in_phase("anneal_score")
    def _intra_disk_candidates(
        self, sx, carry: EngineCarry, key: jax.Array, g, plan=None, slice_=None
    ):
        """K_r intra-broker disk-move candidates (JBOD rebalance_disk mode).

        Replicas move between a broker's OWN logdirs — no broker-level load
        shifts, only the intra-broker disk goals + offline term move
        (reference IntraBrokerDiskCapacity/UsageDistributionGoal,
        Executor.intraBrokerMoveReplicas:1036 alterReplicaLogDirs).
        Returned in the replica-candidate payload shape: src == dst broker,
        so `_apply`'s broker-axis scatters cancel and only replica_disk +
        disk_load actually change.
        """
        st = sx.state
        K = self.K_r
        D = self.shape.max_disks_per_broker
        r = self._slice_draws(slice_, self._sample_sources(sx, key, K, plan))
        fields = ["broker", "part", "disk", "topic", "valid", "is_lead",
                  "load_leader", "load_follower"]
        if plan is not None and self.config.replica_move_cost:
            fields.append("orig_disk")
        rows = self._take_rows(sx, carry, r, tuple(fields))
        b = rows["broker"]
        d_src = rows["disk"]
        part = rows["part"]

        # destination logdir: most-free alive disk on b, excluding the
        # current slot
        pct = carry.disk_load[b] / (st.disk_capacity[b] + 1e-12)
        pct = jnp.where(st.disk_alive[b], pct, jnp.inf)
        pct = jnp.where(jax.nn.one_hot(d_src, D, dtype=bool), jnp.inf, pct)
        d_dst = jnp.argmin(pct, axis=1).astype(jnp.int32)

        off_src = ~(st.broker_alive[b] & st.disk_alive[b, d_src])
        movable = sx.topic_movable[rows["topic"]] | off_src
        dst_ok = st.broker_alive[b] & st.disk_alive[b, d_dst]
        feasible = (
            rows["valid"] & movable & dst_ok & (d_dst != d_src)
        )

        is_lead = rows["is_lead"]
        load = jnp.where(
            is_lead[:, None], rows["load_leader"], rows["load_follower"]
        )
        load = jnp.where(rows["valid"][:, None], load, 0.0)
        ddisk = load[:, int(Resource.DISK)]

        # intra-broker disk terms: one broker, one row reshuffled
        row = carry.disk_load[b]
        shift = (
            jax.nn.one_hot(d_dst, D, dtype=jnp.float32)
            - jax.nn.one_hot(d_src, D, dtype=jnp.float32)
        ) * ddisk[:, None]
        bsum = row.sum(-1)
        delta = self._disk_terms(sx, b, row + shift, bsum, g) - self._disk_terms(
            sx, b, row, bsum, g
        )
        # offline-replica shift (rescuing off a failed logdir)
        delta += self.w.offline * (
            (~dst_ok).astype(jnp.float32) - off_src.astype(jnp.float32)
        ) / sx.n_valid
        # movement pricing vs the ORIGINAL logdir (alterReplicaLogDirs copies
        # the whole replica; reference ExecutionProposal data-to-move)
        if plan is not None and self.config.replica_move_cost:
            orig = rows["orig_disk"]
            stray = (d_dst != orig).astype(jnp.float32) - (d_src != orig).astype(
                jnp.float32
            )
            delta += plan.replica_cost * stray

        payload = dict(r=r, dst=b, d_dst=d_dst, load=load, is_lead=is_lead,
                       pot=rows["load_leader"][:, int(Resource.NW_OUT)],
                       lbin=jnp.where(
                           is_lead, rows["load_leader"][:, int(Resource.NW_IN)], 0.0
                       ),
                       d_src=d_src)
        if self.config.diagnostics:
            # intra-broker candidates never draw destinations from the
            # prior; the mask exists so the diagnostics bundle is uniform
            payload["from_prior"] = jnp.zeros(r.shape, bool)
        return delta, feasible, b, b, part, payload

    @in_phase("anneal_score")
    def _swap_candidates(
        self, sx, carry: EngineCarry, key: jax.Array, g, plan=None, slice_=None
    ):
        """K_s replica-swap candidates: r <-> q exchange brokers (and disk
        slots).  Escapes local optima single relocations cannot leave through
        a feasible intermediate (reference AbstractGoal.maybeApplySwapAction:236,
        ResourceDistributionGoal swap-in/out :502-599; SURVEY §7 hard part (b)).

        Returns (delta, feasible, src, dst, part_r, part_q, payload); the
        surviving swaps are applied as two linked relocation payload rows.
        """
        st = sx.state
        K = self.K_s
        if K == 0:
            z = jnp.zeros((0,), jnp.float32)
            zi = jnp.zeros((0,), jnp.int32)
            zb = jnp.zeros((0,), bool)
            payload = dict(
                r=zi, q=zi, load_r=jnp.zeros((0, NUM_RESOURCES)), load_q=jnp.zeros((0, NUM_RESOURCES)),
                lead_r=zb, lead_q=zb, pot_r=z, pot_q=z, lbin_r=z, lbin_q=z,
                d_r=zi, d_q=zi,
            )
            return z, zb, zi, zi, zi, zi, payload
        with anneal_phase("anneal_sample"):
            k1, k2 = jax.random.split(key)
            r = self._sample_sources(sx, k1, K, plan)
            q = _uniform_idx(k2, (K,), sx.n_source)
            r, q = self._slice_draws(slice_, r, q)
        # ONE row bundle for both draw lanes (gather of a concat == concat
        # of gathers): the model-sharded twin resolves it with a single
        # psum round instead of two
        fields = ["broker", "part", "disk", "topic", "valid", "is_lead",
                  "load_leader", "load_follower"]
        if self.w.pref_leader != 0.0:
            fields.append("pos")
        if plan is not None and self.config.replica_move_cost:
            fields.append("orig_broker")
        n_r = r.shape[0]
        rows = self._take_rows(sx, carry, jnp.concatenate([r, q]), tuple(fields))
        rows_r = {f: a[:n_r] for f, a in rows.items()}
        rows_q = {f: a[n_r:] for f, a in rows.items()}
        src = rows_r["broker"]
        dst = rows_q["broker"]
        part_r = rows_r["part"]
        part_q = rows_q["part"]

        d_r = rows_r["disk"]
        d_q = rows_q["disk"]
        off_r = ~(st.broker_alive[src] & st.disk_alive[src, d_r])
        off_q = ~(st.broker_alive[dst] & st.disk_alive[dst, d_q])
        movable_r = sx.topic_movable[rows_r["topic"]] | off_r
        movable_q = sx.topic_movable[rows_q["topic"]] | off_q
        feasible = (
            rows_r["valid"]
            & rows_q["valid"]
            & movable_r
            & movable_q
            & (src != dst)
            & (part_r != part_q)
            # both ends must be allowed destinations (each receives a replica)
            & sx.dest_ok[src]
            & sx.dest_ok[dst]
            # each replica inherits the other's disk slot — that slot must be
            # alive (relocations argmin over alive disks; swaps must not be
            # the back door onto a failed logdir)
            & st.disk_alive[dst, d_q]
            & st.disk_alive[src, d_r]
        )
        # neither partition may end up duplicated on its new broker
        mem_r = self._take_members(sx, part_r)  # [K, max_rf]
        mem_r_broker = self._member_field(sx, carry, mem_r, "broker", -1)
        feasible &= ~(mem_r_broker == dst[:, None]).any(axis=1)
        mem_q = self._take_members(sx, part_q)
        mem_q_broker = self._member_field(sx, carry, mem_q, "broker", -1)
        feasible &= ~(mem_q_broker == src[:, None]).any(axis=1)

        lead_r = rows_r["is_lead"]
        lead_q = rows_q["is_lead"]
        load_r = jnp.where(
            lead_r[:, None], rows_r["load_leader"], rows_r["load_follower"]
        )
        load_r = jnp.where(rows_r["valid"][:, None], load_r, 0.0)
        load_q = jnp.where(
            lead_q[:, None], rows_q["load_leader"], rows_q["load_follower"]
        )
        load_q = jnp.where(rows_q["valid"][:, None], load_q, 0.0)
        pot_r = rows_r["load_leader"][:, int(Resource.NW_OUT)]
        pot_q = rows_q["load_leader"][:, int(Resource.NW_OUT)]
        lbin_r = jnp.where(lead_r, rows_r["load_leader"][:, int(Resource.NW_IN)], 0.0)
        lbin_q = jnp.where(lead_q, rows_q["load_leader"][:, int(Resource.NW_IN)], 0.0)

        rdisk = int(Resource.DISK)
        # r -> (dst, q's disk slot), q -> (src, r's disk slot)
        delta = self._move_delta(
            sx,
            carry,
            g,
            src=src,
            dst=dst,
            dload_src=load_q - load_r,
            dload_dst=load_r - load_q,
            dcount=jnp.zeros(r.shape, jnp.int32),
            dlcount=lead_r.astype(jnp.int32) - lead_q.astype(jnp.int32),
            dpot=pot_r - pot_q,
            dlbin=lbin_r - lbin_q,
            d_src=d_r,
            d_dst=d_q,
            ddisk=load_r[:, rdisk] - load_q[:, rdisk],
        )

        # rack cells for both partitions (reference RackAwareGoal)
        rack_s, rack_d = st.broker_rack[src], st.broker_rack[dst]

        def rack_delta(part, rack_from, rack_to):
            c_f = self._rack_cell(carry, part, rack_from)
            c_t = self._rack_cell(carry, part, rack_to)
            d = (_relu(c_f - 2.0) - _relu(c_f - 1.0)) + (_relu(c_t) - _relu(c_t - 1.0))
            return jnp.where(rack_from != rack_to, d, 0.0)

        delta += self.w.rack * (
            rack_delta(part_r, rack_s, rack_d) + rack_delta(part_q, rack_d, rack_s)
        ) / sx.n_valid

        # topic cells for both topics (reference TopicReplicaDistributionGoal)
        if self.w.topic_dist != 0.0:
            tt = self.constraint.topic_replica_count_balance_threshold

            def topic_delta(t, b_from, b_to):
                upper = jnp.ceil(g["topic_avg"][t] * tt)
                lower = jnp.floor(g["topic_avg"][t] * max(0.0, 2.0 - tt))

                def cell(cnt):
                    return _relu(cnt - upper) + _relu(lower - cnt)

                ct_f = carry.broker_topic_count[t, b_from].astype(jnp.float32)
                ct_t = carry.broker_topic_count[t, b_to].astype(jnp.float32)
                return (cell(ct_f - 1.0) - cell(ct_f)) + (cell(ct_t + 1.0) - cell(ct_t))

            delta += self.w.topic_dist * (
                topic_delta(rows_r["topic"], src, dst)
                + topic_delta(rows_q["topic"], dst, src)
            ) / g["total_count"]

        # offline-replica shifts for both replicas
        r_ok = st.broker_alive[dst] & st.disk_alive[dst, d_q]
        q_ok = st.broker_alive[src] & st.disk_alive[src, d_r]
        doff = (
            (~r_ok).astype(jnp.float32)
            - off_r.astype(jnp.float32)
            + (~q_ok).astype(jnp.float32)
            - off_q.astype(jnp.float32)
        )
        delta += self.w.offline * doff / sx.n_valid

        # preferred-leader eligibility shifts
        if self.w.pref_leader != 0.0:
            def pref_delta(rows_x, was_off, now_ok, lead):
                pref = (rows_x["pos"] == 0) & rows_x["valid"] & ~lead
                was = pref & ~was_off
                now = pref & now_ok
                return now.astype(jnp.float32) - was.astype(jnp.float32)

            delta += (
                self.w.pref_leader
                * (
                    pref_delta(rows_r, off_r, r_ok, lead_r)
                    + pref_delta(rows_q, off_q, q_ok, lead_q)
                )
                / max(1, self.shape.P)
            )

        # movement pricing for both strays
        if plan is not None and self.config.replica_move_cost:
            orig_r = rows_r["orig_broker"]
            orig_q = rows_q["orig_broker"]
            stray = (
                (dst != orig_r).astype(jnp.float32)
                - (src != orig_r).astype(jnp.float32)
                + (src != orig_q).astype(jnp.float32)
                - (dst != orig_q).astype(jnp.float32)
            )
            delta += plan.replica_cost * stray

        payload = dict(
            r=r, q=q, load_r=load_r, load_q=load_q, lead_r=lead_r, lead_q=lead_q,
            pot_r=pot_r, pot_q=pot_q, lbin_r=lbin_r, lbin_q=lbin_q, d_r=d_r, d_q=d_q,
        )
        return delta, feasible, src, dst, part_r, part_q, payload

    @in_phase("anneal_score")
    def _leadership_candidates(
        self, sx, carry: EngineCarry, key: jax.Array, g, plan=None, slice_=None
    ):
        """K_l leadership-transfer candidates (reference relocateLeadership:374)."""
        st = sx.state
        K = self.K_l
        R = self.shape.R
        if K == 0:
            z = jnp.zeros((0,), jnp.float32)
            zi = jnp.zeros((0,), jnp.int32)
            zb = jnp.zeros((0,), bool)
            zl = jnp.zeros((0, NUM_RESOURCES), jnp.float32)
            payload = dict(rf=zi, rt=zi, dl_f=zl, dl_t=zl, dlbin_src=z, dlbin_dst=z)
            return z, zb, zi, zi, zi, payload
        with anneal_phase("anneal_sample"):
            rt = self._slice_draws(slice_, _uniform_idx(key, (K,), sx.n_source))
        fields = ["broker", "part", "disk", "valid", "is_lead",
                  "load_leader", "load_follower"]
        if self.w.pref_leader != 0.0:
            fields.append("pos")
        if plan is not None and self.config.leadership_move_cost:
            fields.append("orig_lead")
        rows_t = self._take_rows(sx, carry, rt, tuple(fields))
        part = rows_t["part"]
        members = self._take_members(sx, part)  # [K, max_rf]
        m_idx = jnp.minimum(members, R - 1)
        m_lead = self._member_field(sx, carry, members, "is_lead", False)
        rf = m_idx[jnp.arange(rt.shape[0]), jnp.argmax(m_lead, axis=1)]
        rows_f = self._take_rows(
            sx, carry, rf,
            tuple(f for f in fields if f not in ("part", "valid", "is_lead")),
        )

        src, dst = rows_f["broker"], rows_t["broker"]
        dst_ok = st.broker_alive[dst] & st.disk_alive[dst, rows_t["disk"]]
        feasible = (
            rows_t["valid"]
            & ~rows_t["is_lead"]
            & m_lead.any(axis=1)
            & dst_ok
            & sx.lead_ok[dst]
        )

        # load shift: rf leader->follower on src, rt follower->leader on dst
        dl_f = rows_f["load_follower"] - rows_f["load_leader"]  # [K, 4]
        dl_t = rows_t["load_leader"] - rows_t["load_follower"]
        dlbin = rows_t["load_leader"][:, int(Resource.NW_IN)]  # gained by dst
        # NOTE: src loses rf's leader NW_IN; handled via asymmetric lbin deltas
        delta = self._move_delta(
            sx,
            carry,
            g,
            src=src,
            dst=dst,
            dload_src=dl_f,
            dload_dst=dl_t,
            dcount=jnp.zeros(rt.shape, jnp.int32),
            dlcount=jnp.ones(rt.shape, jnp.int32),
            dpot=jnp.zeros(rt.shape, jnp.float32),
            dlbin_src=rows_f["load_leader"][:, int(Resource.NW_IN)],
            dlbin=dlbin,
            d_src=rows_f["disk"],
            d_dst=rows_t["disk"],
            ddisk_src=dl_f[:, int(Resource.DISK)],
            ddisk=dl_t[:, int(Resource.DISK)],
        )

        if self.w.pref_leader != 0.0:
            src_ok = st.broker_alive[src] & st.disk_alive[src, rows_f["disk"]]
            pref_f = (rows_f["pos"] == 0) & src_ok  # rf becomes violating
            pref_t = (rows_t["pos"] == 0) & dst_ok  # rt stops violating
            delta += (
                self.w.pref_leader
                * (pref_f.astype(jnp.float32) - pref_t.astype(jnp.float32))
                / max(1, self.shape.P)
            )

        # movement pricing: a transfer whose new leader is not the partition's
        # ORIGINAL leader pays; restoring the original leader refunds
        # (the executor applies each as a preferred-leader election batch,
        # reference executor/Executor.java:1091)
        if plan is not None and self.config.leadership_move_cost:
            stray = (~rows_t["orig_lead"]).astype(jnp.float32) - (
                ~rows_f["orig_lead"]
            ).astype(jnp.float32)
            delta += plan.lead_cost * stray

        payload = dict(rf=rf, rt=rt, dl_f=dl_f, dl_t=dl_t,
                       dlbin_src=rows_f["load_leader"][:, int(Resource.NW_IN)],
                       dlbin_dst=dlbin)
        return delta, feasible, src, dst, part, payload

    @in_phase("anneal_score")
    def _move_delta(
        self,
        sx,
        carry,
        g,
        *,
        src,
        dst,
        dload_src,
        dload_dst,
        dcount,
        dlcount,
        dpot,
        dlbin,
        d_src,
        d_dst,
        ddisk,
        dlbin_src=None,
        ddisk_src=None,
    ):
        """Objective delta for candidates touching brokers (src, dst).

        dload_src is ADDED to src (callers pass negative values to remove
        load); dload_dst is added to dst.  dcount/dlcount/dpot/dlbin move
        from src to dst unless an asymmetric *_src override is given.
        """
        st = sx.state
        if dlbin_src is None:
            dlbin_src = dlbin
        if ddisk_src is None:
            ddisk_src = ddisk

        def gather(b):
            return (
                carry.broker_load[b],
                carry.broker_replica_count[b],
                carry.broker_leader_count[b],
                carry.broker_potential_nw_out[b],
                carry.broker_leader_bytes_in[b],
            )

        ls, rs, lcs, ps, lbs = gather(src)
        ld, rd, lcd, pd, lbd = gather(dst)
        # ONE stacked _broker_terms call over a [4, K] lane axis
        # (src-old, dst-old, src-new, dst-new) instead of four separate
        # inlines: element-wise identical math, but the traced step program
        # shrinks by ~1.5k equations — warm-start trace time is paced by
        # graph size (this helper is reached from all three candidate kinds)
        b4 = jnp.stack([src, dst, src, dst])
        t4 = self._broker_terms(
            sx,
            b4,
            jnp.stack([ls, ld, ls + dload_src, ld + dload_dst]),
            jnp.stack([rs, rd, rs - dcount, rd + dcount]),
            jnp.stack([lcs, lcd, lcs - dlcount, lcd + dlcount]),
            jnp.stack([ps, pd, ps - dpot, pd + dpot]),
            jnp.stack([lbs, lbd, lbs - dlbin_src, lbd + dlbin]),
            g,
        )
        delta = (t4[2] + t4[3]) - (t4[0] + t4[1])

        # host-granularity capacity (same-host moves cancel)
        h_s, h_d = st.broker_host[src], st.broker_host[dst]
        hl_s, hl_d = carry.host_load[h_s], carry.host_load[h_d]
        th4 = self._host_terms(
            sx,
            jnp.stack([h_s, h_s, h_d, h_d]),
            jnp.stack([hl_s + dload_src, hl_s, hl_d + dload_dst, hl_d]),
        )
        dh = th4[0] - th4[1] + th4[2] - th4[3]
        delta += jnp.where(h_s != h_d, dh, 0.0)

        # intra-broker disk goals
        if self.w.intra_cap != 0.0 or self.w.intra_dist != 0.0:
            row_s, row_d = carry.disk_load[src], carry.disk_load[dst]
            D = self.shape.max_disks_per_broker
            oh_s = jax.nn.one_hot(d_src, D, dtype=jnp.float32)
            oh_d = jax.nn.one_hot(d_dst, D, dtype=jnp.float32)
            row_s2 = row_s - oh_s * ddisk_src[:, None]
            row_d2 = row_d + oh_d * ddisk[:, None]
            bsum_s, bsum_d = row_s.sum(-1), row_d.sum(-1)
            td4 = self._disk_terms(
                sx,
                jnp.stack([src, src, dst, dst]),
                jnp.stack([row_s2, row_s, row_d2, row_d]),
                jnp.stack([bsum_s - ddisk_src, bsum_s, bsum_d + ddisk, bsum_d]),
                g,
            )
            delta += td4[0] - td4[1] + td4[2] - td4[3]

        # dispersion tiebreaker via sufficient statistics
        cap_s = st.broker_capacity[src] + 1e-12
        cap_d = st.broker_capacity[dst] + 1e-12
        p_s, p_d = ls / cap_s, ld / cap_d
        p_s2, p_d2 = (ls + dload_src) / cap_s, (ld + dload_dst) / cap_d
        a_s = sx.alive[src][:, None].astype(jnp.float32)
        a_d = sx.alive[dst][:, None].astype(jnp.float32)
        dsum = a_s * (p_s2 - p_s) + a_d * (p_d2 - p_d)
        dsumsq = a_s * (p_s2**2 - p_s**2) + a_d * (p_d2**2 - p_d**2)
        delta += self._tie_term(
            sx, g["pct_sum"] + dsum, g["pct_sumsq"] + dsumsq
        ) - self._tie_term(sx, g["pct_sum"], g["pct_sumsq"])
        return delta

    # ------------------------------------------------------------------
    # step: propose -> evaluate -> select -> apply
    # ------------------------------------------------------------------

    def _propose_kinds(
        self, sx: EngineStatics, carry: EngineCarry, k_r, k_s, k_l, g,
        plan=None, slice_=None,
    ):
        """Raw per-kind candidate bundles (replica/intra, swap, leadership).

        With `slice_` (the mesh engine's candidate-axis sharding) each
        bundle covers only this shard's contiguous slice of the full-K
        stream; the mesh step all_gathers the bundles back into full-K
        order before `_assemble_prop` — the candidate COLUMNS are the only
        thing that ever crosses shards."""
        repl = (
            self._intra_disk_candidates
            if self.config.intra_broker
            else self._replica_candidates
        )
        return (
            repl(sx, carry, k_r, g, plan, slice_=slice_),
            self._swap_candidates(sx, carry, k_s, g, plan, slice_=slice_),
            self._leadership_candidates(sx, carry, k_l, g, plan, slice_=slice_),
        )

    def _propose(self, sx: EngineStatics, carry: EngineCarry, k_r, k_s, k_l, g, plan=None):
        """Sample + evaluate all candidate kinds; return a selection/apply
        bundle.  Payloads carry src broker / topic / partition explicitly so
        `_apply` never has to index replica-axis arrays for them — which lets
        the mesh engine (parallel/mesh.py) assemble rows evaluated on OTHER
        devices' candidate shards without touching their replica arrays.
        """
        return self._assemble_prop(
            sx, carry, *self._propose_kinds(sx, carry, k_r, k_s, k_l, g, plan)
        )

    @in_phase("anneal_score")
    def _assemble_prop(self, sx: EngineStatics, carry: EngineCarry, raw_r, raw_s, raw_l):
        """Concatenate per-kind bundles into the selection/apply bundle
        (shared verbatim by the plain step and the mesh step's post-gather
        path, so the two can never diverge)."""
        R1 = self.shape.R - 1
        dr, fr, sr, tr, pr, payr = raw_r
        ds, fs, ss, ts, ps1, ps2, pays = raw_s
        dl, fl, sl, tl, pl, payl = raw_l
        # diagnostics rider: the replica rows' took-the-prior-branch mask
        # (never part of the apply payload — swaps/leads are not prior-drawn)
        payr = dict(payr)
        from_prior = payr.pop("from_prior", None)

        delta = jnp.concatenate([dr, ds, dl])
        feas = jnp.concatenate([fr, fs, fl])
        src = jnp.concatenate([sr, ss, sl])
        dst = jnp.concatenate([tr, ts, tl])
        # two partition lanes: swaps touch two partitions; other kinds
        # duplicate their single partition (harmless)
        part1 = jnp.concatenate([pr, ps1, pl])
        part2 = jnp.concatenate([pr, ps2, pl])

        # a surviving swap applies as two linked relocations: r -> (dst, q's
        # disk) and q -> (src, r's disk) — the scatter path is shared
        r_ext = jnp.concatenate([payr["r"], pays["r"], pays["q"]])
        payr_ext = dict(
            r=r_ext,
            src=jnp.concatenate([sr, ss, ts]),
            dst=jnp.concatenate([payr["dst"], ts, ss]),
            d_dst=jnp.concatenate([payr["d_dst"], pays["d_q"], pays["d_r"]]),
            load=jnp.concatenate([payr["load"], pays["load_r"], pays["load_q"]]),
            is_lead=jnp.concatenate([payr["is_lead"], pays["lead_r"], pays["lead_q"]]),
            pot=jnp.concatenate([payr["pot"], pays["pot_r"], pays["pot_q"]]),
            lbin=jnp.concatenate([payr["lbin"], pays["lbin_r"], pays["lbin_q"]]),
            d_src=jnp.concatenate([payr["d_src"], pays["d_r"], pays["d_q"]]),
            topic=self._take_rows(
                sx, carry, jnp.minimum(r_ext, R1), ("topic",)
            )["topic"],
            part=jnp.concatenate([pr, ps1, ps2]),
        )
        # rf/rt disk lookups bundled into ONE row fetch (single psum round
        # on the sharded twin)
        n_f = payl["rf"].shape[0]
        d_ft = self._take_rows(
            sx, carry,
            jnp.minimum(jnp.concatenate([payl["rf"], payl["rt"]]), R1),
            ("disk",),
        )["disk"]
        payl_ext = dict(
            payl,
            src_b=sl,
            dst_b=tl,
            d_f=d_ft[:n_f],
            d_t=d_ft[n_f:],
        )
        out = dict(
            delta=delta, feas=feas, src=src, dst=dst, part1=part1, part2=part2,
            nr=dr.shape[0], ns=ds.shape[0], payr=payr_ext, payl=payl_ext,
        )
        if from_prior is not None:
            out["from_prior"] = from_prior
        return out

    def _select(self, accept, delta, src, dst, part1, part2, num_parts=None):
        """Conflict resolution: unique ranks; a candidate survives iff it is
        the best-ranked touching each of its brokers and its partition(s).
        `num_parts` overrides the partition-segment count (the sharded engine
        selects over GLOBAL partition ids spanning all shards)."""
        B = self.shape.B
        P = self.shape.P if num_parts is None else num_parts
        big = jnp.where(accept, delta, jnp.inf)
        rank = jnp.argsort(jnp.argsort(big)).astype(jnp.int32)
        seg = jnp.concatenate([src, dst, B + part1, B + part2])
        ranks4 = jnp.concatenate([rank, rank, rank, rank])
        min_rank = jax.ops.segment_min(ranks4, seg, num_segments=B + P)
        return (
            accept
            & (min_rank[src] == rank)
            & (min_rank[dst] == rank)
            & (min_rank[B + part1] == rank)
            & (min_rank[B + part2] == rank)
        )

    def _step(self, sx: EngineStatics, carry: EngineCarry, temperature, plan=None):
        with anneal_phase("anneal_sample"):
            key, k_r, k_s, k_l, k_u = jax.random.split(carry.key, 5)
        g = self._globals(sx, carry)
        prop = self._propose(sx, carry, k_r, k_s, k_l, g, plan)
        return self._accept_select_apply(sx, carry, prop, temperature, key, k_u)

    @in_phase("anneal_accept")
    def _accept_select_apply(
        self, sx: EngineStatics, carry: EngineCarry, prop, temperature, key, k_u
    ):
        """Metropolis acceptance + conflict resolution + scatter, from a
        full-K proposal bundle.  Shared by the plain step and the mesh
        step (which only replaces how `prop` was produced), so acceptance
        semantics cannot diverge between the two."""
        delta, feas = prop["delta"], prop["feas"]

        # Metropolis acceptance: delta < -T log u  (greedy at T=0)
        u = jax.random.uniform(k_u, (delta.shape[0],), minval=1e-12, maxval=1.0)
        thresh = -temperature * jnp.log(u)
        accept = feas & (delta < thresh - 1e-12)

        survive = self._select(
            accept, delta, prop["src"], prop["dst"], prop["part1"], prop["part2"]
        )
        nr, ns = prop["nr"], prop["ns"]
        sv_r = survive[:nr]
        sv_s = survive[nr: nr + ns]
        sv_l = survive[nr + ns:]
        sv_r_ext = jnp.concatenate([sv_r, sv_s, sv_s])

        carry = self._apply(sx, carry, sv_r_ext, prop["payr"], sv_l, prop["payl"])
        carry = dataclasses.replace(carry, key=key)
        stats = dict(
            accepted=survive.sum(),
            improving=(feas & (delta < 0)).sum(),
            delta=jnp.where(survive, delta, 0.0).sum(),
        )
        if self.config.diagnostics:
            # per-kind acceptance + prior-draw usage: read-only reductions
            # of the already-computed survival masks (the ledger's
            # per-round acceptance-by-kind trajectory)
            fp = prop.get("from_prior")
            if fp is None:
                fp = jnp.zeros((nr,), bool)
            stats.update(
                acc_replica=sv_r.sum(),
                acc_swap=sv_s.sum(),
                acc_lead=sv_l.sum(),
                prior_cands=fp.sum(),
                prior_acc=(sv_r & fp).sum(),
            )
        return carry, stats

    def _apply(
        self, sx, carry: EngineCarry, sv_r, payr, sv_l, payl,
        *, r_offset=None, p_offset=None, r_size=None, p_size=None,
    ) -> EngineCarry:
        """Scatter surviving candidates into placement + aggregates.

        Payload rows identify everything by explicit fields (replica id, src
        broker, topic, partition) rather than replica-array lookups.  When
        `r_offset`/`p_offset` are given (sharded engine), replica/partition
        ids are GLOBAL: aggregates (replicated broker/host/topic axes) absorb
        every row, while placement scatters translate to shard-local indices
        and rows owned by other shards fall out of range and are dropped.
        """
        st = sx.state
        B, R, D = self.shape.B, self.shape.R, self.shape.max_disks_per_broker
        # local extents of the placement arrays: the sharded engine passes
        # its per-shard row counts so ownership bounds and drop sentinels
        # track the LOCAL arrays, not the global shape
        r_size = R if r_size is None else r_size
        p_size = self.shape.P if p_size is None else p_size
        drop = dict(mode="drop")
        # ownership masks: negative indices would WRAP (python semantics), so
        # rows owned by other shards must be masked to the sentinel explicitly
        if r_offset is None:
            r_ids, own_r = payr["r"], True
        else:
            r_ids = payr["r"] - r_offset
            own_r = (r_ids >= 0) & (r_ids < r_size)
        if p_offset is None:
            p_ids, own_p = payr["part"], True
        else:
            p_ids = payr["part"] - p_offset
            own_p = (p_ids >= 0) & (p_ids < p_size)

        # ---- replica moves ----
        r = jnp.where(sv_r & own_r, r_ids, r_size)
        dst = payr["dst"]
        load = payr["load"] * sv_r[:, None]
        src = payr["src"]
        src_idx = jnp.where(sv_r, src, B)
        dst_idx = jnp.where(sv_r, dst, B)

        replica_broker = carry.replica_broker.at[r].set(dst, **drop)
        replica_disk = carry.replica_disk.at[r].set(payr["d_dst"], **drop)

        bl = carry.broker_load.at[src_idx].add(-load, **drop).at[dst_idx].add(load, **drop)
        ones = sv_r.astype(jnp.int32)
        rc = carry.broker_replica_count.at[src_idx].add(-ones, **drop).at[dst_idx].add(
            ones, **drop
        )
        dlc = (sv_r & payr["is_lead"]).astype(jnp.int32)
        lc = carry.broker_leader_count.at[src_idx].add(-dlc, **drop).at[dst_idx].add(dlc, **drop)
        dpot = payr["pot"] * sv_r
        pot = carry.broker_potential_nw_out.at[src_idx].add(-dpot, **drop).at[dst_idx].add(
            dpot, **drop
        )
        dlb = payr["lbin"] * sv_r
        lb = carry.broker_leader_bytes_in.at[src_idx].add(-dlb, **drop).at[dst_idx].add(
            dlb, **drop
        )
        t = payr["topic"]
        T = self.shape.num_topics
        tc = (
            carry.broker_topic_count.at[jnp.where(sv_r, t, T), src_idx].add(-ones, **drop)
            .at[jnp.where(sv_r, t, T), dst_idx].add(ones, **drop)
        )
        p = jnp.where(sv_r & own_p, p_ids, p_size)
        rack_s = st.broker_rack[src]
        rack_d = st.broker_rack[dst]
        prc = (
            carry.part_rack_count.at[p, rack_s].add(-ones, **drop)
            .at[p, rack_d].add(ones, **drop)
        )
        ddisk = load[:, int(Resource.DISK)]
        dl_ = (
            carry.disk_load.at[src_idx, payr["d_src"]].add(-ddisk, **drop)
            .at[dst_idx, payr["d_dst"]].add(ddisk, **drop)
        )
        h_s = st.broker_host[src]
        h_d = st.broker_host[dst]
        H = self.shape.num_hosts
        hl = (
            carry.host_load.at[jnp.where(sv_r, h_s, H)].add(-load, **drop)
            .at[jnp.where(sv_r, h_d, H)].add(load, **drop)
        )

        # ---- leadership transfers ----
        if r_offset is None:
            rf_ids, rt_ids, own_f, own_t = payl["rf"], payl["rt"], True, True
        else:
            rf_ids = payl["rf"] - r_offset
            rt_ids = payl["rt"] - r_offset
            own_f = (rf_ids >= 0) & (rf_ids < r_size)
            own_t = (rt_ids >= 0) & (rt_ids < r_size)
        rf = jnp.where(sv_l & own_f, rf_ids, r_size)
        rt = jnp.where(sv_l & own_t, rt_ids, r_size)
        is_leader = carry.replica_is_leader.at[rf].set(False, **drop).at[rt].set(True, **drop)

        src_l = payl["src_b"]
        dst_l = payl["dst_b"]
        sl_idx = jnp.where(sv_l, src_l, B)
        tl_idx = jnp.where(sv_l, dst_l, B)
        dl_f = payl["dl_f"] * sv_l[:, None]
        dl_t = payl["dl_t"] * sv_l[:, None]
        bl = bl.at[sl_idx].add(dl_f, **drop).at[tl_idx].add(dl_t, **drop)
        ones_l = sv_l.astype(jnp.int32)
        lc = lc.at[sl_idx].add(-ones_l, **drop).at[tl_idx].add(ones_l, **drop)
        lb = (
            lb.at[sl_idx].add(-payl["dlbin_src"] * sv_l, **drop)
            .at[tl_idx].add(payl["dlbin_dst"] * sv_l, **drop)
        )
        d_f = payl["d_f"]
        d_t = payl["d_t"]
        dl_ = (
            dl_.at[sl_idx, d_f].add(dl_f[:, int(Resource.DISK)], **drop)
            .at[tl_idx, d_t].add(dl_t[:, int(Resource.DISK)], **drop)
        )
        h_f = st.broker_host[src_l]
        h_t = st.broker_host[dst_l]
        hl = (
            hl.at[jnp.where(sv_l, h_f, H)].add(dl_f, **drop)
            .at[jnp.where(sv_l, h_t, H)].add(dl_t, **drop)
        )

        return dataclasses.replace(
            carry,
            replica_broker=replica_broker,
            replica_is_leader=is_leader,
            replica_disk=replica_disk,
            broker_load=bl,
            broker_replica_count=rc,
            broker_leader_count=lc,
            broker_potential_nw_out=pot,
            broker_leader_bytes_in=lb,
            broker_topic_count=tc,
            part_rack_count=prc,
            disk_load=dl_,
            host_load=hl,
        )

    @in_phase("anneal_refresh")
    def _round_prep_impl(self, sx: EngineStatics, carry: EngineCarry):
        """Between-rounds bookkeeping as ONE program: refresh aggregates
        (wash float drift), build the next round's sampling plan, and read
        the cheap early-stop signal.  Separately jitted these three share
        the O(R) aggregate rebuild and O(B) globals/objective work and cost
        three dispatch+sync round trips; fused they cost one."""
        carry = self._refresh_impl(sx, carry)
        plan = self._plan_impl(sx, carry)
        cheap = self._cheap_violations_impl(sx, carry)
        return carry, plan, cheap

    def _scan_impl(
        self, sx: EngineStatics, carry: EngineCarry, temps: jax.Array, plan=None
    ):
        def body(c, t):
            return self._step(sx, c, t, plan)

        return jax.lax.scan(body, carry, temps)

    def _make_scan(self):
        """(statics, carry, temps, plan=None) -> (carry, stats); for external
        composition (portfolio sharding, graft entry)."""
        return self._scan_impl

    # ------------------------------------------------------------------
    # fused whole-anneal program (scan over rounds, rounds scan over steps)
    # ------------------------------------------------------------------

    def _run_fused_impl(self, sx: EngineStatics, carry: EngineCarry):
        return self._fused_rounds_body(sx, carry, verbose=False)

    def _run_fused_verbose_impl(self, sx: EngineStatics, carry: EngineCarry):
        return self._fused_rounds_body(sx, carry, verbose=True)

    def _fused_rounds_body(
        self, sx: EngineStatics, carry: EngineCarry, *, verbose: bool
    ):
        """The entire multi-round anneal as ONE program.

        `lax.scan` over `num_rounds + extra_round_budget` rounds; each
        round body is the existing per-round step scan plus the
        between-rounds program (`_round_prep_impl`: aggregate refresh,
        sampling-plan rebuild, cheap early-stop signal).  The temperature
        schedule, the authoritative full-goal-chain early stop, and the
        extra-polish-rounds loop run in-graph as cond-masked rounds: once
        the `done` flag sets, the remaining round bodies are cheap no-ops.

        Semantics match the legacy host loop exactly — same round budgets,
        same bounded full-chain check count, same RNG chain — with one
        re-phrasing: the early-stop checks run at the TOP of each round
        against the previous round's post-refresh carry, which is the same
        decision the legacy loop takes at the BOTTOM of the previous round
        (the host rebuilds the legacy history shape from the per-round
        flags this returns).

        Returns (final carry, per-round scalars): `accepted`, `ran`,
        `stopped` (early stop fired before this round), `temperature`,
        `cheap`, and — in the verbose variant — the full-chain `objective`.
        Only these O(rounds) scalars are ever fetched eagerly; the carry
        stays on device for the result report to consume.
        """
        cfg = self.config
        total = cfg.num_rounds + cfg.extra_round_budget
        t0, plan0 = self._schedule_init(sx, carry)

        def round_body(st, rnd):
            return self._fused_round_step(sx, st, rnd, verbose=verbose)

        init = (
            carry, plan0, jnp.float32(jnp.inf), jnp.bool_(False),
            jnp.int32(FULL_CHECK_BUDGET), jnp.float32(jnp.inf), jnp.bool_(False),
            t0,
        )
        (carry, *_), ys = jax.lax.scan(round_body, init, jnp.arange(total))
        return carry, ys

    @in_phase("anneal_eval")
    def _schedule_init(self, sx: EngineStatics, carry: EngineCarry):
        """(t0, plan0) of a fresh anneal: the initial temperature scale and
        round-0 sampling plan.  Shared by the whole-anneal fused program
        (inlined) and the segmented runner's init program (standalone) —
        the same traced subprograms, so both paths see identical values
        (the legacy loop already computes them standalone; fused-vs-legacy
        parity is pinned by tests)."""
        obj0, _ = self._eval_impl(sx, carry)
        return obj0 * self.config.init_temperature_scale, self._plan_impl(sx, carry)

    def _fused_round_step(self, sx: EngineStatics, st, rnd, *, verbose: bool):
        """ONE round of the fused schedule — the scan body shared verbatim
        by the whole-anneal program and the segmented slice programs, so
        a segmented run is byte-identical to the unsegmented one by
        construction.  `st` carries (carry, plan, cheap_prev, done,
        checks_left, prev_v, has_prev, t0); `rnd` is the ABSOLUTE round
        index (a slice scans base+arange(L)); rounds past the schedule
        (`rnd >= total` — a slice overhanging the end) are cond-masked
        no-ops exactly like post-early-stop rounds."""
        cfg = self.config
        n_main = cfg.num_rounds
        total = n_main + cfg.extra_round_budget
        tol_on = cfg.early_stop_violations >= 0.0
        tol = jnp.float32(cfg.early_stop_tol)
        carry, plan, cheap_prev, done, checks_left, prev_v, has_prev, t0 = st
        in_range = rnd < total
        active = ~done & in_range
        is_extra = rnd >= n_main
        main_stop = jnp.bool_(False)
        run = active
        if tol_on:
            # main-round gate: the previous round's cheap O(B) signal
            # opens the bounded authoritative check (legacy
            # full_checks_left semantics); extra-round gate: the
            # full-chain violation decides continue/stop every round
            main_gate = (
                active & ~is_extra & (rnd > 0)
                & (checks_left > 0) & (cheap_prev <= tol)
            )
            extra_gate = active & is_extra
            need_full = main_gate | extra_gate
            full_v = jax.lax.cond(
                need_full,
                lambda: self._eval_impl(sx, carry)[1],
                lambda: jnp.float32(jnp.inf),
            )
            main_stop = main_gate & (full_v <= tol)
            checks_left = jnp.where(
                main_gate & ~main_stop, checks_left - 1, checks_left
            )
            extra_stop = extra_gate & (
                (full_v <= tol) | (has_prev & (full_v > prev_v * 0.9))
            )
            stop = main_stop | extra_stop
            done = done | stop
            run = active & ~stop
            prev_v = jnp.where(run & is_extra, full_v, prev_v)
            has_prev = has_prev | (run & is_extra)

        t_r = jnp.where(
            is_extra | (rnd == n_main - 1),
            jnp.float32(0.0),
            t0 * cfg.temperature_decay ** rnd.astype(jnp.float32),
        ).astype(jnp.float32)

        diag = self.config.diagnostics
        stat_keys = (
            ("accepted", "acc_replica", "acc_swap", "acc_lead",
             "prior_cands", "prior_acc")
            if diag
            else ("accepted",)
        )

        def do_round(carry, plan):
            temps = jnp.full((cfg.steps_per_round,), t_r, jnp.float32)
            carry, stats = self._scan_impl(sx, carry, temps, plan)
            carry, plan, cheap = self._round_prep_impl(sx, carry)
            return carry, plan, cheap, {k: stats[k].sum() for k in stat_keys}

        carry, plan, cheap_prev, acc = jax.lax.cond(
            run,
            do_round,
            lambda c, p: (
                c, p, jnp.float32(jnp.inf),
                {k: jnp.int32(0) for k in stat_keys},
            ),
            carry,
            plan,
        )
        # `stopped` marks only the MAIN early stop: the legacy history
        # flags early_stop on the round whose post-refresh state
        # satisfied the full chain, never on an extra-round exit
        ys = dict(
            accepted=acc["accepted"], ran=run, stopped=main_stop,
            temperature=t_r, cheap=cheap_prev,
        )
        if diag:
            # round-boundary goal quality: the full-chain objective + the
            # per-goal violation vector of the post-round carry, masked to
            # NaN on not-ran rounds.  A read of the carry only — the scan
            # state and every RNG stream are untouched, so placements stay
            # byte-identical to the diagnostics-off program.
            n_goals = len(self.chain.goals)
            obj_d, viol_d = jax.lax.cond(
                run,
                lambda: self._eval_vec_impl(sx, carry),
                lambda: (
                    jnp.float32(jnp.nan),
                    jnp.full((n_goals,), jnp.nan, jnp.float32),
                ),
            )
            ys.update(
                objective=obj_d, goal_viol=viol_d,
                acc_replica=acc["acc_replica"], acc_swap=acc["acc_swap"],
                acc_lead=acc["acc_lead"], prior_cands=acc["prior_cands"],
                prior_acc=acc["prior_acc"],
            )
        assert set(ys) == set(self._ys_keys()), (
            "fused ys keys drifted from FUSED_YS_KEYS/FUSED_DIAG_YS_KEYS — "
            "update both, or AOT artifacts unflatten the wrong structure"
        )
        if verbose and "objective" not in ys:
            ys["objective"] = jax.lax.cond(
                run,
                lambda: self._eval_impl(sx, carry)[0],
                lambda: jnp.float32(jnp.nan),
            )
        return (
            carry, plan, cheap_prev, done, checks_left, prev_v, has_prev, t0
        ), ys

    # ------------------------------------------------------------------
    # segmented (preemptible) fused execution — fleet/scheduler.py
    # ------------------------------------------------------------------

    def _seg_init_impl(self, sx: EngineStatics, carry: EngineCarry):
        """Round-0 scan state of the fused schedule as ONE standalone
        program (the segmented runner's prelude): exactly the init the
        whole-anneal program builds in-graph."""
        t0, plan0 = self._schedule_init(sx, carry)
        return (
            plan0, jnp.float32(jnp.inf), jnp.bool_(False),
            jnp.int32(FULL_CHECK_BUDGET), jnp.float32(jnp.inf),
            jnp.bool_(False), t0,
        )

    def _seg_slice_impl(self, L: int, sx, carry, seg, base):
        """Rounds [base, base+L) of the fused schedule: the SAME round
        body as the whole-anneal scan, over a slice of the round indices,
        with the full scan state (carry + plan + early-stop flags + t0)
        carried in and out — splitting a scan into consecutive sub-scans
        of the same body is composition, not approximation.  carry and
        seg are donated: HBM holds one placement copy across slices like
        the unsegmented run."""

        def round_body(st, rnd):
            return self._fused_round_step(sx, st, rnd, verbose=False)

        (carry, *seg), ys = jax.lax.scan(
            round_body, (carry, *seg), base + jnp.arange(L)
        )
        return carry, tuple(seg), ys

    def _seg_fn(self, L: int):
        fn = self._seg_fns.get(L)
        if fn is None:
            fn = jax.jit(partial(self._seg_slice_impl, L), donate_argnums=(1, 2))
            self._seg_fns[L] = fn
        return fn

    def _run_segmented(self, seg_ctx: SegmentContext, *, initial_placement=None):
        """The fused anneal in wall-bounded preemptible slices.

        The fused program cannot be interrupted mid-XLA-execution, so the
        device scheduler's bounded-wall preemption needs the schedule cut
        into separately dispatched slices: run `L` rounds, block until the
        device is actually idle, call `seg_ctx.checkpoint()` (the
        scheduler pauses us here while an URGENT request runs), repeat.
        `L` adapts to `seg_ctx.slice_budget_s` from a measured per-round
        wall EWMA, in powers of two (<= SEGMENT_MAX_ROUNDS) so at most
        log2 distinct slice programs compile per engine.

        Byte parity with the unsegmented run holds by construction: every
        slice scans the SAME `_fused_round_step` body over consecutive
        absolute round indices with the full scan state carried across
        dispatches on device (slices overhanging the schedule are masked
        no-op rounds), and the warm-start path rides the same
        `init_carry_from` copy-in — pinned by tests/test_scheduler.py.
        The cost of preemptibility is one blocking sync per slice instead
        of one per run (reported in the timing record)."""
        cfg = self.config
        sx = self.statics
        t_start = time.monotonic()
        # the slice programs are plain jits outside the AOT tier — their
        # first segmented run traces fresh, and cold-start accounting
        # must say so (once per engine, like the unsegmented path)
        self._record_fused_trace("fresh")
        carry = self._init_for_run(initial_placement)
        if self._jit_seg_init is None:
            self._jit_seg_init = jax.jit(self._seg_init_impl)
        seg = self._jit_seg_init(sx, carry)
        total = cfg.num_rounds + cfg.extra_round_budget
        budget = max(1e-3, float(seg_ctx.slice_budget_s))
        ys_parts: list[dict] = []
        base = 0
        device_s = 0.0
        round_wall = None
        L = 1
        while base < total:
            # a slice length's FIRST dispatch pays the slice program's
            # trace+compile — that wall must not feed the per-round
            # estimate, or every growth step re-inflates the EWMA and
            # collapses the next length back toward 1 (extra syncs for
            # nothing); the very first slice has no other estimate, so
            # its (polluted, conservative) sample is kept and later
            # steady-state slices wash it out
            first_use = L not in self._seg_fns
            t0s = time.monotonic()
            # black-box spool: one Begin per slice DISPATCH, closed only
            # after the blocking sync below — a hang inside the slice
            # program (or a kill mid-slice) leaves "slice K, rounds
            # [base, base+L) in flight" on disk, the exact trail the
            # multichip post-mortem needs (common/blackbox.py)
            _bb = _BLACKBOX
            bb_seq = _bb.begin(
                "engine-slice",
                slice=len(ys_parts), base_round=int(base), rounds=int(L),
                total_rounds=int(total),
            ) if _bb.enabled else 0
            try:
                count_dispatch("engine.slice")
                carry, seg, ys = self._seg_fn(L)(
                    sx, carry, seg, jnp.asarray(base, jnp.int32)
                )
                # the slice boundary IS a blocking sync: the device must
                # be genuinely idle before the scheduler may hand it to
                # an urgent request (seg[2] is the in-graph `done` flag)
                count_dispatch("engine.sync")
                ys_host, done = jax.device_get((ys, seg[2]))
            except BaseException as e:  # noqa: BLE001 — recorded, re-raised
                _bb.end(bb_seq, ok=False, error=repr(e))
                raise
            _bb.end(bb_seq, done=bool(done))
            wall = time.monotonic() - t0s
            device_s += wall
            ys_parts.append(ys_host)
            base += L
            per_round = wall / L
            if round_wall is None:
                round_wall = per_round
            elif not first_use:
                round_wall = 0.5 * round_wall + 0.5 * per_round
            if bool(done) or base >= total:
                break
            L = 1
            while L * 2 * round_wall <= budget and L * 2 <= SEGMENT_MAX_ROUNDS:
                L *= 2
            if seg_ctx.checkpoint is not None:
                seg_ctx.checkpoint()
            # fault-tolerance carry snapshot: the device is idle (the
            # sync above) and carry/seg are not yet donated into the
            # next slice, so the host copy races nothing.  A no-op
            # single predicate when tpu.mesh.ft.checkpoint.every.slices
            # is 0.
            def _capture(base=base, carry=carry, seg=seg, parts=ys_parts):
                count_dispatch("engine.snapshot")
                return CarryCheckpoint(
                    base=int(base),
                    carry=snapshot_host_tree(carry),
                    seg=snapshot_host_tree(seg),
                    ys_parts=[dict(p) for p in parts],
                    n_chains=1,
                )

            seg_ctx.offer_snapshot(_capture)
        ys = {
            k: np.concatenate([p[k] for p in ys_parts]) for k in self._ys_keys()
        }
        history = self._fused_history(ys, verbose=False)
        timing = dict(
            timing=True, fused=True, segmented=True,
            segments=len(ys_parts), blocking_syncs=len(ys_parts),
            device_s=round(device_s, 6),
            host_dispatch_s=round(time.monotonic() - t_start - device_s, 6),
            rounds=rounds_ran(ys["ran"]),
        )
        conv = self._convergence_summary(ys)
        if conv is not None:
            timing["convergence"] = conv
        history.append(timing)
        return self.carry_to_state(carry), history

    # ------------------------------------------------------------------
    # driver
    # ------------------------------------------------------------------

    @device_op("engine.run")
    def run(self, *, verbose: bool = False, initial_placement=None):
        """Execute the annealing schedule; returns (final_state, history).

        history is a list of per-round dicts (round, temperature, accepted,
        optional early_stop/extra/objective) plus ONE timing record
        (`timing=True`) carrying the device/host split and the number of
        blocking host<->device syncs the optimization performed — the
        fused path's contract is O(1) syncs regardless of round count.

        `initial_placement` (optional (replica_broker, replica_is_leader,
        replica_disk) triple of this shape) warm-starts the anneal from a
        prior accepted placement instead of the statics' current one —
        the streaming controller's incremental re-anneal.  The RNG chain,
        schedule, and early-stop semantics are unchanged; only the round-0
        carry differs.

        With an ambient SegmentContext (the device scheduler granted this
        dispatch preemptibly — fleet/scheduler.py) the fused schedule runs
        as wall-bounded slices with a preemption checkpoint between them;
        results are byte-identical to the unsegmented run (see
        `_run_segmented`).  Verbose runs stay unsegmented: they are
        debugging tools, and the per-round eval would have to ride every
        slice program.
        """
        if self.config.fused_rounds:
            seg_ctx = current_segment_context()
            if seg_ctx is not None and not verbose:
                return self._run_segmented(
                    seg_ctx, initial_placement=initial_placement
                )
            return self._run_fused(
                verbose=verbose, initial_placement=initial_placement
            )
        return self._run_legacy(
            verbose=verbose, initial_placement=initial_placement
        )

    def _init_for_run(self, initial_placement):
        key = jax.random.PRNGKey(self.config.seed)
        count_dispatch("engine.init")
        if initial_placement is None:
            return self.init_carry(key)
        return self.init_carry_from(key, initial_placement)

    def _fused_history(self, ys, *, verbose: bool) -> list[dict]:
        """Per-round history records from the fused program's fetched ys
        — one builder for the whole-anneal and segmented runners, so the
        two report identically (a segmented run may have fetched fewer
        trailing not-ran rows; those contribute no records anyway).
        With convergence diagnostics compiled in, each record additionally
        carries the round-boundary objective, the per-goal violation
        vector, acceptance counts by move kind, and prior-draw usage."""
        diag = self.config.diagnostics
        history: list[dict] = []
        for r in range(len(ys["ran"])):
            if ys["stopped"][r] and history:
                history[-1]["early_stop"] = True
            if not ys["ran"][r]:
                continue
            rec = dict(
                round=len(history),
                temperature=float(ys["temperature"][r]),
                accepted=int(ys["accepted"][r]),
            )
            if r >= self.config.num_rounds:
                rec["extra"] = True
            if diag:
                rec["objective"] = float(ys["objective"][r])
                rec["goal_violations"] = [
                    round(float(v), 8) for v in np.asarray(ys["goal_viol"][r])
                ]
                rec["accepted_by_kind"] = {
                    "replica": int(ys["acc_replica"][r]),
                    "swap": int(ys["acc_swap"][r]),
                    "leadership": int(ys["acc_lead"][r]),
                }
                rec["prior"] = {
                    "candidates": int(ys["prior_cands"][r]),
                    "accepted": int(ys["prior_acc"][r]),
                }
            elif verbose:
                rec["objective"] = float(ys["objective"][r])
            history.append(rec)
        return history

    def _convergence_summary(self, ys) -> dict | None:
        """Compact convergence summary from one run's fetched per-round
        ys (None unless diagnostics are compiled in) — attached to the
        run's timing record, threaded into the analyzer.optimize span and
        the decision ledger (analyzer/ledger.py)."""
        if not self.config.diagnostics:
            return None
        ran = np.asarray(ys["ran"]).astype(bool)
        obj = np.asarray(ys["objective"])
        viol = np.asarray(ys["goal_viol"])
        last = int(np.nonzero(ran)[0][-1]) if ran.any() else None
        return dict(
            rounds=int(ran.sum()),
            early_stop=bool(np.asarray(ys["stopped"]).any()),
            objective_trajectory=[round(float(x), 8) for x in obj[ran]],
            temperatures=[float(x) for x in np.asarray(ys["temperature"])[ran]],
            accepted=[int(x) for x in np.asarray(ys["accepted"])[ran]],
            accepted_by_kind=dict(
                replica=int(np.asarray(ys["acc_replica"])[ran].sum()),
                swap=int(np.asarray(ys["acc_swap"])[ran].sum()),
                leadership=int(np.asarray(ys["acc_lead"])[ran].sum()),
            ),
            prior=dict(
                candidates=int(np.asarray(ys["prior_cands"])[ran].sum()),
                accepted=int(np.asarray(ys["prior_acc"])[ran].sum()),
            ),
            goal_names=self.chain.names(),
            final_goal_violations=(
                [round(float(v), 8) for v in viol[last]]
                if last is not None
                else []
            ),
        )

    def _run_fused(self, *, verbose: bool = False, initial_placement=None):
        sx = self.statics
        t_start = time.monotonic()
        carry = self._init_for_run(initial_placement)
        if verbose:
            if self._jit_run_fused_verbose is None:
                self._jit_run_fused_verbose = jax.jit(
                    self._run_fused_verbose_impl, donate_argnums=(1,)
                )
            fused = self._jit_run_fused_verbose
        else:
            fused = self._fn("_jit_run_fused")
            if not isinstance(fused, _WarmedFn):
                # no warm pool ran for this engine: the call below traces
                # the fused program lazily — a fresh trace the cold-start
                # report must see
                self._record_fused_trace("fresh")
        count_dispatch("engine.run")
        carry, ys = fused(sx, carry)
        t_disp = time.monotonic()
        # the run's ONE blocking sync: O(rounds) scalars (completes only
        # when the whole fused program has); the final carry stays on
        # device for the report/proposal-diff programs to consume.
        # Timing-split caveat: with ASYNC dispatch (TPU) host_dispatch_s is
        # host-side trace/dispatch work and device_s is device search time;
        # on a synchronous backend (CPU) the fused call above executes the
        # program inline, so device compute lands in host_dispatch_s and
        # device_s measures only this drain — compare wall clocks, not the
        # split, on CPU.
        count_dispatch("engine.sync")
        ys = jax.device_get(ys)
        t_sync = time.monotonic()

        history = self._fused_history(ys, verbose=verbose)
        timing = dict(
            timing=True, fused=True, blocking_syncs=1,
            host_dispatch_s=round(t_disp - t_start, 6),
            device_s=round(t_sync - t_disp, 6),
            rounds=rounds_ran(ys["ran"]),
        )
        conv = self._convergence_summary(ys)
        if conv is not None:
            timing["convergence"] = conv
        history.append(timing)
        return self.carry_to_state(carry), history

    # ------------------------------------------------------------------
    # fused streaming-cycle program (delta scatter + re-anneal + extract)
    # ------------------------------------------------------------------

    def _cycle_statics(self) -> EngineStatics:
        """Statics variant safe to pass alongside DONATED live load arrays.

        The cycle program donates the live replica_load_leader/follower
        buffers; if the statics' embedded state still held the same Array
        objects, XLA would see a donated buffer aliased by a second input
        (an error).  The cycle statics therefore carry zero-filled
        placeholder load leaves — `_cycle_impl` overwrites them with the
        donated (and freshly scattered) arrays before anything reads
        loads.  Cached per statics generation; the placeholder zeros are
        reused across rebinds (same shape every generation)."""
        cached = self._cycle_sx
        if cached is not None and cached[0] is self.statics:
            return cached[1]
        zeros = (
            cached[2]
            if cached is not None
            else jnp.zeros((self.shape.R, NUM_RESOURCES), jnp.float32)
        )
        sxc = dataclasses.replace(
            self.statics,
            state=dataclasses.replace(
                self.statics.state,
                replica_load_leader=zeros,
                replica_load_follower=zeros,
            ),
        )
        self._cycle_sx = (self.statics, sxc, zeros)
        return sxc

    def _cycle_impl(self, sx, ll, fl, rows, new_ll, new_fl, rb, il, dk):
        """The steady-state streaming cycle as ONE XLA program: delta
        scatter + before-report + warm re-anneal + after-report + device
        validation + the proposal-extraction payload.

        Inlines exactly the programs the staged path dispatches separately
        (LiveState's scatter, optimizer's `_report`, `init_carry_from`,
        the fused anneal, `validate_on_device`), sharing their traced
        subprograms — so with full-K config the resulting placement is
        byte-identical to the staged path by construction (pinned by
        tests/test_controller.py).  `ll`/`fl` are DONATED; the scattered
        arrays come back as outputs, making the caller (LiveState) the
        sole owner of one live load copy at 500k-replica scale.

        Reports run in full f32 regardless of `score_dtype` — they are
        user-facing numbers matching optimizer._report, not search
        internals."""
        drop = dict(mode="drop")
        ll = ll.at[rows].set(new_ll, **drop)
        fl = fl.at[rows].set(new_fl, **drop)
        st = dataclasses.replace(
            sx.state, replica_load_leader=ll, replica_load_follower=fl
        )
        sx = dataclasses.replace(sx, state=st)
        agg_b = compute_aggregates(st)
        obj_b, viol_b, _ = self.chain.evaluate(
            st, agg=agg_b, constraint=self.constraint
        )
        stats_b = compute_stats(st, agg_b)
        key = jax.random.PRNGKey(self.config.seed)
        carry = self._init_from_impl(sx, key, rb, il, dk)
        carry, ys = self._fused_rounds_body(sx, carry, verbose=False)
        final = self.carry_to_state(carry, sx)
        agg_a = compute_aggregates(final)
        obj_a, viol_a, _ = self.chain.evaluate(
            final, agg=agg_a, constraint=self.constraint
        )
        stats_a = compute_stats(final, agg_a)
        payload = dict(
            ys=ys,
            obj_before=obj_b, viol_before=viol_b, stats_before=stats_b,
            obj_after=obj_a, viol_after=viol_a, stats_after=stats_a,
            replica_broker=carry.replica_broker,
            replica_is_leader=carry.replica_is_leader,
            replica_disk=carry.replica_disk,
            replica_offline=final.replica_offline,
            replica_disk_bytes=ll[:, int(Resource.DISK)],
            checks=validate_on_device(final),
        )
        return ll, fl, payload

    @device_op("engine.cycle")
    def run_cycle(self, ll, fl, rows, new_ll, new_fl, initial_placement):
        """Host driver for `_cycle_impl`: ONE dispatch, ONE blocking fetch.

        `ll`/`fl` are the LIVE f32[R, 4] load arrays (donated — the caller
        must adopt the returned pair as the new live arrays); `rows` /
        `new_ll` / `new_fl` are the window delta, `initial_placement` the
        warm-start (rb, il, dk) triple.  Rows are padded to power-of-two
        buckets with the out-of-range sentinel R (dropped by the scatter)
        so successive windows of different delta sizes reuse one compiled
        cycle program — same bucketing as LiveState's standalone scatter.

        Returns (new_ll, new_fl, payload, history): payload is the fetched
        host dict (reports, final placement, checks, disk bytes), history
        the same per-round record list `run()` produces.  No copies of
        `initial_placement` are needed: the cycle program does not donate
        rb/il/dk, unlike the standalone fused run."""
        R = self.shape.R
        n = int(len(rows))
        width = max(64, 1 << (max(n, 1) - 1).bit_length())
        pad = width - n
        rows = np.concatenate(
            [np.asarray(rows, np.int32), np.full(pad, R, np.int32)]
        )
        pad_z = np.zeros((pad, NUM_RESOURCES), np.float32)
        new_ll = np.concatenate([np.asarray(new_ll, np.float32), pad_z])
        new_fl = np.concatenate([np.asarray(new_fl, np.float32), pad_z])
        rb, il, dk = initial_placement
        sxc = self._cycle_statics()
        t_start = time.monotonic()
        count_dispatch("engine.cycle")
        out_ll, out_fl, payload = self._jit_run_cycle(
            sxc, ll, fl,
            jnp.asarray(rows), jnp.asarray(new_ll), jnp.asarray(new_fl),
            jnp.asarray(rb, jnp.int32), jnp.asarray(il, bool),
            jnp.asarray(dk, jnp.int32),
        )
        t_disp = time.monotonic()
        # the cycle's ONE blocking sync: reports + placement + per-round ys
        count_dispatch("engine.extract")
        host = jax.device_get(payload)
        t_sync = time.monotonic()
        history = self._fused_history(host["ys"], verbose=False)
        timing = dict(
            timing=True, fused=True, fused_cycle=True, blocking_syncs=1,
            scatter_width=width,
            host_dispatch_s=round(t_disp - t_start, 6),
            device_s=round(t_sync - t_disp, 6),
            rounds=rounds_ran(host["ys"]["ran"]),
        )
        conv = self._convergence_summary(host["ys"])
        if conv is not None:
            timing["convergence"] = conv
        history.append(timing)
        return out_ll, out_fl, host, history

    def _run_legacy(self, *, verbose: bool = False, initial_placement=None):
        """Legacy Python round loop: one scan dispatch + one blocking sync
        per round.  Kept behind `fused_rounds=False` for parity testing and
        per-round host-side debugging.  Convergence diagnostics are a
        fused-path feature (they ride the fused program's per-round ys);
        the legacy loop ignores `OptimizerConfig.diagnostics` — per-round
        inspection here is what `verbose=True` is for."""
        cfg = self.config
        sx = self.statics
        t_start = time.monotonic()
        sync = dict(n=0, s=0.0)

        def fetch(x):
            """device_get with the blocking wait metered (timing record)."""
            t0 = time.monotonic()
            count_dispatch("engine.sync")
            v = jax.device_get(x)
            sync["n"] += 1
            sync["s"] += time.monotonic() - t0
            return v

        carry = self._init_for_run(initial_placement)

        t0_obj = float(fetch(self._fn("_jit_eval")(sx, carry)[0]))
        t0_obj *= cfg.init_temperature_scale
        plan = self._fn("_jit_plan")(sx, carry)
        history = []
        # the authoritative (full-chain) early-stop check is bounded: when
        # the cheap gate opens but goals folded into candidate deltas (topic
        # dist) still have work, re-checking every round would cost more
        # than it saves
        full_checks_left = FULL_CHECK_BUDGET
        # f32-quantized threshold: must take the SAME branch the fused
        # in-graph compare would (OptimizerConfig.early_stop_tol)
        tol = cfg.early_stop_tol

        def _temp(rnd: int) -> float:
            if rnd == cfg.num_rounds - 1:
                return 0.0
            return t0_obj * (cfg.temperature_decay**rnd)

        # pipelined round loop: round rnd+1's scan is DISPATCHED before
        # round rnd's cheap signal is fetched, so the device keeps
        # annealing through the host's per-round round trip.  When the
        # early stop fires, one speculative round's device work is
        # abandoned — early stops are rare at the scales where a round is
        # expensive, and the stop still returns the pre-speculation state.
        temps0 = jnp.full((cfg.steps_per_round,), _temp(0), jnp.float32)
        next_carry, next_stats = self._fn("_scan")(sx, carry, temps0, plan)
        for rnd in range(cfg.num_rounds):
            stats = next_stats
            # fused between-rounds program: wash float drift out of the
            # aggregates, plan the next round's sampling, read the cheap
            # early-stop signal — one dispatch instead of three
            carry, plan, cheap = self._fn("_jit_round_prep")(sx, next_carry)
            if rnd + 1 < cfg.num_rounds:
                temps = jnp.full(
                    (cfg.steps_per_round,), _temp(rnd + 1), jnp.float32
                )
                next_carry, next_stats = self._fn("_scan")(sx, carry, temps, plan)
            # ONE device round-trip per round: cheap (control flow) and the
            # per-step accept counts ride the same fetch — each extra
            # device_get is a full network round trip
            cheap, step_accepts = fetch((cheap, stats["accepted"]))
            accepted = int(step_accepts.sum())
            history.append(dict(round=rnd, temperature=_temp(rnd), accepted=accepted))
            if verbose:
                history[-1]["objective"] = float(
                    fetch(self._fn("_jit_eval")(sx, carry)[0])
                )
            # early stop: all goals already satisfied.  The O(B) lower bound
            # gates the authoritative full-chain check so healthy rounds pay
            # ~nothing.
            if (
                cfg.early_stop_violations >= 0.0
                and rnd < cfg.num_rounds - 1
                and full_checks_left > 0
                and float(cheap) <= tol
            ):
                if float(fetch(self._fn("_jit_eval")(sx, carry)[1])) <= tol:
                    history[-1]["early_stop"] = True
                    break
                full_checks_left -= 1
        else:
            # schedule exhausted with goals possibly unsatisfied (bad starts:
            # mass decommission) — polish with extra greedy rounds while the
            # full chain reports violations and they keep shrinking
            prev_v = None
            for _ in range(cfg.extra_round_budget):
                v = float(fetch(self._fn("_jit_eval")(sx, carry)[1]))
                if v <= tol or (
                    prev_v is not None
                    and v > float(np.float32(prev_v) * np.float32(0.9))
                ):
                    break
                prev_v = v
                temps = jnp.zeros((cfg.steps_per_round,), jnp.float32)
                carry, stats = self._fn("_scan")(sx, carry, temps, plan)
                carry, plan, _cheap = self._fn("_jit_round_prep")(sx, carry)
                history.append(dict(
                    round=len(history), temperature=0.0, extra=True,
                    accepted=int(fetch(stats["accepted"]).sum()),
                ))
                if verbose:
                    # same record schema as the fused path's verbose extras
                    history[-1]["objective"] = float(
                        fetch(self._fn("_jit_eval")(sx, carry)[0])
                    )
        history.append(dict(
            timing=True, fused=False, blocking_syncs=sync["n"],
            device_s=round(sync["s"], 6),
            host_s=round(time.monotonic() - t_start - sync["s"], 6),
        ))
        return self.carry_to_state(carry), history
