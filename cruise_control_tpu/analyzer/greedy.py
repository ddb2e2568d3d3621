"""Greedy CPU oracle — a faithful re-expression of the reference search.

Mirrors reference analyzer/goals/AbstractGoal.optimize:66-107: goals are
optimized strictly in priority order; for each goal, brokers are visited
and moves are applied when they (a) help the current goal and (b) do not
regress any previously-optimized goal (reference
AnalyzerUtils.isProposalAcceptableForOptimizedGoals:119).  The move
neighborhood matches the reference's: single replica relocations
(AbstractGoal.maybeApplyBalancingAction:179), leadership transfers
(ActionType.LEADERSHIP_MOVEMENT; LeaderBytesInDistributionGoal), and
replica swaps (AbstractGoal.maybeApplySwapAction:236,
ResourceDistributionGoal.java:502-599).

This exists for TESTS AND BENCHMARKS ONLY: it is the quality baseline the
batched TPU engine must match or beat (SURVEY §7 "equal-or-better on the
aggregate score"), the role OptimizationVerifier's greedy runs play in the
reference test suite.  Single-threaded; candidate evaluation goes through
one jitted violation function so large fixtures stay tractable, and a
wall-clock budget caps total work the way the reference's minutes-long
runs would be capped in practice.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from cruise_control_tpu.analyzer.objective import GoalChain
from cruise_control_tpu.config.balancing import BalancingConstraint, DEFAULT_CONSTRAINT
from cruise_control_tpu.models.aggregates import compute_aggregates
from cruise_control_tpu.models.state import ClusterState


def _make_eval(chain: GoalChain, constraint):
    """One jitted program evaluating all goal violations for a state."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def eval_fn(s: ClusterState):
        agg = compute_aggregates(s)
        return jnp.stack([g.violation(s, agg, constraint) for g in chain.goals])

    return lambda s: np.asarray(eval_fn(s), np.float64)


def greedy_optimize(
    state: ClusterState,
    chain: GoalChain,
    constraint: BalancingConstraint = DEFAULT_CONSTRAINT,
    *,
    max_moves_per_goal: int = 200,
    candidate_dests: int = 10,
    seed: int = 0,
    time_budget_s: float | None = None,
    return_info: bool = False,
    device=None,
    options=None,
):
    """Sequential greedy search over single moves, reference-style.

    For tractability the oracle samples `candidate_dests` destinations per
    source replica instead of scanning all brokers (the reference prunes
    similarly via sorted candidate lists, model/SortedReplicas.java:47).
    `time_budget_s` bounds wall-clock: when exhausted, the best state so
    far is returned (the reference search at LinkedIn scale runs minutes;
    benchmarks cap it to keep rounds bounded).

    With `return_info` returns (state, info) where info records whether the
    run CONVERGED (terminated on its own: goals satisfied or no improving
    move within the sampled neighborhood) vs hit the deadline — baseline
    generation needs the distinction (a truncated oracle understates the
    bar).

    `device` pins the whole search — the jitted evaluation AND the
    candidate states the move applicators build — to a specific backend
    device: the service's DEGRADED mode runs the oracle with device=cpu
    while the accelerator is circuit-broken, so the fallback cannot hang
    on the very device it is falling back from.

    `options` (analyzer.options.OptimizationOptions) applies the same
    movement restrictions the engine honors: excluded topics stay put
    (unless offline), excluded/requested destination masks bound where
    replicas may land, and leadership never moves onto
    excluded-for-leadership brokers — so a DEGRADED self-healing fix keeps
    its exclusion contract (recently removed/demoted brokers).
    """
    import contextlib

    import jax

    ctx = (
        jax.default_device(device) if device is not None else contextlib.nullcontext()
    )
    with ctx:
        return _greedy_optimize_impl(
            state, chain, constraint,
            max_moves_per_goal=max_moves_per_goal,
            candidate_dests=candidate_dests,
            seed=seed,
            time_budget_s=time_budget_s,
            return_info=return_info,
            restrictions=_MoveRestrictions.from_options(state, options),
        )


@dataclasses.dataclass(frozen=True)
class _MoveRestrictions:
    """OptimizationOptions rendered as plain numpy masks for the oracle.

    Built through the options' own mask helpers so the oracle shares the
    engine's fitting semantics exactly — notably, a stale mask shorter
    than the real broker count FAILS LOUDLY instead of silently
    un-excluding brokers (OptimizationOptions._fit)."""

    dest_allowed: np.ndarray  # bool[B], replica-move destinations
    lead_allowed: np.ndarray  # bool[B], may receive leadership
    topic_movable: np.ndarray  # bool[T], False = stays put unless offline

    @staticmethod
    def from_options(state: ClusterState, options) -> "_MoveRestrictions":
        from cruise_control_tpu.analyzer.options import DEFAULT_OPTIONS

        options = options if options is not None else DEFAULT_OPTIONS
        return _MoveRestrictions(
            dest_allowed=options.dest_allowed(state),
            lead_allowed=options.leadership_allowed(state),
            topic_movable=options.topic_movable(state),
        )


def _greedy_optimize_impl(
    state: ClusterState,
    chain: GoalChain,
    constraint: BalancingConstraint,
    *,
    max_moves_per_goal: int,
    candidate_dests: int,
    seed: int,
    time_budget_s: float | None,
    return_info: bool,
    restrictions: "_MoveRestrictions",
):
    rng = np.random.default_rng(seed)
    eval_fn = _make_eval(chain, constraint)
    cur = state
    viol = eval_fn(cur)
    t0 = time.monotonic()
    deadline = t0 + time_budget_s if time_budget_s else None
    moves = 0
    hit_deadline = False
    hit_move_cap = False

    for gi in range(len(chain.goals)):
        if hit_deadline:
            break
        moves_this_goal = 0
        while True:
            if viol[gi] <= 1e-12:
                break
            if moves_this_goal >= max_moves_per_goal:
                # ran out of per-goal move budget with the goal still
                # violated — truncation, NOT convergence
                hit_move_cap = True
                break
            if deadline is not None and time.monotonic() > deadline:
                hit_deadline = True
                break
            move = _find_improving_move(
                cur, eval_fn, viol, gi, rng, candidate_dests, deadline, restrictions
            )
            if move is None:
                # a deadline that fired inside the move search is truncation,
                # not convergence
                if deadline is not None and time.monotonic() > deadline:
                    hit_deadline = True
                break
            cur, viol = move
            moves += 1
            moves_this_goal += 1
    if return_info:
        return cur, dict(
            converged=not hit_deadline and not hit_move_cap,
            moves=moves,
            seconds=round(time.monotonic() - t0, 1),
        )
    return cur


def _find_improving_move(
    cur, eval_fn, viol, gi, rng, candidate_dests, deadline, restrictions
):
    """One accepted move: improves goal gi without regressing goals < gi.

    Tries, in the reference's order, relocation -> leadership transfer ->
    swap for each sampled source replica.  `restrictions` bounds the
    neighborhood: destination masks apply to relocations and both sides of
    a swap, excluded topics only move while offline, and leadership never
    lands on an excluded-for-leadership broker.
    """
    valid = np.asarray(cur.replica_valid)
    brokers = np.asarray(cur.replica_broker)
    is_leader = np.asarray(cur.replica_is_leader)
    offline = np.asarray(cur.replica_offline)
    topic = np.asarray(cur.replica_topic)
    alive = np.asarray(cur.broker_alive) & np.asarray(cur.broker_valid)
    alive_ids = np.nonzero(alive & restrictions.dest_allowed)[0]
    part = np.asarray(cur.replica_partition)

    def accepted(nxt):
        nviol = eval_fn(nxt)
        if nviol[gi] < viol[gi] - 1e-12 and not (nviol[:gi] > viol[:gi] + 1e-9).any():
            return nxt, nviol
        return None

    ridx = np.nonzero(valid)[0]
    rng.shuffle(ridx)
    for r in ridx[:64]:
        if deadline is not None and time.monotonic() > deadline:
            return None
        src = brokers[r]
        # excluded-topic replicas stay put unless offline (reference
        # excludedTopics semantics); leadership transfers stay allowed
        movable = restrictions.topic_movable[topic[r]] or offline[r]
        dests = rng.choice(
            alive_ids, size=min(candidate_dests, alive_ids.size), replace=False
        )

        # 1. relocation (reference maybeApplyBalancingAction)
        if movable:
            for dst in dests:
                if deadline is not None and time.monotonic() > deadline:
                    return None
                if dst == src:
                    continue
                # a relocating LEADER replica carries leadership along
                if is_leader[r] and not restrictions.lead_allowed[dst]:
                    continue
                if ((part == part[r]) & (brokers == dst) & valid).any():
                    continue
                got = accepted(_apply_move(cur, int(r), int(dst)))
                if got is not None:
                    return got

        # 2. leadership transfer (reference ActionType.LEADERSHIP_MOVEMENT)
        if not is_leader[r] and alive[src] and restrictions.lead_allowed[src]:
            leader_mask = (part == part[r]) & is_leader & valid
            if leader_mask.any():
                got = accepted(_apply_leadership(cur, int(r), int(leader_mask.argmax())))
                if got is not None:
                    return got

        # 3. swap with a replica on a destination broker (reference
        #    maybeApplySwapAction:236, ResourceDistributionGoal swap-in/out)
        # the counterpart lands on src, so src must be an allowed
        # destination too
        if movable and restrictions.dest_allowed[src]:
            for dst in dests:
                if deadline is not None and time.monotonic() > deadline:
                    return None
                if dst == src:
                    continue
                on_dst = np.nonzero(valid & (brokers == dst) & (part != part[r]))[0]
                if on_dst.size == 0:
                    continue
                q = int(on_dst[rng.integers(on_dst.size)])
                # the counterpart replica is bound by the same topic rule
                if not restrictions.topic_movable[topic[q]] and not offline[q]:
                    continue
                # leadership travels with a swapped leader replica too
                if is_leader[r] and not restrictions.lead_allowed[dst]:
                    continue
                if is_leader[q] and not restrictions.lead_allowed[src]:
                    continue
                # neither partition may end up duplicated
                if ((part == part[r]) & (brokers == dst) & valid).any():
                    continue
                if ((part == part[q]) & (brokers == src) & valid).any():
                    continue
                got = accepted(_apply_swap(cur, int(r), int(q)))
                if got is not None:
                    return got
    return None


def _apply_move(cur: ClusterState, r: int, dst: int) -> ClusterState:
    import jax.numpy as jnp

    rb = np.asarray(cur.replica_broker).copy()
    rb[r] = dst
    offline = np.asarray(cur.replica_offline).copy()
    offline[r] = not bool(np.asarray(cur.broker_alive)[dst])
    return dataclasses.replace(
        cur,
        replica_broker=jnp.asarray(rb),
        replica_offline=jnp.asarray(offline),
    )


def _apply_leadership(cur: ClusterState, rt: int, rf: int) -> ClusterState:
    """Transfer leadership of a partition from replica rf to replica rt."""
    import jax.numpy as jnp

    lead = np.asarray(cur.replica_is_leader).copy()
    lead[rf] = False
    lead[rt] = True
    return dataclasses.replace(cur, replica_is_leader=jnp.asarray(lead))


def _apply_swap(cur: ClusterState, r: int, q: int) -> ClusterState:
    """Swap the brokers of replicas r and q (different partitions)."""
    import jax.numpy as jnp

    rb = np.asarray(cur.replica_broker).copy()
    rb[r], rb[q] = rb[q], rb[r]
    alive = np.asarray(cur.broker_alive)
    offline = np.asarray(cur.replica_offline).copy()
    offline[r] = not bool(alive[rb[r]])
    offline[q] = not bool(alive[rb[q]])
    return dataclasses.replace(
        cur,
        replica_broker=jnp.asarray(rb),
        replica_offline=jnp.asarray(offline),
    )
