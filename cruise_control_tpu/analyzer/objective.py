"""Scalarized goal-chain objective for the batched optimizer.

The reference optimizes goals *sequentially by priority*, letting every
already-optimized goal veto later moves (reference
analyzer/GoalOptimizer.java:437-461, analyzer/AnalyzerUtils.java:119).  A
batched annealer needs one scalar, so the chain is encoded
lexicographically (SURVEY §7 hard part (a)):

  objective = Σ_g  w_g · violation_g(state)  +  w_tie · Σ_g s_g · score_g(state)

with w_g decaying geometrically in priority order and every hard goal
boosted by HARD_BOOST so no weighted sum of soft improvements can pay for a
hard violation.  Violations are dimensionless fractions (each goal
normalizes by its own scale), which is what makes one scalar meaningful.

The balancedness score reported to users mirrors reference
KafkaCruiseControlUtils.balancednessCostByGoal:511-537 (priority weight
1.1x, strictness weight 1.5x for hard goals).
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from cruise_control_tpu.analyzer.goals import DEFAULT_GOAL_ORDER, GOALS_BY_NAME
from cruise_control_tpu.analyzer.goals.base import Goal
from cruise_control_tpu.config.balancing import BalancingConstraint, DEFAULT_CONSTRAINT
from cruise_control_tpu.models.aggregates import BrokerAggregates, compute_aggregates
from cruise_control_tpu.models.state import ClusterState

#: weight multiplier separating hard goals from the soft chain
HARD_BOOST = 1e4
#: geometric decay between adjacent priorities (reference uses priority order
#: as an absolute veto; 0.5 keeps ~2x headroom per rank while staying in f32
#: range across 19 goals)
PRIORITY_DECAY = 0.5
#: weight of the continuous tiebreaker scores relative to the smallest
#: violation weight
TIE_WEIGHT = 1e-3


@dataclasses.dataclass(frozen=True)
class GoalChain:
    """An ordered, weighted goal list (the reference's `default.goals`)."""

    goals: tuple[Goal, ...]
    weights: tuple[float, ...]  # violation weight per goal, same order

    @staticmethod
    def from_names(
        names: list[str] | None = None,
        *,
        hard_boost: float = HARD_BOOST,
        decay: float = PRIORITY_DECAY,
    ) -> "GoalChain":
        names = list(names) if names is not None else list(DEFAULT_GOAL_ORDER)
        goals = tuple(GOALS_BY_NAME[n] for n in names)
        weights = []
        for rank, g in enumerate(goals):
            w = decay**rank
            if g.hard:
                w *= hard_boost
            weights.append(w)
        return GoalChain(goals=goals, weights=tuple(weights))

    def evaluate(
        self,
        state: ClusterState,
        agg: BrokerAggregates | None = None,
        constraint: BalancingConstraint = DEFAULT_CONSTRAINT,
        *,
        score_dtype: str = "float32",
    ):
        """Full evaluation: (scalar objective, violations[G], scores[G]).

        `score_dtype` (config analyzer.precision.score.dtype) selects the
        accumulation precision of the weighted objective sum ONLY: the
        per-goal violations/scores stay f32 (they feed early-stop compares
        and user reports), and the mixed-precision branch is taken only
        for a non-default dtype, so the default traced graph is
        byte-identical to the always-f32 one — the fp32 fallback pin.
        """
        if agg is None:
            agg = compute_aggregates(state)
        violations = jnp.stack([g.violation(state, agg, constraint) for g in self.goals])
        scores = jnp.stack([g.score(state, agg, constraint) for g in self.goals])
        w = jnp.asarray(self.weights, jnp.float32)
        if score_dtype != "float32":
            dt = jnp.dtype(score_dtype)
            obj = (
                (w.astype(dt) * violations.astype(dt)).sum().astype(jnp.float32)
                + TIE_WEIGHT
                * min(self.weights)
                * scores.astype(dt).sum().astype(jnp.float32)
            )
        else:
            obj = (w * violations).sum() + TIE_WEIGHT * min(self.weights) * scores.sum()
        return obj, violations, scores

    def objective_f64(self, violations, scores) -> np.ndarray:
        """`evaluate`'s objective recomposed on the host in float64 from
        its f32 per-goal terms (any leading batch axes).  Reports that
        compare states use this: under one hard violation the f32 sum
        sits near 625, where its ulp (6.1e-5) exceeds the weight of every
        goal ranked below the tenth, so two states that differ only in
        those goals would read as equal."""
        v = np.asarray(violations, np.float64)
        s = np.asarray(scores, np.float64)
        return (
            v @ np.asarray(self.weights, np.float64)
            + TIE_WEIGHT * min(self.weights) * s.sum(-1)
        )

    def hard_mask(self) -> np.ndarray:
        return np.asarray([g.hard for g in self.goals])

    def names(self) -> list[str]:
        return [g.name for g in self.goals]


def balancedness_score(
    violations: np.ndarray,
    chain: GoalChain,
    *,
    priority_weight: float = 1.1,
    strictness_weight: float = 1.5,
) -> float:
    """0-100 user-facing score (reference KafkaCruiseControlUtils.java:511-537).

    The reference sums weight = priority_weight^rank * (strictness_weight if
    hard) over *violated* goals and scales to 100.  A goal is "violated" here
    when its normalized violation exceeds 1e-6 — violations are fractions of
    cluster-wide totals computed in f32, whose noise floor at 500k-replica
    scale is ~1e-8..1e-7; the reference's per-goal epsilons serve the same
    role (its resource epsilons are far coarser than 1e-6 of total load).
    """
    n = len(chain.goals)
    weights = np.array(
        [
            priority_weight ** (n - 1 - i) * (strictness_weight if g.hard else 1.0)
            for i, g in enumerate(chain.goals)
        ],
        np.float64,
    )
    total = weights.sum()
    violated = np.asarray(violations) > 1e-6
    return float(100.0 * (1.0 - weights[violated].sum() / total))


DEFAULT_CHAIN = GoalChain.from_names()
