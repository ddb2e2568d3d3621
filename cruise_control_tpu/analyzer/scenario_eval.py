"""Batched what-if evaluation — N hypothetical clusters, one device program.

The goal chain (analyzer/objective.py) is pure jnp over the ClusterState
pytree, so N scenario states of ONE shared (bucketed) shape stack into a
leading batch axis and score under `jax.vmap` in a single jitted
program: per-scenario objective + per-goal violations for the price of
one dispatch.  That is the planner's workhorse — a rightsize sweep or a
rack-loss matrix is dozens of hypotheticals, and evaluating them
sequentially would pay dispatch + transfer per scenario for arrays that
are 99% identical.

The optional `optimize=True` pass runs the FULL anneal per scenario
through the caller's GoalOptimizer: every scenario state shares the
batch shape, so the optimizer's engine cache compiles ONCE and rebinds
for the rest (observable via the `analyzer.engine-cache-*` counters —
the acceptance contract of the planner).

Supervision: the batched device call runs under the same
DeviceSupervisor the optimizer uses; a wedged device degrades to a
sequential CPU evaluation (tagged `degraded=True`) instead of hanging
the planner endpoints.  The optimize pass needs no extra handling —
GoalOptimizer.optimize already degrades itself.
"""

from __future__ import annotations

import dataclasses
import logging
import time

import numpy as np

from cruise_control_tpu.analyzer.objective import (
    DEFAULT_CHAIN,
    GoalChain,
    balancedness_score,
)
from cruise_control_tpu.common.device_watchdog import device_op
from cruise_control_tpu.config.balancing import BalancingConstraint, DEFAULT_CONSTRAINT
from cruise_control_tpu.models.state import ClusterState

log = logging.getLogger(__name__)

#: goals violated above this are "failed" — the same f32-noise epsilon
#: balancedness_score and OptimizerResult.violated_goals_after use
VIOLATION_TOL = 1e-6


@dataclasses.dataclass(frozen=True)
class ScenarioOutcome:
    """What one hypothetical looks like, before and (optionally) after a fix."""

    name: str
    objective: float
    violations: np.ndarray  # f32[G] per-goal violation at current placement
    violated_goals: list
    balancedness: float
    hard_goals_satisfied: bool
    brokers_alive: int
    degraded: bool = False
    #: present when the full anneal ran: the projected post-fix cluster
    fix: dict | None = None

    def to_json(self) -> dict:
        out = {
            "name": self.name,
            "objective": self.objective,
            "violatedGoals": list(self.violated_goals),
            "balancedness": self.balancedness,
            "hardGoalsSatisfied": self.hard_goals_satisfied,
            "brokersAlive": self.brokers_alive,
        }
        if self.fix is not None:
            out["fix"] = self.fix
        return out


class ScenarioEvaluator:
    """Batch-scores scenario states on the goal chain; optionally anneals
    each through the shared GoalOptimizer."""

    def __init__(
        self,
        chain: GoalChain = DEFAULT_CHAIN,
        constraint: BalancingConstraint = DEFAULT_CONSTRAINT,
        *,
        optimizer=None,
        supervisor=None,
        sensors=None,
        balancedness_weights: tuple[float, float] = (1.1, 1.5),
        max_scenarios: int = 32,
    ):
        """optimizer: GoalOptimizer for the optimize=True pass (its chain
        should be this chain — the facade wires both from config);
        supervisor: DeviceSupervisor shared with the optimizer so a wedged
        device degrades the whole analyzer surface coherently."""
        self.chain = chain
        self.constraint = constraint
        self.optimizer = optimizer
        self.supervisor = supervisor
        self.sensors = sensors
        self.balancedness_weights = balancedness_weights
        self.max_scenarios = max_scenarios
        import threading
        from collections import OrderedDict

        #: jitted batched program per (shape, N, varying fieldset) — the
        #: arrays are arguments, not constants, so one entry serves every
        #: batch of that geometry.  BOUNDED LRU: under topology churn and
        #: varied batch mixes an unbounded map accretes compiled XLA
        #: executables forever (the leak class the optimizer's engine
        #: cache already guards against).  Locked: the facade shares ONE
        #: evaluator across the user-task pool, and OrderedDict reordering
        #: is not thread-safe (same discipline as the engine cache's lock).
        self._batched_fns: OrderedDict = OrderedDict()
        self._batched_fns_cap = 8
        self._fns_lock = threading.Lock()
        self._cpu_fn = None
        self._single_fn = None

    # ------------------------------------------------------------------
    # batched scoring
    # ------------------------------------------------------------------

    def evaluate_states(self, states: list[ClusterState]):
        """(objectives f64[N], violations f64[N, G], degraded) for N states
        of ONE shared shape — one stacked vmap program, one dispatch."""
        import jax

        if not states:
            return np.zeros(0), np.zeros((0, len(self.chain.goals))), False
        shapes = {s.shape for s in states}
        if len(shapes) > 1:
            raise ValueError(
                f"scenario batch spans {len(shapes)} shapes; plan_shape the "
                "batch so it shares one compiled program"
            )
        sup = self.supervisor
        if sup is None:
            viol, scores = self._evaluate_on_device(states)
            return self.chain.objective_f64(viol, scores), viol, False
        from cruise_control_tpu.common.device_watchdog import DeviceDegradedError

        if sup.available():
            try:
                viol, scores = sup.call(
                    lambda: self._evaluate_on_device(states), op="scenario-eval"
                )
                return self.chain.objective_f64(viol, scores), viol, False
            except DeviceDegradedError:
                pass
        obj, viol = self._evaluate_cpu(states)
        if self.sensors is not None:
            self.sensors.counter("planner.degraded-evaluations").inc()
        return obj, viol, True

    @device_op("scenario.batch-eval")
    def _evaluate_on_device(self, states):
        import jax
        import jax.numpy as jnp

        shape = states[0].shape
        fields = [
            f.name for f in dataclasses.fields(ClusterState) if f.name != "shape"
        ]
        # scenario states alias the shared base's arrays for every field
        # their scenario did not touch (models/whatif.py dirty tracking):
        # those ride into the program ONCE; only the genuinely different
        # fields are stacked — for a typical batch that is a couple of
        # broker-axis vectors, not N copies of the model
        shared, varying = {}, {}
        for f in fields:
            vals = [getattr(s, f) for s in states]
            if all(v is vals[0] for v in vals[1:]):
                shared[f] = vals[0]
            else:
                varying[f] = jnp.asarray(np.stack([np.asarray(v) for v in vals]))
        if not varying:
            # every scenario is the identity: score the base once, fan out
            viol, scores = self._single_terms(states[0])
            n = len(states)
            return np.tile(viol, (n, 1)), np.tile(scores, (n, 1))
        key = (shape, len(states), frozenset(varying))
        with self._fns_lock:
            fn = self._batched_fns.get(key)
            if fn is not None:
                self._batched_fns.move_to_end(key)
        if fn is None:
            fn = self.batch_program(shape)
            with self._fns_lock:
                self._batched_fns[key] = fn
                while len(self._batched_fns) > self._batched_fns_cap:
                    self._batched_fns.popitem(last=False)
        viol, scores = jax.device_get(fn(shared, varying))
        return np.asarray(viol, np.float64), np.asarray(scores, np.float64)

    def batch_program(self, shape):
        """The jitted batch scorer for states of `shape`: (shared fields,
        varying fields with a leading batch axis) -> (violations [N, G],
        scores [N, G])."""
        import jax

        chain, constraint = self.chain, self.constraint

        def batched(shared, varying):
            def one(diff):
                s = ClusterState(shape=shape, **shared, **diff)
                _, viol, scores = chain.evaluate(s, constraint=constraint)
                return viol, scores

            # lax.map, not vmap: the goal chain is segment-sum heavy,
            # and batching scatters adds a batch dimension XLA lowers
            # poorly (CPU measurably WORSE than sequential).  lax.map
            # compiles the single-state program once and loops it on
            # device — identical per-scenario numerics (pinned by the
            # scenarios bench gate), one dispatch, one host sync.
            return jax.lax.map(one, varying)

        return jax.jit(batched)

    def _single_terms(self, state):
        """(violations f64[G], scores f64[G]) of one state, jitted."""
        import jax

        if self._single_fn is None:

            def one(s):
                _, viol, scores = self.chain.evaluate(s, constraint=self.constraint)
                return viol, scores

            self._single_fn = jax.jit(one)
        viol, scores = jax.device_get(self._single_fn(state))
        return np.asarray(viol, np.float64), np.asarray(scores, np.float64)

    def _single_eval(self, state):
        """(objective, violations f64[G]) of one state — the sequential
        twin of the batched program, composed the same way."""
        viol, scores = self._single_terms(state)
        return float(self.chain.objective_f64(viol, scores)), viol

    # ------------------------------------------------------------------
    # calibration scoring (decision ledger, analyzer/ledger.py)
    # ------------------------------------------------------------------

    @device_op("scenario.score-state")
    def _score_state_on_device(self, state):
        import jax

        from cruise_control_tpu.models.stats import compute_stats

        if getattr(self, "_score_fn", None) is None:

            def one(s):
                obj, viol, _ = self.chain.evaluate(s, constraint=self.constraint)
                return obj, viol, compute_stats(s)

            self._score_fn = jax.jit(one)
        return jax.device_get(self._score_fn(state))

    def score_state(self, state: ClusterState):
        """(objective, per-goal violations f64[G], ClusterStats, degraded)
        of ONE measured cluster state — the calibration loop's scorer:
        the SAME goal chain + constraint the decision's prediction rode,
        evaluated in one batched dispatch (goal chain + cluster stats as
        one program), supervised like every other evaluator dispatch with
        a sequential-CPU degraded fallback."""
        import jax

        sup = self.supervisor
        if sup is None:
            obj, viol, stats = self._score_state_on_device(state)
            return float(obj), np.asarray(viol, np.float64), stats, False
        from cruise_control_tpu.common.device_watchdog import DeviceDegradedError

        if sup.available():
            try:
                obj, viol, stats = sup.call(
                    lambda: self._score_state_on_device(state),
                    op="calibration-score",
                )
                return float(obj), np.asarray(viol, np.float64), stats, False
            except DeviceDegradedError:
                pass
        from cruise_control_tpu.models.stats import compute_stats

        # degraded twin: objective/violations via the sequential-CPU
        # evaluator path, cluster stats computed on the CPU backend
        cpu = jax.local_devices(backend="cpu")[0]
        host = jax.tree.map(np.asarray, state)
        objs, viols = self._evaluate_cpu([host])
        with jax.default_device(cpu):
            stats = jax.tree.map(np.asarray, compute_stats(host))
        if self.sensors is not None:
            self.sensors.counter("planner.degraded-evaluations").inc()
        return float(objs[0]), np.asarray(viols[0], np.float64), stats, True

    def _evaluate_cpu(self, states):
        """Degraded path: sequential single-state evaluation pinned to the
        host CPU backend — same numbers, no batching, no accelerator."""
        import jax

        cpu = jax.local_devices(backend="cpu")[0]
        if self._cpu_fn is None:

            def one(s):
                _, viol, scores = self.chain.evaluate(s, constraint=self.constraint)
                return viol, scores

            self._cpu_fn = jax.jit(one)
        viols, scores = [], []
        with jax.default_device(cpu):
            for s in states:
                host = jax.tree.map(np.asarray, s)
                v, sc = jax.device_get(self._cpu_fn(host))
                viols.append(np.asarray(v, np.float64))
                scores.append(np.asarray(sc, np.float64))
        viols = np.stack(viols)
        return self.chain.objective_f64(viols, np.stack(scores)), viols

    # ------------------------------------------------------------------
    # the full planner pass
    # ------------------------------------------------------------------

    def evaluate(
        self,
        base_state: ClusterState,
        scenarios,
        catalog=None,
        *,
        optimize=False,
        bucket=None,
    ) -> list[ScenarioOutcome]:
        """Apply each scenario to `base_state`, batch-score all of them,
        and anneal for the projected post-fix view.  `optimize`: one bool
        for the whole batch, or a per-scenario sequence (the facade rides
        a baseline scenario in every /simulate batch and must not pay a
        full anneal for a fix block it never serializes)."""
        from cruise_control_tpu.planner.scenario import apply_scenario, plan_shape

        scenarios = list(scenarios)
        if len(scenarios) > self.max_scenarios:
            raise ValueError(
                f"{len(scenarios)} scenarios exceed planner.max.scenarios="
                f"{self.max_scenarios}"
            )
        if isinstance(optimize, bool):
            optimize = [optimize] * len(scenarios)
        elif len(optimize) != len(scenarios):
            raise ValueError(
                f"optimize mask has {len(optimize)} entries for "
                f"{len(scenarios)} scenarios"
            )
        t0 = time.monotonic()
        shape = plan_shape(base_state, scenarios, bucket=bucket)
        if shape != base_state.shape:
            from cruise_control_tpu.models.builder import pad_state

            # pad ONCE: every scenario state then aliases this base's
            # arrays for its untouched fields, which is what lets the
            # batched program take the shared fields unstacked
            base_state = pad_state(base_state, shape)
        states = [
            apply_scenario(base_state, sc, catalog, shape=shape)
            for sc in scenarios
        ]
        objs, viols, degraded = self.evaluate_states(states)
        hard = self.chain.hard_mask()
        names = self.chain.names()
        pw, sw = self.balancedness_weights
        outcomes = []
        for i, sc in enumerate(scenarios):
            v = viols[i]
            alive = int(
                (np.asarray(states[i].broker_valid) & np.asarray(states[i].broker_alive)).sum()
            )
            fix = None
            if optimize[i] and self.optimizer is not None:
                fix = self._fix_summary(states[i])
            outcomes.append(
                ScenarioOutcome(
                    name=sc.name,
                    objective=float(objs[i]),
                    violations=v,
                    violated_goals=[n for n, x in zip(names, v) if x > VIOLATION_TOL],
                    balancedness=balancedness_score(
                        v, self.chain, priority_weight=pw, strictness_weight=sw
                    ),
                    hard_goals_satisfied=bool((v[hard] <= VIOLATION_TOL).all()),
                    brokers_alive=alive,
                    degraded=degraded,
                    fix=fix,
                )
            )
        if self.sensors is not None:
            self.sensors.counter("planner.scenarios-evaluated").inc(len(scenarios))
            self.sensors.gauge("planner.last-batch-size").set(len(scenarios))
            self.sensors.timer("planner.batch-eval-timer").update(
                time.monotonic() - t0
            )
        return outcomes

    def _fix_summary(self, state: ClusterState) -> dict:
        """Run the full anneal on one scenario state; the projected
        post-fix cluster as a summary dict.  Engine reuse across the batch
        is the point: every scenario shares the planned shape, so the
        optimizer compiles once and rebinds N-1 times."""
        result = self.optimizer.optimize(state)
        out = result.summary()
        out["violatedGoalsBefore"] = [
            n for n, v in zip(result.goal_names, result.violations_before)
            if v > VIOLATION_TOL
        ]
        hard = self.chain.hard_mask()
        after = np.asarray(result.violations_after)
        out["hardGoalsSatisfiedAfter"] = bool(
            (after[hard[: after.size]] <= VIOLATION_TOL).all()
        )
        return out
