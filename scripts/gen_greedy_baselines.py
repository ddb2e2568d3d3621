"""Generate BASELINE_GREEDY.json: the greedy CPU oracle run to convergence
per bench config (the in-bench greedy was budget-truncated, so
`tpu_beats_greedy` compared against a cut-off run).

Builds the EXACT states bench.py uses (same specs/seeds/chains, imported
from bench) and runs `greedy_optimize` with generous caps.  Each entry
records the objective, wall seconds, move count, and whether the run
terminated on its own (`converged`) or hit the safety deadline.  bench.py
prefers these committed numbers over re-running greedy.

Usage:  [GREEDY_CONFIGS=1,2,3,5] [GREEDY_BUDGET_S=1800] python
scripts/gen_greedy_baselines.py
"""

import json
import os
import sys
import time

sys.path.insert(0, "/root/repo")

import bench  # noqa: E402 — spec/config source of truth

from cruise_control_tpu.analyzer.greedy import greedy_optimize  # noqa: E402
from cruise_control_tpu.analyzer.objective import DEFAULT_CHAIN, GoalChain  # noqa: E402

OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "BASELINE_GREEDY.json")
BUDGET = float(os.environ.get("GREEDY_BUDGET_S", "1800"))


def _state_and_chain(name):
    from cruise_control_tpu.testing.fixtures import (
        RandomClusterSpec,
        random_cluster_fast,
        small_cluster,
    )

    if name == "config1":
        return small_cluster(), DEFAULT_CHAIN, dict(moves=20000, dests=8)
    if name == "config2":
        chain = GoalChain.from_names([
            "ReplicaCapacityGoal",
            "DiskUsageDistributionGoal",
            "NetworkInboundUsageDistributionGoal",
            "NetworkOutboundUsageDistributionGoal",
            "CpuUsageDistributionGoal",
        ])
        state = random_cluster_fast(RandomClusterSpec(**bench.SMALL_SPEC), seed=42)
        return state, chain, dict(moves=20000, dests=8)
    if name == "config3":
        chain = GoalChain.from_names([
            "RackAwareGoal",
            "DiskCapacityGoal",
            "IntraBrokerDiskCapacityGoal",
            "IntraBrokerDiskUsageDistributionGoal",
        ])
        state = random_cluster_fast(
            RandomClusterSpec(**{**bench.MID_SPEC, "disks_per_broker": 4}), seed=42
        )
        return state, chain, dict(moves=20000, dests=8)
    if name == "config5":
        import dataclasses as dc

        import jax.numpy as jnp
        import numpy as np

        state = random_cluster_fast(
            RandomClusterSpec(**bench.NORTH_STAR_SPEC), seed=42
        )
        B = state.shape.B
        n_dead = max(2, B // 100)
        alive = np.asarray(state.broker_alive).copy()
        alive[np.arange(B - n_dead, B)] = False
        offline = np.asarray(state.replica_offline) | ~alive[
            np.asarray(state.replica_broker)
        ]
        state = dc.replace(
            state,
            broker_alive=jnp.asarray(alive),
            disk_alive=jnp.asarray(alive[:, None] & np.asarray(state.disk_alive)),
            replica_offline=jnp.asarray(offline),
        )
        return state, DEFAULT_CHAIN, dict(moves=1000, dests=6)
    raise ValueError(name)


def main():
    wanted = (os.environ.get("GREEDY_CONFIGS") or "1,2,3,5").replace(" ", "").split(",")
    results = {}
    if os.path.exists(OUT):
        with open(OUT) as f:
            results = json.load(f)
    for n in wanted:
        name = f"config{n}"
        print(f"=== {name} (budget {BUDGET:.0f}s) ===", flush=True)
        state, chain, caps = _state_and_chain(name)
        t0 = time.time()
        final, info = greedy_optimize(
            state, chain, max_moves_per_goal=caps["moves"],
            candidate_dests=caps["dests"], seed=0, time_budget_s=BUDGET,
            return_info=True,
        )
        obj, _, _ = chain.evaluate(final)
        results[name] = dict(
            objective=float(obj),
            seconds=info["seconds"],
            moves=info["moves"],
            converged=info["converged"],
            budget_s=BUDGET,
            fingerprint=bench._baseline_fingerprint(state, chain),
        )
        print(f"{name}: {results[name]}", flush=True)
        with open(OUT, "w") as f:
            json.dump(results, f, indent=2, sort_keys=True)
        print(f"wrote {OUT} ({time.time()-t0:.0f}s)", flush=True)


if __name__ == "__main__":
    main()
