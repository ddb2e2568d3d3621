"""Diagnose config5 (decommission self-healing) residual goal violations."""

import os
import sys
import time
import dataclasses as dc

sys.path.insert(0, "/root/repo")

from cruise_control_tpu.common.compilation_cache import DEFAULT_CACHE_DIR, enable_persistent_cache

enable_persistent_cache(os.environ.get("BENCH_COMPILE_CACHE", DEFAULT_CACHE_DIR))

import jax.numpy as jnp
import numpy as np

from cruise_control_tpu.analyzer import GoalOptimizer, OptimizerConfig
from cruise_control_tpu.analyzer.objective import DEFAULT_CHAIN
from cruise_control_tpu.testing.fixtures import RandomClusterSpec, random_cluster_fast

SCALE = os.environ.get("DIAG_SCALE", "mid")
SPECS = {
    "mid": dict(
        num_brokers=500, num_racks=20, num_topics=100, num_partitions=50_000, skew=0.5,
        broker_capacity=(100.0, 300_000.0, 300_000.0, 3_000_000.0),
        mean_cpu=0.2, mean_nw_in=500.0, mean_nw_out=600.0, mean_disk=5000.0,
    ),
    "north_star": dict(
        num_brokers=2600, num_racks=52, num_topics=200, num_partitions=200_000,
        min_replication=2, max_replication=3, skew=0.5,
        broker_capacity=(100.0, 500_000.0, 500_000.0, 5_000_000.0),
        mean_cpu=0.15, mean_nw_in=400.0, mean_nw_out=500.0, mean_disk=4000.0,
    ),
}
SEARCH = dict(num_candidates=16384, leadership_candidates=4096,
              steps_per_round=64, num_rounds=8, seed=0)

state = random_cluster_fast(RandomClusterSpec(**SPECS[SCALE]), seed=42)
B = state.shape.B
n_dead = max(2, B // 100)
alive = np.asarray(state.broker_alive).copy()
alive[np.arange(B - n_dead, B)] = False
offline = np.asarray(state.replica_offline) | ~alive[np.asarray(state.replica_broker)]
state = dc.replace(
    state,
    broker_alive=jnp.asarray(alive),
    disk_alive=jnp.asarray(alive[:, None] & np.asarray(state.disk_alive)),
    replica_offline=jnp.asarray(offline),
)
opt = GoalOptimizer(config=OptimizerConfig(**SEARCH))
t0 = time.time()
res = opt.optimize(state, verbose=True)
print(f"wall={time.time()-t0:.1f}s scale={SCALE} dead={n_dead}", flush=True)
print("balancedness", round(res.balancedness_before, 2), "->", round(res.balancedness_after, 2))
print("objective", res.objective_before, "->", res.objective_after)
print("moves: replica", res.num_inter_broker_moves, "leader", res.num_leadership_moves)
print("history:", res.history)
for n, vb, va in zip(res.goal_names, res.violations_before, res.violations_after):
    if va > 1e-12 or vb > 1e-9:
        print(f"  {n:45s} {vb:.3e} -> {va:.3e}")
