"""Where does the north-star warmup go?  trace vs lower vs compile."""

import os
import sys
import time

sys.path.insert(0, "/root/repo")

from cruise_control_tpu.common.compilation_cache import DEFAULT_CACHE_DIR, enable_persistent_cache

enable_persistent_cache(os.environ.get("BENCH_COMPILE_CACHE", DEFAULT_CACHE_DIR))

import jax
import jax.numpy as jnp

from cruise_control_tpu.analyzer import DEFAULT_CHAIN, Engine, OptimizerConfig
from cruise_control_tpu.testing.fixtures import RandomClusterSpec, random_cluster_fast

NORTH = RandomClusterSpec(
    num_brokers=2600, num_racks=52, num_topics=200, num_partitions=200_000,
    min_replication=2, max_replication=3, skew=0.5,
    broker_capacity=(100.0, 500_000.0, 500_000.0, 5_000_000.0),
    mean_cpu=0.15, mean_nw_in=400.0, mean_nw_out=500.0, mean_disk=4000.0,
)

t0 = time.monotonic()
state = random_cluster_fast(NORTH, seed=42)
print(f"fixture {time.monotonic()-t0:.1f}s", flush=True)

t0 = time.monotonic()
cfg = OptimizerConfig(num_candidates=16384, leadership_candidates=4096,
                     steps_per_round=64, num_rounds=8, seed=0)
eng = Engine(state, DEFAULT_CHAIN, config=cfg)
print(f"engine build (statics) {time.monotonic()-t0:.1f}s", flush=True)

t0 = time.monotonic()
carry = eng.init_carry(jax.random.PRNGKey(0))
jax.block_until_ready(carry.broker_load)
print(f"init_carry (jit refresh compile+run) {time.monotonic()-t0:.1f}s", flush=True)

sx = eng.statics
plan = eng._jit_plan(sx, carry)
jax.block_until_ready(plan.broker_cdf)
temps = jnp.zeros((cfg.steps_per_round,), jnp.float32)

t0 = time.monotonic()
traced = eng._scan.trace(sx, carry, temps, plan)
t_trace = time.monotonic() - t0
t0 = time.monotonic()
lowered = traced.lower()
t_lower = time.monotonic() - t0
t0 = time.monotonic()
compiled = lowered.compile()
t_compile = time.monotonic() - t0
print(f"scan: trace={t_trace:.1f}s lower={t_lower:.1f}s compile={t_compile:.1f}s",
      flush=True)

t0 = time.monotonic()
out = compiled(sx, carry, temps, plan)
jax.block_until_ready(out[0].broker_load)
print(f"scan run {time.monotonic()-t0:.2f}s", flush=True)

for name, fn, args in (
    ("round_prep", eng._jit_round_prep, (sx, carry)),
    ("violations", eng._jit_violations, (sx, carry)),
    ("objective", eng._jit_objective, (sx, carry)),
):
    t0 = time.monotonic()
    tr = fn.trace(*args)
    lo = tr.lower()
    t_l = time.monotonic() - t0
    t0 = time.monotonic()
    co = lo.compile()
    print(f"{name}: trace+lower={t_l:.1f}s compile={time.monotonic()-t0:.1f}s", flush=True)
