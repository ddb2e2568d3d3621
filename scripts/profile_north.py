"""Phase-level breakdown of GoalOptimizer.optimize at north-star scale.

Times every component of the measured (second) optimize() call: validate,
report, per-round plan/scan/refresh/early-stop checks, proposal
extraction.  Run on the real TPU to see where the 11.3s goes.
"""

import os
import sys
import time

sys.path.insert(0, "/root/repo")

from cruise_control_tpu.common.compilation_cache import DEFAULT_CACHE_DIR, enable_persistent_cache

enable_persistent_cache(os.environ.get("BENCH_COMPILE_CACHE", DEFAULT_CACHE_DIR))

import jax
import jax.numpy as jnp
import numpy as np

from cruise_control_tpu.analyzer import GoalOptimizer, OptimizerConfig
from cruise_control_tpu.analyzer.objective import DEFAULT_CHAIN, balancedness_score
from cruise_control_tpu.analyzer.proposals import extract_proposals
from cruise_control_tpu.models.state import validate
from cruise_control_tpu.testing.fixtures import RandomClusterSpec, random_cluster_fast

NORTH = RandomClusterSpec(
    num_brokers=2600, num_racks=52, num_topics=200, num_partitions=200_000,
    min_replication=2, max_replication=3, skew=0.5,
    broker_capacity=(100.0, 500_000.0, 500_000.0, 5_000_000.0),
    mean_cpu=0.15, mean_nw_in=400.0, mean_nw_out=500.0, mean_disk=4000.0,
)
SEARCH = dict(
    num_candidates=16384, leadership_candidates=4096,
    steps_per_round=64, num_rounds=8, seed=0,
)


def t(label, fn, *a, **k):
    t0 = time.monotonic()
    out = fn(*a, **k)
    out = jax.block_until_ready(out) if hasattr(out, "block_until_ready") or isinstance(out, (jax.Array,)) else out
    dt = time.monotonic() - t0
    print(f"  {label:38s} {dt*1000:9.1f} ms", flush=True)
    return out, dt


def main():
    print("device:", jax.devices()[0], flush=True)
    t0 = time.monotonic()
    state = random_cluster_fast(NORTH, seed=42)
    print(f"fixture: {time.monotonic()-t0:.1f}s", flush=True)

    opt = GoalOptimizer(config=OptimizerConfig(**SEARCH))
    t0 = time.monotonic()
    warm = opt.optimize(state)
    print(f"warmup optimize: {time.monotonic()-t0:.1f}s (wall_seconds={warm.wall_seconds:.1f})", flush=True)

    # ---- instrumented second run ----
    total0 = time.monotonic()
    _, d_val = t("validate(state)", validate, state)
    (out, d_rep) = t("report(state)", lambda: jax.block_until_ready(opt._report(state)))

    engine, _ = opt._engine_for(state, __import__("cruise_control_tpu.analyzer.options", fromlist=["DEFAULT_OPTIONS"]).DEFAULT_OPTIONS, opt.config)
    cfg = engine.config
    sx = engine.statics
    t0 = time.monotonic()
    carry = engine.init_carry(jax.random.PRNGKey(cfg.seed))
    jax.block_until_ready(carry.broker_load)
    print(f"  {'init_carry':38s} {(time.monotonic()-t0)*1000:9.1f} ms", flush=True)
    t0 = time.monotonic()
    t0_obj = float(engine._jit_objective(sx, carry)) * cfg.init_temperature_scale
    print(f"  {'initial objective':38s} {(time.monotonic()-t0)*1000:9.1f} ms", flush=True)

    full_checks_left = 2
    for rnd in range(cfg.num_rounds):
        t_round = 0.0 if rnd == cfg.num_rounds - 1 else t0_obj * (cfg.temperature_decay ** rnd)
        temps = jnp.full((cfg.steps_per_round,), t_round, jnp.float32)
        r0 = time.monotonic()
        plan = engine._jit_plan(sx, carry)
        jax.block_until_ready(plan.broker_cdf)
        d_plan = time.monotonic() - r0
        r0 = time.monotonic()
        carry, stats = engine._scan(sx, carry, temps, plan)
        jax.block_until_ready(carry.broker_load)
        d_scan = time.monotonic() - r0
        r0 = time.monotonic()
        carry = engine._jit_refresh(sx, carry)
        jax.block_until_ready(carry.broker_load)
        d_refresh = time.monotonic() - r0
        r0 = time.monotonic()
        cheap = float(engine._jit_cheap_violations(sx, carry))
        d_cheap = time.monotonic() - r0
        d_full = 0.0
        stopped = False
        if cfg.early_stop_violations >= 0 and rnd < cfg.num_rounds - 1 and full_checks_left > 0 and cheap <= cfg.early_stop_violations:
            r0 = time.monotonic()
            fullv = float(engine._jit_violations(sx, carry))
            d_full = time.monotonic() - r0
            if fullv <= cfg.early_stop_violations:
                stopped = True
            else:
                full_checks_left -= 1
        acc = int(jax.device_get(stats["accepted"]).sum())
        print(f"  round {rnd}: plan={d_plan*1000:7.1f} scan={d_scan*1000:8.1f} refresh={d_refresh*1000:7.1f} cheap={d_cheap*1000:6.1f} full={d_full*1000:6.1f} ms acc={acc} cheapv={cheap:.2e}{' STOP' if stopped else ''}", flush=True)
        if stopped:
            break
    final = engine.carry_to_state(carry)
    (_, d_rep2) = t("report(final)", lambda: jax.block_until_ready(opt._report(final)))
    _, d_val2 = t("validate(final)", validate, final)
    t0 = time.monotonic()
    props = extract_proposals(state, final)
    print(f"  {'extract_proposals':38s} {(time.monotonic()-t0)*1000:9.1f} ms  ({len(props)} proposals)", flush=True)
    print(f"TOTAL instrumented: {time.monotonic()-total0:.3f}s", flush=True)

    (obj_a, viol_a), _ = opt._report(final)
    print("balancedness_after:", balancedness_score(np.asarray(viol_a), opt.chain), flush=True)


if __name__ == "__main__":
    main()
