"""Survivor compaction before the apply scatters.

`Engine._apply_survivors` packs each step's surviving rows, in row order,
into `Engine.apply_rows`-sized buffers before `_apply` scatters them.
The carry it yields equals the full-width `_apply`'s byte for byte, with
survivors at the cap of each lane (at most one survivor per broker), in
every apply mode: relocations, swaps, leadership, a mix, intra-broker
(JBOD) moves, and the model-sharded offsets.  A fused run's per-round
survivor peak stays within the cap and is reported beside the caps.
"""

import dataclasses as dc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cruise_control_tpu.analyzer.engine import Engine, OptimizerConfig
from cruise_control_tpu.analyzer.objective import DEFAULT_CHAIN
from cruise_control_tpu.testing.fixtures import RandomClusterSpec, random_cluster_fast

SPEC = RandomClusterSpec(
    num_brokers=12, num_partitions=200, num_racks=4, num_topics=6, skew=1.0,
    disks_per_broker=2,
)
CFG = OptimizerConfig(
    num_candidates=128, leadership_candidates=32, swap_candidates=16,
    steps_per_round=8, num_rounds=3, seed=0,
)


def _payloads(eng, rng):
    """Random in-range replica-ext and leadership payloads of full width."""
    sh = eng.shape
    B, R, P, D = sh.B, sh.R, sh.P, sh.max_disks_per_broker
    n_r, n_l = eng.K_r + 2 * eng.K_s, eng.K_l

    def ints(hi, n):
        return rng.integers(0, hi, n).astype(np.int32)

    def floats(*shape):
        return rng.random(shape).astype(np.float32) * 10

    payr = dict(
        r=ints(R, n_r), src=ints(B, n_r), dst=ints(B, n_r), d_dst=ints(D, n_r),
        d_src=ints(D, n_r), load=floats(n_r, 4), is_lead=rng.random(n_r) < 0.3,
        pot=floats(n_r), lbin=floats(n_r), topic=ints(sh.num_topics, n_r),
        part=ints(P, n_r),
    )
    payl = dict(
        rf=ints(R, n_l), rt=ints(R, n_l), src_b=ints(B, n_l), dst_b=ints(B, n_l),
        dl_f=floats(n_l, 4), dl_t=floats(n_l, 4), dlbin_src=floats(n_l),
        dlbin_dst=floats(n_l), d_f=ints(D, n_l), d_t=ints(D, n_l),
    )
    return payr, payl


def _survivors(eng, case, payr, payl, rng):
    """Masks of survivors at the cap: distinct brokers, replicas and
    partitions across survivors, as `_select` guarantees."""
    B = eng.shape.B
    nr, ns = eng.K_r, eng.K_s
    sv_r = np.zeros(nr + 2 * ns, bool)
    sv_l = np.zeros(eng.K_l, bool)
    brokers = iter(rng.permutation(B).tolist())
    replicas = iter(rng.permutation(eng.shape.R).tolist())
    parts = iter(rng.permutation(eng.shape.P).tolist())

    def relocation(i, src, dst):
        sv_r[i] = True
        payr["src"][i], payr["dst"][i] = src, dst
        payr["r"][i], payr["part"][i] = next(replicas), next(parts)

    n_move = {"replica": B // 2, "mix": B // 6, "intra": B}.get(case, 0)
    n_swap = {"swap": B // 2, "mix": B // 6}.get(case, 0)
    n_lead = {"leadership": B // 2, "mix": B // 6, "sharded": B // 4}.get(case, 0)
    if case == "sharded":
        n_move = B // 4
    for i in rng.choice(nr, n_move, replace=False):
        a = next(brokers)
        relocation(i, a, a if case == "intra" else next(brokers))
    for j in rng.choice(ns, n_swap, replace=False):
        a, b = next(brokers), next(brokers)
        relocation(nr + j, a, b)
        relocation(nr + ns + j, b, a)
    for i in rng.choice(eng.K_l, n_lead, replace=False):
        sv_l[i] = True
        payl["src_b"][i], payl["dst_b"][i] = next(brokers), next(brokers)
        payl["rf"][i], payl["rt"][i] = next(replicas), next(replicas)
    return sv_r, sv_l


class _ShardApply(Engine):
    """The engine with one model shard's `_apply`, offsets and local
    extents bound, as the model-sharded twin binds them
    (parallel/model_shard.py)."""

    def __init__(self, engine, **offsets):  # noqa: D107 — shares the engine
        self.__dict__.update(engine.__dict__)
        self._offsets = offsets

    def _apply(self, sx, carry, sv_r, payr, sv_l, payl, **_):
        return Engine._apply(self, sx, carry, sv_r, payr, sv_l, payl, **self._offsets)


def _local_slice(carry, r_lo, r_size, p_lo, p_size):
    """One model shard's carry: local placement rows and partition cells,
    replicated broker/host/topic aggregates."""
    return dc.replace(
        carry,
        replica_broker=carry.replica_broker[r_lo: r_lo + r_size],
        replica_is_leader=carry.replica_is_leader[r_lo: r_lo + r_size],
        replica_disk=carry.replica_disk[r_lo: r_lo + r_size],
        part_rack_count=carry.part_rack_count[p_lo: p_lo + p_size],
    )


@pytest.mark.parametrize(
    "case", ["replica", "swap", "leadership", "mix", "intra", "sharded"]
)
def test_compacted_apply_equals_full_apply(case):
    state = random_cluster_fast(SPEC, seed=3)
    cfg = dc.replace(CFG, intra_broker=(case == "intra"))
    eng = Engine(state, DEFAULT_CHAIN, config=cfg)
    cap_r, cap_l = eng.apply_rows
    B = eng.shape.B
    assert cap_r == B < eng.K_r + 2 * eng.K_s
    assert cap_l == min(eng.K_l, B // 2)
    rng = np.random.default_rng(sum(map(ord, case)))
    payr, payl = _payloads(eng, rng)
    sv_r, sv_l = _survivors(eng, case, payr, payl, rng)
    # at the cap of the lanes the case exercises
    rows = {"swap": (B, 0), "leadership": (0, B // 2), "intra": (B, 0)}
    if case in rows:
        assert (int(sv_r.sum()), int(sv_l.sum())) == rows[case]
    assert sv_r.sum() <= cap_r and sv_l.sum() <= cap_l and sv_r.any() | sv_l.any()

    carry = eng.init_carry(jax.random.PRNGKey(0))
    if case == "sharded":
        # the second of two shards: rows owned by the first drop out
        r_size, p_size = eng.shape.R // 2, eng.shape.P // 2
        carry = _local_slice(carry, r_size, r_size, p_size, p_size)
        eng = _ShardApply(
            eng, r_offset=r_size, p_offset=p_size, r_size=r_size, p_size=p_size
        )
    args = (
        eng.statics, carry,
        jnp.asarray(sv_r), jax.tree.map(jnp.asarray, payr),
        jnp.asarray(sv_l), jax.tree.map(jnp.asarray, payl),
    )
    full = jax.jit(eng._apply)(*args)
    compact = jax.jit(eng._apply_survivors)(*args)
    for f in dc.fields(full):
        want, got = np.asarray(getattr(full, f.name)), np.asarray(getattr(compact, f.name))
        assert want.dtype == got.dtype and want.tobytes() == got.tobytes(), f.name
    # the apply did move something, so the comparison is not vacuous
    changed = [
        f.name for f in dc.fields(full)
        if np.asarray(getattr(full, f.name)).tobytes()
        != np.asarray(getattr(carry, f.name)).tobytes()
    ]
    assert changed


def test_survivor_peak_stays_within_its_cap():
    from cruise_control_tpu.analyzer.optimizer import GoalOptimizer
    from cruise_control_tpu.common.trace import Tracer

    state = random_cluster_fast(SPEC, seed=5)
    cfg = dc.replace(CFG, diagnostics=True)
    eng = Engine(state, DEFAULT_CHAIN, config=cfg)
    _, hist = eng.run()
    timing = next(h for h in hist if h.get("timing"))
    cap_r, cap_l = eng.apply_rows
    assert timing["apply_rows"] == [cap_r, cap_l]
    peak = timing["convergence"]["survivor_peak"]
    rounds = [h for h in hist if not h.get("timing")]
    assert len(peak) == timing["rounds"] == len(rounds)
    assert all(0 <= p <= cap_r for p in peak)
    # a step's survivors are part of its round's accepted count
    assert all(p <= r["accepted"] for p, r in zip(peak, rounds))
    assert max(peak) > 0

    tracer = Tracer()
    res = GoalOptimizer(config=cfg, tracer=tracer).optimize(state)
    timing = next(h for h in res.history if h.get("timing"))
    (root,) = tracer.recent_traces()
    span = next(
        s for s in tracer.trace(root["traceId"]) if s.name == "analyzer.optimize"
    )
    attrs = span.to_json()["attributes"]
    assert attrs["apply_rows"] == timing["apply_rows"] == [cap_r, cap_l]
    assert attrs["convergence"]["survivor_peak"] == timing["convergence"]["survivor_peak"]
