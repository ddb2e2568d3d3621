"""The anneal's phase scopes and round counter.

Every engine program carries the five `jax.named_scope`s of
`engine.ANNEAL_PHASES` in its ops' name stacks (what a profiler trace's
`tf_op` shows), whichever way the anneal is dispatched: the whole-anneal
program, a segmented slice, the four-device mesh step.  The AOT artifact
keeps them through export (tests/test_prewarm.py).  The timing record's
`rounds` is the count of rounds the run executed.
"""

import dataclasses as dc
import re

import jax
import jax.numpy as jnp
import pytest

from cruise_control_tpu.analyzer.engine import (
    ANNEAL_PHASES,
    Engine,
    OptimizerConfig,
    SegmentContext,
    anneal_phase,
    segmented_execution,
)
from cruise_control_tpu.analyzer.objective import DEFAULT_CHAIN
from cruise_control_tpu.testing.fixtures import RandomClusterSpec, random_cluster_fast

SMALL = RandomClusterSpec(
    num_brokers=12, num_partitions=200, num_racks=4, num_topics=6, skew=1.0
)
CFG = OptimizerConfig(
    num_candidates=128, leadership_candidates=32, swap_candidates=16,
    steps_per_round=8, num_rounds=3, seed=0,
)


@pytest.fixture(scope="module")
def engine():
    return Engine(random_cluster_fast(SMALL, seed=3), DEFAULT_CHAIN, config=CFG)


def _avals(eng):
    sx_av = eng.statics_avals()
    key_av = jax.ShapeDtypeStruct((2,), jnp.uint32)
    return sx_av, jax.eval_shape(eng._init_impl, sx_av, key_av)


def _in_scope(phase: str, text: str) -> bool:
    """Whether an op location of a lowered program's text names `phase`
    (locations inside a loop body are relative to it: `"anneal_score/..`)."""
    return re.search(rf'["/]{phase}/', text) is not None


def _scopes(lowered) -> set:
    """Phase scopes named in a lowered program's op locations."""
    text = lowered.as_text(debug_info=True)
    return {p for p in ANNEAL_PHASES if _in_scope(p, text)}


def test_fused_program_carries_every_phase(engine):
    sx_av, carry_av = _avals(engine)
    lowered = engine._jit_run_fused.trace(sx_av, carry_av).lower()
    assert _scopes(lowered) == set(ANNEAL_PHASES)
    assert lowered.as_text().startswith("module @jit__run_fused_impl")


def test_segmented_slice_program_carries_every_phase(engine):
    sx_av, carry_av = _avals(engine)
    seg_av = jax.eval_shape(engine._seg_init_impl, sx_av, carry_av)
    base_av = jax.ShapeDtypeStruct((), jnp.int32)
    lowered = engine._seg_fn(2).trace(sx_av, carry_av, seg_av, base_av).lower()
    # the round body holds every phase; the schedule's init (eval) runs
    # as the segmented runner's own init program
    assert _scopes(lowered) == set(ANNEAL_PHASES)
    init = jax.jit(engine._seg_init_impl).trace(sx_av, carry_av).lower()
    assert "anneal_eval" in _scopes(init)


def test_refresh_and_eval_own_their_subtree(engine):
    """Refresh and eval reuse the step's helpers (globals, the plan's
    gathers); their ops stay under the one owning phase."""
    sx_av, carry_av = _avals(engine)
    text = jax.jit(engine._round_prep_impl).trace(sx_av, carry_av).lower().as_text(
        debug_info=True
    )
    assert _in_scope("anneal_refresh", text)
    for other in set(ANNEAL_PHASES) - {"anneal_refresh"}:
        assert not _in_scope(other, text)


def test_phase_scopes_nest_and_restore():
    def f(x):
        with anneal_phase("anneal_score"):
            y = x * 2.0
            with anneal_phase("anneal_sample"):
                y = y + 1.0
        with anneal_phase("anneal_eval"):
            with anneal_phase("anneal_sample"):
                z = y - 3.0
        return z

    text = jax.jit(f).trace(jnp.ones(3)).lower().as_text(debug_info=True)
    assert "anneal_score/anneal_sample/add" in text
    # eval owns its subtree: no sample scope opens inside it
    assert "anneal_eval/sub" in text
    assert "anneal_eval/anneal_sample" not in text


def test_mesh_step_carries_every_phase():
    from cruise_control_tpu.parallel.mesh import MeshEngine, model_mesh

    me = MeshEngine(
        random_cluster_fast(SMALL, seed=3), DEFAULT_CHAIN,
        mesh=model_mesh(jax.devices()[:4]), config=CFG,
    )
    sx_av, base_av = _avals(me.engine)
    carry_av = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((1,) + a.shape, a.dtype), base_av
    )
    # the shard twin's `_step` (candidate slice + all_gather) runs the
    # same shared functions inside shard_map
    lowered = me._jit_run.trace(sx_av, carry_av).lower()
    assert _scopes(lowered) == set(ANNEAL_PHASES)


@pytest.mark.parametrize("diagnostics", [False, True])
def test_timing_rounds_count_the_rounds_run(diagnostics):
    state = random_cluster_fast(SMALL, seed=5)
    cfg = dc.replace(CFG, diagnostics=diagnostics)
    _, hist = Engine(state, DEFAULT_CHAIN, config=cfg).run()
    timing = next(h for h in hist if h.get("timing"))
    ran = [h for h in hist if not h.get("timing")]
    assert timing["rounds"] == len(ran) >= 1
    if diagnostics:
        assert timing["convergence"]["rounds"] == timing["rounds"]
    eng = Engine(state, DEFAULT_CHAIN, config=cfg)
    with segmented_execution(SegmentContext(slice_budget_s=1e-4)):
        _, seg_hist = eng.run()
    seg_timing = next(h for h in seg_hist if h.get("timing"))
    assert seg_timing["segmented"] and seg_timing["segments"] >= 2
    assert seg_timing["rounds"] == timing["rounds"]
