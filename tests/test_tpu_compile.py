"""The main path's device programs compile for a TPU v5e.

No chip is attached here: the TPU compiler compiles for a described
v5e:2x2 topology from shapes alone, and refuses what the chip's compiler
would refuse (an unsupported op, a program over the device's memory).  A
compile that passes is not a chip run; `chip_smoke.py` is that.

Sizes are bench.py's SMALL_SPEC / SEARCH_SMALL, which compile in seconds.
The topology is described only inside the fixture, never at import: only
one process may load the TPU library, and under xdist every worker
imports this file.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from bench import SEARCH_SMALL, SMALL_SPEC
from cruise_control_tpu.analyzer import DEFAULT_CHAIN, Engine, OptimizerConfig
from cruise_control_tpu.analyzer.scenario_eval import ScenarioEvaluator
from cruise_control_tpu.models.state import ClusterState
from cruise_control_tpu.parallel.mesh import MODEL_AXIS, RESTART_AXIS, MeshEngine, model_mesh
from cruise_control_tpu.testing.fixtures import RandomClusterSpec, random_cluster_fast


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler to describe one
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # the persistent cache (conftest) would store TPU executables it can
    # never read back here, and warn on the next compile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def state():
    return random_cluster_fast(RandomClusterSpec(**SMALL_SPEC), seed=0)


def _avals(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a), sharding=sharding),
        tree,
    )


def _fits(compiled) -> None:
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < used < 16 * 2**30  # one v5e chip's HBM


def test_fused_anneal_compiles_for_v5e(topo, state):
    one = SingleDeviceSharding(topo.devices[0])
    engine = Engine(state, DEFAULT_CHAIN, config=OptimizerConfig(**SEARCH_SMALL))
    sx = engine.statics_avals()
    carry = jax.eval_shape(engine._init_impl, sx, jax.ShapeDtypeStruct((2,), jnp.uint32))
    compiled = (
        jax.jit(engine._run_fused_impl, donate_argnums=(1,))
        .trace(_avals(sx, one), _avals(carry, one))
        .lower()
        .compile()
    )
    _fits(compiled)


def test_scenario_batch_compiles_for_v5e(topo, state):
    one = SingleDeviceSharding(topo.devices[0])
    n = 4
    varying = {"broker_alive", "replica_load_leader", "replica_load_follower"}
    shared, batched = {}, {}
    for f in dataclasses.fields(ClusterState):
        if f.name == "shape":
            continue
        name, leaf = f.name, getattr(state, f.name)
        av = jax.ShapeDtypeStruct(jnp.shape(leaf), jnp.result_type(leaf), sharding=one)
        if name in varying:
            av = jax.ShapeDtypeStruct((n,) + av.shape, av.dtype, sharding=one)
            batched[name] = av
        else:
            shared[name] = av
    assert set(batched) == varying
    program = ScenarioEvaluator().batch_program(state.shape)
    _fits(program.trace(shared, batched).lower().compile())


def test_candidate_sharded_mesh_compiles_for_four_v5e_chips(topo, state):
    cfg = OptimizerConfig(**SEARCH_SMALL)
    # built on four virtual CPU devices (placement needs real devices),
    # then re-meshed onto the four described chips for the compile
    me = MeshEngine(state, DEFAULT_CHAIN, mesh=model_mesh(jax.devices()[:4]), config=cfg)
    me.mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), (RESTART_AXIS, MODEL_AXIS))
    me._build_jits()
    replicated = NamedSharding(me.mesh, P())
    sx = me.engine.statics_avals()
    carry = jax.eval_shape(me.engine._init_impl, sx, jax.ShapeDtypeStruct((2,), jnp.uint32))
    carry_blk = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            (1,) + a.shape, a.dtype, sharding=NamedSharding(me.mesh, P(RESTART_AXIS))
        ),
        carry,
    )
    compiled = me._jit_run.trace(_avals(sx, replicated), carry_blk).lower().compile()
    _fits(compiled)
    # the per-step candidate exchange is the program's collective
    assert "all-gather" in compiled.as_text()
