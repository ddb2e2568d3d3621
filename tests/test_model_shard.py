"""Sharded-MODEL mesh mode (parallel/model_shard.py): byte parity with the
replicated mesh and the plain engine, psum'd broker-aggregate exactness,
collective hygiene of the sub-threshold path, and the pinned workaround
for the variadic-sort miscompile the mode has to dodge.

All tests run on the conftest-provisioned 8-device virtual CPU mesh.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from cruise_control_tpu.analyzer.engine import Engine, OptimizerConfig
from cruise_control_tpu.analyzer.objective import DEFAULT_CHAIN
from cruise_control_tpu.models.builder import pad_state
from cruise_control_tpu.models.sharding import shard_multiple_shape
from cruise_control_tpu.parallel.mesh import MeshEngine, grid_mesh, shard_map_unchecked
from cruise_control_tpu.parallel.model_shard import stable_grouped_order
from cruise_control_tpu.testing.fixtures import RandomClusterSpec, random_cluster_fast

N = 8

CFG = OptimizerConfig(
    num_candidates=48, leadership_candidates=16, swap_candidates=8,
    steps_per_round=4, num_rounds=2, seed=3,
)


def _small_state():
    """Seeded small cluster, prepared for exact cross-mode comparison:
    integer-quantized loads (psum partial sums add exactly in f32) and
    pre-padded to the shard multiple (goals normalize by the PADDED
    partition count, so all three modes must see the same padded shape)."""
    state = random_cluster_fast(
        RandomClusterSpec(num_brokers=12, num_partitions=160, skew=1.5), seed=21
    )
    state = dataclasses.replace(
        state,
        replica_load_leader=jnp.round(state.replica_load_leader * 8),
        replica_load_follower=jnp.round(state.replica_load_follower * 8),
    )
    return pad_state(state, shard_multiple_shape(state.shape, N))


def test_three_way_byte_parity():
    """One seeded anneal, three execution modes, identical bytes.

    The sharded-model mode's whole contract: partitioning the model over
    MODEL_AXIS is an execution-layout change, never a numerics change —
    placements, objective and per-goal violations match the plain engine
    and the replicated mesh bit-for-bit.  The same runs also pin the
    timing-record contract: sharded history reports its analytic psum
    payload (`model_psum_bytes`, the analyzer.mesh-model-psum-bytes
    sensor source) while replicated records must NOT grow the new keys
    (downstream hashes of replicated history stay stable)."""
    state = _small_state()
    mesh = grid_mesh(1, N)
    runs = {}
    for name, eng in (
        ("plain", Engine(state, DEFAULT_CHAIN, config=CFG)),
        ("replicated", MeshEngine(state, DEFAULT_CHAIN, mesh=mesh, config=CFG)),
        ("sharded", MeshEngine(
            state, DEFAULT_CHAIN, mesh=mesh, config=CFG,
            model_shard_min_partitions=1,
        )),
    ):
        final, hist = eng.run()
        obj, viol, _ = DEFAULT_CHAIN.evaluate(final)
        runs[name] = (final, float(obj), np.asarray(viol), hist)
    assert runs["sharded"][0] is not None
    for f in ("replica_broker", "replica_is_leader", "replica_disk"):
        a, b, c = (np.asarray(getattr(runs[n][0], f))
                   for n in ("plain", "replicated", "sharded"))
        np.testing.assert_array_equal(a, b, err_msg=f"plain vs replicated: {f}")
        np.testing.assert_array_equal(b, c, err_msg=f"replicated vs sharded: {f}")
    assert runs["plain"][1] == runs["replicated"][1] == runs["sharded"][1]
    np.testing.assert_array_equal(runs["plain"][2], runs["sharded"][2])

    timing = next(h for h in runs["sharded"][3] if h.get("timing"))
    assert timing.get("model_sharded") is True
    assert timing.get("model_psum_bytes", 0) > 0
    timing = next(h for h in runs["replicated"][3] if h.get("timing"))
    assert "model_sharded" not in timing
    assert "model_psum_bytes" not in timing


def test_sharded_mode_gate():
    """tpu.mesh.model.shard.min.partitions semantics: 0 disables, a
    threshold above the REAL partition count keeps the replicated model,
    at-or-below engages sharding (and requires a >1 model axis)."""
    state = _small_state()
    mesh = grid_mesh(1, N)
    assert not MeshEngine(state, DEFAULT_CHAIN, mesh=mesh, config=CFG).model_sharded
    assert not MeshEngine(
        state, DEFAULT_CHAIN, mesh=mesh, config=CFG,
        model_shard_min_partitions=10**9,
    ).model_sharded
    assert MeshEngine(
        state, DEFAULT_CHAIN, mesh=mesh, config=CFG,
        model_shard_min_partitions=1,
    ).model_sharded
    assert not MeshEngine(
        state, DEFAULT_CHAIN, mesh=grid_mesh(1, 1), config=CFG,
        model_shard_min_partitions=1,
    ).model_sharded


def test_sharded_model_leaves_no_whole_copy_on_one_device():
    """The engine builds its statics whole on the default device; once
    they are placed in slices, no device may keep that whole copy beside
    its slice (the 1.09 GB vs 83 MB per-device asymmetry of the CPU
    mesh bench at 25k brokers / 2M partitions)."""
    import gc

    from cruise_control_tpu.common.profiling import per_device_live_bytes

    state = jax.device_get(_small_state())  # host-resident input
    model_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(state))
    gc.collect()
    before = per_device_live_bytes()
    me = MeshEngine(
        state, DEFAULT_CHAIN, mesh=grid_mesh(1, N), config=CFG,
        model_shard_min_partitions=1,
    )
    gc.collect()
    after = per_device_live_bytes()
    held = [after.get(d.id, 0.0) - before.get(d.id, 0.0) for d in jax.devices()[:N]]
    assert max(held) - min(held) < model_bytes / 2, held
    assert me.engine.statics is me.statics


def test_psum_segment_sum_exactness():
    """Shard-local segment_sum + psum == single-device segment_sum, bit
    for bit, on integer-quantized f32 loads — the identity every broker
    aggregate in the sharded goal chain rests on."""
    from jax.sharding import Mesh, PartitionSpec as P

    rng = np.random.default_rng(5)
    R, B = 2048, 24
    vals = jnp.asarray(rng.integers(0, 512, size=R).astype(np.float32))
    seg = jnp.asarray(rng.integers(0, B, size=R).astype(np.int32))
    reference = jax.ops.segment_sum(vals, seg, num_segments=B)

    mesh = Mesh(np.asarray(jax.devices()[:N]), ("model",))

    def f(v, s):
        part = jax.ops.segment_sum(v, s, num_segments=B)
        return jax.lax.psum(part, "model")[None]

    out = jax.jit(
        shard_map_unchecked(
            f, mesh, in_specs=(P("model"), P("model")), out_specs=P("model")
        )
    )(vals, seg)
    got = np.asarray(out)  # [N, B]: one psum'd (identical) row per shard
    for i in range(N):
        np.testing.assert_array_equal(got[i], np.asarray(reference))


def test_stable_grouped_order_matches_argsort():
    """stable_grouped_order is a drop-in stable argsort for bucketed int
    keys — single-chunk and (via a shrunken packing span) multi-chunk."""
    import cruise_control_tpu.parallel.model_shard as ms

    rng = np.random.default_rng(0)
    for n, nk in [(51, 14), (408, 14), (1000, 7), (1, 3), (37, 1)]:
        seg = rng.integers(0, nk, size=n).astype(np.int32)
        got = np.asarray(stable_grouped_order(jnp.asarray(seg), nk))
        np.testing.assert_array_equal(got, np.argsort(seg, kind="stable"))
    assert stable_grouped_order(jnp.zeros(0, jnp.int32), 4).shape == (0,)
    span = ms._INT32_SPAN
    try:
        ms._INT32_SPAN = 1 << 8  # forces the chunked counting-sort path
        for n, nk in [(1000, 7), (513, 13), (999, 50)]:
            seg = rng.integers(0, nk, size=n).astype(np.int32)
            got = np.asarray(stable_grouped_order(jnp.asarray(seg), nk))
            np.testing.assert_array_equal(got, np.argsort(seg, kind="stable"))
    finally:
        ms._INT32_SPAN = span


def test_variadic_sort_miscompile_guard():
    """Pinned repro of the bug stable_grouped_order exists to dodge.

    On the pinned jax/XLA build, a VARIADIC (two-operand) lax.sort of
    shard-varying data — jnp.argsort lowers to one — inside a
    shard_map(check_vma=False) program whose result rides a lax.scan ys
    export silently hands every device device 0's sort output, corrupting
    even the scan carry.  The packed SINGLE-operand sort must stay
    correct under the exact graph shape that triggers the miscompile; if
    this test ever fails, the sharded-model mode's sampling order (and
    with it byte parity) is broken on this backend."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:N]).reshape(1, N), ("r", "m"))
    per = 6
    x = jax.device_put(
        jnp.arange(N * per, dtype=jnp.int32) % 7,
        NamedSharding(mesh, P("m")),
    )

    def fn(xb):
        o = stable_grouped_order(xb, 7)
        def body(c, t):
            v = (o * jnp.arange(per, dtype=jnp.int32)).sum()
            return c + v, (o, xb)
        acc, (o_ys, x_ys) = jax.lax.scan(body, jnp.int32(0), jnp.zeros(2))
        return (
            jax.lax.all_gather(acc, "m")[None],
            jax.lax.all_gather(o_ys[0], "m")[None],
            jax.lax.all_gather(x_ys[0], "m")[None],
        )

    acc, o, xs = jax.jit(
        shard_map_unchecked(
            fn, mesh, in_specs=(P("m"),), out_specs=(P("r"), P("r"), P("r"))
        )
    )(x)
    acc, o, xs = np.asarray(acc)[0], np.asarray(o)[0], np.asarray(xs)[0]
    truth = np.asarray(jax.device_get(x)).reshape(N, per)
    for i in range(N):
        expect = np.argsort(truth[i], kind="stable")
        np.testing.assert_array_equal(o[i], expect, err_msg=f"shard {i} order")
        np.testing.assert_array_equal(xs[i], truth[i], err_msg=f"shard {i} ys x")
        assert acc[i] == 2 * (expect * np.arange(per)).sum(), f"shard {i} carry"


def test_subthreshold_path_emits_no_model_axis_allreduce():
    """HLO hygiene: below the sharding threshold the mesh program's only
    model-axis collective is the candidate-column gather — no psum
    (all-reduce) may appear.  The sharded program, by contrast, carries
    its ownership/aggregate psums as all-reduces."""
    state = _small_state()
    mesh = grid_mesh(1, N)

    def lowered_text(me):
        keys = jax.random.PRNGKey(CFG.seed)[None]
        carry = me._jit_init(me.statics, keys)
        return me._jit_run.lower(me.statics, carry).as_text()

    replicated = MeshEngine(state, DEFAULT_CHAIN, mesh=mesh, config=CFG)
    text = lowered_text(replicated)
    assert "all_reduce" not in text, "replicated mesh program grew an all-reduce"
    assert "all_gather" in text  # the candidate gather is still there

    sharded = MeshEngine(
        state, DEFAULT_CHAIN, mesh=mesh, config=CFG, model_shard_min_partitions=1
    )
    assert "all_reduce" in lowered_text(sharded)
