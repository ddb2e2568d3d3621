"""Cold-start prewarm tests: the boot manifest, AOT-serialized engine
programs, the fallback ladder, and warm-pool priority ordering.

The contract under test (analyzer/prewarm.py + engine.precompile_async):
a restart may be FASTER because of the manifest/AOT artifacts but must
never be DIFFERENT — any version/fingerprint/aval/checksum mismatch, a
truncated artifact, or a missing manifest falls back rung by rung
(AOT -> fresh trace+compile -> plain lazy jit) to byte-identical
results.  The round-4 in-line AOT cache regressed exactly this
(engine.precompile_async docstring); these are its regression guards.
"""

import dataclasses
import json
import os
import threading
import time

import numpy as np
import pytest

from cruise_control_tpu.analyzer.engine import (
    Engine,
    OptimizerConfig,
    _WarmedFn,
    _WarmPool,
)
from cruise_control_tpu.analyzer.objective import DEFAULT_CHAIN, GoalChain
from cruise_control_tpu.analyzer.prewarm import PrewarmStore, bucket_key
from cruise_control_tpu.common import compilation_cache
from cruise_control_tpu.common.sensors import SensorRegistry
from cruise_control_tpu.config.balancing import DEFAULT_CONSTRAINT
from cruise_control_tpu.models.builder import prewarm_state
from cruise_control_tpu.models.state import ClusterShape
from cruise_control_tpu.testing.fixtures import (
    RandomClusterSpec,
    random_cluster_fast,
)

CFG = OptimizerConfig(
    num_candidates=128, leadership_candidates=32, swap_candidates=16,
    steps_per_round=8, num_rounds=2, seed=0,
)


@pytest.fixture(autouse=True)
def _aot_worthwhile_at_toy_scale(monkeypatch):
    """Production gates artifacts by engine scale (engine.AOT_MIN_*);
    these tests exercise the artifact ladder on toy engines, so lower
    the floor to zero for the duration of each test."""
    import cruise_control_tpu.analyzer.engine as engine_mod

    monkeypatch.setattr(engine_mod, "AOT_MIN_REPLICAS", 0)
    monkeypatch.setattr(engine_mod, "AOT_MIN_CANDIDATES", 0)


@pytest.fixture(scope="module")
def state():
    return random_cluster_fast(
        RandomClusterSpec(
            num_brokers=10, num_partitions=160, num_racks=4, num_topics=6,
            skew=1.0,
        ),
        seed=3,
    )


@pytest.fixture(scope="module")
def golden_artifact(state, tmp_path_factory):
    """ONE cold engine run + AOT export shared by the ladder tests (each
    corruption/drift test copies the artifact into its own directory).
    Module-scoped, so the function-scoped threshold fixture is not yet
    active — lower the floor manually around the build."""
    import cruise_control_tpu.analyzer.engine as engine_mod

    d = tmp_path_factory.mktemp("golden-aot")
    old = (engine_mod.AOT_MIN_REPLICAS, engine_mod.AOT_MIN_CANDIDATES)
    engine_mod.AOT_MIN_REPLICAS = engine_mod.AOT_MIN_CANDIDATES = 0
    try:
        store = _store(d)
        e = Engine(state, DEFAULT_CHAIN, config=CFG, prewarm_store=store)
        e.precompile_async()
        final, _ = e.run()
        assert store.drain(300)
    finally:
        engine_mod.AOT_MIN_REPLICAS, engine_mod.AOT_MIN_CANDIDATES = old
    (name,) = [f for f in os.listdir(d) if f.endswith(".aot")]
    return dict(
        name=name,
        data=open(os.path.join(d, name), "rb").read(),
        placement=_placement(final),
    )


def _install_artifact(tmp_path, golden, data=None):
    with open(os.path.join(tmp_path, golden["name"]), "wb") as f:
        f.write(golden["data"] if data is None else data)


def _store(tmp_path, **kw):
    kw.setdefault("chain", DEFAULT_CHAIN)
    kw.setdefault("constraint", DEFAULT_CONSTRAINT)
    return PrewarmStore(str(tmp_path), **kw)


def _placement(state):
    return tuple(
        np.asarray(getattr(state, f))
        for f in ("replica_broker", "replica_is_leader", "replica_disk")
    )


def _same_placement(a, b) -> bool:
    return all(bool((x == y).all()) for x, y in zip(_placement(a), _placement(b)))


# ---------------------------------------------------------------- manifest


def test_manifest_round_trip(tmp_path, state):
    store = _store(tmp_path)
    store.note(state.shape, 3, CFG, parallel_mode="single")
    # dedup: the same (bucket, config) noted again is ONE entry
    store.note(state.shape, 3, CFG, parallel_mode="single")
    doc = json.load(open(store.manifest_path))
    assert len(doc["entries"]) == 1
    # the second note is a recency touch: deduped in memory (uses=2);
    # its disk write is throttled, so the file may still say uses=1
    assert next(iter(store._entries.values()))["uses"] == 2

    fresh = _store(tmp_path)
    rows = fresh.claim_boot_entries()
    assert len(rows) == 1
    shape, max_rf, cfg, pmode = fresh.entry_engine_inputs(rows[0])
    assert shape == state.shape and max_rf == 3
    assert cfg == CFG  # exact dataclass equality: the engine-cache key
    assert pmode == "single"
    # claimed once per store: a second facade over the same store gets []
    assert fresh.claim_boot_entries() == []


def test_manifest_rejects_foreign_environment(tmp_path, state):
    store = _store(tmp_path)
    store.note(state.shape, 2, CFG)
    other_chain = GoalChain.from_names(["ReplicaCapacityGoal"])
    other = _store(tmp_path, chain=other_chain)
    assert other.claim_boot_entries() == []  # chain fingerprint mismatch
    assert _store(tmp_path).claim_boot_entries()  # same env still claims


def test_manifest_version_and_corruption_tolerance(tmp_path, state):
    store = _store(tmp_path)
    store.note(state.shape, 2, CFG)
    doc = json.load(open(store.manifest_path))
    doc["version"] = 99
    open(store.manifest_path, "w").write(json.dumps(doc))
    assert _store(tmp_path).claim_boot_entries() == []
    open(store.manifest_path, "w").write("{ not json")
    assert _store(tmp_path).claim_boot_entries() == []
    # and a corrupt file never breaks recording: the next note rebuilds it
    store2 = _store(tmp_path)
    store2.note(state.shape, 2, CFG)
    assert _store(tmp_path).claim_boot_entries()


def test_manifest_merges_concurrent_stores_not_last_writer_wins(tmp_path):
    """Two stores over ONE directory (two fleet cores, or two processes
    sharing a cache dir) must UNION their working sets."""
    a, b = _store(tmp_path), _store(tmp_path)
    s1 = ClusterShape(32, 8, 8, 2, 2, 8, 1)
    s2 = ClusterShape(64, 16, 16, 4, 2, 16, 1)
    a.note(s1, 2, CFG)
    b.note(s2, 2, CFG)  # b never saw a's entry in memory
    keys = set(_store(tmp_path).manifest_bucket_keys())
    assert keys == {bucket_key(s1), bucket_key(s2)}


def test_manifest_bounded_by_max_entries(tmp_path):
    store = _store(tmp_path, max_entries=2)
    shapes = [ClusterShape(32 * k, 8, 8, 2, 2, 8, 1) for k in (1, 2, 3)]
    for s in shapes:
        store.note(s, 2, CFG)
        time.sleep(0.002)  # distinct last_used_ms for the recency order
    rows = _store(tmp_path, max_entries=2).claim_boot_entries()
    # most recent two survive, most recent FIRST (the active bucket leads)
    got = [r["bucket"]["R"] for r in rows]
    assert got == [96, 64]


# ------------------------------------------------------------ AOT ladder


def test_cold_engine_records_fresh_trace_and_exports(tmp_path, state):
    compilation_cache.reset_engine_trace_counts()
    store = _store(tmp_path)
    e1 = Engine(state, DEFAULT_CHAIN, config=CFG, prewarm_store=store)
    e1.precompile_async()
    e1.run()
    assert store.drain(300)
    arts = [f for f in os.listdir(tmp_path) if f.endswith(".aot")]
    assert len(arts) == 1
    bk = bucket_key(state.shape)
    assert compilation_cache.engine_trace_counts()[bk] == {"fresh": 1, "aot": 0}


def test_restart_loads_artifact_and_skips_tracing(tmp_path, state, golden_artifact):
    # "restart": fresh store + engine in this process — the artifact (not
    # the jit cache: a new Engine has its own) serves the fused program
    _install_artifact(tmp_path, golden_artifact)
    compilation_cache.reset_engine_trace_counts()
    e2 = Engine(state, DEFAULT_CHAIN, config=CFG, prewarm_store=_store(tmp_path))
    e2.precompile_async()
    final2, _ = e2.run()
    bk = bucket_key(state.shape)
    assert compilation_cache.engine_trace_counts()[bk] == {"fresh": 0, "aot": 1}
    assert all(
        bool((a == b).all())
        for a, b in zip(golden_artifact["placement"], _placement(final2))
    ), "AOT path changed the result"


def test_artifact_program_keeps_name_and_phase_scopes(tmp_path, state, golden_artifact):
    """The AOT-served anneal runs as `jit__run_fused_impl`, and the
    export/deserialize round trip keeps the anneal's phase scopes in its
    ops' metadata (what a device trace's `tf_op` reads)."""
    import jax
    import jax.numpy as jnp

    from cruise_control_tpu.analyzer.engine import ANNEAL_PHASES

    _install_artifact(tmp_path, golden_artifact)
    e = Engine(state, DEFAULT_CHAIN, config=CFG)
    sx_av = e.statics_avals()
    carry_av = jax.eval_shape(
        e._init_impl, sx_av, jax.ShapeDtypeStruct((2,), jnp.uint32)
    )
    leaves_av, _, donate = e._fused_flat_inputs(sx_av, carry_av)
    max_rf = int(e.statics.part_replicas.shape[1])
    compiled = _store(tmp_path).aot_handle(state.shape, max_rf, CFG).load(
        leaves_av, donate
    )
    assert compiled is not None
    text = compiled.as_text()
    assert text.startswith("HloModule jit__run_fused_impl")
    for phase in ANNEAL_PHASES:
        assert f"/{phase}/" in text, phase


def test_corrupt_artifact_falls_back_to_fresh_compile(tmp_path, state, golden_artifact):
    raw = golden_artifact["data"]
    _install_artifact(tmp_path, golden_artifact, raw[: len(raw) // 2])  # torn
    sensors = SensorRegistry()
    compilation_cache.reset_engine_trace_counts()
    store = _store(tmp_path, sensors=sensors)
    e2 = Engine(state, DEFAULT_CHAIN, config=CFG, prewarm_store=store)
    e2.precompile_async()
    final2, _ = e2.run()  # no crash: the ladder steps to the fresh path
    bk = bucket_key(state.shape)
    assert compilation_cache.engine_trace_counts()[bk]["fresh"] == 1
    assert sensors.counter("analyzer.prewarm-aot-rejects").count == 1
    assert all(
        bool((a == b).all())
        for a, b in zip(golden_artifact["placement"], _placement(final2))
    )
    store.drain(300)


def test_aval_drift_in_artifact_header_is_rejected(tmp_path, state, golden_artifact):
    """Defensive rung: an artifact whose key matches but whose recorded
    avals do not (the exact r4 failure mode: stale program, fresh data)
    must be rejected at load, never called."""
    header_line, _, payload = golden_artifact["data"].partition(b"\n")
    header = json.loads(header_line)
    header["avals"][0][0][0] += 1  # drift one dimension
    _install_artifact(
        tmp_path, golden_artifact, json.dumps(header).encode() + b"\n" + payload
    )
    sensors = SensorRegistry()
    store = _store(tmp_path, sensors=sensors)
    e2 = Engine(state, DEFAULT_CHAIN, config=CFG, prewarm_store=store)
    e2.precompile_async()
    e2.run()
    assert sensors.counter("analyzer.prewarm-aot-rejects").count == 1
    store.drain(300)


def test_fused_out_def_matches_traced_structure(state):
    """The AOT-hit path rebuilds the fused program's output treedef from
    FUSED_YS_KEYS instead of tracing (tracing is the cost artifacts
    exist to skip) — pin the constructed structure to the traced one so
    a ys-schema change cannot silently unflatten garbage."""
    import jax
    import jax.numpy as jnp

    e = Engine(state, DEFAULT_CHAIN, config=CFG)
    sx_av = e.statics_avals()
    key_av = jax.ShapeDtypeStruct((2,), jnp.uint32)
    carry_av = jax.eval_shape(e._init_impl, sx_av, key_av)
    traced = jax.tree.structure(
        jax.eval_shape(e._run_fused_impl, sx_av, carry_av)
    )
    assert e._fused_out_def(carry_av) == traced


def test_aot_never_loads_on_the_request_path(tmp_path, state, golden_artifact):
    """Deserialization runs ONLY on warm-pool workers: a run() without
    precompile_async must never touch an artifact (the r4 cache loaded
    in-line on the request path and regressed warm start)."""
    _install_artifact(tmp_path, golden_artifact)
    store = _store(tmp_path)
    e2 = Engine(state, DEFAULT_CHAIN, config=CFG, prewarm_store=store)
    e2.run()  # no precompile: plain lazy jit
    assert store.aot_load_attempts == 0


def test_no_artifacts_no_manifest_matches_plain_engine_bit_for_bit(tmp_path, state):
    """The acceptance pin: a cold run with an EMPTY store (and one with
    no store at all) produces byte-identical placements — the prewarm
    machinery is a pure warm-up accelerator."""
    plain, _ = Engine(state, DEFAULT_CHAIN, config=CFG).run()
    store = _store(tmp_path / "empty")
    e = Engine(state, DEFAULT_CHAIN, config=CFG, prewarm_store=store)
    e.precompile_async()
    with_store, _ = e.run()
    assert _same_placement(plain, with_store)
    store.drain(300)


def test_warmed_fn_aval_drift_falls_back_to_plain_jit():
    """engine.py _WarmedFn: a precompiled executable whose avals no
    longer match the rebound statics (max_rf drift inside one shape
    bucket) must fall back to the ordinary jit path, not crash."""
    shape = ClusterShape(32, 8, 8, 2, 2, 8, 1)
    s2 = prewarm_state(shape, max_rf=2)
    s3 = prewarm_state(shape, max_rf=3)
    e = Engine(s2, DEFAULT_CHAIN, config=CFG)
    e.precompile_async()
    final2, _ = e.run()  # consumes the warm future -> _WarmedFn installed
    assert isinstance(e._jit_run_fused, _WarmedFn)
    assert final2.shape == shape
    e.rebind(s3)  # same ClusterShape, wider replica table: avals drift
    final3, _ = e.run()  # must not raise; falls back + retraces
    ref, _ = Engine(s3, DEFAULT_CHAIN, config=CFG).run()
    assert _same_placement(final3, ref)


# -------------------------------------------------- compilation_cache scan


def test_scan_and_boot_report_under_concurrent_writer(tmp_path):
    d = str(tmp_path / "cache")
    os.makedirs(d)
    stop = threading.Event()

    def writer():
        i = 0
        while not stop.is_set():
            p = os.path.join(d, f"entry-{i % 17}")
            try:
                with open(p, "wb") as f:
                    f.write(b"x" * 128)
                if i % 3 == 0:
                    os.unlink(p)
            except OSError:
                pass
            i += 1

    t = threading.Thread(target=writer, daemon=True)
    t.start()
    try:
        for _ in range(200):
            entries, total = compilation_cache._scan(d)
            assert total >= 0 and isinstance(entries, set)
        # boot_report tolerates the same racing directory when enabled
        report = compilation_cache.boot_report()
        assert report is None or "engineTraces" in report
    finally:
        stop.set()
        t.join(timeout=5)


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_placement(monkeypatch, tmp_path, env_set):
    """JAX_COMPILATION_CACHE_DIR, when set, is THE cache directory: the
    code sets no other (JAX reads the variable itself).  Without it the
    cache sits at one fixed path inside the checkout."""
    import jax

    from cruise_control_tpu.config.app_config import CruiseControlConfig

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compilation_cache.DEFAULT_CACHE_DIR == os.path.join(repo, ".jax_cache")
    env_dir = str(tmp_path / "from-env")
    if env_set:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compilation_cache, "_enabled", False)
    monkeypatch.setattr(compilation_cache, "_cache_dir", None)
    monkeypatch.setattr(compilation_cache, "_boot_entries", None)
    updates = []
    monkeypatch.setattr(jax.config, "update", lambda k, v: updates.append((k, v)))

    configured = str(tmp_path / "configured")
    expected = env_dir if env_set else configured
    assert compilation_cache.enable_persistent_cache(configured) == expected
    dirs = [v for k, v in updates if k == "jax_compilation_cache_dir"]
    assert dirs == ([] if env_set else [configured])

    cfg = CruiseControlConfig({})
    want = env_dir if env_set else compilation_cache.DEFAULT_CACHE_DIR
    assert cfg.compile_cache_dir() == want
    assert cfg.prewarm_manifest_dir() == os.path.join(want, "prewarm")
    disabled = CruiseControlConfig({"tpu.compile.cache.dir": ""}).compile_cache_dir()
    assert disabled == (env_dir if env_set else None)


def test_exit_waits_for_in_flight_warm_compile(tmp_path):
    """A process that exits while the warm pool compiles must exit 0,
    not abort in interpreter finalization."""
    import subprocess
    import sys
    import textwrap

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    child = textwrap.dedent(f"""
        import sys, threading
        sys.path.insert(0, {repo!r})
        import jax, jax.numpy as jnp
        from cruise_control_tpu.analyzer.engine import warm_pool_submit

        def big(x):
            for _ in range(300):
                x = jnp.sin(x) @ x + jnp.cos(x).T
            return x

        started = threading.Event()

        def compile_it():
            started.set()
            av = jax.ShapeDtypeStruct((64, 64), jnp.float32)
            return jax.jit(big).trace(av).lower().compile()

        fut = warm_pool_submit(compile_it)
        warm_pool_submit(compile_it, priority=10)  # queued: dropped at exit
        started.wait(30)
        print("exiting mid-compile", flush=True)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR="")
    out = subprocess.run(
        [sys.executable, "-c", child], env=env, capture_output=True,
        text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "exiting mid-compile" in out.stdout


def test_warm_pool_wait_idle():
    pool = _WarmPool()
    pool.ensure_workers(1)
    release = threading.Event()
    pool.submit(lambda: release.wait(10))
    pool.submit(lambda: None, priority=5)
    assert not pool.wait_idle(0.05)
    release.set()
    assert pool.wait_idle(10)


# ------------------------------------------------------ warm-pool priority


def test_warm_pool_runs_higher_priority_first():
    pool = _WarmPool()
    pool.ensure_workers(1)
    release = threading.Event()
    order: list[str] = []
    blocker = pool.submit(lambda: release.wait(10))
    lo = pool.submit(lambda: order.append("speculative"), priority=100)
    hi = pool.submit(lambda: order.append("active"), priority=0)
    release.set()
    blocker.result(10)
    hi.result(10)
    lo.result(10)
    assert order == ["active", "speculative"]


# ------------------------------------------------------------ service layer


def _service(props, tmp, seed=3, **geometry):
    from cruise_control_tpu.config.app_config import CruiseControlConfig
    from cruise_control_tpu.service.main import build_simulated_service

    base = {
        "partition.metrics.window.ms": 1000,
        "min.samples.per.partition.metrics.window": 1,
        "num.partition.metrics.windows": 3,
        "webserver.http.port": 0,
        "tpu.num.candidates": 128, "tpu.leadership.candidates": 32,
        "tpu.steps.per.round": 8, "tpu.num.rounds": 2,
        "tpu.compile.cache.dir": os.path.join(tmp, "xla"),
        "tpu.prewarm.manifest.dir": os.path.join(tmp, "prewarm"),
    }
    base.update(props)
    return build_simulated_service(CruiseControlConfig(base), seed=seed, **geometry)


@pytest.mark.slow
def test_start_up_boot_prewarms_manifest_bucket(tmp_path):
    from cruise_control_tpu.service.progress import OperationProgress

    tmp = str(tmp_path)
    app, fetcher, admin, sampler = _service({}, tmp)
    cc = app.cc
    r1 = cc.proposals(OperationProgress(), ignore_cache=True)
    cc.core.prewarm_store.drain(300)
    cc.shutdown()

    app2, fetcher2, admin2, sampler2 = _service({}, tmp)
    cc2 = app2.cc
    cc2.start_up(detection_interval_s=3600)
    assert cc2._boot_prewarm_done.wait(120)
    assert cc2.optimizer.has_engine_for(r1.state_before.shape)
    snap = cc2.sensors.snapshot()
    assert snap["analyzer.boot-prewarm-buckets"]["count"] >= 1
    r2 = cc2.proposals(OperationProgress(), ignore_cache=True)
    assert _same_placement(r1.state_after, r2.state_after)
    cc2.shutdown()


@pytest.mark.slow
def test_fleet_facades_merge_one_manifest_and_both_prewarm(tmp_path):
    """Fleet satellite: two clusters with DIFFERENT shape buckets over
    one shared AnalyzerCore record into ONE merged manifest (dedup, not
    last-writer-wins), and a restart prewarns BOTH clusters' buckets."""
    from cruise_control_tpu.service.main import build_simulated_fleet
    from cruise_control_tpu.service.progress import OperationProgress

    tmp = str(tmp_path)
    clusters = {
        "east": dict(num_brokers=6, topics={"T0": 12, "T1": 12}),
        "south": dict(num_brokers=12, topics={"T0": 48, "T1": 48}),
    }
    props = {
        "tpu.compile.cache.dir": os.path.join(tmp, "xla"),
        "tpu.prewarm.manifest.dir": os.path.join(tmp, "prewarm"),
    }
    app, fleet = build_simulated_fleet(props, clusters=clusters, seed=31)
    shapes = {}
    for cid in ("east", "south"):
        res = fleet.facade(cid).proposals(OperationProgress(), ignore_cache=True)
        shapes[cid] = res.state_before.shape
        # twice: recency touches must dedup, not duplicate
        fleet.facade(cid).proposals(OperationProgress(), ignore_cache=True)
    assert shapes["east"] != shapes["south"], "test needs two distinct buckets"
    store = fleet.core.prewarm_store
    assert store is not None
    store.drain(300)
    keys = store.manifest_bucket_keys()
    assert sorted(keys) == sorted(
        {bucket_key(shapes["east"]), bucket_key(shapes["south"])}
    )
    fleet.shutdown()

    app2, fleet2 = build_simulated_fleet(props, clusters=clusters, seed=31)
    fleet2.start_up(detection_interval_s=3600)
    for cid in ("east", "south"):
        assert fleet2.facade(cid)._boot_prewarm_done.wait(120)
    opt = fleet2.core.optimizer
    assert opt.has_engine_for(shapes["east"]), "east bucket not prewarmed"
    assert opt.has_engine_for(shapes["south"]), "south bucket not prewarmed"
    fleet2.shutdown()


def test_controller_first_cycle_waits_for_boot_gate(tmp_path):
    """Boot-prewarm-under-the-controller satellite: the controller thread
    starts immediately (running=True) but its first cycle waits for the
    boot gate, so manifest compiles are in flight before it takes
    ownership of proposal publishing."""
    tmp = str(tmp_path)
    app, fetcher, admin, sampler = _service(
        {"controller.enabled": True, "controller.poll.interval.ms": 50}, tmp
    )
    cc = app.cc
    ctl = cc.controller
    gate = threading.Event()
    ctl.start(boot_gate=gate)
    assert ctl.running
    parts = sampler.all_partition_entities()
    fetcher.fetch_once(parts, 4000, 4999)  # a rolled window is waiting
    time.sleep(0.5)
    assert ctl._stats["windowRolls"] == 0, "cycle ran before the boot gate"
    gate.set()
    deadline = time.monotonic() + 30
    while ctl._stats["windowRolls"] == 0 and time.monotonic() < deadline:
        time.sleep(0.05)
    assert ctl._stats["windowRolls"] >= 1
    cc.shutdown()
