"""GoalOptimizer facade — compute optimization proposals for a cluster model.

Reference: analyzer/GoalOptimizer.java:416-487 (per-goal sequential
optimize + stats + diff) and analyzer/OptimizerResult.java:31.  The TPU
rebuild runs the whole weighted goal chain at once through the batched
annealing engine and reports per-goal violations before/after, cluster
stats, the balancedness score, and the proposal diff.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from cruise_control_tpu.analyzer.engine import Engine, OptimizerConfig
from cruise_control_tpu.analyzer.objective import (
    DEFAULT_CHAIN,
    GoalChain,
    balancedness_score,
)
from cruise_control_tpu.analyzer.options import DEFAULT_OPTIONS, OptimizationOptions
from cruise_control_tpu.common.blackbox import (
    RECORDER as _BLACKBOX,
    blackbox_context,
)
from cruise_control_tpu.common.dispatch import count_dispatch
from cruise_control_tpu.analyzer.proposals import (
    ExecutionProposal,
    ProposalSet,
    extract_proposals,
)
from cruise_control_tpu.config.balancing import BalancingConstraint, DEFAULT_CONSTRAINT
from cruise_control_tpu.models.state import ClusterState, validate
from cruise_control_tpu.models.stats import ClusterStats, compute_stats


@dataclasses.dataclass(frozen=True)
class OptimizerResult:
    """What an optimization run produced (reference analyzer/OptimizerResult.java:31)."""

    proposals: list[ExecutionProposal]
    state_before: ClusterState
    state_after: ClusterState
    stats_before: ClusterStats
    stats_after: ClusterStats
    goal_names: list[str]
    violations_before: np.ndarray  # f32[G]
    violations_after: np.ndarray  # f32[G]
    balancedness_before: float
    balancedness_after: float
    objective_before: float
    objective_after: float
    wall_seconds: float
    history: list[dict]

    @property
    def num_inter_broker_moves(self) -> int:
        # ProposalSet answers from its columns without materializing the
        # ~100k ExecutionProposal objects; plain lists (tests, ad-hoc
        # results) take the object path
        ps = self.proposals
        if isinstance(ps, ProposalSet):
            return ps.num_inter_broker_moves
        return sum(1 for p in ps if p.has_replica_action)

    @property
    def num_leadership_moves(self) -> int:
        ps = self.proposals
        if isinstance(ps, ProposalSet):
            return ps.num_leadership_moves
        return sum(1 for p in ps if p.has_leader_action and not p.has_replica_action)

    @property
    def data_to_move(self) -> float:
        ps = self.proposals
        if isinstance(ps, ProposalSet):
            return ps.data_to_move
        return sum(p.inter_broker_data_to_move for p in ps)

    @property
    def degraded(self) -> bool:
        """True when this result came from the CPU greedy fallback because
        the device path was unavailable (supervisor breaker open) or
        failed with a classified device fault — the history carries a
        `degraded` record with the reason and failure class."""
        return any(h.get("degraded") for h in self.history)

    def violated_goals_after(self, tol: float = 1e-6) -> list[str]:
        """Default tol matches balancedness_score's goal-satisfied epsilon
        (analyzer/objective.py) — a response must not claim balancedness 100
        while listing goals 'violated' by f32 noise."""
        return [n for n, v in zip(self.goal_names, self.violations_after) if v > tol]

    def summary(self) -> dict:
        return {
            "numReplicaMovements": self.num_inter_broker_moves,
            "numLeaderMovements": self.num_leadership_moves,
            "dataToMoveMB": self.data_to_move,
            "balancednessBefore": self.balancedness_before,
            "balancednessAfter": self.balancedness_after,
            "objectiveBefore": self.objective_before,
            "objectiveAfter": self.objective_after,
            "violatedGoalsAfter": self.violated_goals_after(),
            "wallSeconds": self.wall_seconds,
            "degraded": self.degraded,
        }


def parse_parallel_mode(mode: str) -> tuple[int, int] | None:
    """Validate "single" / "sharded" / "grid:RxM"; returns (R, M) for grid
    modes, None otherwise.  The single source of truth for the mode syntax
    (the config validator delegates here)."""
    import re

    if mode in ("single", "sharded"):
        return None
    m = re.fullmatch(r"grid:([1-9]\d*)x([1-9]\d*)", str(mode))
    if m:
        return int(m.group(1)), int(m.group(2))
    raise ValueError(
        f"tpu.parallel.mode must be single | sharded | grid:RxM, got {mode!r}"
    )


def _release_engine(engine) -> None:
    """Free an evicted engine's device buffers (HBM) explicitly.

    Only via the engine's own release() — it knows which statics arrays
    are engine-derived vs caller-owned (deleting blindly would destroy the
    caller's ClusterState buffers, still alive as result.state_before and
    in sibling engines).  Engines without release() fall back to GC."""
    release = getattr(engine, "release", None)
    if release is not None:
        release()


class GoalOptimizer:
    """Entry point the service layer calls (reference GoalOptimizer.optimizations:416)."""

    def __init__(
        self,
        chain: GoalChain = DEFAULT_CHAIN,
        constraint: BalancingConstraint = DEFAULT_CONSTRAINT,
        config: OptimizerConfig = OptimizerConfig(),
        parallel_mode: str = "single",
        mesh_max_devices: int = 0,
        model_shard_min_partitions: int = 0,
        balancedness_weights: tuple[float, float] = (1.1, 1.5),
        engine_cache_size: int = 8,
        sensors=None,
        shape_bucket=None,
        supervisor=None,
        degraded_budget_s: float = 30.0,
        tracer=None,
        profiler_dir: str | None = None,
        prewarm_store=None,
        peak_tracker=None,
        mesh_ft=None,
    ):
        """parallel_mode (config key tpu.parallel.mode): "single" (one
        device), "sharded" (candidate axis sharded over the mesh,
        parallel/sharded.py), or "grid:RxM" (restart portfolio over model
        shards, parallel/grid.py) — both through the shared mesh engine
        layer (parallel/mesh.py).  mesh_max_devices (config key
        tpu.mesh.max.devices) caps how many visible devices the mesh is
        built from; 0 (default) uses them all.

        model_shard_min_partitions (config key
        tpu.mesh.model.shard.min.partitions): real partition count at or
        above which the mesh modes shard the flattened MODEL over the
        model axis (parallel/model_shard.py) instead of replicating it —
        per-chip model memory and per-step row FLOPs drop ~1/n with
        byte-identical placements.  0 (default) keeps the replicated
        model, which wins on collective volume for small clusters.

        balancedness_weights = (priority_weight, strictness_weight) for the
        0-100 balancedness score (reference AnalyzerConfig
        goal.balancedness.{priority,strictness}.weight).

        engine_cache_size (config key tpu.engine.cache.size) bounds the
        per-(shape, config) compiled-engine LRU; evicted engines have
        their device buffers released.  sensors: optional SensorRegistry
        receiving engine-cache hit/miss counters and a size gauge.

        shape_bucket (config keys tpu.shape.bucket.*): ShapeBucketPolicy
        the MULTI-DEVICE engines pad their inputs under, so shard layouts
        derive from bucketed shapes and exact-vs-bucketed builds shard
        identically.  Defaults to the service default policy; the
        single-device path needs no padding here because model builds are
        already bucketed upstream and the engine masks padding anyway.

        supervisor (config keys tpu.supervisor.*): DeviceSupervisor every
        device-path invocation runs under — bounded budget, failure
        classification, retry, circuit breaker (common/device_watchdog.py).
        While the breaker is open (or when a call fails with a classified
        device failure) `optimize` transparently serves a CPU greedy
        result tagged degraded=True instead of hanging or failing; None
        (the default, offline/test usage) keeps the direct path with zero
        behavior change.  degraded_budget_s caps the greedy fallback's
        wall clock (config tpu.supervisor.degraded.greedy.budget.s).

        tracer (config trace.*): flight-recorder Tracer every optimize
        call opens an `analyzer.optimize` span on, with the run's timing
        record (device_s / engine_cache_hit / bucket / degraded) attached
        as attributes; defaults to the process-wide common.trace.TRACER.

        profiler_dir (config tpu.profiler.*): when set, every engine run
        is wrapped in a jax.profiler trace dumped there — the XLA-level
        view for slow-run forensics.  None (default) profiles nothing.

        prewarm_store (config tpu.prewarm.*, analyzer/prewarm.py): the
        durable boot-prewarm manifest + AOT artifact store.  When bound,
        every engine build/rebind records its (bucket, config) working
        set, single-device engines try/save AOT-serialized fused
        programs through their warm pool, and `start_up()` replays the
        manifest so a restart's active buckets compile before the first
        proposal.  None (offline/test/ad-hoc optimizers) records and
        loads nothing.

        peak_tracker (common/profiling.PeakLiveBytesTracker): when bound,
        every optimize records the post-run per-device live bytes into
        the run's shape-bucket cell of the
        `tpu.device.peak-live-bytes-by-bucket` collector.

        mesh_ft (config keys tpu.mesh.ft.*, parallel/ft.py): the mesh
        fault-tolerance controller — per-width breakers, degrade
        episodes, and the slice-boundary checkpoint cadence.  Supervised
        mesh modes default to a controller of their own (checkpointing
        off) so a classified mesh failure degrades the WIDTH ladder
        (narrower mesh -> plain engine -> CPU greedy) instead of opening
        the single-device breaker; pass an explicit controller to wire
        config/sensors, or one with enabled=False to restore the pre-FT
        straight-to-greedy behavior."""
        import threading

        import jax

        self.chain = chain
        self.constraint = constraint
        self.config = config
        self.parallel_mode = parallel_mode
        if mesh_max_devices < 0:
            raise ValueError(
                f"mesh_max_devices must be >= 0, got {mesh_max_devices}"
            )
        self.mesh_max_devices = mesh_max_devices
        if model_shard_min_partitions < 0:
            raise ValueError(
                f"model_shard_min_partitions must be >= 0, got "
                f"{model_shard_min_partitions}"
            )
        self.model_shard_min_partitions = model_shard_min_partitions
        self.balancedness_weights = balancedness_weights
        self._grid_shape = parse_parallel_mode(parallel_mode)
        # device probing stays lazy for the single-device default: only the
        # mesh modes need a count, and jax.devices() on a wedged backend
        # hangs outside any supervisor seam (a hung dispatch)
        if self._grid_shape is not None:
            r, m = self._grid_shape
            n_avail = len(self._mesh_devices())
            if n_avail < r * m:
                raise ValueError(
                    f"tpu.parallel.mode={self.parallel_mode!r} needs "
                    f"{r * m} devices, host has {n_avail} "
                    f"(tpu.mesh.max.devices={mesh_max_devices})"
                )
        elif self.parallel_mode != "single" and len(self._mesh_devices()) < 2:
            # single-chip host: sharded degenerates to the local engine
            self.parallel_mode = "single"
        if engine_cache_size < 1:
            raise ValueError(
                f"engine_cache_size must be >= 1, got {engine_cache_size}"
            )
        from collections import OrderedDict

        #: engines cached per (ClusterShape, search config) in LRU order —
        #: rebinding data is free, recompiling is not (reference amortizes
        #: the same way via its proposal precompute loop,
        #: GoalOptimizer.java:124-175).  Bounded: under topology churn an
        #: unbounded map accretes one full model generation of HBM per
        #: bucket transition; eviction releases the engine's buffers.
        self._engines: OrderedDict = OrderedDict()
        self._parallel_engines: OrderedDict = OrderedDict()
        self._cache_capacity = engine_cache_size
        self._cache_lock = threading.Lock()
        self.sensors = sensors
        self.supervisor = supervisor
        self.degraded_budget_s = degraded_budget_s
        self.prewarm_store = prewarm_store
        self.peak_tracker = peak_tracker
        from cruise_control_tpu.common.trace import TRACER

        self.tracer = tracer if tracer is not None else TRACER
        self.profiler_dir = profiler_dir
        #: per-bucket cumulative cold-start attribution: bucket key ->
        #: {compiles, coldWallSeconds, buildSeconds}.  A cache-miss run's
        #: wall INCLUDES its lazy XLA compile (engine_build_s is host
        #: construction only), so coldWallSeconds is the honest per-bucket
        #: compile+first-run bill — the number ROADMAP item 2's persistent
        #: compile cache must drive toward zero.  Guarded by _cache_lock.
        self._compile_attribution: dict[str, dict] = {}
        #: breaker open-epoch last seen — caches are purged once per open
        #: transition (pull-based: no callback registration to leak across
        #: the facade's short-lived per-request optimizers)
        self._breaker_epoch = supervisor.open_epoch if supervisor is not None else 0
        #: mesh fault tolerance (parallel/ft.py): supervised mesh modes
        #: get a default controller so device loss degrades the width
        #: ladder; "single" mode carries None (zero behavior change)
        if (
            mesh_ft is None
            and self.parallel_mode != "single"
            and supervisor is not None
        ):
            from cruise_control_tpu.parallel.ft import MeshFtController

            mesh_ft = MeshFtController(sensors=sensors)
        self._mesh_ft = mesh_ft if self.parallel_mode != "single" else None
        self._report_cpu = None  # lazy CPU twin of _report (degraded path)
        from cruise_control_tpu.models.state import DEFAULT_BUCKET_POLICY

        self.shape_bucket = (
            shape_bucket if shape_bucket is not None else DEFAULT_BUCKET_POLICY
        )
        #: compile-vs-rebind outcome counters (the churn bench and tests
        #: assert "zero compiles across a churned generation" through these)
        self.engine_cache_hits = 0
        self.engine_cache_misses = 0
        # one persistent jitted program for objective+violations+stats:
        # eager per-op dispatch on large models costs orders of magnitude
        # more than the computation itself
        self._report = jax.jit(
            lambda s: (
                self.chain.evaluate(s, constraint=self.constraint)[:2],
                compute_stats(s),
            )
        )

    # ------------------------------------------------------------------
    # engine cache (bounded LRU, explicit HBM release on eviction)
    # ------------------------------------------------------------------

    @property
    def cache_size(self) -> int:
        """Compiled engines currently resident (plain + parallel) — public
        beside engine_cache_hits/misses: the /fleet rollup and the
        fleet-smoke bench gate read it."""
        return len(self._engines) + len(self._parallel_engines)

    def _record(self, hit: bool, *, count: bool = True) -> None:
        if count:
            if hit:
                self.engine_cache_hits += 1
            else:
                self.engine_cache_misses += 1
            if self.sensors is not None:
                name = "hits" if hit else "misses"
                self.sensors.counter(f"analyzer.engine-cache-{name}").inc()
        if self.sensors is not None:
            self.sensors.gauge("analyzer.engine-cache-size").set(self.cache_size)

    def _cache_get(self, cache, key):
        """Fetch + pin: the engine's busy count is raised under the lock so
        a concurrent eviction never hard-releases an engine mid-run (the
        facade shares one optimizer between request threads and the
        precompute/prewarm thread).  Callers MUST pair with _unpin."""
        with self._cache_lock:
            engine = cache.get(key)
            if engine is not None:
                cache.move_to_end(key)
                engine._cc_busy = getattr(engine, "_cc_busy", 0) + 1
            return engine

    def _unpin(self, engine) -> None:
        # under the same lock as the pinning read-modify-writes: an
        # unlocked decrement could clobber a concurrent _cache_get pin
        # (freeing a live engine) or lose a decrement (leaking it forever)
        with self._cache_lock:
            engine._cc_busy = max(0, getattr(engine, "_cc_busy", 1) - 1)

    def _cache_put(self, cache, key, engine, *, if_absent: bool = False) -> bool:
        """Insert pinned + evict LRU overflow; returns whether `engine`
        was published.  With if_absent=True an existing entry wins and the
        offered engine is released instead (it was never published, so no
        run can be using it) — prewarm's lost-race path.  Evicted (or
        silently replaced) engines are hard-released only when no thread
        holds a pin; a still-busy engine is dropped from the cache and
        left to GC — a rare deferred release beats deleting buffers under
        a live run."""
        released = []
        published = True
        with self._cache_lock:
            old = cache.get(key)
            if old is not None and old is not engine:
                if if_absent:
                    published = False
                else:
                    released.append(old)  # replaced under the same key
            if published:
                engine._cc_busy = getattr(engine, "_cc_busy", 0) + 1
                cache[key] = engine
                cache.move_to_end(key)
                while len(cache) > self._cache_capacity:
                    released.append(cache.popitem(last=False)[1])
        if not published:
            _release_engine(engine)
        for e in released:
            if not getattr(e, "_cc_busy", 0):
                _release_engine(e)
        return published

    def _engine_for(
        self,
        state: ClusterState,
        options: OptimizationOptions,
        config: OptimizerConfig,
        *,
        count: bool = True,
        prior=None,
    ) -> tuple[Engine, dict]:
        """Cached engine for (shape, config) + a compile-vs-rebind outcome
        record ({engine_cache_hit, engine_build_s}) for the result timing.
        The engine comes back PINNED — the caller unpins after run().

        engine_build_s is host construction/rebind time only: the jitted
        programs compile lazily at first run, so the XLA compile itself
        lands in the run's device wall — engine_cache_hit (False exactly
        when that compile will be paid) is the compile signal."""
        key = (state.shape, config)
        engine = self._cache_get(self._engines, key)
        hit = engine is not None
        t0 = time.monotonic()
        if hit:
            try:
                engine.rebind(state, options, prior=prior)
            except BaseException:
                # a failed rebind (bad options mask, device error) must not
                # leave the _cache_get pin behind — a stuck pin exempts the
                # engine from hard release on eviction forever
                self._unpin(engine)
                raise
        else:
            engine = Engine(
                state, self.chain, constraint=self.constraint, options=options,
                config=config, prior=prior, prewarm_store=self.prewarm_store,
            )
            self._cache_put(self._engines, key, engine)
        self._record(hit, count=count)
        self._note_prewarm(engine, config)
        return engine, dict(
            engine_cache_hit=hit, engine_build_s=round(time.monotonic() - t0, 6)
        )

    def _note_prewarm(self, engine, config, *, parallel_mode: str = "single") -> None:
        """Record this engine's (bucket, config) in the boot-prewarm
        manifest — the ACTIVE working set a restart replays.  Best-effort;
        hits refresh recency (throttled on disk), misses write through."""
        store = self.prewarm_store
        if store is None:
            return
        try:
            # the partition-replica table's width (max observed RF) is the
            # one data-dependent aval axis the shape alone does not pin —
            # a prewarm at the wrong width compiles the wrong program
            inner = getattr(engine, "engine", engine)  # mesh engines wrap one
            max_rf = int(inner.statics.part_replicas.shape[1])
            store.note(
                inner.shape, max_rf, config, parallel_mode=parallel_mode
            )
        except Exception:  # noqa: BLE001 — the manifest is best-effort
            pass

    @staticmethod
    def _parallel_key(shape, config, devices):
        """Parallel engines cache per (shape, config, device-id set): the
        mesh fault-tolerance ladder builds engines over SURVIVOR subsets,
        and a reduced-width engine must never be served as (or evicted
        by) the full-width one."""
        return (shape, config, tuple(int(d.id) for d in devices))

    def _parallel_engine(
        self,
        state: ClusterState,
        options: OptimizationOptions,
        config: OptimizerConfig,
        *,
        devices=None,
    ):
        """Multi-device engine per parallel_mode, cached per (shape,
        config, devices) with a data rebind like _engine_for — recompiling
        the sharded programs per request would cost seconds to minutes.
        Shard layouts derive from the (bucketed) global shape, but max_rf
        remains data-dependent; a rebind that changes the local shapes
        falls back to building a fresh engine.  `devices` (mesh ft) builds
        over a survivor subset; None = every mesh device."""
        if devices is None:
            devices = self._mesh_devices()
        key = self._parallel_key(state.shape, config, devices)
        engine = self._cache_get(self._parallel_engines, key)
        t0 = time.monotonic()
        if engine is not None:
            try:
                engine = engine.rebind(state, options)
                self._record(True)
                self._note_prewarm(engine, config, parallel_mode=self.parallel_mode)
                return engine, dict(
                    engine_cache_hit=True,
                    engine_build_s=round(time.monotonic() - t0, 6),
                )
            except ValueError:
                self._unpin(engine)  # local shard shapes changed: rebuild
            except BaseException:
                self._unpin(engine)  # pin must not outlive a failed rebind
                raise
        engine = self._build_parallel_engine(state, options, config, devices=devices)
        self._cache_put(self._parallel_engines, key, engine)
        self._record(False)
        self._note_prewarm(engine, config, parallel_mode=self.parallel_mode)
        return engine, dict(
            engine_cache_hit=False, engine_build_s=round(time.monotonic() - t0, 6)
        )

    def has_engine_for(
        self, shape, *, config: OptimizerConfig | None = None
    ) -> bool:
        """True when a compiled engine for (shape, config) is cached —
        lets the facade's precompute loop skip the padded-model build when
        the next bucket is already warm."""
        cfg = config or self.config
        with self._cache_lock:
            return (shape, cfg) in self._engines or any(
                k[0] == shape and k[1] == cfg for k in self._parallel_engines
            )

    def prewarm(
        self,
        state: ClusterState,
        options: OptimizationOptions = DEFAULT_OPTIONS,
        *,
        config: OptimizerConfig | None = None,
        priority: int = 0,
    ) -> None:
        """Build + background-compile the engine for `state`'s shape without
        running it (the facade pre-warms the NEXT shape bucket with a padded
        model so a bucket overflow hits a warm engine instead of a cold
        compile).  Build-only, never rebind: if an engine for the shape
        already exists — including one a foreground request inserted while
        we were building — it is left untouched, because rebinding it to
        this (possibly stale, zero-padded) snapshot could swap statics
        under a live run.  Does not touch the hit/miss counters.

        Supervised like optimize: with a breaker open nothing is built
        (pre-warming a wedged device only queues more hangs), and a hang
        or device failure during the build is bounded + classified instead
        of wedging the facade's precompute thread forever.  Degradation
        here has no fallback — a skipped prewarm just means the next
        bucket overflow pays its compile.

        `priority` orders this prewarm's compiles on the shared warm pool
        (boot prewarm: the ACTIVE bucket at 0, manifest speculation after
        it, the facade's next-bucket speculation last)."""
        sup = self.supervisor
        if sup is None:
            self._prewarm_on_device(state, options, config=config, priority=priority)
            return
        from cruise_control_tpu.common.device_watchdog import DeviceDegradedError

        self._maybe_purge_after_open()
        if not sup.available():
            return
        try:
            sup.call(
                lambda: self._prewarm_on_device(
                    state, options, config=config, priority=priority
                ),
                op="prewarm",
            )
        except DeviceDegradedError:
            self._maybe_purge_after_open()

    def _prewarm_on_device(
        self,
        state: ClusterState,
        options: OptimizationOptions = DEFAULT_OPTIONS,
        *,
        config: OptimizerConfig | None = None,
        priority: int = 0,
    ) -> None:
        cfg = config or self.config
        parallel = self.parallel_mode != "single"
        key = (
            self._parallel_key(state.shape, cfg, self._mesh_devices())
            if parallel
            else (state.shape, cfg)
        )
        cache = self._parallel_engines if parallel else self._engines
        with self._cache_lock:
            if key in cache:
                return
        # mesh engines warm through the SAME pool as the plain engine
        # (engine.start_warm_pool) — prewarm covers every parallel mode
        engine = (
            self._build_parallel_engine(state, options, cfg)
            if parallel
            else Engine(
                state, self.chain, constraint=self.constraint,
                options=options, config=cfg,
                prewarm_store=self.prewarm_store,
            )
        )
        if not self._cache_put(cache, key, engine, if_absent=True):
            return  # a foreground request built the engine first
        self._record(False, count=False)
        try:
            engine.precompile_async(priority=priority)
        finally:
            self._unpin(engine)

    def _mesh_devices(self):
        """The devices the mesh engine layer may use: every visible device,
        optionally capped by tpu.mesh.max.devices."""
        import jax

        devices = jax.devices()
        if self.mesh_max_devices:
            devices = devices[: self.mesh_max_devices]
        return devices

    def _build_parallel_engine(
        self,
        state: ClusterState,
        options: OptimizationOptions,
        config: OptimizerConfig,
        *,
        devices=None,
    ):
        """Mesh engine for the current parallel_mode over `devices` (None
        = every mesh device, today's exact layout).  A survivor subset
        (mesh ft) keeps the grid's RESTART axis fixed — checkpointed
        chains must map 1:1 onto the rebuilt mesh — and shrinks the MODEL
        axis to what the subset can carry."""
        from cruise_control_tpu.parallel.grid import GridEngine, grid_mesh
        from cruise_control_tpu.parallel.sharded import ShardedEngine, model_mesh

        explicit = devices is not None
        if devices is None:
            devices = self._mesh_devices()
        if self.parallel_mode == "sharded":
            return ShardedEngine(
                state, self.chain, mesh=model_mesh(devices),
                constraint=self.constraint, options=options, config=config,
                bucket=self.shape_bucket,
                model_shard_min_partitions=self.model_shard_min_partitions,
            )
        r, m = self._grid_shape
        if explicit:
            m = len(devices) // r
            if m < 1:
                raise ValueError(
                    f"{len(devices)} devices cannot carry a "
                    f"{r}-restart grid"
                )
        return GridEngine(
            state, self.chain, mesh=grid_mesh(r, m, devices),
            constraint=self.constraint, options=options, config=config,
            bucket=self.shape_bucket,
            model_shard_min_partitions=self.model_shard_min_partitions,
        )

    def optimize(
        self,
        state: ClusterState,
        options: OptimizationOptions = DEFAULT_OPTIONS,
        *,
        verbose: bool = False,
        config: OptimizerConfig | None = None,
        initial_placement=None,
        prior=None,
    ) -> OptimizerResult:
        """Run the goal chain; supervised when a DeviceSupervisor is wired.

        `initial_placement` / `prior` are the streaming controller's
        warm-start inputs (engine.run warm carry + the learned
        move-acceptance prior folded into the sampling plan); both are
        single-device-mode only and ignored by the CPU-greedy degraded
        fallback, which always answers from the current placement.

        Unsupervised (offline/test default) this IS `_optimize_on_device`.
        Supervised, the whole device body — input checks, engine build/
        rebind, compile, anneal, report, extraction — runs inside one
        bounded, classified supervisor call; a breaker already open skips
        the device entirely.  Classified failures (hang / compile / OOM /
        exhausted transient retries) degrade to the CPU greedy path;
        application errors (bad states, bad option masks) propagate
        unchanged so a malformed request can neither degrade the service
        nor get silently served a greedy answer.

        Traced: every call is an `analyzer.optimize` span carrying the
        run's timing record (device_s / blocking_syncs / engine_cache_hit
        / bucket) and degradation verdict as attributes — the flight
        recorder's analyzer stage."""
        cfg = config or self.config
        with self.tracer.span("analyzer.optimize", component="analyzer") as sp:
            if _BLACKBOX.enabled:
                # stamp the dispatch context the black-box spool's leaf
                # records (supervised / device-op / engine-slice) cannot
                # know themselves: which bucket, which search config,
                # which parallel mode — the "what was it doing" half of a
                # hang post-mortem (common/blackbox.py)
                import hashlib

                bucketed = (
                    self.shape_bucket.bucket_shape(state.shape)
                    if self.shape_bucket is not None
                    else state.shape
                )
                ctx = blackbox_context(
                    bucket=self._bucket_key(bucketed),
                    config_fp=hashlib.sha1(
                        repr(cfg).encode()
                    ).hexdigest()[:12],
                    parallel_mode=self.parallel_mode,
                )
            else:
                import contextlib

                ctx = contextlib.nullcontext()
            with ctx:
                result = self._optimize_routed(
                    state, options, verbose, cfg,
                    initial_placement=initial_placement, prior=prior,
                )
            timing = next((h for h in result.history if h.get("timing")), {})
            sp.set(
                parallel_mode=self.parallel_mode,
                degraded=result.degraded,
                wall_s=round(result.wall_seconds, 6),
                num_proposals=len(result.proposals),
                # final per-goal violations ON the span: a /trace replay
                # shows the run's goal quality even with the decision
                # ledger disabled (objective/balancedness beside them)
                objective_after=round(result.objective_after, 6),
                balancedness_after=round(result.balancedness_after, 3),
                goal_violations_after={
                    n: round(float(v), 6)
                    for n, v in zip(
                        result.goal_names, np.asarray(result.violations_after)
                    )
                },
                **{
                    k: timing.get(k)
                    for k in (
                        "device_s", "blocking_syncs", "host_extract_s",
                        "engine_cache_hit", "engine_build_s", "bucket",
                        "mesh_shape", "collective_bytes",
                        # segmented (preemptible) execution under the
                        # device scheduler: how many wall-bounded slices
                        # this anneal dispatched as
                        "segmented", "segments",
                        # convergence diagnostics summary (trajectory,
                        # acceptance by kind, prior usage, final per-goal
                        # violations) when OptimizerConfig.diagnostics
                        "convergence",
                    )
                    if timing.get(k) is not None
                },
            )
            return result

    def _optimize_routed(
        self,
        state: ClusterState,
        options: OptimizationOptions,
        verbose: bool,
        cfg: OptimizerConfig,
        *,
        initial_placement=None,
        prior=None,
    ) -> OptimizerResult:
        """Supervision routing (the pre-trace `optimize` body): device
        path under the supervisor, CPU greedy degradation on breaker-open
        or classified failure — split out so the span wrapper observes
        every route's result uniformly."""
        sup = self.supervisor
        if sup is None:
            return self._optimize_on_device(
                state, options, verbose=verbose, config=cfg,
                initial_placement=initial_placement, prior=prior,
            )
        from cruise_control_tpu.common.device_watchdog import DeviceDegradedError

        self._maybe_purge_after_open()
        if not sup.available():
            return self._optimize_degraded(state, options, cfg, reason="breaker-open")
        ft = self._mesh_ft
        if self.parallel_mode != "single" and ft is not None and ft.enabled:
            return self._optimize_mesh_ft(state, options, verbose, cfg, sup, ft)
        try:
            return sup.call(
                lambda: self._optimize_on_device(
                    state, options, verbose=verbose, config=cfg,
                    initial_placement=initial_placement, prior=prior,
                ),
                op="optimize",
            )
        except DeviceDegradedError as e:
            self._maybe_purge_after_open()
            return self._optimize_degraded(
                state, options, cfg,
                reason=e.failure_class.value, cause=e,
            )

    # ------------------------------------------------------------------
    # mesh fault tolerance (degrade-and-resume width ladder)
    # ------------------------------------------------------------------

    def _reduced_mesh_devices(self, survivors, *, below: int):
        """The next rung's device list after a failure at width `below`:
        the widest power-of-two MODEL-axis width the survivors can carry
        — strictly below the failed width even when attribution named no
        suspect (a blind halving still excludes a wedged chip half the
        time).  Grid modes keep the RESTART axis fixed (checkpointed
        chains must map 1:1 onto the rebuilt mesh) and shrink the model
        axis.  None = no mesh width survives (fall to the plain rung)."""
        r = self._grid_shape[0] if self._grid_shape is not None else 1
        cap = min(len(survivors), below - 1)
        if cap < max(2, r):
            return None
        m = 1
        while m * 2 * r <= cap:
            m *= 2
        return list(survivors[: r * m])

    def _purge_parallel_for_mesh_failure(self, suspect_ids, failed_ids) -> None:
        """Drop parallel engines whose mesh touches the failed chips: a
        lost/wedged device owns buffers of unknown integrity, but engines
        on disjoint survivor subsets — and every single-device engine —
        stay cached (the scoped-purge contract tests/test_mesh_ft.py
        pins)."""
        bad = set(suspect_ids) if suspect_ids else set(failed_ids)
        released = []
        with self._cache_lock:
            for key in [
                k for k in self._parallel_engines if bad & set(k[2])
            ]:
                released.append(self._parallel_engines.pop(key))
        for e in released:
            if not getattr(e, "_cc_busy", 0):
                _release_engine(e)
        self._record(False, count=False)  # refresh the size gauge

    def _optimize_mesh_ft(
        self,
        state: ClusterState,
        options: OptimizationOptions,
        verbose: bool,
        cfg: OptimizerConfig,
        sup,
        ft,
    ) -> OptimizerResult:
        """The mesh width ladder: attempt the widest usable rung, and on a
        classified MESH failure (device lost / collective stall) rebuild
        over the survivors at the next lower power-of-two width, resuming
        from the last slice-boundary checkpoint when one exists.  Every
        mesh attempt runs under that WIDTH's breaker (`sup.call(breaker=
        ...)`) with attribution armed (`mesh_devices=`); non-mesh
        classified failures keep today's straight-to-greedy behavior.
        When no width survives: plain engine under the single-device
        breaker, then CPU greedy — the pre-existing ladder."""
        import contextlib

        from cruise_control_tpu.analyzer.engine import (
            SegmentContext,
            current_segment_context,
            segmented_execution,
        )
        from cruise_control_tpu.common.device_watchdog import (
            CheckpointClock,
            DeviceDegradedError,
            MESH_FAILURE_CLASSES,
            checkpoint_clock_scope,
        )
        from cruise_control_tpu.parallel.ft import CheckpointSlot

        devices = list(self._mesh_devices())
        full_width = len(devices)
        slot = CheckpointSlot()
        clock = CheckpointClock()
        resume = None
        lost: list[int] = []
        last_mesh_error = None
        while devices is not None:
            width = len(devices)
            brk = ft.acquire_width(width)
            if brk is None:  # this width's breaker is open, probe not due
                devices = self._reduced_mesh_devices(devices, below=width)
                continue
            every = ft.checkpoint_every_slices
            if every > 0:
                # install (or augment) the ambient segmented-execution
                # request so mesh slice boundaries feed carry snapshots
                # into the slot; the scheduler's budget and pause
                # callback are preserved.  every=0 installs NOTHING —
                # the off path is byte-for-byte today's dispatch stream.
                ambient = current_segment_context()
                seg_ctx = SegmentContext(
                    ambient.slice_budget_s if ambient is not None else float("inf"),
                    ambient.checkpoint if ambient is not None else None,
                    snapshot_every=every,
                    snapshot_sink=slot.offer,
                    checkpoint_clock=clock,
                )
                scope = segmented_execution(seg_ctx)
            else:
                seg_ctx = None
                scope = contextlib.nullcontext()
            this_resume = resume
            devs = devices
            try:
                with checkpoint_clock_scope(clock), scope:
                    result = sup.call(
                        lambda: self._optimize_on_device(
                            state, options, verbose=verbose, config=cfg,
                            devices=devs, resume=this_resume,
                        ),
                        op="optimize", breaker=brk, mesh_devices=devs,
                    )
            except DeviceDegradedError as e:
                ft.note_width_result(width, ok=False)
                if seg_ctx is not None:
                    # the last offered snapshot may still be persisting on
                    # the background thread — land it before reading the
                    # slot, or a fast failure resumes one boundary stale
                    seg_ctx.wait_snapshot()
                    ft.note_checkpoint_seconds(seg_ctx.snapshot_seconds)
                if e.failure_class not in MESH_FAILURE_CLASSES:
                    # not attributable to specific chips: today's behavior
                    return self._optimize_degraded(
                        state, options, cfg,
                        reason=e.failure_class.value, cause=e,
                    )
                suspects = tuple(int(d) for d in (e.device_ids or ()))
                lost.extend(suspects)
                failed_ids = [int(d.id) for d in devices]
                self._purge_parallel_for_mesh_failure(suspects, failed_ids)
                survivors = (
                    [d for d in devices if int(d.id) not in set(suspects)]
                    if suspects
                    else devices
                )
                nxt = self._reduced_mesh_devices(survivors, below=width)
                ft.note_degrade(
                    lost=suspects,
                    from_width=width,
                    to_width=len(nxt) if nxt is not None else 1,
                    failure_class=e.failure_class.value,
                )
                resume = slot.latest()
                last_mesh_error = e
                devices = nxt
                continue
            ft.note_width_result(width, ok=True)
            if seg_ctx is not None:
                seg_ctx.wait_snapshot()
                ft.note_checkpoint_seconds(seg_ctx.snapshot_seconds)
            ft.note_run_completed(
                width=width, full_width=full_width,
                resumed=this_resume is not None,
            )
            if lost or width < full_width:
                # stamp the degrade on the result: consumers (bench gate,
                # /explain) see which chips were lost and whether the
                # anneal RESUMED (vs restarted) without digging sensors
                result.history.append(
                    dict(
                        mesh_ft=True,
                        lost_devices=sorted(set(lost)),
                        width=width,
                        full_width=full_width,
                        resumed=this_resume is not None,
                        resumed_from_round=(
                            int(this_resume.base)
                            if this_resume is not None
                            else None
                        ),
                    )
                )
            return result
        # no mesh width survives: plain engine under the single-device
        # breaker, then the CPU greedy floor
        from cruise_control_tpu.common.device_watchdog import DeviceDegradedError

        if not sup.available():
            return self._optimize_degraded(
                state, options, cfg, reason="breaker-open",
                cause=last_mesh_error,
            )
        try:
            return sup.call(
                lambda: self._optimize_on_device(
                    state, options, verbose=verbose, config=cfg,
                    force_single=True,
                ),
                op="optimize",
            )
        except DeviceDegradedError as e:
            self._maybe_purge_after_open()
            return self._optimize_degraded(
                state, options, cfg,
                reason=e.failure_class.value, cause=e,
            )

    def optimize_streaming_cycle(
        self,
        state: ClusterState,
        *,
        rows,
        leader_loads,
        follower_loads,
        initial_placement,
        options: OptimizationOptions = DEFAULT_OPTIONS,
        config: OptimizerConfig | None = None,
        prior=None,
        before_host: dict | None = None,
    ):
        """The steady-state streaming cycle as ONE device dispatch + ONE
        host extraction (Engine.run_cycle): delta scatter, warm re-anneal,
        before/after reports, device validation, and the proposal payload
        all ride a single donated jitted program.

        Returns `(OptimizerResult, (new_ll, new_fl))` — the donated-and-
        rescattered live load arrays the caller MUST adopt as the new
        live state (`state`'s own load leaves are dead after this call) —
        or None when the fast path is unavailable: non-single parallel
        mode, supervisor breaker open, or no cached engine for
        (state.shape, config).  On None the caller falls back to the
        staged scatter+optimize path, which builds and caches the engine
        so the NEXT cycle goes fused.

        `before_host` is the controller's reflatten-time placement cache
        (fetch_before_host of the flattened state): placement columns are
        delta-invariant between reflattens, so only `replica_disk_bytes`
        — which the scatter just changed — is refreshed, from the cycle
        payload, with zero extra device traffic.

        The engine call is NOT supervisor-wrapped: supervision exists to
        classify compile hangs and device faults into a degraded answer,
        but the cycle requires an already-cached engine whose programs
        the staged path (which IS supervised) compiled; a post-donation
        failure here must propagate anyway — the live load buffers were
        consumed, so only a reflatten can recover, and the controller's
        loop-failure accounting owns that."""
        cfg = config or self.config
        if self.parallel_mode != "single":
            return None
        sup = self.supervisor
        if sup is not None and not sup.available():
            return None
        engine = self._cache_get(self._engines, (state.shape, cfg))
        if engine is None:
            return None
        from cruise_control_tpu.models.state import DEVICE_CHECKS

        t0 = time.monotonic()
        with self.tracer.span("analyzer.optimize", component="analyzer") as sp:
            try:
                # data-only statics refresh: the prior's CDF/mix are the
                # only statics fields a delta cycle changes (placement
                # metadata is reflatten-invariant; loads are scattered
                # in-graph)
                engine.rebind_prior(prior)
                out_ll, out_fl, host, history = engine.run_cycle(
                    state.replica_load_leader,
                    state.replica_load_follower,
                    rows, leader_loads, follower_loads,
                    initial_placement,
                )
            finally:
                self._unpin(engine)
            self._record(True)
            checks = np.asarray(host["checks"])
            if checks.any():
                bad = [n for n, c in zip(DEVICE_CHECKS, checks) if c]
                raise ValueError(f"optimized state failed sanity checks: {bad}")
            # the effective BEFORE state: the live state with the freshly
            # scattered loads (what the staged path's scatter would have
            # produced); AFTER adds the payload's host placement arrays
            state_before = dataclasses.replace(
                state,
                replica_load_leader=out_ll,
                replica_load_follower=out_fl,
            )
            state_after = dataclasses.replace(
                state_before,
                replica_broker=host["replica_broker"],
                replica_is_leader=host["replica_is_leader"],
                replica_disk=host["replica_disk"],
                replica_offline=host["replica_offline"],
            )
            t_extract = time.monotonic()
            if before_host is not None:
                before_host = dict(
                    before_host, replica_disk_bytes=host["replica_disk_bytes"]
                )
            else:
                from cruise_control_tpu.analyzer.proposals import fetch_before_host

                before_host = fetch_before_host(state_before)
            proposals = extract_proposals(
                state_before, state_after, before_host=before_host
            )
            timing = next((h for h in history if h.get("timing")), None)
            if timing is None:
                timing = dict(timing=True)
                history.append(timing)
            timing["host_extract_s"] = round(time.monotonic() - t_extract, 6)
            timing["engine_cache_hit"] = True
            timing["engine_build_s"] = 0.0
            s = state.shape
            timing["bucket"] = dict(R=s.R, B=s.B, P=s.P, T=s.num_topics)
            viol_b = np.asarray(host["viol_before"])
            viol_a = np.asarray(host["viol_after"])
            wall = time.monotonic() - t0
            result = OptimizerResult(
                proposals=proposals,
                state_before=state_before,
                state_after=state_after,
                stats_before=host["stats_before"],
                stats_after=host["stats_after"],
                goal_names=self.chain.names(),
                violations_before=viol_b,
                violations_after=viol_a,
                balancedness_before=balancedness_score(
                    viol_b,
                    self.chain,
                    priority_weight=self.balancedness_weights[0],
                    strictness_weight=self.balancedness_weights[1],
                ),
                balancedness_after=balancedness_score(
                    viol_a,
                    self.chain,
                    priority_weight=self.balancedness_weights[0],
                    strictness_weight=self.balancedness_weights[1],
                ),
                objective_before=float(host["obj_before"]),
                objective_after=float(host["obj_after"]),
                wall_seconds=wall,
                history=history,
            )
            sp.set(
                parallel_mode=self.parallel_mode,
                fused_cycle=True,
                degraded=False,
                wall_s=round(wall, 6),
                num_proposals=len(result.proposals),
                objective_after=round(result.objective_after, 6),
                balancedness_after=round(result.balancedness_after, 3),
                **{
                    k: timing.get(k)
                    for k in (
                        "device_s", "blocking_syncs", "host_extract_s",
                        "scatter_width", "bucket", "convergence",
                    )
                    if timing.get(k) is not None
                },
            )
            return result, (out_ll, out_fl)

    # ------------------------------------------------------------------
    # per-bucket compile-time attribution (device profiling surface)
    # ------------------------------------------------------------------

    @staticmethod
    def _bucket_key(shape) -> str:
        # one definition (analyzer/prewarm.py): compile attribution, the
        # boot-prewarm manifest, and the coldstart bench's trace report
        # must all name a bucket the same way
        from cruise_control_tpu.analyzer.prewarm import bucket_key

        return bucket_key(shape)

    def _attribute_cold_run(self, shape, *, wall_s: float, build_s: float) -> None:
        with self._cache_lock:
            row = self._compile_attribution.setdefault(
                self._bucket_key(shape),
                {"compiles": 0, "coldWallSeconds": 0.0, "buildSeconds": 0.0},
            )
            row["compiles"] += 1
            row["coldWallSeconds"] = round(row["coldWallSeconds"] + wall_s, 6)
            row["buildSeconds"] = round(row["buildSeconds"] + build_s, 6)

    def compile_attribution(self) -> dict[str, dict]:
        """Cumulative cold-start bill per shape bucket.  A cache-miss
        run's wall INCLUDES its lazy XLA compile (engine_build_s is host
        construction only), so coldWallSeconds is the honest per-bucket
        compile+first-run cost — what ROADMAP item 2's persistent compile
        cache must drive toward zero.  /state AnalyzerState carries it;
        the `analyzer.engine-compile-seconds-by-bucket` collector exposes
        it to /metrics."""
        with self._cache_lock:
            return {k: dict(v) for k, v in self._compile_attribution.items()}

    def compile_attribution_values(self) -> list[tuple[dict, float]]:
        """Collector callback: [({"bucket": key}, coldWallSeconds), ...]."""
        return [
            ({"bucket": k}, v["coldWallSeconds"])
            for k, v in self.compile_attribution().items()
        ]

    def _maybe_purge_after_open(self) -> None:
        """Drop cached engines once per breaker-open transition: a device
        that just wedged/OOMed owns buffers of unknown integrity, and
        recovery should rebuild engines fresh rather than rebind onto
        them.  SCOPED to the failing parallel mode: the single-device
        breaker guards the plain-engine path, so its open drops only
        `_engines` — mesh engines have their own per-width breakers and
        are purged at THEIR failure site (_purge_parallel_for_mesh_failure)
        — except when mesh ft is off and mesh dispatches still ride this
        breaker.  Pinned engines (a hung run still references one from its
        abandoned thread) are dropped from the cache but left to GC."""
        sup = self.supervisor
        if sup is None or sup.open_epoch == self._breaker_epoch:
            return
        self._breaker_epoch = sup.open_epoch
        caches = [self._engines]
        ft = self._mesh_ft
        if self.parallel_mode != "single" and (ft is None or not ft.enabled):
            caches.append(self._parallel_engines)
        released = []
        with self._cache_lock:
            for cache in caches:
                released.extend(cache.values())
                cache.clear()
        for e in released:
            if not getattr(e, "_cc_busy", 0):
                _release_engine(e)
        self._record(False, count=False)  # refresh the size gauge

    def _optimize_on_device(
        self,
        state: ClusterState,
        options: OptimizationOptions = DEFAULT_OPTIONS,
        *,
        verbose: bool = False,
        config: OptimizerConfig | None = None,
        initial_placement=None,
        prior=None,
        devices=None,
        resume=None,
        force_single: bool = False,
    ) -> OptimizerResult:
        """`devices` / `resume` / `force_single` are the mesh
        fault-tolerance ladder's knobs (_optimize_mesh_ft): build the mesh
        engine over a survivor subset, continue a checkpointed anneal from
        its last slice boundary, or take the plain-engine rung below the
        mesh.  All three default to today's behavior."""
        from concurrent.futures import ThreadPoolExecutor

        from cruise_control_tpu.analyzer.proposals import fetch_before_host
        from cruise_control_tpu.models.state import DEVICE_CHECKS, validate_on_device

        t0 = time.monotonic()
        cfg = config or self.config
        # input sanity first — a rejected state must not trigger engine
        # construction or background compilation.  The ON-DEVICE check
        # transfers a [5] count vector instead of the model's bulk arrays
        # (the bulk transfer costs more than the checks); the host
        # validator re-runs for the detailed message only on failure
        count_dispatch("analyzer.validate")
        input_checks = np.asarray(validate_on_device(state))
        if input_checks.any():
            validate(state)  # raises with per-invariant detail
            bad = [n for n, c in zip(DEVICE_CHECKS, input_checks) if c]
            raise ValueError(f"input state failed sanity checks: {bad}")
        # build + warm the engine BEFORE the report: program tracing/
        # compiling proceeds on background threads while the main thread
        # traces the report programs below — the restarted-service warm
        # start (engine.precompile_async docstring)
        engine = None
        cache_info = None
        single = self.parallel_mode == "single" or force_single
        try:
            if single:
                engine, cache_info = self._engine_for(
                    state, options, cfg, prior=prior
                )
            else:
                if initial_placement is not None or prior is not None:
                    raise ValueError(
                        "warm-start placement / move-acceptance prior are "
                        f"single-device only (tpu.parallel.mode={self.parallel_mode!r})"
                    )
                engine, cache_info = self._parallel_engine(
                    state, options, cfg, devices=devices
                )
            # only at production scale: tiny test engines compile in
            # hundreds of ms, and eagerly tracing the rarely-used
            # programs (full-chain violations) would cost more than
            # the overlap wins.  Plain and mesh engines warm through the
            # SAME pool (engine.start_warm_pool), so the sharded variants'
            # shard_map tracing overlaps the report tracing below exactly
            # like the single-device warm start.  An AOT-worthy engine
            # under a bound prewarm store also warms: the warm pool is
            # where artifacts are loaded/exported, and the restart SLO
            # depends on that happening for every active bucket that
            # would pay a real tracing bill.
            aot_worthy = getattr(engine, "aot_worthwhile", None)
            if (
                state.shape.R >= 65_536
                or cfg.num_candidates >= 8_192
                or (
                    self.prewarm_store is not None
                    and aot_worthy is not None
                    and aot_worthy()
                )
            ):
                engine.precompile_async()
            count_dispatch("analyzer.report")
            (obj_b, viol_b), stats_b = self._report(state)
            # the proposal diff needs bulk BEFORE-state arrays on host;
            # pull them on a side thread while the device anneals — input
            # buffers are immutable, and the copy rides the link during
            # compute the host would otherwise spend blocked on the engine
            with ThreadPoolExecutor(max_workers=1) as pool:
                before_host_f = pool.submit(fetch_before_host, state)
                # opt-in device profiling (config tpu.profiler.*): the
                # engine run — where the XLA program actually executes —
                # is the block a profiler dump illuminates
                from cruise_control_tpu.common.profiling import profiler_trace

                run_kwargs = (
                    {"initial_placement": initial_placement}
                    if initial_placement is not None
                    else {}
                )
                if resume is not None and not single:
                    if getattr(engine, "model_sharded", False):
                        # the sharded-model mode has no segmented variant
                        # (mesh.py run() docstring): a reduced-width
                        # retry restarts the schedule instead of resuming
                        resume = None
                    else:
                        run_kwargs["resume"] = resume
                with profiler_trace(self.profiler_dir):
                    final, history = engine.run(verbose=verbose, **run_kwargs)
                before_host = before_host_f.result()
        finally:
            # run() is done with the engine's buffers (everything below
            # reads only the run's OUTPUT arrays); release the eviction
            # pin on EVERY exit path — a pin leaked on an exception would
            # exempt the engine from hard release forever
            if engine is not None:
                self._unpin(engine)
        # dispatch the result report + the on-device sanity check, then do
        # the host-side proposal diff while the device drains them
        count_dispatch("analyzer.report")
        (obj_a, viol_a), stats_a = self._report(final)
        count_dispatch("analyzer.validate")
        final_checks = validate_on_device(final)
        t_extract = time.monotonic()
        proposals = extract_proposals(state, final, before_host=before_host)
        extract_s = time.monotonic() - t_extract
        # complete the device/host timing split the engine started: the
        # proposal diff is the optimizer's host-side share of the wall
        # clock, overlapping the device draining the report programs above
        timing = next((h for h in history if h.get("timing")), None)
        if timing is None:
            timing = dict(timing=True)
            history.append(timing)
        timing["host_extract_s"] = round(extract_s, 6)
        # compile-vs-rebind outcome + the (bucketed) shape served: the
        # observable proof that shape bucketing absorbed a topology change
        # (engine_cache_hit=True, compile_s ~ rebind cost) vs paid a compile
        if cache_info is not None:
            timing.update(cache_info)
        s = state.shape
        timing["bucket"] = dict(R=s.R, B=s.B, P=s.P, T=s.num_topics)
        if self.peak_tracker is not None:
            self.peak_tracker.record(f"R{s.R}-B{s.B}-P{s.P}")
        if self.sensors is not None and timing.get("mesh_shape"):
            # mesh-engine observability (docs/sensors.md "analyzer.mesh-*"):
            # shard count and per-round collective payload are the two
            # numbers that decide whether cross-shard overhead is paying off
            self.sensors.counter("analyzer.mesh-runs").inc()
            self.sensors.gauge("analyzer.mesh-shards").set(
                int(timing["mesh_shape"][1])
            )
            self.sensors.gauge("analyzer.mesh-collective-bytes").set(
                int(timing.get("collective_bytes") or 0)
            )
            if timing.get("model_sharded"):
                # sharded-MODEL runs: the psum payload replacing the
                # replicated model's gathers is the cost side of the
                # ~1/n per-chip memory win (parallel/model_shard.py)
                self.sensors.counter("analyzer.mesh-model-sharded-runs").inc()
                self.sensors.gauge("analyzer.mesh-model-psum-bytes").set(
                    int(timing.get("model_psum_bytes") or 0)
                )
        final_checks = np.asarray(final_checks)
        if final_checks.any():
            bad = [n for n, c in zip(DEVICE_CHECKS, final_checks) if c]
            # re-run the host validator for the detailed message
            validate(final)
            raise ValueError(f"optimized state failed sanity checks: {bad}")
        viol_b = np.asarray(viol_b)
        viol_a = np.asarray(viol_a)
        wall = time.monotonic() - t0
        if cache_info is not None and not cache_info.get("engine_cache_hit", True):
            # cold run: the whole wall (incl. the lazy XLA compile) bills
            # to this shape bucket's cold-start attribution
            self._attribute_cold_run(
                state.shape,
                wall_s=wall,
                build_s=cache_info.get("engine_build_s", 0.0),
            )
        return OptimizerResult(
            proposals=proposals,
            state_before=state,
            state_after=final,
            stats_before=stats_b,
            stats_after=stats_a,
            goal_names=self.chain.names(),
            violations_before=viol_b,
            violations_after=viol_a,
            balancedness_before=balancedness_score(
                viol_b,
                self.chain,
                priority_weight=self.balancedness_weights[0],
                strictness_weight=self.balancedness_weights[1],
            ),
            balancedness_after=balancedness_score(
                viol_a,
                self.chain,
                priority_weight=self.balancedness_weights[0],
                strictness_weight=self.balancedness_weights[1],
            ),
            objective_before=float(obj_b),
            objective_after=float(obj_a),
            wall_seconds=wall,
            history=history,
        )

    # ------------------------------------------------------------------
    # degraded mode (CPU greedy fallback under an open breaker)
    # ------------------------------------------------------------------

    def _optimize_degraded(
        self,
        state: ClusterState,
        options: OptimizationOptions,
        cfg: OptimizerConfig,
        *,
        reason: str,
        cause=None,
    ) -> OptimizerResult:
        """Serve a proposal set WITHOUT the accelerator: the CPU greedy
        oracle (analyzer/greedy.py) under a wall-clock budget, with the
        report programs pinned to the host CPU backend.

        The result is a real OptimizerResult — same extraction semantics,
        same stats/violations/balancedness surface — tagged with a
        `degraded` history record so callers (and the /state endpoint) can
        tell a greedy answer from a TPU answer.  Model arrays are pulled
        to host first; a model already materialized on a wedged device
        cannot be rescued here (the monitor rebuilds from host-side
        samples on the next generation), which is why the facade's model
        build path keeps host copies of every churn-prone array.
        """
        import jax

        from cruise_control_tpu.analyzer.greedy import greedy_optimize
        from cruise_control_tpu.analyzer.proposals import extract_proposals as _extract

        t0 = time.monotonic()
        cpu = jax.local_devices(backend="cpu")[0]
        host_state = jax.tree.map(np.asarray, state)
        # same input contract as the device path: a rejected state raises
        # with per-invariant detail instead of being greedily "optimized"
        validate(host_state)
        final, info = greedy_optimize(
            host_state,
            self.chain,
            self.constraint,
            seed=cfg.seed,
            time_budget_s=self.degraded_budget_s,
            return_info=True,
            device=cpu,
            options=options,  # degraded fixes keep their exclusion contract
        )
        final = jax.tree.map(np.asarray, final)
        if self._report_cpu is None:
            self._report_cpu = jax.jit(
                lambda s: (
                    self.chain.evaluate(s, constraint=self.constraint)[:2],
                    compute_stats(s),
                )
            )
        with jax.default_device(cpu):
            (obj_b, viol_b), stats_b = self._report_cpu(host_state)
            (obj_a, viol_a), stats_a = self._report_cpu(final)
        t_extract = time.monotonic()
        proposals = _extract(host_state, final)
        s = host_state.shape
        history = [
            dict(
                timing=True,
                degraded=True,
                reason=reason,
                failure=(repr(cause) if cause is not None else None),
                greedy=info,
                host_extract_s=round(time.monotonic() - t_extract, 6),
                bucket=dict(R=s.R, B=s.B, P=s.P, T=s.num_topics),
            )
        ]
        if self.sensors is not None:
            self.sensors.counter("analyzer.degraded-proposals").inc()
        viol_b = np.asarray(viol_b)
        viol_a = np.asarray(viol_a)
        return OptimizerResult(
            proposals=proposals,
            state_before=host_state,
            state_after=final,
            stats_before=stats_b,
            stats_after=stats_a,
            goal_names=self.chain.names(),
            violations_before=viol_b,
            violations_after=viol_a,
            balancedness_before=balancedness_score(
                viol_b,
                self.chain,
                priority_weight=self.balancedness_weights[0],
                strictness_weight=self.balancedness_weights[1],
            ),
            balancedness_after=balancedness_score(
                viol_a,
                self.chain,
                priority_weight=self.balancedness_weights[0],
                strictness_weight=self.balancedness_weights[1],
            ),
            objective_before=float(obj_b),
            objective_after=float(obj_a),
            wall_seconds=time.monotonic() - t0,
            history=history,
        )
