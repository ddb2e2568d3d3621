"""Mesh-native engine layer: ONE sharded engine under sharded/grid/portfolio.

Every multi-device mode is a view of the same program over an explicit 2D
``Mesh((RESTART_AXIS, MODEL_AXIS))``:

  * MODEL_AXIS shards the CANDIDATE axis of the anneal.  Each step the
    full-K candidate index stream is drawn from the replicated RNG key
    (identical on every device), each shard evaluates objective deltas for
    only its contiguous K/n slice (``Engine._slice_draws``), and ONE tiled
    ``all_gather`` reassembles the candidate COLUMNS — delta, feasibility,
    src/dst broker, partition ids, apply payload — into full-K order for
    the global conflict resolution that then runs identically everywhere.
  * RESTART_AXIS runs independent annealing chains (different keys) racing
    to the best objective; the winner is selected on the host from the
    per-chain objectives that ride the run's single blocking sync.

  sharded  = Mesh(1, n)   grid:RxM = Mesh(R, M)   portfolio = Mesh(n, 1)

Why gather-candidates-only is safe: the model and the EngineCarry are
REPLICATED over MODEL_AXIS, and after the gather every device applies the
same surviving move set to the same carry — so placements and aggregates
stay byte-identical replicas with no psum'd refresh, no carry exchange,
and no cross-shard scatter.  Communication per step is O(K) candidate
columns — independent of the replica count — and it is the ONLY
collective in the program.

Byte parity by construction: the draws never depend on the mesh size
(full-K streams are drawn before slicing), per-candidate delta math is
row-local, and the gather reassembles the exact full-K order (slices are
edge-padded to n*ceil(K/n) and trimmed after the gather).  A 1-device and
an 8-device run of the same seeded anneal therefore produce identical
objectives, placements, and proposals — the property the virtual-mesh
dryrun and ``bench.py --mesh-smoke`` pin.

The whole multi-round schedule (temperature decay, aggregate refresh,
sampling-plan rebuild, early stop, extra polish rounds) reuses the plain
engine's fused scan-of-scans body (``Engine._fused_rounds_body``) with
the per-shard step swapped in, and the carry is donated — per restart
chain, HBM holds ONE placement copy.  At n=1 the traced program IS the
plain fused program (the slice is the identity and no collective is
emitted), which is what keeps the sharded n=1 overhead under 10%.

MODEL_AXIS additionally has a genuinely SHARDED-MODEL mode
(`model_shard_min_partitions` > 0 and the real partition count at or
above it): the replica/partition-indexed leaves of the statics and the
carry are partitioned over MODEL_AXIS in contiguous row blocks
(``models/sharding.py`` partition-rule tables drive both `device_put`
placement and the shard_map in/out specs), candidate row gathers resolve
by ownership psums, and the goal chain's segment sums run shard-local
with one psum (``parallel/model_shard._ModelShardEngine``).  Per-chip
model memory and per-step O(R)/O(P) FLOPs drop ~1/n — the mode that
carries 25k brokers / 2M partitions on an 8-chip mesh.  Unlike the
replaced rounds-1-5 ``parallel/sharded.py`` design (per-shard RNG
streams, no 1-vs-N parity, ~22% slower at n=1), the
sharded-model mode keeps every RNG draw replicated, so placements stay
byte-identical to the replicated mesh whenever the psum'd objective
partials are exact (integer-quantized loads; float loads track to ulp).
Below the threshold the replicated candidate-sharding mode remains the
default — at small scale the model is tens of MB and candidate
throughput, not HBM, is the axis that pays.

Reference analog: none — the reference optimizer is a single-threaded
Java loop (analyzer/goals/AbstractGoal.java:66-107).
"""

from __future__ import annotations

import dataclasses
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from functools import partial

from cruise_control_tpu.analyzer.engine import (
    CarryCheckpoint,
    Engine,
    OptimizerConfig,
    SEGMENT_MAX_ROUNDS,
    SegmentContext,
    _WarmedFn,
    current_segment_context,
    snapshot_host_tree,
    start_warm_pool,
)
from cruise_control_tpu.analyzer.objective import GoalChain
from cruise_control_tpu.analyzer.options import DEFAULT_OPTIONS, OptimizationOptions
from cruise_control_tpu.common.blackbox import RECORDER as _BLACKBOX
from cruise_control_tpu.common.device_watchdog import device_op
from cruise_control_tpu.common.dispatch import count_dispatch
from cruise_control_tpu.config.balancing import BalancingConstraint, DEFAULT_CONSTRAINT
from cruise_control_tpu.models.sharding import (
    carry_partition_rules,
    match_partition_rules,
    shard_multiple_shape,
    statics_partition_rules,
)
from cruise_control_tpu.models.state import ClusterState, ShapeBucketPolicy
from cruise_control_tpu.parallel.model_shard import _ModelShardEngine

RESTART_AXIS = "restart"
MODEL_AXIS = "model"

log = logging.getLogger(__name__)


def shard_map_unchecked(fn, mesh, in_specs, out_specs):
    """`jax.shard_map` with the replication (vma) check off: every mesh
    program here ends in conflict resolution that runs identically on
    each device after a gather or psum, so its outputs are replicated by
    construction, which the checker cannot prove."""
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def model_mesh(devices=None) -> Mesh:
    """1D candidate-sharding mesh (the "sharded" parallel mode)."""
    devices = devices if devices is not None else jax.devices()
    return Mesh(np.asarray(devices), (MODEL_AXIS,))


def default_mesh(devices=None) -> Mesh:
    """1D restart-portfolio mesh (one chain per device)."""
    devices = devices if devices is not None else jax.devices()
    return Mesh(np.asarray(devices), (RESTART_AXIS,))


def grid_mesh(n_restarts: int, n_shards: int, devices=None) -> Mesh:
    """2D (restart, model) mesh: R chains, each candidate-sharded M ways."""
    devices = np.asarray(devices if devices is not None else jax.devices())
    if devices.size < n_restarts * n_shards:
        raise ValueError(
            f"{devices.size} devices < {n_restarts}x{n_shards} grid"
        )
    grid = devices[: n_restarts * n_shards].reshape(n_restarts, n_shards)
    return Mesh(grid, (RESTART_AXIS, MODEL_AXIS))


def normalize_mesh(mesh: Mesh) -> Mesh:
    """Any supported mesh -> the canonical 2D (restart, model) mesh."""
    names = tuple(mesh.axis_names)
    if names == (RESTART_AXIS, MODEL_AXIS):
        return mesh
    devs = np.asarray(mesh.devices)
    if names == (MODEL_AXIS,):
        return Mesh(devs.reshape(1, -1), (RESTART_AXIS, MODEL_AXIS))
    if names == (RESTART_AXIS,):
        return Mesh(devs.reshape(-1, 1), (RESTART_AXIS, MODEL_AXIS))
    raise ValueError(
        f"mesh axes must be ({RESTART_AXIS!r},), ({MODEL_AXIS!r},) or "
        f"({RESTART_AXIS!r}, {MODEL_AXIS!r}); got {names}"
    )


def _gather_columns(raw, k_full: int):
    """Tiled all_gather of one candidate kind's column bundle back into
    full-K order.  Slices were edge-padded to n*ceil(K/n) rows, and the
    tiled gather concatenates the shards' contiguous slices in order, so
    the first k_full rows ARE the single-device stream."""
    def g(x):
        if x.shape[0] == 0:  # disabled kind: nothing to exchange
            return x
        return jax.lax.all_gather(x, MODEL_AXIS, tiled=True)[:k_full]

    return jax.tree.map(g, raw)


class _ShardStepEngine(Engine):
    """The inner Engine re-skinned for one mesh shard: `_step` evaluates
    only this shard's candidate slice and all_gathers the columns.

    Shares the parent engine's entire state (weights, config, statics
    layout) — only the step differs, so the fused rounds body, the early
    stop, and the sampling-plan rebuild are inherited verbatim and cannot
    diverge from the single-device semantics."""

    def __init__(self, engine: Engine, n_shards: int):  # noqa: D401
        # deliberately NOT calling Engine.__init__: this is a traced-code
        # twin, not a new engine — it shares every attribute (no re-jit)
        self.__dict__.update(engine.__dict__)
        self._mesh_n = n_shards

    def _step(self, sx, carry, temperature, plan=None):
        if self._mesh_n == 1:
            # identity slice, no collective: the traced program IS the
            # plain engine's step (the n=1 overhead guarantee)
            return Engine._step(self, sx, carry, temperature, plan)
        key, k_r, k_s, k_l, k_u = jax.random.split(carry.key, 5)
        g = self._globals(sx, carry)
        idx = jax.lax.axis_index(MODEL_AXIS)
        raw_r, raw_s, raw_l = self._propose_kinds(
            sx, carry, k_r, k_s, k_l, g, plan, slice_=(idx, self._mesh_n)
        )
        raw_r = _gather_columns(raw_r, self.K_r)
        raw_s = _gather_columns(raw_s, self.K_s)
        raw_l = _gather_columns(raw_l, self.K_l)
        prop = self._assemble_prop(sx, carry, raw_r, raw_s, raw_l)
        return self._accept_select_apply(sx, carry, prop, temperature, key, k_u)


class MeshEngine:
    """One engine for every multi-device mode (sharded / grid / portfolio).

    Construction pads the input to its shape bucket (when a policy is
    given) so compiled mesh programs survive topology churn exactly like
    the plain engine, places the statics explicitly as mesh-replicated
    arrays (`NamedSharding(mesh, P())` — arrays committed to one device
    by an earlier single-device run can never poison the mesh program,
    the r4 multichip failure mode), and builds the jitted shard_map
    programs.  `run()` executes the plain engine's fused multi-round
    schedule (`fused_rounds=False` has no mesh variant — the fused body
    is the only one); `run_schedule()` runs an explicit [rounds, steps]
    temperature schedule (the portfolio entry point).
    """

    def __init__(
        self,
        state: ClusterState,
        chain: GoalChain,
        mesh: Mesh | None = None,
        constraint: BalancingConstraint = DEFAULT_CONSTRAINT,
        options: OptimizationOptions = DEFAULT_OPTIONS,
        config: OptimizerConfig = OptimizerConfig(),
        bucket: ShapeBucketPolicy | None = None,
        model_shard_min_partitions: int = 0,
    ):
        self.mesh = normalize_mesh(mesh if mesh is not None else model_mesh())
        self._bucket = bucket if bucket is not None and bucket.enabled else None
        # sharded-model mode gate: opt-in threshold on the REAL partition
        # count (pre-padding — padding must not flip the mode between
        # generations of the same cluster) and a model axis to shard over
        self.model_sharded = (
            model_shard_min_partitions > 0
            and int(self.mesh.shape[MODEL_AXIS]) > 1
            and int(state.shape.P) >= int(model_shard_min_partitions)
        )
        self.global_state = state
        engine = Engine(
            self._padded(state), chain, constraint, options, config
        )
        self._finish_init(engine)

    @classmethod
    def from_engine(cls, engine: Engine, mesh: Mesh) -> "MeshEngine":
        """Wrap an EXISTING plain engine (portfolio_run's entry): reuses
        its statics/config; the caller's engine is never mutated."""
        self = object.__new__(cls)
        self.mesh = normalize_mesh(mesh)
        self._bucket = None
        self.model_sharded = False  # the wrapped engine's shape is as-is
        self.global_state = engine.state
        self._finish_init(engine)
        return self

    def _finish_init(self, engine: Engine) -> None:
        self.n_restarts = int(self.mesh.shape[RESTART_AXIS])
        self.n = int(self.mesh.shape[MODEL_AXIS])
        self.engine = engine
        if not engine.config.fused_rounds:
            # there is no mesh variant of the legacy per-round loop; the
            # fused schedule runs regardless, so say so instead of letting
            # a fused-vs-legacy comparison silently compare fused vs fused
            log.warning(
                "OptimizerConfig.fused_rounds=False has no mesh variant; "
                "the mesh engine always runs the fused schedule"
            )
        self._twin = self._make_twin(engine)
        #: diagnostics of the most recent COMPLETED run (None before/during)
        self.last_info: dict | None = None
        self._warm_futures: dict | None = None
        self._coll_bytes: int | None = None
        #: per-slice-length jitted segmented programs + the lazy
        #: segmented prelude/objective programs (mesh fault tolerance)
        self._seg_mesh_fns: dict = {}
        self._jit_seg_init_mesh = None
        self._jit_obj = None
        self._build_specs()
        self._place_statics()
        self._build_jits()

    def _blackbox_fields(self) -> dict:
        """Fields the `device_op` seam merges into this engine's
        "device-op" Begin records: a killed mesh dispatch's spool verdict
        names the mesh width in flight, not just the op."""
        return {
            "mesh_shape": [self.n_restarts, self.n],
            "n_devices": self.n_restarts * self.n,
        }

    def _make_twin(self, engine: Engine):
        if self.model_sharded:
            return _ModelShardEngine(engine, self.n)
        return _ShardStepEngine(engine, self.n)

    def _build_specs(self) -> None:
        """shard_map in/out spec trees for the statics and the (blocked)
        carry.  Replicated modes use the pytree-prefix specs (P() statics,
        P(RESTART_AXIS) carry) — the pre-sharding programs verbatim; the
        sharded-model mode expands them per-leaf from the models/sharding
        rule tables (the leading restart block axis does not change the
        carry's pytree structure, so the rules match unchanged)."""
        if not self.model_sharded:
            self._sx_specs = P()
            self._carry_specs = P(RESTART_AXIS)
            return
        self._sx_specs = match_partition_rules(
            statics_partition_rules(MODEL_AXIS), self.engine.statics
        )
        carry_av = jax.eval_shape(
            self.engine._init_impl,
            self.engine.statics_avals(),
            jax.ShapeDtypeStruct((2,), jnp.uint32),
        )
        self._carry_specs = match_partition_rules(
            carry_partition_rules(RESTART_AXIS, MODEL_AXIS), carry_av
        )

    # ------------------------------------------------------------------
    # data binding
    # ------------------------------------------------------------------

    def _padded(self, state: ClusterState) -> ClusterState:
        shape = state.shape
        if self._bucket is not None:
            shape = self._bucket.bucket_shape(shape)
        if self.model_sharded:
            # equal contiguous row blocks per shard (on TOP of the bucket
            # shape, so bucketed rebinds stay churn-stable too)
            shape = shard_multiple_shape(shape, self.n_model)
        if shape == state.shape:
            return state
        from cruise_control_tpu.models.builder import pad_state

        return pad_state(state, shape)

    @property
    def n_model(self) -> int:
        return int(self.mesh.shape[MODEL_AXIS])

    def _place_statics(self) -> None:
        """Mesh-replicated copies of the engine statics.  Explicit layout:
        relying on jit's input resharding breaks when an earlier
        single-device program COMMITTED the arrays to one device (the r4
        `portfolio.py:99` devices-mismatch crash); device_put with the
        mesh sharding is correct for committed and uncommitted inputs
        alike.  In sharded-model mode the placement follows the per-leaf
        partition-rule specs instead of blanket replication."""
        if self.model_sharded:
            shardings = jax.tree.map(
                lambda spec: NamedSharding(self.mesh, spec),
                self._sx_specs,
                is_leaf=lambda x: isinstance(x, P),
            )
            self.statics = jax.device_put(self.engine.statics, shardings)
            # the engine built its statics whole on the default device;
            # keeping that copy would leave one device holding the entire
            # model beside its slice.  The sharded copy takes its place
            # (later readers need only its avals and layout).
            self.engine.statics = self._twin.statics = self.statics
        else:
            self.statics = jax.device_put(
                self.engine.statics, NamedSharding(self.mesh, P())
            )

    def rebind(
        self, state: ClusterState, options: OptimizationOptions = DEFAULT_OPTIONS
    ) -> "MeshEngine":
        """Swap in a new model generation without recompiling.  With a
        bucket policy the padded shape is churn-stable, so generations
        inside a bucket always rebind; a bucket overflow raises ValueError
        (the optimizer's signal to build a fresh engine)."""
        self.engine.rebind(self._padded(state), options)
        # the twin snapshot shares the engine's attributes by reference;
        # rebuild it so it can never pin a previous generation's statics
        # (the traced programs read statics from their argument, so this
        # is about buffer lifetime, not numerics)
        self._twin = self._make_twin(self.engine)
        self.global_state = state
        self._place_statics()
        return self

    def release(self) -> None:
        """Drop device buffers on engine-cache eviction.  The mesh
        statics copy's engine-derived arrays are deleted explicitly; the
        `state` leaves are only de-referenced (on a 1-device mesh
        device_put may alias the caller's buffers).  Unusable after."""
        sx = self.statics
        if sx is not None:
            for f in dataclasses.fields(type(sx)):
                if f.name == "state":
                    continue
                for leaf in jax.tree.leaves(getattr(sx, f.name)):
                    try:
                        leaf.delete()
                    except Exception:  # noqa: BLE001 — already-deleted/np
                        pass
        self.statics = None
        self.engine.release()
        self._twin = None  # drop the snapshot's statics reference too
        self.global_state = None
        self._warm_futures = None
        self._seg_mesh_fns = {}
        self._jit_seg_init_mesh = None
        self._jit_obj = None

    # ------------------------------------------------------------------
    # jitted mesh programs
    # ------------------------------------------------------------------

    def _build_jits(self) -> None:
        spec_r = P(RESTART_AXIS)
        self._jit_init = jax.jit(
            shard_map_unchecked(
                self._init_fn, self.mesh,
                in_specs=(self._sx_specs, spec_r), out_specs=self._carry_specs,
            )
        )
        # the fused whole-anneal program; the carry is DONATED so each
        # restart chain holds one placement copy in HBM
        self._jit_run = jax.jit(
            shard_map_unchecked(
                self._run_fn, self.mesh,
                in_specs=(self._sx_specs, self._carry_specs),
                out_specs=(self._carry_specs, spec_r, spec_r),
            ),
            donate_argnums=(1,),
        )
        self._jit_run_verbose = None  # built lazily (adds per-round eval)
        self._jit_schedule = None  # built lazily (portfolio entry point)

    # ---- traced bodies (blocks carry a leading restart axis of 1) ----

    def _init_fn(self, sx, keys_blk):
        carry = self._twin._init_impl(sx, keys_blk[0])
        return jax.tree.map(lambda x: x[None], carry)

    def _run_fn(self, sx, carry_blk):
        return self._run_body(sx, carry_blk, verbose=False)

    def _run_verbose_fn(self, sx, carry_blk):
        return self._run_body(sx, carry_blk, verbose=True)

    def _run_body(self, sx, carry_blk, *, verbose: bool):
        """One restart chain's fused anneal + its final SA objective (the
        host's winner-selection key, riding the same sync as the stats)."""
        eng = self._twin
        carry = jax.tree.map(lambda x: x[0], carry_blk)
        carry, ys = eng._fused_rounds_body(sx, carry, verbose=verbose)
        obj = eng.carry_objective(sx, carry)
        stack = lambda t: jax.tree.map(lambda x: x[None], t)  # noqa: E731
        return stack(carry), stack(ys), obj[None]

    def _schedule_fn(self, sx, carry_blk, temps2d):
        """Explicit-schedule chain (portfolio semantics): scan over temps
        rows with the between-rounds program after every round."""
        eng = self._twin
        carry = jax.tree.map(lambda x: x[0], carry_blk)
        plan = eng._plan_impl(sx, carry)

        def round_body(cp, t_row):
            c, p = cp
            c, stats = eng._scan_impl(sx, c, t_row, p)
            c, p, _cheap = eng._round_prep_impl(sx, c)
            return (c, p), stats["accepted"].sum()

        (carry, _), acc = jax.lax.scan(round_body, (carry, plan), temps2d)
        obj = eng.carry_objective(sx, carry)
        stack = lambda t: jax.tree.map(lambda x: x[None], t)  # noqa: E731
        return stack(carry), obj[None], acc[None]

    # ------------------------------------------------------------------
    # warm start (shared pool with the plain engine)
    # ------------------------------------------------------------------

    def precompile_async(self, *, priority: int = 0) -> None:
        """Trace+lower+compile the mesh programs on the SAME background
        warm pool the plain engine uses (engine.start_warm_pool) so the
        sharded variants' tracing overlaps the caller's serial prelude
        exactly like the single-device warm start.  No AOT artifacts
        here: shard_map'd programs bake mesh/sharding state that the
        round-4 export cache got wrong — the mesh path warms
        by overlap only, at the given pool `priority`."""
        if self._warm_futures is not None:
            return
        sx_av = self.engine.statics_avals()
        key_av = jax.ShapeDtypeStruct((self.n_restarts, 2), jnp.uint32)
        base = jax.eval_shape(
            self.engine._init_impl, sx_av, jax.ShapeDtypeStruct((2,), jnp.uint32)
        )
        carry_av = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct((self.n_restarts,) + a.shape, a.dtype),
            base,
        )
        self._warm_futures = start_warm_pool([
            ("_jit_run", self._jit_run, (sx_av, carry_av)),
            ("_jit_init", self._jit_init, (sx_av, key_av)),
        ], priority=priority)

    def _fn(self, name: str):
        futs = self._warm_futures
        if futs is not None and name in futs:
            fut = futs.pop(name)
            try:
                setattr(self, name, _WarmedFn(fut.result(), getattr(self, name)))
            except Exception as e:  # noqa: BLE001 — fall back to lazy jit
                log.warning("mesh precompile of %s failed: %r", name, e)
        return getattr(self, name)

    # ------------------------------------------------------------------
    # collective accounting
    # ------------------------------------------------------------------

    @property
    def collective_bytes_per_step(self) -> int:
        """Bytes of candidate columns each device holds after the per-step
        gather (the run's ONLY collective): sum over exchanged leaves of
        n*ceil(K/n) padded rows.  0 on a 1-shard mesh (no collective is
        emitted).  Computed abstractly (eval_shape) — no device work.
        In sharded-model mode this is instead the twin's analytic
        ownership-psum byte count (there is no candidate gather)."""
        if self._coll_bytes is None:
            self._coll_bytes = (
                self._twin.psum_bytes_per_step()
                if self.model_sharded
                else self._compute_collective_bytes()
            )
        return self._coll_bytes

    @property
    def collective_bytes_per_round(self) -> int:
        if self.model_sharded:
            return self._twin.psum_bytes_per_round()
        return self.collective_bytes_per_step * self.engine.config.steps_per_round

    def _compute_collective_bytes(self) -> int:
        if self.n == 1:
            return 0
        eng = self.engine
        sx_av = eng.statics_avals()
        key_av = jax.ShapeDtypeStruct((2,), jnp.uint32)
        carry_av = jax.eval_shape(eng._init_impl, sx_av, key_av)
        plan_av = jax.eval_shape(eng._plan_impl, sx_av, carry_av)

        def probe(sx, carry, key, plan):
            g = eng._globals(sx, carry)
            k1, k2, k3 = jax.random.split(key, 3)
            return eng._propose_kinds(sx, carry, k1, k2, k3, g, plan)

        raw = jax.eval_shape(probe, sx_av, carry_av, key_av, plan_av)
        total = 0
        for leaf in jax.tree.leaves(raw):
            k = int(leaf.shape[0])
            rows = self.n * (-(-k // self.n))
            total += rows * int(np.prod(leaf.shape[1:], dtype=np.int64)) * leaf.dtype.itemsize
        return int(total)

    # ------------------------------------------------------------------
    # host-side drivers
    # ------------------------------------------------------------------

    @device_op("mesh.run")
    def run(self, *, verbose: bool = False, resume: CarryCheckpoint | None = None):
        """Execute (or RESUME) the fused schedule on the mesh.

        With an ambient SegmentContext (or an explicit `resume`
        checkpoint) the replicated modes run the schedule in wall-bounded
        slices — the preemption/fault-tolerance seam: carry snapshots
        ride the slice boundaries, and `resume` continues the remaining
        rounds from a CarryCheckpoint captured by ANY mesh width (the
        host trees carry no placement; restore is a device_put under this
        mesh's shardings).  The sharded-model mode has no segmented
        variant (its slice programs would need per-leaf plan specs);
        it always runs whole-schedule, and a mesh failure there restarts
        at the reduced width instead of resuming."""
        seg_ctx = current_segment_context()
        if not verbose and not self.model_sharded and (
            seg_ctx is not None or resume is not None
        ):
            if seg_ctx is None:
                # FT resume outside a scheduler grant: slice only for
                # checkpoint cadence, never for wall bounding
                seg_ctx = SegmentContext(float("inf"))
            return self._run_segmented(seg_ctx, resume=resume)
        if resume is not None:
            raise ValueError(
                "mesh resume requires the segmented path (replicated "
                "modes, non-verbose)"
            )
        return self._run(verbose=verbose)

    def _run(self, *, verbose: bool = False):
        """Execute the fused multi-round schedule on the mesh; returns
        (final_state, history) with the plain engine's history contract
        (winner chain's rounds; `accepted` summed over chains) plus a
        timing record carrying `mesh_shape` and `collective_bytes`."""
        cfg = self.engine.config
        self.last_info = None  # never report a previous run's diagnostics
        t_start = time.monotonic()
        # chain 0 of a 1-chain mesh uses the PLAIN engine's key so the
        # sharded run reproduces the single-device anneal byte-for-byte;
        # portfolios split per-chain keys exactly like portfolio_run
        keys = (
            jax.random.PRNGKey(cfg.seed)[None]
            if self.n_restarts == 1
            else jax.random.split(jax.random.PRNGKey(cfg.seed), self.n_restarts)
        )
        carry = self._fn("_jit_init")(self.statics, keys)
        if verbose:
            if self._jit_run_verbose is None:
                self._jit_run_verbose = jax.jit(
                    shard_map_unchecked(
                        self._run_verbose_fn, self.mesh,
                        in_specs=(self._sx_specs, self._carry_specs),
                        out_specs=(
                            self._carry_specs, P(RESTART_AXIS), P(RESTART_AXIS)
                        ),
                    ),
                    donate_argnums=(1,),
                )
            fused = self._jit_run_verbose
        else:
            fused = self._fn("_jit_run")
        carry, ys, objs = fused(self.statics, carry)
        t_disp = time.monotonic()
        # the run's ONE blocking sync: O(chains * rounds) scalars; the
        # final carries stay on device until the winner extraction below
        ys, objs = jax.device_get((ys, objs))
        t_sync = time.monotonic()
        objs = np.asarray(objs)
        winner = int(np.argmin(objs))
        win_carry = jax.tree.map(lambda x: x[winner], carry)
        state = self.final_state(win_carry)
        history = self._history(ys, winner, cfg, verbose)
        timing = dict(
            timing=True, fused=True, blocking_syncs=1,
            host_dispatch_s=round(t_disp - t_start, 6),
            device_s=round(t_sync - t_disp, 6),
            mesh_shape=[self.n_restarts, self.n],
            collective_bytes=self.collective_bytes_per_round,
        )
        if self.model_sharded:
            # only present when sharded — replicated-mode history records
            # (and everything downstream that hashes them) stay unchanged
            timing["model_sharded"] = True
            timing["model_psum_bytes"] = int(self._twin.psum_bytes_per_round())
        if cfg.diagnostics:
            # convergence summary with the SAME aggregation as the
            # per-round history records above: COUNT fields sum over all
            # chains (accepted == sum(kinds) holds, and the summary can be
            # cross-checked against the round records), while STATE
            # metrics (objective trajectory, final per-goal violations,
            # ran/early-stop) are the winner chain's — the trajectory the
            # served placement actually followed
            win_ys = {k: np.asarray(v)[winner] for k, v in ys.items()}
            for k in ("accepted", "acc_replica", "acc_swap", "acc_lead",
                      "prior_cands", "prior_acc"):
                win_ys[k] = np.asarray(ys[k]).sum(axis=0)
            timing["convergence"] = self.engine._convergence_summary(win_ys)
        history.append(timing)
        self.last_info = dict(
            objectives=objs, winner=winner,
            n_chains=self.n_restarts, n_shards=self.n,
        )
        return state, history

    # ------------------------------------------------------------------
    # segmented (preemptible / checkpointable) mesh execution
    # ------------------------------------------------------------------

    def _seg_init_fn(self, sx, keys_blk):
        """Per-shard segmented prelude: round-0 carry + scan state."""
        eng = self._twin
        carry = eng._init_impl(sx, keys_blk[0])
        seg = eng._seg_init_impl(sx, carry)
        stack = lambda t: jax.tree.map(lambda x: x[None], t)  # noqa: E731
        return stack(carry), stack(seg)

    def _seg_slice_fn(self, L, sx, carry_blk, seg_blk, base):
        """Rounds [base, base+L) of one restart chain — the plain
        engine's `_seg_slice_impl` under the mesh twin, so the sliced
        scan composes to exactly the unsegmented mesh program."""
        eng = self._twin
        carry = jax.tree.map(lambda x: x[0], carry_blk)
        seg = jax.tree.map(lambda x: x[0], seg_blk)
        carry, seg, ys = eng._seg_slice_impl(L, sx, carry, seg, base)
        stack = lambda t: jax.tree.map(lambda x: x[None], t)  # noqa: E731
        return stack(carry), stack(seg), stack(ys)

    def _obj_fn(self, sx, carry_blk):
        eng = self._twin
        carry = jax.tree.map(lambda x: x[0], carry_blk)
        return eng.carry_objective(sx, carry)[None]

    def _seg_mesh_fn(self, L: int):
        fn = self._seg_mesh_fns.get(L)
        if fn is None:
            spec_r = P(RESTART_AXIS)
            fn = jax.jit(
                shard_map_unchecked(
                    partial(self._seg_slice_fn, L), self.mesh,
                    in_specs=(self._sx_specs, self._carry_specs, spec_r, P()),
                    out_specs=(self._carry_specs, spec_r, spec_r),
                ),
                donate_argnums=(1, 2),
            )
            self._seg_mesh_fns[L] = fn
        return fn

    def checkpoint_capture(self, carry, seg, base: int, ys_parts) -> CarryCheckpoint:
        """Host-side CarryCheckpoint of a slice boundary (device idle):
        global numpy trees — no placement — so a narrower mesh can
        restore it with a plain device_put under ITS shardings."""
        count_dispatch("mesh.snapshot")
        return CarryCheckpoint(
            base=int(base),
            carry=snapshot_host_tree(carry),
            seg=snapshot_host_tree(seg),
            ys_parts=[dict(p) for p in ys_parts],
            n_chains=self.n_restarts,
            meta=dict(
                seed=int(self.engine.config.seed),
                mesh_shape=[self.n_restarts, self.n],
            ),
        )

    def _restore_checkpoint(self, ckpt: CarryCheckpoint):
        """device_put a CarryCheckpoint under THIS mesh's shardings.

        The device trees are re-materialized with an eager jnp.copy per
        leaf: device_put of a host tree can ZERO-COPY alias suitably
        aligned numpy buffers (observed on the CPU backend for a subset
        of leaves), and the slice programs donate the carry/seg — a
        donated alias lets XLA scribble its outputs straight into (or
        free) the checkpoint's own memory, silently corrupting it for
        any later resume from the same snapshot (a second degrade in
        one episode, or a retry at another width).  An eager copy op
        always allocates fresh XLA-owned output buffers, so what gets
        donated is never the checkpoint."""
        if int(ckpt.n_chains) != self.n_restarts:
            raise ValueError(
                f"checkpoint has {ckpt.n_chains} chains; this mesh runs "
                f"{self.n_restarts} — resume requires matching chains"
            )
        shard_r = NamedSharding(self.mesh, P(RESTART_AXIS))
        own = lambda t: jax.tree.map(  # noqa: E731
            jnp.copy, jax.device_put(t, shard_r)
        )
        carry = own(ckpt.carry)
        seg = own(ckpt.seg)
        return carry, seg, [dict(p) for p in ckpt.ys_parts], int(ckpt.base)

    def _run_segmented(
        self,
        seg_ctx: SegmentContext,
        *,
        resume: CarryCheckpoint | None = None,
    ):
        """The mesh fused schedule in wall-bounded slices (replicated
        modes): the plain engine's `_run_segmented` loop with every slice
        a whole shard_map program — a mesh slice is never a split
        collective.  Byte parity with the unsegmented mesh run holds by
        scan composition exactly like the single-device pin
        (tests/test_mesh_ft.py); slice boundaries are where the
        fault-tolerance layer captures carry snapshots and where a resume
        re-enters the remaining round schedule."""
        cfg = self.engine.config
        self.last_info = None
        t_start = time.monotonic()
        total = cfg.num_rounds + cfg.extra_round_budget
        budget = max(1e-3, float(seg_ctx.slice_budget_s))
        if resume is not None:
            carry, seg, ys_parts, base = self._restore_checkpoint(resume)
        else:
            keys = (
                jax.random.PRNGKey(cfg.seed)[None]
                if self.n_restarts == 1
                else jax.random.split(
                    jax.random.PRNGKey(cfg.seed), self.n_restarts
                )
            )
            if self._jit_seg_init_mesh is None:
                self._jit_seg_init_mesh = jax.jit(
                    shard_map_unchecked(
                        self._seg_init_fn, self.mesh,
                        in_specs=(self._sx_specs, P(RESTART_AXIS)),
                        out_specs=(self._carry_specs, P(RESTART_AXIS)),
                    )
                )
            count_dispatch("mesh.init")
            carry, seg = self._jit_seg_init_mesh(self.statics, keys)
            ys_parts = []
            base = 0
        device_s = 0.0
        round_wall = None
        L = 1
        slice_i = 0
        while base < total:
            first_use = L not in self._seg_mesh_fns
            t0s = time.monotonic()
            bb_seq = _BLACKBOX.begin(
                "engine-slice",
                slice=slice_i, base_round=int(base), rounds=int(L),
                total_rounds=int(total),
                mesh_shape=[self.n_restarts, self.n],
                n_devices=self.n_restarts * self.n,
            ) if _BLACKBOX.enabled else 0
            try:
                count_dispatch("mesh.slice")
                carry, seg, ys = self._seg_mesh_fn(L)(
                    self.statics, carry, seg, jnp.asarray(base, jnp.int32)
                )
                count_dispatch("mesh.sync")
                ys_host, done_host = jax.device_get((ys, seg[2]))
            except BaseException as e:  # noqa: BLE001 — recorded, re-raised
                _BLACKBOX.end(bb_seq, ok=False, error=repr(e))
                raise
            done = bool(np.all(done_host))
            _BLACKBOX.end(bb_seq, done=done)
            wall = time.monotonic() - t0s
            device_s += wall
            ys_parts.append(ys_host)
            base += L
            slice_i += 1
            per_round = wall / L
            if round_wall is None:
                round_wall = per_round
            elif not first_use:
                round_wall = 0.5 * round_wall + 0.5 * per_round
            if done or base >= total:
                break
            L = 1
            while L * 2 * round_wall <= budget and L * 2 <= SEGMENT_MAX_ROUNDS:
                L *= 2
            if seg_ctx.checkpoint is not None:
                seg_ctx.checkpoint()
            # FT carry snapshot: device idle (the sync above), carry/seg
            # not yet donated into the next slice — the copy races
            # nothing; one predicate when checkpointing is off
            seg_ctx.offer_snapshot(
                lambda c=carry, s=seg, b=base, p=ys_parts:
                    self.checkpoint_capture(c, s, b, p)
            )
        if self._jit_obj is None:
            self._jit_obj = jax.jit(
                shard_map_unchecked(
                    self._obj_fn, self.mesh,
                    in_specs=(self._sx_specs, self._carry_specs),
                    out_specs=P(RESTART_AXIS),
                )
            )
        count_dispatch("mesh.sync")
        objs = np.asarray(jax.device_get(self._jit_obj(self.statics, carry)))
        winner = int(np.argmin(objs))
        win_carry = jax.tree.map(lambda x: x[winner], carry)
        state = self.final_state(win_carry)
        ys = {
            k: np.concatenate([np.asarray(p[k]) for p in ys_parts], axis=1)
            for k in ys_parts[0]
        }
        history = self._history(ys, winner, cfg, verbose=False)
        timing = dict(
            timing=True, fused=True, segmented=True,
            segments=len(ys_parts), blocking_syncs=len(ys_parts) + 1,
            device_s=round(device_s, 6),
            host_dispatch_s=round(
                time.monotonic() - t_start - device_s, 6
            ),
            mesh_shape=[self.n_restarts, self.n],
            collective_bytes=self.collective_bytes_per_round,
        )
        if resume is not None:
            timing["resumed_from_round"] = int(resume.base)
        if seg_ctx.snapshots_taken or seg_ctx.snapshots_skipped:
            timing["snapshots"] = seg_ctx.snapshots_taken
            timing["snapshot_s"] = round(seg_ctx.snapshot_seconds, 6)
        if cfg.diagnostics:
            win_ys = {k: np.asarray(v)[winner] for k, v in ys.items()}
            for k in ("accepted", "acc_replica", "acc_swap", "acc_lead",
                      "prior_cands", "prior_acc"):
                win_ys[k] = np.asarray(ys[k]).sum(axis=0)
            timing["convergence"] = self.engine._convergence_summary(win_ys)
        history.append(timing)
        self.last_info = dict(
            objectives=objs, winner=winner,
            n_chains=self.n_restarts, n_shards=self.n,
        )
        return state, history

    def _history(self, ys, winner: int, cfg, verbose: bool) -> list[dict]:
        """Rebuild the plain engine's history shape from the winner
        chain's per-round flags (Engine._run_fused's exact loop, so a
        1-chain mesh run's history matches the plain engine's)."""
        ran = np.asarray(ys["ran"])[winner]
        stopped = np.asarray(ys["stopped"])[winner]
        temp = np.asarray(ys["temperature"])[winner]
        accepted = np.asarray(ys["accepted"])  # [chains, rounds]
        history: list[dict] = []
        for r in range(len(ran)):
            if stopped[r] and history:
                history[-1]["early_stop"] = True
            if not ran[r]:
                continue
            rec = dict(
                round=len(history),
                temperature=float(temp[r]),
                accepted=int(accepted[:, r].sum()),
            )
            if r >= cfg.num_rounds:
                rec["extra"] = True
            if cfg.diagnostics:
                # engine._fused_history record shape, one schema for
                # downstream consumers.  COUNTS (accepted_by_kind, prior)
                # sum over chains exactly like the pre-existing `accepted`
                # field, so accepted == sum(accepted_by_kind) holds on a
                # multi-chain mesh too; STATE metrics (objective, per-goal
                # violations) are the winner chain's — they describe the
                # placement actually served, and are not additive
                rec["objective"] = float(np.asarray(ys["objective"])[winner, r])
                rec["goal_violations"] = [
                    round(float(v), 8)
                    for v in np.asarray(ys["goal_viol"])[winner, r]
                ]
                rec["accepted_by_kind"] = {
                    "replica": int(np.asarray(ys["acc_replica"])[:, r].sum()),
                    "swap": int(np.asarray(ys["acc_swap"])[:, r].sum()),
                    "leadership": int(np.asarray(ys["acc_lead"])[:, r].sum()),
                }
                rec["prior"] = {
                    "candidates": int(np.asarray(ys["prior_cands"])[:, r].sum()),
                    "accepted": int(np.asarray(ys["prior_acc"])[:, r].sum()),
                }
            elif verbose:
                rec["objective"] = float(np.asarray(ys["objective"])[winner, r])
            history.append(rec)
        return history

    def run_schedule(self, temps, *, seed: int = 0):
        """Run one chain per restart group through an EXPLICIT temperature
        schedule (f32[S] or f32[rounds, S]); returns (best final state,
        {"objectives": f32[chains], "n_chains", "n_shards", "winner"}).
        The portfolio entry point — all rounds device-resident, one
        winner-selection sync."""
        temps = jnp.asarray(temps, jnp.float32)
        if temps.ndim == 1:
            temps = temps[None]
        if self._jit_schedule is None:
            self._jit_schedule = jax.jit(
                shard_map_unchecked(
                    self._schedule_fn, self.mesh,
                    in_specs=(self._sx_specs, self._carry_specs, P()),
                    out_specs=(
                        self._carry_specs, P(RESTART_AXIS), P(RESTART_AXIS)
                    ),
                ),
                donate_argnums=(1,),
            )
        keys = jax.random.split(jax.random.PRNGKey(seed), self.n_restarts)
        carry = self._jit_init(self.statics, keys)
        carry, objs, acc = self._jit_schedule(self.statics, carry, temps)
        objs = np.asarray(jax.device_get(objs))
        winner = int(np.argmin(objs))
        state = self.final_state(jax.tree.map(lambda x: x[winner], carry))
        info = dict(
            objectives=objs, n_chains=self.n_restarts, n_shards=self.n,
            winner=winner, accepted=np.asarray(acc),
        )
        self.last_info = info
        return state, info

    def final_state(self, carry) -> ClusterState:
        """Winner carry -> ClusterState on the CALLER's original (unpadded)
        axes.  pad_state appends padding rows, so the original replicas are
        the leading slice of the padded placement."""
        rb, rl, rd = jax.device_get(
            (carry.replica_broker, carry.replica_is_leader, carry.replica_disk)
        )
        st = self.global_state
        R = st.shape.R
        rb, rl, rd = np.asarray(rb)[:R], np.asarray(rl)[:R], np.asarray(rd)[:R]
        alive = np.asarray(st.broker_alive)
        dalive = np.asarray(st.disk_alive)
        offline = ~(alive[rb] & dalive[rb, rd]) & np.asarray(st.replica_valid)
        return dataclasses.replace(
            st,
            replica_broker=jnp.asarray(rb),
            replica_is_leader=jnp.asarray(rl),
            replica_disk=jnp.asarray(rd),
            replica_offline=jnp.asarray(offline),
        )
