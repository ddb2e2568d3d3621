"""Model-axis sharding twin: the flattened cluster model partitioned over
MODEL_AXIS.

`_ModelShardEngine` is the second traced-code twin of the plain
:class:`~cruise_control_tpu.analyzer.engine.Engine` (beside
``parallel.mesh._ShardStepEngine``, which shards the CANDIDATE axis and
replicates the model).  Here the MODEL itself is a data axis: every
replica-indexed array (placements, per-replica loads/bytes, topic/rack id
columns) and every partition-indexed array (the partition->replica member
table, the per-partition rack-count cells) is partitioned over MODEL_AXIS
in contiguous row blocks, so per-chip memory for the model state and the
per-step O(R)/O(P) FLOPs drop ~1/n.  Broker/host/topic-indexed aggregates
and all scalars stay replicated — they are O(B), tiny next to O(R).

Layout contract
---------------
The padded global shape has R and P rounded up to multiples of n
(``models.sharding.shard_multiple_shape``); shard ``i`` owns the
contiguous GLOBAL rows ``[i*Rl, (i+1)*Rl)`` / ``[i*Pl, (i+1)*Pl)`` of the
replica / partition axes.  Array VALUES keep global ids (a shard-local
``replica_partition`` row still holds a global partition id), so all
cross-row references work unchanged.

RNG and the ownership gather
----------------------------
Every candidate draw comes from the REPLICATED key, so all shards hold
identical (global) row ids each step.  Row gathers at global ids resolve
by ownership: each shard translates ids into its local range, gathers the
rows it owns, zeros the rest, and ONE ``psum`` over MODEL_AXIS assembles
the full bundle (exactly one shard owns each id; ``x + 0`` is exact for
the non-negative floats involved, and integer/bool columns ride as i32).
Everything between the seams — feasibility, delta math, Metropolis
acceptance, conflict resolution — is replicated math over the K candidate
columns and is inherited from the plain engine verbatim; `_step` itself
is Engine._step, untouched.

Scatter-side: `_apply` already takes global ids in its payload, so the
twin only passes its row offsets/extents — rows owned by other shards
fall out of range and drop, broker/host/topic aggregates (replicated)
absorb every row on every shard.  No collective in the scatter.

Byte parity: psum-assembled row bundles are exactly the plain engine's
gathers (ownership makes each sum a single non-zero term), and the
replicated acceptance math consumes identical inputs — so placements are
byte-identical to the replicated-mesh/plain engine whenever the psum'd
OBJECTIVE partial sums are exact, which integer-quantized loads guarantee
(tests/test_model_shard.py) and float loads track to ulp-level rounding.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from cruise_control_tpu.analyzer.engine import Engine, _uniform_idx, in_phase

MODEL_AXIS = "model"

__all__ = ["ShardPlan", "_ModelShardEngine", "MODEL_AXIS", "stable_grouped_order"]

_INT32_SPAN = 1 << 31


def stable_grouped_order(seg: jax.Array, n_keys: int) -> jax.Array:
    """Stable argsort of integer keys built from SINGLE-operand sorts.

    Drop-in for ``jnp.argsort(seg)`` when ``seg`` holds keys in
    ``[0, n_keys)``.  ``jnp.argsort`` lowers to a variadic (two-operand)
    ``lax.sort``; on the pinned jax/XLA build the CPU backend miscompiles
    variadic sorts of shard-varying operands inside a
    ``shard_map(check_vma=False)`` program whose results feed a
    ``lax.scan`` — every device silently receives device 0's sort output
    (tests/test_model_shard.py::test_variadic_sort_miscompile_guard keeps
    a minimal repro pinned).  Single-operand sorts are unaffected, so the
    grouped order is recovered from ``sort(key * L + index)``: the packed
    value stays inside int32 by sorting in chunks of ``L`` rows and
    splicing the chunks with histogram prefix sums (a counting-sort
    composition — stable across chunks because chunk ``c``'s rows keep a
    lower rank than chunk ``c+1``'s within every key bucket).
    """
    n = int(seg.shape[0])
    if n == 0:
        return jnp.zeros(0, jnp.int32)
    # one extra bucket for chunk padding; packed max is nk * L - 1 < 2^31
    nk = n_keys + 1
    chunk = min(n, max(1, _INT32_SPAN // nk))
    n_chunks = -(-n // chunk)
    padded = n_chunks * chunk
    seg_c = jnp.concatenate(
        [seg.astype(jnp.int32), jnp.full(padded - n, n_keys, jnp.int32)]
    ).reshape(n_chunks, chunk)
    packed = jnp.sort(seg_c * chunk + jnp.arange(chunk, dtype=jnp.int32)[None, :])
    keys = packed // chunk  # [C, L] per-chunk sorted keys
    idx = packed % chunk  # [C, L] per-chunk stable order
    if n_chunks == 1:
        return idx[0, :n]
    hist = jax.vmap(
        lambda s: jax.ops.segment_sum(jnp.ones(chunk, jnp.int32), s, num_segments=nk)
    )(seg_c)  # [C, nk]
    # rank of chunk c's bucket-b rows among ALL bucket-b rows: rows of the
    # same bucket on earlier chunks come first, then in-chunk sorted order
    before_chunks = jnp.concatenate(
        [jnp.zeros((1, nk), jnp.int32), jnp.cumsum(hist[:-1], 0, dtype=jnp.int32)]
    )  # [C, nk] exclusive over chunks
    bucket_start = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(hist.sum(0))[:-1].astype(jnp.int32)]
    )  # [nk] global exclusive over buckets
    in_chunk_start = jnp.concatenate(
        [jnp.zeros((n_chunks, 1), jnp.int32), jnp.cumsum(hist, 1, dtype=jnp.int32)[:, :-1]],
        axis=1,
    )  # [C, nk] exclusive over buckets, per chunk
    q = jnp.arange(chunk, dtype=jnp.int32)[None, :]
    within = q - jnp.take_along_axis(in_chunk_start, keys, axis=1)
    pos = (
        bucket_start[keys] + jnp.take_along_axis(before_chunks, keys, axis=1) + within
    )
    gid = idx + (jnp.arange(n_chunks, dtype=jnp.int32) * chunk)[:, None]
    # padding rows land in bucket n_keys at pos >= n and drop
    return (
        jnp.zeros(n, jnp.int32).at[pos.reshape(-1)].set(gid.reshape(-1), mode="drop")
    )


@partial(
    jax.tree_util.register_dataclass,
    data_fields=[
        "broker_cdf", "order", "start", "count", "count_local", "below",
        "replica_cost", "lead_cost",
    ],
    meta_fields=[],
)
@dataclasses.dataclass(frozen=True)
class ShardPlan:
    """SamplingPlan's model-sharded counterpart.

    The broker categorical (`broker_cdf`) and the movement prices are
    replicated scalars/O(B) — identical to the plain plan.  The grouped
    replica index is shard-local (`order`/`start`/`count_local` cover this
    shard's Rl rows), plus two replicated O(B) columns that make the
    replicated two-stage draw resolvable by ownership: `count` (GLOBAL
    per-broker replica counts — the draw `j ~ U[0, count)` must see the
    global group size to match the plain engine's stream) and `below`
    (how many of broker b's replicas live on lower-indexed shards: the
    stable argsort of contiguous row blocks concatenates per-shard groups
    in shard order, so global group position j lives on the shard where
    ``below[b] <= j < below[b] + count_local[b]`` at local offset
    ``j - below[b]``)."""

    broker_cdf: jax.Array  # f32[B] inclusive cumsum of broker probabilities
    order: jax.Array  # i32[Rl] LOCAL replica ids grouped by broker
    start: jax.Array  # i32[B] group offsets into order (local)
    count: jax.Array  # i32[B] GLOBAL replicas per broker (psum'd)
    count_local: jax.Array  # i32[B] this shard's replicas per broker
    below: jax.Array  # i32[B] replicas per broker on lower-indexed shards
    replica_cost: jax.Array  # f32 scalar (replicated)
    lead_cost: jax.Array  # f32 scalar (replicated)


class _ModelShardEngine(Engine):
    """Engine twin with the model sharded over MODEL_AXIS.

    Shares the parent engine's entire ``__dict__`` (weights, config,
    statics layout) exactly like ``_ShardStepEngine`` — only the
    class-level `_model_axis` marker and the row-provider seams differ,
    so the step/round/anneal schedule is inherited verbatim and cannot
    diverge from the single-device semantics."""

    #: class-level (NOT instance) so the shared __dict__ never leaks the
    #: axis name into the plain engine or the candidate-sharding twin
    _model_axis = MODEL_AXIS

    def __init__(self, engine: Engine, n_shards: int):  # noqa: D401
        # deliberately NOT calling Engine.__init__: traced-code twin
        self.__dict__.update(engine.__dict__)
        R, P = engine.shape.R, engine.shape.P
        if R % n_shards or P % n_shards:
            raise ValueError(
                f"model sharding needs R={R}, P={P} divisible by "
                f"n_shards={n_shards} (pad with shard_multiple_shape)"
            )
        self._n_shards = n_shards
        self._r_local = R // n_shards
        self._p_local = P // n_shards
        self._max_rf = int(engine.statics.part_replicas.shape[1])

    # ------------------------------------------------------------------
    # the ownership gather
    # ------------------------------------------------------------------

    def _axis_idx(self):
        return jax.lax.axis_index(self._model_axis)

    def _own_take(self, cols: dict, ids, local_n: int) -> dict:
        """Gather rows at GLOBAL ids from shard-local column arrays.

        ids may have any shape; each column is [local_n, ...].  Exactly
        one shard owns each id (contiguous row blocks), so the masked
        local gathers sum to the exact global gather under ONE bundled
        psum.  Bool columns ride as i32 (psum rejects bools)."""
        li = ids - self._axis_idx() * local_n
        own = (li >= 0) & (li < local_n)
        lc = jnp.clip(li, 0, local_n - 1)
        picked = {}
        bools = set()
        for f, a in cols.items():
            v = a[lc]
            if v.dtype == jnp.bool_:
                bools.add(f)
                v = v.astype(jnp.int32)
            m = own if v.ndim == own.ndim else own.reshape(
                own.shape + (1,) * (v.ndim - own.ndim)
            )
            picked[f] = jnp.where(m, v, jnp.zeros((), v.dtype))
        out = jax.lax.psum(picked, self._model_axis)
        return {f: (v.astype(bool) if f in bools else v) for f, v in out.items()}

    # ---- row-provider seam overrides (see Engine for the contracts) ----

    @in_phase("anneal_sample")
    def _take_rows(self, sx, carry, ids, fields):
        cols = {f: self._row_source(sx, carry, f) for f in fields}
        return self._own_take(cols, ids, self._r_local)

    @in_phase("anneal_sample")
    def _take_members(self, sx, part):
        return self._own_take(
            {"m": sx.part_replicas}, part, self._p_local
        )["m"]

    @in_phase("anneal_sample")
    def _member_field(self, sx, carry, members, field, fill):
        src = {field: self._row_source(sx, carry, field)}
        vals = self._own_take(
            src, jnp.minimum(members, self.shape.R - 1), self._r_local
        )[field]
        return jnp.where(members < self.shape.R, vals, fill)

    def _rack_cell(self, carry, part, rack):
        lp = part - self._axis_idx() * self._p_local
        own = (lp >= 0) & (lp < self._p_local)
        v = carry.part_rack_count[jnp.clip(lp, 0, self._p_local - 1), rack]
        return jax.lax.psum(
            jnp.where(own, v, 0), self._model_axis
        ).astype(jnp.float32)

    # ------------------------------------------------------------------
    # carry layout / sampling plan
    # ------------------------------------------------------------------

    def _prc_shape(self):
        # part_rack_count rows are shard-local (matches the psum_scatter
        # output of the sharded compute_aggregates)
        return (self._p_local, self.shape.num_racks)

    def _plan_build(self, sx, carry, probs, unit):
        st = sx.state
        B = self.shape.B
        Rl = self._r_local
        seg = jnp.where(st.replica_valid, carry.replica_broker, B)  # [Rl]
        count_local = jax.ops.segment_sum(
            jnp.ones(Rl, jnp.int32), seg, num_segments=B + 1
        )[:B]
        count = jax.lax.psum(count_local, self._model_axis)
        # per-broker replicas on LOWER-indexed shards: the shard-order
        # prefix sum of the gathered local counts
        all_counts = jax.lax.all_gather(count_local, self._model_axis)  # [n, B]
        i = self._axis_idx()
        below = jnp.where(
            jnp.arange(self._n_shards)[:, None] < i, all_counts, 0
        ).sum(0)
        start = jnp.concatenate(
            [jnp.zeros(1, jnp.int32), jnp.cumsum(count_local)[:-1].astype(jnp.int32)]
        )
        return ShardPlan(
            broker_cdf=jnp.cumsum(probs),
            order=stable_grouped_order(seg, B + 1),
            start=start,
            count=count,
            count_local=count_local,
            below=below,
            replica_cost=self.config.replica_move_cost * unit,
            lead_cost=self.config.leadership_move_cost * unit,
        )

    @in_phase("anneal_sample")
    def _sample_sources(self, sx, key, n, plan):
        """Replicated draws, ownership-resolved plan lookups.

        The uniform draws and the two-stage (broker, j) draws are the
        plain engine's replicated streams verbatim (global `count` feeds
        the j draw).  The grouped-order lookup runs shard-local: the
        owner of global group position j reads its local `order` row and
        re-offsets to the global id; a psum assembles the result (stable
        argsort over contiguous ownership blocks == the global grouped
        order, so the stream is bit-identical to the plain engine's)."""
        k1, k3, k4, k5 = jax.random.split(key, 4)
        n_imp = (
            int(round(n * self.config.importance_fraction)) if plan is not None else 0
        )
        r = _uniform_idx(k1, (n - n_imp,), sx.n_source)
        if n_imp:
            u = jax.random.uniform(k3, (n_imp,))
            bsel = jnp.clip(
                jnp.searchsorted(plan.broker_cdf, u, side="right"),
                0, sx.n_brokers - 1,
            ).astype(jnp.int32)
            j = (
                jax.random.uniform(k4, (n_imp,)) * plan.count[bsel]
            ).astype(jnp.int32)
            lj = j - plan.below[bsel]
            own = (lj >= 0) & (lj < plan.count_local[bsel])
            r_loc = plan.order[
                jnp.clip(plan.start[bsel] + lj, 0, self._r_local - 1)
            ]
            r_imp = jax.lax.psum(
                jnp.where(own, r_loc + self._axis_idx() * self._r_local, 0),
                self._model_axis,
            )
            fallback = _uniform_idx(k5, (n_imp,), sx.n_source)
            r_imp = jnp.where(plan.count[bsel] > 0, r_imp, fallback)
            r = jnp.concatenate([r, r_imp])
        return r

    def _apply(self, sx, carry, sv_r, payr, sv_l, payl, **_):
        """Payload ids are global; placement scatters translate to this
        shard's rows (others drop), replicated aggregates absorb all rows.
        No collective."""
        i = self._axis_idx()
        return Engine._apply(
            self, sx, carry, sv_r, payr, sv_l, payl,
            r_offset=i * self._r_local, p_offset=i * self._p_local,
            r_size=self._r_local, p_size=self._p_local,
        )

    # ------------------------------------------------------------------
    # collective accounting (analytic: the psum schedule is static)
    # ------------------------------------------------------------------

    def psum_bytes_per_step(self) -> int:
        """Per-device bytes reduced over MODEL_AXIS in one anneal step.

        Counted analytically from the seam-call schedule (every bundle
        shape is a static function of the candidate split / max_rf /
        config flags, so no tracing is needed): source ownership
        resolutions, the per-kind row bundles (6 resp. 5 scalar columns +
        two [K, 4] load columns each), member tables and member-column
        gathers, rack cells, and the assemble-stage topic/disk gathers.
        All exchanged leaves are 4-byte (i32/f32; bools ride as i32)."""
        cfg = self.config
        mrf = self._max_rf
        pref = 1 if self.w.pref_leader != 0.0 else 0
        rcost = 1 if cfg.replica_move_cost else 0
        lcost = 1 if cfg.leadership_move_cost else 0
        Kr, Ks, Kl = self.K_r, self.K_s, self.K_l
        units = 0
        if Kr:
            units += int(round(Kr * cfg.importance_fraction))  # source resolve
            if cfg.intra_broker:
                units += Kr * (14 + rcost)  # row bundle (no members/racks)
            else:
                if cfg.prior_enabled:
                    units += Kr  # prior-dest topic rows
                units += Kr * (14 + pref + rcost)  # row bundle
                units += 2 * Kr * mrf  # members + member brokers
                units += 2 * Kr  # rack cells
        if Ks:
            units += int(round(Ks * cfg.importance_fraction))
            units += 2 * Ks * (14 + pref + rcost)  # both draw lanes, one bundle
            units += 4 * Ks * mrf  # two member tables + member brokers
            units += 4 * Ks  # four rack cells
        if Kl:
            units += Kl * (13 + pref + lcost)  # target rows
            units += 2 * Kl * mrf  # members + member leader flags
            units += Kl * (10 + pref + lcost)  # current-leader rows
            units += 2 * Kl  # assemble d_f/d_t
        units += Kr + 2 * Ks  # assemble topic column over r_ext
        return 4 * units

    def psum_bytes_per_round(self) -> int:
        """psum_bytes_per_step * steps + the per-round O(B + T·B + P·racks)
        exchanges: the aggregate refresh's psum'd segment sums, the
        part_rack_count reduce-scatter, and the plan rebuild's count
        psum/all_gather.  Scalar gsums (objective, goal violations) are
        counted as a flat noise term."""
        sh = self.shape
        refresh = (
            (sh.B + 1) * 8  # broker_load[,4] + 4 scalar broker columns
            + (sh.num_topics * sh.B + 1)
            + (sh.B * sh.max_disks_per_broker + 1)
            + sh.P * sh.num_racks  # reduce-scatter exchange volume
        )
        plan = sh.B * (1 + self._n_shards)  # count psum + all_gather
        scalars = 64
        return (
            self.psum_bytes_per_step() * self.config.steps_per_round
            + 4 * (refresh + plan + scalars)
        )
