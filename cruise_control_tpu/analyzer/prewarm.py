"""Boot prewarm manifest + AOT-serialized engine programs.

The warm-up wall: every process restart pays Python tracing + XLA
compile before the first proposal — the persistent XLA cache (PR 9,
common/compilation_cache.py) skips the compile but not the tracing, and
only once a proposal pass happens to request that bucket.  This module
closes both gaps:

  * **Manifest** (`PrewarmStore.note`): on every engine build/rebind the
    service records its ACTIVE working set — bucketed shape (+ max_rf,
    the one aval axis the shape alone does not pin), the full
    OptimizerConfig, parallel mode, and an environment fingerprint
    (jax/jaxlib version + goal chain + constraint) — to a small durable
    JSON file inside the compile cache's mount (config
    `tpu.prewarm.manifest.*`; the cache's inventory scan prunes it).
    Entries are MERGED on write (read-modify-write under the file's
    directory, dedup by bucket+config+fingerprint), so N fleet facades
    sharing one AnalyzerCore — or two processes sharing one cache
    directory — union their working sets instead of last-writer-wins.
    On boot, `CruiseControl.start_up()` replays the manifest through the
    warm pool (`claim_boot_entries` → `GoalOptimizer.prewarm`) so the
    active buckets are compiling BEFORE the first request, the recovery
    resume, or the streaming controller's first cycle needs a proposal.

  * **AOT artifacts** (`_AotHandle`): the fused whole-anneal program is
    exported per (bucket, config-fingerprint) via `jax.export` the first
    time it compiles, so a warm-disk restart skips Python tracing too.
    Done right this time (the round-4 in-line attempt regressed warm
    start and broke multi-device modes — see Engine.precompile_async):
    deserialization runs ONLY on the warm-pool workers, never the
    request path; artifacts are keyed strictly on the manifest
    fingerprint + the exact input avals + jax/jaxlib version + backend
    platform; and any drift or corruption makes `load` return None so
    the caller falls back to the plain-jit path — correctness never
    depends on an artifact.  The export step also compiles the exported
    module once (in the background, off the request path) so its XLA
    executable lands in the persistent compile cache: the next restart
    pays neither the trace nor the compile.

Reference analog: none — a JVM has no trace/compile step to amortize;
this is the TPU framework's restart SLO (ROADMAP item 2), gated by
`bench.py --coldstart`.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import logging
import os
import tempfile
import threading
import time

log = logging.getLogger(__name__)

#: manifest + artifact layout version; a bump invalidates old files
VERSION = 1

#: throttle for recency-only manifest rewrites (a rebind storm must not
#: turn into an fsync storm; new entries always write immediately)
_TOUCH_WRITE_INTERVAL_S = 60.0

_BUCKET_FIELDS = (
    "R", "B", "P", "topics", "racks", "hosts", "disks", "max_rf"
)


def bucket_key(shape) -> str:
    """Human-readable bucket id — the SAME format GoalOptimizer's
    compile attribution uses, so boot reports and /state rows join."""
    return f"R{shape.R}.B{shape.B}.P{shape.P}.T{shape.num_topics}"


def _bucket_dict(shape, max_rf: int) -> dict:
    return {
        "R": int(shape.num_replicas),
        "B": int(shape.num_brokers),
        "P": int(shape.num_partitions),
        "topics": int(shape.num_topics),
        "racks": int(shape.num_racks),
        "hosts": int(shape.num_hosts),
        "disks": int(shape.max_disks_per_broker),
        "max_rf": int(max_rf),
    }


def _shape_from_dict(b: dict):
    from cruise_control_tpu.models.state import ClusterShape

    return ClusterShape(
        num_replicas=int(b["R"]),
        num_brokers=int(b["B"]),
        num_partitions=int(b["P"]),
        num_topics=int(b["topics"]),
        num_racks=int(b["racks"]),
        num_hosts=int(b["hosts"]),
        max_disks_per_broker=int(b["disks"]),
    )


def _sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]


_source_digest_cache: str | None = None


def _source_digest() -> str:
    """Digest of the Python source that DEFINES the traced engine
    programs (analyzer/ + models/).  An AOT artifact is a frozen trace:
    without this, editing the engine's math would keep serving the OLD
    program from a shared artifact directory — silently.  The persistent
    XLA cache is immune (keyed by HLO); the artifact tier must key on
    source identity explicitly."""
    global _source_digest_cache
    if _source_digest_cache is not None:
        return _source_digest_cache
    h = hashlib.sha256()
    try:
        import cruise_control_tpu.analyzer as _ana
        import cruise_control_tpu.models as _mod

        for pkg in (_ana, _mod):
            root = os.path.dirname(os.path.abspath(pkg.__file__))
            for dirpath, dirs, files in os.walk(root):
                dirs.sort()  # readdir order is filesystem-dependent: two
                # hosts sharing one artifact dir must digest identically
                for fn in sorted(files):
                    if not fn.endswith(".py"):
                        continue
                    path = os.path.join(dirpath, fn)
                    with open(path, "rb") as f:
                        # relative path + separator: a file moved between
                        # subpackages (or renamed) must change the digest
                        h.update(os.path.relpath(path, root).encode() + b"\0")
                        h.update(f.read())
    except Exception:  # noqa: BLE001 — source unavailable (frozen install):
        # fall back to version-only keying rather than disabling prewarm
        h.update(b"no-source")
    _source_digest_cache = h.hexdigest()[:16]
    return _source_digest_cache


def environment_fingerprint(chain, constraint) -> str:
    """Strict identity of everything an engine program bakes in BESIDES
    the OptimizerConfig (which rides each entry verbatim so it can be
    reconstructed): goal chain (names + weights), constraint thresholds,
    the jax/jaxlib versions, and a digest of the engine/model source
    itself (an artifact is a frozen trace — a code change must
    invalidate it).  A restart under a different chain, thresholds,
    runtime, or code must not prewarm (or deserialize) stale programs —
    mismatched entries are simply skipped."""
    import jax
    import jaxlib

    names = ",".join(g.name for g in chain.goals)
    weights = ",".join(repr(float(w)) for w in chain.weights)
    return _sha(
        f"v{VERSION}|{jax.__version__}|{jaxlib.__version__}"
        f"|{_source_digest()}|{names}|{weights}|{constraint!r}"
    )


def _config_dict(config) -> dict:
    return dataclasses.asdict(config)


def _config_from_dict(d: dict):
    """OptimizerConfig back from its JSON form; raises on unknown fields
    (a manifest written by a future version must be skipped, not
    half-applied)."""
    from cruise_control_tpu.analyzer.engine import OptimizerConfig

    return OptimizerConfig(**d)


def _atomic_write(path: str, data: bytes) -> None:
    d = os.path.dirname(path)
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=d, prefix=".tmp-", suffix=os.path.basename(path))
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(data)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


class _AotHandle:
    """Load/save seam for ONE fused program's AOT artifact.

    `load` runs on a warm-pool worker and returns a COMPILED flat
    executable, or None on any mismatch (version, fingerprint, platform,
    avals, checksum) or corruption — the caller's fresh-compile path is
    always the fallback.  `save` exports + persists + compiles the
    exported module once so the persistent XLA cache holds its
    executable for the next restart."""

    def __init__(self, store: "PrewarmStore", key_fp: str, bucket: str):
        self.store = store
        self.key_fp = key_fp
        self.bucket = bucket

    @property
    def path(self) -> str:
        return os.path.join(self.store.directory, f"fused-{self.key_fp}.aot")

    # -------------------------------------------------------------- load

    def load(self, leaves_avals, donate_argnums):
        """Deserialize + compile the artifact against the CURRENT avals.
        None on any problem; never raises."""
        import jax

        self.store.aot_load_attempts += 1
        try:
            with open(self.path, "rb") as f:
                header_line = f.readline()
                payload = f.read()
        except OSError:
            return None  # no artifact: the ordinary cold path
        try:
            header = json.loads(header_line)
            if header.get("v") != VERSION:
                raise ValueError(f"artifact version {header.get('v')}")
            import jaxlib

            if (
                header.get("jax") != jax.__version__
                or header.get("jaxlib") != jaxlib.__version__
            ):
                raise ValueError("jax/jaxlib version drift")
            if header.get("fp") != self.key_fp:
                raise ValueError("fingerprint mismatch")
            if header.get("platform") != jax.default_backend():
                raise ValueError(
                    f"platform {header.get('platform')} != {jax.default_backend()}"
                )
            if header.get("sha256") != hashlib.sha256(payload).hexdigest():
                raise ValueError("payload checksum mismatch (corrupt/truncated)")
            want = [[list(a.shape), str(a.dtype)] for a in leaves_avals]
            if header.get("avals") != want:
                raise ValueError("input aval drift")
            from jax import export as jax_export

            ex = jax_export.deserialize(payload)
            compiled = (
                jax.jit(ex.call, donate_argnums=tuple(donate_argnums))
                .trace(*leaves_avals)
                .lower()
                .compile()
            )
        except Exception as e:  # noqa: BLE001 — artifact is an optimization only
            self.store._count("analyzer.prewarm-aot-rejects")
            log.warning("AOT artifact %s rejected: %r", self.path, e)
            # a rejected artifact must not poison its bucket forever:
            # save_async skips existing files, so leaving the bad one in
            # place would disable the AOT tier for this bucket on every
            # future restart — delete it and let the fresh path re-export
            try:
                os.unlink(self.path)
            except OSError:
                pass
            return None
        self.store._count("analyzer.prewarm-aot-hits")
        return compiled

    # -------------------------------------------------------------- save

    def save_async(self, flat_fn, leaves_avals, donate_argnums, *, priority=1_000):
        """Schedule export+persist (+ one compile of the exported module,
        seeding the persistent XLA cache) on the warm pool at LOW
        priority — never on the path that is waiting for a compile."""
        if os.path.exists(self.path):
            return None
        from cruise_control_tpu.analyzer.engine import warm_pool_submit

        fut = warm_pool_submit(
            lambda: self._save(flat_fn, leaves_avals, donate_argnums),
            priority=priority,
        )
        with self.store._lock:
            self.store._export_futures.append(fut)
        return fut

    def _save(self, flat_fn, leaves_avals, donate_argnums) -> str:
        import jax
        import jaxlib
        from jax import export as jax_export

        jitted = jax.jit(flat_fn, donate_argnums=tuple(donate_argnums))
        ex = jax_export.export(jitted)(*leaves_avals)
        payload = ex.serialize()
        header = {
            "v": VERSION,
            "jax": jax.__version__,
            "jaxlib": jaxlib.__version__,
            "platform": jax.default_backend(),
            "fp": self.key_fp,
            "bucket": self.bucket,
            "avals": [[list(a.shape), str(a.dtype)] for a in leaves_avals],
            "sha256": hashlib.sha256(payload).hexdigest(),
            "ms": int(time.time() * 1000),
        }
        _atomic_write(
            self.path, json.dumps(header).encode() + b"\n" + payload
        )
        # compile the EXPORTED module once so its executable is in the
        # persistent XLA cache: a restart's deserialize-then-compile is a
        # disk hit, not a fresh compile.  (The exported module is not
        # byte-identical to the plain jit's, so without this the first
        # AOT boot would pay the compile the cache was supposed to skip.)
        jax.jit(ex.call, donate_argnums=tuple(donate_argnums)).trace(
            *leaves_avals
        ).lower().compile()
        self.store._count("analyzer.prewarm-aot-exports")
        return self.path


class PrewarmStore:
    """One durable manifest (+ AOT artifact directory) per deployment.

    Built by AnalyzerCore from `tpu.prewarm.*` config and shared by every
    facade over that core (the fleet's merge-not-clobber requirement);
    handed to the long-lived GoalOptimizer only — ad-hoc per-request
    optimizers (custom goal lists) are transient and never recorded."""

    def __init__(
        self,
        directory: str,
        *,
        chain,
        constraint,
        aot_enabled: bool = True,
        max_entries: int = 6,
        sensors=None,
    ):
        self.directory = os.path.expanduser(directory)
        self.env_fp = environment_fingerprint(chain, constraint)
        self.aot_enabled = aot_enabled
        self.max_entries = max(1, int(max_entries))
        self.sensors = sensors
        self._lock = threading.Lock()
        #: in-memory view of OUR entries, key -> entry dict
        self._entries: dict[str, dict] = {}
        self._last_write = 0.0
        self._boot_claimed = False
        self._export_futures: list = []
        #: observability for the never-on-the-request-path guard
        self.aot_load_attempts = 0

    # ------------------------------------------------------------ sensors

    def _count(self, name: str) -> None:
        if self.sensors is not None:
            try:
                self.sensors.counter(name).inc()
            except Exception:  # noqa: BLE001 — accounting must never raise
                pass

    # ------------------------------------------------------------- paths

    @property
    def manifest_path(self) -> str:
        return os.path.join(self.directory, "prewarm-manifest.json")

    # ----------------------------------------------------------- editing

    @staticmethod
    def _entry_key(entry: dict) -> str:
        ident = json.dumps(
            [
                entry["env_fp"],
                [entry["bucket"][f] for f in _BUCKET_FIELDS],
                sorted(entry["config"].items()),
                entry["parallel_mode"],
            ],
            default=str,
        )
        return _sha(ident)

    def note(self, shape, max_rf: int, config, *, parallel_mode: str = "single") -> None:
        """Record one (bucket, config) as active; merge + persist.

        Called on every engine build/rebind the long-lived optimizer
        performs.  New entries write through immediately; recency-only
        touches are throttled to one disk write per minute."""
        entry = {
            "env_fp": self.env_fp,
            "bucket": _bucket_dict(shape, max_rf),
            "config": _config_dict(config),
            "parallel_mode": str(parallel_mode),
            "last_used_ms": int(time.time() * 1000),
            "uses": 1,
        }
        key = self._entry_key(entry)
        with self._lock:
            known = key in self._entries
            if known:
                old = self._entries[key]
                entry["uses"] = int(old.get("uses", 0)) + 1
            self._entries[key] = entry
            now = time.monotonic()
            if known and now - self._last_write < _TOUCH_WRITE_INTERVAL_S:
                return
            self._last_write = now
            try:
                self._write_merged_locked()
            except Exception:  # noqa: BLE001 — the manifest is best-effort
                log.warning("prewarm manifest write failed", exc_info=True)

    def _read_file(self) -> dict[str, dict]:
        try:
            with open(self.manifest_path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            return {}
        if doc.get("version") != VERSION:
            return {}
        out = {}
        for e in doc.get("entries", ()):
            try:
                out[self._entry_key(e)] = e
            except Exception:  # noqa: BLE001 — one bad row must not poison the rest
                continue
        return out

    def _write_merged_locked(self) -> None:
        """Merge our in-memory entries over the on-disk file (another
        process — or another core over the same cache dir — may have
        written since) and persist atomically, bounded by max_entries in
        most-recently-used order.

        The read-modify-write is guarded by an OS file lock (flock on a
        sibling .lock file) so two PROCESSES cannot interleave their
        read and replace steps and silently drop each other's entries —
        self._lock only serializes threads of this store.  Writes are
        rare (new entries + throttled touches) and fast, so a blocking
        lock is fine; a platform without flock degrades to the unlocked
        (atomic-replace, last-merger-wins) behavior."""
        os.makedirs(self.directory, exist_ok=True)
        lock_f = None
        try:
            try:
                import fcntl

                lock_f = open(self.manifest_path + ".lock", "a")
                fcntl.flock(lock_f.fileno(), fcntl.LOCK_EX)
            except Exception:  # noqa: BLE001 — no flock: best-effort merge
                lock_f = None
            merged = self._read_file()
            for k, e in self._entries.items():
                old = merged.get(k)
                if old is not None:
                    e = dict(e)
                    e["uses"] = max(int(e.get("uses", 1)), int(old.get("uses", 1)))
                    e["last_used_ms"] = max(
                        int(e.get("last_used_ms", 0)),
                        int(old.get("last_used_ms", 0)),
                    )
                merged[k] = e
            rows = sorted(
                merged.values(), key=lambda e: -int(e.get("last_used_ms", 0))
            )[: self.max_entries]
            _atomic_write(
                self.manifest_path,
                json.dumps(
                    {"version": VERSION, "entries": rows}, indent=1
                ).encode(),
            )
        finally:
            if lock_f is not None:
                lock_f.close()  # releases the flock

    # -------------------------------------------------------------- boot

    def claim_boot_entries(self) -> list[dict]:
        """The manifest's entries for THIS environment, most recent
        first (the ACTIVE bucket leads, so it compiles before any
        speculation) — claimed at most once per store so N fleet facades
        sharing one core run ONE boot prewarm between them."""
        with self._lock:
            if self._boot_claimed:
                return []
            self._boot_claimed = True
        rows = [
            e
            for e in self._read_file().values()
            if e.get("env_fp") == self.env_fp
        ]
        rows.sort(key=lambda e: -int(e.get("last_used_ms", 0)))
        return rows[: self.max_entries]

    @staticmethod
    def entry_engine_inputs(entry: dict):
        """(ClusterShape, max_rf, OptimizerConfig, parallel_mode) from a
        manifest row; raises on malformed/foreign rows (caller skips)."""
        shape = _shape_from_dict(entry["bucket"])
        return (
            shape,
            int(entry["bucket"]["max_rf"]),
            _config_from_dict(entry["config"]),
            str(entry["parallel_mode"]),
        )

    def manifest_bucket_keys(self) -> list[str]:
        """bucket_key() strings of on-disk entries for this environment
        (the cold-start bench's gate universe)."""
        return [
            bucket_key(_shape_from_dict(e["bucket"]))
            for e in self._read_file().values()
            if e.get("env_fp") == self.env_fp
        ]

    # --------------------------------------------------------------- aot

    def aot_handle(self, shape, max_rf: int, config) -> _AotHandle | None:
        """The artifact handle for one fused program, or None when AOT
        serialization is off.  The backend PLATFORM is part of the key:
        a CPU process and a TPU deployment sharing one artifact directory
        must keep separate artifacts, not alternately reject (and now
        delete) each other's."""
        if not self.aot_enabled:
            return None
        try:
            import jax

            platform = jax.default_backend()
        except Exception:  # noqa: BLE001 — backend unavailable: no AOT
            return None
        ident = json.dumps(
            [
                self.env_fp,
                platform,
                [_bucket_dict(shape, max_rf)[f] for f in _BUCKET_FIELDS],
                sorted(_config_dict(config).items()),
            ],
            default=str,
        )
        return _AotHandle(self, _sha(ident + "|aot"), bucket_key(shape))

    def drain(self, timeout_s: float = 120.0) -> bool:
        """Wait for pending AOT exports (bench/tests; a daemon-threaded
        export must not be lost to process exit mid-write).  True when
        everything finished in time."""
        deadline = time.monotonic() + timeout_s
        with self._lock:
            futs = list(self._export_futures)
        ok = True
        for f in futs:
            try:
                f.result(timeout=max(0.0, deadline - time.monotonic()))
            except Exception:  # noqa: BLE001 — export failure is non-fatal
                ok = False
        return ok
