"""Persistent XLA compilation cache.

The engine's statics-as-arguments design already avoids recompiles WITHIN a
process (analyzer/engine.py module docstring), but a restarted service
would pay every compile again.  JAX's persistent compilation cache writes
compiled executables to disk keyed by HLO fingerprint, so a restarted
service (same shapes, same jax/XLA version) reloads them in milliseconds.

Placement: where the environment sets JAX_COMPILATION_CACHE_DIR, JAX reads
it itself and this module sets no directory of its own.  Otherwise the
cache lives at one fixed, git-ignored path inside the checkout
(DEFAULT_CACHE_DIR), never under a temp name: a later process finds its
entries only at the same path, and a machine that copies the checkout
copies nothing else.

Boot observability (config tpu.compile.cache.dir): enabling the cache
records its on-disk entry inventory; `boot_report()` later diffs against
it so the service can log, after the first proposal pass, how many
executables were loaded warm from disk (hits) vs compiled fresh (misses)
— the number ROADMAP item 2's restart SLO is built on.

Reference analog: none — a JVM has no compile step to amortize; this is a
TPU-framework concern (the proposal-precompute thread
GoalOptimizer.java:124-175 amortizes model generations, not compilation).
"""

from __future__ import annotations

import logging
import os
import threading

log = logging.getLogger(__name__)

#: the environment variable JAX itself reads the cache directory from
ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
#: the cache's home when the environment names none: inside the checkout
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)

_enabled = False
#: entry names present on disk when the cache was enabled (boot inventory)
_boot_entries: set[str] | None = None
_cache_dir: str | None = None

#: per-bucket engine-program trace accounting since process start:
#: bucket key -> {"fresh": python-traced-and-compiled, "aot": loaded from
#: a serialized jax.export artifact (no Python trace)}.  The restart-SLO
#: gate (bench.py --coldstart) asserts "fresh" stays ZERO for every
#: manifest-listed bucket on a warm-disk restart.
_trace_lock = threading.Lock()
_engine_traces: dict[str, dict[str, int]] = {}


def record_engine_trace(bucket: str, *, source: str) -> None:
    """Count one fused-engine-program acquisition for `bucket`.

    source: "fresh" (Python trace + compile — the cost AOT exists to
    kill) or "aot" (deserialized artifact; compile may still be an XLA
    disk-cache hit).  Counted independently of the persistent cache
    being enabled so tests can assert the fallback ladder."""
    with _trace_lock:
        row = _engine_traces.setdefault(bucket, {"fresh": 0, "aot": 0})
        row[source] = row.get(source, 0) + 1


def engine_trace_counts() -> dict[str, dict[str, int]]:
    with _trace_lock:
        return {k: dict(v) for k, v in _engine_traces.items()}


def reset_engine_trace_counts() -> None:
    """Test seam only — boot accounting is per-process in production."""
    with _trace_lock:
        _engine_traces.clear()


def _scan(cache_dir: str) -> tuple[set[str], int]:
    """(entry names, total bytes) currently on disk; tolerant of races.

    Prunes the `prewarm` and `blackbox` subdirectories: the boot-prewarm
    manifest + AOT artifacts (analyzer/prewarm.py) and the black-box
    dispatch spool (common/blackbox.py) live INSIDE the cache dir by
    default so they share its mount/durability, and their writes must
    not read as XLA compile-cache hits/misses in boot_report()."""
    entries: set[str] = set()
    total = 0
    try:
        for root, _dirs, files in os.walk(cache_dir):
            _dirs[:] = [d for d in _dirs if d not in ("prewarm", "blackbox")]
            for fn in files:
                path = os.path.join(root, fn)
                entries.add(os.path.relpath(path, cache_dir))
                try:
                    total += os.path.getsize(path)
                except OSError:
                    pass
    except OSError:
        pass
    return entries, total


def resolve_cache_dir(configured: str | None) -> str | None:
    """The cache directory a deployment uses: JAX_COMPILATION_CACHE_DIR
    when the environment sets it, else `configured`, where None means
    DEFAULT_CACHE_DIR and an empty string disables the cache."""
    env = os.environ.get(ENV_CACHE_DIR)
    if env:
        return env
    if configured is None:
        return DEFAULT_CACHE_DIR
    return os.path.expanduser(configured) or None


def enable_persistent_cache(cache_dir: str | None) -> str | None:
    """Idempotently turn on JAX's durable on-disk compilation cache.

    JAX_COMPILATION_CACHE_DIR, when set, wins over `cache_dir`, and then
    no directory is set in code.  Returns the directory used, or None
    when disabled (no directory from either).  Logs the boot inventory —
    how many cached executables a restart can reload instead of
    re-tracing.
    """
    global _enabled, _boot_entries, _cache_dir
    env = os.environ.get(ENV_CACHE_DIR)
    cache_dir = env or cache_dir
    if not cache_dir:
        return None
    if _enabled:
        return _cache_dir
    import jax

    cache_dir = os.path.expanduser(cache_dir)
    os.makedirs(cache_dir, exist_ok=True)
    if not env:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # persist even sub-second compiles: a cold process pays dozens of
    # 0.1-0.5s "tiny" compiles (zero-fills, reductions) that add whole
    # seconds to warmup; disk hits are ~ms
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.05)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    _enabled = True
    _cache_dir = cache_dir
    _boot_entries, total = _scan(cache_dir)
    log.info(
        "persistent XLA compile cache at %s: %d cached executables "
        "(%.1f MB) available warm at boot",
        cache_dir, len(_boot_entries), total / 1e6,
    )
    return cache_dir


def boot_report() -> dict | None:
    """Hit/miss view since boot: entries present at enable time (warm,
    reloadable = hits for re-traced programs) vs entries written since
    (fresh compiles = misses).  None when the cache is disabled."""
    if not _enabled or _cache_dir is None or _boot_entries is None:
        return None
    now, total = _scan(_cache_dir)
    return {
        "dir": _cache_dir,
        "entriesAtBoot": len(_boot_entries),
        "newCompiles": len(now - _boot_entries),
        "entries": len(now),
        "bytes": total,
        # fresh-trace vs AOT-load split per engine bucket: the number the
        # --coldstart SLO gate reads (zero "fresh" for manifest buckets
        # on a manifest+AOT restart)
        "engineTraces": engine_trace_counts(),
    }
