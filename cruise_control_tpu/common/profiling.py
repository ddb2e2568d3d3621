"""Device profiling hooks: memory/buffer gauges + opt-in jax.profiler dump.

Two observability gaps the trace layer alone does not close:

  * **Where is the HBM going?**  Engine caches, shape-bucket padding and
    scenario batches all hold device buffers; `register_device_gauges`
    publishes per-backend memory-in-use / limit / live-buffer-count gauges
    (aggregates under fixed sensor names, per-device detail as a labeled
    collector) so `/metrics` answers it continuously.
  * **What is the device DOING during a slow run?**  The span layer times
    stages; `profiler_trace` (config `tpu.profiler.*`) wraps one engine
    run in a `jax.profiler.trace` dump — the XLA-level view (op timeline,
    fusion, transfers) an operator attaches TensorBoard/XProf to.  Opt-in:
    profiler dumps cost real time and disk, so the default is off.

Everything here degrades to no-ops on backends without the introspection
APIs (CPU `memory_stats()` returns None; Gauge callbacks that raise read
as NaN), so the gauges are safe to register unconditionally.
"""

from __future__ import annotations

import contextlib


def _memory_stat(field: str) -> float:
    """Sum a memory_stats field across local devices; 0.0 where a backend
    exposes no stats (host CPU) — the per-device collector distinguishes."""
    import jax

    total = 0.0
    for d in jax.local_devices():
        stats = d.memory_stats() if hasattr(d, "memory_stats") else None
        if stats:
            total += float(stats.get(field, 0) or 0)
    return total


def _live_buffer_count() -> float:
    import jax

    return float(len(jax.live_arrays()))


def _per_device_memory() -> list[tuple[dict, float]]:
    import jax

    out = []
    for d in jax.local_devices():
        stats = d.memory_stats() if hasattr(d, "memory_stats") else None
        if stats:
            out.append(
                (
                    {"device": str(d.id), "platform": d.platform},
                    float(stats.get("bytes_in_use", 0) or 0),
                )
            )
    return out


def per_device_live_bytes() -> dict:
    """Live bytes RESIDENT per device right now, keyed by device id.

    Prefers the backend allocator's ``bytes_in_use`` (real HBM, includes
    XLA scratch); backends without ``memory_stats`` (host CPU, including
    the virtual ``--xla_force_host_platform_device_count`` mesh the bench
    and tests run on) fall back to summing each live array's addressable
    shard bytes onto the shard's device — exactly the model/carry
    footprint the sharded-model mode claims to cut, minus scratch."""
    import jax

    out: dict = {}
    stats_seen = False
    for d in jax.local_devices():
        stats = d.memory_stats() if hasattr(d, "memory_stats") else None
        if stats and stats.get("bytes_in_use") is not None:
            stats_seen = True
            out[d.id] = float(stats.get("bytes_in_use", 0) or 0)
    if stats_seen:
        return out
    for arr in jax.live_arrays():
        try:
            for shard in arr.addressable_shards:
                out[shard.device.id] = out.get(shard.device.id, 0.0) + float(
                    shard.data.nbytes
                )
        except Exception:  # noqa: BLE001 — deleted/donated buffers mid-walk
            continue
    return out


class PeakLiveBytesTracker:
    """Max-over-time per-(bucket, device) live-bytes attribution.

    `record(bucket)` samples `per_device_live_bytes` and maxes each
    device's reading into that shape bucket's cell; `values()` is the
    labeled-collector callback shape the sensor registry expects.  The
    optimizer records after every engine run, so the bench's "per-device
    HBM headroom at the north-star shape" claim is a scraped
    `/metrics` series (`tpu.device.peak-live-bytes-by-bucket`), not a
    one-off print."""

    def __init__(self):
        self._peaks: dict = {}

    def record(self, bucket: str) -> None:
        try:
            sample = per_device_live_bytes()
        except Exception:  # noqa: BLE001 — observability never fails a run
            return
        for dev, val in sample.items():
            key = (str(bucket), str(dev))
            if val > self._peaks.get(key, 0.0):
                self._peaks[key] = val

    def values(self) -> list:
        return [
            ({"bucket": b, "device": d}, v) for (b, d), v in sorted(self._peaks.items())
        ]


def register_device_gauges(sensors) -> "PeakLiveBytesTracker":
    """Install the device-memory/buffer sensor surface on a registry.

    Names are fixed (documented in docs/sensors.md; the drift test walks
    them); per-device breakdown rides collector LABELS, never dynamic
    sensor names.  Returns the peak tracker so the optimizer can feed it
    per-bucket samples."""
    sensors.gauge("tpu.device.memory-in-use-bytes", lambda: _memory_stat("bytes_in_use"))
    sensors.gauge("tpu.device.memory-limit-bytes", lambda: _memory_stat("bytes_limit"))
    sensors.gauge("tpu.device.live-buffers", _live_buffer_count)
    sensors.collector("tpu.device.memory-by-device", _per_device_memory)
    tracker = PeakLiveBytesTracker()
    sensors.collector("tpu.device.peak-live-bytes-by-bucket", tracker.values)
    return tracker


@contextlib.contextmanager
def profiler_trace(dump_dir: str | None):
    """Wrap a block in a jax.profiler trace dump when `dump_dir` is set
    (config tpu.profiler.enabled + tpu.profiler.dump.dir); no-op otherwise.

    A profiler that fails to start (unsupported backend, unwritable dir)
    must never fail the optimization it was meant to observe — the error
    is swallowed and the block runs unprofiled."""
    if not dump_dir:
        yield
        return
    import os

    import jax

    try:
        # a directory the dump cannot be written to fails here, before a
        # profiler session starts: a session whose dump failed would stay
        # open and refuse every later trace in the process
        os.makedirs(dump_dir, exist_ok=True)
        ctx = jax.profiler.trace(dump_dir)
        ctx.__enter__()
    except Exception:  # noqa: BLE001 — profiling is best-effort
        yield
        return
    try:
        yield
    finally:
        try:
            ctx.__exit__(None, None, None)
        except Exception:  # noqa: BLE001
            pass
