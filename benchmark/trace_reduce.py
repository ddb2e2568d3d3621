"""Reduce a JAX profiler trace of one measured window to numbers.

The window is the host event named `WINDOW_EVENT` that the harness wraps
around its measured loop; device events are clipped to it.  A device
plane is one chip (`/device:TPU:<n>`); its "XLA Ops" line holds the
operations that ran, its "XLA Modules" line the programs they belong to.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

WINDOW_EVENT = "bench.window"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_MODULE_SUFFIX = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class TraceSummary:
    window_start_ns: float  # on the trace's own clock
    window_s: float
    busy_s: float | None  # union of device-op intervals, mean over chips
    module_s: dict  # program name -> device seconds (chip 0)
    gaps: list  # [(start_ns, end_ns)] idle intervals on chip 0, longest first


def find_xspace(trace_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def _union(intervals: np.ndarray) -> np.ndarray:
    """Merge [N, 2] (start, end) intervals into disjoint sorted ones."""
    if intervals.size == 0:
        return intervals.reshape(0, 2)
    iv = intervals[np.argsort(intervals[:, 0])]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.float64)


def _events(line, lo: float, hi: float):
    """(name, start, end) of a line's events, clipped to [lo, hi]."""
    for ev in line.events:
        s = float(ev.start_ns)
        e = s + float(ev.duration_ns)
        if e <= lo or s >= hi:
            continue
        yield ev.name, max(s, lo), min(e, hi)


def reduce_profile(pd) -> TraceSummary | None:
    """Summary of a `jax.profiler.ProfileData`, or None without a window."""
    window = None
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_EVENT:
                    window = (float(ev.start_ns), float(ev.start_ns) + float(ev.duration_ns))
    if window is None:
        return None
    lo, hi = window
    busy = []
    module_s: dict = {}
    gaps: list = []
    chips = sorted(
        (p for p in pd.planes if _DEVICE_PLANE.match(p.name)),
        key=lambda p: int(p.name.rsplit(":", 1)[1]),
    )
    for i, plane in enumerate(chips):
        lines = {ln.name: ln for ln in plane.lines}
        ops = lines.get("XLA Ops") or lines.get("XLA Modules")
        if ops is None:
            continue
        iv = np.asarray([(s, e) for _, s, e in _events(ops, lo, hi)], np.float64)
        merged = _union(iv.reshape(-1, 2))
        if merged.size == 0:
            continue
        busy.append(float((merged[:, 1] - merged[:, 0]).sum()) * 1e-9)
        if i == 0:
            modules = lines.get("XLA Modules")
            if modules is not None:
                for name, s, e in _events(modules, lo, hi):
                    key = _MODULE_SUFFIX.sub("", name)
                    module_s[key] = module_s.get(key, 0.0) + (e - s) * 1e-9
            edges = np.concatenate([[lo], merged.reshape(-1), [hi]]).reshape(-1, 2)
            gaps = sorted(
                ((float(s), float(e)) for s, e in edges if e > s),
                key=lambda g: g[0] - g[1],
            )
    return TraceSummary(
        window_start_ns=lo,
        window_s=(hi - lo) * 1e-9,
        busy_s=float(np.mean(busy)) if busy else None,
        module_s=module_s,
        gaps=gaps,
    )


def reduce_trace_dir(trace_dir: str) -> TraceSummary | None:
    path = find_xspace(trace_dir)
    if path is None:
        return None
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path))
