"""Device time of the anneal's phases, read from a profiler trace.

    python3 -m benchmark.phases --workload <cell> --seed <n> --seconds <s>

makes one `--trace 1` run of a cell exactly as `benchmark.run` does and
prints its result line, then reads the profiler trace of the traced plans
before it is deleted and prints one more JSON line: for each anneal phase
(`analyzer/engine.py` `ANNEAL_PHASES`, the innermost phase scope in each
op's `tf_op`) the device self time per traced plan, the anneal program's
device time beside it (what `anneal_ms` reads), one `tf_op` per phase as
the window's programs carry it, and the idle gaps of 10 ms or more on
chip 0, each with the innermost annotation open on the trace's host plane
at its middle (program spans are annotations there).

`jax.profiler.ProfileData` gives an op event's name and times, not the
event-metadata stats where a device trace keeps each op's `tf_op`.  This
module reads the device planes' `event_metadata` and `stat_metadata` from
the XSpace wire format itself, skipping each plane's `lines` by their
length prefix, and joins ops to metadata by name within their program.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

from benchmark import trace_reduce

#: the anneal's phase scopes (analyzer/engine.py ANNEAL_PHASES), here as
#: data: a trace of any version of the program is read the same way
PHASES = (
    "anneal_sample", "anneal_score", "anneal_accept", "anneal_refresh",
    "anneal_eval",
)
#: programs of the anneal: fresh, AOT-served and segmented
ANNEAL_PROGRAMS = ("jit__run_fused_impl", "jit__seg_slice_impl")
#: a program's name with its id, as the "XLA Modules" line shows it
_MODULE = re.compile(r"^(.*)\((\d+)\)$")

# XSpace / XPlane / XEventMetadata / XStat field numbers
# (tsl/profiler/protobuf/xplane.proto)
_SPACE_PLANES = 1
_PLANE_NAME, _PLANE_EVENT_METADATA, _PLANE_STAT_METADATA = 2, 4, 5
_META_NAME, _META_STATS = 2, 5
_STAT_METADATA_ID, _STAT_UINT64, _STAT_INT64, _STAT_STR, _STAT_REF = 1, 3, 4, 5, 7


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, i


def _fields(buf: bytes, i: int = 0, end: int | None = None):
    """(field number, value) of one message: a varint as an int, a
    length-delimited field as its (start, end) span, not copied."""
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value = (i, i + n)
            i += n
        elif wire == 1:
            value = (i, i + 8)
            i += 8
        elif wire == 5:
            value = (i, i + 4)
            i += 4
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield key >> 3, value


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_entry(buf: bytes, span) -> tuple[int, tuple | None]:
    key, value = 0, None
    for f, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def op_metadata(buf: bytes, plane_name: str) -> dict:
    """{(op name, program id): tf_op} of one plane of an XSpace; the
    `tf_op` stat loses its ":<type>" suffix ("jit(f)/dot_general")."""
    for f, plane in _fields(buf):
        if f != _SPACE_PLANES:
            continue
        name = None
        metas, stat_names = [], {}
        for pf, v in _fields(buf, *plane):
            if pf == _PLANE_NAME:
                name = _text(buf, v)
                if name != plane_name:
                    break
            elif pf == _PLANE_EVENT_METADATA:
                metas.append(v)
            elif pf == _PLANE_STAT_METADATA:
                key, meta = _map_entry(buf, v)
                stat_names[key] = next(
                    (_text(buf, s) for sf, s in _fields(buf, *meta) if sf == 2), ""
                )
        if name != plane_name:
            continue
        by_name = {v: k for k, v in stat_names.items()}
        tf_op_id, program_id = by_name.get("tf_op"), by_name.get("program_id")
        out = {}
        for entry in metas:
            _, meta = _map_entry(buf, entry)
            op = tf_op = program = None
            for mf, v in _fields(buf, *meta):
                if mf == _META_NAME:
                    op = _text(buf, v)
                elif mf == _META_STATS:
                    stat = dict(_fields(buf, *v))
                    sid = stat.get(_STAT_METADATA_ID)
                    if sid == tf_op_id:
                        if _STAT_STR in stat:
                            tf_op = _text(buf, stat[_STAT_STR])
                        elif _STAT_REF in stat:
                            tf_op = stat_names.get(stat[_STAT_REF])
                    elif sid == program_id:
                        program = stat.get(_STAT_UINT64, stat.get(_STAT_INT64))
            if op is not None and tf_op:
                out.setdefault((op, program), tf_op.rsplit(":", 1)[0])
        return out
    return {}


def self_times(events: list) -> list:
    """Self time of each (start, end) event of one line: an event nested
    inside another is subtracted from the one that directly holds it."""
    order = sorted(range(len(events)), key=lambda k: (events[k][0], -events[k][1]))
    own = [e - s for s, e in events]
    stack: list = []
    for k in order:
        s, e = events[k]
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(k)
    return own


def phase_of(tf_op: str) -> str | None:
    """The innermost anneal phase scope of an op's name stack."""
    inner = None
    for part in tf_op.split("/"):
        if part in PHASES:
            inner = part
    return inner


def _window(pd) -> tuple[float, float] | None:
    for plane in pd.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == trace_reduce.WINDOW_EVENT:
                        s = float(ev.start_ns)
                        return s, s + float(ev.duration_ns)
    return None


def read_phases(pd, buf: bytes, traced: int) -> dict | None:
    """Per traced plan: device self milliseconds of each phase's ops in
    the anneal programs on chip 0, the anneal programs' device time, the
    programs seen, and one tf_op per phase."""
    window = _window(pd)
    plane = next((p for p in pd.planes if p.name == "/device:TPU:0"), None)
    if window is None or plane is None or traced < 1:
        return None
    lo, hi = window
    lines = {ln.name: ln for ln in plane.lines}
    if "XLA Ops" not in lines or "XLA Modules" not in lines:
        return None
    programs = []  # (start, end, name, id)
    for ev in lines["XLA Modules"].events:
        m = _MODULE.match(ev.name)
        s = float(ev.start_ns)
        if m and lo <= s < hi:
            programs.append((s, s + float(ev.duration_ns), m.group(1), int(m.group(2))))
    programs.sort()
    ops = [
        (ev.name, float(ev.start_ns), float(ev.start_ns) + float(ev.duration_ns))
        for ev in lines["XLA Ops"].events
        if lo <= float(ev.start_ns) < hi
    ]
    meta = op_metadata(buf, plane.name)
    own = self_times([(s, e) for _, s, e in ops])
    phase_ns = dict.fromkeys(PHASES, 0.0)
    example: dict = {}
    unscoped_ns = 0.0
    j = 0
    for (name, s, _), t in sorted(zip(ops, own), key=lambda x: x[0][1]):
        while j < len(programs) and programs[j][1] < s:
            j += 1
        if j == len(programs) or programs[j][0] > s:
            continue
        _, _, prog, pid = programs[j]
        if prog not in ANNEAL_PROGRAMS:
            continue
        tf_op = meta.get((name, pid), "")
        ph = phase_of(tf_op)
        if ph is None:
            unscoped_ns += t
            continue
        phase_ns[ph] += t
        example.setdefault(ph, tf_op)
    anneal_ns = sum(e - s for s, e, p, _ in programs if p in ANNEAL_PROGRAMS)
    per_plan = 1e-6 / traced
    return {
        "phase_ms": {p: v * per_plan for p, v in phase_ns.items()},
        "unscoped_ms": unscoped_ns * per_plan,
        "anneal_ms": anneal_ns * per_plan,
        "phase_share": sum(phase_ns.values()) / anneal_ns if anneal_ns else None,
        "programs": sorted({p for _, _, p, _ in programs}),
        "tf_op": example,
    }


def idle_gaps(pd, summary, min_s: float = 0.010) -> list:
    """[label, seconds] of each idle gap on chip 0 of at least `min_s`,
    the label the innermost host-plane annotation open at its middle."""
    annotations = [
        (float(ev.start_ns), float(ev.start_ns) + float(ev.duration_ns), ev.name)
        for plane in pd.planes
        if plane.name.startswith("/host")
        for line in plane.lines
        for ev in line.events
        if ev.name != trace_reduce.WINDOW_EVENT and not ev.name.startswith(("jit", "Pjit"))
    ]
    out = []
    for s, e in summary.gaps:
        if (e - s) * 1e-9 < min_s:
            break  # longest first
        mid = (s + e) / 2
        open_ = [a for a in annotations if a[0] <= mid <= a[1]]
        label = max(open_, key=lambda a: a[0])[2] if open_ else None
        out.append([label, (e - s) * 1e-9])
    return out


def read_trace_dir(trace_dir: str, traced: int) -> dict | None:
    path = trace_reduce.find_xspace(trace_dir)
    if path is None:
        return None
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        buf = f.read()
    pd = ProfileData.from_serialized_xspace(buf)
    out = read_phases(pd, buf, traced)
    summary = trace_reduce.reduce_profile(pd)
    if out is not None and summary is not None:
        out["idle_gaps"] = idle_gaps(pd, summary)
    return out


def main(argv=None) -> int:
    from benchmark import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    traffic = run.resolve(args.workload)[3]
    kept: dict = {}
    reduce = trace_reduce.reduce_trace_dir

    def reduce_and_read(trace_dir):
        # the phases first: the harness deletes the trace once reduced
        kept["phases"] = read_trace_dir(trace_dir, traffic["traced_plans"])
        return reduce(trace_dir)

    trace_reduce.reduce_trace_dir = reduce_and_read
    code = run.main([
        "--workload", args.workload, "--seed", str(args.seed),
        "--seconds", str(args.seconds), "--trace", "1",
    ])
    print(json.dumps({"phases": kept.get("phases")}), flush=True)
    return code


if __name__ == "__main__":
    try:
        code = main()
    except Exception:  # noqa: BLE001 — report, then exit nonzero below
        import traceback

        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)  # the service's daemon threads, as in benchmark.run
