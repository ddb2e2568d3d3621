"""The engine's fused anneal program: its device time per plan in the
traced window.  Traced fresh it is `jit__run_fused_impl`; served from the
boot-prewarm AOT artifact (analyzer/prewarm.py, the only program the
repo exports) it runs as `jit_call`."""

PROGRAMS = ("jit__run_fused_impl", "jit_call")


def read(run):
    tr = run.trace
    if tr is None or not run.traced:
        return None
    s = sum(v for name, v in tr.module_s.items() if name in PROGRAMS)
    return s * 1e3 / run.traced if s > 0 else None
