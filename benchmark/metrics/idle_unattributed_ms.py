"""The device: chip-0 idle time in the traced window during which no
program span is open or the innermost open one is a wrapper (a request's
root, `analyzer.optimize`, a `device.<op>` span, `monitor.cluster_model`),
per traced plan.  Idle time under a leaf span (a stage of the model build
or of the optimizer's device body) is attributed."""

import numpy as np

WRAPPERS = ("service.", "analyzer.optimize", "device.", "monitor.cluster_model")


def read(run):
    tr = run.trace
    spans = [s for p in run.plans for s in p.spans]
    if tr is None or not tr.gaps or not run.traced or not spans:
        return None
    # trace clock -> monotonic, anchored like the harness's breakdown;
    # the gaps are disjoint, so their sorted starts and ends pair up
    to_mono = run.window_start - tr.window_start_ns * 1e-9
    gaps = np.sort(np.asarray(tr.gaps, np.float64) * 1e-9 + to_mono, axis=0)
    idle_before = np.concatenate([[0.0], np.cumsum(gaps[:, 1] - gaps[:, 0])])

    def idle_until(t):
        k = int(np.searchsorted(gaps[:, 0], t, side="right"))
        if k == 0:
            return 0.0
        s, e = gaps[k - 1]
        return float(idle_before[k - 1] + min(max(t - s, 0.0), e - s))

    lo, hi = gaps[0, 0], gaps[-1, 1]
    edges = sorted({lo, hi, *(t for _, s, e in spans for t in (s, e) if lo < t < hi)})
    unattributed = 0.0
    for a, b in zip(edges, edges[1:]):
        mid = (a + b) / 2
        open_ = [x for x in spans if x[1] <= mid <= x[2]]
        if open_ and not max(open_, key=lambda x: x[1])[0].startswith(WRAPPERS):
            continue
        unattributed += idle_until(b) - idle_until(a)
    return unattributed * 1e3 / run.traced
