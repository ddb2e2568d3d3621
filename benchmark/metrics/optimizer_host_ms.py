"""Optimizer and engine: the `analyzer.optimize` span minus the
`engine.run` span inside it (dispatch to the anneal's blocking sync),
mean per plan: the optimizer's own host time around the anneal."""

from benchmark.metrics._plans import mean, span_s


def read(run):
    def host(p):
        opt, eng = span_s(p, "analyzer.optimize"), span_s(p, "engine.run")
        return None if opt is None or eng is None else (opt - eng) * 1e3

    return mean(host(p) for p in run.done)
