"""REST server and user-task pool: the client's wall per plan minus the
request's `service.<endpoint>` root span, mean over the window's plans."""

from benchmark.metrics._plans import mean, span_s


def read(run):
    def rest(p):
        served = span_s(p, "service.")
        return None if served is None else (p.t1 - p.t0 - served) * 1e3

    return mean(rest(p) for p in run.done)
