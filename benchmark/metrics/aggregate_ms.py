"""Monitor and model build: the `monitor.aggregate` span (the metric
windows' aggregation inside `monitor.cluster_model`), mean per plan."""

from benchmark.metrics._plans import mean, span_s


def read(run):
    return mean(
        None if (s := span_s(p, "monitor.aggregate")) is None else s * 1e3
        for p in run.done
    )
