"""Monitor and model build: the `monitor.cluster_model` span, mean per plan."""

from benchmark.metrics._plans import mean, span_s


def read(run):
    return mean(
        None if (s := span_s(p, "monitor.cluster_model")) is None else s * 1e3
        for p in run.done
    )
