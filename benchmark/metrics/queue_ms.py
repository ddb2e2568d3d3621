"""REST server and user-task pool: the `tasks.queue` span, the request's
wait from its submit to the pool thread that runs it, mean per plan."""

from benchmark.metrics._plans import mean, span_s


def read(run):
    return mean(
        None if (s := span_s(p, "tasks.queue")) is None else s * 1e3
        for p in run.done
    )
