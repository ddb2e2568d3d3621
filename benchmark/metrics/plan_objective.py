"""Plan quality: the reference's objective of each checked plan applied to
the initial placement (lower is better), mean over the plans checked."""

from benchmark.metrics._plans import mean


def read(run):
    return mean(r["_objective"] for r in run.readings)
