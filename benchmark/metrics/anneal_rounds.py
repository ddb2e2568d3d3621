"""Engine device program: the anneal rounds each window plan ran (the
timing record's `rounds`: early stop and extra rounds move it), mean."""

from benchmark.metrics._plans import mean


def read(run):
    return mean(
        p.timing["rounds"]
        for p in run.done
        if p.timing and p.timing.get("rounds") is not None
    )
