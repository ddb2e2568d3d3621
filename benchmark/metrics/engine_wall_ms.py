"""Optimizer to engine: `device_s` of each plan's timing record (the host
clock from the engine's dispatch to its sync, not device busy time), mean."""

from benchmark.metrics._plans import mean


def read(run):
    return mean(
        p.timing["device_s"] * 1e3
        for p in run.done
        if p.timing and p.timing.get("device_s") is not None
    )
