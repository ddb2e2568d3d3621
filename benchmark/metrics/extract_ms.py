"""Proposal extraction: `host_extract_s` of each plan's timing record, mean."""

from benchmark.metrics._plans import mean


def read(run):
    return mean(
        p.timing["host_extract_s"] * 1e3
        for p in run.done
        if p.timing and p.timing.get("host_extract_s") is not None
    )
