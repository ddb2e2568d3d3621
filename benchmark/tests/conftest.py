"""The benchmark's own tests run on the CPU at tiny sizes:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q
"""

import copy
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

#: a cluster a CPU test can hold; the random placement still breaks racks
TINY_CLUSTER = {
    "brokers": 12, "racks": 4, "topics": 4, "partitions_per_topic": 30,
    "replication_factor": 3,
}
TINY_SEARCH = {
    "tpu.num.candidates": 128, "tpu.leadership.candidates": 32,
    "tpu.steps.per.round": 16, "tpu.num.rounds": 2,
}


def tiny(config: dict) -> dict:
    c = copy.deepcopy(config)
    c["cluster"] = dict(TINY_CLUSTER)
    c["service"].update(TINY_SEARCH)
    return c


@pytest.fixture
def cpu_harness(monkeypatch):
    """benchmark.run with its chip check steered to the CPU devices and
    every configuration cut to TINY_CLUSTER."""
    import jax

    from benchmark import run

    resolve = run.resolve

    def tiny_resolve(workload):
        bench, cell, config, traffic = resolve(workload)
        return bench, cell, tiny(config), traffic

    monkeypatch.setattr(run, "resolve", tiny_resolve)
    monkeypatch.setattr(run, "require_chip", lambda n: jax.devices()[:n])
    return run
