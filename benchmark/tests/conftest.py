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
#: cells of traffic mixes that no cell of BENCHMARK.json serves yet (PERF.md
#: section 7 says why each waits), rehearsed here at the tiny size, each with
#: the search it needs: at 2 rounds the tiny removal plan still violates
#: RackAwareGoal, the search stopping short as at north2600 (PERF.md
#: section 7, item 1); with 4 it meets every goal
PENDING_CELLS = {
    "north2600.remove_broker": (
        {"name": "north2600.remove_broker", "config": "north2600",
         "traffic": "remove_broker", "chips": 1},
        {"tpu.num.rounds": 4},
    ),
}


def tiny(config: dict, search: dict | None = None) -> dict:
    c = copy.deepcopy(config)
    c["cluster"] = dict(TINY_CLUSTER)
    c["service"].update({**TINY_SEARCH, **(search or {})})
    return c


@pytest.fixture
def cpu_harness(monkeypatch):
    """benchmark.run with its chip check steered to the CPU devices, every
    configuration cut to TINY_CLUSTER, and PENDING_CELLS resolved by name."""
    import jax

    from benchmark import run

    resolve = run.resolve

    def tiny_resolve(workload):
        if workload not in PENDING_CELLS:
            bench, cell, config, traffic = resolve(workload)
            return bench, cell, tiny(config), traffic
        cell, search = PENDING_CELLS[workload]
        bench = run.load_json("BENCHMARK.json")
        config, traffic = run.cell_files(bench, cell)
        return bench, cell, tiny(config, search), traffic

    monkeypatch.setattr(run, "resolve", tiny_resolve)
    monkeypatch.setattr(run, "require_chip", lambda n: jax.devices()[:n])
    return run
