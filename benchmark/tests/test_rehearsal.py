"""CPU rehearsal: every cell resolves to its files by name, and each
traffic mix runs end to end through `benchmark.run.main` at a tiny size,
with the chip check steered to the CPU devices by the test itself."""

import json
import os

import numpy as np
import pytest

from benchmark import run
from benchmark.tests.conftest import PENDING_CELLS

ROOT = run.ROOT


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cell", [w["name"] for w in bench()["workloads"]])
def test_cell_resolves_by_name(cell):
    b, c, config, traffic = run.resolve(cell)
    conf = next(x for x in b["configs"] if x["name"] == c["config"])
    assert conf["file"].startswith("benchmark/configs/")
    assert config["name"] == c["config"]
    assert os.path.exists(os.path.join(ROOT, config["capacity_file"]))
    for key in ("method", "endpoint", "params", "warmup_requests", "check_plans"):
        assert key in traffic
    for m in b["per_layer"]:
        assert callable(run.metric_reader(m["name"]))
    assert set(config["limits"]) == set(__import__("benchmark.correctness").correctness.NUMBERS)


def last_json_line(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])


#: every traffic mix on file, each rehearsed on a cell that serves it
MIXES = sorted(f[: -len(".json")] for f in os.listdir(os.path.join(ROOT, "benchmark", "traffic")))


def rehearsal_cell(traffic: str) -> dict:
    """The first cell of BENCHMARK.json that serves the mix, or else its
    pending cell (conftest.PENDING_CELLS)."""
    cells = bench()["workloads"] + [c for c, _search in PENDING_CELLS.values()]
    return next(w for w in cells if w["traffic"] == traffic)


@pytest.mark.parametrize("traffic", MIXES)
@pytest.mark.parametrize("trace", [0, 1])
def test_mix_runs_end_to_end(cpu_harness, capsys, traffic, trace):
    b = bench()
    cell = rehearsal_cell(traffic)
    rc = cpu_harness.main([
        "--workload", cell["name"], "--seed", str(2**31 + 12345),
        "--seconds", "2", "--trace", str(trace),
    ])
    assert rc == 0
    captured = capsys.readouterr()
    line = last_json_line(captured.out)
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert list(line)[-1] == "checks"
    assert line["correct"] is True
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert line["device"]["count"] == 1
    names = set(line["metrics"])
    if trace:
        expect = {m["name"] for m in b["per_layer"]}
        # the device-trace readers find nothing to read on the CPU
        assert {"rest_ms", "model_build_ms", "window_compiles"} <= names <= expect
        assert line["metrics"]["window_compiles"]["value"] == 0
    else:
        assert names == {m["name"] for m in b["end_to_end"]}
    err = captured.err.strip().splitlines()
    assert all(ln.startswith("check ") for ln in err[-len(line["checks"]):])


def test_every_mix_is_rehearsed():
    served = {w["traffic"] for w in bench()["workloads"]}
    assert served <= set(MIXES) and "remove_broker" in MIXES
    assert all(rehearsal_cell(m)["traffic"] == m for m in MIXES)


@pytest.mark.parametrize(
    "config, count, per_rack", [("north2600", 26, [2] * 13), ("small50", 1, [1]), ("tiny", 1, [1])]
)
def test_removal_mix_names_its_brokers(config, count, per_rack):
    """Every 100th broker of the deployment: 26 of north2600's, on 13
    racks two each; broker 0 alone of small50's and of a tiny cluster."""
    from benchmark.deployment import make_deployment
    from benchmark.tests.conftest import tiny

    traffic = run.load_json("benchmark/traffic/remove_broker.json")
    conf = run.load_json(f"benchmark/configs/{'small50' if config == 'tiny' else config}.json")
    conf = tiny(conf) if config == "tiny" else conf
    n = conf["cluster"]["brokers"]
    removed = run.removed_brokers(traffic, n)
    assert removed == [b for b in range(n) if b % 100 == 0] and len(removed) == count
    assert run.request_params(traffic, removed) == {
        **traffic["params"], "brokerid": ",".join(map(str, removed)),
    }
    racks = make_deployment(conf, 1, ROOT).rack_of_broker[removed]
    assert sorted(np.bincount(racks)[np.unique(racks)]) == per_rack
    proposals = run.load_json("benchmark/traffic/proposals.json")
    assert run.removed_brokers(proposals, n) == []
    assert run.request_params(proposals, []) == proposals["params"]
