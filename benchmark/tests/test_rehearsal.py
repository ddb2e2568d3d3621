"""CPU rehearsal: every cell resolves to its files by name, and each
traffic mix runs end to end through `benchmark.run.main` at a tiny size,
with the chip check steered to the CPU devices by the test itself."""

import json
import os

import pytest

from benchmark import run

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


@pytest.mark.parametrize("traffic", sorted({w["traffic"] for w in bench()["workloads"]}))
@pytest.mark.parametrize("trace", [0, 1])
def test_mix_runs_end_to_end(cpu_harness, capsys, traffic, trace):
    b = bench()
    cell = next(w for w in b["workloads"] if w["traffic"] == traffic)
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
