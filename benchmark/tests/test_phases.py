"""The trace's op metadata read (`benchmark/phases.py`) on the v5e trace
recorded for test_trace_reduce.py, the self-time rule, and the readers of
the program's stage spans, round counter and unattributed idle time."""

import os

import pytest

from benchmark import phases
from benchmark.metrics import (
    aggregate_ms,
    anneal_rounds,
    idle_unattributed_ms,
    optimizer_host_ms,
    queue_ms,
)
from benchmark.run import Plan, Run
from benchmark.trace_reduce import TraceSummary

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "tiny_tpu.xplane.pb")
NEW_READERS = (aggregate_ms, anneal_rounds, idle_unattributed_ms,
               optimizer_host_ms, queue_ms)


@pytest.fixture(scope="module")
def xspace():
    with open(FIXTURE, "rb") as f:
        return f.read()


def test_metadata_read_finds_the_tf_op(xspace):
    meta = phases.op_metadata(xspace, "/device:TPU:0")
    ops = {tf_op for (_, program), tf_op in meta.items() if program}
    assert "jit(_run_fused_impl)/dot_general" in ops
    # joined within a program: the program id rides each key
    assert {p for _, p in meta} == {11281733785093341644, 2073148429842210145}
    assert phases.op_metadata(xspace, "/device:TPU:9") == {}


def test_read_phases_on_a_trace_without_scopes(xspace):
    from jax.profiler import ProfileData

    out = phases.read_phases(ProfileData.from_serialized_xspace(xspace), xspace, 2)
    assert out["programs"] == ["jit__lambda", "jit__run_fused_impl"]
    # the fixture's program predates the scopes: all of it is unscoped
    assert all(v == 0 for v in out["phase_ms"].values())
    assert out["anneal_ms"] == pytest.approx(1.0196e-05 * 1e3 / 2, rel=1e-3)
    assert 0 < out["unscoped_ms"] <= out["anneal_ms"]


def test_self_time_subtracts_nested_events():
    # a loop [0, 10) holding two ops, the second holding one of its own,
    # then a lone op after the loop
    events = [(0, 10), (1, 4), (5, 9), (6, 7), (12, 13)]
    assert phases.self_times(events) == [3, 3, 3, 1, 1]
    assert phases.self_times([]) == []


def test_phase_is_the_innermost_scope():
    tf_op = "jit(_run_fused_impl)/while/body/anneal_score/anneal_sample/gather"
    assert phases.phase_of(tf_op) == "anneal_sample"
    assert phases.phase_of("jit(_run_fused_impl)/while/add") is None


def _run(plans, trace=None, traced=0):
    return Run(window_start=100.0, plans=plans, setup_compiles=0,
               window_compiles=0, peak_bytes=None, trace=trace, traced=traced)


def test_new_readers_find_nothing_without_spans_or_trace():
    bare = _run([Plan(100.0, 101.0, 200, "t")])
    for reader in NEW_READERS:
        assert reader.read(bare) is None, reader.__name__
    assert idle_unattributed_ms.read(_run([])) is None


def test_span_and_counter_readers():
    p = Plan(100.0, 110.0, 200, "t", timing={"rounds": 9})
    p.spans = [
        ("tasks.queue", 100.01, 100.02),
        ("service.proposals", 100.02, 109.9),
        ("monitor.cluster_model", 100.1, 100.8),
        ("monitor.aggregate", 100.2, 100.6),
        ("analyzer.optimize", 101.0, 109.0),
        ("engine.run", 101.5, 108.5),
    ]
    q = Plan(110.0, 120.0, 200, "u", timing={"rounds": 8})
    q.spans = [(n, s + 10, e + 10) for n, s, e in p.spans]
    run = _run([p, q])
    assert anneal_rounds.read(run) == 8.5
    assert optimizer_host_ms.read(run) == pytest.approx(1000.0)
    assert aggregate_ms.read(run) == pytest.approx(400.0)
    assert queue_ms.read(run) == pytest.approx(10.0)


def test_idle_unattributed_counts_idle_under_wrappers_only():
    # window from monotonic 100.0 (trace clock 0); three idle gaps
    gaps = [(0.5e9, 1.5e9), (2.0e9, 2.5e9), (4.0e9, 4.1e9)]
    trace = TraceSummary(window_start_ns=0.0, window_s=5.0, busy_s=3.4,
                         module_s={}, gaps=gaps)
    p = Plan(100.0, 105.0, 200, "t")
    p.spans = [
        ("service.proposals", 100.2, 104.9),
        ("monitor.cluster_model", 100.4, 101.6),
        ("monitor.aggregate", 100.6, 101.2),  # a leaf: 0.6 s attributed
        ("analyzer.optimize", 101.8, 104.5),
        ("device.optimize", 101.9, 104.4),
        ("analyzer.engine", 102.0, 102.3),  # a leaf: 0.3 s attributed
    ]
    # gap 1: 1.0 s, 0.6 under the leaf; gap 2: 0.5 s, 0.3 under the leaf;
    # gap 3: 0.1 s under device.optimize: 0.4 + 0.2 + 0.1 = 0.7 s, one plan
    run = _run([p], trace=trace, traced=1)
    assert idle_unattributed_ms.read(run) == pytest.approx(700.0)
