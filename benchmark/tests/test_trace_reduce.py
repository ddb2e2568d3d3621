"""The trace reduction on a small trace recorded on a TPU v5e: two calls
of a jitted `_run_fused_impl` and two of a small sum inside the
`bench.window` host event."""

import os

import numpy as np
import pytest

from benchmark import trace_reduce

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "tiny_tpu.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    from jax.profiler import ProfileData

    return trace_reduce.reduce_profile(ProfileData.from_file(FIXTURE))


def test_window_and_busy(summary):
    assert summary.window_s == pytest.approx(0.044055158, rel=1e-6)
    # union of the op intervals on the chip: the four programs, not the sleeps
    assert summary.busy_s == pytest.approx(1.1541e-05, rel=1e-3)
    assert 0 < summary.busy_s < summary.window_s


def test_programs_by_stable_name(summary):
    assert set(summary.module_s) == {"jit__run_fused_impl", "jit__lambda"}
    assert summary.module_s["jit__run_fused_impl"] == pytest.approx(1.0196e-05, rel=1e-3)


def test_gaps_cover_the_idle_time(summary):
    lengths = [(e - s) * 1e-9 for s, e in summary.gaps]
    assert lengths == sorted(lengths, reverse=True)
    # the four 10 ms sleeps are the four longest gaps
    assert all(0.009 < g < 0.02 for g in lengths[:4])
    assert sum(lengths) + summary.busy_s == pytest.approx(summary.window_s, rel=1e-6)


def test_union_merges_overlaps():
    iv = np.array([[5, 7], [0, 2], [1, 3], [7, 8], [10, 11]], np.float64)
    assert trace_reduce._union(iv).tolist() == [[0, 3], [5, 8], [10, 11]]


def test_readers_on_the_fixture(summary):
    from benchmark.metrics import anneal_ms, idle_share

    class Run:
        trace = summary
        traced = 2

    assert anneal_ms.read(Run) == pytest.approx(1.0196e-05 * 1e3 / 2, rel=1e-3)
    assert idle_share.read(Run) == pytest.approx(
        100 * (1 - 1.1541e-05 / 0.044055158), rel=1e-6
    )


def test_no_window_no_summary():
    class Empty:
        planes = []

    assert trace_reduce.reduce_profile(Empty) is None
