"""Parity tests: the on-device sanity check (models/state.py
validate_on_device, used on the optimizer's hot path to avoid bulk
device->host transfers) must agree with the host
validate() on every invariant (reference ClusterModel.sanityCheck:1081)."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from cruise_control_tpu.models.state import (
    DEVICE_CHECKS,
    validate,
    validate_on_device,
)
from cruise_control_tpu.testing.fixtures import RandomClusterSpec, random_cluster


@pytest.fixture(scope="module")
def state():
    return random_cluster(
        RandomClusterSpec(num_brokers=10, num_partitions=200), seed=1
    )


def _counts(s):
    return np.asarray(validate_on_device(s))


def test_clean_state_passes_both(state):
    assert not _counts(state).any()
    assert validate(state) == []


def test_duplicate_replica_detected(state):
    brk = np.asarray(state.replica_broker).copy()
    valid = np.asarray(state.replica_valid)
    part = np.asarray(state.replica_partition)
    idx = np.nonzero(valid)[0]
    same = idx[part[idx] == part[idx[0]]]
    brk[same[1]] = brk[same[0]]
    bad = dataclasses.replace(state, replica_broker=jnp.asarray(brk))
    assert _counts(bad)[DEVICE_CHECKS.index(
        "duplicate replica of a partition on one broker")] >= 1
    assert any("duplicate" in p for p in validate(bad, strict=False))


def test_missing_leader_detected(state):
    valid = np.asarray(state.replica_valid)
    part = np.asarray(state.replica_partition)
    lead = np.asarray(state.replica_is_leader).copy()
    idx = np.nonzero(valid)[0]
    lead[idx[part[idx] == part[idx[0]]]] = False
    bad = dataclasses.replace(state, replica_is_leader=jnp.asarray(lead))
    assert _counts(bad)[DEVICE_CHECKS.index(
        "partitions without exactly one leader")] >= 1
    assert any("leader" in p for p in validate(bad, strict=False))


def test_bad_load_detected(state):
    ll = np.asarray(state.replica_load_leader).copy()
    ll[np.nonzero(np.asarray(state.replica_valid))[0][0], 0] = -1.0
    bad = dataclasses.replace(state, replica_load_leader=jnp.asarray(ll))
    assert _counts(bad)[DEVICE_CHECKS.index(
        "non-finite or negative leader loads")] >= 1


def test_out_of_range_broker_detected(state):
    brk = np.asarray(state.replica_broker).copy()
    brk[np.nonzero(np.asarray(state.replica_valid))[0][0]] = state.shape.B + 7
    bad = dataclasses.replace(state, replica_broker=jnp.asarray(brk))
    assert _counts(bad)[DEVICE_CHECKS.index("broker ids out of range")] >= 1
    assert any("out of range" in p for p in validate(bad, strict=False))


def test_optimizer_raises_on_corrupt_result(state, monkeypatch):
    """optimize() must fail loudly when the device check flags the result."""
    from cruise_control_tpu.analyzer import GoalOptimizer, OptimizerConfig

    opt = GoalOptimizer(config=OptimizerConfig(
        num_candidates=128, leadership_candidates=32,
        steps_per_round=4, num_rounds=1))

    class _BadEngine:
        def run(self, verbose=False):
            brk = np.asarray(state.replica_broker).copy()
            valid = np.asarray(state.replica_valid)
            brk[np.nonzero(valid)[0][0]] = state.shape.B + 1
            return dataclasses.replace(
                state, replica_broker=jnp.asarray(brk)
            ), []

    monkeypatch.setattr(opt, "_engine_for", lambda *a, **k: (_BadEngine(), {}))
    # the device check flags the corrupt result, then the host validator
    # raises with the detailed per-invariant message
    with pytest.raises(ValueError, match="sanity check"):
        opt.optimize(state)
