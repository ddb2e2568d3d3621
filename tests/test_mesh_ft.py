"""Mesh fault tolerance (parallel/ft.py + the watchdog's mesh seams).

The layer under test turns a chip dying mid-anneal into a detected,
bounded, RESUMABLE event instead of a bare rc=124: per-device probe
fan-out attributes a failed mesh dispatch to the specific chip
(DEVICE_LOST / COLLECTIVE_STALL), slice boundaries capture host-side
carry checkpoints, and the optimizer's width ladder rebuilds the mesh
over the survivors and resumes the remaining rounds byte-identically.
The acceptance pin at the bottom drives the whole story through a
supervised GoalOptimizer with an injected mid-anneal device loss —
the in-process twin of `bench.py --mesh-chaos`.
"""

import os
import signal
import subprocess
import sys
import textwrap
import threading
import time
import types

import pytest

import jax
import numpy as np

from cruise_control_tpu.analyzer import DEFAULT_CHAIN, OptimizerConfig
from cruise_control_tpu.analyzer.engine import (
    SegmentContext,
    segmented_execution,
)
from cruise_control_tpu.analyzer.optimizer import GoalOptimizer
from cruise_control_tpu.common.blackbox import (
    RECORDER,
    read_spool,
    spool_verdict,
)
from cruise_control_tpu.common.device_watchdog import (
    BreakerState,
    CircuitBreaker,
    CollectiveStallError,
    DeviceDegradedError,
    DeviceLostError,
    DeviceSupervisor,
    FailureClass,
    MESH_FAILURE_CLASSES,
    classify_failure,
    device_op,
    probe_devices,
)
from cruise_control_tpu.common.dispatch import dispatch_meter
from cruise_control_tpu.common.sensors import SensorRegistry
from cruise_control_tpu.parallel.ft import CheckpointSlot, MeshFtController
from cruise_control_tpu.parallel.sharded import ShardedEngine, model_mesh
from cruise_control_tpu.testing import faults
from cruise_control_tpu.testing.fixtures import RandomClusterSpec, random_cluster

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _graft_entry():
    sys.path.insert(0, REPO)
    try:
        import __graft_entry__ as g
    finally:
        sys.path.remove(REPO)
    return g


#: early stop disabled so the slice count is deterministic — the chaos
#: pins below inject at a specific slice boundary
CFG = OptimizerConfig(
    num_candidates=60,
    leadership_candidates=16,
    swap_candidates=8,
    steps_per_round=6,
    num_rounds=4,
    early_stop_violations=-1.0,
    seed=3,
)


def _state(seed=21, brokers=12, parts=160):
    return random_cluster(
        RandomClusterSpec(num_brokers=brokers, num_partitions=parts, skew=1.5),
        seed=seed,
    )


@pytest.fixture(autouse=True)
def _reset_recorder():
    yield
    RECORDER.configure(None)


@pytest.fixture(scope="module")
def mesh_state():
    return _state()


@pytest.fixture(scope="module")
def se8(mesh_state):
    return ShardedEngine(
        mesh_state, DEFAULT_CHAIN,
        mesh=model_mesh(np.asarray(jax.devices()[:8])), config=CFG,
    )


def _placements(state):
    return tuple(
        np.asarray(getattr(state, f))
        for f in ("replica_broker", "replica_is_leader", "replica_disk")
    )


def _same(a, b) -> bool:
    return all(bool((x == y).all()) for x, y in zip(_placements(a), _placements(b)))


# the fault harness's device-op seam, without compiling anything: a fake
# mesh receiver is enough for _dispatch_device_ids / _blackbox_fields
class FakeMeshEngine:
    def __init__(self, devices):
        self.mesh = types.SimpleNamespace(devices=np.asarray(devices, dtype=object))


@device_op("mesh.run")
def fake_mesh_run(engine):
    return "ran"


# ------------------------------------------------------- classification


def test_mesh_failure_taxonomy():
    assert MESH_FAILURE_CLASSES == {
        FailureClass.DEVICE_LOST, FailureClass.COLLECTIVE_STALL,
    }
    assert classify_failure(DeviceLostError("gone", (3,))) is FailureClass.DEVICE_LOST
    assert (
        classify_failure(CollectiveStallError("wedged", (1, 2)))
        is FailureClass.COLLECTIVE_STALL
    )
    # the backend's textual shape (and the fault harness's lookalike)
    assert (
        classify_failure(faults.device_lost_error("mesh.run", 5))
        is FailureClass.DEVICE_LOST
    )
    # DEVICE_LOST markers win over the generic runtime markers that would
    # otherwise retry forever against a chip that no longer exists
    assert (
        classify_failure(RuntimeError("INTERNAL: XLA: device coredump"))
        is FailureClass.DEVICE_LOST
    )
    # HANG / TRANSIENT are NOT mesh classes: no suspect chip to exclude
    assert FailureClass.HANG not in MESH_FAILURE_CLASSES
    assert FailureClass.TRANSIENT not in MESH_FAILURE_CLASSES


def test_device_loss_injector_latches_probes():
    """Once the scheduled loss fires, the chip's attribution probe fails
    too while every other chip's passes — exactly the asymmetry the
    classifier attributes on."""
    devs = jax.devices()
    with faults.device_loss(2, ops=("mesh.run",)) as log:
        # a dispatch NOT involving the chip falls through untouched
        assert fake_mesh_run(FakeMeshEngine(devs[4:])) == "ran"
        # probes before the latch: every chip healthy
        assert all(d is None for d in probe_devices(devs, timeout_s=10.0).values())
        with pytest.raises(RuntimeError, match="DEVICE_LOST"):
            fake_mesh_run(FakeMeshEngine(devs))
        results = probe_devices(devs, timeout_s=10.0)
        assert results[2] is not None and "DEVICE_LOST" in results[2]
        assert all(d is None for i, d in results.items() if i != 2)
    assert log.fired["mesh.run"] == 1 and log.fired["device.probe"] >= 1
    # nest-safe: the hook is restored on exit
    assert fake_mesh_run(FakeMeshEngine(devs)) == "ran"


def test_supervisor_attributes_device_loss_and_spares_main_breaker():
    """A mesh dispatch failure under `call(breaker=..., mesh_devices=...)`
    names the suspect chip via the probe fan-out, opens only the
    caller-owned per-width breaker, and records per-device health."""
    sup = DeviceSupervisor(
        op_timeout_s=30.0, max_retries=0, probe_timeout_s=10.0,
    )
    width_brk = CircuitBreaker(failure_threshold=1, probe_interval_s=60.0)
    devs = jax.devices()
    with faults.device_loss(5, ops=("mesh.run",)):
        with pytest.raises(DeviceDegradedError) as ei:
            sup.call(
                lambda: fake_mesh_run(FakeMeshEngine(devs)),
                op="optimize", breaker=width_brk, mesh_devices=devs,
            )
    assert ei.value.failure_class is FailureClass.DEVICE_LOST
    assert ei.value.device_ids == (5,)
    assert width_brk.state is BreakerState.OPEN
    # the single-device breaker never heard about it
    assert sup.breaker.state is BreakerState.CLOSED and sup.available()
    health = sup.device_health()
    assert health[5]["healthy"] is False and health[0]["healthy"] is True


def test_supervisor_upgrades_subset_hang_to_collective_stall(tmp_path):
    """A hung multi-device dispatch with a strict SUBSET of the mesh
    unresponsive becomes COLLECTIVE_STALL naming the wedged chip — and
    the black-box trail left behind carries the mesh width in flight,
    the record the SIGKILL/timeout verdicts replay to."""
    RECORDER.configure(str(tmp_path / "spool-1.jsonl"))
    state = _state(brokers=8, parts=64)
    engine = ShardedEngine(
        state, DEFAULT_CHAIN,
        mesh=model_mesh(np.asarray(jax.devices()[:8])), config=CFG,
    )
    sup = DeviceSupervisor(
        op_timeout_s=0.5, max_retries=0, probe_timeout_s=1.5,
        breaker_failure_threshold=100,
    )
    devs = jax.devices()
    g = _graft_entry()
    with faults.collective_stall(device_index=3, ops=("mesh.run",)):
        with pytest.raises(DeviceDegradedError) as ei:
            sup.call(
                lambda: engine.run(), op="optimize", mesh_devices=devs,
            )
        # read while the stall HOLDS: the abandoned dispatch is in flight
        # (at context exit the blocked worker returns and the End record
        # lands, so the in-flight window closes)
        records = read_spool(str(tmp_path / "spool-1.jsonl"))
        verdict = spool_verdict(str(tmp_path))
        fields = g._child_failure_fields(None, None, str(tmp_path))
    assert ei.value.failure_class is FailureClass.COLLECTIVE_STALL
    assert ei.value.device_ids == (3,)
    assert sup.device_health()[3]["healthy"] is False
    stuck = [
        r for r in records
        if r["t"] == "device-op" and r["ph"] == "B" and r["op"] == "mesh.run"
    ]
    assert stuck and stuck[-1]["mesh_shape"] == [1, 8]
    assert stuck[-1]["n_devices"] == 8
    assert verdict["mesh_in_flight"]["n_devices"] == 8
    assert verdict["mesh_in_flight"]["mesh_shape"] == [1, 8]
    # the dryrun timeout verdict embeds the same block (__graft_entry__)
    assert fields["mesh_in_flight"]["n_devices"] == 8
    assert fields["spool_configured"] is True


# -------------------------------------------- controller + checkpointing


def test_controller_per_width_breakers_and_probe_lifecycle():
    now = {"t": 0.0}
    ft = MeshFtController(probe_interval_s=10.0, clock=lambda: now["t"])
    brk = ft.acquire_width(8)
    assert brk is not None and brk.state is BreakerState.CLOSED
    brk.record_failure()
    assert brk.state is BreakerState.OPEN
    # widths are independent breakers
    assert ft.acquire_width(4) is not None
    assert ft.acquire_width(8) is None  # probe not due yet
    now["t"] = 11.0
    probe = ft.acquire_width(8)  # the attempt IS the half-open probe
    assert probe is brk and brk.state is BreakerState.HALF_OPEN
    ft.note_width_result(8, ok=False)  # failed probe re-arms the timer
    assert brk.state is BreakerState.OPEN and ft.acquire_width(8) is None
    now["t"] = 22.0
    assert ft.acquire_width(8) is brk
    ft.note_width_result(8, ok=True)
    assert brk.state is BreakerState.CLOSED


def test_controller_episode_fires_once_and_rearms_at_full_width():
    ft = MeshFtController()
    assert ft.poll_event() is None
    ft.note_degrade(lost=(6,), from_width=8, to_width=4,
                    failure_class="device_lost")
    assert ft.episodes == 1 and ft.episode_open
    event = ft.poll_event()
    assert event["lost_devices"] == [6] and event["episode"] == 1
    assert ft.poll_event() is None  # exactly once per episode
    # walking further down the ladder inside the episode: no re-fire
    ft.note_degrade(lost=(3,), from_width=4, to_width=2,
                    failure_class="collective_stall")
    assert ft.episodes == 1 and ft.poll_event() is None
    assert ft.last_event["to_width"] == 2
    # completing at reduced width keeps the episode open...
    ft.note_run_completed(width=2, full_width=8)
    assert ft.episode_open
    # ...recovery to FULL width closes it, re-arming the anomaly
    ft.note_run_completed(width=8, full_width=8)
    assert not ft.episode_open
    ft.note_degrade(lost=(1,), from_width=8, to_width=4,
                    failure_class="device_lost")
    assert ft.episodes == 2 and ft.poll_event()["episode"] == 2
    state = ft.state_json()
    assert state["episodes"] == 2 and state["activeWidth"] == 4


def test_offer_snapshot_cadence_one_in_flight_and_off_path():
    slot = CheckpointSlot()
    assert slot.latest() is None
    gate = threading.Event()
    landed = []

    def slow_sink(ckpt):
        gate.wait(10.0)
        slot.offer(ckpt)
        landed.append(ckpt)

    ctx = SegmentContext(0.0, snapshot_every=2, snapshot_sink=slow_sink)
    ctx.offer_snapshot(lambda: "b1")  # boundary 1: not due
    assert ctx.snapshots_taken == 0
    ctx.offer_snapshot(lambda: "b2")  # boundary 2: captured, persisting
    ctx.offer_snapshot(lambda: "b3")  # boundary 3: not due
    ctx.offer_snapshot(lambda: "b4")  # boundary 4: due but in flight → skip
    assert ctx.snapshots_taken == 1 and ctx.snapshots_skipped == 1
    assert slot.latest() is None  # persist still blocked
    gate.set()
    ctx.wait_snapshot()
    assert slot.latest() == "b2" and landed == ["b2"]
    # a raising sink is logged, never raised into the run it protects
    bad = SegmentContext(
        0.0, snapshot_every=1,
        snapshot_sink=lambda c: (_ for _ in ()).throw(OSError("disk full")),
    )
    bad.offer_snapshot(lambda: "x")
    bad.wait_snapshot()
    assert bad.snapshots_taken == 1
    # snapshot_every=0 (the default): capture must never even be called
    off = SegmentContext(0.0, snapshot_sink=slot.offer)
    off.offer_snapshot(lambda: pytest.fail("off path must not capture"))
    assert off.snapshots_taken == 0


# --------------------------------------------- segmented × mesh parity


@pytest.mark.slow
def test_segmented_mesh_parity_snapshots_and_reduced_width_resume(
    mesh_state, se8
):
    """THE checkpoint-layer invariant chain: a mesh run split into slices
    is byte-identical to the unsegmented mesh run; snapshots ride the
    slice boundaries only when asked (zero `mesh.snapshot` dispatches
    otherwise); and a checkpoint captured at width 8 resumes on a WIDTH-4
    mesh to the same bytes — full-K draws from the replicated key make
    the trajectory width-independent, so reduced-width resume is exact."""
    final, _ = se8.run()
    snaps = []
    ctx = SegmentContext(0.0, snapshot_every=1, snapshot_sink=snaps.append)
    with segmented_execution(ctx), dispatch_meter() as m_on:
        final_seg, hist_seg = se8.run()
    ctx.wait_snapshot()
    timing = next(h for h in hist_seg if h.get("timing"))
    assert timing["segmented"] is True and timing["segments"] >= 3
    assert timing["snapshots"] >= 2 and timing["snapshot_s"] >= 0.0
    assert m_on.counts["mesh.snapshot"] == timing["snapshots"]
    assert _same(final, final_seg)
    assert len(snaps) >= 2
    # checkpointing OFF: the segmented stream has zero snapshot dispatches
    with segmented_execution(SegmentContext(0.0)), dispatch_meter() as m_off:
        final_off, _ = se8.run()
    assert m_off.counts.get("mesh.snapshot", 0) == 0
    assert _same(final, final_off)
    # resume the mid-anneal checkpoint on a narrower mesh
    ck = snaps[1]
    assert ck.base >= 1 and ck.n_chains == 1
    se4 = ShardedEngine(
        mesh_state, DEFAULT_CHAIN,
        mesh=model_mesh(np.asarray(jax.devices()[:4])), config=CFG,
    )
    before = [np.array(leaf, copy=True) for leaf in jax.tree.leaves(ck.carry)]
    final4, hist4 = se4.run(resume=ck)
    t4 = next(h for h in hist4 if h.get("timing"))
    assert t4["resumed_from_round"] == int(ck.base)
    assert t4["mesh_shape"] == [1, 4]
    assert _same(final, final4)
    # the resume must not scribble into the checkpoint: device_put can
    # zero-copy alias the host trees and the slice programs donate the
    # carry — a second resume from the SAME snapshot has to be exact
    after = jax.tree.leaves(ck.carry)
    assert all(np.array_equal(a, b) for a, b in zip(before, after))
    final4b, _ = se4.run(resume=ck)
    assert _same(final4, final4b)


_MESH_KILL_CHILD = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, {repo!r})
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )
    import jax
    import numpy as np

    from cruise_control_tpu.analyzer import DEFAULT_CHAIN, OptimizerConfig
    from cruise_control_tpu.common.blackbox import RECORDER
    from cruise_control_tpu.parallel.sharded import ShardedEngine, model_mesh
    from cruise_control_tpu.testing import faults
    from cruise_control_tpu.testing.fixtures import (
        RandomClusterSpec, random_cluster,
    )

    RECORDER.configure(os.path.join({spool_dir!r}, f"spool-{{os.getpid()}}.jsonl"))
    state = random_cluster(RandomClusterSpec(
        num_brokers=8, num_partitions=48, skew=1.0), seed=0)
    cfg = OptimizerConfig(num_candidates=32, leadership_candidates=8,
                          swap_candidates=0, steps_per_round=2, num_rounds=2,
                          seed=0)
    se = ShardedEngine(state, DEFAULT_CHAIN,
                       mesh=model_mesh(np.asarray(jax.devices()[:8])),
                       config=cfg)
    # the injected stall IS the wedged collective: the mesh dispatch
    # blocks forever with its Begin record (mesh shape stamped) on disk
    with faults.collective_stall(ops=("mesh.run",)):
        se.run()
    print("UNREACHABLE")  # the parent kills us mid-dispatch
""")


def test_kill9_mid_mesh_dispatch_verdict_names_mesh_width(tmp_path):
    """The satellite's SIGKILL regression: kill -9 a process wedged
    inside a MESH dispatch — the surviving spool's verdict (and the
    dryrun timeout verdict built from it) must name the mesh width in
    flight, not just the op."""
    spool_dir = str(tmp_path / "spool")
    os.makedirs(spool_dir)
    child = subprocess.Popen(
        [sys.executable, "-c",
         _MESH_KILL_CHILD.format(repo=REPO, spool_dir=spool_dir)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE,
    )
    try:
        # wait for the in-flight mesh dispatch (the child is hung inside
        # it), then kill -9 — no cooperation from the child
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            records = read_spool(spool_dir)
            if any(
                r["t"] == "device-op" and r["ph"] == "B"
                and r.get("op") == "mesh.run" and r.get("n_devices") == 8
                for r in records
            ):
                break
            if child.poll() is not None:
                out, err = child.communicate(timeout=10)
                pytest.fail(
                    f"child exited rc={child.returncode} before hanging:\n"
                    f"{err.decode(errors='replace')[-2000:]}"
                )
            time.sleep(0.05)
        else:
            pytest.fail("child never dispatched on the mesh")
        os.kill(child.pid, signal.SIGKILL)
        child.wait(timeout=30)
    finally:
        if child.poll() is None:
            child.kill()
    verdict = spool_verdict(spool_dir)
    assert verdict["mesh_in_flight"]["op"] == "mesh.run"
    assert verdict["mesh_in_flight"]["mesh_shape"] == [1, 8]
    assert verdict["mesh_in_flight"]["n_devices"] == 8
    fields = _graft_entry()._child_failure_fields(None, None, spool_dir)
    assert fields["mesh_in_flight"]["n_devices"] == 8


# ------------------------------------------------------- purge scoping


class _DummyEngine:
    def __init__(self):
        self.released = False

    def release(self):
        self.released = True


def test_purge_scoped_to_failing_mesh_not_single_device_engines():
    """The satellite bugfix pin: a mesh failure purges ONLY parallel
    engines whose device set intersects the suspects; single-device
    engines (and disjoint survivor-subset engines) stay cached.  A
    single-device breaker open with mesh-ft active likewise leaves the
    parallel cache alone — mesh engines are purged at THEIR failure
    site."""
    sup = DeviceSupervisor(op_timeout_s=5.0, breaker_failure_threshold=1)
    ft = MeshFtController()
    opt = GoalOptimizer(
        config=CFG, parallel_mode="sharded", supervisor=sup, mesh_ft=ft,
    )
    single, wide, narrow = _DummyEngine(), _DummyEngine(), _DummyEngine()
    opt._engines[("shape", "cfg")] = single
    opt._parallel_engines[("shape", "cfg", (0, 1, 2, 3, 4, 5, 6, 7))] = wide
    opt._parallel_engines[("shape", "cfg", (0, 1, 2, 3))] = narrow
    opt._purge_parallel_for_mesh_failure((5,), [0, 1, 2, 3, 4, 5, 6, 7])
    assert wide.released and not narrow.released and not single.released
    assert ("shape", "cfg", (0, 1, 2, 3)) in opt._parallel_engines
    assert ("shape", "cfg") in opt._engines
    # single-device breaker opens: only _engines dropped (ft active)
    sup.breaker.record_failure()
    assert sup.breaker.state is BreakerState.OPEN
    opt._maybe_purge_after_open()
    assert single.released and not opt._engines
    assert ("shape", "cfg", (0, 1, 2, 3)) in opt._parallel_engines
    # with mesh-ft disabled the mesh rides the single-device breaker, so
    # the pre-FT purge-everything behavior is preserved
    opt._mesh_ft = MeshFtController(enabled=False)
    opt._breaker_epoch = sup.open_epoch - 1  # simulate a new open epoch
    opt._maybe_purge_after_open()
    assert narrow.released and not opt._parallel_engines


# ------------------------------------------------- optimizer FT wiring


def test_goal_optimizer_default_mesh_ft_wiring():
    # supervised mesh mode: a default controller appears (checkpoint off)
    sup = DeviceSupervisor(op_timeout_s=5.0)
    opt = GoalOptimizer(config=CFG, parallel_mode="sharded", supervisor=sup)
    assert opt._mesh_ft is not None and opt._mesh_ft.enabled
    assert opt._mesh_ft.checkpoint_every_slices == 0
    # single-device mode carries none — zero behavior change
    assert GoalOptimizer(config=CFG, supervisor=sup)._mesh_ft is None
    # unsupervised mesh mode: no supervisor seam to ride, none built
    assert GoalOptimizer(config=CFG, parallel_mode="sharded")._mesh_ft is None


def test_config_mesh_ft_accessor_and_validation():
    from cruise_control_tpu.config import ConfigException, CruiseControlConfig

    c = CruiseControlConfig({
        "tpu.parallel.mode": "sharded",
        "tpu.mesh.ft.checkpoint.every.slices": 2,
    })
    ft = c.mesh_ft_controller()
    assert ft is not None and ft.enabled and ft.checkpoint_every_slices == 2
    assert CruiseControlConfig({}).mesh_ft_controller() is None  # single
    off = CruiseControlConfig({
        "tpu.parallel.mode": "sharded", "tpu.mesh.ft.enabled": False,
    }).mesh_ft_controller()
    assert off is not None and not off.enabled
    with pytest.raises(ConfigException):
        CruiseControlConfig({"tpu.mesh.ft.checkpoint.every.slices": -1})


def test_mesh_degraded_anomaly_and_facade_detector():
    from cruise_control_tpu.detector.anomalies import AnomalyType, MeshDegraded
    from cruise_control_tpu.service.facade import CruiseControl

    a = MeshDegraded(
        lost_devices=[6], from_width=8, to_width=4,
        failure_class="device_lost", episode=1,
    )
    assert a.anomaly_type is AnomalyType.MESH_DEGRADED
    assert a.fixable is False  # alert-only: the width ladder IS the fix
    assert "8->4" in a.description() and "device_lost" in a.description()
    # the facade detector drains the controller's once-per-episode event
    ft = MeshFtController()
    stub = types.SimpleNamespace(
        optimizer=types.SimpleNamespace(_mesh_ft=ft)
    )
    assert CruiseControl._detect_mesh_degraded(stub) is None
    ft.note_degrade(lost=(6,), from_width=8, to_width=4,
                    failure_class="device_lost")
    anomaly = CruiseControl._detect_mesh_degraded(stub)
    assert isinstance(anomaly, MeshDegraded)
    assert anomaly.lost_devices == [6] and anomaly.to_width == 4
    assert CruiseControl._detect_mesh_degraded(stub) is None  # drained
    # no controller (single-device mode): detector is a no-op
    none_stub = types.SimpleNamespace(optimizer=types.SimpleNamespace())
    assert CruiseControl._detect_mesh_degraded(none_stub) is None


# --------------------------------------------------- the acceptance pin


@pytest.mark.slow
def test_optimizer_degrade_and_resume_ladder(mesh_state):
    """Device 6 dies at the second slice boundary of a supervised sharded
    anneal: the ladder attributes the loss, opens the WIDTH-8 breaker
    (never the single-device one), rebuilds over the 4 survivors, resumes
    from the last carry checkpoint, and the final placements byte-equal a
    clean run's — with exactly one MESH_DEGRADED event armed."""
    reg = SensorRegistry()
    sup = DeviceSupervisor(
        op_timeout_s=120.0, max_retries=0, probe_timeout_s=10.0,
        sensors=reg,
    )
    ft = MeshFtController(checkpoint_every_slices=1, sensors=reg)
    opt = GoalOptimizer(
        config=CFG, parallel_mode="sharded", supervisor=sup, mesh_ft=ft,
        sensors=reg,
    )
    clean = GoalOptimizer(config=CFG, parallel_mode="sharded").optimize(mesh_state)

    LOST = 6
    tripped = threading.Event()
    boundary = {"n": 0}

    def chk():
        boundary["n"] += 1
        if boundary["n"] == 2:
            tripped.set()
            raise faults.device_lost_error("mesh.run", LOST)

    def probe_effect(op, fn, args, kwargs):
        if tripped.is_set() and getattr(args[0], "id", None) == LOST:
            raise faults.device_lost_error(op, LOST)
        return fn(*args, **kwargs)

    with faults.device_fault(
        probe_effect, ops=(faults.DEVICE_PROBE_OP,)
    ), segmented_execution(SegmentContext(0.0, chk)):
        result = opt.optimize(mesh_state)

    assert not result.degraded, "the ladder must serve from the mesh"
    rec = next(h for h in reversed(result.history) if h.get("mesh_ft"))
    assert rec["lost_devices"] == [LOST]
    assert rec["width"] == 4 and rec["full_width"] == 8
    assert rec["resumed"] is True and rec["resumed_from_round"] >= 1
    timing = next(
        h for h in result.history if h.get("timing") and h.get("segmented")
    )
    assert timing["resumed_from_round"] == rec["resumed_from_round"]
    assert timing["mesh_shape"] == [1, 4]
    # byte parity with the clean run: width-independent draws + exact
    # carry restore means the interrupted anneal loses NOTHING
    assert _same(clean.state_after, result.state_after)
    assert float(clean.objective_after) == float(result.objective_after)
    # one episode, one event, per-width breakers scoped correctly
    assert ft.episodes == 1 and ft.episode_open
    event = ft.poll_event()
    assert event is not None and event["failure_class"] == "device_lost"
    assert event["from_width"] == 8 and event["to_width"] == 4
    assert ft.poll_event() is None
    snap = ft.state_json()
    assert snap["breakers"]["8"]["state"] == "open"
    assert snap["breakers"]["4"]["state"] == "closed"
    assert sup.breaker.state is BreakerState.CLOSED and sup.available()
    # the width-8 engine (touching the lost chip) was purged; the
    # survivor-width engine stays cached for the next request
    cached_ids = [k[2] for k in opt._parallel_engines]
    assert all(LOST not in ids for ids in cached_ids)
    assert any(len(ids) == 4 for ids in cached_ids)
    # sensors: the resume and the attributed loss are both counted
    assert reg.get("analyzer.mesh-ft.resumes").count == 1
    assert reg.get("analyzer.mesh-ft.device-lost").count == 1
    assert reg.get("analyzer.mesh-ft.active-width").snapshot()["value"] == 4
