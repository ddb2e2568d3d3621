"""Black-box telemetry tests (common/blackbox.py).

The acceptance story: a process killed -9 (or hang-timed-out) mid-anneal
leaves an on-disk spool that replays to the EXACT in-flight dispatch —
bucket, slice index, wait class — and the multichip dryrun's timeout
verdict embeds structured last-dispatch records instead of a bare rc
tail.  Plus the recorder invariants those post-mortems depend on: torn
tails tolerated, the ring bounded, the disabled path writing nothing and
changing nothing.
"""

import json
import os
import signal
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from cruise_control_tpu.common.blackbox import (
    BlackBoxRecorder,
    RECORDER,
    blackbox_context,
    in_flight_from_records,
    read_spool,
    spool_verdict,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _reset_recorder():
    """The module-level recorder is process-wide state: every test leaves
    it disabled so suite ordering can never leak a spool."""
    yield
    RECORDER.configure(None)


def _small_state(seed=0):
    from cruise_control_tpu.testing.fixtures import (
        RandomClusterSpec,
        random_cluster,
    )

    return random_cluster(
        RandomClusterSpec(
            num_brokers=6, num_racks=3, num_topics=4, num_partitions=24,
            skew=1.0,
        ),
        seed=seed,
    )


def _small_config(**over):
    from cruise_control_tpu.analyzer import OptimizerConfig

    base = dict(
        num_candidates=64, leadership_candidates=16, swap_candidates=0,
        steps_per_round=2, num_rounds=3, seed=0,
    )
    base.update(over)
    return OptimizerConfig(**base)


# ----------------------------------------------------------------------
# recorder mechanics
# ----------------------------------------------------------------------


def test_recorder_roundtrip_context_and_in_flight(tmp_path):
    rec = BlackBoxRecorder()
    rec.configure(str(tmp_path / "spool-1.jsonl"))
    with blackbox_context(bucket="R64.B8", work_class="background"):
        seq = rec.begin("engine-slice", slice=0, rounds=2)
        rec.end(seq, done=False)
        rec.event("sched-grant", queue_wait_s=0.1)
        open_seq = rec.begin("engine-slice", slice=1, rounds=2)
    # the open dispatch is visible in-process...
    inflight = rec.in_flight()
    assert len(inflight) == 1
    assert inflight[0]["slice"] == 1
    assert inflight[0]["bucket"] == "R64.B8"
    # ...and from the on-disk records (the post-mortem view)
    records = read_spool(rec.path)
    assert [r["ph"] for r in records] == ["B", "E", "I", "B"]
    assert records[2]["work_class"] == "background"
    disk_inflight = in_flight_from_records(records)
    assert len(disk_inflight) == 1 and disk_inflight[0]["seq"] == open_seq
    # closing it clears both views
    rec.end(open_seq)
    assert rec.in_flight() == []
    assert in_flight_from_records(read_spool(rec.path)) == []


def test_exception_lands_in_end_record(tmp_path):
    rec = BlackBoxRecorder()
    rec.configure(str(tmp_path / "spool-1.jsonl"))
    with pytest.raises(ValueError):
        with rec.record("device-op", op="engine.run"):
            raise ValueError("boom")
    records = read_spool(rec.path)
    assert records[-1]["ph"] == "E"
    assert records[-1]["ok"] is False
    assert "boom" in records[-1]["error"]
    assert rec.in_flight() == []


def test_torn_tail_tolerated(tmp_path):
    rec = BlackBoxRecorder()
    rec.configure(str(tmp_path / "spool-1.jsonl"))
    s = rec.begin("supervised", op="optimize")
    rec.end(s)
    # the crash happened mid-write: a torn final line must end the
    # replay, not poison it
    with open(rec.path, "a", encoding="utf-8") as f:
        f.write('{"t": "super')
    records = read_spool(rec.path)
    assert len(records) == 2
    assert records[-1]["ph"] == "E"


def test_ring_rotation_keeps_one_generation(tmp_path):
    rec = BlackBoxRecorder()
    rec.configure(str(tmp_path / "spool-1.jsonl"), max_records=10)
    for i in range(35):
        rec.event("tick", i=i)
    assert os.path.exists(rec.path + ".1")
    records = read_spool(rec.path)
    # bounded: at most two generations' worth ever exists, newest last
    assert len(records) <= 20
    assert records[-1]["i"] == 34
    # the tail spans the rotation seamlessly
    assert [r["i"] for r in records] == list(
        range(records[0]["i"], 35)
    )


def test_unwritable_spool_disables_instead_of_raising(tmp_path):
    """Default-on telemetry must never prevent the service it observes
    from booting: an unopenable spool path leaves the recorder disabled
    (a regular file as a path component fails even for root, unlike
    permission bits)."""
    (tmp_path / "occupied").write_text("")
    rec = BlackBoxRecorder()
    rec.configure(str(tmp_path / "occupied" / "sub" / "spool-1.jsonl"))
    assert not rec.enabled and rec.write_errors == 1
    assert rec.begin("device-op", op="x") == 0  # silent no-op


def test_rotation_preserves_in_flight_begin_records(tmp_path):
    """A long-hung dispatch must survive any number of ring rotations
    driven by healthy traffic: its Begin is re-emitted into each new
    generation, so the post-mortem is never empty for exactly the
    long-hang case the spool exists for."""
    rec = BlackBoxRecorder()
    rec.configure(str(tmp_path / "spool-1.jsonl"), max_records=10)
    hung = rec.begin("engine-slice", slice=3, rounds=1)
    for i in range(45):  # > 4 whole generations of other traffic
        rec.event("tick", i=i)
    inflight = in_flight_from_records(read_spool(rec.path))
    assert [r["seq"] for r in inflight] == [hung]
    assert inflight[0]["slice"] == 3
    rec.end(hung)
    assert in_flight_from_records(read_spool(rec.path)) == []


def test_configure_prunes_dead_pid_spools(tmp_path):
    """'Bounded disk forever' across restarts: configuring a spool in a
    directory deletes sibling spool files of pids that no longer exist
    (a daily-restarted service must not accumulate a file pair per
    run)."""
    dead = tmp_path / "spool-999999999.jsonl"
    dead.write_text("{}\n")
    (tmp_path / "spool-999999999.jsonl.1").write_text("{}\n")
    live = tmp_path / f"spool-{os.getpid() + 0}.jsonl"  # ours, kept
    rec = BlackBoxRecorder()
    rec.configure(str(live))
    assert not dead.exists()
    assert not (tmp_path / "spool-999999999.jsonl.1").exists()
    assert live.exists()


def test_core_disables_recorder_when_config_says_off(tmp_path):
    """blackbox.enabled=false (or an explicitly empty dir) must disable
    a recorder an earlier service in this process turned on — the
    recorder is process-wide and the zero-writes contract is pinned."""
    from cruise_control_tpu.config.app_config import CruiseControlConfig
    from cruise_control_tpu.service.facade import AnalyzerCore

    AnalyzerCore(CruiseControlConfig({
        "blackbox.dir": str(tmp_path / "bb"),
    }))
    assert RECORDER.enabled
    AnalyzerCore(CruiseControlConfig({"blackbox.enabled": False}))
    assert not RECORDER.enabled


def test_spool_verdict_never_raises(tmp_path):
    assert spool_verdict(str(tmp_path / "absent")) == {
        "records": [], "in_flight": [],
    }


# ----------------------------------------------------------------------
# disabled-path pin
# ----------------------------------------------------------------------


def test_disabled_path_writes_nothing_and_results_identical(tmp_path):
    """Recording is pure observation: spool-on and spool-off runs of the
    same seeded anneal produce byte-identical placements, and the
    disabled recorder never touches disk."""
    from cruise_control_tpu.analyzer import DEFAULT_CHAIN, Engine

    state = _small_state()
    results = {}
    for mode in ("recorded", "disabled"):
        if mode == "recorded":
            RECORDER.configure(str(tmp_path / "spool-1.jsonl"))
        else:
            RECORDER.configure(None)
        eng = Engine(state, DEFAULT_CHAIN, config=_small_config())
        final, _ = eng.run()
        results[mode] = np.asarray(final.replica_broker)
    assert (results["recorded"] == results["disabled"]).all()
    recorded = read_spool(str(tmp_path / "spool-1.jsonl"))
    assert recorded, "the enabled run must have spooled its dispatches"
    assert {r["t"] for r in recorded} == {"device-op"}
    # disabled mode wrote nothing: record count unchanged after its run
    assert len(read_spool(str(tmp_path / "spool-1.jsonl"))) == len(recorded)


# ----------------------------------------------------------------------
# hang-timeout: the supervisor's abandonment verdict
# ----------------------------------------------------------------------


def test_hang_timeout_leaves_in_flight_trail(tmp_path):
    """A supervised dispatch that hangs past its budget leaves (a) the
    supervised End record with the abandonment verdict and (b) the
    in-worker device-op Begin permanently in flight — with the
    optimizer's bucket context stamped on it."""
    from cruise_control_tpu.analyzer import GoalOptimizer
    from cruise_control_tpu.common.device_watchdog import DeviceSupervisor
    from cruise_control_tpu.testing import faults

    RECORDER.configure(str(tmp_path / "spool-1.jsonl"))
    sup = DeviceSupervisor(
        op_timeout_s=0.4, max_retries=0, breaker_failure_threshold=100,
        probe=lambda: None,
    )
    opt = GoalOptimizer(config=_small_config(), supervisor=sup)
    opt.optimize(_small_state())  # healthy warm-up: compiles + records
    with faults.device_wedged(ops=("engine.run",)):
        result = opt.optimize(_small_state(seed=1))
        # read while the fault still holds: device_wedged releases its
        # abandoned workers at context exit (their late completion would
        # close the in-flight pair — exactly what a REAL hang never does)
        records = read_spool(str(tmp_path / "spool-1.jsonl"))
    assert result.degraded, "the hang must degrade to the CPU greedy path"
    abandoned = [
        r for r in records
        if r["t"] == "supervised" and r["ph"] == "E" and not r["ok"]
    ]
    assert abandoned and abandoned[-1]["hang"] is True
    inflight = in_flight_from_records(records)
    assert any(
        r["t"] == "device-op" and r["op"] == "engine.run" for r in inflight
    ), f"the hung engine dispatch must stay in flight: {inflight}"
    stuck = next(r for r in inflight if r["t"] == "device-op")
    assert "bucket" in stuck and stuck["config_fp"]


# ----------------------------------------------------------------------
# kill -9 mid-anneal: the acceptance story
# ----------------------------------------------------------------------

_KILL_CHILD = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, {repo!r})
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    from cruise_control_tpu.analyzer import GoalOptimizer, OptimizerConfig
    from cruise_control_tpu.analyzer.engine import Engine
    from cruise_control_tpu.common.blackbox import RECORDER
    from cruise_control_tpu.fleet.scheduler import DeviceScheduler, WorkClass
    from cruise_control_tpu.testing import faults
    from cruise_control_tpu.testing.fixtures import RandomClusterSpec, random_cluster

    RECORDER.configure(os.path.join({spool_dir!r}, f"spool-{{os.getpid()}}.jsonl"))
    state = random_cluster(RandomClusterSpec(
        num_brokers=6, num_racks=3, num_topics=4, num_partitions=24, skew=1.0
    ), seed=0)
    cfg = OptimizerConfig(num_candidates=64, leadership_candidates=16,
                          swap_candidates=0, steps_per_round=2, num_rounds=8,
                          early_stop_violations=-1.0,  # all 8 rounds run
                          seed=0)
    opt = GoalOptimizer(config=cfg)
    sched = DeviceScheduler(slice_budget_s=0.0001)  # tiny budget: 1-round slices
    # the injected hang IS the wedged XLA program: slice dispatch #2
    # (0-based) blocks forever inside the device call
    with faults.method_fault(
        Engine, "_seg_fn", faults.hanging(__import__("threading").Event()),
        schedule=faults.FaultSchedule(calls={{2}}),
    ):
        sched.run(WorkClass.BACKGROUND, lambda: opt.optimize(state))
    print("UNREACHABLE")  # the parent kills us mid-slice
""")


def test_kill9_mid_anneal_spool_replays_to_in_flight_slice(tmp_path):
    """Kill -9 a process wedged inside a segmented-anneal slice (fault
    injected at the engine's slice-program seam): the surviving spool
    must replay to the exact in-flight dispatch — slice index, bucket,
    scheduler work class and queue wait."""
    spool_dir = str(tmp_path / "spool")
    os.makedirs(spool_dir)
    child = subprocess.Popen(
        [sys.executable, "-c",
         _KILL_CHILD.format(repo=REPO, spool_dir=spool_dir)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE,
    )
    try:
        # wait until the spool shows slice 2 dispatched (the child is now
        # hung inside it), then kill -9 — no cooperation from the child
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            records = read_spool(spool_dir)
            if any(
                r["t"] == "engine-slice" and r["ph"] == "B"
                and r.get("slice") == 2
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
            pytest.fail("child never reached slice 2")
        os.kill(child.pid, signal.SIGKILL)
        child.wait(timeout=30)
    finally:
        if child.poll() is None:
            child.kill()
    verdict = spool_verdict(spool_dir)
    stuck = [r for r in verdict["in_flight"] if r["t"] == "engine-slice"]
    assert stuck, f"no in-flight slice in {verdict['in_flight']}"
    assert stuck[-1]["slice"] == 2
    # slices 0 and 1 completed — their pairs closed
    closed = [
        r for r in read_spool(spool_dir)
        if r["t"] == "engine-slice" and r["ph"] == "E"
    ]
    assert len(closed) == 2
    # cross-layer context rode down to the leaf record: the scheduler's
    # wait class + the optimizer's bucket name the wedged dispatch
    assert stuck[-1]["work_class"] == "background"
    assert "queue_wait_s" in stuck[-1]
    assert stuck[-1]["bucket"].startswith("R")
    # the scheduler's grant instant is in the trail too
    assert any(
        r["t"] == "sched-grant" and r["work_class"] == "background"
        for r in read_spool(spool_dir)
    )


# ----------------------------------------------------------------------
# dryrun timeout verdict
# ----------------------------------------------------------------------


def test_child_failure_fields_structured(tmp_path):
    """The dryrun failure verdict builder: output tails + spool tail +
    in-flight records, never raising."""
    sys.path.insert(0, REPO)
    try:
        import __graft_entry__ as g
    finally:
        sys.path.remove(REPO)
    spool = tmp_path / "spool-99.jsonl"
    rec = BlackBoxRecorder()
    rec.configure(str(spool))
    s = rec.begin("device-op", op="portfolio.run")
    rec.end(s)
    rec.begin("engine-slice", slice=7, rounds=4)  # left in flight
    rec.close()
    fields = g._child_failure_fields(
        "x" * 10_000, b"warning: tpu sad\n", str(tmp_path)
    )
    assert len(fields["stdout_tail"]) == g._VERDICT_TAIL_BYTES
    assert fields["stderr_tail"] == "warning: tpu sad\n"
    assert [r["t"] for r in fields["blackbox_tail"]] == [
        "device-op", "device-op", "engine-slice",
    ]
    assert fields["in_flight"][0]["slice"] == 7
    assert "wall_age_s" in fields["in_flight"][0]
    assert fields["spool_configured"] is True
    # unreadable spool dir: empty diagnosis, no exception
    empty = g._child_failure_fields(None, None, str(tmp_path / "absent"))
    assert empty["blackbox_tail"] == [] and empty["in_flight"] == []
    assert empty["spool_configured"] is False


def test_child_failure_fields_empty_spool_vs_never_started(tmp_path):
    """'No data' must be distinguishable from 'recorder never started':
    a spool FILE with zero records (the child configured the recorder,
    then hung before the first dispatch) reads spool_configured=True with
    structured in_flight=[]; a spool DIR with no spool files (the child
    died before RECORDER.configure — import/platform-init hang) reads
    spool_configured=False, in_flight still structurally []."""
    sys.path.insert(0, REPO)
    try:
        import __graft_entry__ as g
    finally:
        sys.path.remove(REPO)
    # recorder configured, zero records written
    configured = tmp_path / "configured"
    configured.mkdir()
    (configured / "spool-123.jsonl").write_text("")
    fields = g._child_failure_fields(None, None, str(configured))
    assert fields["spool_configured"] is True
    assert fields["blackbox_tail"] == []
    assert fields["in_flight"] == []
    # spool dir minted by the parent, child never reached configure
    never = tmp_path / "never"
    never.mkdir()
    fields = g._child_failure_fields(None, None, str(never))
    assert fields["spool_configured"] is False
    assert fields["blackbox_tail"] == []
    assert fields["in_flight"] == []
    # no spool dir at all (recorder disabled by configuration)
    fields = g._child_failure_fields(None, None, None)
    assert fields["spool_configured"] is False
    assert fields["in_flight"] == []


@pytest.mark.slow
def test_dryrun_timeout_verdict_embeds_spool(monkeypatch, capsys):
    """The real timeout path: a dryrun child killed at its budget yields
    a JSON verdict with combined output tails AND the child's black-box
    records (regression for the bare rc=124 kill of a hung dispatch)."""
    sys.path.insert(0, REPO)
    try:
        import __graft_entry__ as g
    finally:
        sys.path.remove(REPO)
    monkeypatch.setenv("DRYRUN_SUBPROC_TIMEOUT_S", "3")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.delenv("GRAFT_DRYRUN_CHILD", raising=False)
    monkeypatch.delenv("BLACKBOX_SPOOL_DIR", raising=False)
    with pytest.raises(RuntimeError, match="killed after"):
        g.dryrun_multichip(8)
    out = capsys.readouterr().out
    verdict = json.loads(
        [l for l in out.splitlines() if '"dryrun_multichip"' in l][-1]
    )
    assert verdict["value"] == -1.0
    for key in ("stdout_tail", "stderr_tail", "blackbox_tail", "in_flight",
                "spool_configured"):
        assert key in verdict, f"timeout verdict missing {key}"
    # the structured fields are typed even when the 3 s budget killed the
    # child before anything was recorded — "no data" stays machine-readable
    assert isinstance(verdict["in_flight"], list)
    assert isinstance(verdict["spool_configured"], bool)
    if not verdict["blackbox_tail"]:
        assert verdict["in_flight"] == []
