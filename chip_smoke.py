#!/usr/bin/env python3
"""Chip smoke: the served rebalance path, end to end, on a TPU.

    python chip_smoke.py               one chip: the REST service at the
                                       north-star deployment
    python chip_smoke.py --four-chips  four chips: the mesh modes at the
                                       north-star shape against the plain
                                       one-chip engine, and nothing else

The one-chip path builds the service the way an operator's process does
(`service/main.py` `build_service` over the simulated backend) for 2,600
brokers on 52 racks and 200 topics x 1,000 partitions at replication
factor 3, with the full `default.goals` chain and `bench.py`'s SEARCH
widths; every other key keeps its default, boot prewarm and AOT export
included.  It then answers five requests over HTTP and checks each
answer.  Any failed check, any degraded (CPU-fallback) answer and any
counted prewarm/AOT failure exits nonzero.

Request walls printed here are first calls and include compilation: they
are diagnostics, not a benchmark.  The last line of stdout is the JSON
verdict; nothing is printed there unless every check passed.
"""

from __future__ import annotations

import json
import sys
import time
import urllib.error
import urllib.parse
import urllib.request

#: the north-star deployment (BASELINE.md; bench.py NORTH_STAR_SPEC)
NUM_BROKERS = 2600
NUM_RACKS = 52
NUM_TOPICS = 200
PARTITIONS_PER_TOPIC = 1000
REPLICATION = 3
#: BASELINE config 5: decommission 1% of the brokers (every 100th)
REMOVED_BROKERS = tuple(range(0, NUM_BROKERS, 100))
SEED = 0
#: every async request must finish inside this (compile included)
REQUEST_DEADLINE_S = 600.0
#: counters that only count a failure the service otherwise survives
MUST_STAY_ZERO = (
    "analyzer.degraded-proposals",
    "planner.degraded-evaluations",
    "analyzer.boot-prewarm-failures",
    "analyzer.prewarm-aot-rejects",
    "analyzer.prewarm-failures",
    "analyzer.precompute-failures",
    "analyzer.supervisor.breaker-opened",
)

#: what boot prewarm and AOT did (printed, not checked)
PREWARM_COUNTERS = (
    "analyzer.boot-prewarm-buckets",
    "analyzer.prewarm-aot-exports",
    "analyzer.prewarm-aot-hits",
)


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def require_tpu(count: int):
    """The devices, or exit nonzero: this script never runs on a CPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(
            f"chip_smoke: JAX found no TPU (platform {devices[0].platform!r}); "
            "nothing was run"
        )
    if len(devices) < count:
        sys.exit(f"chip_smoke: need {count} TPU chips, JAX found {len(devices)}")
    devices = devices[:count]
    print(f"device: {devices[0].device_kind} x{len(devices)}", flush=True)
    return devices


# ----------------------------------------------------------------------
# one chip: the served path
# ----------------------------------------------------------------------


class Client:
    """urllib against the running service; polls async answers to the end."""

    def __init__(self, app):
        self.base = f"http://{app.host}:{app.port}{app.prefix}"

    def _once(self, method, endpoint, params, headers):
        url = f"{self.base}/{endpoint}?{urllib.parse.urlencode(params)}"
        req = urllib.request.Request(url, method=method, headers=headers)
        try:
            with urllib.request.urlopen(req, timeout=REQUEST_DEADLINE_S) as r:
                return r.status, json.loads(r.read()), r.headers.get("User-Task-ID")
        except urllib.error.HTTPError as e:
            raise SmokeFailure(
                f"{method} /{endpoint}: HTTP {e.code}: {e.read()[:2000]!r}"
            ) from e

    def call(self, method: str, endpoint: str, **params) -> dict:
        t0 = time.monotonic()
        status, body, task = self._once(method, endpoint, params, {})
        while status == 202:
            check(
                time.monotonic() - t0 < REQUEST_DEADLINE_S,
                f"{method} /{endpoint} still running after {REQUEST_DEADLINE_S} s",
            )
            time.sleep(0.5)
            status, body, _ = self._once(
                method, endpoint, params, {"User-Task-ID": task}
            )
        print(
            f"{method} /{endpoint}: HTTP {status}, first call incl. compile "
            f"{time.monotonic() - t0:.3f} s",
            flush=True,
        )
        check(status == 200, f"{method} /{endpoint}: HTTP {status}")
        return body


def check_plan(label: str, body: dict) -> None:
    moves = body["numReplicaMovements"] + body["numLeaderMovements"]
    print(
        f"  {label}: {body['numReplicaMovements']} replica + "
        f"{body['numLeaderMovements']} leader moves, balancedness "
        f"{body['balancednessBefore']:.3f} -> {body['balancednessAfter']:.3f}, "
        f"violated after {body['violatedGoalsAfter']}",
        flush=True,
    )
    check(moves > 0, f"{label}: no proposals")
    check(
        body["balancednessAfter"] >= body["balancednessBefore"],
        f"{label}: balancedness fell",
    )
    check(body["degraded"] is False, f"{label}: degraded answer")


def run_served(
    *,
    num_brokers: int,
    num_racks: int,
    num_topics: int,
    partitions_per_topic: int,
    removed_brokers,
    search: dict,
) -> None:
    """Build the service at this size, answer five requests over HTTP,
    and check every answer; raises SmokeFailure on the first bad one."""
    from cruise_control_tpu.analyzer.engine import warm_pool_wait_idle
    from cruise_control_tpu.common.compilation_cache import boot_report
    from cruise_control_tpu.config.app_config import CruiseControlConfig
    from cruise_control_tpu.service.main import build_simulated_service

    config = CruiseControlConfig({
        "partition.metrics.window.ms": 1000,
        "min.samples.per.partition.metrics.window": 1,
        "num.partition.metrics.windows": 3,
        "webserver.http.port": 0,
        "tpu.num.candidates": search["num_candidates"],
        "tpu.leadership.candidates": search["leadership_candidates"],
        "tpu.steps.per.round": search["steps_per_round"],
        "tpu.num.rounds": search["num_rounds"],
    })
    t0 = time.monotonic()
    app, _fetcher, admin, _sampler = build_simulated_service(
        config,
        num_brokers=num_brokers,
        num_racks=num_racks,
        topics={f"T{i}": partitions_per_topic for i in range(num_topics)},
        replication=REPLICATION,
        seed=SEED,
    )
    cc = app.cc
    topo = admin.topology()
    n_replicas = sum(len(p.replicas) for p in topo.partitions)
    print(
        f"service built in {time.monotonic() - t0:.3f} s: {len(topo.brokers)} "
        f"brokers, {len(topo.partitions)} partitions, {n_replicas} replicas",
        flush=True,
    )
    cc.start_up(precompute=True)
    app.start()
    try:
        client = Client(app)
        state = client.call("GET", "state")
        check("AnalyzerState" in state, "/state has no AnalyzerState")

        check_plan("proposals", client.call("GET", "proposals"))
        check_plan("rebalance", client.call("POST", "rebalance", dryrun="true"))

        removed = set(removed_brokers)
        stranded = sum(
            b in removed for p in topo.partitions for b in p.replicas
        )
        body = client.call(
            "POST", "remove_broker",
            brokerid=",".join(map(str, sorted(removed))), dryrun="true",
        )
        check_plan("remove_broker", body)
        # OfflineReplicaGoal's violation is (replicas on dead brokers) /
        # (all replicas): one replica left reads 1/600k, above its 1e-6
        # "violated" tolerance
        check(
            "OfflineReplicaGoal" not in body["violatedGoalsAfter"],
            "remove_broker: replicas left on a removed broker",
        )
        check(
            body["numReplicaMovements"] >= stranded,
            f"remove_broker: {body['numReplicaMovements']} replica moves "
            f"for {stranded} replicas on removed brokers",
        )

        rack = topo.brokers[0].rack
        rack_size = sum(b.rack == rack for b in topo.brokers)
        body = client.call(
            "POST", "simulate", optimize="true",
            scenarios=json.dumps([{"name": "lose-rack", "killRacks": [rack]}]),
        )
        check(body["degraded"] is False, "simulate: degraded answer")
        check(len(body["scenarios"]) == 1, "simulate: not one scenario back")
        sc = body["scenarios"][0]
        check(
            sc["brokersAlive"] == body["baseline"]["brokersAlive"] - rack_size,
            f"simulate: losing {rack} left {sc['brokersAlive']} brokers alive",
        )
        check(
            "OfflineReplicaGoal" in sc["violatedGoals"],
            "simulate: a lost rack stranded no replica",
        )
        check_plan("simulate fix", sc["fix"])
        check(
            "OfflineReplicaGoal" not in sc["fix"]["violatedGoalsAfter"],
            "simulate: the fix left replicas on the lost rack",
        )

        store = cc.optimizer.prewarm_store
        if store is not None:
            check(store.drain(REQUEST_DEADLINE_S), "AOT export did not finish")
        breaker = cc.supervisor.state_json()["breaker"]
        check(breaker == "closed", f"device breaker is {breaker}")

        def count(name):
            sensor = cc.sensors.get(name)
            return sensor.count if sensor is not None else 0

        counts = {name: count(name) for name in MUST_STAY_ZERO}
        print(f"failure counters: {counts}", flush=True)
        check(not any(counts.values()), f"failures counted: {counts}")
        print(
            "prewarm: "
            + ", ".join(f"{name} {count(name)}" for name in PREWARM_COUNTERS),
            flush=True,
        )
        report = boot_report()
        if report is not None:
            print(
                f"compile cache {report['dir']}: {report['entriesAtBoot']} "
                f"entries at boot, {report['newCompiles']} new compiles, "
                f"engine traces {report['engineTraces']}",
                flush=True,
            )
    finally:
        app.stop()
        cc.shutdown()
    # the next-bucket prewarm may still be compiling
    check(
        warm_pool_wait_idle(REQUEST_DEADLINE_S),
        "background compiles still running",
    )


def one_chip() -> dict:
    device = require_tpu(1)[0]
    from bench import SEARCH

    run_served(
        num_brokers=NUM_BROKERS,
        num_racks=NUM_RACKS,
        num_topics=NUM_TOPICS,
        partitions_per_topic=PARTITIONS_PER_TOPIC,
        removed_brokers=REMOVED_BROKERS,
        search=SEARCH,
    )
    peak = (device.memory_stats() or {}).get("peak_bytes_in_use")
    print(f"peak_bytes_in_use: {peak}", flush=True)
    return {"platform": device.platform, "kind": device.device_kind, "count": 1}


# ----------------------------------------------------------------------
# four chips: the mesh modes against the plain engine
# ----------------------------------------------------------------------


def peak_bytes(devices) -> list:
    """peak_bytes_in_use per device (None where the backend keeps none)."""
    return [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]


def live_bytes(devices) -> list[float]:
    """Bytes resident per device now (allocator count, else live arrays)."""
    from cruise_control_tpu.common.profiling import per_device_live_bytes

    live = per_device_live_bytes()
    return [live.get(d.id, 0.0) for d in devices]


def run_mesh(devices, state, cfg) -> None:
    """Model-sharded and candidate-sharded runs of one seeded anneal on
    `devices`, the plain engine on one of them, then portfolio and 2 x n/2
    grid runs.  `state` is host-resident (numpy leaves).

    Checks: no device holds the whole model while the others hold slices
    (model-sharded mode, run first so nothing else is resident); the
    candidate-sharded placement equals the plain engine's byte for byte;
    the portfolio and grid answers are their best chain."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from cruise_control_tpu.analyzer import DEFAULT_CHAIN, Engine
    from cruise_control_tpu.models.state import validate
    from cruise_control_tpu.parallel.mesh import (
        MeshEngine,
        default_mesh,
        grid_mesh,
        model_mesh,
    )
    from cruise_control_tpu.parallel.portfolio import portfolio_run

    n = len(devices)
    obj0 = float(DEFAULT_CHAIN.evaluate(state)[0])

    def finished(label, final, t0):
        check(validate(final) == [], f"{label}: invalid placement")
        obj = float(DEFAULT_CHAIN.evaluate(final)[0])
        print(
            f"{label}: objective {obj0:.6f} -> {obj:.6f} in {time.monotonic() - t0:.3f} s "
            f"(first call, compile included); peak bytes per device "
            f"{peak_bytes(devices)}",
            flush=True,
        )
        check(obj <= obj0 + 1e-6, f"{label}: objective worsened")

    def best_chain(label, final, objectives):
        obj = float(DEFAULT_CHAIN.evaluate(final)[0])
        best = float(np.min(objectives))
        check(
            abs(obj - best) < max(1e-3, 1e-3 * abs(best)),
            f"{label}: answer's objective {obj} is not the best chain's {best}",
        )

    # `state` lives on the host, so what the run adds to each device is
    # what the mesh engine itself placed there; a whole copy of the model
    # kept beside device 0's slice would add at least `model_bytes` there
    model_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(state))
    before = live_bytes(devices)
    t0 = time.monotonic()
    ms = MeshEngine(
        state, DEFAULT_CHAIN, mesh=model_mesh(devices), config=cfg,
        model_shard_min_partitions=1,
    )
    check(ms.model_sharded, "model-sharded mode did not engage")
    ms_final, _ = ms.run()
    held = [a - b for a, b in zip(live_bytes(devices), before)]
    print(
        f"model-sharded: bytes added per device {held} (whole model "
        f"{model_bytes})",
        flush=True,
    )
    check(
        held[0] - min(held[1:]) < model_bytes,
        "model-sharded: device 0 holds the whole model beside its slice",
    )
    finished("model-sharded", ms_final, t0)
    del ms, ms_final

    t0 = time.monotonic()
    se = MeshEngine(state, DEFAULT_CHAIN, mesh=model_mesh(devices), config=cfg)
    sharded_final, _ = se.run()
    finished("candidate-sharded", sharded_final, t0)
    del se

    t0 = time.monotonic()
    engine = Engine(state, DEFAULT_CHAIN, config=cfg)
    plain_final, _ = engine.run()
    finished("plain one-chip engine", plain_final, t0)
    for field in ("replica_broker", "replica_is_leader", "replica_disk"):
        differ = int(
            (np.asarray(getattr(plain_final, field))
             != np.asarray(getattr(sharded_final, field))).sum()
        )
        check(
            differ == 0,
            f"candidate-sharded {field} differs from the plain engine's "
            f"in {differ} replicas",
        )
    print(
        f"candidate-sharded over {n} devices == plain one-chip engine, byte for byte",
        flush=True,
    )

    t0 = time.monotonic()
    temps = jnp.zeros((cfg.num_rounds, cfg.steps_per_round), jnp.float32)
    pf_final, info = portfolio_run(engine, default_mesh(devices), temps, seed=0)
    finished("portfolio", pf_final, t0)
    check(info["n_chains"] == n, "portfolio: wrong chain count")
    best_chain("portfolio", pf_final, info["objectives"])

    t0 = time.monotonic()
    ge = MeshEngine(
        state, DEFAULT_CHAIN, mesh=grid_mesh(2, n // 2, devices), config=cfg
    )
    grid_final, _ = ge.run()
    finished(f"grid 2x{n // 2}", grid_final, t0)
    best_chain("grid", grid_final, ge.last_info["objectives"])


def four_chips() -> dict:
    devices = require_tpu(4)
    import jax

    from bench import NORTH_STAR_SPEC, SEARCH

    from cruise_control_tpu.analyzer import OptimizerConfig
    from cruise_control_tpu.testing.fixtures import (
        RandomClusterSpec,
        random_cluster_fast,
    )

    state = jax.device_get(
        random_cluster_fast(RandomClusterSpec(**NORTH_STAR_SPEC), seed=SEED)
    )
    print(
        f"fixture: {state.shape.B} brokers, {state.shape.P} partitions, "
        f"{state.shape.R} replicas",
        flush=True,
    )
    run_mesh(devices, state, OptimizerConfig(**SEARCH))
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}


def main(argv) -> int:
    try:
        device = four_chips() if "--four-chips" in argv else one_chip()
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
