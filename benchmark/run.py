"""Run one benchmark cell once on the chip.

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell (`workloads` in BENCHMARK.json)
names a configuration (benchmark/configs/<config>.json) and a traffic mix
(benchmark/traffic/<traffic>.json); per-layer metrics are read by
benchmark/metrics/<metric>.py.  One run:

1. refuses to run without the chips the cell asks for;
2. makes the deployment from --seed and builds the service on the
   program's simulated backend (`service/main.py` `build_service`);
3. starts it (`start_up(precompute=True)`, HTTP on an ephemeral port),
   waits until the precompute pass, boot prewarm, AOT export and every
   warm-pool compile are idle, sends the mix's warm-up requests, and waits
   for quiet again: that is `setup_s`;
4. runs a closed loop of one HTTP client for --seconds; no request starts
   after that, and `plan_s` is (last plan in hand - window start) / plans;
5. reads the device's peak memory, stops the service, and checks a sample
   of the window's plans, drawn from the seed, against the reference
   (benchmark/correctness.py).

The last line of stdout is the JSON result; the numbers compared, each
with its limit, are the last lines of stderr and the result's last key.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib
import json
import os
import queue
import sys
import threading
import time
import urllib.error
import urllib.parse
import urllib.request

PROCESS_START = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the persistent compile cache: one fixed directory inside the checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
#: a request, a quiet wait, or the trace write may take this long at most
DEADLINE_S = 600.0


class BenchError(Exception):
    pass


# ----------------------------------------------------------------------
# files, found by name
# ----------------------------------------------------------------------


def load_json(rel: str) -> dict:
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def resolve(workload: str) -> tuple[dict, dict, dict, dict]:
    """(benchmark, cell, configuration, traffic mix) of one workload."""
    bench = load_json("BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    return (bench, cell, *cell_files(bench, cell))


def cell_files(bench: dict, cell: dict) -> tuple[dict, dict]:
    """(configuration, traffic mix) that a cell names."""
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(conf["file"])
    traffic = load_json(os.path.join("benchmark", "traffic", f"{cell['traffic']}.json"))
    return config, traffic


def removed_brokers(traffic: dict, num_brokers: int) -> list:
    """The broker ids a mix's requests remove: with `"remove_brokers":
    {"every": N}`, each id b of the deployment with b % N == 0."""
    every = traffic.get("remove_brokers", {}).get("every")
    return list(range(0, num_brokers, every)) if every else []


def request_params(traffic: dict, removed: list) -> dict:
    """The mix's query parameters, with `brokerid` filled from the removed brokers."""
    params = {k: str(v) for k, v in traffic["params"].items()}
    if removed:
        params["brokerid"] = ",".join(map(str, removed))
    return params


def metric_reader(name: str):
    return importlib.import_module(f"benchmark.metrics.{name}").read


# ----------------------------------------------------------------------
# the chip
# ----------------------------------------------------------------------


def require_chip(count: int):
    """The devices, or exit nonzero with no result: no CPU fallback."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"benchmark: JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < count:
        sys.exit(f"benchmark: the cell needs {count} TPU chips, JAX found {len(devices)}")
    return devices[:count]


class CompileCounter:
    """Backend compiles seen through jax.monitoring: every program the
    process acquires fires one backend-compile event, a persistent-cache
    hit fires a cache-hit event beside it."""

    BACKEND = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.backend = 0
        self.hits = 0
        self.installed = False
        self.events: list = []  # (monotonic end, program, seconds) per backend event
        self._lock = threading.Lock()

    def install(self):
        import jax.monitoring as mon

        self.installed = True
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if event == self.BACKEND:
            with self._lock:
                self.backend += 1
                self.events.append((time.monotonic(), kw.get("fun_name"), duration))

    def _on_event(self, event, **kw):
        if event == self.HIT:
            with self._lock:
                self.hits += 1

    def snapshot(self) -> tuple[int, int]:
        with self._lock:
            return self.backend, self.hits


# ----------------------------------------------------------------------
# the served path
# ----------------------------------------------------------------------


class Client:
    """urllib against the running service; re-polls a 202 at once (the
    server already holds each poll for up to a second)."""

    def __init__(self, app):
        self.base = f"http://{app.host}:{app.port}{app.prefix}"

    def _once(self, method, endpoint, params, headers):
        url = f"{self.base}/{endpoint}?{urllib.parse.urlencode(params)}"
        req = urllib.request.Request(url, method=method, headers=headers)
        try:
            with urllib.request.urlopen(req, timeout=DEADLINE_S) as r:
                return r.status, json.loads(r.read()), r.headers.get("User-Task-ID")
        except urllib.error.HTTPError as e:
            return e.code, {"errorMessage": e.read()[:2000].decode("utf-8", "replace")}, None

    def call(self, method: str, endpoint: str, params: dict) -> tuple[int, dict]:
        t0 = time.monotonic()
        status, body, task = self._once(method, endpoint, params, {})
        while status == 202:
            if time.monotonic() - t0 > DEADLINE_S:
                return 504, {"errorMessage": f"still running after {DEADLINE_S} s"}
            status, body, _ = self._once(method, endpoint, params, {"User-Task-ID": task})
        return status, body


@dataclasses.dataclass
class Plan:
    """One request of the window."""

    t0: float
    t1: float
    status: int
    trace_id: str | None
    snapshot: dict | None = None  # host copy of the program's result
    timing: dict | None = None  # that result's timing record
    spans: list = dataclasses.field(default_factory=list)  # (name, start, end)


@dataclasses.dataclass
class Run:
    """What a run measured; the metric readers take their numbers from it."""

    window_start: float
    plans: list
    setup_compiles: int
    window_compiles: int
    peak_bytes: int | None
    trace: object = None  # trace_reduce.TraceSummary of the first `traced` plans
    traced: int = 0
    compile_events: list = dataclasses.field(default_factory=list)
    setup_split: dict = dataclasses.field(default_factory=dict)
    readings: list = dataclasses.field(default_factory=list)  # compared numbers per checked plan
    removed: list = dataclasses.field(default_factory=list)  # brokers each request removed

    @property
    def done(self) -> list:
        return [p for p in self.plans if p.status == 200]


def service_config(config: dict):
    from cruise_control_tpu.config.app_config import CruiseControlConfig

    props = dict(config["service"])
    props["capacity.config.file"] = os.path.join(ROOT, config["capacity_file"])
    props["partition.metrics.window.ms"] = config["monitor"]["window_ms"]
    props["num.partition.metrics.windows"] = config["monitor"]["complete_windows"]
    props["webserver.http.port"] = 0
    return CruiseControlConfig(props)


def build(config: dict, dep):
    """The service over the deployment, its windows fed; (app, split)."""
    from cruise_control_tpu.executor.admin import SimulatedClusterAdmin
    from cruise_control_tpu.monitor.topology import StaticMetadataProvider
    from cruise_control_tpu.service.main import build_service

    from benchmark.deployment import DeploymentSampler, cluster_topology

    split = {}
    t = time.monotonic()
    metadata = StaticMetadataProvider(cluster_topology(dep))
    admin = SimulatedClusterAdmin(metadata, link_rate_bytes_per_s=1e12)
    sampler = DeploymentSampler(dep)
    app, fetcher = build_service(service_config(config), metadata, admin, sampler)
    split["service_build_s"] = time.monotonic() - t
    t = time.monotonic()
    sampler.fetch_all_windows(fetcher)
    split["ingest_s"] = time.monotonic() - t
    return app, split


def watch(cc):
    """While `recording` is set, hand each optimize() result, keyed by the
    trace id of its request, to a thread that copies what the check needs
    to the host (`snapshots`) and drops the result, so retained results do
    not hold device memory.  `first_pass` is set when the precompute loop
    has finished its first pass (its next-bucket prewarm is the last thing
    it enqueues).  Returns (snapshots, recording, first_pass, finish)."""
    from cruise_control_tpu.common.trace import current_trace_id

    from benchmark.correctness import snapshot

    snapshots: dict = {}
    recording = threading.Event()
    pending: queue.Queue = queue.Queue()
    opt = cc.optimizer
    optimize = opt.optimize

    def recorded(*a, **kw):
        res = optimize(*a, **kw)
        if recording.is_set():
            pending.put((current_trace_id(), res))
        return res

    def copier():
        while (item := pending.get()) is not None:
            tid, res = item
            timing = next((h for h in res.history if h.get("timing")), None)
            snapshots[tid] = (snapshot(res), timing)
            del res, item

    thread = threading.Thread(target=copier, daemon=True, name="bench-snapshot")
    thread.start()

    def finish():
        pending.put(None)
        thread.join(DEADLINE_S)

    opt.optimize = recorded
    first_pass = threading.Event()
    prewarm_next = cc._prewarm_next_bucket

    def prewarm_then_signal():
        try:
            prewarm_next()
        finally:
            first_pass.set()

    cc._prewarm_next_bucket = prewarm_then_signal
    return snapshots, recording, first_pass, finish


def wait_quiet(cc, first_pass: threading.Event) -> None:
    """Until the precompute pass, boot prewarm, AOT export and every
    warm-pool compile have finished."""
    from cruise_control_tpu.analyzer.engine import warm_pool_wait_idle

    if not first_pass.wait(DEADLINE_S):
        raise BenchError("the precompute pass did not finish")
    if not cc._boot_prewarm_done.wait(DEADLINE_S):
        raise BenchError("boot prewarm did not finish")
    store = cc.optimizer.prewarm_store
    if store is not None and not store.drain(DEADLINE_S):
        raise BenchError("the AOT export did not finish")
    if not warm_pool_wait_idle(DEADLINE_S):
        raise BenchError("warm-pool compiles did not finish")


def span_times(cc, trace_id: str) -> list:
    return [
        (s.name, s.start_mono, s.end_mono)
        for s in cc.tracer.trace(trace_id)
        if s.end_mono is not None
    ]


#: one compile counter per process, installed before the first compile
COUNTER = CompileCounter()


def run_cell(config: dict, traffic: dict, seed: int, seconds: float,
             trace: bool, log=print):
    """Set up and measure one run; (Run, deployment)."""
    import jax

    from benchmark.deployment import make_deployment

    counter = COUNTER
    if not counter.installed:
        counter.install()
    split = {}
    t = time.monotonic()
    dep = make_deployment(config, seed, ROOT)
    split["generate_s"] = time.monotonic() - t
    app, build_split = build(config, dep)
    split.update(build_split)
    cc = app.cc
    snapshots, recording, first_pass, finish = watch(cc)
    t = time.monotonic()
    cc.start_up(precompute=True)
    app.start()
    split["start_s"] = time.monotonic() - t
    client = Client(app)
    removed = removed_brokers(traffic, dep.num_brokers)
    params = request_params(traffic, removed)
    method, endpoint = traffic["method"], traffic["endpoint"]
    try:
        # the precompute pass first, so the warm-up never races it for the
        # chip: one order, one set-up time
        t = time.monotonic()
        wait_quiet(cc, first_pass)
        split["precompute_s"] = time.monotonic() - t
        t = time.monotonic()
        for _ in range(traffic["warmup_requests"]):
            status, body = client.call(method, endpoint, params)
            if status != 200:
                raise BenchError(f"warm-up {method} /{endpoint}: HTTP {status}: {body}")
        split["warmup_s"] = time.monotonic() - t
        t = time.monotonic()
        wait_quiet(cc, first_pass)
        split["quiet_wait_s"] = time.monotonic() - t
        setup_backend, setup_hits = counter.snapshot()
        setup_end = time.monotonic()
        split["setup_s"] = setup_end - PROCESS_START
        log(f"set-up: {json.dumps({k: round(v, 3) for k, v in split.items()})}")

        trace_dir = os.path.join(ROOT, ".bench_trace")
        if trace:
            # device and host events, not every Python call: the Python
            # tracer multiplies the host path's time several fold
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        plans = []
        recording.set()
        # a traced run traces its first `traced_plans` plans (a device
        # trace of a whole window outgrows the profiler's buffers), then
        # serves the rest of the window untraced
        traced = traffic["traced_plans"] if trace else None

        def serve():
            t0 = time.monotonic()
            status, body = client.call(method, endpoint, params)
            plans.append(Plan(t0, time.monotonic(), status, body.get("_traceId")))

        start = time.monotonic()
        end = start + seconds
        with jax.profiler.TraceAnnotation("bench.window"):
            while time.monotonic() < end and len(plans) != traced:
                serve()
        if trace:
            jax.profiler.stop_trace()
        while time.monotonic() < end:
            serve()
        recording.clear()
        window_backend, _ = counter.snapshot()
        peak = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.local_devices()
        ) or None
        finish()
        for p in plans:
            p.snapshot, p.timing = snapshots.get(p.trace_id, (None, None))
            if p.trace_id:
                p.spans = span_times(cc, p.trace_id)
    finally:
        from cruise_control_tpu.analyzer.engine import warm_pool_wait_idle

        app.stop()
        cc.shutdown()
        warm_pool_wait_idle(DEADLINE_S)
    run = Run(
        window_start=start,
        plans=plans,
        setup_compiles=setup_backend - setup_hits,
        window_compiles=window_backend - setup_backend,
        peak_bytes=peak,
        setup_split=split,
        compile_events=[e for e in counter.events if start <= e[0]][: window_backend - setup_backend],
        traced=min(len(plans), traced or 0),
        removed=removed,
    )
    if trace:
        from benchmark.trace_reduce import reduce_trace_dir

        import shutil

        t = time.monotonic()
        run.trace = reduce_trace_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)  # hundreds of MB at north
        log(f"trace reduced in {time.monotonic() - t:.1f} s")
    return run, dep


def checked_plans(run: Run, seed: int, traffic: dict) -> list:
    """The window's plans the check reads: `check_plans` of them, drawn
    from the seed, each with its whole plan (correctness.Served)."""
    import numpy as np

    from benchmark import correctness

    done = [p for p in run.done if p.snapshot is not None]
    rng = np.random.default_rng([seed, 1])
    k = min(len(done), traffic["check_plans"])
    picked = sorted(rng.choice(len(done), size=k, replace=False).tolist()) if k else []
    out = [correctness.served(done[i].snapshot) for i in picked]
    for p in run.plans:
        p.snapshot = None
    gc.collect()
    return out


def compare_plans(run: Run, served: list, dep, config: dict, control_dtype=None) -> list:
    """One reading of the compared numbers per plan (see correctness.py),
    with the brokers the run's requests removed dead in the reference;
    `control_dtype` puts the reference, in that precision, in the
    program's place."""
    from benchmark import correctness

    reference = correctness.Reference(dep, config, removed=run.removed)
    control = None if control_dtype is None else correctness.Reference(
        dep, config, dtype=control_dtype, removed=run.removed
    )
    return [correctness.compare(s, reference, control) for s in served]


def metric_value(m: dict, run: Run, balancedness: float | None):
    name = m["name"]
    if name == "setup_s":
        return run.setup_split["setup_s"]
    if name == "plan_s":
        done = run.done
        return (done[-1].t1 - run.window_start) / len(done) if done else None
    if name == "balancedness":
        return balancedness
    return metric_reader(name)(run)


def breakdown(run: Run) -> dict | None:
    """Top device programs by time, and the longest idle gaps labelled with
    the innermost program span open at their middle."""
    tr = run.trace
    if tr is None or tr.busy_s is None:
        return None
    ops = sorted(tr.module_s.items(), key=lambda kv: -kv[1])[:10]
    # trace clock -> monotonic: the window opened at run.window_start
    to_mono = run.window_start - tr.window_start_ns * 1e-9
    spans = [s for p in run.plans for s in p.spans]
    gaps = []
    for s, e in tr.gaps[:10]:
        mid = (s + e) * 0.5e-9 + to_mono
        open_spans = [x for x in spans if x[1] <= mid <= x[2]]
        if open_spans:
            label = max(open_spans, key=lambda x: x[1])[0]
        elif any(p.t0 <= mid <= p.t1 for p in run.plans):
            label = "client (request in flight, no span open)"
        else:
            label = "client (between requests)"
        gaps.append([label, (e - s) * 1e-9])
    return {"device_ops": [[n, v] for n, v in ops], "idle_gaps": gaps}


def diagnose(run: Run, log) -> None:
    """Per-plan walls and program spans, and any compile in the window,
    on stderr."""
    keys = ("host_dispatch_s", "device_s", "host_extract_s", "engine_cache_hit", "engine_build_s")
    for i, p in enumerate(run.plans):
        spans = {}
        for name, s, e in p.spans:
            spans[name] = round(spans.get(name, 0.0) + e - s, 4)
        timing = {k: p.timing.get(k) for k in keys} if p.timing else None
        log(
            f"plan {i}: HTTP {p.status}, {p.t1 - p.t0:.4f} s from "
            f"{p.t0 - run.window_start:.3f} s; spans {json.dumps(spans)}; timing {json.dumps(timing)}"
        )
    for t, name, d in run.compile_events:
        log(f"window compile: {name} {d:.3f} s at {t - run.window_start:.3f} s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench, cell, config, traffic = resolve(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    devices = require_chip(cell["chips"])
    from benchmark import correctness

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    run, dep = run_cell(config, traffic, args.seed, args.seconds, bool(args.trace), log)
    limits = config["limits"]
    readings = compare_plans(run, checked_plans(run, args.seed, traffic), dep, config)
    run.readings = readings
    worst = correctness.worst(readings) if readings else {}
    bal = (
        sum(r["_balancedness"] for r in readings) / len(readings) if readings else None
    )
    checks = {
        k: {"value": worst.get(k), "limit": limits[k]} for k in limits
    }
    correct = bool(worst) and all(
        worst[k] is not None and worst[k] <= limits[k] for k in limits
    )
    key = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in bench[key]:
        v = metric_value(m, run, bal)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    d = devices[0]
    device = {
        "platform": d.platform, "kind": d.device_kind, "count": len(devices),
        "memory_peak_bytes": run.peak_bytes,
    }
    out = {
        "correct": correct,
        "attempted": len(run.plans),
        "failed": len(run.plans) - len(run.done),
        "metrics": metrics,
        "device": device,
    }
    if args.trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        bd = breakdown(run)
        if bd is not None:
            out["breakdown"] = bd
    out["checks"] = checks
    diagnose(run, log)
    log(
        f"run: {len(run.done)} plans, {time.monotonic() - PROCESS_START:.1f} s in all; "
        f"plan objective {[r['_objective'] for r in readings]}"
    )
    for k, c in checks.items():
        log(f"check {k} {c['value']} limit {c['limit']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr, flush=True)
        code = 1
    except Exception:  # noqa: BLE001 — report, then exit nonzero below
        import traceback

        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads of the service (detector, precompute, warm pool) must
    # not race interpreter teardown; everything is stopped and flushed
    os._exit(code)
