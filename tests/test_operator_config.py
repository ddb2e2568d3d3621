"""Shipped operator sample configs must actually work (the reference
ships config/cruisecontrol.properties +
capacity*.json; an operator must not have to author them from scratch).

Reference analogs: config/cruisecontrol.properties:1, capacity.json,
capacityJBOD.json, capacityCores.json +
config/BrokerCapacityConfigFileResolver.java (schema semantics).
"""

import os

import numpy as np
import pytest

from cruise_control_tpu.common.resources import Resource
from cruise_control_tpu.config.app_config import CruiseControlConfig, load_properties
from cruise_control_tpu.monitor.capacity import FileCapacityResolver

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONF = os.path.join(REPO, "config")


def test_properties_file_parses_with_no_unknown_values():
    props = load_properties(os.path.join(CONF, "cruisecontrol.properties"))
    assert props, "sample properties must not be empty"
    config = CruiseControlConfig(props)
    # every uncommented key resolves through the typed config
    for key in props:
        config.get(key)
    # spot-check typed parsing happened (not raw strings)
    assert config.get("tpu.num.candidates") == 16384
    assert config.get("partition.metrics.window.ms") == 300_000
    assert config.get("cruise.control.metrics.serde.format") == "native"
    assert config.get("capacity.config.file") == "config/capacity.json"


def test_capacity_json_plain():
    r = FileCapacityResolver(os.path.join(CONF, "capacity.json"))
    default = r.capacity_for_broker("r0", "h0", 99)  # falls back to -1
    assert default.capacity[Resource.DISK] == 500_000.0
    assert default.capacity[Resource.CPU] == 100.0
    b0 = r.capacity_for_broker("r0", "h0", 0)
    assert b0.capacity[Resource.DISK] == 1_000_000.0
    assert b0.capacity[Resource.NW_IN] == 100_000.0


def test_capacity_json_jbod():
    r = FileCapacityResolver(os.path.join(CONF, "capacityJBOD.json"))
    default = r.capacity_for_broker("r0", "h0", 42)
    assert default.disk_capacities == {"/data/d0": 250_000.0, "/data/d1": 250_000.0}
    assert default.capacity[Resource.DISK] == 500_000.0  # sum of logdirs
    b0 = r.capacity_for_broker("r0", "h0", 0)
    assert len(b0.disk_capacities) == 3
    assert b0.capacity[Resource.DISK] == 1_000_000.0


def test_capacity_json_cores():
    r = FileCapacityResolver(os.path.join(CONF, "capacityCores.json"))
    default = r.capacity_for_broker("r0", "h0", 7)
    assert default.num_cores == 16
    assert default.capacity[Resource.CPU] == 100.0  # percent-based
    assert r.capacity_for_broker("r0", "h0", 0).num_cores == 32


def test_openapi_spec_is_current():
    """docs/openapi.json must match what scripts/gen_api_spec.py derives
    from the served endpoint/parameter/schema declarations — a drifted
    spec is worse than none (reference regenerates its Swagger wiki via
    build_api_wiki.sh)."""
    import json
    import importlib.util

    spec_path = os.path.join(REPO, "docs", "openapi.json")
    gen_path = os.path.join(REPO, "scripts", "gen_api_spec.py")
    s = importlib.util.spec_from_file_location("gen_api_spec", gen_path)
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    with open(spec_path) as f:
        committed = json.load(f)
    assert committed == mod.build_spec(), (
        "docs/openapi.json is stale — run scripts/gen_api_spec.py"
    )
    # every served endpoint appears with its method
    from cruise_control_tpu.config.endpoints import GET_ENDPOINTS, POST_ENDPOINTS

    for ep in GET_ENDPOINTS:
        assert "get" in committed["paths"][f"/{ep}"]
    for ep in POST_ENDPOINTS:
        assert "post" in committed["paths"][f"/{ep}"]


def test_openapi_paths_match_endpoint_tables_exactly():
    """Bidirectional openapi <-> GET_ENDPOINTS/POST_ENDPOINTS drift gate:
    the committed spec must cover EXACTLY the served endpoint set — no
    endpoint missing from the spec, no ghost path lingering after an
    endpoint is removed, no method served that the spec does not declare."""
    import json

    from cruise_control_tpu.config.endpoints import GET_ENDPOINTS, POST_ENDPOINTS

    with open(os.path.join(REPO, "docs", "openapi.json")) as f:
        spec = json.load(f)
    served = {f"/{ep}" for ep in GET_ENDPOINTS} | {f"/{ep}" for ep in POST_ENDPOINTS}
    assert set(spec["paths"]) == served, (
        "docs/openapi.json paths drifted from config/endpoints.py — "
        "run scripts/gen_api_spec.py"
    )
    for ep in GET_ENDPOINTS:
        assert set(spec["paths"][f"/{ep}"]) >= {"get"}
    for ep in POST_ENDPOINTS:
        assert set(spec["paths"][f"/{ep}"]) >= {"post"}
    # and no method is declared that the server does not dispatch
    for path, ops in spec["paths"].items():
        ep = path.lstrip("/")
        for method in ops:
            assert (method == "get" and ep in GET_ENDPOINTS) or (
                method == "post" and ep in POST_ENDPOINTS
            ), f"{method.upper()} {path} declared in the spec but not served"


def test_service_boots_from_shipped_properties():
    """The start script's exact path: load the shipped properties, boot the
    service from them (simulated backend — no bootstrap.servers), serve a
    request, and verify the configured JBOD capacity file reached the
    monitor's resolver."""
    import json
    import urllib.request

    from cruise_control_tpu.service.main import build_simulated_service

    props = load_properties(os.path.join(CONF, "cruisecontrol.properties"))
    # ephemeral port + JBOD capacities + tiny engine so the test is fast
    props.update({
        "webserver.http.port": "0",
        "capacity.config.file": os.path.join(CONF, "capacityJBOD.json"),
        "tpu.num.candidates": "128",
        "tpu.leadership.candidates": "32",
        "tpu.steps.per.round": "8",
        "tpu.num.rounds": "2",
        "num.partition.metrics.windows": "3",
        "partition.metrics.window.ms": "1000",
    })
    config = CruiseControlConfig(props)
    app, fetcher, admin, sampler = build_simulated_service(config)
    app.start()
    try:
        url = f"http://{app.host}:{app.port}{app.prefix}/state?substates=monitor"
        with urllib.request.urlopen(url, timeout=30) as resp:
            assert resp.status == 200
            payload = json.loads(resp.read())
        assert "MonitorState" in payload
        # the JBOD capacity file is live in the monitor
        cap = app.cc.monitor.capacity_resolver.capacity_for_broker("r0", "h0", 1)
        assert cap.disk_capacities == {"/data/d0": 250_000.0, "/data/d1": 250_000.0}
    finally:
        app.stop()


# ------------------------------------------------ sensor-catalog drift gate


def _documented_sensor_names():
    """Parse the docs/sensors.md table into (concrete names, regex
    patterns).  Cell grammar the parser understands:

      * ```a.b.c` ``                       one name
      * ```a.b.c` / `.d` ``                suffix shorthand: second name
                                           replaces the last segment(s)
      * ```a.{x,y}` ``                     brace expansion
      * ```a.<type>.rate` ``               placeholder -> regex pattern
    """
    import re

    names: set[str] = set()
    patterns: list[str] = []
    with open(os.path.join(REPO, "docs", "sensors.md")) as f:
        for line in f:
            if not line.startswith("|") or line.startswith("|---"):
                continue
            cell = line.split("|")[1].strip()
            if cell in ("sensor", ""):
                continue
            base = None
            for tok in re.findall(r"`([^`]+)`", cell):
                if tok.startswith("."):
                    assert base is not None, f"suffix {tok!r} with no base"
                    suffix = tok[1:].split(".")
                    parts = base.split(".")
                    tok = ".".join(parts[: len(parts) - len(suffix)] + suffix)
                else:
                    base = tok
                m = re.match(r"(.*)\{([^}]+)\}(.*)", tok)
                expanded = (
                    [f"{m.group(1)}{alt}{m.group(3)}" for alt in m.group(2).split(",")]
                    if m
                    else [tok]
                )
                for name in expanded:
                    if "<" in name:
                        patterns.append(
                            "^"
                            + re.sub(r"<[^>]+>", r"[a-z0-9_-]+", re.escape(name).replace(
                                re.escape("<"), "<").replace(re.escape(">"), ">"))
                            + "$"
                        )
                    else:
                        names.add(name)
    assert names, "docs/sensors.md table parsed empty"
    return names, patterns


def test_runtime_sensor_names_are_documented():
    """Every sensor a full-service smoke registers must appear in
    docs/sensors.md — the sensors twin of the openapi<->endpoint-table
    drift gate.  (The reverse direction is
    test_documented_sensor_names_exist_in_source.)"""
    import re

    from cruise_control_tpu.service.main import build_simulated_service

    documented, patterns = _documented_sensor_names()
    app, fetcher, admin, sampler = build_simulated_service(seed=23)
    try:
        cc = app.cc
        # drive the proposal pipeline + an execution so the monitor,
        # analyzer, device-supervisor and executor surfaces all register
        from cruise_control_tpu.service.progress import OperationProgress

        result = cc.proposals(OperationProgress(), ignore_cache=True)
        cc.rebalance(OperationProgress(), dryrun=False)
        runtime = set(cc.sensors.snapshot())
        assert result is not None and runtime
        undocumented = {
            n
            for n in runtime
            if n not in documented
            and not any(re.match(p, n) for p in patterns)
        }
        assert not undocumented, (
            f"sensors registered at runtime but missing from docs/sensors.md: "
            f"{sorted(undocumented)}"
        )
    finally:
        app.stop()


def test_documented_sensor_names_exist_in_source():
    """Every name docs/sensors.md lists must still exist in the package
    source — a renamed/removed sensor must not leave a ghost row. Dynamic
    (pattern) rows are checked by their literal fragments."""
    import re

    documented, patterns = _documented_sensor_names()
    src = []
    for dirpath, _dirs, files in os.walk(os.path.join(REPO, "cruise_control_tpu")):
        for fn in files:
            if fn.endswith(".py"):
                with open(os.path.join(dirpath, fn)) as f:
                    src.append(f.read())
    blob = "\n".join(src)

    def in_source(name: str) -> bool:
        if name in blob:
            return True
        # f-string-built families (f"executor.recovery.{name}",
        # f"analyzer.engine-cache-{name}"): accept a documented name whose
        # prefix appears in source immediately followed by a placeholder
        for i, ch in enumerate(name):
            if ch in ".-" and name[: i + 1] + "{" in blob:
                return True
        return False

    ghosts = [n for n in documented if not in_source(n)]
    assert not ghosts, f"docs/sensors.md rows with no source analog: {ghosts}"
    for p in patterns:
        # ^anomaly\-detector\.[a-z0-9_-]+\.rate$ -> fragments around the
        # placeholder must both appear in source
        frags = [
            re.sub(r"\\(.)", r"\1", frag)
            for frag in re.split(r"\[[^\]]+\]\+", p.strip("^$"))
        ]
        for frag in frags:
            assert frag.strip(".") == "" or frag in blob or frag.strip(".") in blob, (
                f"pattern fragment {frag!r} from docs/sensors.md not in source"
            )
