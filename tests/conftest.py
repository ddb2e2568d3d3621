"""Test config: an 8-device virtual CPU platform before any compute.

Multi-chip sharding paths run on a virtual device mesh here; the chip
itself is driven by chip_smoke.py, never by the tests.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# Persistent XLA compilation cache (common/compilation_cache.py): the suite
# is compile-dominated — dozens of Engine instances re-compile structurally
# identical programs (jit caches are per-instance, the disk cache is keyed
# by HLO fingerprint) — so both repeat suite runs and same-shape engines
# within one run load executables in ~ms instead of seconds.  Override the
# location with TEST_COMPILE_CACHE=; set it empty to disable.
from cruise_control_tpu.common.compilation_cache import (  # noqa: E402
    DEFAULT_CACHE_DIR,
    enable_persistent_cache,
)

enable_persistent_cache(os.environ.get("TEST_COMPILE_CACHE", DEFAULT_CACHE_DIR))


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: full-stack integration tests (embedded wire cluster)"
    )
