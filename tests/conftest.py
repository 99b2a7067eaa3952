"""Test configuration.

Force the CPU backend with 8 virtual devices BEFORE jax initializes, so
sharding/mesh tests exercise the multi-chip code paths without TPU hardware.
The chip itself is reached only through `chip_smoke.py`; the v5e compile
rehearsals (tests/test_chip_compile.py) describe the chip without one.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# persistent XLA compile cache: the real-data device/fused fixtures compile
# full-envelope programs (minutes of XLA on a small host); caching them
# across runs keeps the default suite affordable. An operator's
# JAX_COMPILATION_CACHE_DIR wins; otherwise the repo's one fixed path.
from racon_tpu.sched import default_cache_dir  # noqa: E402

os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", default_cache_dir())
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import pathlib

import pytest

REFERENCE_DATA = pathlib.Path("/root/reference/test/data")


@pytest.fixture(scope="session")
def reference_data():
    if not REFERENCE_DATA.is_dir():
        pytest.skip("reference test data not available")
    return REFERENCE_DATA
