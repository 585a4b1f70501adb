"""Test harness configuration.

Tests run on a virtual 8-device CPU mesh so multi-chip sharding logic is
exercised without TPU hardware (the driver separately dry-runs the
multi-chip path). The platform is pinned to the CPU here, before any
computation runs, whatever JAX_PLATFORMS the environment exports: the
suite must never take a chip. On the chip the program is proven by
`python chip_smoke.py` instead.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import sys  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))
from stellard_tpu.utils.xlacache import enable_compilation_cache  # noqa: E402

enable_compilation_cache()

import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _heap_as_found():
    """A node owns the process's old generation from setup() to stop()
    (node/heapaging.py). A test file that leaves one running must not
    hand the next file of its worker a frozen heap and a threshold out
    of reach: what it left is given back here."""
    yield
    from stellard_tpu.node.heapaging import HEAP_AGING

    while HEAP_AGING.owners:
        HEAP_AGING.release()
