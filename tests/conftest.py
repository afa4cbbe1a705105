"""Test config: force an 8-device virtual CPU mesh so multi-chip sharding
paths are exercised without TPU hardware (chip_smoke.py and benchmark/
run on the real chip). The suite never reaches a TPU: jax_platforms is
pinned to cpu before any backend is initialized."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import sys  # noqa: E402
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
