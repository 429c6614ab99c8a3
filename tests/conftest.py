"""Test configuration: run JAX on a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; sharding/collective tests run
against `--xla_force_host_platform_device_count=8` (the stand-in for the
reference's sbt-multi-jvm cluster tests, SURVEY.md §4).
"""

import os

# A hard override, not a setdefault: on a host with a chip JAX would pick the
# TPU, and the tests need the virtual CPU mesh and exact (non-emulated)
# float64.  The program itself runs on the chip through chip_smoke.py.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_ENABLE_X64"] = "1"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
