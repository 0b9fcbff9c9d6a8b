"""Test configuration: force CPU with 8 virtual devices so multi-chip sharding
logic is testable without TPU hardware (SURVEY §4: the reference tests
distributed semantics in-process with local[N]; the JAX equivalent is
xla_force_host_platform_device_count).

The platform is forced through jax.config so the suite runs on the host
backend whatever JAX_PLATFORMS says (the backend initializes lazily, so this
works as long as it runs before any device use): unit tests — notably the
float64 finite-difference gradient checks — need the CPU, and a chip belongs
to one process at a time, so a test run must never claim it. chip_smoke.py
is what exercises the real chip.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")
# float64 needed for finite-difference gradient checks
jax.config.update("jax_enable_x64", True)
