"""Test harness config: force JAX onto CPU with 8 virtual devices so the
multi-chip sharding paths compile and run without TPU hardware (the pattern
recommended in SURVEY.md §4: XLA_FLAGS=--xla_force_host_platform_device_count=8).

The tests never take the chip: the platform is pinned through jax.config
before any backend initialises (pytest loads this conftest before test
modules touch jax.devices()), so a plain ``pytest`` on a machine with a TPU
still runs on the CPU and leaves the chip to whichever process holds it.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
