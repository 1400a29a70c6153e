"""Test env: jax on the CPU with 8 virtual devices, so the multi-chip
sharding tests run without TPU hardware. XLA_FLAGS is read when the CPU
client is created, so setting it here (before any backend starts) works.

The native library is built once, before any test module is collected:
the native suites decide their `skipif(not available())` marks at import
time, so a library built later (by a fixture) would leave the count of
tests that run depending on whether an earlier run left the build
behind."""

import os
import subprocess
import warnings

_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test, deselected by -m 'not slow'"
    )
    if hasattr(config, "workerinput"):
        return  # an xdist worker: the controller built before spawning it
    from ggrs_tpu.native.build import build

    try:
        # incremental: a tree that is already built pays a no-op make;
        # without make/g++ this returns False and the native tests skip
        build()
    except (OSError, subprocess.CalledProcessError) as exc:
        warnings.warn(f"native build failed, native tests skip: {exc}")
