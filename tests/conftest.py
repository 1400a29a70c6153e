"""Test env: jax on the CPU with 8 virtual devices, so the multi-chip
sharding tests run without TPU hardware. XLA_FLAGS is read when the CPU
client is created, so setting it here (before any backend starts) works."""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")
