"""CPU tests of the benchmark: four virtual CPU devices, the checkout root
importable, JAX's persistent cache off (a CPU entry in the checkout's cache
would be carried to the chip)."""

import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
)
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _no_compile_cache(monkeypatch):
    from benchmark import harness

    monkeypatch.setattr(harness, "enable_cache", lambda: None)
