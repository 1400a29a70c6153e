"""Kernels (tpu/pallas_tiled.py, the entity-tiled kernel under shard_map):
device us of the kernel per frame advanced, per chip (device trace)."""

from benchmark.metrics._synctest import kernel_us_per_frame as read  # noqa: F401
