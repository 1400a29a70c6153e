"""Kernels (tpu/pallas_tiled.py): the sharded entity-tiled kernel's share of
its HBM roofline per chip, in % (each chip moves its entity shard's bytes;
benchmark/metrics/_synctest.py says why HBM)."""

from benchmark.metrics._synctest import kernel_hbm_roofline as read  # noqa: F401
