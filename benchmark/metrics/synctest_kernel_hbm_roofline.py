"""Kernels (tpu/pallas_core.py): the whole-batch SyncTest kernel's share of
its HBM roofline, in % (benchmark/metrics/_synctest.py says why HBM)."""

from benchmark.metrics._synctest import kernel_hbm_roofline as read  # noqa: F401
