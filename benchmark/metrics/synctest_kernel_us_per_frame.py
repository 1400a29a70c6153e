"""Kernels (tpu/pallas_core.py, the whole-batch kernel): device us of the
fused SyncTest kernel per frame advanced in the traced span (device trace)."""

from benchmark.metrics._synctest import kernel_us_per_frame as read  # noqa: F401
