"""Session telemetry: metrics registry, flight recorder, desync forensics.

Usage:

    from ggrs_tpu.obs import enable_global_telemetry
    enable_global_telemetry(dump_dir="/tmp/ggrs")   # before/after start, any time
    ...
    snap = session.telemetry()       # one structured snapshot (dict)
    text = GLOBAL_TELEMETRY.prometheus()  # Prometheus text format

Everything is near-zero-cost while disabled (the default): instrumentation
sites check `GLOBAL_TELEMETRY.enabled` and skip. Importing this package
does not import jax.
"""

from .metrics import (
    DISPATCH_DEPTH_BUCKETS,
    FRAME_ADVANTAGE_BUCKETS,
    LOG2_BUCKETS,
    LOG2_BUCKETS_MS,
    SESSION_COUNT_BUCKETS,
    SHARD_IMBALANCE_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from .recorder import FlightEvent, FlightRecorder, jsonable
from .telemetry import GLOBAL_TELEMETRY, Telemetry, enable_global_telemetry
from .gc_pause import install_gc_watch

install_gc_watch()

__all__ = [
    "DISPATCH_DEPTH_BUCKETS",
    "FRAME_ADVANTAGE_BUCKETS",
    "LOG2_BUCKETS",
    "LOG2_BUCKETS_MS",
    "Counter",
    "FlightEvent",
    "FlightRecorder",
    "Gauge",
    "GLOBAL_TELEMETRY",
    "Histogram",
    "MetricsRegistry",
    "SESSION_COUNT_BUCKETS",
    "SHARD_IMBALANCE_BUCKETS",
    "Telemetry",
    "enable_global_telemetry",
    "jsonable",
]
