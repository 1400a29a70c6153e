"""Device core (tpu/backend.py MultiSessionDeviceCore._fence_wait): ms per
host tick the host blocked on device work it had dispatched, from
ggrs_async_fence_stall_ms (program counter)."""

from benchmark.metrics._span import hist_sum, per


def read(run):
    return per(run, hist_sum(run, "ggrs_async_fence_stall_ms"), "host_ticks")
