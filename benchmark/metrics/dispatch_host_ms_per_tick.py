"""Device core, host side (serve/host.py _pump_device and _resident_pump
over tpu/backend.py MultiSessionDeviceCore): ms per host tick in span
host/dispatch, coalescing rows and enqueuing device programs (program
counter)."""

from benchmark.metrics._span import per, span_sum


def read(run):
    return per(run, span_sum(run, "host/dispatch"), "host_ticks")
