"""Serving host, the CPython runtime under it (obs/gc_pause.py): ms per
host tick stopped in garbage collections, ggrs_gc_pause_ms summed over
generations (program counter)."""

from benchmark.metrics._span import hist_sum, per


def read(run):
    return per(run, hist_sum(run, "ggrs_gc_pause_ms"), "host_ticks")
