"""Serving host (serve/host.py): ms per host tick in
ggrs_host_tax_ms{phase=parse,drain} (program counter)."""

from benchmark.metrics._tax import tax_ms_per_tick


def read(run):
    return tax_ms_per_tick(run, ("parse", "drain"))
