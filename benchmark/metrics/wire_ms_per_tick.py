"""Protocol plane (network/pump.py, network/endpoint_batch.py): ms per host
tick in ggrs_host_tax_ms{phase=pump,endpoint,encode} (program counter)."""

from benchmark.metrics._tax import tax_ms_per_tick


def read(run):
    return tax_ms_per_tick(run, ("pump", "endpoint", "encode"))
