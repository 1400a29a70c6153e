"""Shared by the ggrs_host_tax_ms readers: ms of the given phases per host
tick over the window (program counters, on in --trace 1 runs)."""


def tax_ms_per_tick(run, phases):
    hist = run.counters.get("ggrs_host_tax_ms")
    ticks = run.raw.get("host_ticks")
    if not hist or not ticks:
        return None
    values = hist["values"]
    if not any(p in values for p in phases):
        return None
    return sum(values[p]["sum"] for p in phases if p in values) / ticks
