"""Device, serve cells: % of the traced window the chip was idle."""

from benchmark.metrics._idle import idle_share


def read(run):
    return idle_share(run) if "session_ticks" in run.raw else None
