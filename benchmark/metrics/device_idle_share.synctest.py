"""Device, synctest cells: % of the traced window the chips were idle."""

from benchmark.metrics._idle import idle_share


def read(run):
    return idle_share(run) if "check_distance" in run.raw else None
