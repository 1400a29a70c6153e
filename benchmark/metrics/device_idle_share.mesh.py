"""Device, mesh cells: % of the traced window the chips were idle, averaged
over the cell's chips (device trace)."""

from benchmark.metrics._idle import idle_share as read  # noqa: F401
