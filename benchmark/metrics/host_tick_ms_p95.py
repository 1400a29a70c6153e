"""End to end, serve cells: the 95th percentile, over all host ticks of the
window, of one fleet frame's wall time (every peer's submit_input plus
SessionHost.tick), against the 16.7 ms frame budget (host clock)."""

import numpy as np


def read(run):
    ticks = run.raw.get("tick_ms")
    if not ticks:
        return None
    return float(np.percentile(ticks, 95))
