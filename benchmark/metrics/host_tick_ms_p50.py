"""Serving host: the median host tick of the window (host clock). It sets
the fleet's pace (session_ticks_per_s ~ sessions / median tick), and is the
steadier statistic beside host_tick_ms_p95."""

import numpy as np


def read(run):
    ticks = run.raw.get("tick_ms")
    if not ticks:
        return None
    return float(np.percentile(ticks, 50))
