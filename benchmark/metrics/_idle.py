"""Shared by the device_idle_share readers: % of the traced window in which
no op ran on the device, averaged over the cell's chips (device trace)."""


def idle_share(run):
    t = run.trace
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
