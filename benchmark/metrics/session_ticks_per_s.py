"""End to end, serve cells: session-ticks completed over the whole window
(frames advanced, summed over every hosted session) per second of window,
the window ending in block_until_ready on the host's device core (host
clock). Divided by 60 it is the sessions one chip serves at 60 Hz."""


def read(run):
    r = run.raw
    if "session_ticks" not in r:
        return None
    return r["session_ticks"] / r["window_s"]
