"""Sessions (sessions/, sync_layer.py): frames resimulated per session-tick,
the sum of ggrs_rollback_depth_frames over the window's session-ticks
(program counter; a count the traffic fixes)."""


def read(run):
    hist = run.counters.get("ggrs_rollback_depth_frames")
    ticks = run.raw.get("session_ticks")
    if not hist or not ticks:
        return None
    return sum(v["sum"] for v in hist["values"].values()) / ticks
