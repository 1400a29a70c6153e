"""End to end, synctest cells: frames resimulated by SyncTest rollbacks over
the whole window, frames advanced x check_distance / window seconds, the
window ending in block_until_ready (host clock). BASELINE.json's unit."""


def read(run):
    r = run.raw
    if "check_distance" not in r:
        return None
    return r["frames"] * r["check_distance"] / r["window_s"]
