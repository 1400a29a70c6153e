"""Device core (tpu/backend.py, tpu/mailbox.py): device dispatches per host
tick, (megabatches + resident driver dispatches) / host ticks, from the
core's own counters over the window (program counter)."""


def read(run):
    r = run.raw
    if "dispatches" not in r or not r.get("host_ticks"):
        return None
    return r["dispatches"] / r["host_ticks"]
