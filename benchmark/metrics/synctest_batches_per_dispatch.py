"""Sessions, SyncTest (tpu/sync_test.py): advance_frames batches per device
program the session dispatched, ggrs_synctest_batches_total over
ggrs_synctest_dispatches_total across the window (program counters). None
where the program keeps neither counter or dispatched nothing."""


def _total(run, name):
    counter = run.counters.get(name)
    if not counter:
        return None
    return sum(counter["values"].values())


def read(run):
    batches = _total(run, "ggrs_synctest_batches_total")
    dispatches = _total(run, "ggrs_synctest_dispatches_total")
    if batches is None or not dispatches:
        return None
    return batches / dispatches
