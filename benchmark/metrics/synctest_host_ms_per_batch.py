"""Sessions, SyncTest (tpu/sync_test.py): host ms per advance_frames batch,
span synctest/advance (input staging plus the batch program's enqueue)
over the window's batches (program counter)."""

from benchmark.metrics._span import per, span_sum


def read(run):
    return per(run, span_sum(run, "synctest/advance"), "batches")
