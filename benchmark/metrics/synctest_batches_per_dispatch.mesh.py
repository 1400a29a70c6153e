"""Sessions, SyncTest, mesh cells: synctest_batches_per_dispatch, reported
beside the mesh cell's own end-to-end metric (program counters)."""

from benchmark.metrics.synctest_batches_per_dispatch import read  # noqa: F401
