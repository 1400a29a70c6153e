"""End to end, mesh synctest cells: rollback_frames_per_s over the cell's
chips (host clock). A metric of its own so that it carries its own bound:
the mesh cell is device-bound and spreads ~0.06 %, the one-chip cell ~3 %."""

from benchmark.metrics.rollback_frames_per_s import read  # noqa: F401
