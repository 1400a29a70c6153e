"""Mesh (parallel/sharded.py, the sharded tiled kernel's psum): device ms of
collective ops (all-reduce and kin) per frame advanced in the traced span,
per chip (device trace)."""

from benchmark.trace_reduce import is_collective, op_seconds


def read(run):
    frames = run.traced.get("frames")
    if run.trace is None or not frames:
        return None
    coll_s = op_seconds(run.trace, is_collective)
    return coll_s * 1e3 / frames if coll_s > 0 else None
