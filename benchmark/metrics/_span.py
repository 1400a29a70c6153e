"""Shared by the span readers (program counters, on in --trace 1 runs):
ms of one tracer span's ggrs_span_ms row, or of a whole histogram, per
host tick or per batch of the window. A program that keeps no span table
in its registry (no ggrs_span_ms) gives None, and so does one without the
histogram read."""


def span_sum(run, path):
    """Total ms in span `path` over the window."""
    spans = run.counters.get("ggrs_span_ms")
    if not spans:
        return None
    row = spans["values"].get(path)
    return row["sum"] if row else None


def hist_sum(run, name):
    """Total ms of histogram `name` over the window, summed over its
    labels; only where the program also keeps its spans in the registry
    (the same release timed both)."""
    hist = run.counters.get(name)
    if not hist or "ggrs_span_ms" not in run.counters:
        return None
    return sum(v["sum"] for v in hist["values"].values())


def per(run, total, unit):
    n = run.raw.get(unit)
    if total is None or not n:
        return None
    return total / n
