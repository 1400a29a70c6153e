"""Tracer spans: one table, the ggrs_span_ms histogram of a registry."""

import gc

import pytest

from ggrs_tpu.obs import GLOBAL_TELEMETRY, MetricsRegistry
from ggrs_tpu.utils.tracing import Tracer


def _spans(reg):
    return reg.snapshot()["ggrs_span_ms"]["values"]


def test_spans_aggregate_and_nest():
    reg = MetricsRegistry()
    t = Tracer(enabled=True, registry=reg)
    for _ in range(3):
        with t.span("tick"):
            with t.span("resim"):
                pass
    assert t.stats["tick"].count == 3
    assert t.stats["tick/resim"].count == 3
    assert t.stats["tick"].sum >= t.stats["tick/resim"].sum
    assert "tick/resim" in t.report()
    # the table IS the registry histogram: same rows, same sums
    spans = _spans(reg)
    assert spans["tick"]["count"] == 3
    assert spans["tick/resim"]["sum"] == t.stats["tick/resim"].sum


def test_xprof_annotated_spans_record_normally():
    """xprof mode wraps spans in jax.profiler.TraceAnnotation regions;
    aggregation semantics are unchanged."""
    t = Tracer(enabled=True, xprof=True, registry=MetricsRegistry())
    # the constructor path must actually resolve the annotation class —
    # a None here means spans silently skip xprof region emission
    assert t._annotation_cls is not None
    with t.span("outer"):
        with t.span("inner", absolute=True):
            pass
    assert t.stats["outer"].count == 1
    assert t.stats["inner"].count == 1
    assert t.annotation("host/gc") is not None
    t.enabled = False
    assert t.annotation("host/gc") is None


def test_disabled_tracer_records_nothing():
    reg = MetricsRegistry()
    t = Tracer(enabled=False, registry=reg)
    with t.span("x"):
        pass
    assert not t.stats
    assert _spans(reg) == {}
    # one shared no-op context, no per-call object
    assert t.span("x") is t.span("y", absolute=True)


def test_report_sizes_name_column_to_longest_path():
    t = Tracer(enabled=True, registry=MetricsRegistry())
    long_name = "session/" + "x" * 60
    with t.span(long_name):
        pass
    with t.span("tick"):
        pass
    lines = t.report().splitlines()
    # the name column sizes to the longest path, so every row's numeric
    # fields start at the same offset — long paths no longer shift them
    name_width = len(long_name)
    count_end = name_width + 1 + 8  # "{name:{w}} {count:>8d}"
    for line in lines:
        assert len(line) > count_end
        field = line[name_width + 1 : count_end].strip()
        assert field in ("count",) or field.isdigit(), (
            f"count column misaligned in {line!r}"
        )
    row = next(l for l in lines if long_name in l)
    assert row.split()[0] == long_name


def test_report_sort_by_total_surfaces_hot_spans_first():
    import time

    t = Tracer(enabled=True, registry=MetricsRegistry())
    with t.span("cold"):
        pass
    with t.span("hot"):
        time.sleep(0.002)
    rows = t.report(sort_by="total").splitlines()[1:]
    assert rows[0].split()[0] == "hot"
    assert rows[1].split()[0] == "cold"

    with pytest.raises(ValueError):
        t.report(sort_by="mean")


@pytest.mark.parametrize("enabled", [True, False])
def test_span_feed_shares_one_clock_pair(enabled):
    """A fed span's duration lands in the feed child exactly as in its own
    row; with the tracer off the feed is still timed, the table not."""
    reg = MetricsRegistry()
    t = Tracer(enabled=enabled, registry=reg)
    feed = reg.histogram("fed_ms", "", ("phase",)).labels("parse")
    for _ in range(4):
        with t.span("host/advance", absolute=True, feed=feed):
            sum(range(1000))
    assert feed.count == 4 and feed.sum > 0
    if enabled:
        assert t.stats["host/advance"].sum == feed.sum
    else:
        assert not t.stats


def test_reset_and_stats_clear_zero_the_registry_rows():
    reg = MetricsRegistry()
    t = Tracer(enabled=True, registry=reg)
    with t.span("a"):
        pass
    t.stats.clear()
    assert not t.stats
    with t.span("a"):
        pass
    t.reset()
    assert _spans(reg)["a"]["count"] == 0


def test_gc_pauses_recorded_per_generation_only_with_telemetry_on():
    pause = GLOBAL_TELEMETRY.registry.get("ggrs_gc_pause_ms")
    assert pause is not None  # installed at `import ggrs_tpu.obs`
    was = GLOBAL_TELEMETRY.enabled
    try:
        GLOBAL_TELEMETRY.enabled = False
        pause.reset()
        gc.collect(2)
        assert sum(c.count for c in pause.bound_children().values()) == 0
        GLOBAL_TELEMETRY.enabled = True
        gc.collect(2)
        gc.collect(0)
        gen = {k[0]: c for k, c in pause.bound_children().items()}
        assert gen["2"].count >= 1 and gen["2"].sum > 0
        assert gen["0"].count >= 1
    finally:
        GLOBAL_TELEMETRY.enabled = was
        pause.reset()
