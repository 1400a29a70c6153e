"""Lightweight per-phase tracing.

The reference ships no tracing at all (SURVEY.md §5); its only perf
instrumentation is byte counters in the protocol. Here every hot phase
(save/load/advance/fused-tick/poll) can be timed with nested spans at
near-zero cost when disabled. Device work is asynchronous under jax, so
spans measure host-side dispatch unless the caller blocks; the fused-tick
span in the backend brackets the dispatch + any forced sync, which is the
latency the session actually observes.

The span table is a registry histogram, `ggrs_span_ms{span=<path>}`, so
spans reach every reader of the metrics registry (the JSON snapshot, the
Prometheus export) as an ordinary instrument. A span can also feed one
other bound histogram child from the same clock reads (`feed=`), so a
phase that is both a span and a metric is timed once.
"""

from __future__ import annotations

from collections.abc import Mapping
from time import perf_counter_ns
from typing import Dict, Iterator, List, Optional

from ..errors import ConfigError
from ..obs.metrics import LOG2_BUCKETS_MS, BoundHistogram, MetricsRegistry


class _NoSpan:
    """The disabled span: one shared, stateless context object."""

    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False


_NO_SPAN = _NoSpan()


class _Span:
    """One timed region: a single perf_counter_ns pair feeds the span's
    ggrs_span_ms child (None when the tracer is off) and the caller's
    `feed` child (None when not asked for)."""

    __slots__ = ("_stack", "_name", "_child", "_feed", "_note", "_t0")

    def __init__(self, stack, name, child, feed, note):
        self._stack = stack
        self._name = name
        self._child = child
        self._feed = feed
        self._note = note

    def __enter__(self) -> None:
        if self._note is not None:
            # a named region in xprof / TensorBoard profiles, aligning
            # host-side phases with the device timeline
            self._note.__enter__()
        if self._stack is not None:
            self._stack.append(self._name)
        self._t0 = perf_counter_ns()

    def __exit__(self, exc_type, exc, tb) -> bool:
        ms = (perf_counter_ns() - self._t0) / 1e6
        if self._note is not None:
            self._note.__exit__(None, None, None)
        if self._stack is not None:
            self._stack.pop()
        if self._child is not None:
            self._child.observe(ms)
        if self._feed is not None:
            self._feed.observe(ms)
        return False


class SpanTable(Mapping):
    """Read view of a tracer's spans: path -> its ggrs_span_ms child
    (`.count`, `.sum` in ms), for every span observed since the last
    reset. `clear()` zeroes the spans in the registry."""

    __slots__ = ("_hist",)

    def __init__(self, hist):
        self._hist = hist

    def _observed(self) -> Dict[str, BoundHistogram]:
        return {key[0]: child
                for key, child in self._hist.bound_children().items()
                if child.count}

    def __getitem__(self, path: str) -> BoundHistogram:
        return self._observed()[path]

    def __iter__(self) -> Iterator[str]:
        return iter(self._observed())

    def __len__(self) -> int:
        return len(self._observed())

    def clear(self) -> None:
        self._hist.reset()


class Tracer:
    """Aggregating tracer; `span()` is a shared no-op context when
    disabled. `registry` defaults to the process-wide telemetry
    registry."""

    def __init__(self, enabled: bool = True, xprof: bool = False,
                 registry: Optional[MetricsRegistry] = None):
        self.enabled = enabled
        # must precede the xprof assignment: the setter resolves the
        # annotation class, and this default would otherwise clobber it
        self._annotation_cls = None
        # also emit jax.profiler.TraceAnnotation regions so spans appear in
        # xprof/TensorBoard device profiles (SURVEY.md §5: xprof hooks)
        self.xprof = xprof
        if registry is None:
            from ..obs.telemetry import GLOBAL_TELEMETRY

            registry = GLOBAL_TELEMETRY.registry
        self._hist = registry.histogram(
            "ggrs_span_ms",
            "host time inside each tracer span, by span path",
            ("span",),
            buckets=LOG2_BUCKETS_MS,
        )
        # path -> bound child: a span costs one dict lookup, not a labels()
        # call (registry resets zero children in place, so these stay valid)
        self._children: Dict[str, BoundHistogram] = {}
        self._stack: List[str] = []

    @property
    def xprof(self) -> bool:
        return self._xprof

    @xprof.setter
    def xprof(self, value: bool) -> None:
        self._xprof = value
        if value:
            # import once, outside any timed region, so the one-time import
            # cost never lands inside a span's measurement
            import jax.profiler

            self._annotation_cls = jax.profiler.TraceAnnotation

    @property
    def stats(self) -> SpanTable:
        return SpanTable(self._hist)

    def span(self, name: str, absolute: bool = False, feed=None):
        """`absolute` records under `name` alone regardless of the active
        span stack — for phases reached through multiple parents (e.g. the
        P2P message pump, called both standalone and inside the advance
        span) whose totals must land in ONE row to be comparable.

        `feed`: a bound histogram child that also receives the span's
        duration in ms, timed even while the tracer is off (callers pass
        it only while telemetry is on)."""
        if not self.enabled:
            if feed is None:
                return _NO_SPAN
            return _Span(None, name, None, feed, None)
        if absolute or not self._stack:
            path = name
        else:
            path = "/".join(self._stack) + "/" + name
        child = self._children.get(path)
        if child is None:
            child = self._children[path] = self._hist.labels(path)
        return _Span(self._stack, name, child, feed, self.annotation(path))

    def annotation(self, path: str):
        """A profiler region named `path`, or None unless the tracer is on
        in xprof mode. For regions outside the span table (GC pauses)."""
        if self.enabled and self._xprof and self._annotation_cls is not None:
            return self._annotation_cls(path)
        return None

    def report(self, sort_by: str = "name") -> str:
        """`sort_by="total"` surfaces hot spans first (descending total
        time); `"name"` keeps the stable alphabetical listing. The name
        column sizes itself to the longest span path, so deeply nested
        spans no longer break column alignment."""
        stats = dict(self.stats)
        if sort_by == "name":
            names = sorted(stats)
        elif sort_by == "total":
            names = sorted(stats, key=lambda n: (-stats[n].sum, n))
        else:
            raise ConfigError(
                f"sort_by must be 'name' or 'total', got {sort_by!r}"
            )
        width = max([len("span")] + [len(n) for n in names])
        lines = [f"{'span':{width}s} {'count':>8s} {'mean ms':>10s} {'total ms':>10s}"]
        for name in names:
            s = stats[name]
            lines.append(
                f"{name:{width}s} {s.count:8d} {s.sum / s.count:10.4f} {s.sum:10.2f}"
            )
        return "\n".join(lines)

    def reset(self) -> None:
        self._hist.reset()


# process-wide default tracer, disabled unless opted in
GLOBAL_TRACER = Tracer(enabled=False)


def enable_global_tracing(xprof: bool = False) -> Tracer:
    GLOBAL_TRACER.enabled = True
    GLOBAL_TRACER.xprof = xprof
    return GLOBAL_TRACER
