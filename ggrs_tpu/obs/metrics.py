"""Metrics primitives: Counter / Gauge / Histogram behind one registry.

The reference ships byte counters only (SURVEY.md §5); this is the
instrument layer everything else plugs into. Design constraints, in order:

1. Near-zero cost when telemetry is disabled — instruments are only
   *updated* behind `GLOBAL_TELEMETRY.enabled` checks at the call sites
   (the Tracer.span idiom), so creating them eagerly is free.
2. Bound children stay valid across `reset()` — endpoints and backends
   pre-bind labeled children once in their constructors, so a reset must
   zero the underlying cells in place, never replace them.
3. Histograms use FIXED log-scale buckets (powers of two) so two
   snapshots are always mergeable/comparable and the export never
   depends on observed data.
"""

from __future__ import annotations

from bisect import bisect_left
from typing import Dict, Iterable, List, Optional, Tuple

from ..errors import ConfigError

# default fixed log-scale buckets (upper bounds, `le` semantics);
# +Inf is implicit as the overflow bucket
LOG2_BUCKETS: Tuple[float, ...] = tuple(float(2**k) for k in range(0, 11))
# millisecond durations need sub-ms resolution (fence stalls, RTTs)
LOG2_BUCKETS_MS: Tuple[float, ...] = tuple(2.0**k for k in range(-3, 11))
# frame advantage is signed: symmetric log-scale around zero
FRAME_ADVANTAGE_BUCKETS: Tuple[float, ...] = (
    -64.0, -32.0, -16.0, -8.0, -4.0, -2.0, -1.0, 0.0,
    1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0,
)
# session-count distributions (megabatch sizes, admission-queue depths):
# log2 up to the largest host fleet a single device core plausibly serves
SESSION_COUNT_BUCKETS: Tuple[float, ...] = tuple(
    float(2**k) for k in range(0, 13)
)
# routed dispatch depth (window slots actually executed per dispatch):
# finer than log2 in the interactive range so adjacent depth variants
# (3 vs 6 slots) land in distinct buckets; le=1 isolates the megabatch
# zero-rollback fast path, which the dispatch smoke gate asserts on
DISPATCH_DEPTH_BUCKETS: Tuple[float, ...] = (
    1.0, 2.0, 3.0, 4.0, 6.0, 8.0, 12.0, 16.0, 24.0, 32.0,
)
# max/mean live rows per session-mesh shard per megabatch dispatch:
# 1.0 is a perfectly balanced dispatch, the mesh's shard count the
# worst case (every row on one shard); sub-2 resolution is where the
# host's slot->shard affinity either works or doesn't
SHARD_IMBALANCE_BUCKETS: Tuple[float, ...] = (
    1.0, 1.25, 1.5, 2.0, 3.0, 4.0, 6.0, 8.0, 16.0,
)


def _escape_label(value: str) -> str:
    return value.replace("\\", "\\\\").replace("\n", "\\n").replace('"', '\\"')


def _restore_instrument(kind, name, help, labelnames, buckets):
    """Unpickle target for instruments: get-or-create from the process's
    GLOBAL registry, so a deserialized object graph (a fleet wire ticket
    carrying sessions between host processes, ggrs_tpu.fleet.ticket)
    lands on LIVE instruments in the receiving process — its increments
    show up in that process's exporters — instead of an orphaned copy
    whose updates nobody can scrape."""
    from .telemetry import GLOBAL_TELEMETRY

    reg = GLOBAL_TELEMETRY.registry
    if kind == "counter":
        return reg.counter(name, help, labelnames)
    if kind == "gauge":
        return reg.gauge(name, help, labelnames)
    return reg.histogram(name, help, labelnames, buckets=buckets)


def _restore_bound(kind, name, help, labelnames, buckets, key):
    return _restore_instrument(kind, name, help, labelnames, buckets).labels(*key)


def _fmt_value(v: float) -> str:
    # integers render without a trailing .0 — easier on the eyes and on
    # naive parsers; everything else keeps full float repr
    if isinstance(v, float) and v.is_integer() and abs(v) < 1e15:
        return str(int(v))
    return repr(float(v))


class _BoundPickle:
    """Bound children pickle BY NAME, not by cell: unpickling re-binds
    through the receiving process's global registry (see
    _restore_bound), so objects that pre-bind labeled children in their
    constructors — endpoints, input queues — survive a cross-process
    hop (fleet wire tickets) with live instruments."""

    __slots__ = ()

    def __reduce__(self):
        inst, key = self._origin
        return (_restore_bound, (
            inst.kind, inst.name, inst.help, inst.labelnames,
            getattr(inst, "buckets", None), key,
        ))


class BoundCounter(_BoundPickle):
    """A counter child bound to one label-value tuple."""

    __slots__ = ("_cell", "_origin")

    def __init__(self, cell: List[float]):
        self._cell = cell

    def inc(self, amount: float = 1.0) -> None:
        self._cell[0] += amount

    @property
    def value(self) -> float:
        return self._cell[0]


class BoundGauge(_BoundPickle):
    __slots__ = ("_cell", "_origin")

    def __init__(self, cell: List[float]):
        self._cell = cell

    def set(self, value: float) -> None:
        self._cell[0] = value

    def inc(self, amount: float = 1.0) -> None:
        self._cell[0] += amount

    def dec(self, amount: float = 1.0) -> None:
        self._cell[0] -= amount

    @property
    def value(self) -> float:
        return self._cell[0]


class _HistCell:
    """Per-child histogram state: non-cumulative bucket counts + sum."""

    __slots__ = ("counts", "sum", "count")

    def __init__(self, n_buckets: int):
        self.counts = [0] * (n_buckets + 1)  # +1: the +Inf overflow bucket
        self.sum = 0.0
        self.count = 0

    def zero(self) -> None:
        for i in range(len(self.counts)):
            self.counts[i] = 0
        self.sum = 0.0
        self.count = 0


class BoundHistogram(_BoundPickle):
    __slots__ = ("_cell", "_buckets", "_origin")

    def __init__(self, cell: _HistCell, buckets: Tuple[float, ...]):
        self._cell = cell
        self._buckets = buckets

    def observe(self, value: float) -> None:
        c = self._cell
        c.counts[bisect_left(self._buckets, value)] += 1
        c.sum += value
        c.count += 1

    @property
    def count(self) -> int:
        return self._cell.count

    @property
    def sum(self) -> float:
        return self._cell.sum


class _Instrument:
    """Shared child-management for the three instrument kinds."""

    kind = "untyped"

    def __init__(self, name: str, help: str, labelnames: Tuple[str, ...]):
        self.name = name
        self.help = help
        self.labelnames = tuple(labelnames)
        self._children: Dict[Tuple[str, ...], object] = {}
        self._bound: Dict[Tuple[str, ...], object] = {}

    def _new_cell(self):
        raise NotImplementedError

    def _bind(self, cell):
        raise NotImplementedError

    def labels(self, *labelvalues) -> object:
        key = tuple(str(v) for v in labelvalues)
        if len(key) != len(self.labelnames):
            raise ConfigError(
                f"{self.name}: expected {len(self.labelnames)} label values "
                f"({self.labelnames}), got {len(key)}"
            )
        bound = self._bound.get(key)
        if bound is None:
            cell = self._children.get(key)
            if cell is None:
                cell = self._new_cell()
                self._children[key] = cell
            bound = self._bind(cell)
            bound._origin = (self, key)  # pickle-by-name backref
            self._bound[key] = bound
        return bound

    def bound_children(self) -> Dict[Tuple[str, ...], object]:
        """Every child created so far, label tuple -> bound child."""
        return {key: self.labels(*key) for key in self._children}

    # unlabeled convenience: metric.inc()/set()/observe() act on the () child
    def _default(self):
        if self.labelnames:
            raise ConfigError(
                f"{self.name} is labeled {self.labelnames}; use .labels()"
            )
        return self.labels()

    def reset(self) -> None:
        """Zero every child IN PLACE — bound children stay valid."""
        for cell in self._children.values():
            if isinstance(cell, _HistCell):
                cell.zero()
            else:
                cell[0] = 0.0

    def __reduce__(self):
        # instruments pickle by name and re-resolve from the receiving
        # process's global registry — the same live-rebinding contract
        # as bound children (_BoundPickle)
        return (_restore_instrument, (
            self.kind, self.name, self.help, self.labelnames,
            getattr(self, "buckets", None),
        ))


class Counter(_Instrument):
    kind = "counter"

    def _new_cell(self):
        return [0.0]

    def _bind(self, cell):
        return BoundCounter(cell)

    def inc(self, amount: float = 1.0) -> None:
        self._default().inc(amount)

    @property
    def value(self) -> float:
        return self._default().value

    def snapshot(self) -> dict:
        return {
            "type": self.kind,
            "help": self.help,
            "values": {
                ",".join(k) if k else "": cell[0]
                for k, cell in self._children.items()
            },
        }

    def prometheus_lines(self) -> List[str]:
        lines = _header(self)
        for key, cell in sorted(self._children.items()):
            lines.append(f"{self.name}{_labelset(self.labelnames, key)} {_fmt_value(cell[0])}")
        return lines


class Gauge(Counter):
    kind = "gauge"

    def _bind(self, cell):
        return BoundGauge(cell)

    def set(self, value: float) -> None:
        self._default().set(value)

    def dec(self, amount: float = 1.0) -> None:
        self._default().dec(amount)


class Histogram(_Instrument):
    kind = "histogram"

    def __init__(
        self,
        name: str,
        help: str,
        labelnames: Tuple[str, ...],
        buckets: Optional[Iterable[float]] = None,
    ):
        super().__init__(name, help, labelnames)
        b = tuple(float(x) for x in (buckets if buckets is not None else LOG2_BUCKETS))
        assert b == tuple(sorted(b)) and len(b) > 0, "buckets must be sorted"
        self.buckets = b

    def _new_cell(self):
        return _HistCell(len(self.buckets))

    def _bind(self, cell):
        return BoundHistogram(cell, self.buckets)

    def observe(self, value: float) -> None:
        self._default().observe(value)

    def snapshot(self) -> dict:
        values = {}
        for key, cell in self._children.items():
            values[",".join(key) if key else ""] = {
                "count": cell.count,
                "sum": cell.sum,
                "buckets": {
                    **{
                        _fmt_value(le): cell.counts[i]
                        for i, le in enumerate(self.buckets)
                    },
                    "+Inf": cell.counts[-1],
                },
            }
        return {"type": self.kind, "help": self.help, "values": values}

    def prometheus_lines(self) -> List[str]:
        lines = _header(self)
        names = self.labelnames + ("le",)
        for key, cell in sorted(self._children.items()):
            cum = 0
            for i, le in enumerate(self.buckets):
                cum += cell.counts[i]
                lines.append(
                    f"{self.name}_bucket{_labelset(names, key + (_fmt_value(le),))} {cum}"
                )
            lines.append(
                f"{self.name}_bucket{_labelset(names, key + ('+Inf',))} {cell.count}"
            )
            base = _labelset(self.labelnames, key)
            lines.append(f"{self.name}_sum{base} {_fmt_value(cell.sum)}")
            lines.append(f"{self.name}_count{base} {cell.count}")
        return lines


def _header(m: _Instrument) -> List[str]:
    lines = []
    if m.help:
        lines.append(f"# HELP {m.name} {m.help}")
    lines.append(f"# TYPE {m.name} {m.kind}")
    return lines


def _labelset(names: Tuple[str, ...], values: Tuple[str, ...]) -> str:
    if not names:
        return ""
    pairs = ",".join(
        f'{n}="{_escape_label(v)}"' for n, v in zip(names, values)
    )
    return "{" + pairs + "}"


class MetricsRegistry:
    """Get-or-create instrument registry. One per Telemetry object; the
    process-wide one lives on GLOBAL_TELEMETRY."""

    def __init__(self):
        self._metrics: Dict[str, _Instrument] = {}

    def _get(self, cls, name, help, labelnames, **kw) -> _Instrument:
        m = self._metrics.get(name)
        if m is None:
            m = cls(name, help, tuple(labelnames), **kw)
            self._metrics[name] = m
        elif type(m) is not cls:
            raise ConfigError(
                f"metric {name!r} already registered as {m.kind}, not {cls.kind}"
            )
        return m

    def counter(self, name: str, help: str = "", labelnames=()) -> Counter:
        return self._get(Counter, name, help, labelnames)

    def gauge(self, name: str, help: str = "", labelnames=()) -> Gauge:
        return self._get(Gauge, name, help, labelnames)

    def histogram(
        self, name: str, help: str = "", labelnames=(), buckets=None
    ) -> Histogram:
        return self._get(Histogram, name, help, labelnames, buckets=buckets)

    def get(self, name: str) -> Optional[_Instrument]:
        return self._metrics.get(name)

    def snapshot(self) -> dict:
        return {name: m.snapshot() for name, m in sorted(self._metrics.items())}

    def prometheus_lines(self) -> List[str]:
        lines: List[str] = []
        for _, m in sorted(self._metrics.items()):
            lines.extend(m.prometheus_lines())
        return lines

    def reset(self) -> None:
        """Zero every instrument in place (bound children stay valid)."""
        for m in self._metrics.values():
            m.reset()
