"""Garbage-collector pauses as a metric: `ggrs_gc_pause_ms{generation}`.

A CPython collection stops the host thread wherever it happens to be, so
its time otherwise lands inside whatever span was open (or in an idle gap
of the device trace named after an unrelated phase). One `gc.callbacks`
hook, installed once when `ggrs_tpu.obs` is imported, times every
collection from its `start` to its `stop` callback. A collection that
starts while telemetry is off costs the hook one flag check at each end.
While the tracer's xprof mode is on it also opens a `host/gc`
TraceAnnotation over the pause; the name is absolute and the tracer's
span stack is not touched, so a collection never nests under the span it
interrupted.
"""

from __future__ import annotations

import gc
from time import perf_counter_ns

from .metrics import LOG2_BUCKETS_MS
from .telemetry import GLOBAL_TELEMETRY


class GcPauseWatch:
    __slots__ = ("_pause", "_t0", "_note", "_tracer")

    def __init__(self):
        hist = GLOBAL_TELEMETRY.registry.histogram(
            "ggrs_gc_pause_ms",
            "host time stopped in one CPython garbage collection, by "
            "generation",
            ("generation",),
            buckets=LOG2_BUCKETS_MS,
        )
        self._pause = tuple(hist.labels(g) for g in range(3))
        self._t0 = 0
        self._note = None
        self._tracer = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            if not GLOBAL_TELEMETRY.enabled:
                return
            tracer = self._tracer
            if tracer is None:
                # bound on first use: utils.tracing imports this package
                from ..utils.tracing import GLOBAL_TRACER

                tracer = self._tracer = GLOBAL_TRACER
            note = self._note = tracer.annotation("host/gc")
            if note is not None:
                note.__enter__()
            self._t0 = perf_counter_ns()
            return
        if self._t0 == 0:
            return  # this collection started with telemetry off
        ms = (perf_counter_ns() - self._t0) / 1e6
        self._t0 = 0
        if self._note is not None:
            self._note.__exit__(None, None, None)
            self._note = None
        self._pause[info["generation"]].observe(ms)


_WATCH = None


def install_gc_watch() -> GcPauseWatch:
    """Install the process's one GC pause hook (idempotent)."""
    global _WATCH
    if _WATCH is None:
        _WATCH = GcPauseWatch()
        gc.callbacks.append(_WATCH)
    return _WATCH
