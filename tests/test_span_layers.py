"""Spans and counters where the host's layers do their work: the served
tick split into pump/drain/advance/dispatch/lifecycle, the sessions' own
advance, the host core's fence, and the fused SyncTest host loop."""

import jax
import numpy as np
import pytest

from ggrs_tpu.models.ex_game import ExGame
from ggrs_tpu.network.sockets import InMemoryNetwork
from ggrs_tpu.obs import GLOBAL_TELEMETRY
from ggrs_tpu.serve import SessionHost
from ggrs_tpu.serve.loadgen import (
    build_matches,
    drive_scripted,
    held_scripts,
    sync_fleet,
)
from ggrs_tpu.utils.clock import FakeClock
from ggrs_tpu.utils.tracing import GLOBAL_TRACER

TICK_CHILDREN = ("host/pump", "host/drain", "host/advance", "host/dispatch",
                 "host/lifecycle")


@pytest.fixture
def traced():
    """Telemetry and the global tracer on for one test, clean slate."""
    GLOBAL_TELEMETRY.reset()
    GLOBAL_TELEMETRY.enabled = True
    GLOBAL_TRACER.enabled = True
    try:
        yield GLOBAL_TELEMETRY.registry
    finally:
        GLOBAL_TRACER.enabled = False
        GLOBAL_TELEMETRY.enabled = False
        GLOBAL_TELEMETRY.reset()


def _fleet(sessions, **host_kw):
    clock = FakeClock()
    net = InMemoryNetwork(clock, latency_ms=20, jitter_ms=5, seed=5)
    host = SessionHost(ExGame(num_players=2, num_entities=64),
                       max_prediction=8, num_players=2,
                       max_sessions=sessions, clock=clock, **host_kw)
    matches = build_matches(host, net, clock, sessions=sessions,
                            players_cycle=(2,), seed=5)
    sync_fleet(host, matches, clock)
    return host, matches, clock


def _drive(host, matches, clock, ticks):
    scripts = held_scripts(matches, ticks, seed=5)
    assert not drive_scripted(host, matches, clock, scripts, ticks)


def _values(reg, name):
    return reg.snapshot()[name]["values"]


def test_served_tick_tiles_into_layer_spans(traced):
    """host/tick's own time, outside its five layer spans, stays small;
    the drain and advance spans feed ggrs_host_tax_ms from the same clock
    reads; the sessions' advance is one absolute row."""
    host, matches, clock = _fleet(8)
    _drive(host, matches, clock, 10)
    traced.reset()
    ticks = 60
    sessions = [host.session(k) for keys in matches for k in keys]
    start = sum(s.current_frame for s in sessions)
    _drive(host, matches, clock, ticks)
    # how many sessions advance on a tick depends on the device window's
    # backpressure (readiness of in-flight work), so count what happened
    advanced_frames = sum(s.current_frame for s in sessions) - start
    spans = _values(traced, "ggrs_span_ms")
    assert spans["host/tick"]["count"] == ticks
    for child in TICK_CHILDREN:
        assert spans[child]["count"] == ticks, child
    total = spans["host/tick"]["sum"]
    self_ms = total - sum(spans[c]["sum"] for c in TICK_CHILDREN)
    assert 0 <= self_ms <= 0.10 * total, (self_ms, total)

    tax = _values(traced, "ggrs_host_tax_ms")
    assert spans["host/advance"]["sum"] == tax["parse"]["sum"]
    assert spans["host/drain"]["sum"] == tax["drain"]["sum"]
    assert spans["host/advance"]["count"] == tax["parse"]["count"]

    advanced = spans["session/advance"]
    assert advanced["count"] == advanced_frames > 0
    assert 0 < advanced["sum"] < spans["host/advance"]["sum"]
    assert not any(k.endswith("/session/advance") for k in spans)


def test_host_core_fence_observed(traced, monkeypatch):
    """With one megabatch allowed in flight, every further dispatch waits
    on the oldest: the wait is a tpu/async_fence span and a
    ggrs_async_fence_stall_ms observation, one clock pair for both. The
    device is made to report no work finished early, so the drain pass
    retires nothing and the waits happen however fast the device is."""
    from ggrs_tpu.tpu import backend

    host, matches, clock = _fleet(4, async_inflight=1)
    monkeypatch.setattr(backend, "_array_is_ready", lambda arr: False)
    traced.reset()
    _drive(host, matches, clock, 20)
    stall = _values(traced, "ggrs_async_fence_stall_ms")[""]
    fence = _values(traced, "ggrs_span_ms")["tpu/async_fence"]
    assert stall["count"] > 0
    assert fence["count"] == stall["count"]
    assert fence["sum"] == stall["sum"]


def test_fence_not_observed_with_telemetry_off():
    host, matches, clock = _fleet(4, async_inflight=1)
    GLOBAL_TELEMETRY.reset()
    _drive(host, matches, clock, 10)
    stall = GLOBAL_TELEMETRY.registry.get("ggrs_async_fence_stall_ms")
    assert stall.bound_children()[()].count == 0


def test_synctest_spans_add_no_device_read(traced, monkeypatch):
    """The synctest/* spans read no device value: advance_frames runs
    under jax's device-to-host guard, and (because that guard exempts
    same-device reads on the CPU) with every Python-visible array read
    refused, while the verdict read in check() trips the refusal."""
    from jax._src import array as jax_array

    from ggrs_tpu.tpu import TpuSyncTestSession

    sess = TpuSyncTestSession(ExGame(num_players=2, num_entities=64),
                              num_players=2, check_distance=2, backend="xla")
    batch = np.zeros((6, 2, 1), np.uint8)
    sess.advance_frames(batch)  # compile outside the guard
    sess.check()
    traced.reset()

    def refuse(self):
        raise AssertionError("device value read")

    monkeypatch.setattr(jax_array.ArrayImpl, "_value", property(refuse))
    with jax.transfer_guard_device_to_host("disallow"):
        for _ in range(3):
            sess.advance_frames(batch)
        with pytest.raises(AssertionError, match="device value read"):
            sess.check()
    monkeypatch.undo()
    sess.check()
    spans = _values(traced, "ggrs_span_ms")
    for name in ("synctest/advance", "synctest/stage", "synctest/dispatch"):
        assert spans[name]["count"] == 3, name
    assert spans["synctest/advance"]["sum"] >= spans["synctest/dispatch"]["sum"]
    assert spans["synctest/check"]["count"] == 2
