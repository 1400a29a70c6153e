"""Async device-resident dispatch pipeline (TpuRollbackBackend
(async_dispatch=True)): the host stays ahead of the device behind a small
in-flight fence, ticks ride fused multi-tick batches, and checksums stay
lazy futures drained in batches. None of that may change a single bit:
these tests pin the async path to the eager path through forced rollbacks,
a mid-run disconnect (the forced-rollback-with-DISCONNECTED-statuses case)
and the desync-report protocol, and pin the lazy report drain's ordering.
"""

import numpy as np
import pytest

from ggrs_tpu import (
    DesyncDetected,
    DesyncDetection,
    LoadGameState,
    PlayerType,
    SaveGameState,
    SessionBuilder,
    SessionState,
)
from ggrs_tpu.models.ex_game import ExGame
from ggrs_tpu.network.sockets import InMemoryNetwork
from ggrs_tpu.sync_layer import PendingChecksumReport
from ggrs_tpu.tpu import TpuRollbackBackend
from ggrs_tpu.utils.clock import FakeClock
from stubs import GameStub, RandomChecksumGameStub

ENTITIES = 64
PLAYERS = 2


def make_backend(async_dispatch, **kw):
    return TpuRollbackBackend(
        ExGame(num_players=PLAYERS, num_entities=ENTITIES),
        max_prediction=8,
        num_players=PLAYERS,
        async_dispatch=async_dispatch,
        **kw,
    )


def assert_states_equal(a, b):
    sa, sb = a.state_numpy(), b.state_numpy()
    for k in sa:
        np.testing.assert_array_equal(
            np.asarray(sa[k]), np.asarray(sb[k]), err_msg=f"state[{k}]"
        )


# ----------------------------------------------------------------------
# parity: SyncTest forced rollbacks
# ----------------------------------------------------------------------


def drive_synctest(backend, ticks, check_distance=4):
    sess = (
        SessionBuilder(input_size=1)
        .with_num_players(PLAYERS)
        .with_max_prediction_window(8)
        .with_check_distance(check_distance)
        .start_synctest_session()
    )
    getters = []
    for t in range(ticks):
        for h in range(PLAYERS):
            sess.add_local_input(h, bytes([(t * (3 + h) + h) % 16]))
        reqs = sess.advance_frame()
        backend.handle_requests(reqs)
        # capture per save, via getters stable across ring-slot reuse —
        # comparing cell.checksum at the end would only see the last
        # save landing in each reused cell
        getters += [
            (r.frame, r.cell.checksum_getter())
            for r in reqs
            if isinstance(r, SaveGameState)
        ]
    return [(f, g()) for f, g in getters]


def test_async_bit_parity_through_forced_rollbacks():
    """Same SyncTest request stream (a forced rollback every tick past
    check_distance) through an eager and an async backend: every saved
    checksum and the final state bit-identical. The async run's lazy
    drain happens when the getters resolve, long after the ticks."""
    eager, asynch = make_backend(False), make_backend(True)
    se = drive_synctest(eager, 30)
    sa = drive_synctest(asynch, 30)
    assert asynch.lazy_ticks == TpuRollbackBackend.ASYNC_DEFAULT_LAZY_TICKS
    assert se == sa
    assert_states_equal(eager, asynch)


def test_async_dispatch_signatures_canonicalize():
    """Repeated rollback blocks of one shape must coalesce onto a handful
    of canonical dispatch signatures (each keyed to one cached jitted
    program), not one per tick."""
    backend = make_backend(True)
    drive_synctest(backend, 40)
    sigs = backend.dispatch_signatures
    assert sum(sigs.values()) >= 40  # every segment tallied
    assert len(sigs) <= 6, f"signature explosion: {sigs}"


# ----------------------------------------------------------------------
# parity: P2P misprediction rollbacks + mid-run disconnect
# ----------------------------------------------------------------------


def run_p2p_device(async_mode, frames=60, disconnect_tick=30):
    """A deterministic 2-player P2P run: fixed network latency makes
    session 0 predict (and mispredict) remote inputs, and a mid-run
    disconnect forces the rollback-with-DISCONNECTED-statuses path. The
    whole world (clock, network, scripts) is pinned, so eager and async
    runs see identical request streams — any checksum difference is the
    backend's fault."""
    clock = FakeClock()
    net = InMemoryNetwork(clock, latency_ms=40, seed=11)

    def build(my_addr, other_addr, local_handle):
        b = (
            SessionBuilder(input_size=1)
            .with_num_players(PLAYERS)
            .with_max_prediction_window(8)
            .with_clock(clock)
        )
        b = b.add_player(PlayerType.local(), local_handle)
        b = b.add_player(
            PlayerType.remote(other_addr), 1 - local_handle
        )
        return b.start_p2p_session(net.socket(my_addr))

    s0, s1 = build("a", "b", 0), build("b", "a", 1)
    for _ in range(400):
        for s in (s0, s1):
            s.poll_remote_clients()
            s.events()
        clock.advance(20)
        if all(
            s.current_state() == SessionState.RUNNING for s in (s0, s1)
        ):
            break
    else:
        raise AssertionError("sessions failed to synchronize")

    backend = make_backend(async_mode)
    peer = GameStub()
    getters = []
    saw_rollback_after_disconnect = False
    for f in range(frames):
        s0.add_local_input(0, bytes([(f * 3 + 1) % 16]))
        reqs = s0.advance_frame()
        backend.handle_requests(reqs)
        getters += [
            (r.frame, r.cell.checksum_getter())
            for r in reqs
            if isinstance(r, SaveGameState)
        ]
        if f > disconnect_tick and any(
            isinstance(r, LoadGameState) for r in reqs
        ):
            saw_rollback_after_disconnect = True
        s0.events()
        if f == disconnect_tick:
            s0.disconnect_player(1)
        if f < disconnect_tick:
            s1.add_local_input(1, bytes([(f * 5 + 2) % 16]))
            peer.handle_requests(s1.advance_frame())
            s1.events()
        clock.advance(16)
    assert saw_rollback_after_disconnect
    stream = [(f, g()) for f, g in getters]
    return stream, backend


def test_async_parity_through_disconnect_rollback():
    eager_stream, eager = run_p2p_device(False)
    async_stream, asynch = run_p2p_device(True)
    assert eager_stream == async_stream
    assert_states_equal(eager, asynch)


# ----------------------------------------------------------------------
# lazy desync-report drain: ordering + batching
# ----------------------------------------------------------------------


class FakeGetter:
    def __init__(self, value):
        self.value = value
        self.ready = False
        self.prefetches = 0

    def prefetch(self):
        self.prefetches += 1

    def __call__(self):
        return self.value


class FakeCell:
    def __init__(self, frame, getter):
        self.frame = frame
        self._getter = getter

    def checksum_getter(self):
        return self._getter


def test_pending_report_drains_in_frame_order():
    """Reports queue while their device values are in flight: between
    interval ticks nothing is emitted and nothing forces a sync, but the
    oldest in-flight value's copy starts; the interval flush emits every
    report in capture order — a ready report NEVER jumps an unready older
    one (the peer would see out-of-order frames)."""
    rep = PendingChecksumReport()
    getters = {f: FakeGetter(f * 1000 + 7) for f in (10, 20, 30)}
    for f in (10, 20, 30):
        rep.capture(f, FakeCell(f, getters[f]))
    emitted = []
    emit = lambda frame, checksum: emitted.append((frame, checksum))

    rep.bind_and_prefetch()
    assert emitted == []  # head in flight: nothing emitted, no sync forced
    assert getters[10].prefetches > 0  # ...but its copy was started

    getters[20].ready = True  # a LATER report landing first
    rep.bind_and_prefetch()
    assert emitted == []  # must not jump the queue past frame 10
    assert getters[20].prefetches == 0  # only the head's copy starts

    getters[10].ready = True
    blocked = rep.flush(emit)  # the interval tick: everything, in order
    assert emitted == [(10, 10007), (20, 20007), (30, 30007)]
    assert blocked == 1  # frame 30's value was still in flight
    assert len(rep) == 0


def test_pending_report_drops_reused_slot():
    """A report whose ring cell was overwritten before the first read is
    dropped (its checksum now belongs to a different frame); younger
    reports still drain."""
    rep = PendingChecksumReport()
    stale = FakeGetter(1)
    live = FakeGetter(2)
    rep.capture(5, FakeCell(99, stale))  # cell.frame != captured frame
    rep.capture(6, FakeCell(6, live))
    live.ready = True
    emitted = []
    rep.flush(lambda f, c: emitted.append((f, c)))
    assert emitted == [(6, 2)]


def test_desync_reports_surface_on_correct_frames_async():
    """End-to-end ordering witness: session 0 fulfills on the async device
    backend, its peer publishes garbage checksums — every DesyncDetected
    event must name a frame session 0 actually reported, with the exact
    checksum its lazy drain emitted for that frame."""
    clock = FakeClock()
    net = InMemoryNetwork(clock, seed=17)

    def build(my_addr, other_addr, local_handle):
        b = (
            SessionBuilder(input_size=1)
            .with_num_players(PLAYERS)
            .with_max_prediction_window(8)
            .with_desync_detection_mode(DesyncDetection.on(10))
            .with_clock(clock)
        )
        b = b.add_player(PlayerType.local(), local_handle)
        b = b.add_player(PlayerType.remote(other_addr), 1 - local_handle)
        return b.start_p2p_session(net.socket(my_addr))

    s0, s1 = build("a", "b", 0), build("b", "a", 1)
    for _ in range(400):
        for s in (s0, s1):
            s.poll_remote_clients()
            s.events()
        clock.advance(20)
        if all(
            s.current_state() == SessionState.RUNNING for s in (s0, s1)
        ):
            break
    else:
        raise AssertionError("sessions failed to synchronize")

    backend = make_backend(True)
    peer = RandomChecksumGameStub()
    events = []
    for f in range(150):
        s0.add_local_input(0, b"\x01")
        backend.handle_requests(s0.advance_frame())
        s1.add_local_input(1, b"\x01")
        peer.handle_requests(s1.advance_frame())
        events += s0.events() + s1.events()
        clock.advance(16)
    desyncs = [e for e in events if isinstance(e, DesyncDetected)]
    assert desyncs, "random peer checksums must trip desync detection"
    history = s0.local_checksum_history
    for e in [e for e in desyncs if e.addr == "b"]:
        assert e.frame in history, (
            f"desync reported for frame {e.frame} session 0 never published"
        )
        assert e.local_checksum == history[e.frame]


# ----------------------------------------------------------------------
# plumbing: knobs survive checkpoints, composition with beam
# ----------------------------------------------------------------------


def test_async_checkpoint_roundtrip(tmp_path):
    backend = make_backend(True)
    drive_synctest(backend, 12)
    path = str(tmp_path / "async.npz")
    backend.save(path)
    restored = TpuRollbackBackend.restore(
        path, ExGame(num_players=PLAYERS, num_entities=ENTITIES)
    )
    assert restored.async_dispatch
    assert restored.lazy_ticks == backend.lazy_ticks
    assert restored.async_inflight == backend.async_inflight
    assert_states_equal(restored, backend)


def test_async_composes_with_beam():
    """Speculation adoption flushes the pending batch before anchoring;
    the fence must not deadlock or reorder around it."""
    asynch = make_backend(True, beam_width=8)
    eager = make_backend(False)
    se = drive_synctest(eager, 30)
    sa = drive_synctest(asynch, 30)
    assert se == sa
    assert_states_equal(eager, asynch)
    assert asynch.beam_hits + asynch.beam_partial_hits + asynch.beam_misses > 0
