"""Batched wire pump parity (network/pump.py).

The batched pump replaces the per-message decode/apply/send loops with
pooled one-pass decodes and field-level appliers; these tests pin that
the replacement is BIT-IDENTICAL to the legacy path it displaced:

  1. decode parity: batch_decode over randomized valid / truncated /
     oversized / garbage datagram streams reconstructs exactly the
     messages decode_all accepts — and drops exactly what it drops;
  2. endpoint-state parity: the same hostile stream applied through
     handle_decoded vs handle_message leaves two identically-seeded
     PeerEndpoints in identical observable state;
  3. session parity: a lossy 2x2 P2P mesh driven batched vs legacy
     produces identical checksum histories and connect status (native
     endpoints ride along where the library is built);
  4. hosted parity: an 8-session SessionHost fleet run batched vs with
     the pre-batched per-session pump pins bitwise checksum/ring/state
     equality on every device slot.
"""

import random

import numpy as np
import pytest

from ggrs_tpu import DesyncDetection, PlayerType, SessionBuilder, SessionState
from ggrs_tpu.native import available
from ggrs_tpu.network.messages import (
    InputMsg,
    ChecksumReport,
    InputAck,
    KeepAlive,
    Message,
    QualityReply,
    QualityReport,
    SyncReply,
    SyncRequest,
    decode_all,
    encode_message,
)
from ggrs_tpu.network.protocol import PeerEndpoint
from ggrs_tpu.network.pump import batch_decode, decode_record, record_to_message
from ggrs_tpu.network.sockets import InMemoryNetwork
from ggrs_tpu.sync_layer import ConnectionStatus, PendingChecksumReport
from ggrs_tpu.utils.clock import FakeClock


def random_body(rng):
    kind = rng.randrange(8)
    if kind == 0:
        return SyncRequest(rng.getrandbits(32))
    if kind == 1:
        return SyncReply(rng.getrandbits(32))
    if kind == 2:
        n_status = rng.randrange(0, 5)
        return InputMsg(
            peer_connect_status=[
                ConnectionStatus(bool(rng.randrange(2)),
                                 rng.randrange(-1, 1000))
                for _ in range(n_status)
            ],
            disconnect_requested=bool(rng.randrange(2)),
            start_frame=rng.randrange(-1, 1000),
            ack_frame=rng.randrange(-1, 1000),
            bytes_=bytes(rng.randrange(256)
                         for _ in range(rng.randrange(0, 40))),
        )
    if kind == 3:
        return InputAck(rng.randrange(-1, 1000))
    if kind == 4:
        return QualityReport(rng.randrange(-128, 128), rng.getrandbits(48))
    if kind == 5:
        return QualityReply(rng.getrandbits(48))
    if kind == 6:
        return ChecksumReport(checksum=rng.getrandbits(128),
                              frame=rng.randrange(0, 1000))
    return KeepAlive()


def random_stream(rng, n):
    """(addr, wire) pairs: valid, truncated, oversized-trailer, garbage."""
    out = []
    for i in range(n):
        addr = f"peer{rng.randrange(3)}"
        roll = rng.random()
        wire = encode_message(
            Message(rng.randrange(1, 1 << 16), random_body(rng))
        )
        if roll < 0.55:
            pass  # valid as encoded
        elif roll < 0.7:
            wire = wire[: rng.randrange(0, len(wire))]  # truncated
        elif roll < 0.85:
            # oversized: trailing garbage the codec must ignore
            wire = wire + bytes(rng.randrange(256)
                                for _ in range(rng.randrange(1, 20)))
        else:
            wire = bytes(rng.randrange(256)
                         for _ in range(rng.randrange(0, 64)))
        out.append((addr, wire))
    return out


def test_batch_decode_matches_legacy_decode_all():
    """Record-for-record decode parity over randomized hostile streams."""
    for seed in range(20):
        rng = random.Random(seed)
        pairs = random_stream(rng, 120)
        legacy = dict()
        for i, (addr, wire) in enumerate(pairs):
            got = decode_all([(addr, wire)])
            legacy[i] = got[0][1] if got else None
        records = batch_decode(
            [(0, addr, wire) for addr, wire in pairs]
        )
        assert len(records) == len(pairs)
        # the scalar small-pass twin must agree record-for-record with
        # the vectorized path (statuses normalize to tuples of pairs)
        for (_, wire), rec in zip(pairs, records):
            scalar = decode_record(wire)
            if rec is None:
                assert scalar is None
            else:
                norm = rec[:5] + (
                    tuple(tuple(s) for s in rec[5]), rec[6]
                )
                snorm = scalar[:5] + (
                    tuple(tuple(s) for s in scalar[5]), scalar[6]
                )
                assert norm == snorm
        for i, ((addr, wire), rec) in enumerate(zip(pairs, records)):
            if legacy[i] is None:
                assert rec is None, (
                    f"seed {seed} datagram {i}: batched decoded what "
                    f"legacy dropped ({wire!r})"
                )
                continue
            assert rec is not None, (
                f"seed {seed} datagram {i}: batched dropped what legacy "
                f"decoded ({legacy[i]})"
            )
            msg = record_to_message(rec, wire)
            assert msg.magic == legacy[i].magic
            assert msg.body == legacy[i].body, (
                f"seed {seed} datagram {i}: {msg.body} != {legacy[i].body}"
            )
            # wire stamp: recv byte accounting must see the datagram size
            assert msg._wire == legacy[i]._wire


def make_endpoint(seed, clock):
    return PeerEndpoint(
        handles=[1], peer_addr="peer", num_players=2, local_players=1,
        max_prediction=8, disconnect_timeout_ms=2000,
        disconnect_notify_start_ms=500, fps=60, input_size=1,
        clock=clock, rng=random.Random(seed),
    )


def endpoint_state(ep):
    return {
        "state": ep.state,
        "remote_magic": ep.remote_magic,
        "packets_recv": ep.packets_recv,
        "bytes_recv": ep.bytes_recv,
        "packets_sent": ep.packets_sent,
        "bytes_sent": ep.bytes_sent,
        "pending": list(ep.pending_output),
        "last_acked": ep.last_acked_input,
        "recv_inputs": dict(ep.recv_inputs),
        "connect": [(s.disconnected, s.last_frame)
                    for s in ep.peer_connect_status],
        "checksums": dict(ep.checksum_history),
        "rtt": ep.round_trip_time,
        "remote_adv": ep.remote_frame_advantage,
        "events": list(ep.event_queue),
        "sends": [encode_message(m) for m in ep.send_queue],
    }


def test_endpoint_handle_decoded_matches_handle_message():
    """The same stream through the field-level applier vs the object
    applier must leave identically-seeded endpoints bit-identical."""
    for seed in range(8):
        rng = random.Random(1000 + seed)
        clock = FakeClock()
        a = make_endpoint(seed, clock)
        b = make_endpoint(seed, clock)
        a.synchronize()
        b.synchronize()
        pairs = random_stream(rng, 150)
        records = batch_decode([(0, addr, w) for addr, w in pairs])
        for (addr, wire), rec in zip(pairs, records):
            if rec is None:
                continue
            msg = record_to_message(rec, wire)
            a.handle_message(msg)
            b.handle_decoded(
                rec[0], rec[1], len(wire),
                rec[2], rec[3], rec[4], rec[5], rec[6],
            )
            clock.advance(7)
        assert endpoint_state(a) == endpoint_state(b), f"seed {seed}"


def drive_mesh(batched, use_native, ticks=120, loss=0.05, seed=5):
    """A 2-player P2P mesh over a seeded lossy wire; returns per-session
    observable outcomes. All nondeterminism is seeded, so batched and
    legacy runs see byte-identical traffic unless behavior diverges."""
    from stubs import GameStub

    clock = FakeClock()
    net = InMemoryNetwork(clock, latency_ms=15, jitter_ms=5, loss=loss,
                          seed=seed)

    def build(my_addr, other_addr, local_handle):
        b = (
            SessionBuilder(input_size=1)
            .with_num_players(2)
            .with_max_prediction_window(8)
            .with_clock(clock)
            .with_desync_detection_mode(DesyncDetection.on(interval=10))
            .with_rng(random.Random(hash(my_addr) & 0xFFFF))
        )
        if use_native:
            b = b.with_native_endpoints(True)
        b = b.add_player(PlayerType.local(), local_handle)
        b = b.add_player(PlayerType.remote(other_addr), 1 - local_handle)
        return b.start_p2p_session(net.socket(my_addr))

    sessions = [build("a", "b", 0), build("b", "a", 1)]
    games = [GameStub(), GameStub()]
    for s in sessions:
        s.batched_pump = batched
    for _ in range(400):
        for s in sessions:
            s.poll_remote_clients()
            s.events()
        clock.advance(20)
        if all(s.current_state() == SessionState.RUNNING for s in sessions):
            break
    else:
        raise AssertionError("mesh failed to synchronize")

    script = random.Random(seed ^ 0xBEEF)
    inputs = [[script.randrange(16) for _ in range(ticks)] for _ in range(2)]
    for t in range(ticks):
        for i, s in enumerate(sessions):
            s.add_local_input(i, bytes([inputs[i][t]]))
            games[i].handle_requests(s.advance_frame())
            s.events()
        clock.advance(16)
    return [
        {
            "frame": s.current_frame,
            "checksum_history": dict(s.local_checksum_history),
            "connect": [(c.disconnected, c.last_frame)
                        for c in s.local_connect_status],
            "game_state": (g.gs.frame, g.gs.state),
        }
        for s, g in zip(sessions, games)
    ]


@pytest.mark.parametrize("use_native", [False] + ([True] if available() else []))
def test_session_parity_batched_vs_legacy(use_native):
    """Lossy mesh: batched pump vs legacy per-message pump, identical
    outcomes (checksum history is the bitwise witness)."""
    batched = drive_mesh(True, use_native)
    legacy = drive_mesh(False, use_native)
    assert batched == legacy
    # the run must actually exercise desync detection's checksum lane
    assert batched[0]["checksum_history"]


def test_pending_checksum_report_serial_guard():
    """Binding between interval ticks must not bind entries captured
    within the serial guard — their correcting rollback may be
    unfulfilled."""

    class Cell:
        def __init__(self, frame):
            self.frame = frame
            self.bound = 0

        def checksum_getter(self):
            self.bound += 1
            return lambda: 123

    pcr = PendingChecksumReport()
    young = Cell(20)
    old = Cell(10)
    pcr.capture(10, old, serial=5)
    pcr.capture(20, young, serial=9)
    pcr.bind_and_prefetch(max_serial=7)
    assert old.bound == 1 and young.bound == 0
    # the interval flush binds and emits everything, in capture order
    emitted = []
    pcr.flush(lambda f, c: emitted.append(f))
    assert emitted == [10, 20]
    assert young.bound == 1


def build_hosted_fleet(batched, seed=13):
    from ggrs_tpu.models.ex_game import ExGame
    from ggrs_tpu.serve import SessionHost
    from ggrs_tpu.serve.loadgen import (
        build_matches,
        drive_scripted,
        make_scripts,
        sync_fleet,
    )

    clock = FakeClock()
    net = InMemoryNetwork(clock, latency_ms=20, jitter_ms=8, loss=0.03,
                          seed=seed)
    host = SessionHost(
        ExGame(num_players=4, num_entities=16),
        max_prediction=8, num_players=4, max_sessions=12,
        clock=clock, idle_timeout_ms=0, batched_pump=batched,
    )
    matches = build_matches(host, net, clock, sessions=8, seed=seed)
    sync_fleet(host, matches, clock)
    ticks = 60
    scripts = make_scripts(matches, ticks, seed=seed)
    desyncs = drive_scripted(host, matches, clock, scripts, ticks)
    assert not desyncs, f"hosted fleet desynced (batched={batched})"
    host.device.block_until_ready()
    return host, matches


def test_hosted_fleet_parity_batched_vs_prebatched_pump():
    """8-session hosted run, batched fleet pump vs the pre-batched
    per-session pump: bitwise state/ring parity on every device slot,
    identical checksum histories on every session."""
    host_a, matches_a = build_hosted_fleet(True)
    host_b, matches_b = build_hosted_fleet(False)
    assert host_a.batched_pump and not host_b.batched_pump
    keys_a = [k for keys in matches_a for k in keys]
    keys_b = [k for keys in matches_b for k in keys]
    assert len(keys_a) == len(keys_b) >= 8
    for ka, kb in zip(keys_a, keys_b):
        sa, sb = host_a.session(ka), host_b.session(kb)
        assert sa.current_frame == sb.current_frame
        assert sa.local_checksum_history == sb.local_checksum_history
        slot_a = host_a._lanes[ka].slot
        slot_b = host_b._lanes[kb].slot
        state_a = host_a.device.state_numpy(slot_a)
        state_b = host_b.device.state_numpy(slot_b)
        leaves_a, _ = _tree_flatten(state_a)
        leaves_b, _ = _tree_flatten(state_b)
        for la, lb in zip(leaves_a, leaves_b):
            assert np.array_equal(np.asarray(la), np.asarray(lb))
    # ring parity across the whole stacked fleet
    import jax

    ra = jax.device_get(host_a.device.rings)
    rb = jax.device_get(host_b.device.rings)
    for la, lb in zip(jax.tree.leaves(ra), jax.tree.leaves(rb)):
        assert np.array_equal(np.asarray(la), np.asarray(lb))


def _tree_flatten(tree):
    import jax

    return jax.tree.flatten(tree)
