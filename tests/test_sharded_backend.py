"""Entity-sharded product backend: a world partitioned over the mesh's
`entity` axis must run inside real sessions (SyncTest AND P2P) with
bit-parity vs the unsharded backend — state, checksums, and the desync
detector all agree. This is the multi-chip request path (the rollback seam
src/sessions/p2p_session.rs:621-673 executed over a device mesh,
BASELINE.json configs[4])."""

import random

import numpy as np
import pytest

from ggrs_tpu import (
    DesyncDetected,
    DesyncDetection,
    PlayerType,
    SessionBuilder,
    SessionState,
)
from ggrs_tpu.models import ex_game
from ggrs_tpu.network.sockets import InMemoryNetwork
from ggrs_tpu.parallel.mesh import make_mesh
from ggrs_tpu.tpu import TpuRollbackBackend
from ggrs_tpu.utils.clock import FakeClock

NUM_PLAYERS = 2
ENTITIES = 128  # divisible by the 4-wide entity axis of the 8-device mesh

import jax  # noqa: F401  (kept: the fixture and parity tests poke jax)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(8)  # (beam=2, entity=4) on the virtual CPU devices


def make_backend(mesh=None, beam_width=0, max_prediction=8):
    game = ex_game.ExGame(NUM_PLAYERS, ENTITIES)
    return TpuRollbackBackend(
        game,
        max_prediction=max_prediction,
        num_players=NUM_PLAYERS,
        beam_width=beam_width,
        mesh=mesh,
    )


def drive_synctest(handler, frames, check_distance, max_prediction=8, seed=3):
    sess = (
        SessionBuilder(input_size=1)
        .with_num_players(NUM_PLAYERS)
        .with_max_prediction_window(max_prediction)
        .with_check_distance(check_distance)
        .start_synctest_session()
    )
    rng = np.random.default_rng(seed)
    for _ in range(frames):
        for h in range(NUM_PLAYERS):
            sess.add_local_input(h, bytes([int(rng.integers(0, 16))]))
        handler.handle_requests(sess.advance_frame())
    return sess


def assert_state_equal(a, b):
    for key in ("frame", "pos", "vel", "rot"):
        np.testing.assert_array_equal(np.asarray(a[key]), np.asarray(b[key]))


def test_sharded_state_placement(mesh):
    backend = make_backend(mesh)
    ent = mesh.shape["entity"]
    # entity arrays actually split: each device holds N/ent rows
    shard = backend.core.state["pos"].addressable_shards[0]
    assert shard.data.shape[0] == ENTITIES // ent
    ring_shard = backend.core.ring["pos"].addressable_shards[0]
    assert ring_shard.data.shape == (
        backend.core.ring_len + 1,
        ENTITIES // ent,
        2,
    )


@pytest.mark.parametrize("check_distance", [2, 7])
def test_sharded_backend_bit_parity(mesh, check_distance):
    """Same request stream through the sharded and unsharded backends:
    final state and every saved checksum must be bitwise identical."""
    sharded = make_backend(mesh)
    plain = make_backend(None)
    drive_synctest(sharded, 50, check_distance)
    drive_synctest(plain, 50, check_distance)
    assert_state_equal(sharded.state_numpy(), plain.state_numpy())


def test_sharded_backend_with_beam(mesh):
    """Beam speculation over the sharded core: candidate futures shard the
    `beam` axis, adoption still bit-matches the plain resim path."""
    def drive_constant(handler, frames):
        sess = (
            SessionBuilder(input_size=1)
            .with_num_players(NUM_PLAYERS)
            .with_max_prediction_window(8)
            .with_check_distance(3)
            .start_synctest_session()
        )
        for _ in range(frames):
            for h in range(NUM_PLAYERS):
                sess.add_local_input(h, bytes([h + 1]))
            handler.handle_requests(sess.advance_frame())

    sharded = make_backend(mesh, beam_width=8)
    plain = make_backend(None)
    drive_constant(sharded, 40)
    drive_constant(plain, 40)
    assert_state_equal(sharded.state_numpy(), plain.state_numpy())
    # a constant script makes the repeat-last member the corrected script:
    # the sharded adopt path must actually run
    assert sharded.beam_hits > 0


def test_sharded_backend_with_lazy_ticks(mesh):
    """Lazy tick batching composes with the mesh-sharded core: the fused
    multi-tick scan runs under GSPMD over the entity axis, bit-matching
    the plain per-tick sharded backend (and the unsharded one)."""
    sharded_plain = make_backend(mesh)
    sharded_lazy = TpuRollbackBackend(
        ex_game.ExGame(NUM_PLAYERS, ENTITIES),
        max_prediction=8,
        num_players=NUM_PLAYERS,
        mesh=mesh,
        lazy_ticks=5,
    )
    drive_synctest(sharded_lazy, 30, check_distance=3)
    drive_synctest(sharded_plain, 30, check_distance=3)
    assert_state_equal(sharded_lazy.state_numpy(), sharded_plain.state_numpy())
    unsharded = make_backend(None)
    drive_synctest(unsharded, 30, check_distance=3)
    assert_state_equal(sharded_lazy.state_numpy(), unsharded.state_numpy())


def test_sharded_pallas_tick_bit_parity(mesh):
    """The sharded request path on the entity-tiled pallas kernel
    (ShardedPallasTickCore: one local kernel per device + psum'd checksum
    partials) must bit-match the sharded XLA scan AND the unsharded
    backend — state, ring, and every saved checksum. Lazy ticks force the
    multi-row dispatches the kernel serves; the forced-rollback SyncTest
    stream exercises loads, masked saves, and resim inside the kernel."""
    # 512 entities: each of the 4 entity shards gets one 128-lane tile
    game = ex_game.ExGame(NUM_PLAYERS, 512)
    sharded_pallas = TpuRollbackBackend(
        game,
        max_prediction=8,
        num_players=NUM_PLAYERS,
        mesh=mesh,
        lazy_ticks=5,
        tick_backend="pallas-interpret",
    )
    assert sharded_pallas.core.tick_backend == "pallas-interpret"
    sharded_xla = TpuRollbackBackend(
        ex_game.ExGame(NUM_PLAYERS, 512),
        max_prediction=8,
        num_players=NUM_PLAYERS,
        mesh=mesh,
        lazy_ticks=5,
        tick_backend="xla",
    )
    drive_synctest(sharded_pallas, 30, check_distance=3)
    drive_synctest(sharded_xla, 30, check_distance=3)
    assert_state_equal(sharded_pallas.state_numpy(), sharded_xla.state_numpy())
    unsharded = TpuRollbackBackend(
        ex_game.ExGame(NUM_PLAYERS, 512),
        max_prediction=8,
        num_players=NUM_PLAYERS,
    )
    drive_synctest(unsharded, 30, check_distance=3)
    assert_state_equal(sharded_pallas.state_numpy(), unsharded.state_numpy())
    # the sharded state is actually partitioned over the mesh
    shard = sharded_pallas.core.state["pos"].addressable_shards[0]
    assert shard.data.shape[0] == 512 // mesh.shape["entity"]


def test_sharded_pallas_beam_bit_parity(mesh):
    """The SHARDED pallas beam rollout (ShardedPallasBeamRollout: one
    local entity-tiled rollout per device, psum'd checksum partials —
    the restriction VERDICT r4 flagged at resim.py:204-207, lifted): a
    mesh-sharded backend speculating through the pallas kernel must
    adopt trajectories bit-identical to the sharded XLA speculation AND
    the unsharded backend."""
    from ggrs_tpu.tpu.pallas_beam import ShardedPallasBeamRollout

    def drive_constant(handler, frames):
        sess = (
            SessionBuilder(input_size=1)
            .with_num_players(NUM_PLAYERS)
            .with_max_prediction_window(8)
            .with_check_distance(3)
            .start_synctest_session()
        )
        for _ in range(frames):
            for h in range(NUM_PLAYERS):
                sess.add_local_input(h, bytes([h + 1]))
            handler.handle_requests(sess.advance_frame())

    def build(mesh_, spec_backend):
        return TpuRollbackBackend(
            ex_game.ExGame(NUM_PLAYERS, 512),
            max_prediction=8,
            num_players=NUM_PLAYERS,
            beam_width=8,
            mesh=mesh_,
            spec_backend=spec_backend,
        )

    sharded_pallas = build(mesh, "pallas-interpret")
    drive_constant(sharded_pallas, 40)
    # the sharded rollout actually ran (no silent XLA demotion) and the
    # constant script made the repeat-last member adopt
    assert sharded_pallas.core.spec_backend == "pallas-interpret"
    assert any(
        isinstance(r, ShardedPallasBeamRollout)
        for r in sharded_pallas.core._beam_rollouts.values()
    ), "mesh-sharded speculation did not use ShardedPallasBeamRollout"
    assert sharded_pallas.beam_hits > 0

    sharded_xla = build(mesh, "xla")
    drive_constant(sharded_xla, 40)
    assert_state_equal(
        sharded_pallas.state_numpy(), sharded_xla.state_numpy()
    )
    unsharded = TpuRollbackBackend(
        ex_game.ExGame(NUM_PLAYERS, 512),
        max_prediction=8,
        num_players=NUM_PLAYERS,
        beam_width=8,
    )
    drive_constant(unsharded, 40)
    assert_state_equal(sharded_pallas.state_numpy(), unsharded.state_numpy())


def test_sharded_pallas_tick_checksums_and_verify(mesh):
    """Checksum values read back through the lazy ledger and the on-device
    verify verdict must agree between the sharded pallas tick kernel and
    the unsharded XLA path (psum'd partial sums == unsharded totals,
    bit-for-bit)."""
    from ggrs_tpu.tpu.resim import ResimCore

    rng = np.random.default_rng(11)
    game_a = ex_game.ExGame(NUM_PLAYERS, 512)
    game_b = ex_game.ExGame(NUM_PLAYERS, 512)
    sharded = ResimCore(
        game_a, 8, NUM_PLAYERS, mesh=mesh, device_verify=True,
        tick_backend="pallas-interpret",
    )
    plain = ResimCore(game_b, 8, NUM_PLAYERS, device_verify=True)
    W, P = sharded.window, NUM_PLAYERS
    # a hand-driven multi-row buffer: row 0 plain advance+saves, row 1 a
    # rollback (load + resim), row 2 padding
    rows = []
    frame = 0
    for t in range(2):
        inputs = rng.integers(0, 16, size=(W, P, 1), dtype=np.uint8)
        statuses = np.zeros((W, P), dtype=np.int32)
        save_slots = np.full((W,), sharded.scratch_slot, dtype=np.int32)
        count = 3
        for i in range(count + 1):
            save_slots[i] = (frame + i) % sharded.ring_len
        rows.append(
            sharded.pack_tick_row(
                t == 1, frame % sharded.ring_len, inputs, statuses,
                save_slots, count, start_frame=frame,
            )
        )
        if t == 0:
            frame += count
            frame -= count  # rollback row reloads the same base
    rows.append(sharded.pad_tick_row())
    buf = np.stack(rows)
    his_s, los_s = sharded.tick_multi(buf)
    his_p, los_p = plain.tick_multi(buf.copy())
    np.testing.assert_array_equal(np.asarray(his_s), np.asarray(his_p))
    np.testing.assert_array_equal(np.asarray(los_s), np.asarray(los_p))
    assert sharded.check_device_verdict() == plain.check_device_verdict()
    for key in ("pos", "vel", "rot", "frame"):
        np.testing.assert_array_equal(
            np.asarray(sharded.state[key]), np.asarray(plain.state[key])
        )
        np.testing.assert_array_equal(
            np.asarray(sharded.ring[key]), np.asarray(plain.ring[key])
        )


def test_sharded_checkpoint_roundtrip(tmp_path, mesh):
    backend = make_backend(mesh)
    drive_synctest(backend, 20, check_distance=2)
    path = str(tmp_path / "ckpt.npz")
    backend.save(path)

    game = ex_game.ExGame(NUM_PLAYERS, ENTITIES)
    # restore sharded -> unsharded and vice versa: layout-agnostic
    plain = TpuRollbackBackend.restore(path, game)
    resharded = TpuRollbackBackend.restore(path, game, mesh=mesh)
    assert_state_equal(plain.state_numpy(), backend.state_numpy())
    assert_state_equal(resharded.state_numpy(), backend.state_numpy())
    shard = resharded.core.state["pos"].addressable_shards[0]
    assert shard.data.shape[0] == ENTITIES // mesh.shape["entity"]


# ---------------------------------------------------------------------------
# the decisive end-to-end: a sharded world inside a live P2P session
# ---------------------------------------------------------------------------


def build_pair(clock, net):
    def build(my_addr, other_addr, local_handle):
        return (
            SessionBuilder(input_size=1)
            .with_num_players(2)
            .with_max_prediction_window(8)
            .with_desync_detection_mode(DesyncDetection.on(interval=10))
            .with_clock(clock)
            # seed from the handle, NOT hash(addr): string hashing is
            # per-process randomized, which would make handshake timing
            # (and any marginal failure) unreproducible across runs
            .with_rng(random.Random(1234 + local_handle))
            .add_player(PlayerType.local(), local_handle)
            .add_player(PlayerType.remote(other_addr), 1 - local_handle)
            .start_p2p_session(net.socket(my_addr))
        )

    return build("a", "b", 0), build("b", "a", 1)


def sync_sessions(sessions, clock):
    for _ in range(400):
        for s in sessions:
            s.poll_remote_clients()
            s.events()
        clock.advance(20)
        if all(s.current_state() == SessionState.RUNNING for s in sessions):
            return
    raise AssertionError("sessions failed to synchronize")


def test_p2p_sharded_vs_unsharded_peer(mesh):
    """One peer runs the mesh-sharded backend, the other the single-device
    backend, desync detection on: the framework's own detector must stay
    silent for the whole run (checksums bit-agree across layouts), and the
    final worlds must match."""
    clock = FakeClock()
    net = InMemoryNetwork(clock=clock)
    sess_a, sess_b = build_pair(clock, net)
    back_a = make_backend(mesh)
    back_b = make_backend(None)
    sync_sessions([sess_a, sess_b], clock)

    rng = np.random.default_rng(7)
    desyncs = []
    for frame in range(60):
        for sess, backend, handle in ((sess_a, back_a, 0), (sess_b, back_b, 1)):
            sess.poll_remote_clients()
            desyncs += [e for e in sess.events() if isinstance(e, DesyncDetected)]
            sess.add_local_input(handle, bytes([int(rng.integers(0, 16))]))
            backend.handle_requests(sess.advance_frame())
        clock.advance(17)
    # let in-flight inputs land, then advance twice more so each peer's
    # pending rollbacks run and its ring slots at confirmed frames are final
    for _ in range(10):
        sess_a.poll_remote_clients()
        sess_b.poll_remote_clients()
        clock.advance(17)
    for _ in range(2):
        for sess, backend, handle in ((sess_a, back_a, 0), (sess_b, back_b, 1)):
            sess.poll_remote_clients()
            desyncs += [e for e in sess.events() if isinstance(e, DesyncDetected)]
            sess.add_local_input(handle, b"\x00")
            backend.handle_requests(sess.advance_frame())
        clock.advance(17)

    assert desyncs == [], f"sharded vs unsharded checksum mismatch: {desyncs[:3]}"
    assert back_a.current_frame == back_b.current_frame == 62
    assert sess_a.local_checksum_history and sess_b.local_checksum_history

    # bitwise cross-layout check: both rings hold the identical snapshot of
    # the last mutually-confirmed frame
    c = min(sess_a.confirmed_frame(), sess_b.confirmed_frame())
    assert c > 62 - back_a.core.ring_len, "confirmed frame fell out of the ring"
    snap_a = back_a.core.fetch_ring_slot(c % back_a.core.ring_len)
    snap_b = back_b.core.fetch_ring_slot(c % back_b.core.ring_len)
    assert int(np.asarray(snap_a["frame"])) == c
    assert_state_equal(snap_a, snap_b)
