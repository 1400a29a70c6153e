"""Device rollback backend: request-stream fusion must be semantically
identical to fulfilling the same requests one-by-one on host (the oracle
path), including through rollbacks, ring reuse and checksum production."""

import numpy as np
import pytest

from ggrs_tpu import AdvanceFrame, LoadGameState, SaveGameState, SessionBuilder
from ggrs_tpu.models import ex_game
from ggrs_tpu.ops.fixed_point import combine_checksum

NUM_PLAYERS = 2
ENTITIES = 128


class OracleRunner:
    """Fulfills the ordered request list on host with the numpy oracle —
    the straight, unfused execution of the same contract."""

    def __init__(self):
        self.state = ex_game.init_oracle(NUM_PLAYERS, ENTITIES)

    def _copy(self):
        return {k: np.copy(v) for k, v in self.state.items()}

    def handle_requests(self, requests):
        for req in requests:
            if isinstance(req, SaveGameState):
                assert int(self.state["frame"]) == req.frame
                req.cell.save(
                    req.frame,
                    self._copy(),
                    combine_checksum(*ex_game.checksum_oracle(self.state)),
                )
            elif isinstance(req, LoadGameState):
                data = req.cell.load()
                assert data is not None
                self.state = {k: np.copy(v) for k, v in data.items()}
            elif isinstance(req, AdvanceFrame):
                inputs = np.array([buf[0] for buf, _ in req.inputs], dtype=np.uint8)
                statuses = np.array([int(s) for _, s in req.inputs], dtype=np.int32)
                self.state = ex_game.step_oracle(
                    self.state, inputs, statuses, NUM_PLAYERS
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
    for frame in range(frames):
        for h in range(NUM_PLAYERS):
            sess.add_local_input(h, bytes([int(rng.integers(0, 16))]))
        handler.handle_requests(sess.advance_frame())


@pytest.mark.parametrize("check_distance", [2, 7])
def test_fused_backend_matches_oracle(check_distance):
    from ggrs_tpu.tpu import TpuRollbackBackend

    game = ex_game.ExGame(NUM_PLAYERS, ENTITIES)
    backend = TpuRollbackBackend(game, max_prediction=8, num_players=NUM_PLAYERS)
    oracle = OracleRunner()

    drive_synctest(backend, 60, check_distance)
    drive_synctest(oracle, 60, check_distance)

    dev = backend.state_numpy()
    for key in ("frame", "pos", "vel", "rot"):
        np.testing.assert_array_equal(np.asarray(dev[key]), oracle.state[key])


def test_synctest_checksum_consistency_on_device():
    """The fused device path must survive SyncTest's per-tick forced rollback
    + checksum-history comparison for a long run (no MismatchedChecksum)."""
    from ggrs_tpu.tpu import TpuRollbackBackend

    game = ex_game.ExGame(NUM_PLAYERS, ENTITIES)
    backend = TpuRollbackBackend(game, max_prediction=8, num_players=NUM_PLAYERS)
    drive_synctest(backend, 300, check_distance=4)
    assert backend.current_frame == 300


def test_snapshot_refs_and_lazy_checksums():
    from ggrs_tpu.tpu import SnapshotRef, TpuRollbackBackend

    game = ex_game.ExGame(NUM_PLAYERS, ENTITIES)
    backend = TpuRollbackBackend(game, max_prediction=4, num_players=NUM_PLAYERS)

    sess = (
        SessionBuilder(input_size=1)
        .with_num_players(NUM_PLAYERS)
        .with_max_prediction_window(4)
        .with_check_distance(2)
        .start_synctest_session()
    )
    cells = []
    for frame in range(6):
        for h in range(NUM_PLAYERS):
            sess.add_local_input(h, bytes([frame]))
        reqs = sess.advance_frame()
        backend.handle_requests(reqs)
        cells += [r.cell for r in reqs if isinstance(r, SaveGameState)]

    # cells hold device snapshot handles + resolvable checksums
    assert all(isinstance(c.load(), SnapshotRef) for c in cells)
    assert all(isinstance(c.checksum, int) for c in cells)


def test_multi_segment_request_list():
    """Sparse-saving P2P ticks can contain two Load-led rollback blocks in
    one request list; the backend must fuse each segment separately."""
    from ggrs_tpu.sync_layer import GameStateCell
    from ggrs_tpu.tpu import TpuRollbackBackend

    game = ex_game.ExGame(NUM_PLAYERS, 64)
    backend = TpuRollbackBackend(game, max_prediction=4, num_players=NUM_PLAYERS)

    def adv(frame):
        return AdvanceFrame(
            inputs=[(bytes([frame % 7]), 0), (bytes([(frame * 3) % 7]), 0)]
        )

    c0, c1 = GameStateCell(), GameStateCell()
    backend.handle_requests(
        [SaveGameState(c0, 0), adv(0), SaveGameState(c1, 1), adv(1)]
    )
    assert backend.current_frame == 2

    c1b, c0b = GameStateCell(), GameStateCell()
    backend.handle_requests(
        [
            LoadGameState(c0, 0), adv(0), SaveGameState(c1b, 1), adv(1),
            LoadGameState(c0, 0), adv(0), adv(1),
        ]
    )
    assert backend.current_frame == 2
    # both segments replayed the same inputs from the same snapshot: the
    # final state must equal the straight-line oracle
    oracle = ex_game.init_oracle(NUM_PLAYERS, 64)
    for f in range(2):
        inputs = np.array([f % 7, (f * 3) % 7], dtype=np.uint8)
        oracle = ex_game.step_oracle(oracle, inputs, np.zeros(2, np.int32), NUM_PLAYERS)
    dev = backend.state_numpy()
    for key in ("frame", "pos", "vel", "rot"):
        np.testing.assert_array_equal(np.asarray(dev[key]), oracle[key])


def test_deferred_synctest_on_device_matches_oracle():
    """Deferred checksum verification over the device backend: same end
    state as the oracle, no mismatch, and the ledger batches transfers
    (each drain burst resolves every pending checksum batch at once)."""
    from ggrs_tpu.tpu import TpuRollbackBackend

    game = ex_game.ExGame(NUM_PLAYERS, ENTITIES)
    backend = TpuRollbackBackend(game, max_prediction=8, num_players=NUM_PLAYERS)
    sess = (
        SessionBuilder(input_size=1)
        .with_num_players(NUM_PLAYERS)
        .with_max_prediction_window(8)
        .with_check_distance(4)
        .with_deferred_checksum_verification(10)
        .start_synctest_session()
    )
    rng = np.random.default_rng(3)
    for frame in range(80):
        for h in range(NUM_PLAYERS):
            sess.add_local_input(h, bytes([int(rng.integers(0, 16))]))
        backend.handle_requests(sess.advance_frame())
    sess.flush_checksum_checks()
    # every batch an observation referenced is resolved without a fresh
    # round trip: drains prefetch the next burst's batches, so resolution
    # consumes landed host copies. Only batches no observation ever read
    # (at most the last burst's tail, registered after the final in-run
    # prefetch) may remain unresolved in the ledger.
    unresolved = [b for b in backend.ledger._pending if b._np is None]
    assert len(unresolved) <= 2
    assert all(not b._prefetched for b in unresolved)

    oracle = OracleRunner()
    drive_synctest(oracle, 80, check_distance=4, seed=3)
    dev = backend.state_numpy()
    for key in ("frame", "pos", "vel", "rot"):
        np.testing.assert_array_equal(np.asarray(dev[key]), oracle.state[key])


def test_checksum_ledger_batches_fetches(monkeypatch):
    """One resolve() call must fetch ALL pending batches in a single
    jax.device_get (the transfer-count contract the request path's speed relies on)."""
    import jax

    from ggrs_tpu.tpu import TpuRollbackBackend

    game = ex_game.ExGame(NUM_PLAYERS, 64)
    backend = TpuRollbackBackend(game, max_prediction=4, num_players=NUM_PLAYERS)
    sess = (
        SessionBuilder(input_size=1)
        .with_num_players(NUM_PLAYERS)
        .with_max_prediction_window(4)
        .with_check_distance(2)
        .start_synctest_session()
    )
    cells = []
    for frame in range(8):
        for h in range(NUM_PLAYERS):
            sess.add_local_input(h, bytes([frame % 5]))
        reqs = sess.advance_frame()
        backend.handle_requests(reqs)
        cells += [r.cell for r in reqs if isinstance(r, SaveGameState)]
    # Reading ONE checksum must resolve every pending batch via a single
    # packed device->host transfer; the remaining reads must cost nothing.
    import ggrs_tpu.tpu.backend as backend_mod

    transfers = []
    orig_asarray = np.asarray

    def counting_asarray(x, *args, **kwargs):
        if isinstance(x, jax.Array):
            transfers.append(1)
        return orig_asarray(x, *args, **kwargs)

    monkeypatch.setattr(backend_mod.np, "asarray", counting_asarray)
    _ = [c.checksum for c in cells[-4:]]
    assert sum(transfers) == 1
    assert all(b._np is not None for b in backend.ledger._pending) or not backend.ledger._pending
