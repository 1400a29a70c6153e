"""Lazy tick batching (TpuRollbackBackend(lazy_ticks=N)): ticks accumulate
as packed control words and dispatch as ONE fused multi-tick program when
the buffer fills or a device result is needed. Each dispatch costs a
fixed amount of host time regardless of content, so this divides
the interactive request path's dominant cost by the buffer depth — while
staying bit-identical to per-tick dispatch (these tests are the proof)."""

import numpy as np
import pytest

from ggrs_tpu import SessionBuilder
from ggrs_tpu.models.ex_game import ExGame
from ggrs_tpu.tpu import TpuRollbackBackend

ENTITIES = 64
PLAYERS = 2


def make_backend(lazy_ticks=0, **kw):
    return TpuRollbackBackend(
        ExGame(num_players=PLAYERS, num_entities=ENTITIES),
        max_prediction=6,
        num_players=PLAYERS,
        lazy_ticks=lazy_ticks,
        **kw,
    )


def make_synctest(check_distance=4):
    return (
        SessionBuilder(input_size=1)
        .with_num_players(PLAYERS)
        .with_max_prediction_window(6)
        .with_check_distance(check_distance)
        .start_synctest_session()
    )


def drive_pair(lazy, plain, ticks, inputs_for):
    sess_lazy, sess_plain = make_synctest(), make_synctest()
    lazy_cells, plain_cells = [], []
    for t in range(ticks):
        for h in range(PLAYERS):
            buf = inputs_for(t, h)
            sess_lazy.add_local_input(h, buf)
            sess_plain.add_local_input(h, buf)
        rl = sess_lazy.advance_frame()
        rp = sess_plain.advance_frame()
        lazy.handle_requests(rl)
        plain.handle_requests(rp)
        lazy_cells += [r.cell for r in rl if hasattr(r, "cell")]
        plain_cells += [r.cell for r in rp if hasattr(r, "cell")]
    return lazy_cells, plain_cells


def assert_states_equal(a, b):
    sa, sb = a.state_numpy(), b.state_numpy()
    for k in sa:
        np.testing.assert_array_equal(np.asarray(sa[k]), np.asarray(sb[k]),
                                      err_msg=f"state[{k}]")


@pytest.mark.parametrize("lazy_ticks", [3, 8])
def test_lazy_bit_parity_with_per_tick_dispatch(lazy_ticks):
    """Same SyncTest request stream (forced rollbacks included, buffered
    mid-stream) through a lazy and a per-tick backend: final state and
    EVERY saved checksum bit-identical. Checksums resolve through the
    future batch, which forces the flush."""
    lazy, plain = make_backend(lazy_ticks), make_backend(0)
    lc, pc = drive_pair(
        lazy, plain, 25, lambda t, h: bytes([(t * (3 + h) + h) % 16])
    )
    assert_states_equal(lazy, plain)
    assert len(lc) == len(pc)
    for cl, cp in zip(lc, pc):
        assert cl.frame == cp.frame
        assert cl.checksum == cp.checksum, f"checksum at frame {cl.frame}"


def test_lazy_state_fetch_flushes_mid_buffer():
    """state_numpy() between flush points must materialize the buffered
    ticks (the rendering path gets per-tick behavior automatically)."""
    lazy, plain = make_backend(8), make_backend(0)
    sess_lazy, sess_plain = make_synctest(), make_synctest()
    for t in range(9):
        for h in range(PLAYERS):
            sess_lazy.add_local_input(h, bytes([t % 7]))
            sess_plain.add_local_input(h, bytes([t % 7]))
        lazy.handle_requests(sess_lazy.advance_frame())
        plain.handle_requests(sess_plain.advance_frame())
        # mid-buffer fetch every tick: identical to per-tick dispatch
        assert_states_equal(lazy, plain)


def test_lazy_composes_with_beam():
    """Lazy batching + speculation: the rollout flushes the buffer before
    anchoring, adoptions flush before committing — still bit-identical."""
    lazy = make_backend(4, beam_width=8)
    plain = make_backend(0)
    drive_pair(lazy, plain, 30, lambda t, h: bytes([3 + 2 * h]))
    assert_states_equal(lazy, plain)
    assert lazy.beam_hits > 0  # constant inputs: adoptions must still fire


def test_lazy_checkpoint_flushes(tmp_path):
    """save() must not checkpoint a stale (pre-flush) device state."""
    lazy, plain = make_backend(8), make_backend(0)
    drive_pair(lazy, plain, 10, lambda t, h: bytes([t % 5]))
    path = str(tmp_path / "lazy.npz")
    lazy.save(path)
    restored = TpuRollbackBackend.restore(
        path, ExGame(num_players=PLAYERS, num_entities=ENTITIES)
    )
    assert_states_equal(restored, plain)
    assert restored.current_frame == lazy.current_frame
