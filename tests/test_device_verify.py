"""On-device SyncTest verification in the request-path backend: the
first-seen checksum history and mismatch verdict live on device, so a
determinism run makes ZERO per-burst checksum readbacks (each readback
is a host/device synchronization on the interactive path). Semantics mirror the fused session's _save_and_check /
the reference comparison (src/sessions/sync_test_session.rs:85-146)."""

import numpy as np
import pytest

import jax

from ggrs_tpu import SessionBuilder
from ggrs_tpu.errors import MismatchedChecksum
from ggrs_tpu.models.ex_game import ExGame
from ggrs_tpu.tpu import TpuRollbackBackend

PLAYERS = 2
ENTITIES = 128


def make_backend(beam_width=0, device_verify=True, max_prediction=8):
    return TpuRollbackBackend(
        ExGame(PLAYERS, ENTITIES),
        max_prediction=max_prediction,
        num_players=PLAYERS,
        beam_width=beam_width,
        device_verify=device_verify,
    )


def make_session(check_distance=4, max_prediction=8):
    return (
        SessionBuilder(input_size=1)
        .with_num_players(PLAYERS)
        .with_max_prediction_window(max_prediction)
        .with_check_distance(check_distance)
        .with_device_checksum_verification()
        .start_synctest_session()
    )


def drive(backend, frames, sess=None, check_distance=4, inputs_for=None,
          start=0):
    sess = sess or make_session(check_distance)
    inputs_for = inputs_for or (lambda t, h: bytes([(t * (3 + h) + h) % 16]))
    for t in range(start, start + frames):
        for h in range(PLAYERS):
            sess.add_local_input(h, inputs_for(t, h))
        backend.handle_requests(sess.advance_frame())
    return sess


def test_clean_run_verdict_clean():
    backend = make_backend()
    drive(backend, 60)
    backend.check()  # no divergence: must not raise
    mismatch, frame = backend.core.check_device_verdict()
    assert not mismatch and frame == -1


def test_injected_ring_corruption_is_latched():
    """Corrupt a saved snapshot between ticks: the next re-save of that
    frame recomputes a different checksum than first recorded — the device
    latch must trip with the right frame and stay tripped."""
    backend = make_backend()
    sess = drive(backend, 30, check_distance=4)
    backend.check()
    core = backend.core
    # corrupt the frame the NEXT tick's rollback loads (current - d): any
    # later frame's slot is re-saved clean before it would be read
    bad_frame = backend.current_frame - 4
    slot = bad_frame % core.ring_len
    core.ring = {
        **core.ring,
        "pos": core.ring["pos"].at[slot, 0, 0].add(7),
    }
    drive(backend, 10, sess=sess, start=30)
    # the first divergent RE-SAVE is the frame after the corrupted load
    # (the loaded frame itself is not re-saved by the request grammar)
    with pytest.raises(MismatchedChecksum) as exc:
        backend.check()
    assert exc.value.frame == bad_frame + 1
    # the latch holds the FIRST mismatching frame even as the run continues
    drive(backend, 10, sess=sess, start=40)
    with pytest.raises(MismatchedChecksum) as exc2:
        backend.check()
    assert exc2.value.frame == bad_frame + 1


def test_device_verify_through_beam_adoption():
    """Adopted rollbacks feed the same device history (their checksums come
    from the speculation): constant inputs make every rollback adopt, and
    the verdict must stay clean — then an injected corruption must still
    be caught on the resim that re-saves it."""
    backend = make_backend(beam_width=8)
    drive(backend, 40, check_distance=3, inputs_for=lambda t, h: bytes([h + 1]))
    assert backend.beam_hits > 10
    backend.check()


def test_requires_device_verify_flag():
    backend = make_backend(device_verify=False)
    drive(backend, 10)
    with pytest.raises(AssertionError):
        backend.check()


def test_no_readbacks_during_run(monkeypatch):
    """The whole point: a device-verified run transfers nothing back per
    tick. Count device_get calls AND ledger flushes (the two device->host
    paths) across 40 ticks — only the final check() may fetch, once."""
    backend = make_backend()
    sess = drive(backend, 5)  # warm/compile outside the counted window
    gets, flushes = [], []
    orig = jax.device_get
    monkeypatch.setattr(jax, "device_get", lambda x: (gets.append(1), orig(x))[1])
    monkeypatch.setattr(backend.ledger, "flush", lambda: flushes.append(1))
    drive(backend, 40, sess=sess, start=5)
    assert sum(gets) == 0 and sum(flushes) == 0, "run performed readbacks"
    # nobody resolved any checksum batch either
    assert all(b._np is None for b in backend.ledger._pending)
    backend.check()
    assert sum(gets) == 1


def test_mispaired_flush_fails_loudly():
    """A device-verify session must not silently no-op host verification
    APIs (a mispaired run would report vacuous success)."""
    from ggrs_tpu.errors import InvalidRequest

    sess = make_session()
    with pytest.raises(InvalidRequest, match="backend.check"):
        sess.flush_checksum_checks()
