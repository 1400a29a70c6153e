"""Coalesced SyncTest dispatch (ggrs_tpu/tpu/sync_test.py): consecutive
advance_frames calls run as one device program per group of batches, and
anything that reads the carry dispatches what is staged first.

Parity: two sessions take the same calls; one reads its carry after every
call, so each batch is a dispatch of its own, the other reads only where
the scenario must. Their carries, frames and verdicts must agree word for
word, on the XLA scan, both interpreted kernels and the sharded tiled core
on a 4-device CPU mesh. Engagement: the dispatch counters, and no compile
after the first call of a length."""

import jax
import jax.tree_util as jtu
import numpy as np
import pytest

from ggrs_tpu.errors import InvalidRequest, MismatchedChecksum
from ggrs_tpu.models.ex_game import ExGame
from ggrs_tpu.obs import GLOBAL_TELEMETRY
from ggrs_tpu.tpu import TpuSyncTestSession
from ggrs_tpu.tpu import sync_test

P = 2
D = 3
T = 120  # K = 480 // 120 = 4 batches per group
T2 = 96  # K = 5


def _script(frames, seed=3):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 16, size=(frames, P, 1), dtype=np.uint8)


BACKENDS = {
    # backend, entities, mesh devices
    "xla": ("xla", 128, None),
    "pallas-interpret": ("pallas-interpret", 256, None),
    "pallas-tiled-interpret": ("pallas-tiled-interpret", 512, None),
    "sharded-tiled": ("pallas-tiled-interpret", 512, 4),
}


def _session(which, **kw):
    from ggrs_tpu.parallel.mesh import make_mesh

    backend, entities, devices = BACKENDS[which]
    mesh = make_mesh(devices) if devices else None
    return TpuSyncTestSession(ExGame(P, entities), num_players=P,
                              check_distance=D, backend=backend, mesh=mesh,
                              **kw)


def _carry(sess):
    return jtu.tree_leaves_with_path(jax.device_get(sess.carry))


def _corrupt_next_load(sess):
    """Add 7 to every position in the ring slot the next frame's rollback
    loads (c - d); returns the frame its re-save latches (c - d + 1)."""
    c = sess.current_frame
    ring = dict(sess.carry["ring"])
    ring["pos"] = ring["pos"].at[(c - D) % sess.ring_len].add(7)
    sess.carry = {**sess.carry, "ring": ring}
    return c - D + 1


def _run(which, scenario, observe, tmp_path):
    """Drive one session through `scenario`; `observe` reads the carry
    after every call. Returns the final session and what it reported."""
    kw = {"input_delay": 2} if scenario == "input_delay" else {}
    if scenario == "flush_interval":
        kw["flush_interval"] = 4 * T + 1
    sess = _session(which, **kw)
    script = _script(8 * T)
    lengths = {
        "partial_group": [T] * 6,  # one group, two staged at check()
        "length_change": [T, T, T2, T2, T2, T, T],
        "input_delay": [T] * 6,
        "flush_interval": [T] * 6,  # check() from inside call 5
        "save_restore": [T] * 6,
        "corrupt_ring": [T] * 6,
    }[scenario]
    reported = []
    pos = 0
    for i, t in enumerate(lengths):
        if scenario == "corrupt_ring" and i == 2:
            reported.append(("latches", _corrupt_next_load(sess)))
        if scenario == "save_restore" and i == 3:
            path = str(tmp_path / f"{which}-{observe}.npz")
            sess.save(path)  # three batches staged unless observed
            sess = TpuSyncTestSession.restore(
                path, sess.game, backend=sess.backend
            )
        sess.advance_frames(script[pos:pos + t])
        pos += t
        if observe:
            _ = sess.carry
    try:
        sess.check()
        reported.append(("verdict", None))
    except MismatchedChecksum as e:
        reported.append(("verdict", e.frame))
    reported.append(("frame", sess.current_frame))
    return sess, reported


# host-side bookkeeping (lengths, delay, auto-check, checkpoints) on the XLA
# scan; what reaches a kernel's program (a group, a partial group, a
# verdict latched inside a group) on every backend
SCENARIOS = ["partial_group", "length_change", "input_delay",
             "flush_interval", "save_restore", "corrupt_ring"]
CASES = [(w, s) for s in SCENARIOS for w in BACKENDS
         if w == "xla" or s in ("partial_group", "corrupt_ring")]


@pytest.mark.parametrize("which,scenario", CASES)
def test_coalesced_matches_per_call_dispatch(which, scenario, tmp_path):
    each, said_each = _run(which, scenario, True, tmp_path)
    grouped, said_grouped = _run(which, scenario, False, tmp_path)
    assert said_grouped == said_each
    if scenario == "corrupt_ring":
        assert said_each[0] == ("latches", said_each[1][1])
    else:
        assert said_each[0] == ("verdict", None)
    a, b = _carry(each), _carry(grouped)
    assert len(a) == len(b)
    for (path, x), (_, y) in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=jtu.keystr(path))


@pytest.fixture
def telemetry():
    GLOBAL_TELEMETRY.reset()
    GLOBAL_TELEMETRY.enabled = True
    try:
        yield GLOBAL_TELEMETRY.registry
    finally:
        GLOBAL_TELEMETRY.enabled = False
        GLOBAL_TELEMETRY.reset()


def _counted(registry):
    snap = registry.snapshot()
    return tuple(
        int(sum(snap[name]["values"].values()))
        for name in ("ggrs_synctest_batches_total",
                     "ggrs_synctest_dispatches_total")
    )


@pytest.mark.parametrize("t,observe,dispatches", [
    (60, False, 8),  # 64 calls in groups of 8, none staged at check()
    (60, True, 64),  # the carry read after every call: one call each
    (480, False, 64),  # a batch of COALESCE_FRAMES runs alone
    (500, False, 64),
])
def test_dispatches_per_call(telemetry, t, observe, dispatches):
    sess = TpuSyncTestSession(ExGame(P, 64), num_players=P,
                              check_distance=2, backend="xla")
    script = _script(t)
    for _ in range(64):
        sess.advance_frames(script)
        if observe:
            _ = sess.carry
    sess.check()
    assert _counted(telemetry) == (64, dispatches)
    assert sess.current_frame == 64 * t


@pytest.mark.parametrize("which", ["xla", "sharded-tiled"])
def test_no_compile_after_first_call_of_a_length(which):
    """The first call of a length compiles the group program, the first
    partial flush the single-batch one; 2K more calls and their check()
    compile nothing."""
    sess = _session(which)
    batch = _script(T)
    sess.advance_frames(batch)
    sess.check()
    compiles = []

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        for _ in range(2 * (sync_test.COALESCE_FRAMES // T)):
            sess.advance_frames(batch)
        sess.check()
        sess.block_until_ready()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert compiles == []


@pytest.mark.parametrize("shape", [(0, P, 1), (4, P + 1, 1), (4, P, 2),
                                   (4, P)])
def test_bad_input_shape_raises_on_the_call(shape):
    sess = TpuSyncTestSession(ExGame(P, 64), num_players=P,
                              check_distance=2, backend="xla")
    with pytest.raises(InvalidRequest):
        sess.advance_frames(np.zeros(shape, np.uint8))
    assert sess.current_frame == 0
