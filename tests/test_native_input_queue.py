"""C++ InputQueue parity: random operation sequences must behave identically
to the Python oracle (same outputs, same errors, same internal watermarks)."""

import os
import random

import pytest

from ggrs_tpu.frame_info import PlayerInput
from ggrs_tpu.input_queue import InputQueue


@pytest.fixture(scope="module")
def native_queue_cls():
    from ggrs_tpu import native as nat

    if not nat.available():
        if os.path.exists(nat._LIB_PATH):
            pytest.fail("native library built but failed to load")
        pytest.skip("native library not built (make -C native)")
    from ggrs_tpu.native.input_queue import NativeInputQueue

    return NativeInputQueue


def run_both(py_q, nat_q, op, *args):
    """Apply an operation to both queues; both must agree on result or both
    must fail."""
    results = []
    for q in (py_q, nat_q):
        try:
            results.append(("ok", getattr(q, op)(*args)))
        except AssertionError:
            results.append(("err", None))
    (k1, v1), (k2, v2) = results
    assert k1 == k2, f"{op}{args}: python={k1}, native={k2}"
    if k1 == "ok":
        if op == "confirmed_input":
            assert v1.buf == v2.buf and v1.frame == v2.frame
        else:
            assert v1 == v2, f"{op}{args}: {v1} != {v2}"


def check_watermarks(py_q, nat_q):
    assert py_q.first_incorrect_frame == nat_q.first_incorrect_frame
    assert py_q.last_added_frame == nat_q.last_added_frame
    assert py_q.length == nat_q.length


@pytest.mark.parametrize("input_size", [1, 4])
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_random_operation_sequences(native_queue_cls, input_size, seed):
    rng = random.Random(seed)
    py_q = InputQueue(input_size)
    nat_q = native_queue_cls(input_size)

    next_frame = 0
    for _ in range(600):
        op = rng.random()
        if op < 0.45:
            buf = bytes(rng.randrange(4) for _ in range(input_size))
            run_both(py_q, nat_q, "add_input", PlayerInput(next_frame, buf))
            if py_q.last_added_frame != -1:
                next_frame += 1
        elif op < 0.8:
            # fetch near the frontier: confirmed or prediction
            target = max(0, next_frame - rng.randrange(0, 4) + rng.randrange(0, 3))
            if py_q.first_incorrect_frame == -1:
                run_both(py_q, nat_q, "input", target)
        elif op < 0.88:
            run_both(py_q, nat_q, "reset_prediction")
        elif op < 0.95:
            if py_q.last_added_frame > 2:
                frame = rng.randrange(0, py_q.last_added_frame)
                run_both(py_q, nat_q, "discard_confirmed_frames", frame)
        else:
            if py_q.last_added_frame >= 0:
                run_both(py_q, nat_q, "confirmed_input", py_q.last_added_frame)
        check_watermarks(py_q, nat_q)


def test_frame_delay_parity(native_queue_cls):
    for delay in (0, 2, 5):
        py_q = InputQueue(1)
        nat_q = native_queue_cls(1)
        py_q.set_frame_delay(delay)
        nat_q.set_frame_delay(delay)
        for i in range(30):
            run_both(py_q, nat_q, "add_input", PlayerInput(i, bytes([i % 7])))
            run_both(py_q, nat_q, "input", i)
            check_watermarks(py_q, nat_q)


def test_misprediction_detection_parity(native_queue_cls):
    py_q = InputQueue(1)
    nat_q = native_queue_cls(1)
    for q in (py_q, nat_q):
        q.add_input(PlayerInput(0, b"\x07"))
        q.input(1)  # predict 7
        q.input(2)
        q.add_input(PlayerInput(1, b"\x09"))  # wrong prediction
    assert py_q.first_incorrect_frame == nat_q.first_incorrect_frame == 1
    for q in (py_q, nat_q):
        q.reset_prediction()
    run_both(py_q, nat_q, "input", 1)
    check_watermarks(py_q, nat_q)


def test_session_with_native_queues_matches_python_queues(native_queue_cls):
    """A full SyncTest session run must be byte-identical between queue
    implementations (same request stream, same stub evolution)."""
    from ggrs_tpu import SessionBuilder
    from stubs import GameStub

    def run(native):
        sess = (
            SessionBuilder(input_size=2)
            .with_num_players(2)
            .with_check_distance(3)
            .with_input_delay(1)
            .with_native_input_queues(native)
            .start_synctest_session()
        )
        stub = GameStub()
        for frame in range(120):
            for h in range(2):
                sess.add_local_input(h, bytes([frame % 9, (frame * 3 + h) % 5]))
            stub.handle_requests(sess.advance_frame())
        return stub

    a = run(False)
    b = run(True)
    assert a.gs.frame == b.gs.frame
    assert a.gs.state == b.gs.state
    assert a.history == b.history
    assert a.saved_frames == b.saved_frames
    assert a.loaded_frames == b.loaded_frames
