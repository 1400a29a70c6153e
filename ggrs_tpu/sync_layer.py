"""Rollback bookkeeping: snapshot ring + per-player input queues.

Behavioral parity with the reference (src/sync_layer.rs). The snapshot ring
holds ``max_prediction + 2`` cells addressed by ``frame % len``
(src/sync_layer.rs:61-75); save/load are *requests* fulfilled by the caller,
so state stays opaque — a user object on the CPU path, or a device ring slot
handle on the TPU path (ggrs_tpu.tpu.backend).
"""

from __future__ import annotations

import threading
from typing import Any, List, Optional, Sequence, Tuple

from .errors import PredictionThreshold
from .frame_info import GameState, PlayerInput
from .input_queue import InputQueue
from .obs import GLOBAL_TELEMETRY, LOG2_BUCKETS
from .types import (
    NULL_FRAME,
    Frame,
    InputStatus,
    LoadGameState,
    PlayerHandle,
    Request,
    SaveGameState,
)


class ConnectionStatus:
    """Connection status of one player as seen by one peer
    (src/network/messages.rs:6-18)."""

    __slots__ = ("disconnected", "last_frame")

    def __init__(self, disconnected: bool = False, last_frame: Frame = NULL_FRAME):
        self.disconnected = disconnected
        self.last_frame = last_frame

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return f"ConnectionStatus(disconnected={self.disconnected}, last_frame={self.last_frame})"

    def __eq__(self, other: object) -> bool:
        return (
            isinstance(other, ConnectionStatus)
            and self.disconnected == other.disconnected
            and self.last_frame == other.last_frame
        )


class GameStateCell:
    """A shared, lockable snapshot slot handed to the user inside
    Save/Load requests (src/sync_layer.rs:15-52)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._state = GameState()
        self._checksum_fn = None

    def save(self, frame: Frame, data: Any, checksum: Optional[int]) -> None:
        assert frame != NULL_FRAME
        with self._lock:
            self._state.frame = frame
            self._state.data = data
            self._state.checksum = checksum
            self._checksum_fn = None

    def save_lazy(self, frame: Frame, data: Any, checksum_fn) -> None:
        """Like save(), but the checksum is produced on demand. Used by the
        device backend so a tick doesn't block on a device->host transfer
        unless someone actually reads the checksum."""
        assert frame != NULL_FRAME
        with self._lock:
            self._state.frame = frame
            self._state.data = data
            self._state.checksum = None
            self._checksum_fn = checksum_fn

    def load(self) -> Any:
        with self._lock:
            return self._state.data

    @property
    def frame(self) -> Frame:
        with self._lock:
            return self._state.frame

    @property
    def checksum(self) -> Optional[int]:
        with self._lock:
            if self._checksum_fn is not None:
                self._state.checksum = self._checksum_fn()
                self._checksum_fn = None
            return self._state.checksum

    def checksum_getter(self):
        """Zero-arg callable producing this save's checksum, stable across
        later overwrites of the cell (ring slots are reused every
        ring_len frames). Lets callers defer the read — on the device
        backend forcing `checksum` blocks on a device->host transfer."""
        with self._lock:
            if self._checksum_fn is not None:
                return self._checksum_fn
            value = self._state.checksum
            return lambda: value

    def __getstate__(self):
        # cross-process hop (fleet wire tickets): the lock is rebuilt on
        # the other side, and a still-lazy checksum is RESOLVED here —
        # the getter contract makes early resolution observationally
        # neutral (same value, cached in place), while a pickled lazy fn
        # would drag device arrays into the ticket
        return {
            "frame": self.frame,
            "data": self.load(),
            "checksum": self.checksum,  # forces _checksum_fn if pending
        }

    def __setstate__(self, state):
        self._lock = threading.Lock()
        self._state = GameState()
        self._state.frame = state["frame"]
        self._state.data = state["data"]
        self._state.checksum = state["checksum"]
        self._checksum_fn = None


class _ResolvedGetter:
    """A picklable stand-in for a bound checksum getter whose value was
    resolved before a cross-process hop: same call contract as
    GameStateCell.checksum_getter's return (callable, `ready` True)."""

    __slots__ = ("value",)
    ready = True

    def __init__(self, value):
        self.value = value

    def __call__(self):
        return self.value


class PendingChecksumReport:
    """Deferred desync-detection reports, shared by the Python and native P2P
    sessions (p2p_session.py / native/session.py).

    Capture the *cell* at tick t; bind its checksum getter on the first
    flush attempt — one tick later at the earliest, once the capturing
    tick's requests are fulfilled and the cell holds the converged value
    (reading it in the same tick can publish a mid-correction checksum and
    raise false desyncs); then keep the getter, because getters are stable
    across later overwrites of the reused ring slot (GameStateCell
    .checksum_getter) while the cell itself is not.

    Multiple reports can be outstanding at once (a queue, not a single
    slot): under the async dispatch pipeline a checksum may still be
    in-flight on the device when the next observation interval arrives,
    and the old single-slot design silently dropped the unflushed report.
    Between interval ticks bind_and_prefetch binds getters and starts a
    background copy of the oldest in-flight value, emitting nothing; the
    interval tick's flush emits every report in capture (frame) order.
    Reports whose ring slot was reused before the first read are
    dropped, as before."""

    # outstanding-report bound: ~two ring rotations of observations. Past
    # it the oldest report's slot is long reused and it would drop at
    # binding time anyway; the bound just keeps a never-flushing caller
    # from accumulating cells.
    MAX_PENDING = 16

    __slots__ = ("_pending",)

    def __init__(self) -> None:
        from collections import deque

        self._pending = deque()

    def __len__(self) -> int:
        return len(self._pending)

    def __getstate__(self):
        # cross-process hop: entries travel with their cell references
        # (object sharing with saved_states is preserved by pickle), and
        # an already-BOUND getter — a device-lazy checksum or a closure,
        # both unpicklable — is resolved to its value now. Resolution is
        # value-only (no emit), so it cannot perturb message timing: the
        # flush on the receiving side emits the identical report at the
        # identical tick the un-serialized twin would have.
        entries = []
        for frame, cell, getter, serial in self._pending:
            if getter is not None and not isinstance(getter, _ResolvedGetter):
                getter = _ResolvedGetter(getter())
            entries.append([frame, cell, getter, serial])
        return {"pending": entries}

    def __setstate__(self, state):
        from collections import deque

        self._pending = deque(state["pending"])

    def capture(self, frame: Frame, cell: GameStateCell, serial: int = 0) -> None:
        """`serial` stamps the capturing tick (a monotonic advance
        counter): bind_and_prefetch can then skip entries whose
        capturing tick's requests may not be fulfilled yet (its
        `max_serial`)."""
        self._pending.append([frame, cell, None, serial])
        while len(self._pending) > self.MAX_PENDING:
            self._pending.popleft()

    def _bind(self, max_serial: Optional[int]) -> None:
        """Bind a getter for EVERY queued old-enough report (not just the
        head: binding is cheap and non-blocking, getters are stable
        across later ring-slot reuse, and a younger report's slot can be
        overwritten while an older value is still in flight), dropping
        entries whose ring slot was reused before the first read. THE
        one binding walk — flush() and bind_and_prefetch() both route
        through it, so the serial guard and the reuse drop can never
        diverge between the emitting and the resolve-only paths."""
        from collections import deque

        bound = deque()
        for entry in self._pending:
            frame, cell, getter, serial = entry
            if getter is None:
                if max_serial is not None and serial > max_serial:
                    bound.append(entry)  # too fresh to bind yet
                    continue
                if cell.frame != frame:  # ring slot reused before read
                    continue
                entry[2] = cell.checksum_getter()
            bound.append(entry)
        self._pending = bound

    def bind_and_prefetch(self, max_serial: Optional[int] = None) -> None:
        """The resolve-only half of flush(), run between interval ticks:
        bind getters for every old-enough entry and start a background
        prefetch on the head, so the interval-forced flush later finds
        the bytes already moved — but emit NOTHING. Report emission then
        happens at fixed interval ticks regardless of when device values
        became host-ready, which keeps the wire byte-stream independent
        of dispatch cadence — the property that lets a resident
        (mailbox-driven) host put bit-identical traffic on a seeded
        lossy network as its dispatch-per-tick twin.

        `max_serial` (the pump pass): only entries whose capture serial
        is <= it are bound — a report captured at tick t covers a frame
        whose *correcting* rollback may still sit in tick t's
        unfulfilled (or, hosted, un-dispatched) request list, so the
        pump stays a couple of advances behind the capture frontier.
        Getters still waiting on an UNDISPATCHED batch (a resident fill
        cycle's future) are left alone: prefetching those would force
        the very driver dispatch the mailbox exists to defer."""
        self._bind(max_serial)
        for _frame, _cell, getter, _serial in self._pending:
            if getter is None:
                return
            if not getattr(getter, "ready", True):
                if not getattr(getter, "dispatch_pending", False):
                    prefetch = getattr(getter, "prefetch", None)
                    if callable(prefetch):
                        prefetch()
                return

    def flush(self, emit) -> int:
        """The interval tick's publish: bind and resolve every captured
        report, calling emit(frame, checksum) at most once each, in
        capture order. Returns the number of reports that were resolved
        while NOT host-ready — i.e. that blocked on a device transfer
        (the drain the pump-side bind_and_prefetch exists to make zero
        in steady state)."""
        self._bind(None)
        blocked = 0
        while self._pending:
            frame, _cell, getter, _serial = self._pending.popleft()
            if not getattr(getter, "ready", True):
                blocked += 1
            checksum = getter()
            if checksum is not None:
                emit(frame, checksum)
        return blocked


class SavedStates:
    """Ring of snapshot cells; capacity max_prediction + 2 so the next frame
    has a slot while the full rollback distance stays loadable
    (src/sync_layer.rs:54-76)."""

    def __init__(self, max_prediction: int):
        self.states: List[GameStateCell] = [
            GameStateCell() for _ in range(max_prediction + 2)
        ]

    def get_cell(self, frame: Frame) -> GameStateCell:
        assert frame >= 0
        return self.states[frame % len(self.states)]


class SyncLayer:
    """(src/sync_layer.rs:78-273)"""

    def __init__(
        self,
        num_players: int,
        max_prediction: int,
        input_size: int,
        use_native_queues: bool = False,
    ):
        self.num_players = num_players
        self.max_prediction = max_prediction
        self.input_size = input_size
        self.saved_states = SavedStates(max_prediction)
        self.last_confirmed_frame: Frame = NULL_FRAME
        self._last_saved_frame: Frame = NULL_FRAME
        self.current_frame: Frame = 0
        if use_native_queues:
            from .native.input_queue import NativeInputQueue

            self.input_queues = [NativeInputQueue(input_size) for _ in range(num_players)]
        else:
            self.input_queues = [InputQueue(input_size) for _ in range(num_players)]
        # stamp the owning player onto each queue so its prediction
        # counters carry a player label (native queues ignore it)
        for i, q in enumerate(self.input_queues):
            q.obs_player = i
        # pre-bound telemetry instruments (updated only when enabled)
        _reg = GLOBAL_TELEMETRY.registry
        self._m_saves = _reg.counter(
            "ggrs_state_saves_total", "SaveGameState requests emitted"
        )
        self._m_loads = _reg.counter(
            "ggrs_state_loads_total", "LoadGameState requests emitted (rollbacks)"
        )
        self._m_depth = _reg.histogram(
            "ggrs_rollback_depth_frames",
            "frames resimulated per rollback",
            buckets=LOG2_BUCKETS,
        )
        self._m_lag = _reg.gauge(
            "ggrs_confirmed_frame_lag",
            "current frame minus last confirmed frame",
        )

    def advance_frame(self) -> None:
        self.current_frame += 1

    def save_current_state(self) -> Request:
        self._last_saved_frame = self.current_frame
        if GLOBAL_TELEMETRY.enabled:
            self._m_saves.inc()
        cell = self.saved_states.get_cell(self.current_frame)
        return SaveGameState(cell=cell, frame=self.current_frame)

    def set_frame_delay(self, player_handle: PlayerHandle, delay: int) -> None:
        assert player_handle < self.num_players
        self.input_queues[player_handle].set_frame_delay(delay)

    def reset_prediction(self) -> None:
        for q in self.input_queues:
            q.reset_prediction()

    def load_frame(self, frame_to_load: Frame) -> Request:
        """(src/sync_layer.rs:139-155)"""
        assert (
            frame_to_load != NULL_FRAME
            and frame_to_load < self.current_frame
            and frame_to_load >= self.current_frame - self.max_prediction
        ), "tried to load a frame outside the rollback window"
        cell = self.saved_states.get_cell(frame_to_load)
        assert cell.frame == frame_to_load
        if GLOBAL_TELEMETRY.enabled:
            self._m_loads.inc()
            self._m_depth.observe(self.current_frame - frame_to_load)
        self.current_frame = frame_to_load
        return LoadGameState(cell=cell, frame=frame_to_load)

    def add_local_input(self, player_handle: PlayerHandle, inp: PlayerInput) -> Frame:
        """Prediction-threshold gate + queue insert (src/sync_layer.rs:159-174).
        Raises PredictionThreshold when the speculation window is exhausted."""
        frames_ahead = self.current_frame - self.last_confirmed_frame
        if (
            self.current_frame >= self.max_prediction
            and frames_ahead >= self.max_prediction
        ):
            raise PredictionThreshold()
        assert inp.frame == self.current_frame
        return self.input_queues[player_handle].add_input(inp)

    def add_remote_input(self, player_handle: PlayerHandle, inp: PlayerInput) -> None:
        self.input_queues[player_handle].add_input(inp)

    def synchronized_inputs(
        self, connect_status: Sequence[ConnectionStatus]
    ) -> List[Tuple[bytes, InputStatus]]:
        """Inputs (confirmed or predicted) for the current frame; disconnected
        players yield zeroed dummies (src/sync_layer.rs:187-200)."""
        inputs: List[Tuple[bytes, InputStatus]] = []
        for i, status in enumerate(connect_status):
            if status.disconnected and status.last_frame < self.current_frame:
                inputs.append((bytes(self.input_size), InputStatus.DISCONNECTED))
            else:
                inputs.append(self.input_queues[i].input(self.current_frame))
        return inputs

    def confirmed_inputs(
        self, frame: Frame, connect_status: Sequence[ConnectionStatus]
    ) -> List[PlayerInput]:
        """(src/sync_layer.rs:203-217)"""
        inputs: List[PlayerInput] = []
        for i, status in enumerate(connect_status):
            if status.disconnected and status.last_frame < frame:
                inputs.append(PlayerInput.blank(NULL_FRAME, self.input_size))
            else:
                inputs.append(self.input_queues[i].confirmed_input(frame))
        return inputs

    def set_last_confirmed_frame(self, frame: Frame, sparse_saving: bool) -> None:
        """Raise the confirmed watermark and GC inputs before it
        (src/sync_layer.rs:220-244)."""
        first_incorrect = NULL_FRAME
        for q in self.input_queues:
            first_incorrect = max(first_incorrect, q.first_incorrect_frame)

        if sparse_saving:
            frame = min(frame, self._last_saved_frame)

        assert first_incorrect == NULL_FRAME or first_incorrect >= frame, (
            "would discard inputs still needed for rollback"
        )
        self.last_confirmed_frame = frame
        if GLOBAL_TELEMETRY.enabled and frame != NULL_FRAME:
            self._m_lag.set(self.current_frame - frame)
        if self.last_confirmed_frame > 0:
            for q in self.input_queues:
                q.discard_confirmed_frames(frame - 1)

    def check_simulation_consistency(self, first_incorrect: Frame) -> Frame:
        """Earliest misprediction across all queues (src/sync_layer.rs:247-257)."""
        for q in self.input_queues:
            incorrect = q.first_incorrect_frame
            if incorrect != NULL_FRAME and (
                first_incorrect == NULL_FRAME or incorrect < first_incorrect
            ):
                first_incorrect = incorrect
        return first_incorrect

    def saved_state_by_frame(self, frame: Frame) -> Optional[GameStateCell]:
        cell = self.saved_states.get_cell(frame)
        return cell if cell.frame == frame else None

    def pending_predicted_inputs(self) -> List[dict]:
        """Per-player predictions still standing in (JSON-able form, for
        the desync forensics bundle): which players are being speculated
        on, at what frame, with what repeated input."""
        out: List[dict] = []
        for player, q in enumerate(self.input_queues):
            pred = getattr(q, "prediction", None)  # native queues: None
            if pred is not None and pred.frame != NULL_FRAME:
                out.append(
                    {"player": player, "frame": pred.frame, "input": pred.buf.hex()}
                )
        return out

    @property
    def last_saved_frame(self) -> Frame:
        return self._last_saved_frame
