"""ctypes wrappers for the native (C++) session core (native/session.cpp).

`NativeP2PSession`, `NativeSyncTestSession` and `NativeSpectatorSession`
expose the same Python surface as the sessions in ggrs_tpu.sessions (the
behavioral oracles), behind `SessionBuilder.with_native_sessions()`. A full
tick — message intake, rollback bookkeeping, input send — runs in C++; the
boundaries kept host-side are exactly the ones the C ABI names:

* **wire I/O** — this wrapper routes datagrams between the socket (UDP or
  the fault-injecting in-memory net) and endpoint indices,
* **game state** — native requests carry snapshot-ring *cell indices*; the
  wrapper owns the `GameStateCell` ring and hands out the same ordered
  `SaveGameState` / `LoadGameState` / `AdvanceFrame` request objects, so
  the TPU backend plugs in unchanged,
* **checksums** — materialized here (lazily, so a device backend never
  stalls a tick on a device->host transfer) and fed back for desync
  detection / SyncTest verification,
* **clocks** — every stateful call passes now_ms from the injectable Clock.

Wire format and protocol semantics are byte-identical to the Python stack,
so native sessions interoperate with Python sessions on the same network
(tests/test_native_session_core.py drives mixed pairs).
"""

from __future__ import annotations

import ctypes
import random as _random
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Sequence, Tuple

from ..errors import (
    ContractViolation,
    InvalidRequest,
    MismatchedChecksum,
    NotSynchronized,
    PredictionThreshold,
    SpectatorTooFarBehind,
)
from ..network.messages import decode_message, encode_message
from ..network.network_stats import NetworkStats
from ..network.sockets import RECV_BUFFER_SIZE
from ..sessions.sync_test_session import DeferredChecks
from ..sync_layer import GameStateCell, PendingChecksumReport, SavedStates
from ..types import (
    NULL_FRAME,
    AdvanceFrame,
    DesyncDetected,
    DesyncDetection,
    Disconnected,
    Event,
    Frame,
    InputStatus,
    LoadGameState,
    NetworkInterrupted,
    NetworkResumed,
    PlayerHandle,
    PlayerType,
    PlayerTypeKind,
    Request,
    SaveGameState,
    SessionState,
    Synchronized,
    Synchronizing,
    WaitRecommendation,
)
from ..utils.clock import Clock
from . import load

_MAX_PLAYERS = 16
_MAX_TOTAL_HANDLES = 32
_MAX_INPUT = 64
# drain-buffer cap for ggrs_sess_drain_wire: aliases the transport's
# shared receive bound (see native/endpoint.py _SEND_BUF_CAP — same
# truncation hazard, same wire-contract lint pin)
_WIRE_BUF_CAP = RECV_BUFFER_SIZE
_U128_MASK = (1 << 128) - 1
_INT32_MIN = -(1 << 31)

# session event tags (native/session.cpp SEV_*)
_SEV_SYNCHRONIZING = 1
_SEV_SYNCHRONIZED = 2
_SEV_DISCONNECTED = 3
_SEV_INTERRUPTED = 4
_SEV_RESUMED = 5
_SEV_WAIT_RECOMMENDATION = 6
_SEV_DESYNC_DETECTED = 7

# request tags (native/session.cpp REQ_*)
_REQ_SAVE = 0
_REQ_LOAD = 1
_REQ_ADVANCE = 2

# error codes (native/session.cpp SERR_*)
_SERR_NOT_SYNCHRONIZED = -2
_SERR_PREDICTION_THRESHOLD = -3
_SERR_MISSING_INPUT = -4
_SERR_MISMATCHED_CHECKSUM = -5
_SERR_SPECTATOR_TOO_FAR_BEHIND = -6
_SERR_INVALID_HANDLE = -7
_SERR_LOCAL_PLAYER = -8
_SERR_ALREADY_DISCONNECTED = -9
_SERR_CAPACITY = -11

_KIND_CODE = {
    PlayerTypeKind.LOCAL: 0,
    PlayerTypeKind.REMOTE: 1,
    PlayerTypeKind.SPECTATOR: 2,
}


class _SessConfig(ctypes.Structure):
    _fields_ = [
        ("session_type", ctypes.c_int32),
        ("num_players", ctypes.c_int32),
        ("max_prediction", ctypes.c_int32),
        ("input_size", ctypes.c_int32),
        ("input_delay", ctypes.c_int32),
        ("sparse_saving", ctypes.c_int32),
        ("desync_interval", ctypes.c_int32),
        ("check_distance", ctypes.c_int32),
        ("max_frames_behind", ctypes.c_int32),
        ("catchup_speed", ctypes.c_int32),
        ("fps", ctypes.c_int32),
        ("disconnect_timeout_ms", ctypes.c_int32),
        ("disconnect_notify_start_ms", ctypes.c_int32),
        ("total_handles", ctypes.c_int32),
        ("num_endpoints", ctypes.c_int32),
        ("player_kinds", ctypes.c_int32 * _MAX_TOTAL_HANDLES),
        ("player_endpoints", ctypes.c_int32 * _MAX_TOTAL_HANDLES),
        ("rng_seed", ctypes.c_uint64),
    ]


class _SessReq(ctypes.Structure):
    _fields_ = [
        ("type", ctypes.c_int32),
        ("frame", ctypes.c_int32),
        ("cell", ctypes.c_int32),
        ("statuses", ctypes.c_int32 * _MAX_PLAYERS),
        ("inputs", ctypes.c_uint8 * (_MAX_PLAYERS * _MAX_INPUT)),
    ]


class _SessEvent(ctypes.Structure):
    _fields_ = [
        ("type", ctypes.c_int32),
        ("ep", ctypes.c_int32),
        ("a", ctypes.c_int32),
        ("b", ctypes.c_int32),
        ("local_checksum", ctypes.c_uint8 * 16),
        ("remote_checksum", ctypes.c_uint8 * 16),
    ]


class _Stats(ctypes.Structure):
    _fields_ = [
        ("send_queue_len", ctypes.c_int32),
        ("ping_ms", ctypes.c_uint32),
        ("kbps_sent", ctypes.c_uint32),
        ("local_frames_behind", ctypes.c_int32),
        ("remote_frames_behind", ctypes.c_int32),
    ]


_configured = False


def _lib():
    global _configured
    lib = load()
    assert lib is not None, "native library not built (make -C native)"
    if not _configured:
        lib.ggrs_sess_new.restype = ctypes.c_void_p
        lib.ggrs_sess_new.argtypes = [ctypes.POINTER(_SessConfig), ctypes.c_uint64]
        lib.ggrs_sess_free.argtypes = [ctypes.c_void_p]
        lib.ggrs_sess_state.restype = ctypes.c_long
        lib.ggrs_sess_state.argtypes = [ctypes.c_void_p]
        for fn in (
            "ggrs_sess_current_frame",
            "ggrs_sess_confirmed_frame",
            "ggrs_sess_last_saved_frame",
            "ggrs_sess_frames_behind_host",
            "ggrs_sess_last_error_frame",
            "ggrs_sess_take_checksum_request",
            "ggrs_sess_request_count",
        ):
            getattr(lib, fn).restype = ctypes.c_int32
            getattr(lib, fn).argtypes = [ctypes.c_void_p]
        lib.ggrs_sess_frames_ahead.restype = ctypes.c_long
        lib.ggrs_sess_frames_ahead.argtypes = [ctypes.c_void_p]
        lib.ggrs_sess_connect_status.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint8),
            ctypes.POINTER(ctypes.c_int32), ctypes.c_long,
        ]
        lib.ggrs_sess_copy_requests.restype = ctypes.c_long
        lib.ggrs_sess_copy_requests.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(_SessReq), ctypes.c_long,
        ]
        lib.ggrs_sess_handle_wire.argtypes = [
            ctypes.c_void_p, ctypes.c_long, ctypes.c_char_p, ctypes.c_long,
            ctypes.c_uint64,
        ]
        lib.ggrs_sess_drain_wire.restype = ctypes.c_long
        lib.ggrs_sess_drain_wire.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_int32), ctypes.c_char_p,
            ctypes.c_long,
        ]
        lib.ggrs_sess_poll.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.ggrs_sess_add_local_input.restype = ctypes.c_long
        lib.ggrs_sess_add_local_input.argtypes = [
            ctypes.c_void_p, ctypes.c_long, ctypes.c_char_p,
        ]
        lib.ggrs_sess_advance_frame.restype = ctypes.c_long
        lib.ggrs_sess_advance_frame.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64, ctypes.POINTER(_SessReq),
            ctypes.c_long,
        ]
        lib.ggrs_sess_next_event.restype = ctypes.c_long
        lib.ggrs_sess_next_event.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(_SessEvent),
        ]
        lib.ggrs_sess_disconnect_player.restype = ctypes.c_long
        lib.ggrs_sess_disconnect_player.argtypes = [
            ctypes.c_void_p, ctypes.c_long, ctypes.c_uint64,
        ]
        lib.ggrs_sess_network_stats.restype = ctypes.c_long
        lib.ggrs_sess_network_stats.argtypes = [
            ctypes.c_void_p, ctypes.c_long, ctypes.c_uint64,
            ctypes.POINTER(_Stats),
        ]
        lib.ggrs_sess_provide_checksum.argtypes = [
            ctypes.c_void_p, ctypes.c_int32, ctypes.c_char_p, ctypes.c_uint64,
        ]
        lib.ggrs_sess_st_verify.restype = ctypes.c_long
        lib.ggrs_sess_st_verify.argtypes = [
            ctypes.c_void_p, ctypes.c_int32, ctypes.c_int, ctypes.c_char_p,
            ctypes.c_int32,
        ]
        _configured = True
    return lib


def _csum_bytes(checksum: int) -> bytes:
    return (checksum & _U128_MASK).to_bytes(16, "little")


class _NativeSessionBase:
    """Shared plumbing: lifecycle, cell ring, request/event conversion."""

    def telemetry(self) -> dict:
        """One structured snapshot, parity with the Python sessions'
        telemetry(). The native core keeps its own internal counters, so
        the session section here is the ctypes-visible surface only; the
        process-wide metrics/recorder/tracer sections are identical."""
        from ..obs import GLOBAL_TELEMETRY

        snap = GLOBAL_TELEMETRY.snapshot()
        section = {"type": f"native_{type(self).__name__}"}
        for attr in ("current_frame", "last_saved_frame", "confirmed_frame"):
            try:
                value = getattr(self, attr, None)
                section[attr] = int(value() if callable(value) else value)
            except (TypeError, ValueError):
                pass  # attr absent/None on this session flavor
        snap["session"] = section
        return snap

    def __init__(
        self,
        num_players: int,
        max_prediction: int,
        input_size: int,
        max_requests_per_tick: int = 0,
    ):
        if num_players > _MAX_PLAYERS:
            raise InvalidRequest(
                f"Native sessions support at most {_MAX_PLAYERS} players "
                f"(got {num_players})."
            )
        if input_size > _MAX_INPUT:
            raise InvalidRequest(
                f"Native sessions support at most {_MAX_INPUT}-byte inputs "
                f"(got {input_size})."
            )
        self.num_players = num_players
        self.max_prediction = max_prediction
        self.input_size = input_size
        self.cells: List[GameStateCell] = SavedStates(max_prediction).states
        lib = _lib()
        self._lib = lib  # before ggrs_sess_new so __del__ is safe on failure
        self._h = None
        # worst case for rollback sessions: frame-0 save + load +
        # max_prediction x (save+advance) + final save + advance, with
        # headroom; spectators instead need one request per catch-up frame
        cap = max(2 * max_prediction + 16, max_requests_per_tick)
        self._req_buf = (_SessReq * cap)()
        self._req_cap = cap
        self._wire_buf = ctypes.create_string_buffer(_WIRE_BUF_CAP)
        self._ep_out = ctypes.c_int32(0)

    def __del__(self):
        h = getattr(self, "_h", None)
        if h:
            self._lib.ggrs_sess_free(h)
            self._h = None

    def _start(self, cfg: _SessConfig, now_ms: int) -> None:
        h = self._lib.ggrs_sess_new(ctypes.byref(cfg), now_ms)
        if not h:
            raise InvalidRequest("native session rejected the configuration")
        self._h = h

    def _raise(self, rc: int) -> None:
        if rc == _SERR_NOT_SYNCHRONIZED:
            raise NotSynchronized()
        if rc == _SERR_PREDICTION_THRESHOLD:
            raise PredictionThreshold()
        if rc == _SERR_MISSING_INPUT:
            raise InvalidRequest("Missing local input while calling advance_frame().")
        if rc == _SERR_MISMATCHED_CHECKSUM:
            raise MismatchedChecksum(self._lib.ggrs_sess_last_error_frame(self._h))
        if rc == _SERR_SPECTATOR_TOO_FAR_BEHIND:
            raise SpectatorTooFarBehind()
        if rc == _SERR_INVALID_HANDLE:
            raise InvalidRequest("Invalid Player Handle.")
        if rc == _SERR_LOCAL_PLAYER:
            raise InvalidRequest("Local Player cannot be disconnected.")
        if rc == _SERR_ALREADY_DISCONNECTED:
            raise InvalidRequest("Player already disconnected.")
        raise ContractViolation(f"native session internal error (code {rc})")

    def _convert_requests(self, n: int) -> List[Request]:
        isz = self.input_size
        out: List[Request] = []
        for i in range(n):
            r = self._req_buf[i]
            if r.type == _REQ_SAVE:
                out.append(SaveGameState(cell=self.cells[r.cell], frame=r.frame))
            elif r.type == _REQ_LOAD:
                cell = self.cells[r.cell]
                # mirror sync_layer.load_frame's cell-freshness assert
                assert cell.frame == r.frame, "snapshot ring cell is stale"
                out.append(LoadGameState(cell=cell, frame=r.frame))
            else:
                raw = bytes(r.inputs[: self.num_players * isz])
                inputs = [
                    (raw[p * isz : (p + 1) * isz], InputStatus(r.statuses[p]))
                    for p in range(self.num_players)
                ]
                out.append(AdvanceFrame(inputs=inputs))
        return out

    def _advance_native(self, now_ms: int) -> List[Request]:
        n = self._lib.ggrs_sess_advance_frame(
            self._h, now_ms, self._req_buf, self._req_cap
        )
        if n == _SERR_CAPACITY:
            # the advance ran; the requests are still held natively — grow
            # the buffer and re-copy, losing nothing
            self._req_cap = self._lib.ggrs_sess_request_count(self._h)
            self._req_buf = (_SessReq * self._req_cap)()
            n = self._lib.ggrs_sess_copy_requests(
                self._h, self._req_buf, self._req_cap
            )
        if n < 0:
            self._raise(n)
        return self._convert_requests(n)


class _NativeNetworkedSession(_NativeSessionBase):
    """Adds socket plumbing + event conversion for P2P and spectator."""

    def __init__(
        self,
        num_players: int,
        max_prediction: int,
        input_size: int,
        socket: Any,
        addr_of_ep: List[Any],
        clock: Optional[Clock],
        max_requests_per_tick: int = 0,
    ):
        super().__init__(num_players, max_prediction, input_size,
                         max_requests_per_tick)
        self.socket = socket
        self.clock = clock or Clock()
        self._addr_of_ep = list(addr_of_ep)
        # one address can back several endpoints (a remote-player endpoint
        # and a spectator endpoint, as in the Python builder); incoming
        # datagrams fan out to all of them, like P2PSession's message pump
        self._eps_of_addr: Dict[Any, List[int]] = {}
        for i, addr in enumerate(addr_of_ep):
            self._eps_of_addr.setdefault(addr, []).append(i)
        self._wire_recv = hasattr(socket, "receive_all_wire")
        self._wire_send = hasattr(socket, "send_wire")

    # -- wire pump ------------------------------------------------------

    def poll_remote_clients(self) -> None:
        now = self.clock.now_ms()
        lib = self._lib
        if self._wire_recv:
            # raw datagrams flow socket -> C++ endpoint with no Python codec
            for from_addr, wire in self.socket.receive_all_wire():
                for ep in self._eps_of_addr.get(from_addr, ()):
                    lib.ggrs_sess_handle_wire(self._h, ep, wire, len(wire), now)
        else:
            for from_addr, msg in self.socket.receive_all_messages():
                eps = self._eps_of_addr.get(from_addr)
                if eps:
                    wire = encode_message(msg)
                    for ep in eps:
                        lib.ggrs_sess_handle_wire(self._h, ep, wire, len(wire), now)
        lib.ggrs_sess_poll(self._h, now)
        # drain-free tick (P2PSession._pump_checksums' native twin):
        # bind desync checksums on the pump and prefetch the oldest
        # in-flight one, emitting nothing; stay two advances behind the
        # capture frontier so no mid-correction value can bind early
        # (spectator sessions share this pump but have no checksum lane)
        pcr = getattr(self, "_pending_checksum_report", None)
        if pcr is not None and self.desync_detection.enabled and len(pcr):
            pcr.bind_and_prefetch(max_serial=self._advance_serial - 2)
        self._send_all()

    def _send_all(self) -> None:
        lib = self._lib
        while True:
            n = lib.ggrs_sess_drain_wire(
                self._h, ctypes.byref(self._ep_out), self._wire_buf, _WIRE_BUF_CAP
            )
            if n <= 0:
                return
            wire = self._wire_buf.raw[:n]
            addr = self._addr_of_ep[self._ep_out.value]
            if self._wire_send:
                self.socket.send_wire(wire, addr)
            else:
                self.socket.send_to(decode_message(wire), addr)

    # -- events ---------------------------------------------------------

    def events(self) -> List[Event]:
        out: List[Event] = []
        ev = _SessEvent()
        lib = self._lib
        while lib.ggrs_sess_next_event(self._h, ctypes.byref(ev)):
            addr = (
                self._addr_of_ep[ev.ep]
                if 0 <= ev.ep < len(self._addr_of_ep)
                else None
            )
            t = ev.type
            if t == _SEV_SYNCHRONIZING:
                out.append(Synchronizing(addr=addr, total=ev.a, count=ev.b))
            elif t == _SEV_SYNCHRONIZED:
                out.append(Synchronized(addr=addr))
            elif t == _SEV_DISCONNECTED:
                out.append(Disconnected(addr=addr))
            elif t == _SEV_INTERRUPTED:
                out.append(NetworkInterrupted(addr=addr, disconnect_timeout_ms=ev.a))
            elif t == _SEV_RESUMED:
                out.append(NetworkResumed(addr=addr))
            elif t == _SEV_WAIT_RECOMMENDATION:
                out.append(WaitRecommendation(skip_frames=ev.a))
            elif t == _SEV_DESYNC_DETECTED:
                out.append(
                    DesyncDetected(
                        frame=ev.a,
                        local_checksum=int.from_bytes(bytes(ev.local_checksum), "little"),
                        remote_checksum=int.from_bytes(bytes(ev.remote_checksum), "little"),
                        addr=addr,
                    )
                )
        return out

    def current_state(self) -> SessionState:
        return (
            SessionState.RUNNING
            if self._lib.ggrs_sess_state(self._h)
            else SessionState.SYNCHRONIZING
        )

    def _network_stats(self, ep_idx: int) -> NetworkStats:
        out = _Stats()
        rc = self._lib.ggrs_sess_network_stats(
            self._h, ep_idx, self.clock.now_ms(), ctypes.byref(out)
        )
        if rc != 0:
            raise NotSynchronized()
        return NetworkStats(
            send_queue_len=out.send_queue_len,
            ping_ms=out.ping_ms,
            kbps_sent=out.kbps_sent,
            local_frames_behind=out.local_frames_behind,
            remote_frames_behind=out.remote_frames_behind,
        )


class NativeP2PSession(_NativeNetworkedSession):
    """Drop-in replacement for P2PSession backed by the C++ session core."""

    def __init__(
        self,
        num_players: int,
        max_prediction: int,
        socket: Any,
        handles: Dict[PlayerHandle, PlayerType],
        sparse_saving: bool,
        desync_detection: DesyncDetection,
        input_delay: int,
        input_size: int,
        fps: int,
        disconnect_timeout_ms: int,
        disconnect_notify_start_ms: int,
        clock: Optional[Clock] = None,
        rng: Optional[_random.Random] = None,
    ):
        self.handles = dict(handles)
        if any(h >= _MAX_TOTAL_HANDLES for h in self.handles):
            raise InvalidRequest(
                f"Native sessions support player/spectator handles below "
                f"{_MAX_TOTAL_HANDLES}."
            )
        # group handles by unique remote address — remote-player endpoints
        # and spectator endpoints are separate even when they share an
        # address, exactly like the Python builder (builder.py
        # start_p2p_session / reference builder.rs:264-293)
        addr_of_ep: List[Any] = []
        remote_ep_of_addr: Dict[Any, int] = {}
        spec_ep_of_addr: Dict[Any, int] = {}
        for handle in sorted(self.handles):
            ptype = self.handles[handle]
            if ptype.kind == PlayerTypeKind.LOCAL:
                continue
            group = (
                spec_ep_of_addr
                if ptype.kind == PlayerTypeKind.SPECTATOR
                else remote_ep_of_addr
            )
            if ptype.addr not in group:
                group[ptype.addr] = len(addr_of_ep)
                addr_of_ep.append(ptype.addr)
        self._remote_ep_of_addr = remote_ep_of_addr
        self._spec_ep_of_addr = spec_ep_of_addr

        super().__init__(
            num_players, max_prediction, input_size, socket, addr_of_ep, clock
        )
        self.desync_detection = desync_detection
        self._pending_checksum_report = PendingChecksumReport()
        # drain-free tick bookkeeping (P2PSession's twins): advance
        # serial gates the pump-side flush; blocked ticks are the gate
        # counter bench/smoke read
        self._advance_serial = 0
        self.drain_blocked_ticks = 0

        rng = rng or _random.Random()
        cfg = _SessConfig()
        cfg.session_type = 0
        cfg.num_players = num_players
        cfg.max_prediction = max_prediction
        cfg.input_size = input_size
        cfg.input_delay = input_delay
        cfg.sparse_saving = 1 if sparse_saving else 0
        cfg.desync_interval = desync_detection.interval if desync_detection.enabled else 0
        cfg.fps = fps
        cfg.disconnect_timeout_ms = disconnect_timeout_ms
        cfg.disconnect_notify_start_ms = disconnect_notify_start_ms
        cfg.max_frames_behind = 10
        cfg.catchup_speed = 1
        cfg.total_handles = max(self.handles) + 1 if self.handles else 0
        cfg.num_endpoints = len(addr_of_ep)
        for h in range(cfg.total_handles):
            ptype = self.handles.get(h)
            cfg.player_kinds[h] = _KIND_CODE[ptype.kind] if ptype else -1
            if ptype is None or ptype.kind == PlayerTypeKind.LOCAL:
                cfg.player_endpoints[h] = -1
            elif ptype.kind == PlayerTypeKind.SPECTATOR:
                cfg.player_endpoints[h] = spec_ep_of_addr[ptype.addr]
            else:
                cfg.player_endpoints[h] = remote_ep_of_addr[ptype.addr]
        cfg.rng_seed = rng.getrandbits(64)
        self._start(cfg, self.clock.now_ms())

    # -- public API (parity with P2PSession) ----------------------------

    def add_local_input(self, player_handle: PlayerHandle, buf: bytes) -> None:
        if player_handle not in self.local_player_handles():
            raise InvalidRequest(
                "The player handle you provided is not referring to a local player."
            )
        if len(buf) != self.input_size:
            raise InvalidRequest(
                f"Input must be exactly {self.input_size} bytes, got {len(buf)}."
            )
        rc = self._lib.ggrs_sess_add_local_input(self._h, player_handle, buf)
        if rc < 0:
            self._raise(rc)

    def advance_frame(self) -> List[Request]:
        self.poll_remote_clients()
        if self.desync_detection.enabled:
            # flush BEFORE this tick's advance: a report captured at tick t
            # covers a frame whose correcting rollback may have been in tick
            # t's request list — PendingChecksumReport reads the value only
            # once the caller fulfilled those requests, i.e. by now.
            # Reports publish at the interval tick only, as in P2PSession
            interval = self.desync_detection.interval
            pcr = self._pending_checksum_report
            if self.current_frame % interval == interval - 1:
                if pcr.flush(self._emit_checksum_report):
                    self.drain_blocked_ticks += 1
            else:
                pcr.bind_and_prefetch()
        self._advance_serial += 1
        requests = self._advance_native(self.clock.now_ms())
        if self.desync_detection.enabled:
            self._capture_checksum_request()
        self._send_all()
        return requests

    def _capture_checksum_request(self) -> None:
        frame = self._lib.ggrs_sess_take_checksum_request(self._h)
        if frame == NULL_FRAME:
            return
        self._pending_checksum_report.capture(
            frame, self.cells[frame % len(self.cells)],
            serial=self._advance_serial,
        )

    def _emit_checksum_report(self, frame: Frame, checksum: int) -> None:
        self._lib.ggrs_sess_provide_checksum(
            self._h, frame, _csum_bytes(checksum), self.clock.now_ms()
        )

    def disconnect_player(self, player_handle: PlayerHandle) -> None:
        if player_handle not in self.handles:
            raise InvalidRequest("Invalid Player Handle.")
        rc = self._lib.ggrs_sess_disconnect_player(
            self._h, player_handle, self.clock.now_ms()
        )
        if rc < 0:
            self._raise(rc)

    def network_stats(self, player_handle: PlayerHandle) -> NetworkStats:
        ptype = self.handles.get(player_handle)
        if ptype is None or ptype.kind == PlayerTypeKind.LOCAL:
            raise InvalidRequest(
                "Given player handle not referring to a remote player or spectator"
            )
        group = (
            self._spec_ep_of_addr
            if ptype.kind == PlayerTypeKind.SPECTATOR
            else self._remote_ep_of_addr
        )
        return self._network_stats(group[ptype.addr])

    def confirmed_frame(self) -> Frame:
        return self._lib.ggrs_sess_confirmed_frame(self._h)

    @property
    def current_frame(self) -> Frame:
        return self._lib.ggrs_sess_current_frame(self._h)

    @property
    def last_saved_frame(self) -> Frame:
        return self._lib.ggrs_sess_last_saved_frame(self._h)

    @property
    def local_connect_status(self):
        """Per-player (disconnected, last_frame) view, parity with
        P2PSession.local_connect_status."""
        from ..sync_layer import ConnectionStatus

        n = self.num_players
        disc = (ctypes.c_uint8 * n)()
        last = (ctypes.c_int32 * n)()
        self._lib.ggrs_sess_connect_status(self._h, disc, last, n)
        return [ConnectionStatus(bool(disc[i]), last[i]) for i in range(n)]

    def frames_ahead_estimate(self) -> int:
        return self._lib.ggrs_sess_frames_ahead(self._h)

    def _handles_of(self, kind: PlayerTypeKind) -> List[PlayerHandle]:
        return sorted(h for h, p in self.handles.items() if p.kind == kind)

    def local_player_handles(self) -> List[PlayerHandle]:
        return self._handles_of(PlayerTypeKind.LOCAL)

    def remote_player_handles(self) -> List[PlayerHandle]:
        return self._handles_of(PlayerTypeKind.REMOTE)

    def spectator_handles(self) -> List[PlayerHandle]:
        return self._handles_of(PlayerTypeKind.SPECTATOR)

    def handles_by_address(self, addr: Any) -> List[PlayerHandle]:
        return sorted(
            h
            for h, p in self.handles.items()
            if p.kind != PlayerTypeKind.LOCAL and p.addr == addr
        )

    def num_spectators(self) -> int:
        return len(self.spectator_handles())


class NativeSyncTestSession(_NativeSessionBase):
    """Drop-in replacement for SyncTestSession backed by the C++ core.
    Checksum comparison history lives natively; this wrapper reads the cell
    checksums (it owns the cells) and feeds observations to st_verify —
    eagerly, or `deferred_checksum_lag` ticks late in batched bursts."""

    def __init__(
        self,
        num_players: int,
        max_prediction: int,
        check_distance: int,
        input_delay: int,
        input_size: int,
        deferred_checksum_lag: int = 0,
    ):
        super().__init__(num_players, max_prediction, input_size)
        self.check_distance = check_distance
        self.deferred_checksum_lag = deferred_checksum_lag
        self._pending_checks = DeferredChecks(deferred_checksum_lag)
        self._tick = 0

        cfg = _SessConfig()
        cfg.session_type = 1
        cfg.num_players = num_players
        cfg.max_prediction = max_prediction
        cfg.input_size = input_size
        cfg.input_delay = input_delay
        cfg.check_distance = check_distance
        cfg.total_handles = num_players
        for h in range(num_players):
            cfg.player_kinds[h] = 0  # all players are local in a sync test
            cfg.player_endpoints[h] = -1
        self._start(cfg, 0)

    @property
    def current_frame(self) -> Frame:
        return self._lib.ggrs_sess_current_frame(self._h)

    def add_local_input(self, player_handle: PlayerHandle, buf: bytes) -> None:
        if player_handle >= self.num_players:
            raise InvalidRequest("The player handle you provided is not valid.")
        if len(buf) != self.input_size:
            raise InvalidRequest(
                f"Input must be exactly {self.input_size} bytes, got {len(buf)}."
            )
        rc = self._lib.ggrs_sess_add_local_input(self._h, player_handle, buf)
        if rc < 0:
            self._raise(rc)

    def advance_frame(self) -> List[Request]:
        current = self.current_frame
        self._tick += 1
        if self.check_distance > 0 and current > self.check_distance:
            if self.deferred_checksum_lag > 0:
                self._schedule_checks(current)
                if self._tick % self.deferred_checksum_lag == 0:
                    self._drain_due_checks(current)
            else:
                oldest_allowed = current - self.check_distance
                for i in range(self.check_distance + 1):
                    frame_to_check = current - i
                    cell = self.cells[frame_to_check % len(self.cells)]
                    if cell.frame != frame_to_check:
                        continue
                    self._verify(frame_to_check, cell.checksum, oldest_allowed)
        return self._advance_native(0)

    def _verify(self, frame: Frame, checksum: Optional[int], oldest_allowed: int) -> None:
        has = 0 if checksum is None else 1
        csum = _csum_bytes(checksum) if checksum is not None else bytes(16)
        rc = self._lib.ggrs_sess_st_verify(self._h, frame, has, csum, oldest_allowed)
        if rc < 0:
            self._raise(rc)

    def _schedule_checks(self, current: Frame) -> None:
        for i in range(self.check_distance + 1):
            frame_to_check = current - i
            cell = self.cells[frame_to_check % len(self.cells)]
            if cell.frame != frame_to_check:
                continue
            self._pending_checks.schedule(
                self._tick, frame_to_check, cell.checksum_getter()
            )

    def _drain_due_checks(self, current: Frame) -> None:
        oldest_live = current - (self.check_distance + self.deferred_checksum_lag + 1)
        self._pending_checks.drain_due(
            self._tick, lambda frame, getter: self._verify(frame, getter(), oldest_live)
        )

    def flush_checksum_checks(self) -> None:
        """Force every deferred comparison now (end of run / tests)."""
        self._pending_checks.flush(
            lambda frame, getter: self._verify(frame, getter(), _INT32_MIN)
        )


class NativeSpectatorSession(_NativeNetworkedSession):
    """Drop-in replacement for SpectatorSession backed by the C++ core."""

    def __init__(
        self,
        num_players: int,
        socket: Any,
        host_addr: Any,
        max_prediction: int,
        max_frames_behind: int,
        catchup_speed: int,
        input_size: int,
        fps: int,
        disconnect_timeout_ms: int,
        disconnect_notify_start_ms: int,
        clock: Optional[Clock] = None,
        rng: Optional[_random.Random] = None,
    ):
        super().__init__(
            num_players, max_prediction, input_size, socket, [host_addr], clock,
            max_requests_per_tick=catchup_speed + 1,
        )
        rng = rng or _random.Random()
        cfg = _SessConfig()
        cfg.session_type = 2
        cfg.num_players = num_players
        cfg.max_prediction = max_prediction
        cfg.input_size = input_size
        cfg.max_frames_behind = max_frames_behind
        cfg.catchup_speed = catchup_speed
        cfg.fps = fps
        cfg.disconnect_timeout_ms = disconnect_timeout_ms
        cfg.disconnect_notify_start_ms = disconnect_notify_start_ms
        cfg.total_handles = num_players
        cfg.num_endpoints = 1
        for h in range(num_players):
            cfg.player_kinds[h] = 1  # every handle is a remote player
            cfg.player_endpoints[h] = 0
        cfg.rng_seed = rng.getrandbits(64)
        self._start(cfg, self.clock.now_ms())

    @property
    def current_frame(self) -> Frame:
        return self._lib.ggrs_sess_current_frame(self._h)

    def frames_behind_host(self) -> int:
        diff = self._lib.ggrs_sess_frames_behind_host(self._h)
        assert diff >= 0
        return diff

    def network_stats(self) -> NetworkStats:
        return self._network_stats(0)

    def advance_frame(self) -> List[Request]:
        self.poll_remote_clients()
        requests = self._advance_native(self.clock.now_ms())
        self._send_all()
        return requests
