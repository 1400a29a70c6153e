"""Fluent session builder holding every runtime knob, with the reference's
defaults and validation (src/sessions/builder.rs)."""

from __future__ import annotations

from typing import Any, Dict

from ..errors import InvalidRequest
from ..types import DesyncDetection, PlayerHandle, PlayerType, PlayerTypeKind
from .sync_test_session import SyncTestSession

# Defaults (src/sessions/builder.rs:13-27)
DEFAULT_PLAYERS = 2
DEFAULT_INPUT_DELAY = 0
DEFAULT_DISCONNECT_TIMEOUT_MS = 2000
DEFAULT_DISCONNECT_NOTIFY_START_MS = 500
DEFAULT_FPS = 60
DEFAULT_MAX_PREDICTION_FRAMES = 8
DEFAULT_CHECK_DISTANCE = 2
DEFAULT_MAX_FRAMES_BEHIND = 10
DEFAULT_CATCHUP_SPEED = 1
SPECTATOR_BUFFER_SIZE = 60
MAX_EVENT_QUEUE_SIZE = 100


class SessionBuilder:
    """Builds all session types. `input_size` is the compile-time POD input
    contract (the Config::Input analog, src/lib.rs:250-255): every player's
    input is exactly this many bytes per frame."""

    def __init__(self, input_size: int = 1):
        if input_size < 1:
            raise InvalidRequest("input_size must be at least 1 byte")
        self.input_size = input_size
        self.num_players = DEFAULT_PLAYERS
        self.max_prediction = DEFAULT_MAX_PREDICTION_FRAMES
        self.fps = DEFAULT_FPS
        self.sparse_saving = False
        self.desync_detection = DesyncDetection.off()
        self.disconnect_timeout_ms = DEFAULT_DISCONNECT_TIMEOUT_MS
        self.disconnect_notify_start_ms = DEFAULT_DISCONNECT_NOTIFY_START_MS
        self.input_delay = DEFAULT_INPUT_DELAY
        self.check_distance = DEFAULT_CHECK_DISTANCE
        self.max_frames_behind = DEFAULT_MAX_FRAMES_BEHIND
        self.catchup_speed = DEFAULT_CATCHUP_SPEED
        self.handles: Dict[PlayerHandle, PlayerType] = {}
        self._local_players = 0
        self.clock = None  # optional injected Clock for deterministic tests
        self.rng = None  # optional injected random.Random for endpoint magics
        self.use_native_queues = False
        self.use_native_endpoints = False
        self.use_native_sessions = False
        self.deferred_checksum_lag = 0
        self.device_checksum_verification = False

    # ------------------------------------------------------------------
    # fluent setters (src/sessions/builder.rs:90-244)
    # ------------------------------------------------------------------

    def add_player(self, player_type: PlayerType, player_handle: PlayerHandle) -> "SessionBuilder":
        if player_handle in self.handles:
            raise InvalidRequest("Player handle already in use.")
        if player_type.kind in (PlayerTypeKind.LOCAL, PlayerTypeKind.REMOTE):
            if player_handle >= self.num_players:
                raise InvalidRequest(
                    "For a player, the handle should be between 0 and num_players."
                )
            if player_type.kind == PlayerTypeKind.LOCAL:
                self._local_players += 1
        else:
            if player_handle < self.num_players:
                raise InvalidRequest(
                    "For a spectator, the handle should be num_players or higher."
                )
        self.handles[player_handle] = player_type
        return self

    def with_num_players(self, num_players: int) -> "SessionBuilder":
        self.num_players = num_players
        return self

    def with_max_prediction_window(self, window: int) -> "SessionBuilder":
        if window == 0:
            raise InvalidRequest("Only prediction windows above 0 are supported.")
        self.max_prediction = window
        return self

    def with_input_delay(self, delay: int) -> "SessionBuilder":
        self.input_delay = delay
        return self

    def with_fps(self, fps: int) -> "SessionBuilder":
        if fps == 0:
            raise InvalidRequest("FPS should be higher than 0.")
        self.fps = fps
        return self

    def with_sparse_saving_mode(self, sparse_saving: bool) -> "SessionBuilder":
        self.sparse_saving = sparse_saving
        return self

    def with_desync_detection_mode(self, mode: DesyncDetection) -> "SessionBuilder":
        self.desync_detection = mode
        return self

    def with_disconnect_timeout(self, timeout_ms: int) -> "SessionBuilder":
        self.disconnect_timeout_ms = timeout_ms
        return self

    def with_disconnect_notify_delay(self, notify_delay_ms: int) -> "SessionBuilder":
        self.disconnect_notify_start_ms = notify_delay_ms
        return self

    def with_check_distance(self, check_distance: int) -> "SessionBuilder":
        self.check_distance = check_distance
        return self

    def with_max_frames_behind(self, max_frames_behind: int) -> "SessionBuilder":
        if max_frames_behind < 1:
            raise InvalidRequest("Max frames behind cannot be smaller than 1.")
        if max_frames_behind >= SPECTATOR_BUFFER_SIZE:
            raise InvalidRequest(
                "Max frames behind cannot be larger or equal than the spectator buffer size."
            )
        self.max_frames_behind = max_frames_behind
        return self

    def with_catchup_speed(self, catchup_speed: int) -> "SessionBuilder":
        if catchup_speed < 1:
            raise InvalidRequest("Catchup speed cannot be smaller than 1.")
        if catchup_speed >= self.max_frames_behind:
            raise InvalidRequest(
                "Catchup speed cannot be larger or equal than the allowed maximum frames behind."
            )
        self.catchup_speed = catchup_speed
        return self

    def with_clock(self, clock) -> "SessionBuilder":
        """Inject a Clock (e.g. FakeClock) driving all endpoint timers —
        the determinism seam the reference lacks (SURVEY.md §4)."""
        self.clock = clock
        return self

    def with_rng(self, rng) -> "SessionBuilder":
        """Inject a seeded random.Random for endpoint magics/nonces."""
        self.rng = rng
        return self

    def with_deferred_checksum_verification(self, lag: int) -> "SessionBuilder":
        """SyncTest extension for device backends: compare checksum
        observations `lag` ticks late, in bursts of one batched
        device->host transfer — the per-tick comparisons of the eager path
        would each stall on a transfer. Mismatches still raise, at most `lag` ticks later. 0
        restores the reference's eager semantics."""
        if lag < 0:
            raise InvalidRequest("Deferred checksum lag cannot be negative.")
        self.deferred_checksum_lag = lag
        return self

    def with_device_checksum_verification(
        self, enabled: bool = True
    ) -> "SessionBuilder":
        """SyncTest extension for device backends: skip the host-side
        checksum comparison entirely and delegate the verdict to the
        fulfilling backend (TpuRollbackBackend(device_verify=True) keeps
        the first-seen history + mismatch latch on device; read it with
        backend.check()). The session's forced rollbacks are unchanged —
        this removes the LAST per-run device->host checksum traffic, a
        synchronization per readback on the interactive path. Python sessions only."""
        self.device_checksum_verification = enabled
        return self

    def with_native_input_queues(self, enabled: bool = True) -> "SessionBuilder":
        """Back per-player input queues with the C++ ring (native/
        input_queue.cpp) instead of the Python oracle. Requires the native
        library to be built (make -C native); inputs are capped at 64 bytes
        per player on this path."""
        if enabled:
            from ..native import NATIVE_MAX_INPUT_SIZE

            if self.input_size > NATIVE_MAX_INPUT_SIZE:
                raise InvalidRequest(
                    f"Native input queues support at most {NATIVE_MAX_INPUT_SIZE}"
                    f"-byte inputs (got {self.input_size})."
                )
        self.use_native_queues = enabled
        return self

    def with_native_endpoints(self, enabled: bool = True) -> "SessionBuilder":
        """Back per-peer reliability endpoints with the C++ state machine
        (native/endpoint.cpp) instead of the Python implementation. Same
        wire format, so native and Python peers interoperate. Requires the
        native library (make -C native); inputs are capped at 64 bytes."""
        if enabled:
            from ..native import NATIVE_MAX_INPUT_SIZE, available

            if not available():
                raise InvalidRequest(
                    "Native endpoints require the native library (make -C native)."
                )
            if self.input_size > NATIVE_MAX_INPUT_SIZE:
                raise InvalidRequest(
                    f"Native endpoints support at most {NATIVE_MAX_INPUT_SIZE}"
                    f"-byte inputs (got {self.input_size})."
                )
        self.use_native_endpoints = enabled
        return self

    def with_native_sessions(self, enabled: bool = True) -> "SessionBuilder":
        """Back the whole session layer — sync layer, per-frame pipeline,
        rollback driver, message pump — with the C++ session core
        (native/session.cpp) instead of the Python sessions. The session
        composes the C++ input queues and C++ endpoints natively, so a full
        tick runs without touching Python; the request/cell contract, wire
        format and event surface are unchanged. Requires the native library
        (make -C native); inputs are capped at 64 bytes, players at 16."""
        if enabled:
            from ..native import NATIVE_MAX_INPUT_SIZE, available

            if not available():
                raise InvalidRequest(
                    "Native sessions require the native library (make -C native)."
                )
            if self.input_size > NATIVE_MAX_INPUT_SIZE:
                raise InvalidRequest(
                    f"Native sessions support at most {NATIVE_MAX_INPUT_SIZE}"
                    f"-byte inputs (got {self.input_size})."
                )
        self.use_native_sessions = enabled
        return self

    # ------------------------------------------------------------------
    # session constructors
    # ------------------------------------------------------------------

    def start_synctest_session(self) -> SyncTestSession:
        """(src/sessions/builder.rs:342-354)"""
        if self.check_distance >= self.max_prediction:
            raise InvalidRequest("Check distance too big.")
        if self.use_native_sessions:
            if self.device_checksum_verification:
                raise InvalidRequest(
                    "Device checksum verification requires the Python "
                    "session (the native session verifies on host)."
                )
            from ..native.session import NativeSyncTestSession

            return NativeSyncTestSession(
                self.num_players,
                self.max_prediction,
                self.check_distance,
                self.input_delay,
                self.input_size,
                deferred_checksum_lag=self.deferred_checksum_lag,
            )
        return SyncTestSession(
            self.num_players,
            self.max_prediction,
            self.check_distance,
            self.input_delay,
            self.input_size,
            use_native_queues=self.use_native_queues,
            deferred_checksum_lag=self.deferred_checksum_lag,
            host_verification=not self.device_checksum_verification,
        )

    def start_p2p_session(self, socket: Any):
        """(src/sessions/builder.rs:251-304)"""
        from .p2p_session import P2PSession, PlayerRegistry

        for handle in range(self.num_players):
            if handle not in self.handles:
                raise InvalidRequest(
                    "Not enough players have been added. Keep registering players "
                    "up to the defined player number."
                )

        if self.use_native_sessions:
            from ..native.session import NativeP2PSession

            return NativeP2PSession(
                num_players=self.num_players,
                max_prediction=self.max_prediction,
                socket=socket,
                handles=dict(self.handles),
                sparse_saving=self.sparse_saving,
                desync_detection=self.desync_detection,
                input_delay=self.input_delay,
                input_size=self.input_size,
                fps=self.fps,
                disconnect_timeout_ms=self.disconnect_timeout_ms,
                disconnect_notify_start_ms=self.disconnect_notify_start_ms,
                clock=self.clock,
                rng=self.rng,
            )

        registry = PlayerRegistry(dict(self.handles))
        # group handles by unique remote address; one endpoint per address
        by_addr: Dict[Any, list] = {}
        spec_by_addr: Dict[Any, list] = {}
        for handle, ptype in self.handles.items():
            if ptype.kind == PlayerTypeKind.REMOTE:
                by_addr.setdefault(ptype.addr, []).append(handle)
            elif ptype.kind == PlayerTypeKind.SPECTATOR:
                spec_by_addr.setdefault(ptype.addr, []).append(handle)

        for addr, handles in by_addr.items():
            registry.remotes[addr] = self._create_endpoint(
                handles, addr, self._local_players
            )
        for addr, handles in spec_by_addr.items():
            # the host of a spectator sends inputs for all players
            registry.spectators[addr] = self._create_endpoint(
                handles, addr, self.num_players
            )

        return P2PSession(
            num_players=self.num_players,
            max_prediction=self.max_prediction,
            socket=socket,
            players=registry,
            sparse_saving=self.sparse_saving,
            desync_detection=self.desync_detection,
            input_delay=self.input_delay,
            input_size=self.input_size,
            use_native_queues=self.use_native_queues,
        )

    def _endpoint_cls(self):
        if self.use_native_endpoints:
            from ..native.endpoint import NativePeerEndpoint

            return NativePeerEndpoint
        from ..network.protocol import PeerEndpoint

        return PeerEndpoint

    def start_spectator_session(self, host_addr: Any, socket: Any):
        """(src/sessions/builder.rs:310-334)"""
        from .spectator_session import SpectatorSession

        if self.use_native_sessions:
            from ..native.session import NativeSpectatorSession

            return NativeSpectatorSession(
                num_players=self.num_players,
                socket=socket,
                host_addr=host_addr,
                max_prediction=self.max_prediction,
                max_frames_behind=self.max_frames_behind,
                catchup_speed=self.catchup_speed,
                input_size=self.input_size,
                fps=self.fps,
                disconnect_timeout_ms=self.disconnect_timeout_ms,
                disconnect_notify_start_ms=self.disconnect_notify_start_ms,
                clock=self.clock,
                rng=self.rng,
            )

        host = self._endpoint_cls()(
            handles=list(range(self.num_players)),
            peer_addr=host_addr,
            num_players=self.num_players,
            local_players=1,  # irrelevant: spectators never send inputs
            max_prediction=self.max_prediction,
            disconnect_timeout_ms=self.disconnect_timeout_ms,
            disconnect_notify_start_ms=self.disconnect_notify_start_ms,
            fps=self.fps,
            input_size=self.input_size,
            clock=self.clock,
            rng=self.rng,
        )
        host.synchronize()
        return SpectatorSession(
            num_players=self.num_players,
            socket=socket,
            host=host,
            max_frames_behind=self.max_frames_behind,
            catchup_speed=self.catchup_speed,
            input_size=self.input_size,
        )

    def _create_endpoint(self, handles, peer_addr, local_players):
        endpoint = self._endpoint_cls()(
            handles=handles,
            peer_addr=peer_addr,
            num_players=self.num_players,
            local_players=local_players,
            max_prediction=self.max_prediction,
            disconnect_timeout_ms=self.disconnect_timeout_ms,
            disconnect_notify_start_ms=self.disconnect_notify_start_ms,
            fps=self.fps,
            input_size=self.input_size,
            clock=self.clock,
            rng=self.rng,
        )
        endpoint.synchronize()
        return endpoint
