"""Passive replica session: receives confirmed inputs from a host and
advances, catching up when too far behind.

Behavioral parity with the reference (src/sessions/p2p_spectator_session.rs):
60-frame input ring, catch-up policy, PredictionThreshold when input hasn't
arrived and SpectatorTooFarBehind when the ring was overwritten. Spectators
never save/load/rollback.
"""

from __future__ import annotations

from collections import deque
from typing import Any, Deque, List

from ..errors import NotSynchronized, PredictionThreshold, SpectatorTooFarBehind
from ..frame_info import PlayerInput
from ..network.network_stats import NetworkStats
from ..obs import GLOBAL_TELEMETRY
from ..network.pump import GLOBAL_PUMP
from ..network.protocol import (
    EvDisconnected,
    EvInput,
    EvNetworkInterrupted,
    EvNetworkResumed,
    EvSynchronized,
    EvSynchronizing,
    PeerEndpoint,
)
from ..sync_layer import ConnectionStatus
from ..types import (
    NULL_FRAME,
    AdvanceFrame,
    Disconnected,
    Event,
    Frame,
    InputStatus,
    NetworkInterrupted,
    NetworkResumed,
    Request,
    SessionState,
    Synchronized,
    Synchronizing,
)
from ..utils.tracing import GLOBAL_TRACER

from .builder import MAX_EVENT_QUEUE_SIZE, SPECTATOR_BUFFER_SIZE

NORMAL_SPEED = 1

# frames spectators advanced, split by whether the catch-up rule fired,
# and how many received frames a viewer still trails after each advance
# (recorded only while telemetry is on)
_m_frames = GLOBAL_TELEMETRY.registry.counter(
    "ggrs_spectator_frames_total",
    "frames spectators advanced, by speed (normal | catchup)",
    ("speed",),
)
_m_frames_normal = _m_frames.labels("normal")
_m_frames_catchup = _m_frames.labels("catchup")
_m_frames_behind = GLOBAL_TELEMETRY.registry.histogram(
    "ggrs_spectator_frames_behind",
    "frames a spectator is behind the host's inputs it has received, "
    "after each successful advance",
)


class SpectatorSession:
    def __init__(
        self,
        num_players: int,
        socket: Any,
        host: PeerEndpoint,
        max_frames_behind: int,
        catchup_speed: int,
        input_size: int,
    ):
        self.state = SessionState.SYNCHRONIZING
        self.num_players = num_players
        self.input_size = input_size
        self.inputs: List[List[PlayerInput]] = [
            [PlayerInput.blank(NULL_FRAME, input_size) for _ in range(num_players)]
            for _ in range(SPECTATOR_BUFFER_SIZE)
        ]
        self.host_connect_status = [ConnectionStatus() for _ in range(num_players)]
        self.socket = socket
        self.host = host
        self.event_queue: Deque[Event] = deque()
        self.current_frame: Frame = NULL_FRAME
        self.last_recv_frame: Frame = NULL_FRAME
        self.max_frames_behind = max_frames_behind
        self.catchup_speed = catchup_speed
        # serve-host attachment (same contract as P2PSession's hooks)
        self._host = None
        self._host_key = None
        # batched wire pump toggle + route cache (see P2PSession's twins)
        self.batched_pump = True
        self._pump_routes_cache = None
        self._pump_recv = None  # bound receive_all_wire, cached by the pump
        # vectorized protocol plane (network/endpoint_batch.py): set by
        # EndpointFleet.adopt, None while scalar (see P2PSession's twin)
        self._fleet_state = None

    def on_host_attach(self, host: Any, key: Any) -> None:
        """SessionHost.attach hook; see P2PSession.on_host_attach."""
        if self._host is not None:
            from ..errors import InvalidRequest

            raise InvalidRequest(
                f"session already attached to a host (key={self._host_key!r})"
            )
        self._host = host
        self._host_key = key

    def on_host_detach(self) -> None:
        if self._fleet_state is not None:
            self._fleet_state.fleet.retire_session(self)
        self._host = None
        self._host_key = None

    @property
    def host_key(self) -> Any:
        return self._host_key

    def current_state(self) -> SessionState:
        return self.state

    def frames_behind_host(self) -> int:
        diff = self.last_recv_frame - self.current_frame
        assert diff >= 0
        return diff

    def network_stats(self) -> NetworkStats:
        return self.host.network_stats()

    def telemetry(self) -> dict:
        """One structured snapshot (see P2PSession.telemetry)."""
        from dataclasses import asdict

        snap = GLOBAL_TELEMETRY.snapshot()
        try:
            network = asdict(self.network_stats())
        except NotSynchronized as exc:
            network = {"unavailable": type(exc).__name__}
        snap["session"] = {
            "type": "spectator",
            "state": self.state.value,
            "current_frame": self.current_frame,
            "last_recv_frame": self.last_recv_frame,
            "frames_behind_host": max(self.last_recv_frame - self.current_frame, 0),
            "network": {"host": network},
        }
        return snap

    def events(self) -> List[Event]:
        out = list(self.event_queue)
        self.event_queue.clear()
        return out

    def advance_frame(self) -> List[Request]:
        """(src/sessions/p2p_spectator_session.rs:109-138). The span is
        absolute, as P2PSession's session/advance: hosted and standalone
        spectators land in one row."""
        with GLOBAL_TRACER.span("spectator/advance", absolute=True):
            return self._advance_frame_impl()

    def _advance_frame_impl(self) -> List[Request]:
        # hosted sessions skip the internal pump (see P2PSession's twin):
        # the SessionHost already drained this tick
        if self._host is None:
            self.poll_remote_clients()
        if self.state != SessionState.RUNNING:
            raise NotSynchronized()

        requests: List[Request] = []
        catchup = self.frames_behind_host() > self.max_frames_behind
        frames_to_advance = self.catchup_speed if catchup else NORMAL_SPEED
        for _ in range(frames_to_advance):
            frame_to_grab = self.current_frame + 1
            synced_inputs = self._inputs_at_frame(frame_to_grab)
            requests.append(AdvanceFrame(inputs=synced_inputs))
            # only advance if grabbing the inputs succeeded
            self.current_frame += 1
        if GLOBAL_TELEMETRY.enabled:
            frames = _m_frames_catchup if catchup else _m_frames_normal
            frames.inc(frames_to_advance)
            _m_frames_behind.observe(self.frames_behind_host())
        return requests

    def poll_remote_clients(self) -> None:
        if self.batched_pump and hasattr(self.socket, "receive_all_wire"):
            GLOBAL_PUMP.pump((self,))
        else:
            self._poll_legacy()

    def _poll_legacy(self) -> None:
        """Unbatched per-message pump (the batched_pump=False parity
        reference and the fallback for sockets without a wire lane)."""
        for from_addr, msg in self.socket.receive_all_messages():
            if self.host.is_handling_message(from_addr):
                self.host.handle_message(msg)
        self._pump_post(None)

    def _pump_routes(self) -> dict:
        """Batched-pump dispatch table: the one host endpoint."""
        routes = self._pump_routes_cache
        if routes is None:
            routes = {
                self.host.peer_addr: ((
                    self.host,
                    getattr(self.host, "handle_decoded", None),
                    getattr(self.host, "handle_wire", None),
                ),),
            }
            self._pump_routes_cache = routes
        return routes

    def _pump_now(self) -> int:
        """One hoisted clock read per pump pass (P2PSession twin)."""
        return self.host.clock.now_ms()

    def _pump_post(self, wire_out=None, now=None) -> None:
        if now is None:
            now = self._pump_now()
        self._pump_endpoint(now)
        self._pump_encode(wire_out)

    def _pump_endpoint(self, now) -> None:
        addr = self.host.peer_addr
        for event in self.host.poll(self.host_connect_status, now):
            self._handle_event(event, addr)

    def _pump_encode(self, wire_out=None) -> None:
        if wire_out is None:
            self.host.send_all_messages(self.socket)
        else:
            self.host.drain_sends(wire_out)

    # vectorized protocol plane (network/endpoint_batch.py) ------------

    def _fleet_size(self) -> int:
        return 1

    def _fleet_profile(self):
        """One fleet row — the host endpoint. No frame-advantage prefix
        (spectators never call update_local_frame_advantage from the
        pump; it runs on EvInput receipt) and no checksum drain."""
        from ..network.protocol import PeerEndpoint

        if not isinstance(self.host, PeerEndpoint):
            return None
        addr = self.host.peer_addr
        return {
            "endpoints": [self.host],
            "emits": [
                lambda event, _a=addr, _s=self: _s._handle_event(event, _a)
            ],
            "adv_n": 0,
            "connect_status": self.host_connect_status,
            "checksums": False,
        }

    def _inputs_at_frame(self, frame_to_grab: Frame):
        """(src/sessions/p2p_spectator_session.rs:173-202)"""
        player_inputs = self.inputs[frame_to_grab % SPECTATOR_BUFFER_SIZE]
        if player_inputs[0].frame < frame_to_grab:
            raise PredictionThreshold()  # host input not here yet; wait
        if player_inputs[0].frame > frame_to_grab:
            raise SpectatorTooFarBehind()  # ring overwritten; unrecoverable

        out = []
        for handle, player_input in enumerate(player_inputs):
            if (
                self.host_connect_status[handle].disconnected
                and self.host_connect_status[handle].last_frame < frame_to_grab
            ):
                out.append((player_input.buf, InputStatus.DISCONNECTED))
            else:
                out.append((player_input.buf, InputStatus.CONFIRMED))
        return out

    def _handle_event(self, event: Any, addr: Any) -> None:
        """(src/sessions/p2p_spectator_session.rs:204-253)"""
        if isinstance(event, EvSynchronizing):
            self._push_event(Synchronizing(addr=addr, total=event.total, count=event.count))
        elif isinstance(event, EvNetworkInterrupted):
            self._push_event(
                NetworkInterrupted(addr=addr, disconnect_timeout_ms=event.disconnect_timeout_ms)
            )
        elif isinstance(event, EvNetworkResumed):
            self._push_event(NetworkResumed(addr=addr))
        elif isinstance(event, EvSynchronized):
            self.state = SessionState.RUNNING
            self._push_event(Synchronized(addr=addr))
        elif isinstance(event, EvDisconnected):
            self._push_event(Disconnected(addr=addr))
        elif isinstance(event, EvInput):
            inp = event.input
            # mirror the native twin's defensive guards: a buggy/hostile
            # endpoint must not index out of range or rewind the ring
            if event.player < 0 or event.player >= self.num_players or inp.frame < 0:
                return
            if inp.frame < self.last_recv_frame:
                return
            self.inputs[inp.frame % SPECTATOR_BUFFER_SIZE][event.player] = inp
            self.last_recv_frame = inp.frame
            self.host.update_local_frame_advantage(inp.frame)
            for i in range(self.num_players):
                self.host_connect_status[i] = ConnectionStatus(
                    self.host.peer_connect_status[i].disconnected,
                    self.host.peer_connect_status[i].last_frame,
                )
        self._trim_events()

    def _push_event(self, event: Event) -> None:
        tel = GLOBAL_TELEMETRY
        if tel.enabled:
            d = event.to_dict()
            tel.record(d.pop("kind"), frame=d.pop("frame", -1), **d)
        self.event_queue.append(event)
        self._trim_events()

    def _trim_events(self) -> None:
        while len(self.event_queue) > MAX_EVENT_QUEUE_SIZE:
            self.event_queue.popleft()
