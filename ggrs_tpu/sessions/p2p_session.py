"""The main P2P runtime: per-frame pipeline, rollback driver, message pump.

Behavioral parity with the reference (src/sessions/p2p_session.rs): ordered
request generation (save/load/advance), confirmed-frame accounting as the min
over connected peers, disconnect propagation with forced rollback to the
disconnect frame, sparse-saving mode, spectator input broadcast, wait
recommendations and checksum-exchange desync detection. The returned request
list is the seam where the TPU backend plugs in: a whole rollback block
(Load + N x Save/Advance) is fused into one device dispatch by
ggrs_tpu.tpu.backend.TpuRollbackBackend.
"""

from __future__ import annotations

from collections import deque
from typing import Any, Deque, Dict, List, Optional, Sequence

from ..errors import InvalidRequest, NotSynchronized
from ..frame_info import PlayerInput
from ..network.network_stats import NetworkStats
from ..network.pump import GLOBAL_PUMP
from ..network.protocol import (
    MAX_CHECKSUM_HISTORY_SIZE,
    EvDisconnected,
    EvInput,
    EvNetworkInterrupted,
    EvNetworkResumed,
    EvSynchronized,
    EvSynchronizing,
    PeerEndpoint,
)
from ..obs import GLOBAL_TELEMETRY
from ..sync_layer import ConnectionStatus, PendingChecksumReport, SyncLayer
from ..utils.tracing import GLOBAL_TRACER
from ..types import (
    NULL_FRAME,
    AdvanceFrame,
    DesyncDetected,
    DesyncDetection,
    Disconnected,
    Event,
    Frame,
    NetworkInterrupted,
    NetworkResumed,
    PlayerHandle,
    PlayerType,
    PlayerTypeKind,
    Request,
    SessionState,
    Synchronized,
    Synchronizing,
    WaitRecommendation,
)

from .builder import MAX_EVENT_QUEUE_SIZE

# the host's fan-out: one per confirmed input sent to a running spectator
# endpoint (recorded only while telemetry is on)
_m_spectator_sends = GLOBAL_TELEMETRY.registry.counter(
    "ggrs_spectator_sends_total",
    "confirmed inputs a P2P host sent to its running spectator endpoints",
)

RECOMMENDATION_INTERVAL = 60
MIN_RECOMMENDATION = 3


class PlayerRegistry:
    """(src/sessions/p2p_session.rs:22-113)"""

    def __init__(self, handles: Dict[PlayerHandle, PlayerType]):
        self.handles = handles
        self.remotes: Dict[Any, PeerEndpoint] = {}
        self.spectators: Dict[Any, PeerEndpoint] = {}

    def _handles_of(self, kind: PlayerTypeKind) -> List[PlayerHandle]:
        return sorted(h for h, p in self.handles.items() if p.kind == kind)

    def local_player_handles(self) -> List[PlayerHandle]:
        return self._handles_of(PlayerTypeKind.LOCAL)

    def remote_player_handles(self) -> List[PlayerHandle]:
        return self._handles_of(PlayerTypeKind.REMOTE)

    def spectator_handles(self) -> List[PlayerHandle]:
        return self._handles_of(PlayerTypeKind.SPECTATOR)

    def num_players(self) -> int:
        return sum(
            1
            for p in self.handles.values()
            if p.kind in (PlayerTypeKind.LOCAL, PlayerTypeKind.REMOTE)
        )

    def num_spectators(self) -> int:
        return len(self.spectator_handles())

    def handles_by_address(self, addr: Any) -> List[PlayerHandle]:
        return sorted(
            h
            for h, p in self.handles.items()
            if p.kind != PlayerTypeKind.LOCAL and p.addr == addr
        )


class P2PSession:
    def __init__(
        self,
        num_players: int,
        max_prediction: int,
        socket: Any,
        players: PlayerRegistry,
        sparse_saving: bool,
        desync_detection: DesyncDetection,
        input_delay: int,
        input_size: int,
        use_native_queues: bool = False,
    ):
        self.num_players = num_players
        self.max_prediction = max_prediction
        self.sparse_saving = sparse_saving
        self.socket = socket
        self.player_reg = players
        self.input_size = input_size
        self.desync_detection = desync_detection

        self.local_connect_status = [ConnectionStatus() for _ in range(num_players)]
        self.sync_layer = SyncLayer(
            num_players, max_prediction, input_size, use_native_queues
        )
        for handle, ptype in players.handles.items():
            if ptype.kind == PlayerTypeKind.LOCAL:
                self.sync_layer.set_frame_delay(handle, input_delay)

        # no remotes -> no synchronization phase needed
        if not players.remotes and not players.spectators:
            self.state = SessionState.RUNNING
        else:
            self.state = SessionState.SYNCHRONIZING

        self.disconnect_frame: Frame = NULL_FRAME
        self.next_recommended_sleep: Frame = 0
        self.next_spectator_frame: Frame = 0
        self.frames_ahead = 0
        self.event_queue: Deque[Event] = deque()
        self.local_inputs: Dict[PlayerHandle, PlayerInput] = {}
        self.local_checksum_history: Dict[Frame, int] = {}
        self._pending_checksum_report = PendingChecksumReport()
        self._wire_dispatch = None  # decided on first poll (socket+endpoints)
        # batched wire pump (network/pump.py): pooled one-pass decode +
        # field-level apply + batched sends. False pins the legacy
        # per-message loop — the parity suite's reference arm.
        self.batched_pump = True
        self._pump_routes_cache = None
        self._pump_clock = None  # cached by _pump_now on first resolution
        self._pump_recv = None  # bound receive_all_wire, cached by the pump
        # vectorized protocol plane (network/endpoint_batch.py): set by
        # EndpointFleet.adopt when a pump pass crosses the SMALL_FLEET
        # crossover; None means the endpoints run the scalar twin
        self._fleet_state = None
        # monotonic advance counter: stamps checksum-report captures so
        # the pump-side flush stays behind the capture frontier
        self._advance_serial = 0
        # checksum-report publish policy: "ready" (default) emits on the
        # pump pass as soon as a value is host-ready; "interval" defers
        # EMISSION to the interval-forced flush while the pump still
        # binds/prefetches (PendingChecksumReport.bind_and_prefetch) —
        # publish timing is then a pure function of the frame counter,
        # not of dispatch cadence. SessionHost sets "interval" on every
        # hosted p2p lane so a resident (mailbox-driven) host puts
        # bit-identical bytes on a seeded lossy wire as its
        # dispatch-per-tick twin.
        self.checksum_publish = "ready"
        # ticks whose interval-forced checksum flush had to BLOCK on a
        # device transfer (the host tax the pump-side drain removes);
        # plain int always maintained, registry counter behind enabled
        self.drain_blocked_ticks = 0
        self._m_drain_blocked = GLOBAL_TELEMETRY.registry.counter(
            "ggrs_drain_blocked_ticks_total",
            "ticks whose forced checksum flush blocked on a device drain",
        )
        # desyncs already dumped to a forensics bundle: comparison intervals
        # re-detect the same divergence every pass, one dump per (peer,
        # frame) is the useful quantity
        self._desyncs_dumped: set = set()
        # serve-host attachment (ggrs_tpu.serve.SessionHost): the host
        # drives poll/advance and fulfills requests on its shared device
        # core, so a session must belong to at most one host at a time
        self._host = None
        self._host_key = None

    # ------------------------------------------------------------------
    # serve-host lifecycle hooks (ggrs_tpu/serve/host.py)
    # ------------------------------------------------------------------

    def on_host_attach(self, host: Any, key: Any) -> None:
        """Called by SessionHost.attach: from here the HOST owns this
        session's pump/advance loop and request fulfillment. Attaching an
        already-hosted session is an error — two hosts would both fulfill
        its requests against different device slots."""
        if self._host is not None:
            raise InvalidRequest(
                f"session already attached to a host (key={self._host_key!r})"
            )
        self._host = host
        self._host_key = key

    def on_host_detach(self) -> None:
        """Called by SessionHost.detach/evict: the session is standalone
        again (its device slot is recycled; any un-dispatched rows were
        dropped with it). A fleet-adopted session retires to scalar hot
        state here — the host's pump owns the fleet rows, and a detached
        session must not keep views into them."""
        if self._fleet_state is not None:
            self._fleet_state.fleet.retire_session(self)
        self._host = None
        self._host_key = None

    @property
    def host_key(self) -> Any:
        """The key this session is hosted under, or None when standalone."""
        return self._host_key

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------

    def add_local_input(self, player_handle: PlayerHandle, buf: bytes) -> None:
        if player_handle not in self.player_reg.local_player_handles():
            raise InvalidRequest(
                "The player handle you provided is not referring to a local player."
            )
        if len(buf) != self.input_size:
            raise InvalidRequest(
                f"Input must be exactly {self.input_size} bytes, got {len(buf)}."
            )
        self.local_inputs[player_handle] = PlayerInput(
            self.sync_layer.current_frame, buf
        )

    def advance_frame(self) -> List[Request]:
        """The per-tick pipeline (src/sessions/p2p_session.rs:253-371).

        The whole method is host work with no device dependency: under the
        async dispatch pipeline it runs while the PREVIOUS tick's fused
        rollback batch is still executing on device (the session/advance
        and session/pump spans are the overlap phases — compare their
        total against the backend's tpu/async_fence stalls to see how much
        of the device time the host actually hid). The span is absolute:
        hosted and standalone sessions land in one row."""
        with GLOBAL_TRACER.span("session/advance", absolute=True):
            return self._advance_frame_impl()

    def _advance_frame_impl(self) -> List[Request]:
        # hosted sessions skip the internal pump: SessionHost drains every
        # session's sockets once per host tick immediately before
        # advancing the ready ones — repeating it here would double the
        # fleet's per-tick socket/protocol work for nothing
        if self._host is None:
            self.poll_remote_clients()
        if self.state != SessionState.RUNNING:
            raise NotSynchronized()
        self._advance_serial += 1

        requests: List[Request] = []

        # --- rollbacks and game state management
        if self.sync_layer.current_frame == 0:
            requests.append(self.sync_layer.save_current_state())

        self._update_player_disconnects()
        confirmed_frame = self.confirmed_frame()

        first_incorrect = self.sync_layer.check_simulation_consistency(
            self.disconnect_frame
        )
        if first_incorrect != NULL_FRAME:
            # Edge the reference would panic on (sync_layer.rs:141-145): a
            # disconnect recorded at exactly the current frame means nothing
            # simulated yet used wrong inputs — no rollback needed.
            if first_incorrect < self.sync_layer.current_frame:
                self._adjust_gamestate(first_incorrect, confirmed_frame, requests)
            self.disconnect_frame = NULL_FRAME

        last_saved = self.sync_layer.last_saved_frame
        if self.sparse_saving:
            self._check_last_saved_state(last_saved, confirmed_frame, requests)
        else:
            requests.append(self.sync_layer.save_current_state())

        # --- ship confirmed inputs to spectators, then GC them (reference
        # ordering: broadcast precedes GC with the same watermark, so GC can
        # never discard a frame the spectators haven't been sent)
        self._send_confirmed_inputs_to_spectators(confirmed_frame)
        self.sync_layer.set_last_confirmed_frame(confirmed_frame, self.sparse_saving)

        # --- desync detection
        if self.desync_detection.enabled:
            self._check_checksum_send_interval(confirmed_frame)
            self._compare_local_checksums_against_peers()

        # --- wait recommendation
        self._check_wait_recommendation()

        # --- register local inputs and send them
        for handle in self.player_reg.local_player_handles():
            player_input = self.local_inputs.get(handle)
            if player_input is None:
                raise InvalidRequest(
                    "Missing local input while calling advance_frame()."
                )
            actual_frame = self.sync_layer.add_local_input(handle, player_input)
            assert actual_frame != NULL_FRAME
            # input delay may shift the frame the input lands on
            self.local_inputs[handle] = PlayerInput(actual_frame, player_input.buf)
            self.local_connect_status[handle].last_frame = actual_frame

        for endpoint in self.player_reg.remotes.values():
            endpoint.send_input(self.local_inputs, self.local_connect_status)
            endpoint.send_all_messages(self.socket)
        self.local_inputs.clear()

        # --- second spectator broadcast: the watermark recomputed after the
        # local inputs landed covers the current frame, so a host's spectators
        # see frame f's confirmed input at tick f (the reference only ships it
        # from tick f+1, p2p_session.rs:278,303). Queues are flushed here so
        # the packet leaves this tick; GC stays with the earlier broadcast.
        if self.num_spectators() > 0:
            self._send_confirmed_inputs_to_spectators(self.confirmed_frame())
            for endpoint in self.player_reg.spectators.values():
                endpoint.send_all_messages(self.socket)

        # --- advance
        inputs = self.sync_layer.synchronized_inputs(self.local_connect_status)
        self.sync_layer.advance_frame()
        requests.append(AdvanceFrame(inputs=inputs))
        return requests

    def poll_remote_clients(self) -> None:
        """Message pump (src/sessions/p2p_session.rs:375-423)."""
        # absolute: the pump runs both standalone (idle loop) and inside
        # advance_frame's session/advance span — one stats row for both,
        # so the documented pump-vs-async_fence comparison reads the total
        with GLOBAL_TRACER.span("session/pump", absolute=True):
            self._poll_remote_clients_impl()

    def _poll_remote_clients_impl(self) -> None:
        if self._wire_dispatch is None:
            # all-native fast path: raw datagrams flow socket -> C++ endpoint
            # without touching the Python codec
            self._wire_dispatch = hasattr(self.socket, "receive_all_wire") and all(
                hasattr(ep, "handle_wire")
                for ep in list(self.player_reg.remotes.values())
                + list(self.player_reg.spectators.values())
            )
        if (
            self.batched_pump
            and not self._wire_dispatch
            and hasattr(self.socket, "receive_all_wire")
        ):
            # batched pump: pooled one-pass decode + field-level apply
            # (network/pump.py) — the all-native session keeps its raw
            # wire lane below, where Python decode would be pure overhead
            GLOBAL_PUMP.pump((self,))
        else:
            self._poll_legacy()

    def _poll_legacy(self) -> None:
        """The unbatched per-message pump: one decode + one
        handle_message per datagram. Kept as the parity reference
        (batched_pump=False) and the fallback for sockets without a
        wire lane; all-native sessions route here for their raw
        socket -> C++ dispatch."""
        if self._wire_dispatch is None:
            # reached directly via the pump's fallback lane: make the
            # same socket+endpoint decision _poll_remote_clients_impl
            # would have
            self._wire_dispatch = hasattr(self.socket, "receive_all_wire") and all(
                hasattr(ep, "handle_wire")
                for ep in list(self.player_reg.remotes.values())
                + list(self.player_reg.spectators.values())
            )
        if self._wire_dispatch:
            for from_addr, wire in self.socket.receive_all_wire():
                endpoint = self.player_reg.remotes.get(from_addr)
                if endpoint is not None:
                    endpoint.handle_wire(wire)
                endpoint = self.player_reg.spectators.get(from_addr)
                if endpoint is not None:
                    endpoint.handle_wire(wire)
        else:
            for from_addr, msg in self.socket.receive_all_messages():
                endpoint = self.player_reg.remotes.get(from_addr)
                if endpoint is not None:
                    endpoint.handle_message(msg)
                endpoint = self.player_reg.spectators.get(from_addr)
                if endpoint is not None:
                    endpoint.handle_message(msg)
        self._pump_post(None)

    def _pump_routes(self) -> dict:
        """addr -> ((endpoint, handle_decoded | None, handle_wire |
        None), ...): the batched pump's per-address dispatch table.
        Built once — the endpoint registry is fixed at session build."""
        routes = self._pump_routes_cache
        if routes is None:
            routes = {}
            for reg in (self.player_reg.remotes, self.player_reg.spectators):
                for addr, ep in reg.items():
                    routes.setdefault(addr, []).append((
                        ep,
                        getattr(ep, "handle_decoded", None),
                        getattr(ep, "handle_wire", None),
                    ))
            routes = {a: tuple(v) for a, v in routes.items()}
            self._pump_routes_cache = routes
        return routes

    def _pump_now(self) -> int:
        """One hoisted clock read for a whole pump pass: every timer and
        stats touch in the pass observes this single instant (no per-peer
        clock syscalls, no cross-peer timer skew within a pass). The
        clock object is cached on first resolution — every endpoint of a
        session shares the clock it was built with, so the registry scan
        is pure lookup overhead on the per-pump hot path."""
        clock = self._pump_clock
        if clock is not None:
            return clock.now_ms()
        for reg in (self.player_reg.remotes, self.player_reg.spectators):
            for endpoint in reg.values():
                self._pump_clock = endpoint.clock
                return endpoint.clock.now_ms()
        return 0

    def _pump_post(self, wire_out=None, now=None) -> None:
        """Timer/event/send phase of one pump pass, shared verbatim by
        the batched pump's scalar crossover path and the legacy loop.
        `wire_out` collects (wire, addr) pairs for a batched socket
        drain; None sends per-message as before."""
        if now is None:
            now = self._pump_now()
        self._pump_endpoint(now)
        self._pump_encode(wire_out)

    def _pump_endpoint(self, now) -> None:
        """Frame-advantage + timer + event + checksum phase — the scalar
        twin of EndpointFleet.endpoint_phase (network/endpoint_batch.py),
        which replays exactly this sequence per session on the rows its
        masks select."""
        remotes = self.player_reg.remotes
        spectators = self.player_reg.spectators
        current = self.sync_layer.current_frame
        for endpoint in remotes.values():
            if endpoint.is_running():
                endpoint.update_local_frame_advantage(current)

        endpoints = list(remotes.values()) + list(spectators.values())
        events = []
        for endpoint in endpoints:
            handles = list(endpoint.handles)
            addr = endpoint.peer_addr
            for event in endpoint.poll(self.local_connect_status, now):
                events.append((event, handles, addr))

        for event, handles, addr in events:
            self._handle_event(event, handles, addr)

        # drain-free tick: resolve desync-detection checksums during the
        # pump, not the tick — see _pump_checksums
        self._pump_checksums()

    def _pump_encode(self, wire_out=None) -> None:
        """Send-drain phase — the scalar twin of
        EndpointFleet.encode_phase, which drains only the endpoints the
        send-dirty flags select."""
        endpoints = list(self.player_reg.remotes.values()) + list(
            self.player_reg.spectators.values()
        )
        if wire_out is None:
            for endpoint in endpoints:
                endpoint.send_all_messages(self.socket)
        else:
            for endpoint in endpoints:
                endpoint.drain_sends(wire_out)

    # ------------------------------------------------------------------
    # vectorized protocol plane (network/endpoint_batch.py)
    # ------------------------------------------------------------------

    def _fleet_size(self) -> int:
        return len(self.player_reg.remotes) + len(self.player_reg.spectators)

    def _fleet_profile(self):
        """What EndpointFleet.adopt needs to hoist this session's
        endpoints into fleet rows, or None when the session is not
        fleetable (native endpoints keep their hot state across the FFI
        boundary; endpoint-less solo sessions have nothing to hoist).
        Row order is remotes-then-spectators — the scalar phase order —
        with the remotes prefix (`adv_n`) carrying the vectorized
        frame-advantage update."""
        remotes = list(self.player_reg.remotes.values())
        spectators = list(self.player_reg.spectators.values())
        endpoints = remotes + spectators
        if not endpoints:
            return None
        if any(not isinstance(ep, PeerEndpoint) for ep in endpoints):
            return None
        emits = []
        for ep in endpoints:
            handles = list(ep.handles)
            addr = ep.peer_addr
            emits.append(
                lambda event, _h=handles, _a=addr, _s=self: _s._handle_event(
                    event, _h, _a
                )
            )
        return {
            "endpoints": endpoints,
            "emits": emits,
            "adv_n": len(remotes),
            "connect_status": self.local_connect_status,
            "checksums": True,
        }

    def _pump_checksums(self) -> None:
        """Opportunistic, non-blocking drain of pending desync-detection
        reports on the pump pass: resolve the host-ready ones, prefetch
        the oldest still-in-flight one, so the interval-forced flush in
        _check_checksum_send_interval finds the bytes already moved and
        the tick path never blocks on a checksum transfer in steady
        state. Entries captured within the last two advances are left
        untouched (max_serial): their frame's correcting rollback may
        still sit in an unfulfilled — or, hosted, un-dispatched —
        request list, and binding the getter early would publish a
        mid-correction checksum."""
        pcr = self._pending_checksum_report
        if len(pcr):
            if self.checksum_publish == "interval":
                pcr.bind_and_prefetch(max_serial=self._advance_serial - 2)
            else:
                pcr.flush(
                    force=False,
                    emit=self._emit_checksum_report,
                    max_serial=self._advance_serial - 2,
                )

    def disconnect_player(self, player_handle: PlayerHandle) -> None:
        """(src/sessions/p2p_session.rs:430-456)"""
        ptype = self.player_reg.handles.get(player_handle)
        if ptype is None:
            raise InvalidRequest("Invalid Player Handle.")
        if ptype.kind == PlayerTypeKind.LOCAL:
            raise InvalidRequest("Local Player cannot be disconnected.")
        if ptype.kind == PlayerTypeKind.REMOTE:
            if self.local_connect_status[player_handle].disconnected:
                raise InvalidRequest("Player already disconnected.")
            last_frame = self.local_connect_status[player_handle].last_frame
            self._disconnect_player_at_frame(player_handle, last_frame)
        else:
            self._disconnect_player_at_frame(player_handle, NULL_FRAME)

    def events(self) -> List[Event]:
        out = list(self.event_queue)
        self.event_queue.clear()
        return out

    def network_stats(self, player_handle: PlayerHandle) -> NetworkStats:
        ptype = self.player_reg.handles.get(player_handle)
        if ptype is None or ptype.kind == PlayerTypeKind.LOCAL:
            raise InvalidRequest(
                "Given player handle not referring to a remote player or spectator"
            )
        reg = (
            self.player_reg.remotes
            if ptype.kind == PlayerTypeKind.REMOTE
            else self.player_reg.spectators
        )
        return reg[ptype.addr].network_stats()

    def telemetry(self) -> dict:
        """One structured snapshot: process-wide metrics + flight-recorder
        tail + tracer spans (GLOBAL_TELEMETRY.snapshot()) plus this
        session's own section (state, frames, per-peer NetworkStats)."""
        snap = GLOBAL_TELEMETRY.snapshot()
        snap["session"] = self._telemetry_session_section()
        return snap

    def _telemetry_session_section(self) -> dict:
        from dataclasses import asdict

        network: Dict[str, Any] = {}
        for handle, ptype in sorted(self.player_reg.handles.items()):
            if ptype.kind == PlayerTypeKind.LOCAL:
                continue
            try:
                network[str(handle)] = asdict(self.network_stats(handle))
            except NotSynchronized as exc:
                network[str(handle)] = {"unavailable": type(exc).__name__}
        # per-player prediction accuracy from THIS session's own queues
        # (the global labeled counters blend every session in the process;
        # queues are per-session, so this stays honest with several
        # sessions alive). Native queues expose no tallies and are skipped.
        accuracy: Dict[str, float] = {}
        for player, q in enumerate(self.sync_layer.input_queues):
            served = getattr(q, "predictions_served", 0)
            if served > 0:
                wrong = getattr(q, "mispredictions", 0)
                accuracy[str(player)] = 1.0 - min(wrong / served, 1.0)
        return {
            "type": "p2p",
            "state": self.state.value,
            "current_frame": self.sync_layer.current_frame,
            "last_confirmed_frame": self.sync_layer.last_confirmed_frame,
            "frames_ahead": self.frames_ahead,
            "local_players": self.player_reg.local_player_handles(),
            "remote_players": self.player_reg.remote_player_handles(),
            "spectators": self.player_reg.spectator_handles(),
            "prediction_accuracy": accuracy,
            "network": network,
        }

    def confirmed_frame(self) -> Frame:
        """min(last_frame) over connected peers (src/sessions/p2p_session.rs:487-498)."""
        confirmed = 2**31 - 1
        for status in self.local_connect_status:
            if not status.disconnected:
                confirmed = min(confirmed, status.last_frame)
        assert confirmed < 2**31 - 1
        return confirmed

    @property
    def current_frame(self) -> Frame:
        return self.sync_layer.current_frame

    @property
    def last_saved_frame(self) -> Frame:
        return self.sync_layer.last_saved_frame

    def current_state(self) -> SessionState:
        return self.state

    def local_player_handles(self) -> List[PlayerHandle]:
        return self.player_reg.local_player_handles()

    def remote_player_handles(self) -> List[PlayerHandle]:
        return self.player_reg.remote_player_handles()

    def spectator_handles(self) -> List[PlayerHandle]:
        return self.player_reg.spectator_handles()

    def handles_by_address(self, addr: Any) -> List[PlayerHandle]:
        return self.player_reg.handles_by_address(addr)

    def num_spectators(self) -> int:
        return self.player_reg.num_spectators()

    # ------------------------------------------------------------------
    # internals
    # ------------------------------------------------------------------

    def _disconnect_player_at_frame(self, player_handle: PlayerHandle, last_frame: Frame) -> None:
        """(src/sessions/p2p_session.rs:555-595)"""
        ptype = self.player_reg.handles[player_handle]
        if ptype.kind == PlayerTypeKind.REMOTE:
            endpoint = self.player_reg.remotes[ptype.addr]
            for handle in endpoint.handles:
                self.local_connect_status[handle].disconnected = True
            endpoint.disconnect()
            if self.sync_layer.current_frame > last_frame:
                # resimulate from the disconnect so predictions made for the
                # dead player are redone with Disconnected dummy inputs
                self.disconnect_frame = last_frame + 1
        elif ptype.kind == PlayerTypeKind.SPECTATOR:
            self.player_reg.spectators[ptype.addr].disconnect()
        self._check_initial_sync()

    def _check_initial_sync(self) -> None:
        if self.state != SessionState.SYNCHRONIZING:
            return
        for endpoint in list(self.player_reg.remotes.values()) + list(
            self.player_reg.spectators.values()
        ):
            if not endpoint.is_synchronized():
                return
        self.state = SessionState.RUNNING

    def _adjust_gamestate(
        self, first_incorrect: Frame, min_confirmed: Frame, requests: List[Request]
    ) -> None:
        """Rollback driver (src/sessions/p2p_session.rs:621-673)."""
        current_frame = self.sync_layer.current_frame
        frame_to_load = (
            self.sync_layer.last_saved_frame if self.sparse_saving else first_incorrect
        )
        assert frame_to_load <= first_incorrect
        count = current_frame - frame_to_load
        tel = GLOBAL_TELEMETRY
        if tel.enabled:
            tel.record(
                "rollback_begin",
                frame=frame_to_load,
                depth=count,
                first_incorrect=first_incorrect,
            )

        requests.append(self.sync_layer.load_frame(frame_to_load))
        assert self.sync_layer.current_frame == frame_to_load
        self.sync_layer.reset_prediction()

        for i in range(count):
            inputs = self.sync_layer.synchronized_inputs(self.local_connect_status)
            if self.sparse_saving:
                if self.sync_layer.current_frame == min_confirmed:
                    requests.append(self.sync_layer.save_current_state())
            else:
                if i > 0:
                    requests.append(self.sync_layer.save_current_state())
            self.sync_layer.advance_frame()
            requests.append(AdvanceFrame(inputs=inputs))
        assert self.sync_layer.current_frame == current_frame
        if tel.enabled:
            tel.record("rollback_end", frame=current_frame, resimulated=count)

    def _check_last_saved_state(
        self, last_saved: Frame, confirmed_frame: Frame, requests: List[Request]
    ) -> None:
        """Sparse-saving keepalive of the snapshot ring
        (src/sessions/p2p_session.rs:778-802)."""
        if self.sync_layer.current_frame - last_saved >= self.max_prediction:
            if confirmed_frame >= self.sync_layer.current_frame:
                requests.append(self.sync_layer.save_current_state())
            else:
                self._adjust_gamestate(last_saved, confirmed_frame, requests)
            assert confirmed_frame == NULL_FRAME or self.sync_layer.last_saved_frame == min(
                confirmed_frame, self.sync_layer.current_frame
            )

    def _send_confirmed_inputs_to_spectators(self, confirmed_frame: Frame) -> None:
        """(src/sessions/p2p_session.rs:676-703). Span absolute: it runs
        inside session/advance, and its row reads the same either way."""
        if self.num_spectators() == 0:
            return
        counting = GLOBAL_TELEMETRY.enabled
        with GLOBAL_TRACER.span("session/spectator_send", absolute=True):
            while self.next_spectator_frame <= confirmed_frame:
                inputs = self.sync_layer.confirmed_inputs(
                    self.next_spectator_frame, self.local_connect_status
                )
                assert len(inputs) == self.num_players
                input_map = {}
                for handle, inp in enumerate(inputs):
                    assert inp.frame in (NULL_FRAME, self.next_spectator_frame)
                    # disconnected dummies must still carry the right frame so the
                    # endpoint-level frame stamp stays consistent
                    input_map[handle] = PlayerInput(self.next_spectator_frame, inp.buf)
                for endpoint in self.player_reg.spectators.values():
                    if endpoint.is_running():
                        endpoint.send_input(input_map, self.local_connect_status)
                        if counting:
                            _m_spectator_sends.inc()
                self.next_spectator_frame += 1

    def _update_player_disconnects(self) -> None:
        """Cross-peer disconnect reconciliation
        (src/sessions/p2p_session.rs:707-742)."""
        for handle in range(self.num_players):
            queue_connected = True
            queue_min_confirmed = 2**31 - 1
            for endpoint in self.player_reg.remotes.values():
                if not endpoint.is_running():
                    continue
                status = endpoint.peer_connect_status[handle]
                queue_connected = queue_connected and not status.disconnected
                queue_min_confirmed = min(queue_min_confirmed, status.last_frame)

            local_connected = not self.local_connect_status[handle].disconnected
            local_min_confirmed = self.local_connect_status[handle].last_frame
            if local_connected:
                queue_min_confirmed = min(queue_min_confirmed, local_min_confirmed)

            if not queue_connected and (
                local_connected or local_min_confirmed > queue_min_confirmed
            ):
                self._disconnect_player_at_frame(handle, queue_min_confirmed)

    def _max_frame_advantage(self) -> int:
        interval = None
        for endpoint in self.player_reg.remotes.values():
            for handle in endpoint.handles:
                if not self.local_connect_status[handle].disconnected:
                    adv = endpoint.average_frame_advantage()
                    interval = adv if interval is None else max(interval, adv)
        return 0 if interval is None else interval

    def frames_ahead_estimate(self) -> int:
        return self.frames_ahead

    def _check_wait_recommendation(self) -> None:
        self.frames_ahead = self._max_frame_advantage()
        if (
            self.sync_layer.current_frame > self.next_recommended_sleep
            and self.frames_ahead >= MIN_RECOMMENDATION
        ):
            self.next_recommended_sleep = (
                self.sync_layer.current_frame + RECOMMENDATION_INTERVAL
            )
            self._push_event(WaitRecommendation(skip_frames=self.frames_ahead))

    def _handle_event(self, event: Any, player_handles: List[PlayerHandle], addr: Any) -> None:
        """(src/sessions/p2p_session.rs:805-871)"""
        if isinstance(event, EvSynchronizing):
            self._push_event(Synchronizing(addr=addr, total=event.total, count=event.count))
        elif isinstance(event, EvNetworkInterrupted):
            self._push_event(
                NetworkInterrupted(addr=addr, disconnect_timeout_ms=event.disconnect_timeout_ms)
            )
        elif isinstance(event, EvNetworkResumed):
            self._push_event(NetworkResumed(addr=addr))
        elif isinstance(event, EvSynchronized):
            self._check_initial_sync()
            self._push_event(Synchronized(addr=addr))
        elif isinstance(event, EvDisconnected):
            for handle in player_handles:
                last_frame = (
                    self.local_connect_status[handle].last_frame
                    if handle < self.num_players
                    else NULL_FRAME  # spectator
                )
                self._disconnect_player_at_frame(handle, last_frame)
            self._push_event(Disconnected(addr=addr))
        elif isinstance(event, EvInput):
            player, inp = event.player, event.input
            assert player < self.num_players
            if not self.local_connect_status[player].disconnected:
                current_remote_frame = self.local_connect_status[player].last_frame
                assert (
                    current_remote_frame == NULL_FRAME
                    or current_remote_frame + 1 == inp.frame
                ), "remote input arrived out of sequence"
                self.local_connect_status[player].last_frame = inp.frame
                self.sync_layer.add_remote_input(player, inp)

    def _push_event(self, event: Event) -> None:
        tel = GLOBAL_TELEMETRY
        if tel.enabled:
            d = event.to_dict()
            tel.record(d.pop("kind"), frame=d.pop("frame", -1), **d)
        self.event_queue.append(event)
        while len(self.event_queue) > MAX_EVENT_QUEUE_SIZE:
            self.event_queue.popleft()

    # ------------------------------------------------------------------
    # desync detection (src/sessions/p2p_session.rs:873-928)
    # ------------------------------------------------------------------

    def _check_checksum_send_interval(self, confirmed_frame: Frame) -> None:
        interval = self.desync_detection.interval
        current = self.sync_layer.current_frame
        # Flush BEFORE capturing this tick's observation: a report captured
        # at tick t covers a frame whose *correcting* rollback may still be
        # in tick t's (unfulfilled) request list — PendingChecksumReport
        # reads the value on a later tick, once the cell is final.
        force = current % interval == interval - 1
        if self.checksum_publish == "interval" and not force:
            # deterministic publish: the advance-side opportunistic flush
            # binds/prefetches only — emission waits for the forced tick,
            # so the wire stream is independent of dispatch cadence
            self._pending_checksum_report.bind_and_prefetch()
            blocked = 0
        else:
            blocked = self._pending_checksum_report.flush(
                force=force, emit=self._emit_checksum_report
            )
        if blocked:
            # the pump-side drain (_pump_checksums) exists to keep this
            # zero: a nonzero rate means the tick path still pays device
            # transfers (scripts/check.sh --pump-smoke gates on it)
            self.drain_blocked_ticks += 1
            if GLOBAL_TELEMETRY.enabled:
                self._m_drain_blocked.inc()
        # Deliberate divergence from the reference (p2p_session.rs:903): it
        # reports last_saved-1, which under misprediction is a *speculative*
        # frame — both peers would checksum half-predicted states and raise
        # false desyncs. Only frames <= confirmed_frame are bit-identical
        # across peers by construction, so clamp to that.
        frame_to_send = min(self.sync_layer.last_saved_frame - 1, confirmed_frame)
        if current % interval == 0 and frame_to_send > self.max_prediction:
            cell = self.sync_layer.saved_state_by_frame(frame_to_send)
            # the confirmed frame may have rotated out of the snapshot ring
            if cell is not None:
                self._pending_checksum_report.capture(
                    frame_to_send, cell, serial=self._advance_serial
                )
        if len(self.local_checksum_history) > MAX_CHECKSUM_HISTORY_SIZE:
            keep_after = current - MAX_CHECKSUM_HISTORY_SIZE
            self.local_checksum_history = {
                f: c for f, c in self.local_checksum_history.items() if f > keep_after
            }

    def _emit_checksum_report(self, frame: Frame, checksum: int) -> None:
        for endpoint in self.player_reg.remotes.values():
            endpoint.send_checksum_report(frame, checksum)
        self.local_checksum_history[frame] = checksum

    def _compare_local_checksums_against_peers(self) -> None:
        if self.sync_layer.current_frame % self.desync_detection.interval != 0:
            return
        for endpoint in self.player_reg.remotes.values():
            for remote_frame, remote_checksum in endpoint.checksum_history.items():
                local = self.local_checksum_history.get(remote_frame)
                if local is not None and local != remote_checksum:
                    self._push_event(
                        DesyncDetected(
                            frame=remote_frame,
                            local_checksum=local,
                            remote_checksum=remote_checksum,
                            addr=endpoint.peer_addr,
                        )
                    )
                    self._dump_desync_forensics(
                        remote_frame, local, remote_checksum, endpoint.peer_addr
                    )

    def _dump_desync_forensics(
        self, frame: Frame, local: int, remote: int, addr: Any
    ) -> None:
        """One forensics bundle per (peer, frame) divergence: the frame,
        both checksums, the flight-recorder tail (rollbacks,
        mispredictions, disconnects leading up to it) and the predictions
        still standing — enough to diagnose a desync after the process is
        gone. Telemetry must be enabled: without the recorder running
        there is no history worth dumping."""
        tel = GLOBAL_TELEMETRY
        if not tel.enabled or (addr, frame) in self._desyncs_dumped:
            return
        self._desyncs_dumped.add((addr, frame))
        tel.write_desync_forensics(
            frame=frame,
            local_checksum=local,
            remote_checksum=remote,
            addr=addr,
            pending_predicted_inputs=self.sync_layer.pending_predicted_inputs(),
            session=self._telemetry_session_section(),
        )
