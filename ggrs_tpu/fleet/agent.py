"""The per-host agent: one SessionHost worth of match islands behind a
control socket.

`AgentCore` is sans-io-shaped: `step()` does one bounded unit of work —
pump the control connection, tick every live island through the SHARED
`step_islands` loop, heartbeat and checkpoint on their cadences — so
in-process tests drive it deterministically (FakeClock, socketpair)
while `main()` wraps the same object in a paced real-time loop as a real
OS process (`python -m ggrs_tpu.fleet.agent`).

The data plane never waits for the control plane: islands tick whether
or not the director is reachable (a control partition costs heartbeats,
not frames), and the ONLY control-plane signal that stops the data
plane is **fencing** — a reply or call carrying a newer epoch than ours
means the director already re-placed our sessions on a sibling, and the
one correct move is to stop advancing immediately and terminate without
writing another checkpoint. Anything else (continuing to tick, one last
"helpful" checkpoint) is the split-brain double-hosting the epoch
scheme exists to prevent.

Crash recovery cadence: every `checkpoint_every` host ticks the agent
serializes its co-located islands into one fleet ticket
(ggrs_tpu.fleet.ticket) and atomically replaces
`<base_dir>/host<id>.ckpt`. Serialization is observationally neutral
(see ticket.py), so the checkpointed run and an unfaulted run are the
same run.

Durable input journal (docs/DESIGN.md "Durable recovery"): on top of
the in-RAM ticket the agent journals each co-located mem-plane island's
CONFIRMED input rows to a crash-consistent segment WAL under
`<base_dir>/journal_h<id>/m<match>` — one journal per MATCH (every peer
of an island confirms bit-identical rows, so peer 0's lane taps for the
whole island). Tickets taken at export/drain carry the journal bytes by
value, so a migrated match's durable history moves with it; the
director SEIZES journal files at fence time exactly like ticket bytes,
and its failover ladder falls back ticket → ticket+journal-tail-verify
→ journal-only resimulation from genesis (`journal_rebuild` below) —
the tier that makes TOTAL host loss (ticket destroyed, process gone)
recoverable with zero confirmed-frame loss.
"""

from __future__ import annotations

import os
from typing import Any, Dict, List, Optional

from ..errors import HostFull
from ..obs import GLOBAL_TELEMETRY
from ..utils.checkpoint import atomic_write_bytes
from ..utils.clock import Clock, FakeClock
from .island import FRAME_MS, MatchIsland, MatchSpec, ReboundUdpSocket, step_islands
from .rpc import RpcPeer
from .ticket import (
    dumps_ticket,
    export_islands,
    import_islands,
    loads_ticket,
    read_ticket_file,
)
from .wire import FRAME_CALL, FleetConn

FENCED_EXIT_CODE = 86


class AgentCore:
    """One agent's whole state: host, islands, control peer, cadences.

    `clock` paces the CONTROL plane (heartbeats, partitions) — real
    monotonic in a process, FakeClock in tests. The host and the
    islands run in virtual time advanced one frame per step, the same
    cadence the single-process twin uses."""

    def __init__(self, game, *, base_dir: str = ".",
                 clock: Optional[Clock] = None,
                 max_sessions: int = 16, max_prediction: int = 8,
                 num_players: int = 4, hb_interval_ms: int = 150,
                 checkpoint_every: int = 32, warmup: bool = False,
                 label: str = "", resident: bool = False,
                 resident_ticks: int = 8, sdc_audit_every: int = 0,
                 journal: bool = True, journal_fsync_every: int = 0,
                 journal_segment_bytes: int = 1 << 18,
                 speculation: bool = False, speculation_seed: int = 0):
        """`resident=True` runs the agent's SessionHost on the
        device-resident serving loop (PR 13's mailbox + while_loop
        driver) — bit-identical to the dispatch-per-tick agent by the
        resident contract, and every fleet operation (checkpoint
        tickets, SIGKILL-restore, cross-process migration) drains the
        mailbox back to canonical form first, so tickets from a
        resident agent import into a non-resident one and vice versa.
        `sdc_audit_every` enables the host's sampled SDC audit lane.
        `journal=True` (the default) journals every co-located
        mem-plane island's confirmed inputs per match under
        `<base_dir>/journal_h<host_id>` — observationally neutral to
        the data plane (a host-side tap), `journal_fsync_every` sets
        the writer's fsync cadence."""
        from ..serve.host import SessionHost

        self.clock = clock or Clock()
        self.base_dir = base_dir
        self.hb_interval_ms = hb_interval_ms
        self.checkpoint_every = checkpoint_every
        self.label = label
        self.host = SessionHost(
            game,
            max_prediction=max_prediction,
            num_players=num_players,
            max_sessions=max_sessions,
            clock=FakeClock(),
            idle_timeout_ms=0,
            warmup=warmup,
            resident=resident,
            resident_ticks=resident_ticks,
            sdc_audit_every=sdc_audit_every,
            speculation=speculation,
            speculation_seed=speculation_seed,
        )
        # model-rollout undo buffer: (version, blob) pairs — _cur_model
        # is what serves now ((None, None) = per-lane online models),
        # _prev_model is what the last install displaced, so the
        # director's rollback_model is one cheap local swap-back with
        # no re-push over the wire
        self._cur_model: tuple = (None, None)
        self._prev_model: Optional[tuple] = None
        if warmup:
            # the failover/migration import path runs EAGER per-leaf
            # device updates whose first compile costs whole heartbeats;
            # a round-trip of slot 0's own residue compiles them all
            # before serving (bytes land back identical, so it is a
            # no-op on state)
            self.host.device.import_slot(
                0, self.host.device.export_slot(0)
            )
        self.islands: Dict[int, MatchIsland] = {}
        self._spread: set = set()  # match_ids whose island is a half
        self._reserved: Dict[int, Dict[int, ReboundUdpSocket]] = {}
        self.peer: Optional[RpcPeer] = None
        self.host_id: Optional[int] = None
        self.epoch = 0
        self.registered = False
        self.terminated: Optional[str] = None
        self.tick_index = 0
        self.last_checkpoint: Optional[dict] = None
        self.checkpoints_written = 0
        self._pending: Dict[int, str] = {}  # rid -> kind of our own call
        self._last_hb = self.clock.now_ms()
        self._partition_until: Optional[int] = None
        self._draining = False
        # slot quarantines the host surfaced, and what became of them:
        # match_id -> "rebuilt" (mini-failover from the last checkpoint
        # ticket) | "lost" (no clean ticket covered the match)
        self.quarantines: Dict[int, str] = {}
        # durable per-match input journals: match_id -> tapped host key
        # (peer 0's lane); the directory is fixed at registration when
        # the host_id lands
        self.journal_enabled = journal
        self.journal_fsync_every = journal_fsync_every
        self.journal_segment_bytes = journal_segment_bytes
        self.journal_dir: Optional[str] = None
        self._island_journal: Dict[int, Any] = {}
        self.journal_frames_replayed = 0

    # ------------------------------------------------------------------
    # control-plane lifecycle
    # ------------------------------------------------------------------

    def attach_conn(self, conn: FleetConn) -> None:
        self.peer = RpcPeer(conn, label="director")

    def start(self) -> None:
        """Send the registration call (answered asynchronously on a
        later step — the agent never blocks on the director)."""
        assert self.peer is not None
        rid = self.peer.next_rid()
        self._pending[rid] = "register"
        self.peer.conn.send(FRAME_CALL, 0, {
            "op": "register", "rid": rid, "pid": os.getpid(),
            "label": self.label,
            "max_sessions": self.host.max_sessions,
        }, now_ms=self.clock.now_ms())

    def partition(self, duration_ms: int) -> None:
        """Simulate a symmetric control partition: frames stop flowing
        both ways for `duration_ms` (the data plane is untouched)."""
        self._partition_until = self.clock.now_ms() + duration_ms

    def _terminate(self, reason: str) -> None:
        self.terminated = reason
        if GLOBAL_TELEMETRY.enabled:
            GLOBAL_TELEMETRY.record(
                "fleet_agent_terminated", reason=reason,
                host=self.host_id if self.host_id is not None else -1,
                tick=self.tick_index,
            )

    # ------------------------------------------------------------------
    # the step loop
    # ------------------------------------------------------------------

    def step(self) -> None:
        if self.terminated is not None:
            return
        now = self.clock.now_ms()
        conn = self.peer.conn if self.peer is not None else None
        if conn is not None:
            if self._partition_until is not None:
                if now < self._partition_until:
                    conn.partitioned = True
                else:
                    conn.partitioned = False
                    self._partition_until = None
            self._pump_control(now)
        if self.terminated is not None:
            return  # fenced mid-pump: no further advance, ever
        # data plane: islands tick regardless of director reachability
        active = [
            i for i in self.islands.values()
            if i.keys and not i.done and not i.failed
        ]
        if active:
            # snapshot key->match ownership BEFORE stepping: the island
            # loop's vanished-lane guard wipes a quarantined island's
            # keys, and the verdicts drained after must still map back
            # to the match they poisoned
            owners = {
                key: mid
                for mid, isl in self.islands.items()
                for key in isl.keys.values()
            }
            step_islands(self.host, active)
            for poisoned in self.host.take_quarantines():
                self._on_quarantine(poisoned, owners.get(poisoned.key))
            self.host.clock.advance(FRAME_MS)
            self.tick_index += 1
            if (
                self.checkpoint_every
                and self.tick_index % self.checkpoint_every == 0
            ):
                # heartbeat on BOTH sides of the pause: the export's
                # fence flush is the longest silence this loop emits,
                # and it must not eat into the suspicion budget
                if conn is not None and self.registered:
                    self._send_heartbeat(now)
                self.write_checkpoint()
                if conn is not None and self.registered:
                    self._send_heartbeat(self.clock.now_ms())
        if (
            conn is not None
            and self.registered
            and now - self._last_hb >= self.hb_interval_ms
        ):
            self._send_heartbeat(now)

    def _pump_control(self, now: int) -> None:
        self.peer.conn.flush(now)
        self.peer.pump(
            on_frame=lambda epoch, body, blob: self._on_call(
                epoch, body, blob, now
            )
        )
        for rid in list(self.peer.replies):
            kind = self._pending.pop(rid, None)
            _epoch, body, _blob = self.peer.replies.pop(rid)
            if not body.get("ok", False) and body.get("kind") == "fenced":
                # the director fenced this incarnation: our sessions are
                # (or are about to be) someone else's — stop advancing
                self._terminate("fenced")
                return
            if kind == "register" and body.get("ok"):
                self.host_id = body["host_id"]
                self.epoch = body["epoch"]
                self.registered = True
                if self.journal_enabled:
                    # per-incarnation directory: a respawned replacement
                    # gets a fresh host_id, so a predecessor's files can
                    # never masquerade as this incarnation's history
                    self.journal_dir = os.path.join(
                        self.base_dir, f"journal_h{self.host_id}"
                    )
                self._last_hb = now - self.hb_interval_ms  # hb soon

    def _on_quarantine(self, poisoned, mid=None) -> None:
        """A hosted slot was quarantined (typed SlotPoisoned from the
        host's device-fault containment): treat it as a MINI-FAILOVER
        of the owning match — the PR 11 seize/adopt machinery turned
        inward. The island is torn down whole (a mem-plane match's
        surviving peers can never confirm another frame against a dead
        sibling) and rebuilt from the agent's last crash-checkpoint
        ticket, every peer re-adopted at the checkpoint frame exactly
        as a director failover would place it on a sibling host. No
        clean ticket covering the match -> the match is lost: marked
        failed, reported in the heartbeat, excluded from future
        checkpoints."""
        if mid is None:
            for m, island in self.islands.items():
                if poisoned.key in island.keys.values():
                    mid = m
                    break
        if mid is None or mid not in self.islands:
            return  # a non-island session (not spawned by the director)
        island = self.islands[mid]
        for key in list(island.keys.values()):
            if key in self.host._lanes:
                self.host.detach(key)
        island.keys = {}
        island.failed = True
        outcome = "lost"
        ckpt = self.last_checkpoint
        if ckpt is not None and mid not in self._spread:
            try:
                entries, _meta = loads_ticket(
                    read_ticket_file(ckpt["path"])
                )
                entries = [
                    e for e in entries
                    if e["island"].spec.match_id == mid
                ]
                if entries:
                    for e in entries:
                        e.pop("journal", None)  # periodic tickets carry
                        # none, but be robust to drained-ticket reuse
                    restored = import_islands(self.host, entries)
                    self.islands[mid] = restored[0]
                    # resume the match's journal on the rebuilt lane:
                    # the on-disk history is intact (the quarantine was
                    # a device fault, not a disk fault) and the redrive
                    # verifies against it
                    self._attach_island_journal(restored[0])
                    outcome = "rebuilt"
            except Exception as exc:  # noqa: BLE001 - a failed rebuild
                # must degrade to "match lost", never take the agent
                # (and its innocent matches) down with it — but the
                # rebuild's stack IS the outage explanation, so it goes
                # to the flight recorder before we move on
                outcome = "lost"
                if GLOBAL_TELEMETRY.enabled:
                    GLOBAL_TELEMETRY.record(
                        "fleet_rebuild_failed", match=mid,
                        error=f"{type(exc).__name__}: {exc}",
                    )
        self.quarantines[mid] = outcome
        if GLOBAL_TELEMETRY.enabled:
            GLOBAL_TELEMETRY.record(
                "fleet_quarantine", match=mid,
                host=self.host_id if self.host_id is not None else -1,
                outcome=outcome, reason=poisoned.reason,
                slot=poisoned.slot, frame=poisoned.frame,
            )
        # refresh crash cover NOW: a lost island must not resurrect
        # from a stale ticket, and a rebuilt one needs cover at its
        # rebuilt frame
        self.write_checkpoint()

    # ------------------------------------------------------------------
    # durable per-match input journals
    # ------------------------------------------------------------------

    def _journal_path(self, match_id: int) -> Optional[str]:
        if self.journal_dir is None:
            return None
        return os.path.join(self.journal_dir, f"m{match_id}")

    def _attach_island_journal(self, island, files=None,
                               tail=None) -> None:
        """Tap peer 0's lane of a co-located mem-plane island into the
        match's journal (`files` seeds it first — seized/migrated
        bytes, so the history stays contiguous from genesis; `tail`
        pre-observes the source recorder's not-yet-durable rows so the
        adoption hole journals too). Degradation-only failure mode: a
        corrupt local journal leaves the match served but unjournaled,
        never unserved."""
        from ..errors import JournalError

        if not self.journal_enabled or self.journal_dir is None:
            return
        spec = island.spec
        if spec.data_plane != "mem" or not island.keys:
            return
        try:
            path = self._journal_path(spec.match_id)
            if files:
                from ..journal.wal import seed_journal

                seed_journal(path, files)
            peer = min(island.keys)
            attached = self.host.attach_journal(
                island.keys[peer], path,
                meta={
                    "match_id": spec.match_id,
                    "spec": spec.to_json(),
                    "host_id": self.host_id,
                    "epoch": self.epoch,
                    "peer": peer,
                    "input_delay": spec.input_delay,
                },
                fsync_every=self.journal_fsync_every,
                segment_bytes=self.journal_segment_bytes,
            )
        except (JournalError, OSError) as exc:
            # degradation-only, as documented: a disk that refuses the
            # seed must not fail an IMPORT the islands already adopted
            # under — the director's retry on a sibling would double-
            # host the match. The match serves unjournaled instead.
            attached = None
            if GLOBAL_TELEMETRY.enabled:
                GLOBAL_TELEMETRY.record(
                    "fleet_journal_attach_degraded",
                    match=spec.match_id, error=type(exc).__name__,
                )
        if attached is not None:
            self._island_journal[spec.match_id] = island.keys[peer]
            if tail:
                self.host.seed_journal_tail(island.keys[peer], tail)

    def _detach_island_journal(self, match_id: int) -> None:
        self._island_journal.pop(match_id, None)

    def _journal_section(self) -> Dict[str, Any]:
        matches = {}
        for mid, key in list(self._island_journal.items()):
            if key not in self.host._lanes:
                continue
            frontier = self.host.journal_frontier(key)
            if frontier is not None:
                matches[str(mid)] = frontier
        return {"dir": self.journal_dir, "matches": matches}

    def _send_heartbeat(self, now: int) -> None:
        self._last_hb = now
        rid = self.peer.next_rid()
        self._pending[rid] = "heartbeat"
        while len(self._pending) > 64:
            # replies lost to a partition never arrive; don't hoard rids
            self._pending.pop(next(iter(self._pending)))
        self.peer.conn.send(FRAME_CALL, self.epoch, {
            "op": "heartbeat", "rid": rid,
            "host_id": self.host_id,
            "tick": self.tick_index,
            "sessions": self.host.active_sessions,
            "free_slots": len(self.host._free_slots),
            "islands": {
                str(mid): i.section() for mid, i in self.islands.items()
            },
            "checkpoint": self.last_checkpoint,
            "desyncs": sum(i.desyncs for i in self.islands.values()),
            "quarantines": {
                str(m): outcome for m, outcome in self.quarantines.items()
            },
            **(
                {"journal": self._journal_section()}
                if self.journal_enabled and self.journal_dir is not None
                else {}
            ),
            **(
                {"model": {
                    "version": self.host.input_model_version,
                    "spec_hit_rate": round(self.host.spec_hit_rate, 4),
                }}
                if self.host.speculation else {}
            ),
        }, now_ms=now)

    # ------------------------------------------------------------------
    # serving director calls
    # ------------------------------------------------------------------

    def _on_call(self, call_epoch: int, body: dict, blob: bytes,
                 now: int) -> None:
        rid = body.get("rid")
        if rid is None:
            return
        if self.peer.replay_cached(rid, now):
            return  # duplicate delivery: idempotent by reply cache
        op = body.get("op", "")
        if self.registered and call_epoch != self.epoch:
            if call_epoch > self.epoch:
                # the director moved on without us — acknowledge and die
                self.peer.reply(self.epoch, rid, {
                    "kind": "fenced", "error": "agent epoch superseded",
                    "epoch": call_epoch, "host_id": self.host_id,
                }, ok=False, now_ms=now)
                self._terminate("fenced")
                return
            self.peer.reply(self.epoch, rid, {
                "kind": "stale", "error": "call carries an older epoch",
                "epoch": self.epoch,
            }, ok=False, now_ms=now)
            return
        try:
            result = self._dispatch(op, body, blob, now)
        except Exception as exc:  # noqa: BLE001 - fleet isolation: one
            # op failing (a GGRSError, or an OSError like the udp
            # rebind's EADDRINUSE data-plane fence) must become a typed
            # error REPLY, never a dead agent taking innocent matches
            # with it
            if GLOBAL_TELEMETRY.enabled:
                GLOBAL_TELEMETRY.record(
                    "fleet_op_failed", op=op,
                    error=f"{type(exc).__name__}: {exc}",
                )
            self.peer.reply(self.epoch, rid, {
                "kind": type(exc).__name__, "error": str(exc),
            }, ok=False, now_ms=now)
            return
        reply_body, reply_blob, then = result
        self.peer.reply(
            self.epoch, rid, reply_body, reply_blob, now_ms=now
        )
        self.peer.conn.flush(now)
        if then is not None:
            self._terminate(then)

    def _dispatch(self, op: str, body: dict, blob: bytes, now: int):
        """Returns (reply_body, reply_blob, terminate_reason|None)."""
        if op == "ping":
            return {"pong": True, "tick": self.tick_index}, b"", None
        if op == "spawn_match":
            return self._op_spawn(body), b"", None
        if op == "reserve_ports":
            return self._op_reserve(body), b"", None
        if op == "spawn_spread":
            return self._op_spawn_spread(body), b"", None
        if op == "release_match":
            return self._op_release(body), b"", None
        if op == "export_match":
            return *self._op_export(body), None
        if op == "import":
            return self._op_import(blob), b"", None
        if op == "journal_rebuild":
            return self._op_journal_rebuild(blob, now), b"", None
        if op == "report":
            return self._op_report(body), b"", None
        if op == "drain":
            rbody, rblob = self._op_drain()
            return rbody, rblob, "drained"
        if op == "partition":
            self.partition(int(body.get("ms", 0)))
            return {"partition_ms": body.get("ms", 0)}, b"", None
        if op == "install_model":
            return self._op_install_model(body, blob), b"", None
        if op == "rollback_model":
            return self._op_rollback_model(), b"", None
        if op == "shutdown":
            return {"bye": True}, b"", "shutdown"
        from ..errors import InvalidRequest

        raise InvalidRequest(f"unknown fleet op {op!r}")

    def _op_install_model(self, body: dict, blob: bytes) -> dict:
        """Deserialize a registry blob and hot-swap it into the host's
        speculation planner. Identity/format mismatches raise typed and
        become an error reply — the director sees exactly which host
        refused and why, and the host keeps serving its old model."""
        from ..learn.model import ArrayInputModel

        model = ArrayInputModel.from_bytes(blob)
        version = body.get("version", model.version)
        self.host.install_input_model(model, version=version)
        self._prev_model = self._cur_model
        self._cur_model = (version, blob)
        return {
            "installed": version,
            "spec_hit_rate": round(self.host.spec_hit_rate, 4),
        }

    def _op_rollback_model(self) -> dict:
        """Undo the last install: restore the displaced model from the
        local undo buffer ((None, None) reverts to the per-lane online
        models). Idempotent once — a second rollback with an empty
        buffer also lands on online, the safe floor."""
        from ..learn.model import ArrayInputModel

        version, blob = self._prev_model or (None, None)
        model = ArrayInputModel.from_bytes(blob) if blob else None
        self.host.install_input_model(model, version=version)
        self._cur_model = (version, blob)
        self._prev_model = None
        return {"rolled_back_to": version}

    def _op_spawn(self, body: dict) -> dict:
        if self._draining:
            raise HostFull("agent is draining: not admitting matches")
        spec = MatchSpec.from_json(body["spec"])
        if self.host.active_sessions + spec.players > self.host.max_sessions:
            raise HostFull(
                f"match of {spec.players} exceeds the "
                f"{self.host.max_sessions - self.host.active_sessions} "
                "free session slots"
            )
        island = MatchIsland.build(spec)
        island.attach(self.host)
        self.islands[spec.match_id] = island
        self._attach_island_journal(island)
        # crash cover from the first tick: a match only a future periodic
        # checkpoint would capture is a match a kill can lose
        self.write_checkpoint()
        return {"match": spec.match_id, "peers": len(island.peers)}

    def _op_reserve(self, body: dict) -> dict:
        mid = int(body["match"])
        peers = [int(p) for p in body["peers"]]
        bucket = self._reserved.setdefault(mid, {})
        for p in peers:
            if p not in bucket:
                bucket[p] = ReboundUdpSocket(0)
        return {"ports": {str(p): bucket[p].port for p in peers}}

    def _op_spawn_spread(self, body: dict) -> dict:
        if self._draining:
            raise HostFull("agent is draining: not admitting matches")
        spec = MatchSpec.from_json(body["spec"])
        local = [int(p) for p in body["peers"]]
        island = MatchIsland.build(
            spec, local_peers=local,
            reserved=self._reserved.pop(spec.match_id, None),
        )
        island.attach(self.host)
        self.islands[spec.match_id] = island
        self._spread.add(spec.match_id)
        return {"match": spec.match_id, "peers": local}

    def _op_release(self, body: dict) -> dict:
        """Tear down a finished (or abandoned) match: detach its
        sessions, recycle the slots, close its real sockets."""
        from ..errors import InvalidRequest

        mid = int(body["match"])
        island = self.islands.pop(mid, None)
        if island is None:
            raise InvalidRequest(f"unknown match {mid}")
        self._spread.discard(mid)
        self._detach_island_journal(mid)
        for key in island.keys.values():
            if key in self.host._lanes:
                self.host.detach(key)
        island.keys = {}
        for sock in island.sockets.values():
            close = getattr(sock, "close", None)
            if callable(close):
                close()
        self.write_checkpoint()  # the released match must not resurrect
        return {"match": mid}

    def _op_export(self, body: dict):
        from ..errors import InvalidRequest

        mid = int(body["match"])
        island = self.islands.get(mid)
        if island is None:
            raise InvalidRequest(f"unknown match {mid}")
        if mid in self._spread:
            raise InvalidRequest(
                f"match {mid} is spread across agents: a half cannot "
                "migrate (its sibling's ack state would dangle)"
            )
        tails = self._capture_journal_tails([island])
        entries = export_islands(self.host, [island], detach=True)
        self.islands.pop(mid)
        self._attach_ticket_journals(entries, tails)
        blob = dumps_ticket(entries, self._ticket_meta())
        # refresh the crash checkpoint WITHOUT the exported match: were
        # this host killed later, a stale checkpoint would resurrect a
        # second copy of a match that now lives elsewhere
        self.write_checkpoint()
        return {"match": mid}, blob

    def _capture_journal_tails(
        self, islands: List[Any]
    ) -> Dict[int, dict]:
        """BEFORE a detaching export: final-drain each exported match's
        tap and snapshot the rows not yet durable (played but
        unconfirmed at the export instant) — the destination seeds its
        recorder with them, covering the hole between the durable
        frontier and the first frame it will observe itself."""
        tails: Dict[int, dict] = {}
        if not self.journal_enabled or self.journal_dir is None:
            return tails
        for island in islands:
            mid = island.spec.match_id
            key = self._island_journal.get(mid)
            if key is None or key not in self.host._lanes:
                continue
            tail = self.host.journal_tail(key)
            if tail:
                tails[mid] = tail
        return tails

    def _attach_ticket_journals(
        self, entries: List[dict], tails: Optional[Dict[int, dict]] = None
    ) -> None:
        """Fold each exported match's journal bytes (+ the captured
        recorder tail) into its ticket entry (read AFTER export
        detached+synced the tap, so the bytes are the complete
        history): the durable lineage migrates with the match instead
        of stranding on the source host."""
        from ..journal.wal import journal_files

        if not self.journal_enabled or self.journal_dir is None:
            return
        for entry in entries:
            mid = entry["island"].spec.match_id
            self._detach_island_journal(mid)
            files = journal_files(self._journal_path(mid))
            if files:
                entry["journal"] = files
                if tails and mid in tails:
                    entry["journal_tail"] = tails[mid]

    def _op_import(self, blob: bytes) -> dict:
        entries, meta = loads_ticket(blob)
        journal_seed = {
            entry["island"].spec.match_id: entry.pop("journal")
            for entry in entries
            if entry.get("journal")
        }
        journal_tails = {
            entry["island"].spec.match_id: entry.pop("journal_tail")
            for entry in entries
            if entry.get("journal_tail")
        }
        adopted = import_islands(self.host, entries)
        out = {}
        for island in adopted:
            self.islands[island.spec.match_id] = island
            self._attach_island_journal(
                island,
                files=journal_seed.get(island.spec.match_id),
                tail=journal_tails.get(island.spec.match_id),
            )
            out[str(island.spec.match_id)] = {
                str(k): v for k, v in island.frames().items()
            }
        # the adopted matches need crash cover NOW, not at the next
        # periodic tick: a kill in that gap would lose exactly the
        # sessions a failover/migration just moved here
        self.write_checkpoint()
        return {"adopted": out}

    def _op_journal_rebuild(self, blob: bytes, now: int) -> dict:
        """The failover ladder's THIRD tier: rebuild matches from their
        seized journals ALONE — no ticket, no surviving process state.
        Each match island is rebuilt from its spec with the journal's
        confirmed rows mapped back to per-peer submit scripts, then the
        whole batch redrives from genesis through the ONE megabatch
        drive loop (`step_islands`) in a tight catch-up to the journal
        frontier: N lost matches resimulate as one fleet, every
        re-confirmed row VERIFIED bit-for-bit against the journaled
        bytes by the resumed writer. Deterministic by the repo's one
        contract — the rebuilt run is a pure function of (spec,
        confirmed inputs) — so the recovered match is bitwise the match
        that died."""
        import pickle

        from ..errors import InvalidRequest
        from ..journal.metrics import journal_replayed_frames_total
        from ..journal.recover import journal_coverage, scripts_from_journal
        from ..journal.wal import read_journal_script, seed_journal

        if self._draining:
            raise HostFull("agent is draining: not rebuilding matches")
        payload = pickle.loads(blob)
        rebuilt: List[tuple] = []
        failed: Dict[str, str] = {}
        for mid_s, entry in sorted(payload.items(), key=lambda kv: int(kv[0])):
            spec = MatchSpec.from_json(entry["spec"])
            island = None
            try:
                if (
                    self.host.active_sessions + spec.players
                    > self.host.max_sessions
                ):
                    raise HostFull(
                        f"journal rebuild of match {spec.match_id} "
                        "exceeds the free session slots"
                    )
                path = self._journal_path(spec.match_id)
                if path is None:
                    raise InvalidRequest("agent has no journal directory")
                seed_journal(path, entry["files"])
                inputs, _statuses, jmeta = read_journal_script(path)
                if int(jmeta.get("first_frame", 0)) != 0:
                    # a journal whose first surviving segment starts
                    # past genesis (leading segment lost/quarantined)
                    # cannot seed a from-genesis resimulation: frames
                    # would map to the wrong cursors silently — refuse
                    # typed instead
                    from ..errors import JournalCorrupt

                    raise JournalCorrupt(
                        "journal does not cover genesis "
                        f"(first_frame={jmeta.get('first_frame')})",
                        path=path,
                        frame=int(jmeta.get("first_frame", 0)),
                    )
                island = MatchIsland.build(spec)
                island.scripts = scripts_from_journal(
                    inputs,
                    input_delay=spec.input_delay,
                    ticks=spec.ticks,
                    # beyond the journaled frontier the match resumes
                    # live traffic; the spec-derived script is the
                    # harness's stand-in for it (and bit-equal to what
                    # the journal pinned — the twin-parity gates verify)
                    fallback=island.scripts,
                )
                island.attach(self.host)
                self.islands[spec.match_id] = island
                # resume-attach AFTER seeding: the writer retains the
                # seized rows as its verify set, so the catch-up
                # redrive below is checked row-for-row against the
                # durable bytes
                self._attach_island_journal(island)
                rebuilt.append(
                    (island, journal_coverage(
                        inputs, input_delay=spec.input_delay
                    ))
                )
            except Exception as exc:  # noqa: BLE001 - per-match
                # isolation: ONE poison journal (corrupt from genesis,
                # capacity miss) must not abort the sibling rebuilds or
                # leave its own half-attached residue serving
                if island is not None:
                    for lkey in list(island.keys.values()):
                        if lkey in self.host._lanes:
                            self.host.detach(lkey)
                    island.keys = {}
                self.islands.pop(spec.match_id, None)
                self._detach_island_journal(spec.match_id)
                failed[mid_s] = f"{type(exc).__name__}: {exc}"
                if GLOBAL_TELEMETRY.enabled:
                    GLOBAL_TELEMETRY.record(
                        "fleet_journal_rebuild_failed",
                        match=spec.match_id,
                        error=type(exc).__name__,
                    )
        # batched catch-up resimulation: drive ONLY the rebuilt islands
        # (their private clocks advance; co-hosted live islands stay
        # frozen) until each reaches its journal frontier. Heartbeats
        # bracket the stretch — recovery must not read as death.
        steps = 0
        cap = 8 * max(
            (i.spec.ticks + i.COOLDOWN_FACTOR * i.spec.max_prediction + 64
             for i, _ in rebuilt),
            default=0,
        )
        conn = self.peer.conn if self.peer is not None else None
        frames_before = {
            i.spec.match_id: min(i.frames().values(), default=0)
            for i, _ in rebuilt
        }
        while steps < cap:
            live = [
                i for i, cov in rebuilt
                if not i.done and not i.failed and i.cursor < cov
            ]
            if not live:
                break
            step_islands(
                self.host,
                [i for i, _ in rebuilt if not i.done and not i.failed],
            )
            self.host.clock.advance(FRAME_MS)
            steps += 1
            if conn is not None and self.registered and steps % 64 == 0:
                self._send_heartbeat(self.clock.now_ms())
        replayed = sum(
            max(min(i.frames().values(), default=0)
                - frames_before[i.spec.match_id], 0)
            for i, _ in rebuilt
        )
        self.journal_frames_replayed += replayed
        journal_replayed_frames_total().inc(replayed)
        # the catch-up advanced host ticks no OTHER lane saw: re-anchor
        # their wedge monitors so recovery can't read as a lane wedge
        for lane in self.host._lanes.values():
            lane.last_progress_tick = self.host._tick_index
            lane.wedge_reported = False
        if GLOBAL_TELEMETRY.enabled:
            GLOBAL_TELEMETRY.record(
                "fleet_journal_rebuild",
                host=self.host_id if self.host_id is not None else -1,
                matches=len(rebuilt), frames=replayed, steps=steps,
            )
        # crash cover at the recovered frame, from tick one
        self.write_checkpoint()
        return {
            "rebuilt": {
                str(i.spec.match_id): {
                    str(k): v for k, v in i.frames().items()
                }
                for i, _ in rebuilt
            },
            "failed": failed,
            "replayed_frames": replayed,
            "steps": steps,
        }

    def _op_report(self, body: dict) -> dict:
        digests = bool(body.get("digests", True))
        report = {}
        for mid, island in self.islands.items():
            entry = island.section()
            entry["histories"] = {
                str(k): {str(f): c for f, c in h.items()}
                for k, h in island.histories().items()
            }
            if digests and island.keys:
                entry["digest"] = island.state_digest(self.host)
            entry["spread"] = mid in self._spread
            report[str(mid)] = entry
        return {"islands": report, "tick": self.tick_index}

    def _op_drain(self):
        """Rolling-upgrade export: quiesce, serialize EVERY co-located
        island with detach, hand the ticket back. Spread halves cannot
        ride a ticket; draining an agent that still hosts one is a
        scheduling error surfaced as typed InvalidRequest."""
        from ..errors import InvalidRequest

        if self._spread:
            raise InvalidRequest(
                f"agent hosts spread match halves {sorted(self._spread)}; "
                "finish or kill them before a rolling upgrade"
            )
        self._draining = True
        islands = list(self.islands.values())
        tails = self._capture_journal_tails(islands)
        entries = export_islands(self.host, islands, detach=True)
        self._attach_ticket_journals(entries, tails)
        blob = dumps_ticket(entries, self._ticket_meta())
        self.islands.clear()
        return {"exported": len(islands)}, blob

    # ------------------------------------------------------------------
    # crash-recovery checkpoints
    # ------------------------------------------------------------------

    def _ticket_meta(self) -> dict:
        return {
            "host_id": self.host_id,
            "epoch": self.epoch,
            "tick": self.tick_index,
            "frames": {
                str(mid): {str(k): v for k, v in i.frames().items()}
                for mid, i in self.islands.items()
            },
        }

    def checkpoint_path(self) -> str:
        return os.path.join(self.base_dir, f"host{self.host_id}.ckpt")

    def write_checkpoint(self) -> Optional[str]:
        """Atomic fleet ticket of every co-located island (detach=False:
        the host keeps serving). Spread halves are excluded — they
        cannot be restored without their sibling's consent. Fenced or
        terminated agents never write: a zombie's checkpoint must not
        exist, and the director's seize-at-fence ignores late ones."""
        if self.terminated is not None or self.host_id is None:
            return None
        islands = [
            i for mid, i in self.islands.items()
            if mid not in self._spread and i.keys and not i.failed
        ]
        if not islands and self.last_checkpoint is None:
            return None  # nothing to cover and nothing stale to retract
        # an EMPTY ticket is meaningful: it retracts matches a previous
        # checkpoint covered that have since been exported or released
        entries = export_islands(self.host, islands, detach=False)
        meta = self._ticket_meta()
        path = self.checkpoint_path()
        # durable=False: os.replace already makes SIGKILL-torn files
        # impossible, and an fsync stall at this cadence starves the
        # heartbeat loop into a false suspicion
        atomic_write_bytes(
            path, dumps_ticket(entries, meta), durable=False
        )
        self.last_checkpoint = {
            "path": path, "tick": self.tick_index,
            "frames": meta["frames"],
        }
        self.checkpoints_written += 1
        return path


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    import time

    parser = argparse.ArgumentParser(
        description="ggrs fleet agent: one SessionHost behind a director"
    )
    parser.add_argument("--director", required=True,
                        help="host:port of the director's control socket")
    parser.add_argument("--base-dir", default=".")
    parser.add_argument("--label", default="")
    parser.add_argument("--players", type=int, default=4)
    parser.add_argument("--entities", type=int, default=8)
    parser.add_argument("--max-sessions", type=int, default=16)
    parser.add_argument("--max-prediction", type=int, default=8)
    parser.add_argument("--hb-interval-ms", type=int, default=150)
    parser.add_argument("--checkpoint-every", type=int, default=32)
    parser.add_argument("--tick-interval-ms", type=float, default=4.0,
                        help="real-time pacing of the island frame loop")
    parser.add_argument("--warmup", action="store_true")
    parser.add_argument("--no-journal", action="store_true",
                        help="disable the durable per-match input journal")
    parser.add_argument("--journal-fsync-every", type=int, default=0)
    parser.add_argument("--platform", default=None,
                        help="force a jax platform (the multi-process "
                        "launcher runs agents on cpu: a chip belongs to "
                        "one process)")
    args = parser.parse_args(argv)

    if args.platform:
        os.environ.setdefault("JAX_PLATFORMS", args.platform)
        import jax

        jax.config.update("jax_platforms", args.platform)
    from ..utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from ..models.ex_game import ExGame
    from .wire import connect

    game = ExGame(num_players=args.players, num_entities=args.entities)
    core = AgentCore(
        game,
        base_dir=args.base_dir,
        max_sessions=args.max_sessions,
        max_prediction=args.max_prediction,
        num_players=args.players,
        hb_interval_ms=args.hb_interval_ms,
        checkpoint_every=args.checkpoint_every,
        warmup=args.warmup,
        label=args.label,
        journal=not args.no_journal,
        journal_fsync_every=args.journal_fsync_every,
    )
    host, _, port = args.director.rpartition(":")
    core.attach_conn(connect((host or "127.0.0.1", int(port))))
    core.start()
    print(f"[agent {args.label}] pid={os.getpid()} connected to "
          f"{args.director}", flush=True)
    interval_s = args.tick_interval_ms / 1000.0
    last_report = time.monotonic()
    was_registered = False
    while core.terminated is None:
        t0 = time.monotonic()
        core.step()
        if core.registered and not was_registered:
            was_registered = True
            print(f"[agent {args.label}] registered host_id="
                  f"{core.host_id} epoch={core.epoch}", flush=True)
        step_ms = (time.monotonic() - t0) * 1000.0
        if step_ms > 250:
            print(f"[agent {args.label}] SLOW step {step_ms:.0f}ms at "
                  f"tick={core.tick_index}", flush=True)
        if time.monotonic() - last_report > 2.0:
            last_report = time.monotonic()
            host = core.host
            print(f"[agent {args.label}] tick={core.tick_index} "
                  f"islands={sorted(core.islands)} "
                  f"sync={[(m, i.synced, i.cursor, i.done) for m, i in sorted(core.islands.items())]} "
                  f"gc={host.sessions_gced} evict={host.sessions_evicted} "
                  f"ckpts={core.checkpoints_written}", flush=True)
        if core.peer.conn.closed:
            # the director is gone for good (socket-level close, not a
            # partition): keep serving the data plane until the matches
            # finish, then exit — sessions outrank the control plane
            # (quarantined islands count as finished: they will never
            # tick again, and waiting on them would leak this process)
            if all(i.done or i.failed for i in core.islands.values()):
                core.terminated = "orphaned"
                break
        delay = interval_s - (time.monotonic() - t0)
        if delay > 0:
            time.sleep(delay)
    print(f"[agent {args.label}] terminated: {core.terminated} "
          f"(tick={core.tick_index})", flush=True)
    return FENCED_EXIT_CODE if core.terminated == "fenced" else 0


if __name__ == "__main__":  # pragma: no cover - process entry
    raise SystemExit(main())
