"""Fleet operations: live session migration, over-admission spillover,
host kill→restore — the multi-host story on top of SessionHost.

Live migration moves ONE mid-match session between two SessionHosts with
remote peers none the wiser: the source host flushes the session's staged
rows through its fence, exports the session's complete device residue
(live world + snapshot ring, `MultiSessionDeviceCore.export_slot`) into a
`MigrationTicket` together with the lane bookkeeping, and detaches; the
destination imports the slot bytes (`import_slot`, validated shape by
shape) and adopts the session at its exact frame. The session OBJECT —
protocol endpoints, input queues, pending checksum reports — rides the
ticket: its reliability state is the thing that makes the move invisible,
because peers keep talking to the same endpoint state machine at the same
address. Datagrams that arrive during the handoff wait in the socket and
REPLAY through the ordinary receive path on the first post-adoption pump,
so the peers observe one tick of extra jitter, not a resync.

`HostGroup` stacks policy on the same handoff: admission spillover
(HostFull on one host routes the attach to a sibling, bounded
retry/backoff, typed `GroupSaturated` when the whole group is full),
load-shedding migration, and kill→restore (a dying host's emergency
drain→checkpoint rebuilds as a fresh host via `load_stacked`, every
surviving session re-adopted AT ITS OLD SLOT with endpoint timers rebased
so the blackout cannot fire spurious disconnects).

The degradation ladder, in order of increasing violence: backpressure
(queue on the per-tick row budget) → spillover (sibling host) → evict
(idle/disconnect GC) → drain (graceful, checkpointed). docs/DESIGN.md
"Fleet operations" has the full handshake diagram.
"""

from __future__ import annotations

import random as _random
import time as _time
from typing import Any, Dict, List, Optional, Tuple

from ..errors import (
    CheckpointIncompatible,
    GroupSaturated,
    HarvestTimeout,
    HostFull,
    InvalidRequest,
)
from ..obs import GLOBAL_TELEMETRY, LOG2_BUCKETS_MS
from .host import SessionHost


def migrations_total():
    """Get-or-create THE migration counter — one definition shared by
    migrate_session and the smoke/bench gates that assert on it."""
    return GLOBAL_TELEMETRY.registry.counter(
        "ggrs_migrations_total",
        "live sessions handed between SessionHosts (export+import pairs)",
    )


def migration_ms_histogram():
    return GLOBAL_TELEMETRY.registry.histogram(
        "ggrs_migration_ms",
        "wall-clock cost of one live migration "
        "(fence flush + slot export + slot import + adoption)",
        buckets=LOG2_BUCKETS_MS,
    )


def spillovers_total():
    return GLOBAL_TELEMETRY.registry.counter(
        "ggrs_group_spillovers_total",
        "admissions a HostGroup routed past a full first-choice host",
    )


def saturations_total():
    return GLOBAL_TELEMETRY.registry.counter(
        "ggrs_group_saturated_total",
        "admissions the whole HostGroup rejected after retry/backoff",
    )


class MigrationTicket:
    """Everything one live session needs to resume on another host: the
    session object (protocol/endpoint/input-queue state travels by
    reference — it IS the continuity the peers observe), the exported
    device slot bytes, and the lane bookkeeping. `slot_state=None` marks
    a restore-from-checkpoint ticket: the destination's stacked worlds
    already hold the bytes at `slot`. `input_stats` carries the source
    lane's learned input-model statistics by value (None when the source
    was not speculating) so speculation resumes warm on the destination
    instead of relearning every player's habits from zero."""

    __slots__ = ("session", "key", "slot", "current_frame",
                 "pending_inputs", "slot_state", "input_stats")

    def __init__(self, session, key, slot, current_frame,
                 pending_inputs, slot_state, input_stats=None):
        self.session = session
        self.key = key
        self.slot = slot
        self.current_frame = current_frame
        self.pending_inputs = frozenset(pending_inputs)
        self.slot_state = slot_state
        self.input_stats = input_stats


def _resume_endpoints(session, now_ms: int) -> None:
    """Rebase every endpoint's receive baseline after a handoff pause so
    a blackout the session itself caused (migration, host kill) cannot
    fire a spurious disconnect before the peers' backlog replays."""
    reg = getattr(session, "player_reg", None)
    endpoints = (
        list(reg.remotes.values()) + list(reg.spectators.values())
        if reg is not None
        else [session.host]  # spectator session: one host endpoint
    )
    for ep in endpoints:
        resume = getattr(ep, "resume_after_pause", None)
        if callable(resume):
            resume(now_ms)


def export_session(host: SessionHost, key: Any) -> MigrationTicket:
    """Checkpoint one live session out of `host`: flush its staged rows
    through the fence, copy its slot's world+ring to host memory, detach.
    The session stops being pumped the moment this returns — import it
    promptly (peers tolerate a pause well under their disconnect
    timeout, observing it as ordinary jitter)."""
    lane = host._lanes.get(key)
    if lane is None:
        raise InvalidRequest(f"unknown host key {key!r}")
    if lane.rows:
        # the staged rows must land on device BEFORE the export reads the
        # slot, or the exported world is behind lane.current_frame
        host._flush_ready(f"migration export of {key!r}")
    seam = getattr(host, "fault_seam", None)
    for attempt in (0, 1):
        try:
            if seam is not None:
                seam.before_harvest("migration_export")
            slot_state = host.device.export_slot(lane.slot)
            break
        except HarvestTimeout:
            # transient readback stall: the residue still exists on
            # device — block the fence and retry once, so the export
            # either completes whole or surfaces typed (never a
            # half-copied slot riding a ticket)
            host.harvest_timeouts += 1
            if attempt:
                raise
            host.device.block_until_ready()
    ticket = MigrationTicket(
        lane.session, key, lane.slot, lane.current_frame,
        set(lane.pending_inputs), slot_state,
        host.export_input_model_state(key),  # before detach drops the lane
    )
    host.detach(key)
    if GLOBAL_TELEMETRY.enabled:
        GLOBAL_TELEMETRY.record(
            "session_exported", key=str(key), slot=lane.slot,
            frame=lane.current_frame,
        )
    return ticket


def import_session(host: SessionHost, ticket: MigrationTicket, *,
                   key: Any = None, slot: Optional[int] = None) -> Any:
    """Adopt an exported session into `host` and resume it: slot bytes
    imported (or, for a restore ticket, claimed in place), lane resumed
    at the exact frame, endpoint timers rebased. The next host tick pumps
    the backlog that queued at the session's socket during the handoff —
    the input-queue replay that makes the move invisible to peers."""
    if slot is None and ticket.slot_state is None:
        slot = ticket.slot  # restore path: the worlds are already there
    new_key = host.adopt(
        ticket.session,
        current_frame=ticket.current_frame,
        slot_state=ticket.slot_state,
        pending_inputs=ticket.pending_inputs,
        key=key,
        slot=slot,
    )
    if ticket.input_stats is not None:
        # warm the destination's speculation lane; an incompatible or
        # absent planner degrades to a cold start, never a failed import
        host.import_input_model_state(new_key, ticket.input_stats)
    _resume_endpoints(ticket.session, host.clock.now_ms())
    return new_key


def migrate_session(src: SessionHost, dst: SessionHost, key: Any, *,
                    key_on_dst: Any = None) -> Any:
    """THE one-call live migration: export from `src`, import into `dst`,
    returns the session's key on `dst`. On an import failure (dst full /
    incompatible) the session is re-imported into `src` — a failed
    migration must degrade to 'nothing happened', never to a lost
    session — and the original error re-raises."""
    t0 = _time.perf_counter()
    ticket = export_session(src, key)
    try:
        new_key = import_session(dst, ticket, key=key_on_dst)
    except BaseException:
        import_session(src, ticket, key=key)  # roll back onto the source
        raise
    if GLOBAL_TELEMETRY.enabled:
        migrations_total().inc()
        migration_ms_histogram().observe(
            (_time.perf_counter() - t0) * 1000.0
        )
        GLOBAL_TELEMETRY.record(
            "session_migrated", key=str(key), to_key=str(new_key),
            frame=ticket.current_frame,
        )
    return new_key


class _GroupRecord:
    __slots__ = ("host_idx", "hkey", "session", "suspended_slot",
                 "suspended_frame", "suspended_inputs")

    def __init__(self, host_idx, hkey, session):
        self.host_idx = host_idx
        self.hkey = hkey
        self.session = session
        self.suspended_slot = None
        self.suspended_frame = None
        self.suspended_inputs = ()


class HostGroup:
    """N SessionHosts behind one admission/handoff policy. Group keys are
    stable across migrations and kill→restore cycles, so a driver
    (loadgen, chaos harness) addresses sessions without tracking which
    host currently owns them. Duck-types the slice of the SessionHost
    surface the loadgen helpers use (attach / submit_input / tick /
    session / keys / num_players / game / clock).

    Admission: `attach` tries hosts least-loaded first; HostFull routes
    to the next sibling (SPILLOVER); when every host rejects, the group
    backs off — advancing the injectable clock and ticking the fleet so
    eviction/GC can free slots — and retries up to `max_attempts` before
    raising the typed, terminal `GroupSaturated` with a per-host
    occupancy map."""

    def __init__(self, hosts: List[SessionHost], *,
                 clock=None, host_factory=None,
                 max_attempts: int = 3, backoff_ms: int = 32,
                 backoff_seed: int = 0):
        if not hosts:
            raise InvalidRequest("a HostGroup needs at least one host")
        self.hosts = list(hosts)
        self.clock = clock or hosts[0].clock
        self._host_factory = host_factory
        self.max_attempts = max_attempts
        self.backoff_ms = backoff_ms
        # seeded jitter source for the admission backoff: a FIXED
        # exponential schedule synchronizes every rejected admission in a
        # flash crowd onto the same retry instants (a retry storm that
        # re-collides forever); jitter decorrelates them, the seed keeps
        # a soak bit-reproducible
        self._backoff_rng = _random.Random(backoff_seed ^ 0xB0FF)
        self.dead: set = set()
        self._records: Dict[Any, _GroupRecord] = {}
        self._by_host: List[Dict[Any, Any]] = [dict() for _ in self.hosts]
        self._next_gkey = 0
        self._pending_events: Dict[Any, List[Any]] = {}
        self._kill_tickets: Dict[int, List[MigrationTicket]] = {}
        # lifetime stats (the group section of chaos reports)
        self.migrations = 0
        self.spillovers = 0
        self.saturations = 0
        self.kills = 0
        self.restores = 0
        self.evictions_seen = 0
        self.inputs_dropped = 0

    @classmethod
    def build(cls, game, n_hosts: int, *, clock=None,
              max_attempts: int = 3, backoff_ms: int = 32,
              backoff_seed: int = 0, **host_kw) -> "HostGroup":
        """Construct `n_hosts` identically-configured SessionHosts plus
        the factory kill→restore needs to rebuild one."""
        factory = lambda: SessionHost(game, clock=clock, **host_kw)  # noqa: E731
        hosts = [factory() for _ in range(n_hosts)]
        return cls(
            hosts, clock=clock, host_factory=factory,
            max_attempts=max_attempts, backoff_ms=backoff_ms,
            backoff_seed=backoff_seed,
        )

    # ------------------------------------------------------------------
    # loadgen-facing surface (duck-types SessionHost)
    # ------------------------------------------------------------------

    @property
    def num_players(self) -> int:
        return self.hosts[0].num_players

    @property
    def game(self):
        return self.hosts[0].game

    @property
    def active_sessions(self) -> int:
        return len(self._records)

    def keys(self) -> List[Any]:
        return list(self._records)

    def keys_on(self, host_idx: int) -> List[Any]:
        return [
            g for g, r in self._records.items() if r.host_idx == host_idx
        ]

    def session(self, gkey: Any):
        return self._records[gkey].session

    def host_of(self, gkey: Any) -> Optional[int]:
        return self._records[gkey].host_idx

    # ------------------------------------------------------------------
    # admission: spillover + bounded retry/backoff
    # ------------------------------------------------------------------

    def _alive(self) -> List[int]:
        return [i for i in range(len(self.hosts)) if i not in self.dead]

    def _occupancy(self) -> Dict[str, str]:
        return {
            f"host{i}": (
                "dead" if i in self.dead else
                f"{self.hosts[i].active_sessions}"
                f"/{self.hosts[i].max_sessions}"
            )
            for i in range(len(self.hosts))
        }

    def _register(self, host_idx: int, hkey: Any, session) -> Any:
        gkey = self._next_gkey
        self._next_gkey += 1
        self._records[gkey] = _GroupRecord(host_idx, hkey, session)
        self._by_host[host_idx][hkey] = gkey
        return gkey

    def attach(self, session) -> Any:
        attempts = 0
        for attempt in range(self.max_attempts):
            order = sorted(
                self._alive(),
                key=lambda i: self.hosts[i].active_sessions,
            )
            for rank, i in enumerate(order):
                attempts += 1
                try:
                    hkey = self.hosts[i].attach(session)
                except HostFull:
                    continue
                if rank > 0 or attempt > 0:
                    self.spillovers += 1
                    if GLOBAL_TELEMETRY.enabled:
                        spillovers_total().inc()
                        GLOBAL_TELEMETRY.record(
                            "group_spillover", host=i, attempt=attempt
                        )
                return self._register(i, hkey, session)
            if attempt + 1 < self.max_attempts:
                self._backoff(attempt)
        self.saturations += 1
        if GLOBAL_TELEMETRY.enabled:
            saturations_total().inc()
            GLOBAL_TELEMETRY.record(
                "group_saturated", attempts=attempts
            )
        raise GroupSaturated(
            f"every host in the group rejected the admission "
            f"({self._occupancy()})",
            attempts=attempts, per_host=self._occupancy(),
        )

    def backoff_delay_ms(self, attempt: int) -> int:
        """One jittered exponential backoff draw: uniform over
        [base/2, base] with base = backoff_ms << attempt. Exposed (and
        consumed in draw order) so a unit test can pin the exact retry
        schedule a seed produces."""
        base = self.backoff_ms << attempt
        return self._backoff_rng.randrange(base // 2, base + 1)

    def _backoff(self, attempt: int) -> None:
        """Between admission attempts: give eviction/disconnect GC a
        chance to free slots — tick the fleet and advance the injectable
        clock by a seeded-jittered exponential delay (backoff_delay_ms).
        Events surfaced by the backoff ticks are buffered into the next
        tick() result, not dropped."""
        advance = getattr(self.clock, "advance", None)
        if callable(advance):
            advance(self.backoff_delay_ms(attempt))
        for gkey, evs in self.tick().items():
            self._pending_events.setdefault(gkey, []).extend(evs)

    # ------------------------------------------------------------------
    # driving
    # ------------------------------------------------------------------

    def submit_input(self, gkey: Any, handle, buf: bytes) -> bool:
        """Route one local input to whichever host owns the session now.
        Inputs for a suspended (killed-host) or evicted session are
        DROPPED and counted — exactly what a user disconnected from a
        dead host experiences — never an exception in the drive loop."""
        rec = self._records.get(gkey)
        if rec is None or rec.host_idx is None:
            self.inputs_dropped += 1
            return False
        if rec.session.host_key is None:  # evicted since last tick
            self._forget(gkey)
            self.inputs_dropped += 1
            return False
        self.hosts[rec.host_idx].submit_input(rec.hkey, handle, buf)
        return True

    def tick(self) -> Dict[Any, List[Any]]:
        """Tick every alive host; returns events keyed by GROUP key.
        Reconciles evictions (disconnect GC / idle timeout on a member
        host) into the group's own bookkeeping."""
        merged: Dict[Any, List[Any]] = {}
        if self._pending_events:
            merged, self._pending_events = self._pending_events, {}
        for i in self._alive():
            for hkey, evs in self.hosts[i].tick().items():
                gkey = self._by_host[i].get(hkey)
                merged.setdefault(
                    gkey if gkey is not None else ("host", i, hkey), []
                ).extend(evs)
        for gkey, rec in list(self._records.items()):
            if rec.host_idx is not None and rec.session.host_key is None:
                self._forget(gkey)
                self.evictions_seen += 1
        return merged

    def _forget(self, gkey: Any) -> None:
        rec = self._records.pop(gkey, None)
        if rec is not None and rec.host_idx is not None:
            self._by_host[rec.host_idx].pop(rec.hkey, None)

    def detach(self, gkey: Any) -> None:
        """Remove a session from whichever host owns it and drop the
        group record (the group-level twin of SessionHost.detach)."""
        rec = self._records.get(gkey)
        if rec is None:
            raise InvalidRequest(f"unknown group key {gkey!r}")
        if rec.host_idx is not None and rec.session.host_key is not None:
            self.hosts[rec.host_idx].detach(rec.hkey)
        self._forget(gkey)

    # ------------------------------------------------------------------
    # load shedding: migration + drain-to-siblings
    # ------------------------------------------------------------------

    def pick_migration_target(self, src_idx: int) -> Optional[int]:
        """Least-loaded alive sibling with a free slot, or None."""
        candidates = [
            i for i in self._alive()
            if i != src_idx and self.hosts[i]._free_slots
            and not self.hosts[i].draining
        ]
        if not candidates:
            return None
        return min(candidates, key=lambda i: self.hosts[i].active_sessions)

    def migrate(self, gkey: Any, to: Optional[int] = None) -> int:
        """Live-migrate one session to `to` (default: the least-loaded
        sibling). Returns the destination host index; raises HostFull
        when no sibling can take it (the caller decides whether that is
        terminal — the chaos harness just skips the event)."""
        rec = self._records[gkey]
        if rec.host_idx is None:
            raise InvalidRequest(
                f"session {gkey!r} is suspended (its host was killed)"
            )
        dst_idx = to if to is not None else (
            self.pick_migration_target(rec.host_idx)
        )
        if dst_idx is None:
            raise HostFull("no sibling host can absorb the migration")
        new_hkey = migrate_session(
            self.hosts[rec.host_idx], self.hosts[dst_idx], rec.hkey
        )
        self._by_host[rec.host_idx].pop(rec.hkey, None)
        rec.host_idx, rec.hkey = dst_idx, new_hkey
        self._by_host[dst_idx][new_hkey] = gkey
        self.migrations += 1
        return dst_idx

    def drain_host(self, host_idx: int,
                   checkpoint_path: Optional[str] = None) -> dict:
        """Evict a host from service the GRACEFUL way: live-migrate every
        session to siblings via the same handoff path admissions spill
        through (GroupSaturated if they cannot fit), then drain the empty
        host. The 'scale down one host' operation."""
        for gkey in self.keys_on(host_idx):
            try:
                self.migrate(gkey)
            except HostFull:
                self.saturations += 1
                if GLOBAL_TELEMETRY.enabled:
                    saturations_total().inc()
                raise GroupSaturated(
                    f"draining host{host_idx}: no sibling capacity for "
                    f"session {gkey!r} ({self._occupancy()})",
                    per_host=self._occupancy(),
                ) from None
        summary = self.hosts[host_idx].drain(checkpoint_path)
        self.dead.add(host_idx)
        return summary

    # ------------------------------------------------------------------
    # kill -> restore-from-checkpoint
    # ------------------------------------------------------------------

    def kill_host(self, host_idx: int, checkpoint_path: str) -> int:
        """A host 'dies': its shutdown handler manages one emergency
        drain→checkpoint (staged rows flushed, stacked worlds written to
        `checkpoint_path`), then the process is gone. Sessions are
        suspended — not pumped, not advanced, their inputs dropped —
        until restore_host() brings the host back. Returns the number of
        suspended sessions."""
        if host_idx in self.dead:
            raise InvalidRequest(
                f"kill_host({host_idx}): host is already dead"
            )
        host = self.hosts[host_idx]
        host.drain(checkpoint_path)
        tickets: List[MigrationTicket] = []
        for gkey in self.keys_on(host_idx):
            rec = self._records[gkey]
            lane = host._lanes[rec.hkey]
            tickets.append(MigrationTicket(
                rec.session, rec.hkey, lane.slot, lane.current_frame,
                set(lane.pending_inputs), None,  # bytes live in the file
            ))
            host.detach(rec.hkey)
            self._by_host[host_idx].pop(rec.hkey, None)
            rec.host_idx = None  # suspended
        self._kill_tickets[host_idx] = tickets
        self.dead.add(host_idx)
        self.kills += 1
        if GLOBAL_TELEMETRY.enabled:
            GLOBAL_TELEMETRY.record(
                "host_killed", host=host_idx, sessions=len(tickets),
                checkpoint=str(checkpoint_path),
            )
        return len(tickets)

    def restore_host(self, host_idx: int, checkpoint_path: str) -> int:
        """Rebuild a killed host from its checkpoint: fresh SessionHost
        from the factory, stacked worlds loaded back in one pass
        (`load_stacked`), every suspended session re-adopted AT ITS OLD
        SLOT with endpoint timers rebased — so the peers' backlog replays
        on the next tick instead of tripping disconnect detection.
        Returns the number of resumed sessions."""
        from ..utils.checkpoint import load_device_checkpoint

        if host_idx not in self.dead:
            raise InvalidRequest(
                f"restore_host({host_idx}): host was never killed"
            )
        if self._host_factory is None:
            raise InvalidRequest(
                "restore_host needs a host_factory (build the group via "
                "HostGroup.build, or pass host_factory=)"
            )
        host = self._host_factory()
        tree, meta = load_device_checkpoint(checkpoint_path)
        for key, want in (
            ("kind", "MultiSessionDeviceCore"),
            ("capacity", host.device.capacity),
            ("num_players", host.num_players),
            ("max_prediction", host.max_prediction),
        ):
            if meta.get(key) != want:
                raise CheckpointIncompatible(
                    f"checkpoint {checkpoint_path!r} {key} does not match "
                    "the replacement host",
                    found=meta.get(key), expected=want,
                )
        host.device.load_stacked(tree["rings"], tree["states"])
        tickets = self._kill_tickets.pop(host_idx, [])
        self.hosts[host_idx] = host
        self.dead.discard(host_idx)
        for ticket in tickets:
            # import_session rebases the endpoint timers too
            hkey = import_session(host, ticket, key=ticket.key)
            gkey = None
            for g, rec in self._records.items():
                if rec.session is ticket.session:
                    gkey = g
                    rec.host_idx, rec.hkey = host_idx, hkey
                    break
            self._by_host[host_idx][hkey] = gkey
        self.restores += 1
        if GLOBAL_TELEMETRY.enabled:
            GLOBAL_TELEMETRY.record(
                "host_restored", host=host_idx, sessions=len(tickets),
            )
        return len(tickets)

    # ------------------------------------------------------------------
    # reporting
    # ------------------------------------------------------------------

    def queue_waits(self) -> List[int]:
        """Every member host's plain queue-wait samples, pooled."""
        out: List[int] = []
        for host in self.hosts:
            out.extend(host.queue_waits)
        return out

    def group_section(self) -> dict:
        return {
            "hosts": len(self.hosts),
            "dead": sorted(self.dead),
            "sessions": len(self._records),
            "occupancy": self._occupancy(),
            "migrations": self.migrations,
            "spillovers": self.spillovers,
            "saturations": self.saturations,
            "kills": self.kills,
            "restores": self.restores,
            "evictions_seen": self.evictions_seen,
            "inputs_dropped": self.inputs_dropped,
        }
