"""SessionHost: N concurrent sessions multiplexed onto ONE shared device
core via cross-session continuous batching.

The single-session stack leaves the device idle whenever its one session
waits on remote input; a serving process cannot afford that. The host owns
many sessions (P2P and spectator), pumps all of their sockets every host
tick, and coalesces every session whose `advance_frame` produced work into
one fused cross-session MEGABATCH dispatch on a
`ggrs_tpu.tpu.backend.MultiSessionDeviceCore` — each session world is one
slot of a stacked device pytree, each session tick one packed control row,
and the whole fleet's tick is one gather → vmapped-tick → scatter program
behind the PR 1 async fence. Rows are data, so a freshly attached session,
a mid-rollback session and a quiet session all ride the same cached
program; megabatch row counts pad to a small set of bucket sizes so the
jit cache stays bounded no matter how the fleet churns. The scheduler
additionally groups ready rows by ROLLBACK DEPTH (depth-adaptive
dispatch): zero-rollback ticks — the dominant traffic — ride a dedicated
fast program that skips the ring gather/scatter and the resim scan
outright, and rollback rows ride windowed programs sized to their depth
bucket, so one deep rollback never drags the whole fleet's rows to the
full window (docs/DESIGN.md "Depth-adaptive dispatch").

Lifecycle: admission control (`max_sessions`, typed HostFull rejection),
idle-session eviction and disconnect GC driven by the injectable Clock,
and graceful drain (stop admitting, flush the fence, checkpoint the
stacked worlds via utils/checkpoint). Backpressure: when the device
window is full (`max_inflight_rows`), ready sessions queue in arrival
order and the host reports queue depth.

Telemetry rides the PR 2 obs registry: sessions active/evicted/rejected,
megabatch-size histogram, cross-session occupancy, admission-queue wait
histogram — one `host.telemetry()` snapshot folds them in with every
hosted session's own section.

`resident=True` retires even the one-dispatch-per-tick cadence: staged
rows feed a device-resident input mailbox (tpu/mailbox.py) and a jitted
`lax.while_loop` virtual-tick driver consumes up to `resident_ticks` of
them per single dispatch — the host demoted to an async feeder
(pump → mailbox write → driver dispatch → lazy harvest), bit-identical
to the dispatch-per-tick twin (docs/DESIGN.md "Device-resident serving
loop").
"""

from __future__ import annotations

import os
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..analysis.sanitize import active_alloc_sanitizer
from ..journal.wal import canonical_statuses
from ..errors import (
    ConfigError,
    DeviceDispatchFailed,
    DrainStalled,
    GGRSError,
    HarvestTimeout,
    HostFull,
    InvalidRequest,
    InvariantViolation,
    JournalError,
    JournalStalled,
    PredictionThreshold,
    SlotPoisoned,
)
from ..obs import GLOBAL_TELEMETRY, SESSION_COUNT_BUCKETS
from ..types import (
    Event,
    InputStatus,
    LoadGameState,
    PlayerHandle,
    Request,
    SessionState,
)
from ..utils.clock import Clock
from ..utils.tracing import GLOBAL_TRACER

DEFAULT_IDLE_TIMEOUT_MS = 30_000

# _drive_resident's "the drive raised and the recovery ladder ran"
# sentinel — distinct from None, which drive_mailbox legitimately
# returns for an empty mailbox
_DRIVE_FAILED = object()

# lazily-resolved backend types (importing ggrs_tpu.serve must not pull
# jax; the per-row retire path must not re-run import machinery either)
_BACKEND_REFS = None


def _backend_refs():
    global _BACKEND_REFS
    if _BACKEND_REFS is None:
        from ..tpu.backend import SnapshotRef, _LazyChecksum

        _BACKEND_REFS = (SnapshotRef, _LazyChecksum)
    return _BACKEND_REFS


def _array_is_ready(arr) -> bool:
    global _ARRAY_IS_READY
    if _ARRAY_IS_READY is None:
        from ..tpu.backend import _array_is_ready as impl

        _ARRAY_IS_READY = impl
    return _ARRAY_IS_READY(arr)


_ARRAY_IS_READY = None

# the "no env rows for this group" sentinel (shared: the dispatch loop
# must not build a (0, []) default per megabatch pass)
_NO_ENV: Tuple[int, tuple] = (0, ())


class _StagedRow:
    """One parsed request segment awaiting its megabatch: the packed
    control row plus the SaveGameState requests whose cells get their
    lazy checksums bound when the dispatch happens. `last_active` (the
    row's 1-based last active slot) and `fast` (zero-rollback fast-path
    eligibility) are the scheduler's depth-routing keys, computed once
    at parse time so grouping never rescans rows. `adopt` (None on
    ordinary rows) marks a row the verify pass matched against a
    standing speculative draft: (DraftBatch, packed adopt row) — it
    dispatches through device.adopt_slot instead of joining a megabatch
    group, serving the matched prefix from the draft trajectory."""

    __slots__ = ("row", "saves", "start_frame", "count", "last_active",
                 "fast", "adopt")

    def __init__(self, row, saves, start_frame, count, last_active, fast,
                 adopt=None):
        self.row = row
        self.saves = saves
        self.start_frame = start_frame
        self.count = count
        self.last_active = last_active
        self.fast = fast
        self.adopt = adopt


class _JournalTap:
    """One journaled lane's durable-input pipeline: a pure-observer
    InputRecorder over the lane's request stream feeding a segment WAL
    (journal/wal.py) at the confirmed frontier. Strictly host-side —
    the session is never touched, so journaling is observationally
    neutral to the match (the twin-parity suites run with it on)."""

    __slots__ = ("writer", "recorder", "path")

    def __init__(self, writer, recorder, path):
        self.writer = writer
        self.recorder = recorder
        self.path = path


class _Lane:
    """Host-side per-session state: device slot, staged rows, scheduling
    and liveness bookkeeping."""

    # a lane stages at most two rows per advance (misprediction rollback
    # + sparse-saving keepalive segments) and cannot advance again until
    # they dispatch, and a dispatched row is host-copied into the pooled
    # bucket staging before dispatch() returns — so a 4-deep rotating
    # row pool can never hand out a buffer still staged or in flight
    ROW_POOL = 4

    __slots__ = (
        "key", "session", "slot", "kind", "num_players", "local_handles",
        "max_prediction", "rows", "current_frame", "last_activity_ms",
        "pending_inputs", "queued_since_tick", "ticks_advanced",
        "throttled_ticks", "last_error", "failed", "row_pool", "row_flip",
        "starved", "confirmed_watermark",
        # invariant monitors (always-on, cheap)
        "max_confirmed_seen", "last_progress_seen", "last_progress_tick",
        "wedge_reported",
        # durable input journal (attach_journal installs; None = off)
        "journal",
        # SDC audit lane (maintained only when the host samples audits):
        # frame -> (played inputs u8[P,I], statuses i32[P]) — rollback
        # segments overwrite predicted values with the corrected truth,
        # so the record is always what the device actually played last —
        # plus the saved frames whose ring rows can anchor a replay and
        # each save's recorded (lazy) checksum, the at-rest reference
        # the audit sweep compares recomputed ring rows against
        "audit_inputs", "saved_frames", "audit_saved_checksums",
    )

    def __init__(self, key, session, slot, kind, num_players,
                 local_handles, max_prediction, now_ms, packed_len):
        self.key = key
        self.session = session
        self.slot = slot
        self.kind = kind  # "p2p" | "spectator"
        self.num_players = num_players
        self.local_handles = frozenset(local_handles)
        self.max_prediction = max_prediction
        self.rows: deque = deque()
        self.current_frame = 0
        self.last_activity_ms = now_ms
        self.pending_inputs: set = set()
        self.queued_since_tick: Optional[int] = None
        self.ticks_advanced = 0
        self.throttled_ticks = 0
        self.last_error: Optional[str] = None
        self.failed = False  # quarantined: stops advancing, app detaches
        # input starvation (the prediction-threshold gate blocked this
        # tick) + the fresh confirmed watermark the gate computed —
        # the speculative bubble-filling scheduler's draft keys
        self.starved = False
        self.journal: Optional[_JournalTap] = None
        self.confirmed_watermark: Optional[int] = None
        self.max_confirmed_seen: Optional[int] = None
        self.last_progress_seen = 0
        self.last_progress_tick = 0
        self.wedge_reported = False
        self.audit_inputs: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        self.saved_frames: set = set()
        self.audit_saved_checksums: Dict[int, Any] = {}
        # pooled packed-row buffers (pack_tick_row_into targets): staging
        # a segment allocates nothing on the steady-state path
        self.row_pool = [
            np.empty((packed_len,), dtype=np.int32)
            for _ in range(self.ROW_POOL)
        ]
        self.row_flip = 0

    def next_row_buf(self) -> np.ndarray:
        self.row_flip = (self.row_flip + 1) % len(self.row_pool)
        return self.row_pool[self.row_flip]


class SessionHost:
    """Own N sessions, one shared device core; see the module docstring.

    Usage:
        host = SessionHost(game, max_prediction=8, num_players=4,
                           max_sessions=64, clock=clock)
        key = host.attach(session)            # raises HostFull past budget
        host.submit_input(key, handle, buf)   # per local player per tick
        events = host.tick()                  # pump + schedule + megabatch
        ...
        host.drain(checkpoint_path="host.npz")

    Every session the host admits must share the host's game config (the
    stacked worlds are one pytree): same model, same input_size, and a
    player count <= the host's `num_players` layout — absent players pad
    as DISCONNECTED, which both peers of a match do identically, so
    desync detection still agrees across hosts."""

    def __init__(self, game, *, max_prediction: int = 8,
                 num_players: int = 2, max_sessions: int = 16,
                 max_inflight_rows: Optional[int] = None,
                 clock: Optional[Clock] = None,
                 idle_timeout_ms: int = DEFAULT_IDLE_TIMEOUT_MS,
                 async_inflight: int = 4, warmup: bool = False,
                 depth_routing: bool = True, batched_pump: bool = True,
                 mesh=None, speculation: bool = False,
                 speculation_seed: int = 0, resident: bool = False,
                 resident_ticks: int = 16, sdc_audit_every: int = 0,
                 wedge_limit_ticks: int = 256,
                 drive_failure_limit: int = 3,
                 shed_after_stall_ticks: int = 256,
                 strict_invariants: bool = False,
                 journal_dir: Optional[str] = None,
                 journal_fsync_every: int = 0,
                 journal_segment_bytes: int = 1 << 18):
        """`max_inflight_rows`: the device-window budget — session tick
        rows admitted past the fence before ready sessions start queuing
        (default: 2 full megabatches' worth). `idle_timeout_ms`: sessions
        with no submitted input / advanced frame for this long are
        evicted (0 disables). `warmup=True` compiles every megabatch
        bucket (the full row x depth grid under depth routing) before
        the first attach. `depth_routing=True` groups ready sessions by
        rollback depth and dispatches one megabatch per occupied depth
        bucket — zero-rollback ticks ride a dedicated fast program —
        instead of dragging every row to the full window; False pins the
        single full-window megabatch (the parity suite's reference).
        `batched_pump=True` drains the WHOLE fleet's sockets through one
        pooled batched decode pass per host tick (network/pump.py) —
        one pass per message type over the union of every session's
        datagrams — instead of N per-message `poll_remote_clients`
        loops; False pins the legacy per-session pump (the parity
        suite's reference). `async_inflight` defaults to 4 megabatches
        (was 2): a wider fence keeps the steady-state tick from ever
        blocking on the oldest dispatch while the checksum ledger drains
        off the pump pass.

        `speculation=True` turns input starvation into useful device
        work: a lane the prediction gate blocks gets a width-1 draft of
        its near future (learned input model, counter-based draws)
        rolled out on device beside the confirmed megabatch work, and
        the arriving inputs verify against the draft per frame — a full
        prefix hit serves the whole tick via one adopt dispatch instead
        of a full-window resim, a misprediction truncates to the
        longest-correct prefix (the suffix resimulates inside the same
        adopt program), a total miss falls back to the normal rollback
        path. Bitwise-identical to a speculation=False twin in every
        arrival pattern (tests/test_speculation.py pins it); requires
        the game to declare statuses_contract='disconnect-only'.
        `speculation_seed` keys the drafts' counter-based draws.

        `mesh`: a device mesh with a `session` axis
        (parallel.mesh.make_session_mesh) puts the stacked session
        worlds on the mesh via ShardedMultiSessionDeviceCore — the
        megabatch GSPMD-partitions across chips, and the scheduler adds
        slot->shard AFFINITY: admission picks slots on the least-loaded
        shard and lane packing groups each megabatch's rows by shard, so
        the dispatch's gather/scatter stays mostly shard-local instead
        of all-to-all. Everything else (sessions, envs, migration,
        checkpoints — which stay canonical and restore across layouts)
        is unchanged, and the sharded host is bit-identical to a
        single-device twin fed the same traffic.

        `resident=True` is the DEVICE-RESIDENT SERVING LOOP: the host
        becomes feed-and-harvest only. Staged session rows stop
        dispatching one megabatch per host tick; instead they append to
        a donated device-resident input mailbox (tpu/mailbox.py — one
        batched scatter per host tick), and every `resident_ticks` host
        ticks ONE jitted `lax.while_loop` virtual-tick driver dispatch
        ticks the whole fleet through its staged rows — rollbacks
        resimulating in-loop, lanes at different fill depths walking
        their own watermarks — with checksums accumulating into
        device-side [K, S, W] output rings harvested lazily behind the
        async fence. Dispatch cadence drops from >= 1 megabatch per host
        tick to ~1/K driver dispatches per tick. A lane outrunning K
        degrades to an extra dispatch (ggrs_mailbox_overflow_total),
        never a dropped input; adopts, draft launches, slot lifecycle,
        migration export, checkpoint and drain all drain the mailbox
        back to canonical form first, so every export/import,
        kill→restore and sharded↔unsharded contract survives unchanged.
        Bit-identical to a resident=False twin fed the same traffic
        (tests/test_resident_loop.py pins state, ring bytes and checksum
        histories); the dispatch-per-tick path is kept as that parity
        twin.

        DEVICE FAULT DOMAINS (docs/DESIGN.md "Device fault domains"):
        `sdc_audit_every=N` (0 = off) samples the SDC AUDIT LANE every N
        host ticks — each eligible lane's live world is double-computed
        from its last ring anchor through the full-window parity
        program and compared checksum-for-checksum; a mismatch
        quarantines the slot (typed SlotPoisoned + forensics bundle)
        within the sampling bound. A dispatch/drive raise
        (DeviceDispatchFailed — the fault seam's simulated XLA runtime
        failure, or a real one) retries once as a transient, then
        quarantines the culprit slots and re-dispatches survivors
        bit-exactly; `drive_failure_limit` LIFETIME resident-drive
        failures DEGRADE the host to its dispatch-per-tick twin instead
        of crashing (bit-identical, slower — a device whose runtime
        keeps failing is hardware-suspect, so the fallback is sticky). `shed_after_stall_ticks`
        of a wedged fence (ready queue pinned at a full device window)
        sheds admission — attach raises HostFull — until the stall
        clears. `wedge_limit_ticks` bounds the always-on invariant
        monitors (lane progress, confirmed-watermark monotonicity,
        mailbox accounting), which record typed InvariantViolations
        with forensics (`strict_invariants=True` raises them
        instead).

        DURABLE INPUT JOURNAL (docs/DESIGN.md "Durable recovery"):
        `journal_dir` journals every p2p lane's CONFIRMED input rows to
        a crash-consistent segment WAL under `journal_dir/lane<key>`
        (per-lane `attach_journal` gives a caller-chosen path — the
        fleet agent journals per match island). The tap is a pure
        observer riding the pump: each host tick drains the lane's
        confirmed frontier from an InputRecorder into the journal —
        identical traffic on both serving arms, since the staged
        request stream is arm-independent by the deterministic-publish
        contract. `journal_fsync_every` bounds power-loss exposure to N
        appends (0 = fsync at rotation/checkpoint/drain only; SIGKILL
        never loses acknowledged appends either way). Journaling is a
        durability feature, never a liveness dependency: a disk that
        refuses an append (ENOSPC) degrades THAT lane to unjournaled
        with a typed JournalStalled + invariant trip — the host keeps
        serving."""
        from ..network.pump import WirePump, host_tax_histogram
        from ..tpu.backend import MultiSessionDeviceCore

        if speculation:
            # the adopt route replays drafted frames rolled out with
            # all-CONFIRMED statuses — only correct for games whose step
            # reads statuses solely to substitute DISCONNECTED players'
            # inputs (the same contract the single-session beam enforces)
            contract = getattr(game, "statuses_contract", None)
            if contract != "disconnect-only":
                raise ConfigError(
                    "host speculation adopts drafts rolled out with "
                    "all-CONFIRMED statuses; declare statuses_contract = "
                    "'disconnect-only' on the game class to opt in "
                    f"(got {contract!r} on {type(game).__name__})"
                )
        self.mesh = mesh
        self.device = MultiSessionDeviceCore.create(
            game, max_prediction, num_players, max_sessions,
            async_inflight=async_inflight, depth_routing=depth_routing,
            mesh=mesh, speculation=speculation,
            sdc_audit=sdc_audit_every > 0,
        )
        self.depth_routing = depth_routing
        self.game = game
        self.max_sessions = max_sessions
        self.num_players = num_players
        self.max_prediction = max_prediction
        self.max_inflight_rows = (
            max_inflight_rows
            if max_inflight_rows is not None
            else 2 * max_sessions
        )
        if self.max_inflight_rows < 1:
            raise InvalidRequest(
                f"max_inflight_rows must be >= 1 "
                f"(got {self.max_inflight_rows})"
            )
        self.clock = clock or Clock()
        self.idle_timeout_ms = idle_timeout_ms
        self._lanes: Dict[Any, _Lane] = {}
        self._envs: List[Any] = []  # attached RollbackEnv blocks
        self._free_slots = list(range(max_sessions - 1, -1, -1))
        # keys with staged rows, ARRIVAL order (the backpressure queue)
        self._ready: deque = deque()
        # per-pass scratch reused across megabatch passes — the dispatch
        # loop allocates nothing per pass (ALLOC001 discipline)
        self._picked_scratch: List[Tuple[_Lane, _StagedRow]] = []
        self._adopts_scratch: List[Tuple[_Lane, _StagedRow]] = []
        self._groups_scratch: Dict[Any, List[Tuple[_Lane, _StagedRow]]] = {}
        self._draining = False
        self._drained = False
        self._tick_index = 0
        self._next_key = 0
        # lifetime stats (host section of telemetry snapshots)
        self.sessions_admitted = 0
        self.sessions_rejected = 0
        self.sessions_evicted = 0
        self.sessions_gced = 0
        self.desyncs_observed = 0
        # plain queue-wait samples (ticks a session's staged rows waited
        # before dispatch), always maintained so chaos harnesses can read
        # a p99 without telemetry; bounded so a long soak can't grow it
        self.queue_waits: List[int] = []
        _reg = GLOBAL_TELEMETRY.registry
        self._m_active = _reg.gauge(
            "ggrs_host_sessions_active", "sessions currently attached"
        )
        self._m_evicted = _reg.counter(
            "ggrs_host_sessions_evicted_total",
            "sessions evicted for idleness or disconnect GC",
        )
        self._m_rejected = _reg.counter(
            "ggrs_host_sessions_rejected_total",
            "attach attempts rejected by admission control (HostFull)",
        )
        self._m_queue_depth = _reg.gauge(
            "ggrs_host_queue_depth",
            "ready sessions waiting on the device-window budget",
        )
        self._m_queue_wait = _reg.histogram(
            "ggrs_host_queue_wait_ticks",
            "host ticks a session's staged rows waited before dispatch",
            buckets=SESSION_COUNT_BUCKETS,
        )
        # device fault domains: quarantine machinery, the sampled SDC
        # audit lane, always-on invariant monitors and the degradation
        # ladder (docs/DESIGN.md "Device fault domains")
        self.fault_seam = None  # serve/faults.py FaultInjector installs
        self._audit_every = sdc_audit_every
        self.wedge_limit_ticks = wedge_limit_ticks
        self.drive_failure_limit = drive_failure_limit
        self.shed_after_stall_ticks = shed_after_stall_ticks
        self.strict_invariants = strict_invariants
        # durable input journal (docs/DESIGN.md "Durable recovery")
        self._journal_dir = journal_dir
        self._journal_fsync_every = journal_fsync_every
        self._journal_segment_bytes = journal_segment_bytes
        self.journal_lanes_degraded = 0
        if journal_dir is not None:
            # instruments exist from construction (the exporter
            # convention), and the directory exists before the first
            # lane attaches mid-tick
            from ..journal import metrics as _jm

            _jm.journal_rows_total()
            _jm.journal_bytes_total()
            _jm.journal_segments_total()
            _jm.journal_fsyncs_total()
            _jm.journal_stalls_total()
            _jm.journal_corrupt_segments_total()
            os.makedirs(journal_dir, exist_ok=True)
        self._quarantines: List[SlotPoisoned] = []
        self.quarantines_total = 0
        self.device_faults = 0
        self.harvest_timeouts = 0
        self.invariant_trips: List[InvariantViolation] = []
        self._pending_audits: List[Tuple[Any, List[Tuple]]] = []
        self.audits_sampled = 0
        self.audit_mismatches = 0
        self._resident_degraded = False
        self._drive_failures = 0
        self._shed_admission = False
        self._stall_ticks = 0
        self.degrades = 0
        self._m_quarantines = _reg.counter(
            "ggrs_slot_quarantines_total",
            "session slots quarantined out of the shared device stack "
            "(typed SlotPoisoned + forensics bundle each)",
            ("reason",),
        )
        self._m_sdc_audits = _reg.counter(
            "ggrs_sdc_audits_total",
            "lanes double-computed by the sampled SDC audit lane",
        )
        self._m_sdc_mismatches = _reg.counter(
            "ggrs_sdc_mismatches_total",
            "SDC audit mismatches (silent corruption caught: live world "
            "vs full-window reference replay from the ring anchor)",
        )
        self._m_degraded = _reg.counter(
            "ggrs_degraded_mode_total",
            "degradation-ladder steps taken (resident loop falling back "
            "to dispatch-per-tick, admission shed under a fence stall)",
            ("mode",),
        )
        self._m_invariants = _reg.counter(
            "ggrs_invariant_trips_total",
            "always-on invariant monitor trips (typed InvariantViolation "
            "+ forensics bundle each)",
            ("invariant",),
        )
        self._m_spectator_starved = _reg.counter(
            "ggrs_spectator_starved_total",
            "spectator lane ticks that could not advance: the host "
            "peer's input for the next frame had not arrived",
        )
        # fleet-wide batched wire pump + host-tax attribution (the pump
        # phase's own child is observed inside WirePump.pump; the shared
        # instrument is defined once, in network/pump.py)
        self.batched_pump = batched_pump
        self._pump = WirePump()
        self._m_tax_parse = host_tax_histogram().labels("parse")
        self._m_tax_drain = host_tax_histogram().labels("drain")
        # speculative bubble-filling (serve/speculation.py): when the
        # prediction gate starves a lane, the scheduler drafts its near
        # future from the lane's learned input model into the megabatch
        # and serves the arrival rollback from the draft (verify-and-
        # adopt) — bitwise-identical to a never-speculating twin in
        # every arrival pattern. Off by default; the parity suite's
        # reference arm is a speculation=False host.
        self.speculation = speculation
        if speculation:
            from .speculation import SpeculationPlanner

            core = self.device.core
            self._spec = SpeculationPlanner(
                num_players=num_players,
                input_size=game.input_size,
                window=core.window,
                ring_len=core.ring_len,
                max_prediction=max_prediction,
                seed=speculation_seed,
            )
        else:
            self._spec = None
        # pooled draft-row buffers, grown to device capacity on first use
        self._draft_row_pool: List[np.ndarray] = []
        # device-resident serving loop: attach the input mailbox BEFORE
        # warmup so the driver variants compile with the megabatch grid
        self.resident = resident
        self.resident_ticks = resident_ticks
        self._mbox_ticks = 0  # host ticks since the last driver dispatch
        # effective drive cadence: starts at resident_ticks and tightens
        # as lanes with desync detection attach (_commit_lane) — a drive
        # must land BEFORE each lane's interval-forced checksum flush, or
        # the flush forces a synchronous mid-advance drive and the
        # harvest stops overlapping host work
        self._resident_cadence = resident_ticks
        if resident:
            if resident_ticks < 1:
                raise InvalidRequest(
                    f"resident_ticks must be >= 1 (got {resident_ticks})"
                )
            self.device.attach_mailbox(resident_ticks)
        if warmup:
            self.device.warmup()

    # ------------------------------------------------------------------
    # admission / lifecycle
    # ------------------------------------------------------------------

    def _validate_session(self, session):
        """The admission checks attach() and adopt() share: session type,
        player-layout fit, input size, prediction window. Validates
        EVERYTHING the staging path will assume, so an incompatible
        session is rejected here with a clear error instead of crashing
        tick() for the whole fleet later. Returns the lane parameters
        (kind, n_players, local_handles, max_prediction)."""
        from ..sessions.p2p_session import P2PSession
        from ..sessions.spectator_session import SpectatorSession

        if isinstance(session, P2PSession):
            kind = "p2p"
        elif isinstance(session, SpectatorSession):
            kind = "spectator"
        else:
            raise InvalidRequest(
                "only Python P2PSession/SpectatorSession can be hosted "
                f"(got {type(session).__name__}; native sessions drive "
                "their own core)"
            )
        n_players = session.num_players
        if n_players > self.num_players:
            raise InvalidRequest(
                f"session has {n_players} players; host layout is "
                f"{self.num_players}"
            )
        if session.input_size != self.game.input_size:
            raise InvalidRequest(
                f"session input_size {session.input_size} != game "
                f"input_size {self.game.input_size}"
            )
        if kind == "p2p":
            if session.max_prediction > self.max_prediction:
                raise InvalidRequest(
                    f"session max_prediction {session.max_prediction} "
                    f"exceeds the host window ({self.max_prediction})"
                )
            local_handles = session.local_player_handles()
            max_prediction = session.max_prediction
        else:
            local_handles = []
            max_prediction = self.max_prediction
        return kind, n_players, local_handles, max_prediction

    def _claim_admission(self, key: Any, slot: Optional[int]):
        """Admission-control gate shared by attach() and adopt(): raises
        HostFull (draining / out of slots), resolves the key, and claims
        a device slot — the requested one for a checkpoint-restore
        re-adoption, else the free-list head."""
        if self._draining:
            self._reject()
            raise HostFull("host is draining: not admitting sessions")
        if self._shed_admission:
            # degradation ladder: a wedged fence sheds new admissions
            # BEFORE the backlog wedges the hosted fleet
            self._reject()
            raise HostFull(
                "host is shedding admission: device fence stalled for "
                f"{self._stall_ticks} ticks at a full inflight window"
            )
        if not self._free_slots:
            self._reject()
            raise HostFull(
                f"host is at max_sessions={self.max_sessions}"
            )
        if key is None:
            key = self._next_key
            self._next_key += 1
        if key in self._lanes:
            raise InvalidRequest(f"host key {key!r} already in use")
        if slot is None:
            slot = self._pick_free_slot()
        else:
            # restore-from-checkpoint re-adoption: the stacked worlds
            # already hold this session AT ITS OLD SLOT
            try:
                self._free_slots.remove(slot)
            except ValueError:
                raise InvalidRequest(
                    f"device slot {slot} is not free on this host"
                ) from None
        return key, slot

    def _pick_free_slot(self) -> int:
        """Admission slot choice. Single device: the free-list head. On
        a session mesh: the free slot whose shard carries the FEWEST
        live worlds (lanes + attached env blocks; ties to the lowest
        shard) — slot->shard affinity's admission half, keeping the
        fleet spread so each megabatch's per-shard row groups stay
        balanced (the `ggrs_shard_imbalance` histogram is the health
        surface)."""
        if self.mesh is None:
            return self._free_slots.pop()
        return self._pick_affine_slot(self._shard_load())

    def _shard_load(self) -> List[int]:
        """Live worlds per shard (lanes + attached env blocks)."""
        dev = self.device
        load = [0] * dev.session_shards
        for lane in self._lanes.values():
            load[dev.shard_of(lane.slot)] += 1
        for env in self._envs:
            for s in env.slots:
                load[dev.shard_of(s)] += 1
        return load

    def _pick_affine_slot(self, load: List[int]) -> int:
        dev = self.device
        best = min(
            range(len(self._free_slots)),
            key=lambda i: (
                load[dev.shard_of(self._free_slots[i])],
                dev.shard_of(self._free_slots[i]),
                self._free_slots[i],  # lowest slot within a shard: a
                # fresh sharded host assigns the same slots as its
                # single-device twin (round-robin layout => ascending
                # slot order IS shard-spread order), which is what lets
                # parity tests compare canonical stacks slot-for-slot
            ),
        )
        return self._free_slots.pop(best)

    def _pick_free_slots_block(self, n: int) -> List[int]:
        """Admission's block half: `n` slots for an env block. On a mesh
        each pick is accounted as in-flight load before the next, so the
        block itself spreads over the least-loaded shards instead of
        stacking on whichever shard was lightest at entry. On a fresh
        host this yields 0..n-1 exactly like the single-device pop order
        (round-robin layout), keeping env parity tests slot-for-slot."""
        if self.mesh is None:
            return [self._free_slots.pop() for _ in range(n)]
        load = self._shard_load()
        slots = []
        for _ in range(n):
            s = self._pick_affine_slot(load)
            load[self.device.shard_of(s)] += 1
            slots.append(s)
        return slots

    def _commit_lane(self, session, key: Any, slot: int, kind: str,
                     n_players: int, local_handles, max_prediction: int,
                     current_frame: int) -> _Lane:
        if not self.batched_pump:
            # the legacy-pump host is the parity reference: its sessions
            # must pump per-message too, or the "pre-batched" arm would
            # still ride the batched single-session pump underneath
            session.batched_pump = False
        if kind == "p2p" and self.resident:
            # keep the drive cadence two ticks inside the lane's desync
            # interval: the interval-forced flush then always finds its
            # values already driven and pump-harvested, instead of
            # forcing a synchronous drive on the advance path
            det = getattr(session, "desync_detection", None)
            if det is not None and getattr(det, "enabled", False):
                self._resident_cadence = max(
                    1,
                    min(self._resident_cadence, det.interval - 2),
                )
        if kind == "p2p":
            # hosted lanes publish checksum reports at the interval-
            # forced flush ONLY (resolution still rides the pump pass):
            # publish timing is then a pure function of the frame
            # counter, not of when device values became host-ready — a
            # resident host's lazier harvest cadence would otherwise
            # shift report datagrams on the seeded wire and fork the
            # fault stream away from its dispatch-per-tick twin's
            session.checksum_publish = "interval"
        lane = _Lane(
            key, session, slot, kind, n_players, local_handles,
            max_prediction, self.clock.now_ms(),
            self.device.core._packed_len,
        )
        lane.current_frame = current_frame
        # the wedge monitor's baseline is the ATTACH tick: a session
        # admitted late into a long-lived host starts its progress
        # clock here, not at host tick 0
        lane.last_progress_tick = self._tick_index
        self._lanes[key] = lane
        self.sessions_admitted += 1
        if self._spec is not None and kind == "p2p":
            self._spec.attach(key, num_players=n_players)
        if GLOBAL_TELEMETRY.enabled:
            self._m_active.set(len(self._lanes))
        return lane

    def attach(self, session, *, key: Any = None) -> Any:
        """Admit a session; returns its host key. Raises HostFull when the
        host is at max_sessions or draining, InvalidRequest when the
        session is incompatible with the host layout or already hosted."""
        key, slot = self._claim_admission(key, None)
        try:
            kind, n_players, local_handles, max_prediction = (
                self._validate_session(session)
            )
            # attach() admits only FRESH sessions: the lane's frame
            # bookkeeping starts at 0 (mid-match sessions arrive through
            # adopt(), with their device slot riding a migration ticket)
            if kind == "p2p" and session.sync_layer.current_frame != 0:
                raise InvalidRequest(
                    "host requires a fresh session (frame 0); this one is "
                    f"at frame {session.sync_layer.current_frame} "
                    "(mid-match sessions migrate via serve.migrate)"
                )
            if kind == "spectator" and session.current_frame >= 0:
                raise InvalidRequest(
                    "host requires a fresh spectator session; this one "
                    f"already advanced to frame {session.current_frame}"
                )
            # the hook raises on double-attach BEFORE we commit the slot
            session.on_host_attach(self, key)
        except BaseException:
            self._free_slots.append(slot)
            raise
        self.device.reset_slot(slot)
        lane = self._commit_lane(
            session, key, slot, kind, n_players, local_handles,
            max_prediction, 0,
        )
        if self._journal_dir is not None and lane.kind == "p2p":
            self.attach_journal(key)
        if GLOBAL_TELEMETRY.enabled:
            GLOBAL_TELEMETRY.record(
                "host_session_attached", key=str(key), slot=slot
            )
        return key

    def adopt(self, session, *, current_frame: int, slot_state=None,
              pending_inputs=(), key: Any = None,
              slot: Optional[int] = None) -> Any:
        """Admit a MID-MATCH session — the receiving half of a live
        migration or a kill→restore re-adoption (ggrs_tpu/serve/migrate).
        `slot_state` is an `export_slot()` payload imported into the
        claimed slot (validated shape-by-shape, MigrationIncompatible on
        any mismatch); `slot_state=None` claims `slot` with the worlds
        already in place (the restore-from-checkpoint path, where
        load_stacked put every slot's bytes back at once). The lane
        resumes at `current_frame` with `pending_inputs` re-armed, so the
        first tick after adoption advances exactly where the source host
        left off."""
        key, claimed = self._claim_admission(key, slot)
        try:
            kind, n_players, local_handles, max_prediction = (
                self._validate_session(session)
            )
            if kind == "p2p" and (
                session.sync_layer.current_frame != current_frame
            ):
                raise InvalidRequest(
                    f"adopt() frame {current_frame} disagrees with the "
                    f"session's own frame "
                    f"{session.sync_layer.current_frame}"
                )
            session.on_host_attach(self, key)
            try:
                if slot_state is not None:
                    self.device.import_slot(claimed, slot_state)
            except BaseException:
                session.on_host_detach()
                raise
        except BaseException:
            self._free_slots.append(claimed)
            raise
        lane = self._commit_lane(
            session, key, claimed, kind, n_players, local_handles,
            max_prediction, current_frame,
        )
        lane.pending_inputs = set(pending_inputs)
        if self._journal_dir is not None and lane.kind == "p2p":
            self.attach_journal(key)
        if GLOBAL_TELEMETRY.enabled:
            GLOBAL_TELEMETRY.record(
                "host_session_adopted", key=str(key), slot=claimed,
                frame=current_frame,
            )
        return key

    def _reject(self) -> None:
        self.sessions_rejected += 1
        if GLOBAL_TELEMETRY.enabled:
            self._m_rejected.inc()

    def detach(self, key: Any) -> None:
        """Remove a session and recycle its device slot. Staged rows that
        never dispatched are dropped with it (the slot is reset, so no
        other session can observe the partial state)."""
        lane = self._lanes.pop(key, None)
        if lane is None:
            raise InvalidRequest(f"unknown host key {key!r}")
        if lane.journal is not None:
            # final frontier drain + fsync: a detach (migration export,
            # eviction, quarantine) must not strand confirmed rows in
            # the recorder
            try:
                self._pump_journal_lane(lane)
                if lane.journal is not None:
                    lane.journal.writer.close()
            except (JournalError, OSError):
                pass
            lane.journal = None
        if lane.queued_since_tick is not None or lane.rows:
            try:
                self._ready.remove(key)
            except ValueError:
                pass
        lane.session.on_host_detach()
        if self._spec is not None:
            self._spec.drop(key)
        self._free_slots.append(lane.slot)
        if GLOBAL_TELEMETRY.enabled:
            self._m_active.set(len(self._lanes))
            GLOBAL_TELEMETRY.record(
                "host_session_detached", key=str(key), slot=lane.slot
            )

    def attach_env(self, num_envs: int, **env_kw):
        """MIXED-TRAFFIC MODE: reserve `num_envs` device slots for a
        batched RL environment sharing this host's megabatch. The
        returned `RollbackEnv` stages its step/snapshot/restore rows
        with the host, and every `env.step()` runs ONE host tick — env
        rows join the ready sessions' depth groups, so training and
        interactive traffic dispatch as one program per group on one
        device core. Raises HostFull when the slot budget (shared with
        session admission) cannot cover the block."""
        from ..env.rollback_env import RollbackEnv

        if self._draining:
            self._reject()
            raise HostFull("host is draining: not admitting env blocks")
        if self._shed_admission:
            self._reject()
            raise HostFull(
                "host is shedding admission: device fence stalled"
            )
        if num_envs < 1 or num_envs > len(self._free_slots):
            self._reject()
            raise HostFull(
                f"env block of {num_envs} exceeds the {len(self._free_slots)}"
                " free session slots"
            )
        slots = self._pick_free_slots_block(num_envs)
        try:
            env = RollbackEnv(
                self.game,
                num_envs=num_envs,
                max_prediction=self.max_prediction,
                device=self.device,
                slots=slots,
                host=self,
                **env_kw,
            )
        except BaseException:
            # a rejected construction (bad knob combination) must not
            # leak the popped slots out of session admission
            self._free_slots.extend(slots)
            raise
        self._envs.append(env)
        if GLOBAL_TELEMETRY.enabled:
            GLOBAL_TELEMETRY.record(
                "host_env_attached", num_envs=num_envs,
                slots=f"{min(slots)}..{max(slots)}",
            )
        return env

    def detach_env(self, env) -> None:
        """Release an env block's device slots back to session admission."""
        self._envs.remove(env)
        self._free_slots.extend(env.slots)

    def session(self, key: Any):
        return self._lanes[key].session

    def keys(self) -> List[Any]:
        return list(self._lanes)

    @property
    def active_sessions(self) -> int:
        return len(self._lanes)

    @property
    def queue_depth(self) -> int:
        """Ready sessions still waiting on the device-window budget."""
        return len(self._ready)

    # ------------------------------------------------------------------
    # per-tick driving
    # ------------------------------------------------------------------

    def submit_input(self, key: Any, handle: PlayerHandle, buf: bytes) -> None:
        """Queue one local player's input for the session's next advance;
        the session advances on the next host tick once every local
        handle has input."""
        lane = self._lanes[key]
        lane.session.add_local_input(handle, buf)
        lane.pending_inputs.add(handle)
        lane.last_activity_ms = self.clock.now_ms()

    def tick(self) -> Dict[Any, List[Event]]:
        """One host cycle: pump every session's sockets, advance each
        ready session, coalesce their tick rows into megabatches under
        the device-window budget, then run eviction/GC. Returns the
        events each session surfaced this tick, keyed by host key."""
        with GLOBAL_TRACER.span("host/tick", absolute=True):
            out = self._tick_impl()
        san = active_alloc_sanitizer()
        if san is not None:
            # outside the span so the probe charges this tick's churn,
            # not the tracer's bookkeeping, to the allocation budget
            san.note_tick()
        return out

    def _tick_impl(self) -> Dict[Any, List[Event]]:
        self._tick_index += 1
        events: Dict[Any, List[Event]] = {}
        tel = GLOBAL_TELEMETRY

        # 1. pump: every session's sockets drain every host tick, even for
        # sessions that won't advance — protocol liveness (sync handshake,
        # quality reports, disconnect timers) must not depend on input.
        # Batched: ONE pooled decode pass over the union of the fleet's
        # datagrams (network/pump.py), per-session errors quarantined;
        # legacy: N per-session poll loops (the parity reference).
        with GLOBAL_TRACER.span("host/pump", absolute=True):
            lanes = list(self._lanes.values())
            if self.batched_pump:
                errors = self._pump.pump(
                    [lane.session for lane in lanes], isolate=True
                )
                for sess, exc in errors:
                    for lane in lanes:
                        if lane.session is sess:
                            lane.last_error = type(exc).__name__
                            break
            else:
                for lane in lanes:
                    try:
                        lane.session.poll_remote_clients()
                    except GGRSError as exc:  # keep serving the rest
                        lane.last_error = type(exc).__name__
            for lane in lanes:
                evs = lane.session.events()
                if evs:
                    events[lane.key] = evs
                    lane.last_activity_ms = max(
                        lane.last_activity_ms, self.clock.now_ms()
                    )
                    for ev in evs:
                        if type(ev).__name__ == "DesyncDetected":
                            self.desyncs_observed += 1

        # 1b. drain pass: retire ready fence entries and resolve every
        # host-ready checksum batch OFF the tick path — with the batched
        # checksum pump in the sessions, the steady-state tick never
        # blocks on a device->host transfer (drain_blocked_ticks == 0).
        # A HarvestTimeout (fault seam / real readback stall) is
        # transient by contract: the values still exist on device, so
        # this tick's drain is skipped and the next pass resolves them.
        with GLOBAL_TRACER.span(
            "host/drain", absolute=True,
            feed=self._m_tax_drain if tel.enabled else None,
        ):
            try:
                if self.fault_seam is not None:
                    self.fault_seam.before_harvest("drain")
                self.device.ledger.drain_ready()
                self.device.poll_retired()
            except HarvestTimeout:
                self.harvest_timeouts += 1
                if tel.enabled:
                    tel.record("harvest_timeout", op="drain")
            self._resolve_audits()

        # 2. advance ready sessions and stage their rows
        with GLOBAL_TRACER.span(
            "host/advance", absolute=True,
            feed=self._m_tax_parse if tel.enabled else None,
        ):
            for lane in list(self._lanes.values()):
                if not self._lane_ready(lane):
                    continue
                try:
                    requests = lane.session.advance_frame()
                except PredictionThreshold:
                    # spectator whose host input hasn't arrived: benign
                    lane.throttled_ticks += 1
                    if tel.enabled and lane.kind == "spectator":
                        self._m_spectator_starved.inc()
                    continue
                except GGRSError as exc:
                    lane.last_error = type(exc).__name__
                    if GLOBAL_TELEMETRY.enabled:
                        GLOBAL_TELEMETRY.record(
                            "host_session_error",
                            key=str(lane.key),
                            error=type(exc).__name__,
                        )
                    continue
                lane.pending_inputs.clear()
                lane.ticks_advanced += 1
                lane.last_activity_ms = self.clock.now_ms()
                if lane.journal is not None:
                    # pure observer: the tap tracks the same ordered
                    # request stream the backend consumes, BEFORE any
                    # staging can fail — last-write-wins rollback
                    # corrections included
                    lane.journal.recorder.observe(requests)
                try:
                    self._stage(lane, requests)
                except Exception as exc:
                    # fleet isolation: a session whose request stream the
                    # parser rejects is QUARANTINED (its device slot may
                    # have missed a tick, so it must not keep advancing),
                    # never a crash of the whole host tick. Rows staged
                    # before the failing segment are dropped too — they
                    # will never be followed by their successors, and
                    # lingering rows would pin the lane past eviction/GC
                    # (leaking its slot until a manual detach)
                    lane.rows.clear()
                    lane.failed = True
                    lane.last_error = type(exc).__name__
                    if GLOBAL_TELEMETRY.enabled:
                        GLOBAL_TELEMETRY.record(
                            "host_session_error",
                            key=str(lane.key),
                            error=type(exc).__name__,
                            stage="parse",
                        )
                    continue
                if self.resident_active:
                    # feed-and-harvest: rows move straight into the
                    # mailbox fill cycle instead of the dispatch queue
                    self._stage_resident(lane)
                if (
                    not self.resident_active
                    and lane.rows
                    and not lane.failed
                    and lane.queued_since_tick is None
                ):
                    # dispatch-per-tick scheduling — also the DEGRADED
                    # resident host's path (and _stage_resident hands
                    # rows back here when a drive failure degrades the
                    # host mid-stage)
                    lane.queued_since_tick = self._tick_index
                    self._ready.append(lane.key)

        # 2b. durable journal: drain each journaled lane's confirmed
        # frontier into its segment WAL (a host-side pure observer —
        # rows below the frontier are final by the protocol, so the
        # journal never records a value a rollback could still change)
        self._pump_journals()

        # 3. dispatch megabatches under the device-window budget (env
        # blocks still dispatch synchronously; in resident mode session
        # lanes never enter the ready queue, so this is env-only there)
        with GLOBAL_TRACER.span("host/dispatch", absolute=True):
            self._pump_device()
            if self.resident_active:
                self._resident_pump()

        # 3b. speculative bubble-filling: draft the input-starved lanes'
        # futures into the device (one vmapped rollout batch riding the
        # same bucket grid) so their empty megabatch rows become standing
        # drafts the arrival tick can adopt. AFTER the confirmed
        # dispatches and capped by the budget they left over: draft work
        # fills genuinely idle device window, it never crowds a ready
        # session's row out of this tick
        if self._spec is not None and not self._draining:
            self._launch_drafts()

        # 3c. the sampled SDC audit lane: double-compute eligible lanes
        # from their ring anchors through the full-window reference
        # program, resolved lazily by the next drain passes
        if self._audit_every:
            self._maybe_audit()

        # 3d. degradation ladder, fence-stall arm: a ready queue pinned
        # at a full device window for `shed_after_stall_ticks` sheds
        # admission until the stall clears
        if self._ready and self.device.inflight_rows >= self.max_inflight_rows:
            self._stall_ticks += 1
            if (
                self.shed_after_stall_ticks
                and not self._shed_admission
                and self._stall_ticks >= self.shed_after_stall_ticks
            ):
                self._shed_admission = True
                self.degrades += 1
                if tel.enabled:
                    self._m_degraded.labels("shed_admission").inc()
                    tel.record(
                        "host_degraded", mode="shed_admission",
                        stall_ticks=self._stall_ticks,
                    )
        else:
            self._stall_ticks = 0
            if self._shed_admission:
                self._shed_admission = False
                if tel.enabled:
                    tel.record("host_admission_restored")

        with GLOBAL_TRACER.span("host/lifecycle", absolute=True):
            # 3e. always-on invariant monitors (cheap: a handful of
            # integer compares per lane)
            self._check_invariants()

            # 4. lifecycle: disconnect GC, then idle eviction
            self._run_gc(events)
        return events

    @property
    def resident_active(self) -> bool:
        """True while the resident loop is the serving path — False on
        dispatch-per-tick hosts AND on a resident host the degradation
        ladder dropped back to its dispatch-per-tick twin."""
        return self.resident and not self._resident_degraded

    def _stage_resident(self, lane: _Lane) -> None:
        """Move a lane's freshly parsed rows into the device mailbox's
        fill cycle (the resident twin of queueing for _pump_device):
        saves bind lazy checksums against the cycle's future batch at
        their [K, S, W] harvest index, so nothing blocks. Adopt rows —
        a standing speculative draft matched this segment — force a
        driver dispatch first (the lane's earlier rows must land before
        the adopt serves its prefix), then dispatch through adopt_slot
        exactly as the twin does.

        A DeviceDispatchFailed from the forced drive inside staging runs
        the recovery ladder (_recover_drive_failure) and retries the
        row; if the ladder quarantined THIS lane its rows are gone, and
        if it degraded the host the remaining rows fall through to the
        caller's queue path."""
        SnapshotRef, _LazyChecksum = _backend_refs()
        dev = self.device
        ring_len = dev.core.ring_len
        while lane.rows and not lane.failed:
            if not self.resident_active:
                return  # degraded mid-stage: caller queues the rest
            staged = lane.rows[0]
            if staged.adopt is not None:
                if self._drive_resident() is _DRIVE_FAILED:
                    continue  # ladder ran; re-check lane/mode and retry
                if lane.failed or not self.resident_active:
                    continue
                draft_batch, packed = staged.adopt
                batch = dev.adopt_slot(lane.slot, draft_batch, packed)
                base = 0
            else:
                try:
                    batch, base = dev.stage_mailbox_row(
                        lane.slot, staged.row,
                        last_active=staged.last_active, fast=staged.fast,
                    )
                except DeviceDispatchFailed as exc:
                    # the row was NOT staged (the raise fires before any
                    # mailbox state changes): recover, then retry it
                    self._recover_drive_failure(exc)
                    continue
            lane.rows.popleft()
            for slot_i, save in staged.saves:
                lazy = _LazyChecksum(batch, base + slot_i)
                save.cell.save_lazy(
                    save.frame,
                    SnapshotRef(save.frame, save.frame % ring_len),
                    lazy,
                )
                if self._audit_every and lane.kind == "p2p":
                    lane.audit_saved_checksums[save.frame] = lazy

    def _resident_pump(self) -> None:
        """The resident scheduler's per-tick tail: land this tick's
        staged rows on the device in ONE batched mailbox transfer, then
        decide whether this tick drives. Drives fire every
        `resident_ticks` host ticks, or early when any lane is within
        two rows of the mailbox depth — the early drive keeps a
        double-row tick (misprediction rollback + keepalive segment)
        from ever overflowing in steady state, so
        ggrs_mailbox_overflow_total stays a true anomaly counter."""
        dev = self.device
        mbox = dev.mailbox
        dev.commit_mailbox()
        if not mbox.pending_rows:
            self._mbox_ticks = 0
            return
        self._mbox_ticks += 1
        if (
            self._mbox_ticks >= self._resident_cadence
            or mbox.max_fill() >= mbox.depth - 2
        ):
            self._drive_resident()
            self._mbox_ticks = 0

    # ------------------------------------------------------------------
    # device-fault recovery ladder (docs/DESIGN.md "Device fault
    # domains"): transient retry -> culprit quarantine -> degrade to the
    # dispatch-per-tick twin. Survivors keep ticking bit-exactly at
    # every rung (retries re-execute identical rows; quarantined lanes'
    # pending mailbox rows are masked off before the next drive; the
    # degraded twin is the parity reference by construction).
    # ------------------------------------------------------------------

    def _on_device_fault(self, exc: DeviceDispatchFailed) -> None:
        self.device_faults += 1
        if GLOBAL_TELEMETRY.enabled:
            GLOBAL_TELEMETRY.record(
                "device_dispatch_failed", op=exc.op,
                slots=list(exc.slots), injected=exc.injected,
            )

    def _drive_resident(self):
        """drive_mailbox behind the recovery ladder. Returns the drive's
        checksum batch (None for an empty mailbox), or _DRIVE_FAILED
        after a raise was contained — by then the ladder has retried,
        quarantined culprits and/or degraded, and the caller re-checks
        its lane/mode state and tries again."""
        try:
            return self.device.drive_mailbox()
        except DeviceDispatchFailed as exc:
            self._recover_drive_failure(exc)
            return _DRIVE_FAILED

    def _recover_drive_failure(self, exc: DeviceDispatchFailed) -> None:
        """A resident drive raised (worlds untouched by contract): retry
        once as a transient, then quarantine the culprit slots the
        failure names and drive the survivors; `drive_failure_limit`
        lifetime failures degrade the host to its dispatch-per-tick
        twin. An unattributed persistent failure re-raises — the whole
        device is suspect, and pretending otherwise would serve
        corrupt frames."""
        self._on_device_fault(exc)
        self._drive_failures += 1
        for attempt in (0, 1):
            try:
                self.device.drive_mailbox()
                break
            except DeviceDispatchFailed as exc2:
                self._on_device_fault(exc2)
                self._drive_failures += 1
                culprits = [
                    key for key, lane in self._lanes.items()
                    if lane.slot in set(exc2.slots) and not lane.failed
                ]
                if not culprits or attempt > 0:
                    raise
                for key in culprits:
                    self.quarantine(key, "drive_failed", error=exc2)
        if (
            self._drive_failures >= self.drive_failure_limit
            and not self._resident_degraded
            and self.resident
        ):
            self._degrade_resident()

    def _degrade_resident(self) -> None:
        """Drop from the resident loop to the dispatch-per-tick twin —
        bit-identical scheduling-wise (the cadence is a pure perf knob,
        pinned by test_resident_parity_any_cadence), so a host that
        keeps tripping over its driver serves slower instead of
        crashing 64 sessions. The mailbox is empty here (the recovery
        drive that brought failures past the limit just drained it)."""
        mbox = self.device.mailbox
        if mbox is not None and (mbox.pending_rows or mbox.staged_count):
            # degrading while the ring still owes rows would strand
            # them forever: surface the accounting bug typed
            raise InvariantViolation(
                f"degrade with {mbox.pending_rows} mailbox rows pending",
                invariant="degrade_with_pending_rows",
            )
        self._resident_degraded = True
        self.degrades += 1
        if GLOBAL_TELEMETRY.enabled:
            self._m_degraded.labels("dispatch_per_tick").inc()
            GLOBAL_TELEMETRY.record(
                "host_degraded", mode="dispatch_per_tick",
                drive_failures=self._drive_failures,
            )

    # ------------------------------------------------------------------
    # slot quarantine: contain a poisoned slot, keep survivors serving
    # ------------------------------------------------------------------

    def quarantine(self, key: Any, reason: str, *, error=None,
                   frame: Optional[int] = None) -> Optional[SlotPoisoned]:
        """Quarantine one hosted session's device slot: its staged rows
        and any rows the mailbox still owes it are discarded (masked off
        before the next drive — survivors' rows are untouched), the
        lane detaches, the slot's residue is scrubbed before reuse, and
        the verdict is surfaced as a typed SlotPoisoned (take_quarantines
        drains them — the fleet agent treats each like a mini-failover)
        with a forensics bundle. Returns the SlotPoisoned (None for an
        unknown key)."""
        lane = self._lanes.get(key)
        if lane is None:
            return None
        q_frame = frame if frame is not None else lane.current_frame
        lane.failed = True
        lane.last_error = reason
        lane.rows.clear()
        dropped = 0
        if self.resident and self.device.mailbox is not None:
            dropped = self.device.drop_mailbox_lane(lane.slot)
        # faults pinned on this slot stop firing: the slot is dead
        seam = self.fault_seam
        if seam is not None and hasattr(seam, "dispatch_cleared"):
            seam.dispatch_cleared(lane.slot)
        self.quarantines_total += 1
        tel = GLOBAL_TELEMETRY
        forensics = None
        if tel.enabled:
            self._m_quarantines.labels(reason).inc()
            tel.record(
                "slot_quarantined", frame=q_frame, key=str(key),
                slot=lane.slot, reason=reason, dropped_rows=dropped,
            )
            forensics = tel.write_forensics(
                "quarantine", frame=q_frame, key=str(key),
                slot=lane.slot, reason=reason,
                error=repr(error) if error is not None else None,
                dropped_rows=dropped, tick=self._tick_index,
                sessions_active=len(self._lanes),
            )
        err = SlotPoisoned(
            f"hosted session {key!r} quarantined",
            slot=lane.slot, key=key, reason=reason, frame=q_frame,
            forensics=forensics,
        )
        self._quarantines.append(err)
        slot = lane.slot
        self.detach(key)
        self.device.reset_slot(slot)
        return err

    def take_quarantines(self) -> List[SlotPoisoned]:
        """Drain the typed quarantine verdicts surfaced since the last
        call (the fleet agent polls this every step)."""
        out, self._quarantines = self._quarantines, []
        return out

    # ------------------------------------------------------------------
    # durable input journal (docs/DESIGN.md "Durable recovery")
    # ------------------------------------------------------------------

    def attach_journal(self, key: Any, path: Optional[str] = None, *,
                       meta: Optional[dict] = None,
                       fsync_every: Optional[int] = None,
                       segment_bytes: Optional[int] = None) -> Optional[str]:
        """Journal one hosted p2p lane's confirmed input rows at `path`
        (default `journal_dir/lane<key>`). Resumes an existing journal
        at the same path — the writer's open-time scan truncates a torn
        tail and retains the recorded rows, so a restore's redrive is
        VERIFIED against the durable bytes instead of re-appended.
        Returns the journal path, or None when the journal could not be
        opened (corrupt beyond continuity): the lane then serves
        unjournaled — durability degrades, serving never does."""
        from ..journal.wal import JournalWriter
        from ..utils.replay import InputRecorder

        lane = self._lanes[key]
        if lane.kind != "p2p":
            raise InvalidRequest(
                f"only p2p lanes journal (lane {key!r} is {lane.kind})"
            )
        if lane.journal is not None:
            raise InvalidRequest(f"lane {key!r} already journals")
        if path is None:
            if self._journal_dir is None:
                raise InvalidRequest(
                    "attach_journal needs a path on a host without "
                    "journal_dir"
                )
            path = os.path.join(self._journal_dir, f"lane{key}")
        base_meta = {
            "kind": "ggrs-input-journal",
            "game_cls": type(self.game).__name__,
            "num_players": lane.num_players,
            "input_size": self.game.input_size,
            "num_entities": getattr(self.game, "num_entities", None),
            **(meta or {}),
        }
        try:
            writer = JournalWriter(
                path,
                meta=base_meta,
                segment_bytes=(
                    segment_bytes
                    if segment_bytes is not None
                    else self._journal_segment_bytes
                ),
                fsync_every=(
                    fsync_every
                    if fsync_every is not None
                    else self._journal_fsync_every
                ),
            )
        except (JournalError, OSError) as exc:
            # raw OSError covers the writer's own disk touches
            # (makedirs, scan repair, segment open) — an unwritable
            # disk at attach time must degrade, not fail admission with
            # the lane already committed
            self._journal_fault(lane, exc, stage="open")
            return None
        lane.journal = _JournalTap(
            writer,
            InputRecorder(
                base_frame=writer.next_frame,
                # anchor unanchored (sparse-saving) first segments at
                # the lane's actual frame, not 0 — a mid-match adopt
                # would otherwise misfile rows
                next_frame=lane.current_frame,
            ),
            path,
        )
        if GLOBAL_TELEMETRY.enabled:
            GLOBAL_TELEMETRY.record(
                "journal_attached", key=str(key), path=path,
                resumed_frames=writer.next_frame,
            )
        return path

    def journal_frontier(self, key: Any) -> Optional[int]:
        """Frames durably journaled for a lane (None when unjournaled)
        — what the fleet heartbeat reports per match."""
        tap = self._lanes[key].journal
        return tap.writer.next_frame if tap is not None else None

    def journal_tail(self, key: Any) -> Optional[dict]:
        """Final-drain the lane's journal, then snapshot the rows NOT
        yet durable (played-but-unconfirmed at this instant) — a
        migration ticket carries them so the destination's recorder
        covers the hole between the durable frontier and the first
        frame the destination will observe itself."""
        lane = self._lanes[key]
        if lane.journal is None:
            return None
        self._pump_journal_lane(lane)
        if lane.journal is None:  # the final drain degraded it
            return None
        return lane.journal.recorder.pending_rows()

    def seed_journal_tail(self, key: Any, rows: dict) -> None:
        """Pre-observe a source recorder's pending rows into an adopted
        lane's tap (see journal_tail)."""
        tap = self._lanes[key].journal
        if tap is not None and rows:
            tap.recorder.seed_rows(rows)

    def _journal_fault(self, lane: _Lane, exc: Exception, *,
                       stage: str) -> None:
        """DEGRADE-TO-UNJOURNALED: the journal is a durability feature,
        never a liveness dependency — a refused append (ENOSPC), a
        corrupt resume or a redrive/journal divergence detaches the
        TAP, trips a typed invariant for the operator, and the lane
        keeps serving."""
        from ..journal.metrics import journal_stalls_total

        tap = lane.journal
        lane.journal = None
        if tap is not None:
            try:
                tap.writer.close()
            except (JournalError, OSError):
                pass
        self.journal_lanes_degraded += 1
        if isinstance(exc, (JournalStalled, OSError)):
            # unconditional like the wal.py counters: the disk-refusal
            # signal must not depend on the telemetry toggle
            journal_stalls_total().inc()
        self._trip_invariant(
            "journal_degraded", key=lane.key, frame=lane.current_frame,
            info=(
                f"lane {lane.key!r} journal degraded at {stage}: "
                f"{type(exc).__name__}: {exc}"
            ),
        )

    def _pump_journal_lane(self, lane: _Lane) -> None:
        """Drain one lane's confirmed frontier into its journal: rows
        the recorder re-observed below the resume watermark verify
        against the durable bytes (the restore-redrive overlap), fresh
        confirmed rows append. Every failure path degrades typed."""
        tap = lane.journal
        if tap is None:
            return
        sl = getattr(lane.session, "sync_layer", None)
        if sl is None:
            return
        # the AS-PLAYED confirmed frontier: sync_layer raises
        # last_confirmed_frame only inside advance_frame, AFTER the
        # rollback pass corrected every misprediction below it (its
        # discard assert is exactly "first_incorrect >= frame"), so
        # rows < watermark hold truth under the recorder's
        # last-write-wins rule. The LIVE min-over-peers frontier is
        # deliberately not used: an input can arrive without ever being
        # re-played (the tail of a match), leaving the recorder's row a
        # stale prediction — journaling it would diverge across peers.
        confirmed = sl.last_confirmed_frame - 1
        if confirmed < 0:
            return
        rec = tap.recorder
        rec.confirm_through(confirmed)
        try:
            if self.fault_seam is not None and hasattr(
                self.fault_seam, "before_journal_append"
            ):
                self.fault_seam.before_journal_append(tap.path)
            for f, inp, st in rec.take_stale(confirmed):
                tap.writer.verify_row(f, inp, canonical_statuses(st))
            drained = rec.drain_confirmed()
            if drained is not None:
                start, inputs, st = drained
                tap.writer.append_rows(
                    start, inputs, canonical_statuses(st)
                )
        except (JournalError, OSError, InvalidRequest) as exc:
            # InvalidRequest = a frame gap the writer refused (an
            # adoption hole no ticket tail covered): durability for
            # this lane is over, serving is not
            self._journal_fault(lane, exc, stage="append")

    def _pump_journals(self) -> None:
        for lane in self._lanes.values():
            if lane.journal is not None:
                self._pump_journal_lane(lane)

    def flush_journals(self) -> None:
        """Drain every journaled lane's frontier and fsync the active
        segments — the checkpoint/drain/export durability point."""
        for lane in list(self._lanes.values()):
            self._pump_journal_lane(lane)
            tap = lane.journal
            if tap is None:
                continue
            try:
                tap.writer.sync()
            except (JournalError, OSError) as exc:
                self._journal_fault(lane, exc, stage="sync")

    def _launch_drafts(self) -> None:
        """Collect every starved p2p lane that can be drafted this tick
        (fresh watermark, anchor snapshot live in its ring, played
        history complete) and launch ONE draft batch for all of them —
        bubbles fill as a fleet, not one dispatch per lane. Entries
        order by owning shard on a session mesh, the same lane-packing
        affinity as ordinary megabatch rows."""
        device = self.device
        core = device.core
        # the budget the confirmed dispatches left over this tick: draft
        # rows fill idle window only — a saturated device has no bubbles
        # to fill, so skip rather than add inflight work real sessions
        # will queue behind next tick
        budget = self.max_inflight_rows - device.poll_retired()
        if budget <= 0:
            return
        entries: List[Tuple[int, np.ndarray]] = []
        metas = []
        for lane in self._lanes.values():
            if (
                not lane.starved
                or lane.rows
                or lane.failed
                or lane.kind != "p2p"
            ):
                continue
            # the host already KNOWS what each local player will play
            # next — the inputs submitted during the starvation sit in
            # the session's pending map — so the draft pins them instead
            # of guessing
            pending = getattr(lane.session, "local_inputs", None) or {}
            local_pins = {
                h: pi.buf
                for h, pi in pending.items()
                if h in lane.local_handles
            }
            # inputs that ARRIVED during the stall sit confirmed in the
            # session's per-player queues (the gate blocks on the
            # watermark, not on every queue) — the draft pins those true
            # values instead of guessing, and the per-player confirmed
            # frontier is the draft's freshness fingerprint: any new
            # arrival makes the standing draft stale, so it re-drafts
            # with the fresh truth pinned in
            sl = getattr(lane.session, "sync_layer", None)
            queues = sl.input_queues if sl is not None else None
            fingerprint = (
                tuple(q.last_added_frame for q in queues)
                if queues is not None
                else None
            )

            def lookup(p, frame, _qs=queues):
                if _qs is None or p >= len(_qs):
                    return None
                q = _qs[p]
                # NativeInputQueue keeps its ring in C++ (no host-visible
                # .inputs): drafts for such a lane just guess instead of
                # pinning arrived truth — still correct, less informed
                ring = getattr(q, "inputs", None)
                if ring is None:
                    return None
                rec = ring[frame % len(ring)]
                if frame <= q.last_added_frame and rec.frame == frame:
                    return rec.buf
                return None

            plan = self._spec.plan_draft(
                lane.key,
                current_frame=lane.current_frame,
                watermark=lane.confirmed_watermark,
                local_pins=local_pins,
                confirmed_lookup=lookup,
                fingerprint=fingerprint,
            )
            if plan is None:
                continue
            anchor, scripts, statuses = plan
            metas.append((lane, anchor, scripts, statuses, fingerprint))
        if not metas:
            return
        if self.mesh is not None:
            # the same lane-packing affinity as ordinary megabatch rows:
            # a lane's member rows stay adjacent on their owning shard
            metas.sort(key=lambda m: device.shard_of(m[0].slot))
        # pack every lane's member scripts as rows of ONE draft batch,
        # capped at the device capacity (member 0 — the lineage script —
        # wins the last slots over extra bet members); rows come from a
        # host-level pool (device.draft copies them into its own pooled
        # staging, so reuse next tick is safe) — the steady-state draft
        # path allocates nothing, same discipline as _Lane.row_pool
        pool = self._draft_row_pool
        while len(pool) < device.capacity:
            pool.append(np.empty((device._draft_len,), dtype=np.int32))
        cap = min(device.capacity, budget)
        packed_metas = []
        for lane, anchor, scripts, statuses, fingerprint in metas:
            room = cap - len(entries)
            if room < 1:
                break
            members = []
            for script in scripts[:room]:
                row = pool[len(entries)]
                device.pack_draft_row_into(
                    row, anchor % core.ring_len, statuses, script
                )
                members.append(len(entries))
                entries.append((lane.slot, row))
            packed_metas.append(
                (lane, anchor, scripts[: len(members)], members,
                 fingerprint)
            )
        if self.resident_active:
            # drafts anchor on ring snapshots: rows the mailbox still
            # owes must land before the rollout reads the rings
            if self._drive_resident() is _DRIVE_FAILED:
                return  # ladder ran; draft again next tick
        batch = device.draft(entries)
        for lane, anchor, scripts, members, fingerprint in packed_metas:
            self._spec.install_draft(
                lane.key, anchor=anchor, scripts=scripts, batch=batch,
                members=members, watermark=lane.confirmed_watermark,
                fingerprint=fingerprint,
            )
        if GLOBAL_TELEMETRY.enabled:
            GLOBAL_TELEMETRY.record(
                "spec_draft_launched", lanes=len(packed_metas),
                rows=len(entries),
            )

    # ------------------------------------------------------------------
    # SDC audit lane: sampled double-compute vs the full-window
    # reference program (docs/DESIGN.md "Device fault domains")
    # ------------------------------------------------------------------

    def _build_audit_row(self, lane: _Lane):
        """One lane's audit row: load at the OLDEST ring anchor whose
        replay the record still covers, re-advance every played frame
        up to the live one, saves all scratch. The oldest anchor
        maximizes the lookback window — corruption that struck within
        the last ~max_prediction frames is caught before a post-fault
        save 'heals' the ring into consistency with the corrupt world.
        Returns (row, anchor, count) or None when the lane has no
        coverage (fresh, mid-rollback backlog, or saves out of
        range)."""
        core = self.device.core
        cur = lane.current_frame
        rec = lane.audit_inputs
        lo = max(cur - (core.ring_len - 1), 0)
        anchor = None
        for f in sorted(lane.saved_frames):
            if f < lo or f > cur:
                continue
            if cur - f > core.max_prediction + 1:
                continue  # replay must fit one packed row
            if all(g in rec for g in range(f, cur)):
                anchor = f
                break
        if anchor is None:
            return None
        count = cur - anchor
        W, P, I = core.window, self.num_players, self.game.input_size
        inputs = np.zeros((W, P, I), dtype=np.uint8)
        statuses = np.zeros((W, P), dtype=np.int32)
        save_slots = np.full((W,), core.scratch_slot, dtype=np.int32)
        for k in range(count):
            inp, st = rec[anchor + k]
            inputs[k] = inp
            statuses[k] = st
        row = core.pack_tick_row_into(
            np.empty((core._packed_len,), dtype=np.int32),
            do_load=True,
            load_slot=anchor % core.ring_len,
            inputs=inputs,
            statuses=statuses,
            save_slots=save_slots,
            advance_count=count,
            start_frame=anchor,
        )
        # the at-rest sweep's expectations: every LIVE ring row whose
        # save checksum the host recorded — (ring slot, frame, recorded
        # lazy checksum), captured by reference NOW so later saves
        # can't retroactively change what this audit compares against
        expect = [
            (f % core.ring_len, f, lane.audit_saved_checksums[f])
            for f in sorted(lane.saved_frames)
            if cur - core.ring_len < f <= cur
            and f in lane.audit_saved_checksums
        ]
        return row, anchor, count, expect

    def _maybe_audit(self) -> None:
        """Every `sdc_audit_every` host ticks, double-compute EVERY
        eligible lane (one vmapped batch on the shared bucket grid):
        detection of a flipped bit is then guaranteed within
        sdc_audit_every + the anchor lookback (~max_prediction frames)
        ticks — the sampling bound the acceptance soak pins. Results
        resolve lazily off the drain pass; a mismatch quarantines the
        slot."""
        if self._tick_index % self._audit_every:
            return
        entries: List[Tuple[int, np.ndarray]] = []
        metas = []
        for lane in self._lanes.values():
            if (
                lane.failed
                or lane.kind != "p2p"
                or lane.rows  # staged rows not yet on device: stale view
                or lane.queued_since_tick is not None
            ):
                continue
            built = self._build_audit_row(lane)
            if built is None:
                continue
            row, anchor, count, expect = built
            entries.append((lane.slot, row))
            metas.append(
                (lane.key, anchor, count, lane.current_frame, expect)
            )
            if len(entries) >= self.device.capacity:
                break
        if not entries:
            return
        if self.resident_active and self.device.mailbox.pending_rows:
            # the audit reads rings/states: rows the mailbox still owes
            # must land first (an extra drive is a pure cadence change)
            if self._drive_resident() is _DRIVE_FAILED:
                return  # ladder ran; audit again next cycle
        out = self.device.audit_rows(entries)
        self._pending_audits.append((out, metas))
        self.audits_sampled += len(entries)
        if GLOBAL_TELEMETRY.enabled:
            self._m_sdc_audits.inc(len(entries))

    def _resolve_audits(self, block: bool = False) -> None:
        """Resolve host-ready audit batches (all of them when `block`):
        a (reference replay, live world) checksum mismatch is silent
        data corruption — quarantine the slot with reason sdc_audit."""
        if not self._pending_audits:
            return
        from ..ops.fixed_point import combine_checksum

        remaining = []
        for pending in self._pending_audits:
            out, metas = pending
            ref_hi, ref_lo, live_hi, live_lo, ring_hi, ring_lo = out
            if not block and not _array_is_ready(ref_hi):
                remaining.append(pending)
                continue
            rh, rl = np.asarray(ref_hi), np.asarray(ref_lo)
            lh, ll = np.asarray(live_hi), np.asarray(live_lo)
            qh, ql = np.asarray(ring_hi), np.asarray(ring_lo)
            for k, (key, anchor, count, frame, expect) in enumerate(metas):
                verdicts = []
                if rh[k] != lh[k] or rl[k] != ll[k]:
                    # the replayed lineage and the live world disagree:
                    # one of them (or the anchor row) flipped
                    verdicts.append({
                        "check": "replay",
                        "ref": [int(rh[k]), int(rl[k])],
                        "live": [int(lh[k]), int(ll[k])],
                    })
                for rs, f, lazy in expect:
                    recomputed = combine_checksum(qh[k][rs], ql[k][rs])
                    if recomputed != lazy():
                        # a stored snapshot's bytes no longer hash to
                        # what the device computed when it SAVED them:
                        # at-rest corruption a future rollback would
                        # load and serve
                        verdicts.append({
                            "check": "ring_row", "frame": f,
                            "ring_slot": rs,
                            "recomputed": int(recomputed),
                            "recorded": int(lazy()),
                        })
                if not verdicts:
                    continue
                self.audit_mismatches += 1
                if GLOBAL_TELEMETRY.enabled:
                    self._m_sdc_mismatches.inc()
                    GLOBAL_TELEMETRY.record(
                        "sdc_mismatch", frame=frame, key=str(key),
                        anchor=anchor, replayed=count,
                        verdicts=verdicts,
                    )
                self.quarantine(key, "sdc_audit", frame=frame)
        self._pending_audits = remaining

    # ------------------------------------------------------------------
    # always-on invariant monitors
    # ------------------------------------------------------------------

    def _trip_invariant(self, invariant: str, *, key: Any = None,
                        frame: int = -1, info: str = "") -> None:
        tel = GLOBAL_TELEMETRY
        forensics = None
        if tel.enabled:
            self._m_invariants.labels(invariant).inc()
            tel.record(
                "invariant_trip", frame=frame, invariant=invariant,
                key=str(key), info=info,
            )
            forensics = tel.write_forensics(
                "invariant", frame=frame, invariant=invariant,
                key=str(key), info=info, tick=self._tick_index,
            )
        err = InvariantViolation(
            info or f"invariant {invariant} violated",
            invariant=invariant, key=key, frame=frame,
            forensics=forensics,
        )
        if len(self.invariant_trips) < 256:
            self.invariant_trips.append(err)
        if self.strict_invariants:
            raise err

    def _check_invariants(self) -> None:
        """The cheap always-on monitors — the bug class the WAN soak
        found by accident (a stale watermark permanently wedging a
        session), watched deliberately: per-lane confirmed-frame
        progress (no RUNNING lane silent past wedge_limit_ticks,
        latched until progress resumes) and resident mailbox
        accounting (staged-row count vs watermark image)."""
        tick = self._tick_index
        if self.wedge_limit_ticks:
            for lane in self._lanes.values():
                if lane.failed:
                    continue
                if lane.ticks_advanced != lane.last_progress_seen:
                    lane.last_progress_seen = lane.ticks_advanced
                    lane.last_progress_tick = tick
                    lane.wedge_reported = False
                elif (
                    not lane.wedge_reported
                    and tick - lane.last_progress_tick
                    > self.wedge_limit_ticks
                    and lane.session.current_state()
                    == SessionState.RUNNING
                ):
                    lane.wedge_reported = True
                    self._trip_invariant(
                        "lane_wedged", key=lane.key,
                        frame=lane.current_frame,
                        info=(
                            f"RUNNING lane {lane.key!r} advanced no "
                            f"frame for {tick - lane.last_progress_tick}"
                            " ticks"
                        ),
                    )
        if self.resident_active and self.device.mailbox is not None:
            mbox = self.device.mailbox
            counted = int(mbox._counts.sum())
            if mbox.pending_rows != counted or mbox.max_fill() > mbox.depth:
                self._trip_invariant(
                    "mailbox_accounting",
                    info=(
                        f"mailbox pending_rows={mbox.pending_rows} vs "
                        f"watermark image {counted} "
                        f"(max_fill={mbox.max_fill()}/{mbox.depth})"
                    ),
                )

    def _lane_ready(self, lane: _Lane) -> bool:
        lane.starved = False
        if lane.failed:  # quarantined by a staging error
            return False
        if lane.rows:  # staged rows must dispatch before the next advance
            return False
        s = lane.session
        if s.current_state() != SessionState.RUNNING:
            return False
        if lane.kind == "spectator":
            return True
        if not lane.local_handles <= lane.pending_inputs:
            return False
        # mirror sync_layer.add_local_input's prediction-threshold gate so
        # a throttled session never advances into the partially-mutated
        # PredictionThreshold raise mid-advance. The watermark must be the
        # FRESH confirmed frame (min over connected peers, what
        # advance_frame is about to set) — not the stale
        # sl.last_confirmed_frame, which only updates inside
        # advance_frame: gating on the stale value wedges a session
        # permanently once RTT exceeds the prediction window, because the
        # advance that would refresh the watermark is exactly what the
        # gate blocks (found by the WAN-profile chaos soak, where
        # cross-region links run 10+ frames of RTT). Sparse saving needs
        # no extra clamp here: set_last_confirmed_frame clamps the
        # watermark to last_saved_frame, but _check_last_saved_state runs
        # FIRST in the same advance and repairs last_saved to
        # min(confirmed, current) whenever the lag reaches the window
        # (p2p_session asserts it), so in the unrepaired region
        # current - last_saved < max_prediction and only the confirmed
        # term below can bind the in-advance PredictionThreshold raise.
        sl = s.sync_layer
        if sl.current_frame >= lane.max_prediction:
            confirmed = min(
                (
                    st.last_frame
                    for st in s.local_connect_status
                    if not st.disconnected
                ),
                default=None,
            )
            if confirmed is not None:
                # invariant monitor: the confirmed watermark is
                # monotone by protocol — a regression means a peer's
                # frame accounting (or ours) corrupted
                prev = lane.max_confirmed_seen
                if prev is not None and confirmed < prev:
                    self._trip_invariant(
                        "confirmed_regressed", key=lane.key,
                        frame=confirmed,
                        info=(
                            f"confirmed watermark regressed "
                            f"{prev} -> {confirmed} on lane {lane.key!r}"
                        ),
                    )
                else:
                    lane.max_confirmed_seen = confirmed
            if (
                confirmed is None
                or sl.current_frame - confirmed >= lane.max_prediction
            ):
                lane.throttled_ticks += 1
                # INPUT-STARVED: every local input is in but the gate
                # blocks on missing remote inputs — the lane's megabatch
                # row would be a device bubble. The speculation scheduler
                # drafts these lanes' futures instead (_launch_drafts).
                lane.starved = True
                lane.confirmed_watermark = confirmed
                return False
        return True

    # ------------------------------------------------------------------
    # request staging (parse -> packed rows)
    # ------------------------------------------------------------------

    def _stage(self, lane: _Lane, requests: List[Request]) -> None:
        # split BEFORE each LoadGameState (a load begins a new segment).
        # Steady-state traffic carries no loads, so the whole batch
        # stages as one segment with zero copies; only rollback ticks
        # pay the per-segment slice.
        if not requests:
            return
        start = 0
        for i in range(1, len(requests)):
            if isinstance(requests[i], LoadGameState):
                self._stage_segment(lane, requests[start:i])
                start = i
        self._stage_segment(
            lane, requests if start == 0 else requests[start:]
        )

    def _parse_staging(self):
        """The host-wide pooled parse triple (inputs, statuses,
        save_slots), refilled with neutral values per segment: the walk's
        output is consumed synchronously by pack_tick_row_into, so one
        triple serves the whole fleet with zero steady-state allocation."""
        core = self.device.core
        if not hasattr(self, "_parse_bufs"):
            W, P, I = core.window, self.num_players, self.game.input_size
            self._parse_bufs = (
                np.zeros((W, P, I), dtype=np.uint8),
                np.zeros((W, P), dtype=np.int32),
                np.full((W,), core.scratch_slot, dtype=np.int32),
            )
        inputs, statuses, save_slots = self._parse_bufs
        inputs.fill(0)
        statuses.fill(0)
        save_slots.fill(core.scratch_slot)
        return inputs, statuses, save_slots

    def _stage_segment(self, lane: _Lane, requests: List[Request]) -> None:
        from ..tpu.backend import parse_request_segment

        core = self.device.core
        W, P = core.window, self.num_players
        inputs, statuses, save_slots = self._parse_staging()
        if lane.num_players < P:
            # pad players beyond the session's count as DISCONNECTED: the
            # game model substitutes its deterministic dummy input, and
            # every peer of the match pads identically
            statuses[:, lane.num_players:] = int(InputStatus.DISCONNECTED)
        load, start_frame, count, saves, last_active, trailing = (
            parse_request_segment(
                requests,
                window=W,
                ring_len=core.ring_len,
                max_prediction=core.max_prediction,
                current_frame=lane.current_frame,
                inputs=inputs,
                statuses=statuses,
                save_slots=save_slots,
            )
        )
        # per-row canonical signature into the SHARED plan cache: the
        # fleet's repeated shapes coalesce across sessions
        self.device.plan_cache.note(
            (load is not None, count, last_active, trailing is not None),
            frame=start_frame,
        )
        if self._audit_every and lane.kind == "p2p":
            # SDC audit record: what the device is about to PLAY for
            # each advanced frame (rollback segments overwrite earlier
            # predicted values with the corrected truth, keeping the
            # record equal to the lineage the live bytes derive from),
            # plus the frames whose ring rows can anchor a replay
            rec = lane.audit_inputs
            for k in range(count):
                rec[start_frame + k] = (
                    inputs[k].copy(), statuses[k].copy()
                )
            for _slot_i, save in saves:
                lane.saved_frames.add(save.frame)
            floor = start_frame + count - (core.ring_len - 1)
            if len(rec) > 2 * core.window:
                for f in [f for f in rec if f < floor]:
                    del rec[f]
                lane.saved_frames = {
                    f for f in lane.saved_frames if f >= floor
                }
                for f in [
                    f for f in lane.audit_saved_checksums if f < floor
                ]:
                    del lane.audit_saved_checksums[f]
        # speculative bubble-filling: record what this lane actually
        # played (the verify pass's ground truth + the input model's
        # training stream), then check the segment against any standing
        # draft — a matched prefix turns this row into an ADOPT row
        # served from the draft trajectory instead of a resim
        adopt = None
        if self._spec is not None and lane.kind == "p2p":
            load_frame = load.frame if load is not None else None
            # verify BEFORE record_segment: the lineage check reads the
            # played rows strictly before the load point (unaffected by
            # this segment), and record_segment's stale-draft discard
            # must not kill the draft the segment is about to adopt — a
            # load AT the anchor is the deepest serveable rollback
            hit = None
            if not lane.rows:
                hit = self._spec.verify(
                    lane.key, load_frame=load_frame, start=start_frame,
                    count=count, inputs=inputs, statuses=statuses,
                )
            self._spec.record_segment(
                lane.key, load_frame=load_frame, start=start_frame,
                count=count, inputs=inputs, statuses=statuses,
                saves=saves,
            )
            if hit is not None:
                draft, member, shift, matched = hit
                packed = core.pack_adopt_row(
                    member,
                    (load.frame % core.ring_len)
                    if load is not None
                    else 0,
                    count, shift, start_frame, matched, save_slots,
                    statuses=statuses, inputs=inputs,
                )
                adopt = (draft.batch, packed)
        if adopt is not None:
            lane.rows.append(
                _StagedRow(
                    None, saves, start_frame, count, last_active, False,
                    adopt=adopt,
                )
            )
            lane.current_frame = start_frame + count
            return
        # pack straight into the lane's pooled row buffer (no per-tick
        # allocation); the scheduler's depth grouping reads the routing
        # keys off the staged row instead of rescanning it
        row = core.pack_tick_row_into(
            lane.next_row_buf(),
            do_load=load is not None,
            load_slot=(load.frame % core.ring_len) if load is not None else 0,
            inputs=inputs,
            statuses=statuses,
            save_slots=save_slots,
            advance_count=count,
            start_frame=start_frame,
        )
        lane.rows.append(
            _StagedRow(
                row, saves, start_frame, count, last_active,
                self.device.fast_eligible(row, last_active),
            )
        )
        lane.current_frame = start_frame + count

    # ------------------------------------------------------------------
    # megabatch scheduling
    # ------------------------------------------------------------------

    def _pump_device(self) -> None:
        """Coalesce the ready queue's head rows into megabatches, oldest
        arrivals first, until the device window is full or the queue is
        empty. One row per session per megabatch preserves each session's
        in-order request stream; a session with a second staged row
        (sparse-saving keepalive) keeps its queue position.

        Depth routing: each pass's picked rows split into the
        zero-rollback FAST group (no load, one advance — the dominant
        shape in real traffic) plus one group per occupied depth bucket,
        and every group dispatches as its own megabatch program sized to
        its depth — one deep-rollback session no longer drags the other
        63 sessions' rows to the full window. Groups are disjoint lanes,
        so the one-row-per-session-per-megabatch invariant holds within
        each pass.

        Mixed traffic: rows staged by attached env blocks (attach_env)
        fold into the same groups — env step rows join the fast group,
        snapshot/restore rows their depth bucket — so one dispatch
        carries training AND interactive rows. Env rows are synchronous
        training traffic (env.step blocks on this tick): when the
        inflight budget is exhausted they retire the fence and dispatch
        anyway rather than queue."""
        core = self.device.core
        # env-staged rows for this pass: gkey -> [max last_active, rows]
        env_groups: Dict[Any, List] = {}
        for env in self._envs:
            for gkey, la, entries in env._take_staged():
                slot = env_groups.get(gkey)
                if slot is None:
                    slot = env_groups[gkey] = [0, []]
                if la > slot[0]:
                    slot[0] = la
                slot[1].extend(entries)
        picked = self._picked_scratch
        adopts = self._adopts_scratch
        groups = self._groups_scratch
        while self._ready or env_groups:
            budget = self.max_inflight_rows - self.device.poll_retired()
            if budget <= 0:
                if not env_groups:
                    break
                # env rows must land THIS tick: retire the fence and
                # take the dispatch slot the budget was protecting
                self.device.retire_fence()
            env_rows = 0
            for _la, e in env_groups.values():
                env_rows += len(e)
            take = min(
                max(budget, 0),
                len(self._ready),
                max(self.device.capacity - env_rows, 0),
            )
            picked.clear()
            adopts.clear()
            groups.clear()
            # _ready is a deque in arrival order; nothing retires (and
            # so mutates it) until the picking loop is done
            for key in self._ready:
                if take <= 0:
                    break
                take -= 1
                lane = self._lanes[key]
                staged = lane.rows[0]
                if staged.adopt is not None:
                    adopts.append((lane, staged))
                else:
                    picked.append((lane, staged))
            if not picked and not adopts and not env_groups:
                break
            # ADOPT rows first: each serves its lane's tick from a
            # standing draft in one per-slot dispatch (prefix from the
            # trajectory, mispredicted suffix resimulated in-program) —
            # the whole point of having drafted the bubble
            for lane, staged in adopts:
                draft_batch, packed = staged.adopt
                batch = self.device.adopt_slot(
                    lane.slot, draft_batch, packed
                )
                self._retire_row(lane, staged, batch, 0)
            if self.depth_routing:
                for lane, staged in picked:
                    gkey = (
                        "fast"
                        if staged.fast
                        else self.device.depth_bucket_for(staged.last_active)
                    )
                    g = groups.get(gkey)
                    if g is None:
                        g = groups[gkey] = []
                    g.append((lane, staged))
            else:
                groups[None] = picked
            for gkey, group in groups.items():
                env = env_groups.pop(gkey, None) if env_groups else None
                env_la, env_entries = env if env is not None else _NO_ENV
                if self.mesh is not None:
                    # lane-packing affinity: order each megabatch's rows
                    # by the shard that owns their world, so the staged
                    # block's session-axis partitions line up with the
                    # slots they gather/scatter (stable sorts — in-shard
                    # arrival order, and the one-row-per-slot invariant,
                    # are untouched; env rows carry no save bindings)
                    group.sort(key=self._shard_key_lane)
                    if env_entries:
                        env_entries.sort(key=self._shard_key_entry)
                batch, group = self._dispatch_group(
                    gkey, group, env_entries, env_la
                )
                for k, (lane, staged) in enumerate(group):
                    self._retire_row(lane, staged, batch, k * core.window)
            while env_groups:
                # env-only depth groups (no session row picked for their
                # bucket this pass) dispatch on their own
                gkey, (env_la, env_entries) = env_groups.popitem()
                if self.mesh is not None and env_entries:
                    env_entries.sort(key=self._shard_key_entry)
                batch, group = self._dispatch_group(
                    gkey, (), env_entries, env_la
                )
                for k, (lane, staged) in enumerate(group):
                    self._retire_row(lane, staged, batch, k * core.window)
        if GLOBAL_TELEMETRY.enabled:
            self._m_queue_depth.set(len(self._ready))

    def _shard_key_lane(self, ls):
        """Lane-packing sort key (hoisted: no per-pass lambda)."""
        return self.device.shard_of(ls[0].slot)

    def _shard_key_entry(self, e):
        return self.device.shard_of(e[0])

    def _dispatch_group(self, gkey, group, env_entries, env_la):
        """Dispatch one depth group behind the fault-containment ladder:
        a DeviceDispatchFailed (raised BEFORE the program runs — worlds
        untouched) retries once as a transient; a second raise naming
        culprit slots quarantines them and re-dispatches the survivors
        bit-exactly (identical rows, identical program); persistent AND
        unattributed re-raises — the whole device is suspect. Returns
        (checksum batch | None, surviving group) with save-binding
        positions matching the surviving entries."""
        for attempt in range(3):
            try:
                return self._dispatch_group_once(
                    gkey, group, env_entries, env_la
                )
            except DeviceDispatchFailed as exc:
                group = self._dispatch_group_fault(exc, attempt, group)
        raise DeviceDispatchFailed(
            "megabatch dispatch still failing after quarantine",
            op="megabatch",
        )

    def _dispatch_group_once(self, gkey, group, env_entries, env_la):
        """One dispatch attempt — the steady-state body: per-call scratch
        only, nothing allocated per retry iteration."""
        group = [ls for ls in group if not ls[0].failed]
        # session entries FIRST: save bindings index the batch by
        # position, and env rows need no post-dispatch binding
        entries = [(lane.slot, staged.row) for lane, staged in group]
        entries.extend(env_entries)
        if not entries:
            return None, group
        if gkey == "fast":
            batch, _bucket = self.device.dispatch(entries, fast=True)
        elif gkey is None:
            batch, _bucket = self.device.dispatch(entries)
        else:
            la = env_la
            for _, staged in group:
                if staged.last_active > la:
                    la = staged.last_active
            batch, _bucket = self.device.dispatch(entries, last_active=la)
        return batch, group

    def _dispatch_group_fault(self, exc, attempt, group):
        """The containment ladder's fault arm (cold: runs only when a
        dispatch already raised). Returns the surviving group for the
        next attempt."""
        self._on_device_fault(exc)
        if attempt == 0:
            return group  # transient: the retry re-runs identically
        slots = set(exc.slots)
        culprits = [lane for lane, _ in group if lane.slot in slots]
        if not culprits:
            raise  # unattributed: the whole device is suspect
        for lane in culprits:
            self.quarantine(lane.key, "dispatch_failed", error=exc)
        return [ls for ls in group if not ls[0].failed]

    def _retire_row(self, lane: _Lane, staged: _StagedRow, batch,
                    base: int) -> None:
        """Post-dispatch bookkeeping shared by megabatch rows and adopt
        rows: pop the staged row, bind its saves' lazy checksums at
        `base` into the dispatch's checksum batch, and settle the lane's
        queue-wait accounting when its last row dispatched."""
        SnapshotRef, _LazyChecksum = _backend_refs()
        ring_len = self.device.core.ring_len
        lane.rows.popleft()
        for slot_i, save in staged.saves:
            lazy = _LazyChecksum(batch, base + slot_i)
            save.cell.save_lazy(
                save.frame,
                SnapshotRef(save.frame, save.frame % ring_len),
                lazy,
            )
            if self._audit_every and lane.kind == "p2p":
                lane.audit_saved_checksums[save.frame] = lazy
        if not lane.rows:
            self._ready.remove(lane.key)
            waited = self._tick_index - lane.queued_since_tick
            if len(self.queue_waits) < 1 << 16:
                self.queue_waits.append(waited)
            if GLOBAL_TELEMETRY.enabled:
                self._m_queue_wait.observe(waited)
            lane.queued_since_tick = None

    # ------------------------------------------------------------------
    # eviction / GC / drain
    # ------------------------------------------------------------------

    def _run_gc(self, events: Dict[Any, List[Event]]) -> None:
        now = self.clock.now_ms()
        for lane in list(self._lanes.values()):
            if lane.rows:
                continue  # drain its staged work first
            if self._all_remotes_gone(lane):
                self._evict(lane, "disconnect_gc")
                self.sessions_gced += 1
                continue
            if (
                self.idle_timeout_ms > 0
                and now - lane.last_activity_ms >= self.idle_timeout_ms
            ):
                self._evict(lane, "idle_timeout")

    def _all_remotes_gone(self, lane: _Lane) -> bool:
        """Disconnect GC predicate: a P2P session whose every remote peer
        (players and spectators) has disconnected serves nobody; a
        spectator whose host endpoint died can never advance again."""
        from ..network.protocol import ProtocolState

        s = lane.session
        if lane.kind == "spectator":
            return s.host.state in (
                ProtocolState.DISCONNECTED, ProtocolState.SHUTDOWN
            )
        remotes = s.remote_player_handles()
        if not remotes:
            return False  # solo/local-only session: nothing to GC on
        if any(
            not s.local_connect_status[h].disconnected for h in remotes
        ):
            return False
        # spectator endpoints still alive keep the session useful
        return not any(
            ep.is_running() for ep in s.player_reg.spectators.values()
        )

    def _evict(self, lane: _Lane, reason: str) -> None:
        self.sessions_evicted += 1
        tel = GLOBAL_TELEMETRY
        if tel.enabled:
            self._m_evicted.inc()
            tel.record(
                "host_session_evicted", key=str(lane.key), reason=reason
            )
        self.detach(lane.key)

    def _flush_ready(self, reason: str, *, max_passes: int = 10_000) -> None:
        """Flush every staged row through the device — the shared tail of
        graceful drain, the non-terminal checkpoint, and a migration
        export. A queue that refuses to empty (wedged fence, broken
        budget accounting, a monkeypatched scheduler) raises the typed,
        operator-facing DrainStalled carrying the stuck depth and fence
        state — and a flight-recorder event — instead of dying as a bare
        AssertionError in a shutdown path."""
        passes = 0
        while self._ready:
            # retire the whole fence first so the budget can never pin the
            # queue: each pass then dispatches at least one megabatch.
            # block_until_ready drains the mailbox, so an armed/real
            # drive fault can surface HERE — route it through the same
            # recovery ladder as the tick path instead of letting a
            # checkpoint/migration flush crash the host
            try:
                self.device.block_until_ready()
            except DeviceDispatchFailed as exc:
                self._recover_drive_failure(exc)
            self._pump_device()
            passes += 1
            if passes >= max_passes and self._ready:
                depth = len(self._ready)
                inflight = self.device.inflight_rows
                if GLOBAL_TELEMETRY.enabled:
                    GLOBAL_TELEMETRY.record(
                        "host_drain_stalled", reason=reason,
                        queue_depth=depth, inflight_rows=inflight,
                        passes=passes,
                    )
                raise DrainStalled(
                    f"{reason}: ready queue failed to flush",
                    queue_depth=depth, inflight_rows=inflight,
                    passes=passes,
                )
        try:
            self.device.block_until_ready()
        except DeviceDispatchFailed as exc:
            self._recover_drive_failure(exc)
            self.device.block_until_ready()
        self._resolve_audits(block=True)

    def _save_checkpoint(self, path: str) -> None:
        """device.save behind the harvest-timeout recovery contract: a
        readback timeout mid-checkpoint (the kill-mid-harvest race — an
        export racing an in-flight checksum batch) blocks the fence and
        retries ONCE, so the checkpoint either completes whole or the
        typed HarvestTimeout surfaces — never a torn file (the write
        itself is atomic) and never a silently skipped save."""
        for attempt in (0, 1):
            try:
                if self.fault_seam is not None:
                    self.fault_seam.before_harvest("checkpoint")
                self.device.save(path)
                break
            except HarvestTimeout:
                self.harvest_timeouts += 1
                if GLOBAL_TELEMETRY.enabled:
                    GLOBAL_TELEMETRY.record(
                        "harvest_timeout", op="checkpoint"
                    )
                if attempt:
                    raise
                self.device.block_until_ready()
        if self.fault_seam is not None:
            self.fault_seam.after_checkpoint(path)

    def checkpoint(self, path: str) -> None:
        """Durably checkpoint the stacked device worlds WITHOUT draining:
        flush staged rows and the fence, write the .npz, keep serving.
        The periodic crash-recovery story — a kill→restore rebuilds a
        host from the latest checkpoint (serve/migrate.HostGroup)."""
        self._flush_ready("checkpoint")
        self.flush_journals()
        self._save_checkpoint(path)
        if GLOBAL_TELEMETRY.enabled:
            GLOBAL_TELEMETRY.record(
                "host_checkpointed", path=str(path),
                sessions=len(self._lanes),
            )

    def drain(self, checkpoint_path: Optional[str] = None) -> dict:
        """Graceful shutdown: stop admitting (attach raises HostFull),
        flush every staged row and the async fence, optionally checkpoint
        the stacked device worlds, and return a final summary. Sessions
        stay attached (detach them, or let the process exit). Raises
        DrainStalled (typed, with the stuck queue depth and fence state)
        if the flush cannot make progress."""
        self._draining = True
        self._flush_ready("drain")
        self.flush_journals()
        if checkpoint_path is not None:
            self._save_checkpoint(checkpoint_path)
        self._drained = True
        summary = self._host_section()
        summary["checkpoint"] = checkpoint_path
        if GLOBAL_TELEMETRY.enabled:
            GLOBAL_TELEMETRY.record(
                "host_drained", sessions=len(self._lanes),
                checkpoint=str(checkpoint_path),
            )
        return summary

    @property
    def draining(self) -> bool:
        return self._draining

    # ------------------------------------------------------------------
    # telemetry
    # ------------------------------------------------------------------

    def _host_section(self) -> dict:
        dev = self.device
        sessions = {}
        for key, lane in self._lanes.items():
            entry = {
                "kind": lane.kind,
                "slot": lane.slot,
                "state": lane.session.current_state().value,
                "current_frame": lane.current_frame,
                "staged_rows": len(lane.rows),
                "ticks_advanced": lane.ticks_advanced,
                "throttled_ticks": lane.throttled_ticks,
            }
            if self.mesh is not None:
                entry["shard"] = self.device.shard_of(lane.slot)
            if lane.last_error:
                entry["last_error"] = lane.last_error
            if lane.failed:
                entry["failed"] = True
            sessions[str(key)] = entry
        return {
            "active": len(self._lanes),
            "max_sessions": self.max_sessions,
            "draining": self._draining,
            "admitted": self.sessions_admitted,
            "rejected": self.sessions_rejected,
            "evicted": self.sessions_evicted,
            "disconnect_gced": self.sessions_gced,
            "desyncs_observed": self.desyncs_observed,
            "queue_depth": len(self._ready),
            "inflight_rows": dev.inflight_rows,
            "max_inflight_rows": self.max_inflight_rows,
            "megabatches": dev.megabatches,
            "rows_dispatched": dev.rows_dispatched,
            "mean_megabatch_rows": (
                round(dev.rows_dispatched / dev.megabatches, 3)
                if dev.megabatches
                else None
            ),
            "plan_signatures": len(dev.plan_cache.signatures),
            "buckets": list(dev.buckets),
            "session_shards": dev.session_shards,
            # device fault domains: quarantine/degrade/audit health
            "quarantines": self.quarantines_total,
            "device_faults": self.device_faults,
            "harvest_timeouts": self.harvest_timeouts,
            "invariant_trips": len(self.invariant_trips),
            "shedding_admission": self._shed_admission,
            # durable input journal (absent when no lane journals, so
            # old readers stay compatible)
            **(
                {
                    "journal": {
                        "lanes": sum(
                            1
                            for lane in self._lanes.values()
                            if lane.journal is not None
                        ),
                        "frames_journaled": sum(
                            lane.journal.writer.frames_journaled
                            for lane in self._lanes.values()
                            if lane.journal is not None
                        ),
                        "bytes_written": sum(
                            lane.journal.writer.bytes_written
                            for lane in self._lanes.values()
                            if lane.journal is not None
                        ),
                        "fsyncs": sum(
                            lane.journal.writer.fsyncs
                            for lane in self._lanes.values()
                            if lane.journal is not None
                        ),
                        "degraded": self.journal_lanes_degraded,
                    }
                }
                if self._journal_dir is not None
                or self.journal_lanes_degraded
                or any(
                    lane.journal is not None
                    for lane in self._lanes.values()
                )
                else {}
            ),
            **(
                {
                    "sdc_audit": {
                        "every": self._audit_every,
                        "sampled": self.audits_sampled,
                        "mismatches": self.audit_mismatches,
                        "pending": len(self._pending_audits),
                    }
                }
                if self._audit_every
                else {}
            ),
            # vectorized protocol plane (network/endpoint_batch.py):
            # row occupancy + pass counts of this host's pump fleet
            "endpoint_fleet": self._pump.fleet.stats(),
            "sessions": sessions,
            "envs": [env._env_section() for env in self._envs],
            # speculative bubble-filling hit rate and volume (absent on
            # non-speculating hosts, so old readers stay compatible)
            **(
                {"speculation": self._spec.section()}
                if self._spec is not None
                else {}
            ),
            # device-resident loop section (absent on dispatch-per-tick
            # hosts, so old readers stay compatible)
            **(
                {
                    "resident": {
                        "depth": self.resident_ticks,
                        "driver_dispatches": dev.driver_dispatches,
                        "vticks_executed": dev.vticks_executed,
                        "vticks_per_dispatch": (
                            round(
                                dev.vticks_executed
                                / dev.driver_dispatches,
                                3,
                            )
                            if dev.driver_dispatches
                            else None
                        ),
                        "mailbox_pending": dev.mailbox.pending_rows,
                        "mailbox_overflows": dev.mailbox.overflows,
                        "degraded": self._resident_degraded,
                        "drive_failures": self._drive_failures,
                    }
                }
                if self.resident
                else {}
            ),
        }

    @property
    def frames_served_from_speculation(self) -> int:
        """Frames adopted from speculative drafts (0 on a
        non-speculating host) — the gated live bench arm's headline."""
        return self._spec.frames_adopted if self._spec is not None else 0

    @property
    def spec_hit_rate(self) -> float:
        """Adopted / serveable frames (one member's window per draft;
        0.0 on a non-speculating host) — prediction quality, independent
        of the draft width."""
        if self._spec is None or not self._spec.frames_draftable:
            return 0.0
        return self._spec.frames_adopted / self._spec.frames_draftable

    # ------------------------------------------------------------------
    # input-model hot-swap (ggrs_tpu/learn/ deploy seam)
    # ------------------------------------------------------------------

    @property
    def input_model_version(self):
        """Registry version of the installed draft model (None on a
        non-speculating host or when drafting from the online model) —
        what the fleet heartbeat reports."""
        return self._spec.model_version if self._spec is not None else None

    def install_input_model(self, model, *, version=None) -> None:
        """Hot-swap the speculation draft model at a tick boundary:
        every lane drafts its NEXT draft from a clone of `model`
        (learn.ArrayInputModel — any InputHistoryModel works); None
        reverts to per-lane online models. Standing drafts keep
        standing and verify exactly as before — the model feeds only
        the draft seam, so the never-speculating twin is provably
        unaffected (the speculation parity suite pins this across the
        swap). Identity mismatches refuse typed before any lane is
        touched."""
        from ..errors import ModelIncompatible
        from ..learn.metrics import model_installs_total, model_version_gauge

        if self._spec is None:
            raise InvalidRequest(
                "install_input_model needs a speculation=True host"
            )
        if model is not None:
            found = (model.num_players, model.input_size)
            expected = (self._spec.num_players, self._spec.input_size)
            if found != expected:
                raise ModelIncompatible(
                    "input model (players, input_size) mismatch",
                    found=found, expected=expected,
                )
            if version is None:
                version = getattr(model, "version", None)
        self._spec.install_model(model, version=version)
        model_installs_total().inc()
        model_version_gauge().set(float(version or 0))
        if GLOBAL_TELEMETRY.enabled:
            GLOBAL_TELEMETRY.record(
                "input_model_installed",
                version=version,
                model_kind=getattr(model, "kind", None) if model is not None
                else "online",
                lanes=len(self._spec._lanes),
            )

    def export_input_model_state(self, key: Any) -> Optional[dict]:
        """A lane's learned input statistics by value (None when not
        speculating) — migration tickets carry this so the destination
        resumes speculation warm instead of relearning from zero."""
        if self._spec is None:
            return None
        return self._spec.export_model_state(key)

    def import_input_model_state(self, key: Any,
                                 state: Optional[dict]) -> bool:
        """Seed an adopted lane's model from exported statistics;
        incompatible exports degrade to a cold start, never an error."""
        if self._spec is None or not state:
            return False
        return self._spec.import_model_state(key, state)

    def telemetry(self) -> dict:
        """One structured snapshot: the process-wide obs snapshot
        (metrics incl. the host instruments, flight-recorder tail, tracer
        spans) plus a `host` section aggregating scheduler/lifecycle
        state and every hosted session's own session section."""
        snap = GLOBAL_TELEMETRY.snapshot()
        host = self._host_section()
        for key, lane in self._lanes.items():
            section_fn = getattr(
                lane.session, "_telemetry_session_section", None
            )
            if callable(section_fn):
                try:
                    host["sessions"][str(key)]["session"] = section_fn()
                except GGRSError:  # e.g. stats window too young
                    pass
        snap["host"] = host
        return snap
