"""Session error hierarchy (reference: src/error.rs:11-36)."""

from __future__ import annotations


class GGRSError(Exception):
    """Base class for all session errors."""


class PredictionThreshold(GGRSError):
    """The prediction window is exhausted; cannot accept more local input
    until remote input confirms older frames (src/error.rs:13)."""

    def __str__(self) -> str:  # pragma: no cover - cosmetic
        return "Prediction threshold is reached, cannot proceed without catching up."


class InvalidRequest(GGRSError):
    """Invalid API usage (src/error.rs:15-18)."""

    def __init__(self, info: str):
        super().__init__(info)
        self.info = info


class MismatchedChecksum(GGRSError):
    """Checksum mismatch during a SyncTest resimulation (src/error.rs:22-25)."""

    def __init__(self, frame: int, local: int | None = None, expected: int | None = None):
        super().__init__(f"Detected checksum mismatch during rollback on frame {frame}.")
        self.frame = frame
        self.local = local
        self.expected = expected


class NotSynchronized(GGRSError):
    """The session has not finished synchronizing with all remotes
    (src/error.rs:27)."""


class StatsWindowTooYoung(NotSynchronized):
    """network_stats() was called before the first full second of the stats
    window elapsed — the kbps figures would divide by zero. A subclass of
    NotSynchronized so existing catch-all callers keep working, but
    distinguishable: the endpoint IS synchronized, just too fresh to
    report rates."""


class SpectatorTooFarBehind(GGRSError):
    """The spectator fell further behind the host than its input buffer can
    cover; catching up is impossible (src/error.rs:29)."""


class HostFull(GGRSError):
    """SessionHost admission control rejected an attach: the host is at its
    `max_sessions` budget or draining. Typed (not a bare InvalidRequest) so
    a fleet router can catch it and place the session on another host."""

    def __init__(self, info: str):
        super().__init__(info)
        self.info = info


class DrainStalled(GGRSError):
    """A host flush (graceful drain, migration export, kill-time
    checkpoint) failed to empty the ready queue: some staged rows can
    never dispatch — a wedged fence, a monkeypatched scheduler, or a bug
    in the budget accounting. Carries the stuck queue depth and the rows
    the last pass dispatched so an operator sees WHAT is wedged, not a
    bare AssertionError from the guard counter."""

    def __init__(self, info: str, *, queue_depth: int = 0,
                 rows_this_tick: int = 0, passes: int = 0):
        super().__init__(
            f"{info} (queue_depth={queue_depth}, "
            f"rows_this_tick={rows_this_tick}, passes={passes})"
        )
        self.info = info
        self.queue_depth = queue_depth
        self.rows_this_tick = rows_this_tick
        self.passes = passes


class CheckpointIncompatible(GGRSError):
    """A durable checkpoint cannot be restored here: its format version
    is newer than this build understands, its payload manifest does not
    match the file's contents (truncation/corruption), or its meta names
    a different core/game than the restore target. Carries both versions
    so the operator-facing message says which side to upgrade, instead of
    a shape error deep inside the restore."""

    def __init__(self, info: str, *, found=None, expected=None):
        detail = ""
        if found is not None or expected is not None:
            detail = f" (found={found!r}, expected={expected!r})"
        super().__init__(info + detail)
        self.info = info
        self.found = found
        self.expected = expected


class ModelIncompatible(CheckpointIncompatible):
    """A serialized input-model snapshot cannot be used here: its format
    version is newer than this build understands, its checksum does not
    match the registry manifest (truncation/corruption), or its game
    identity (players, input size) names a different game than the
    install target. Same shape as its checkpoint parent so registry
    readers handle both with one except clause."""


class MigrationIncompatible(InvalidRequest):
    """A live-migration ticket cannot be imported into the destination
    host: different game config (state tree shapes), input size, window,
    or ring length. A subclass of InvalidRequest so catch-all admission
    handling keeps working, but typed so a fleet router can distinguish
    'pick another host' from 'this ticket is poison'."""


class GroupSaturated(HostFull):
    """Every host in a HostGroup rejected the admission (or handoff)
    after the bounded retry/backoff ran out: the whole group is at
    capacity. A subclass of HostFull so single-host callers keep
    working; carries the attempt count and a per-host occupancy map for
    the operator."""

    def __init__(self, info: str, *, attempts: int = 0,
                 per_host=None):
        super().__init__(info)
        self.attempts = attempts
        self.per_host = dict(per_host or {})


class FleetError(GGRSError):
    """Base for multi-process fleet control-plane failures
    (ggrs_tpu.fleet): RPC transport faults, fencing rejections,
    placement exhaustion."""


class RpcTimeout(FleetError):
    """A control-plane RPC ran out of retries: every attempt (with
    exponential backoff + jitter between them) timed out without a
    reply. Carries the peer, op and attempt count so the operator sees
    WHICH link is dead, not a bare socket timeout."""

    def __init__(self, info: str, *, peer=None, op: str = "",
                 attempts: int = 0):
        super().__init__(
            f"{info} (peer={peer!r}, op={op!r}, attempts={attempts})"
        )
        self.info = info
        self.peer = peer
        self.op = op
        self.attempts = attempts


class CircuitOpen(RpcTimeout):
    """The per-peer circuit breaker is open: enough consecutive RPC
    failures that further calls are refused outright until the cooldown
    elapses (then one half-open trial decides). A subclass of RpcTimeout
    so 'peer unavailable' handling catches both; typed so a router can
    distinguish 'do not even try' from 'tried and died'."""

    def __init__(self, info: str, *, peer=None, op: str = "",
                 until_ms: int = 0):
        super().__init__(info, peer=peer, op=op, attempts=0)
        self.until_ms = until_ms


class Fenced(FleetError):
    """A control message carried a stale host epoch: the director
    already fenced that incarnation (bumped its epoch) and re-placed its
    sessions. The only correct reaction for the sender is to stop
    advancing state and terminate — its world is no longer the world."""

    def __init__(self, info: str, *, host_id=None, stale_epoch: int = 0,
                 current_epoch: int = 0):
        super().__init__(
            f"{info} (host={host_id!r}, stale_epoch={stale_epoch}, "
            f"current_epoch={current_epoch})"
        )
        self.info = info
        self.host_id = host_id
        self.stale_epoch = stale_epoch
        self.current_epoch = current_epoch


class FleetSaturated(HostFull):
    """Every agent in the fleet rejected (or could not be reached for)
    an admission after the bounded retry/jittered-backoff schedule ran
    out. The cross-process twin of GroupSaturated — a subclass of
    HostFull so single-host callers keep working; carries the attempt
    count and the per-host occupancy the director last observed."""

    def __init__(self, info: str, *, attempts: int = 0, per_host=None):
        super().__init__(info)
        self.attempts = attempts
        self.per_host = dict(per_host or {})


class DeviceFault(GGRSError):
    """Base for device-domain failures (ggrs_tpu/serve/faults.py is the
    deterministic injection seam; the real accelerator is the other
    producer): a dispatch that raised, a readback that never returned,
    corruption the audit lane caught. Every subclass carries enough
    context for the quarantine forensics bundle to name the blast
    radius without a debugger attached."""


class DeviceDispatchFailed(DeviceFault):
    """A device dispatch (megabatch, resident drive, draft, adopt)
    raised — the simulated XLA runtime failure the fault seam fires, or
    a real one caught at the same boundary. `slots` names the LOGICAL
    session slots the producer could attribute the failure to (empty =
    unattributed: the whole batch is suspect and the host's recovery is
    retry-then-degrade, not targeted quarantine). Fired BEFORE the
    program executes, so the stacked worlds are untouched and survivors
    can re-dispatch bit-exactly."""

    def __init__(self, info: str, *, op: str = "dispatch",
                 slots=(), injected: bool = False):
        slot_list = sorted(int(s) for s in slots)
        super().__init__(
            f"{info} (op={op!r}, slots={slot_list}, injected={injected})"
        )
        self.info = info
        self.op = op
        self.slots = tuple(slot_list)
        self.injected = injected


class HarvestTimeout(DeviceFault):
    """A device->host readback (checksum harvest, ledger drain, export
    copy) timed out. Transient by contract: the values still exist on
    device, so the correct reaction is block-and-retry (the host's
    drain pass skips a tick; checkpoint/export retry synchronously) —
    never dropping the harvest, which would orphan lazy checksum
    bindings."""

    def __init__(self, info: str, *, op: str = "harvest",
                 pending: int = 0):
        super().__init__(f"{info} (op={op!r}, pending={pending})")
        self.info = info
        self.op = op
        self.pending = pending


class SlotPoisoned(DeviceFault):
    """One session slot's device residue can no longer be trusted — a
    persistent dispatch failure pinned on it, or the SDC audit lane
    caught its bytes diverging from the reference recompute. The host
    QUARANTINES the slot (drops its staged work, detaches the lane,
    keeps ticking survivors bit-exactly) and surfaces this error with
    the forensics bundle path; the fleet agent treats it as a
    mini-failover (rebuild the match from its last clean checkpoint
    ticket, or hand it to the director)."""

    def __init__(self, info: str, *, slot: int = -1, key=None,
                 reason: str = "", frame: int = -1,
                 forensics=None):
        super().__init__(
            f"{info} (slot={slot}, key={key!r}, reason={reason!r}, "
            f"frame={frame})"
        )
        self.info = info
        self.slot = slot
        self.key = key
        self.reason = reason
        self.frame = frame
        self.forensics = forensics


class InvariantViolation(GGRSError):
    """An always-on cheap invariant monitor tripped: confirmed-frame
    watermark regressed, a RUNNING lane wedged without progress past
    its budget, mailbox accounting went inconsistent — the class of bug
    the WAN chaos soak previously found only by accident. Carries the
    invariant's name and a forensics bundle path so the trip is
    diagnosable after the process is gone."""

    def __init__(self, info: str, *, invariant: str = "", key=None,
                 frame: int = -1, forensics=None):
        super().__init__(
            f"{info} (invariant={invariant!r}, key={key!r}, "
            f"frame={frame})"
        )
        self.info = info
        self.invariant = invariant
        self.key = key
        self.frame = frame
        self.forensics = forensics


class MailboxLaneFull(GGRSError):
    """A mailbox lane was staged past its virtual-tick depth without an
    intervening drive — the caller must drive first (the core's
    stage_mailbox_row does; hitting this means a scheduler bypassed it).
    Typed so the operator sees WHICH lane wedged at WHAT depth instead
    of a bare AssertionError in the staging hot path."""

    def __init__(self, info: str, *, lane: int = -1, depth: int = 0):
        super().__init__(f"{info} (lane={lane}, depth={depth})")
        self.info = info
        self.lane = lane
        self.depth = depth


class JournalError(GGRSError):
    """Base for durable input-journal failures (ggrs_tpu/journal): the
    crash-consistent write-ahead log of confirmed tick rows that makes
    total host loss recoverable by deterministic resimulation."""


class JournalCorrupt(JournalError):
    """A journal segment failed its open-time scan — a CRC32 mismatch or
    framing violation in a NON-final segment (a torn tail on the final
    segment is expected crash residue and is truncated, never an error)
    — or a resumed redrive re-confirmed a row whose bytes disagree with
    what the journal durably recorded. The scan QUARANTINES the segment
    (renamed aside) and recovery falls back to the next failover-ladder
    tier; this error carries the segment and offset so the operator can
    autopsy the quarantined bytes."""

    def __init__(self, info: str, *, path: str = "", segment: str = "",
                 offset: int = -1, frame: int = -1):
        detail = f" (segment={segment!r}, offset={offset}"
        if frame >= 0:
            detail += f", frame={frame}"
        super().__init__(info + detail + ")")
        self.info = info
        self.path = path
        self.segment = segment
        self.offset = offset
        self.frame = frame


class JournalStalled(JournalError):
    """A journal append/fsync could not complete — ENOSPC, EIO, a dying
    disk. The journal is a durability feature, never a liveness
    dependency: the host's reaction is DEGRADE-TO-UNJOURNALED (typed
    invariant trip, serving continues without the durability guarantee),
    never a wedged or crashed host. Carries the errno so the operator
    sees disk-full vs device-error without a debugger."""

    def __init__(self, info: str, *, path: str = "", errno: int = 0):
        super().__init__(f"{info} (path={path!r}, errno={errno})")
        self.info = info
        self.path = path
        self.errno = errno


class RetraceBudgetExceeded(GGRSError):
    """The retrace sanitizer observed more compiled programs than the
    dispatch-bucket budget allows: a jit cache meant to be bounded by the
    (row bucket x depth bucket) grid is growing mid-serve, which means a
    dispatch signature escaped canonicalization (every compile carries
    stack provenance in the message). Raised only with GGRS_SANITIZE=1 /
    an installed sanitizer — production paths never pay the check."""


class ImplicitHostTransfer(GGRSError):
    """The transfer sanitizer caught an implicit device->host
    materialization (float()/bool()/.item()/np conversion of a device
    array) inside a post-warmup resident drive or dispatch region. Each
    such sync serializes the host against the device pipeline — the
    exact stall class the resident loop exists to avoid — and would be
    invisible in tests that only check outputs. Raised only with
    GGRS_SANITIZE=1 / an installed sanitizer inside transfer_guard_scope
    after freeze; production paths never pay the check."""


# ---------------------------------------------------------------------------
# stdlib bridge errors (EXC001 discipline)
#
# Every raise in the repo must be typed — a GGRSError — so fleet
# isolation can attribute blast radius and flight recorders capture
# context. But plenty of sites have a decade of callers (and stdlib
# conventions) expecting ValueError / TypeError / AssertionError /
# KeyError / TimeoutError. The bridges below dual-inherit: `except
# ValueError` keeps catching exactly what it caught before, while
# `except GGRSError` now sees the whole typed surface. New code should
# prefer the specific hierarchy above; bridges are for contracts whose
# stdlib face is load-bearing.
# ---------------------------------------------------------------------------


class ConfigError(GGRSError, ValueError):
    """Invalid configuration or argument value at a construction/setup
    seam (bad window size, malformed key, out-of-range knob). The
    ValueError face keeps pre-discipline callers and tests working."""


class DataFormatError(GGRSError, ValueError):
    """Malformed bytes or arrays at a decode/parse seam (truncated
    varint, bad RLE run, shape mismatch in a recorded script). Sites
    that already have a richer typed error (DecodeError, JournalCorrupt)
    should raise that instead."""


class TypeContractError(GGRSError, TypeError):
    """A value of the wrong kind crossed an API seam (unknown message
    class, non-Request in a request list). TypeError face preserved."""


class ContractViolation(GGRSError, AssertionError):
    """An internal invariant a caller cannot trigger through the public
    API failed — the typed replacement for bare `raise AssertionError`
    (AssertionError face preserved for callers treating it as such)."""


class RegistryMiss(GGRSError, KeyError):
    """A name was looked up in a registry (kernel adapters, metric
    families) that has no such entry. KeyError face preserved."""


class DeadlineExceeded(GGRSError, TimeoutError):
    """A wait on an external process/resource ran out of time (chaos
    harness child processes, drain deadlines). TimeoutError face
    preserved."""
