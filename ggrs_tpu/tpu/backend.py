"""TpuRollbackBackend: fulfills a session's ordered request list on device.

This is the pluggable seam BASELINE.json prescribes: sessions
(SyncTestSession, P2PSession) keep emitting the reference's ordered
Save/Load/Advance requests (src/lib.rs:169-194), and this backend consumes
them — but instead of executing them one by one through user callbacks, it
parses the request grammar

    [Load?] (Save? Advance)* Save?

(the exact shape every session emits per tick: first-frame double save,
dense/sparse rollback blocks, trailing confirmed-frame saves) and lowers the
whole tick into ONE fused device dispatch via ResimCore. Snapshot data never
leaves the device; cells are filled with lightweight SnapshotRef handles and
lazy checksums that only force a device->host transfer when read.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..analysis.sanitize import (
    active_sanitizer,
    transfer_guard_scope,
    warmup_scope,
)
from ..errors import ConfigError, ContractViolation, TypeContractError
from ..obs import (
    GLOBAL_TELEMETRY,
    LOG2_BUCKETS,
    LOG2_BUCKETS_MS,
    SESSION_COUNT_BUCKETS,
    SHARD_IMBALANCE_BUCKETS,
)
from ..ops.fixed_point import combine_checksum
from ..types import (
    AdvanceFrame,
    Frame,
    InputStatus,
    LoadGameState,
    Request,
    SaveGameState,
)
from ..utils.tracing import GLOBAL_TRACER
from .resim import ResimCore


@dataclass(frozen=True)
class SnapshotRef:
    """Opaque handle stored in a GameStateCell: the snapshot lives in the
    device ring, addressed by frame (slot = frame % ring_len)."""

    frame: Frame
    ring_slot: int


@dataclass(frozen=True)
class DraftBatch:
    """One draft dispatch's device-resident results: per-member per-frame
    trajectories (traj pytree [B, W, ...]), per-frame post-step checksums
    (his/los [B, W]) and the anchor checksums (a_hi/a_lo [B]) — the
    "ring-parked branch" a later adopt_slot serves (a prefix of) a
    session tick from. Member k is the k-th drafted slot of the launch;
    the confirmed stacked worlds are never touched by a draft."""

    traj: Any
    his: Any
    los: Any
    a_hi: Any
    a_lo: Any
    bucket: int


def _array_is_ready(arr) -> bool:
    is_ready = getattr(arr, "is_ready", None)
    return bool(is_ready()) if callable(is_ready) else True


def fence_stall_histogram():
    """THE ggrs_async_fence_stall_ms instrument, shared by the
    single-session backend's fence and the host core's."""
    return GLOBAL_TELEMETRY.registry.histogram(
        "ggrs_async_fence_stall_ms",
        "time the host blocked on device work it had dispatched",
        buckets=LOG2_BUCKETS_MS,
    )


class _ChecksumBatch:
    """One dispatch's worth of device checksums ([W] for a single tick,
    [T, W] for a lazy multi-tick flush — lazy checksum indices are flat
    row-major either way); fetched to host at most once, and only if some
    cell's checksum is actually read. Resolution goes through the owning
    ChecksumLedger so every pending batch rides the same device->host
    transfer — every device->host round trip is a synchronization point,
    so per-read transfers would dominate the whole tick."""

    def __init__(self, his, los, ledger: "ChecksumLedger"):
        self._his = his
        self._los = los
        self._np: Optional[Tuple[np.ndarray, np.ndarray]] = None
        self._prefetched = False
        self._ledger = ledger
        ledger.register(self)

    def prefetch(self) -> None:
        """Start a background device->host copy (non-blocking). Only marked
        prefetched when a copy actually started: resolve() trusts the flag
        to read per-batch without a fresh round trip, which would otherwise
        turn into per-batch blocking transfers on array types without
        copy_to_host_async (those keep the packed ledger-flush path)."""
        if self._np is None and not self._prefetched:
            started = False
            for arr in (self._his, self._los):
                copy = getattr(arr, "copy_to_host_async", None)
                if callable(copy):
                    copy()
                    started = True
            self._prefetched = started

    @property
    def ready(self) -> bool:
        """True when resolve() will not block on device work/transfer."""
        return self._np is not None or (
            _array_is_ready(self._his) and _array_is_ready(self._los)
        )

    def resolve(self, idx: int) -> int:
        if self._np is None and self._prefetched:
            # consume the async host copy directly; going through the
            # ledger's packed transfer would re-fetch what the prefetch
            # already moved. Callers prefetch a full drain period before
            # resolving, so this conversion is a host-memory read in steady
            # state (and at worst waits on the in-flight copy — still
            # cheaper than a fresh packed round trip).
            self._store(self._his, self._los)
        if self._np is None:
            self._ledger.flush()
        if self._np is None:  # evicted from the ledger before this read
            self._store(self._his, self._los)
        return combine_checksum(self._np[0][idx], self._np[1][idx])

    def _store(self, his: np.ndarray, los: np.ndarray) -> None:
        # flat row-major: multi-tick [T, W] batches index as j*W + i
        self._np = (np.asarray(his).ravel(), np.asarray(los).ravel())


class ChecksumLedger:
    """Batches checksum transfers across ticks: the first read of ANY lazy
    checksum fetches every pending batch in ONE jax.device_get. Bounded so
    sessions that never read checksums (desync detection off) don't
    accumulate stale batches; evicted batches resolve individually."""

    MAX_PENDING = 128

    def __init__(self):
        self._pending: List[_ChecksumBatch] = []

    def register(self, batch: _ChecksumBatch) -> None:
        self._pending.append(batch)
        if len(self._pending) > self.MAX_PENDING:
            del self._pending[: -self.MAX_PENDING]

    def drain_ready(self) -> int:
        """Non-blocking drain for the pump pass (the drain-free tick):
        resolve every pending batch whose device arrays are already
        host-ready — a host-memory copy, no transfer wait — and start a
        background host copy on the oldest still-executing batch so the
        next pass (or a forced flush) finds its bytes moved. Returns the
        number of batches still pending."""
        still: List[_ChecksumBatch] = []
        for b in self._pending:
            if b._np is not None:
                continue
            if b.ready:
                b._store(b._his, b._los)
            else:
                still.append(b)
        self._pending = still
        if still:
            still[0].prefetch()
        return len(still)

    def flush(self) -> None:
        todo = [b for b in self._pending if b._np is None]
        self._pending.clear()
        if not todo:
            return
        # Pack every pending value into ONE device array before fetching:
        # each transferred array pays a fixed latency regardless of size,
        # so fetching 2N small arrays is ~2N round trips while one packed
        # array is exactly one. The batch list is
        # padded to a power-of-two so the eager concatenate only ever
        # compiles for a handful of shapes, not one per drain size.
        import jax.numpy as jnp

        parts = [jnp.ravel(b._his) for b in todo] + [
            jnp.ravel(b._los) for b in todo
        ]
        bucket = 1
        while bucket < len(parts):
            bucket *= 2
        parts += [parts[0]] * (bucket - len(parts))
        packed = np.asarray(jnp.concatenate(parts))

        counts = [p.shape[0] for p in parts]
        offsets = np.concatenate([[0], np.cumsum(counts)])
        n = len(todo)
        for i, b in enumerate(todo):
            his = packed[offsets[i] : offsets[i + 1]]
            los = packed[offsets[n + i] : offsets[n + i + 1]]
            b._store(his, los)


class _LazyChecksum:
    """Zero-arg callable stored in a GameStateCell; supports non-blocking
    readiness checks and background prefetch."""

    __slots__ = ("_batch", "_idx")

    def __init__(self, batch: _ChecksumBatch, idx: int):
        self._batch = batch
        self._idx = idx

    def __call__(self) -> int:
        return self._batch.resolve(self._idx)

    def prefetch(self) -> None:
        self._batch.prefetch()

    @property
    def ready(self) -> bool:
        return self._batch.ready

    @property
    def dispatch_pending(self) -> bool:
        """True while the owning batch's dispatch hasn't happened yet (a
        resident fill cycle's future): prefetching such a getter would
        FORCE the dispatch — deterministic-publish binding skips those
        (sync_layer.PendingChecksumReport.bind_and_prefetch)."""
        return getattr(self._batch, "dispatch_pending", False)


class _FutureChecksumBatch:
    """Checksum-batch stand-in for ticks still sitting in the lazy tick
    buffer (no dispatch has happened, so no device arrays exist yet).
    First touch forces the backend's buffer flush, which installs the real
    batch; every method then delegates. Cells handed out before the flush
    keep working unmodified — laziness composes with laziness."""

    __slots__ = ("_flush", "batch")

    def __init__(self, flush_fn):
        self._flush = flush_fn
        self.batch: Optional[_ChecksumBatch] = None

    def _ensure(self) -> _ChecksumBatch:
        if self.batch is None:
            self._flush()
            assert self.batch is not None, "flush did not materialize batch"
        return self.batch

    def resolve(self, idx: int) -> int:
        return self._ensure().resolve(idx)

    def prefetch(self) -> None:
        # dispatching the buffer is non-blocking, so an early prefetch can
        # legitimately force it: the copy then overlaps device execution
        self._ensure().prefetch()

    @property
    def ready(self) -> bool:
        return self.batch is not None and self.batch.ready

    @property
    def dispatch_pending(self) -> bool:
        return self.batch is None


class DispatchPlanCache:
    """Canonical dispatch-signature tally: (has_load, advance_count,
    last_active, trailing_save?) -> dispatch count, fronting one jit
    cache. A TpuRollbackBackend owns one by default; a SessionHost's
    MultiSessionDeviceCore shares ONE across every hosted session —
    which is the point of canonicalization: every session's rollback
    blocks of a given shape coalesce onto the same cached program, so
    the Nth session admitted compiles nothing. Bounded in practice: the
    request grammar admits O(window^2) shapes."""

    def __init__(self):
        self.signatures: dict = {}
        _reg = GLOBAL_TELEMETRY.registry
        self._m_hits = _reg.counter(
            "ggrs_dispatch_plan_hits_total",
            "request segments whose canonical signature was already cached",
        )
        self._m_misses = _reg.counter(
            "ggrs_dispatch_plan_misses_total",
            "request segments that introduced a new canonical signature",
        )

    def note(self, sig, frame: Frame = -1, *, metrics: bool = True) -> bool:
        """Tally one dispatch of canonical signature `sig`; returns
        whether the signature was already known (a plan-cache hit).
        `metrics=False` keeps the tally out of the hit/miss counters —
        for signature populations that aren't request segments (e.g.
        megabatch bucket programs), which would otherwise pollute the
        segment-canonicalization hit rate operators read."""
        hit = sig in self.signatures
        self.signatures[sig] = self.signatures.get(sig, 0) + 1
        tel = GLOBAL_TELEMETRY
        if metrics and tel.enabled:
            if hit:
                self._m_hits.inc()
            else:
                self._m_misses.inc()
                tel.record("plan_cache_miss", frame=frame, signature=str(sig))
        return hit

    def clear(self) -> None:
        self.signatures.clear()


def parse_request_segment(
    requests: List[Request],
    *,
    window: int,
    ring_len: int,
    max_prediction: int,
    current_frame: Frame,
    inputs: np.ndarray,
    statuses: np.ndarray,
    save_slots: np.ndarray,
):
    """One pass over a session's request segment — the grammar
    [Load?] (Save? Advance)* Save? — into caller-owned packed staging
    (inputs u8[W,P,I], statuses i32[W,P], save_slots i32[W], all
    pre-filled with their neutral values; P may exceed the session's
    player count, in which case the caller pre-fills the pad columns).

    Returns (load, start_frame, count, saves, last_active,
    trailing_save): `saves` is [(window_slot, SaveGameState)] for lazy-
    checksum cell binding, `last_active` the row's 1-based last active
    slot for branchless-variant routing. THE one implementation of the
    grammar, shared by TpuRollbackBackend (pooled staging, per-backend
    jit cache) and the serve host's session lanes (fresh staging, one
    shared megabatch program)."""
    load: Optional[LoadGameState] = None
    slots: List[Tuple[Optional[SaveGameState], AdvanceFrame]] = []
    pending_save: Optional[SaveGameState] = None

    for req in requests:
        if isinstance(req, LoadGameState):
            assert load is None and not slots and pending_save is None, (
                "unsupported request pattern: Load must lead a segment"
            )
            load = req
        elif isinstance(req, SaveGameState):
            if pending_save is not None:
                # first-frame double save (p2p_session.rs:270-272 + :295)
                assert pending_save.frame == req.frame
            pending_save = req
        elif isinstance(req, AdvanceFrame):
            slots.append((pending_save, req))
            pending_save = None
        else:
            raise TypeContractError(f"unknown request {req!r}")
    trailing_save = pending_save

    count = len(slots)
    assert count <= max_prediction + 1, "tick exceeds the fused window"
    assert trailing_save is None or count < window

    start_frame = load.frame if load is not None else current_frame
    saves: List[Tuple[int, SaveGameState]] = []

    for i, (save, adv) in enumerate(slots):
        if save is not None:
            assert save.frame == start_frame + i, (
                f"save of frame {save.frame} out of order "
                f"(expected {start_frame + i})"
            )
            save_slots[i] = save.frame % ring_len
            saves.append((i, save))
        for p, (buf, status) in enumerate(adv.inputs):
            inputs[i, p] = np.frombuffer(buf, dtype=np.uint8)
            statuses[i, p] = int(status)
    if trailing_save is not None:
        assert trailing_save.frame == start_frame + count
        save_slots[count] = trailing_save.frame % ring_len
        saves.append((count, trailing_save))

    last_active = max(count, 1)
    if saves:
        last_active = max(last_active, saves[-1][0] + 1)
    return load, start_frame, count, saves, last_active, trailing_save


class TpuRollbackBackend:
    """Request-fulfilling rollback backend over a device game.

    Usage:
        backend = TpuRollbackBackend(game, max_prediction=8, num_players=2)
        requests = session.advance_frame()
        backend.handle_requests(requests)
    """

    # adaptive-gate value tracking. Every time a rollback CONSULTS the
    # standing speculation, one (branch_frames_served, member0_frames_
    # served, launches_spanned) sample lands in a trailing window —
    # launches superseded before any rollback looked at them count as
    # cost. Two economic signals, one per launch width (_launch_width):
    # branch-member serves justify the FULL width; member-0 serves
    # justify the width-1 HISTORY-ONLY launch (pinned history +
    # repeat-last at 1/B the rollout FLOPs — the measured costs decide
    # what that is worth: per-program overhead dominates
    # at interactive sizes and the widths price nearly the same, on
    # bigger worlds the B-fold device work is real). Below
    # MIN_SERVED_PER_LAUNCH
    # on both, the beam stands down entirely, except for a PROBE BURST
    # of consecutive full-width launches every VALUE_PROBE_INTERVAL
    # value-gated ticks: a burst (not a lone probe) because a speculation
    # consulted many ticks after its launch is stale by shift and would
    # miss regardless of the input regime — recovery needs a consult of
    # a FRESH spec (and member 0 rides in every full probe, so both
    # signals stay sampled).
    VALUE_WINDOW = 32  # consult samples retained
    MIN_SERVED_PER_LAUNCH = 0.3
    # the soft bar, applied when the MEASURED idle covers the measured
    # launch cost: a budget-covered launch costs the session nothing it
    # cares about (the beam is a latency feature riding idle), so value
    # gating then only protects against pointlessness — streams where
    # speculation serves literally nothing. The hard bar above prices
    # launches that the frame budget cannot absorb. Without the split,
    # streams with RARE rollbacks (one per ~10 ticks) could never clear
    # 0.3 frames/launch even with perfect candidates — every launch
    # superseded before a rollback counts as cost — and the gate locked
    # out exactly the serves it existed to enable (measured: neutral arm
    # 0.19 served at 71% gated vs 0.56 with fresh launches).
    MIN_SERVED_IDLE = 0.02
    VALUE_MIN_SAMPLES = 8  # consults before the gate may close
    VALUE_PROBE_INTERVAL = 24
    VALUE_PROBE_BURST = 3

    # async_dispatch with lazy_ticks unset batches this many ticks per
    # fused dispatch: deep enough to amortize the per-dispatch host
    # floor ~an order of magnitude, shallow enough that the live state
    # lags the session by at most ~half a max_prediction window
    ASYNC_DEFAULT_LAZY_TICKS = 8

    def __init__(self, game, max_prediction: int, num_players: int,
                 beam_width: int = 0, mesh=None, device_verify: bool = False,
                 speculation_gate: str = "always",
                 defer_speculation: bool = False, lazy_ticks: int = 0,
                 spec_backend: str = "auto", tick_backend: str = "auto",
                 async_dispatch: bool = False, async_inflight: int = 4,
                 plan_cache: Optional["DispatchPlanCache"] = None,
                 depth_routing: bool = True):
        """`mesh`: optional jax Mesh with an `entity` axis — the world and
        its snapshot ring shard across it (see ResimCore); the session-facing
        contract (requests in, SnapshotRefs + lazy checksums out) is
        unchanged, and checksums stay bit-identical to the unsharded
        backend, so sharded and unsharded peers interoperate in one P2P
        session (desync detection agrees).

        `device_verify`: keep the SyncTest first-seen checksum history and
        mismatch verdict ON DEVICE (read with check()) so determinism runs
        never pay per-burst checksum readbacks (each one a host/device
        synchronization). Only for confirmed-input replay (SyncTest): P2P
        rollbacks legitimately re-save corrected frames.

        `speculation_gate`: "always" launches a full-width speculation
        every tick (pays B*L speculative steps of device time
        unconditionally); "adaptive" picks a LAUNCH WIDTH per tick
        (_launch_width): the full beam when (a) the measured idle time
        between ticks covers the measured full-rollout cost — on a paced
        loop with spare frame budget the beam rides idle device time for
        free — and (b) recent launches' BRANCH members are actually
        being adopted (a trailing window of branch-frames-served-per-
        launch over MIN_SERVED_PER_LAUNCH); the width-1 HISTORY-ONLY
        rollout (member 0: pinned history + repeat-last, 1/B the FLOPs)
        when branch value is absent but member-0 serves aren't —
        forced-replay workloads where the corrected script IS played
        history; nothing at all when neither width earns its cost, with
        a periodic full-width probe burst every VALUE_PROBE_INTERVAL
        gated ticks so a regime change (a player starts toggling)
        re-opens the gate. Both widths' costs are measured once in
        warmup() (required for adaptive mode); host-loop idle is the
        proxy for device idle — async dispatch hides true device
        occupancy from the host.

        `defer_speculation`: keep the speculation launch OFF the tick's
        critical path — handle_requests() only fulfills requests; the
        caller launches the (gated) speculation from its idle time via
        launch_pending_speculation(). The launch costs ~1ms of host time
        (candidate generation + dispatch), which a real-time loop should
        pay after presenting the frame, not before.

        `async_dispatch`: the ASYNC DEVICE-RESIDENT DISPATCH PIPELINE.
        Three coupled behaviors, all bit-identical to the eager path
        (tests/test_async_dispatch.py is the proof):
        (1) device residency — lazy_ticks defaults to
        ASYNC_DEFAULT_LAZY_TICKS when unset, so the carry/state batch
        stays on device across ticks and dispatches as fused multi-tick
        programs; host protocol code keeps consuming the same lazy
        checksum futures it already does, drained in batches only when a
        SyncTest comparison or desync report actually reads a value.
        (2) overlap — dispatches are fenced at `async_inflight` in-flight
        batches (a small double-buffered carry at the default of 2): the
        host runs the NEXT tick's message pump / input prediction /
        request generation while the device executes the previous batch,
        and only when a third batch would enter the window does the host
        wait — on the OLDEST batch, not a full drain (the stall is
        spanned as tpu/async_fence: it is exactly the device time the
        pipeline failed to overlap). The fence also bounds how far the
        dispatch queue can run ahead (an unfenced loop can queue seconds
        of device work and then pay it all inside one blocking read).
        Host-side staging (parse buffers, the flush's multi-tick row
        buffer) rotates through async_inflight+1 pooled buffers instead
        of allocating per tick — safe to reuse precisely because the
        fence proves the dispatch that read a buffer has retired before
        the pool rotates back to it.
        (3) canonicalized dispatch signatures — request lists parse once
        into packed control rows via signature-keyed plans (the parse
        knows each row's last active slot, so branchless-variant routing
        skips its rescan), and repeated rollback blocks
        (Load + N x Save/Advance) of the same shape hit the same cached
        jitted program; distinct signatures are counted in
        dispatch_signatures for inspection.

        `lazy_ticks`: > 0 enables LAZY TICK BATCHING — ticks (rollbacks
        included) accumulate as packed control words on the host and
        dispatch as ONE fused multi-tick device program when the buffer
        fills or any device result is actually needed (a checksum read,
        state_numpy(), a speculation launch, flush()). Nothing a session
        needs synchronously lives on device — checksums are already lazy —
        so (with every dispatch paying a fixed host cost regardless of
        content) this divides the request path's dominant
        cost by the buffer depth. The live state lags the session by up to
        lazy_ticks frames between flushes: loops that render every frame
        call state_numpy() (or flush()) per frame and get per-tick
        dispatch behavior back automatically.

        `depth_routing`: route the lazy multi-tick flush to the depth
        variant covering the buffer's deepest row (max last-active slot
        across the staged ticks) instead of always scanning full-window
        rows — bit-identical, proportionally less device work per
        zero-rollback tick. False pins the full-window scan (the parity
        suite's reference arm)."""
        self.core = ResimCore(
            game, max_prediction, num_players, mesh=mesh,
            device_verify=device_verify, spec_backend=spec_backend,
            tick_backend=tick_backend,
        )
        if (
            beam_width
            and self.core._beam_sharding is not None
            and beam_width % mesh.shape["beam"] != 0
        ):
            raise ConfigError(
                f"beam_width={beam_width} must divide evenly over the mesh's "
                f"beam axis ({mesh.shape['beam']}) — an indivisible beam "
                "would silently run replicated, wasting every beam shard"
            )
        self.num_players = num_players
        self.input_size = game.input_size
        self.current_frame: Frame = 0
        self.ledger = ChecksumLedger()
        # Speculative input beam (north star: the rollback becomes a select).
        # With beam_width > 0, every tick additionally rolls out B candidate
        # input futures from the frame the NEXT rollback is expected to load
        # (steady-state rollback depth shifts by one per tick); when the
        # rollback arrives and its corrected input script matches a member,
        # the precomputed trajectory is adopted — no resimulation. Correct
        # for any game whose step branches on statuses only to zero out
        # DISCONNECTED players (candidates are speculated as CONFIRMED).
        if beam_width:
            # the adoption-correctness contract (documented above) is now
            # ENFORCED, not assumed: games declare it explicitly
            contract = getattr(game, "statuses_contract", None)
            if contract != "disconnect-only":
                raise ConfigError(
                    "beam speculation adopts trajectories rolled out with "
                    "all-CONFIRMED statuses, which is only correct for games "
                    "whose step reads statuses solely to substitute "
                    "DISCONNECTED players' inputs; declare statuses_contract "
                    "= 'disconnect-only' on the game class to opt in "
                    f"(got {contract!r} on {type(game).__name__})"
                )
        self.beam_width = beam_width
        self._spec = None  # (anchor_frame, beam_inputs, device results)
        self._last_segment = None  # launch args, deferred to end of tick
        self.beam_hits = 0  # full adoptions (every corrected frame served)
        self.beam_partial_hits = 0  # prefix adoptions (suffix resimulated)
        self.beam_misses = 0
        # THE adoption metric: fraction of rollback frames served from
        # speculation = rollback_frames_adopted / rollback_frames (a full
        # hit serves all of a rollback's frames, a partial hit its matched
        # prefix) — honest about partial wins in a way hit counts aren't
        self.rollback_frames = 0
        self.rollback_frames_adopted = 0
        # per-player input history feeding the branching candidate
        # generator: last row seen and the previous DISTINCT row (the
        # toggle partner). Rows with predicted values repeat the last
        # confirmed input, so observed transitions are always real ones.
        p, i = num_players, game.input_size
        self._last_inputs = np.zeros((p, i), dtype=np.uint8)
        self._prev_inputs = np.zeros((p, i), dtype=np.uint8)
        # (inputs u8[P,I], statuses i32[P]) actually played per recent
        # frame: shift-flexible adoption checks a member's pre-load rows
        # against this history (frames before the load are confirmed-
        # correct, so what was played is what happened)
        self._played: dict = {}
        # online hold-length/transition statistics per player, learned
        # from FINALIZED rows (frames beyond rollback reach, so nothing
        # a later correction can rewrite ever enters the statistics);
        # ranks the beam's branch candidates by measured likelihood
        # instead of a uniform offset sweep (input_model.py)
        from .input_model import InputHistoryModel

        self.input_model = InputHistoryModel(num_players, game.input_size)
        self._finalized_to = -1  # newest frame already fed to the model
        # observed rollback depth (current-after-tick minus load frame);
        # the next speculation anchors one frame deeper than the depth
        # predicts so ±1 jitter still lands inside the member window
        self._depth = 2
        assert speculation_gate in ("always", "adaptive")
        self.speculation_gate = speculation_gate
        self.defer_speculation = defer_speculation
        assert lazy_ticks >= 0
        assert async_inflight >= 1
        self.async_dispatch = async_dispatch
        self.async_inflight = async_inflight
        if async_dispatch and lazy_ticks == 0:
            lazy_ticks = self.ASYNC_DEFAULT_LAZY_TICKS
        self.lazy_ticks = lazy_ticks
        self.depth_routing = depth_routing
        self._tick_rows: List[np.ndarray] = []  # packed rows awaiting dispatch
        # max 1-based last active slot across the buffered rows: the lazy
        # flush routes the multi-tick scan to the depth variant covering
        # it (pad rows are inert at any variant, so only real rows count)
        self._buffered_last_active = 0
        self._tick_future: Optional[_FutureChecksumBatch] = None
        # async pipeline state: the in-flight dispatch fence (device result
        # handles, oldest first) and the rotating host staging pools —
        # parse triples reused every segment (they never escape: packing
        # copies them into the dispatch row), multi-tick flush buffers
        # reused only under the fence guarantee (they DO escape into the
        # dispatch, where jax may alias aligned host memory)
        from collections import deque as _deque

        self._inflight: "_deque" = _deque()
        self._stage_pool: Optional[list] = None
        self._stage_flip = 0
        self._multi_bufs: Optional[list] = None
        self._multi_flip = 0
        self._multi_active: Optional[np.ndarray] = None
        self._multi_count = 0
        self._pad_row: Optional[np.ndarray] = None
        # canonicalized dispatch signatures observed (async bookkeeping /
        # test hook): (has_load, advance_count, last_active, trailing?) ->
        # dispatch count, via a DispatchPlanCache (optionally shared —
        # backends fronting one jit cache should share one tally)
        self.plan_cache = plan_cache or DispatchPlanCache()
        # pre-bound telemetry instruments (updated behind enabled checks)
        _reg = GLOBAL_TELEMETRY.registry
        self._m_fence_stall = fence_stall_histogram()
        self._m_inflight = _reg.gauge(
            "ggrs_async_inflight", "dispatches currently inside the async fence"
        )
        self._m_batch = _reg.histogram(
            "ggrs_fused_batch_ticks",
            "session ticks fused into one multi-tick device dispatch",
            buckets=LOG2_BUCKETS,
        )
        self.beam_gated = 0  # ticks where the FULL-width launch was withheld
        # width-1 history-only launches (member 0: pinned history +
        # repeat-last). Under a beam-sharded mesh the minimal legal width
        # is the beam axis (an indivisible width would run replicated)
        self.beam_history_launches = 0
        self._history_width = (
            mesh.shape["beam"]
            if beam_width and self.core._beam_sharding is not None
            else 1
        )
        self._spec_cost_s: Optional[float] = None  # measured in warmup()
        self._spec_hist_cost_s: Optional[float] = None  # width-1, warmup()
        # None until the first idle sample lands: seeding the EMA from 0.0
        # made the gate stand down for the first ~20-30 ticks of a fully
        # idle loop while the blend warmed up (r3 advisor)
        self._idle_ema_s: Optional[float] = None
        self._last_tick_end: Optional[float] = None
        # value tracking for the adaptive gate: (frames_served,
        # launches_spanned) per consult — see the class-attribute comment
        from collections import deque

        self._launch_value: deque = deque(maxlen=self.VALUE_WINDOW)
        self._spec_consulted = False
        self._launches_since_consult = 0
        self._value_gated_streak = 0
        # tick counter + the tick of the standing spec's launch: value
        # samples are recorded only from FRESH consults (spec launched
        # the immediately-preceding tick). A gated stretch leaves a stale
        # spec standing, and a stale spec misses BY SHIFT regardless of
        # candidate quality — sampling those misses as evidence against
        # the candidates locked the gate shut on exactly the regimes the
        # probe bursts exist to re-open (measured: the neutral arm sat at
        # 0.19 frames-served with 71% gating while the same candidates
        # served 0.56+ when launched fresh).
        self._tick_index = 0
        self._spec_tick = -10

    # ------------------------------------------------------------------

    def handle_requests(self, requests: List[Request]) -> None:
        """A tick is usually one fused batch, but sparse-saving P2P ticks can
        legally contain two rollback blocks (misprediction rollback + ring
        keepalive rollback, p2p_session.rs:286+:792): split into one batch
        per LoadGameState and fuse each."""
        import time as _time

        if self.speculation_gate == "adaptive":
            now = _time.perf_counter()
            if self._last_tick_end is not None:
                idle = now - self._last_tick_end
                # EMA over ~10 ticks: reacts to phase changes (a pause
                # menu, a scene load) without flapping on single-frame
                # jitter; the first sample SEEDS the EMA outright
                self._idle_ema_s = (
                    idle
                    if self._idle_ema_s is None
                    else 0.9 * self._idle_ema_s + 0.1 * idle
                )
        self._tick_index += 1
        segment: List[Request] = []
        for req in requests:
            if isinstance(req, LoadGameState) and segment:
                self._run_segment(segment)
                segment = []
            segment.append(req)
        if segment:
            self._run_segment(segment)
        # one speculation per tick, from the final segment's frontier — an
        # earlier segment's beam could never be matched (only the last
        # segment defines the next tick's expected rollback anchor). A
        # fresh launch every tick keeps the candidates built from the
        # newest input history, which measures as a much higher hit rate
        # than reusing a standing rollout across ticks.
        if not self.defer_speculation:
            self.launch_pending_speculation()
        if self.speculation_gate == "adaptive":
            self._last_tick_end = _time.perf_counter()

    def launch_pending_speculation(self) -> None:
        """Launch (or gate) the speculation staged by the last tick. With
        defer_speculation=True, call this from loop idle time after the
        frame's critical path; otherwise handle_requests calls it
        automatically."""
        if self.beam_width and self._last_segment is not None:
            if self._last_segment[2] == 0:  # count: nothing to anchor on
                self._last_segment = None
                return
            width = self._launch_width()
            if width != self.beam_width:
                self.beam_gated += 1
            if width:
                if width != self.beam_width:
                    self.beam_history_launches += 1
                self._launch_speculation(*self._last_segment, width=width)
            self._last_segment = None

    def _launch_width(self) -> int:
        """The adaptive gate. Returns the width to launch at — the full
        beam, the width-1 history-only rollout, or 0 for no launch.

        BUDGET — speculation is worth launching only when the loop's idle
        time can absorb its device cost; otherwise the speculative steps
        delay the NEXT real tick by more than an adopted rollback could
        ever save. 80% slack biases toward speculating (a near-covered
        cost still wins when a deep rollback adopts). An unseeded idle
        EMA (no second tick yet) counts as affordable. The full and the
        history widths are budgeted separately (both costs measured in
        warmup()): an idle budget too thin for the B-wide rollout often
        still covers the width-1 one.

        VALUE — two signals from the consult trail, one per width. Full
        width is justified only by BRANCH-member adoptions (trailing
        branch-frames-served-per-launch >= MIN_SERVED_PER_LAUNCH); when
        that fails, a PROBE BURST of consecutive full-width launches
        every VALUE_PROBE_INTERVAL value-gated ticks keeps sampling the
        regime with fresh-at-consult specs so toggling players re-open
        it. The history width is justified by MEMBER-0 adoptions —
        SyncTest-style replays where the corrected script is played
        history and the pinned member serves it at 1/B the rollout
        FLOPs (the measured per-width costs price what that is worth);
        in P2P regimes member 0 serves nothing by construction (the load
        frame is the first incorrect frame), the history signal decays,
        and value-gated ticks stand fully down exactly as before this
        width existed (full probes keep sampling BOTH signals: member 0
        rides in every full launch)."""
        full, hist = self.beam_width, self._history_width
        if self.speculation_gate != "adaptive":
            return full
        if self._spec_cost_s is None:
            return full  # not yet measured (warmup pending): don't stall
        idle = self._idle_ema_s
        # ONE covered-by-idle predicate per width, reused by both the
        # affordability decision and the soft/hard bar choice below so
        # the two can never drift (a soft bar for a width the budget
        # then refuses to launch would be incoherent). `idle is None`
        # (no second tick yet) counts as affordable but NOT as measured
        # coverage — the soft bar requires evidence.
        full_covered = idle is not None and idle >= 0.8 * self._spec_cost_s
        full_affordable = idle is None or full_covered
        hist_cost = (
            self._spec_hist_cost_s
            if self._spec_hist_cost_s is not None
            # unmeasured (older checkpoint): assume the FULL cost. Per-
            # dispatch overhead dominates at interactive sizes, so a
            # linear width/full scaling would admit history launches into
            # idle budgets that cannot actually absorb them (r4 advisor);
            # the conservative fallback only ever under-launches until
            # warmup() measures the real width-1 cost
            else self._spec_cost_s
        )
        hist_covered = idle is not None and idle >= 0.8 * hist_cost
        hist_affordable = idle is None or hist_covered
        if len(self._launch_value) >= self.VALUE_MIN_SAMPLES:
            launches = max(sum(n for _, _, n in self._launch_value), 1)
            branch_rate = sum(b for b, _, _ in self._launch_value) / launches
            hist_rate = sum(h for _, h, _ in self._launch_value) / launches
            # bar per width: soft when measured idle covers that width's
            # measured cost (see MIN_SERVED_IDLE), hard otherwise
            full_bar = (
                self.MIN_SERVED_IDLE
                if full_covered
                else self.MIN_SERVED_PER_LAUNCH
            )
            hist_bar = (
                self.MIN_SERVED_IDLE
                if hist_covered
                else self.MIN_SERVED_PER_LAUNCH
            )
            hist_ok = hist_rate >= hist_bar
            # full width earns its keep when its MARGINAL value over the
            # history width (branch serves) clears the bar — or, in
            # blended regimes where neither signal alone clears it, when
            # the TOTAL does (the pre-split gate's signal: width-1 alone
            # would forfeit the branch share). When member-0 serves
            # dominate and the branch marginal is under the bar, full is
            # NOT ok even though the total is huge: that's exactly the
            # regime the cheaper history width exists for.
            branch_ok = branch_rate >= full_bar or (
                not hist_ok
                and branch_rate + hist_rate >= full_bar
            )
        else:
            branch_ok = hist_ok = True
        if branch_ok:
            self._value_gated_streak = 0
            if full_affordable:
                return full
            if hist_ok and hist_affordable:
                return hist
            return 0
        # full width value-gated: probe at the END of each interval (the
        # streak keeps counting through probes — it clears only when
        # branch adoptions lift the trailing ratio back over the bar)
        self._value_gated_streak += 1
        probing = (
            (self._value_gated_streak - 1) % self.VALUE_PROBE_INTERVAL
            >= self.VALUE_PROBE_INTERVAL - self.VALUE_PROBE_BURST
        )
        if probing and full_affordable:
            return full
        if hist_ok and hist_affordable:
            return hist
        return 0

    def _next_stage(self):
        """Rotate the pooled (inputs, statuses, save_slots) parse triple.
        The triple never reaches the device: every dispatch path copies it
        host-side first — pack_tick_row/pack_tick_row_into for ticks,
        adopt's own packed buffer for beam adoption — so reuse needs no
        fence and is safe in eager mode too. The pool is
        async_inflight + 1 deep only so the CURRENT segment's triple (read
        by the beam bookkeeping until the tick ends) is never the one
        being refilled; one spare would do, the depth just mirrors the
        multi-buf pool."""
        core = self.core
        if self._stage_pool is None:
            W, P, I = core.window, self.num_players, self.input_size
            self._stage_pool = [
                (
                    np.zeros((W, P, I), dtype=np.uint8),
                    np.zeros((W, P), dtype=np.int32),
                    np.full((W,), core.scratch_slot, dtype=np.int32),
                )
                for _ in range(self.async_inflight + 1)
            ]
        self._stage_flip = (self._stage_flip + 1) % len(self._stage_pool)
        inputs, statuses, save_slots = self._stage_pool[self._stage_flip]
        inputs.fill(0)
        statuses.fill(0)
        save_slots.fill(core.scratch_slot)
        return inputs, statuses, save_slots

    @property
    def dispatch_signatures(self) -> dict:
        """Signature -> dispatch count view of the plan cache (test hook /
        bookkeeping; the historical attribute name)."""
        return self.plan_cache.signatures

    def _parse_segment(self, requests: List[Request]):
        """One pass over a request segment into packed-dispatch staging
        (the shared parse_request_segment grammar walk over this backend's
        pooled staging). Returns (load, start_frame, count, inputs,
        statuses, save_slots, saves, last_active): `last_active` is the
        row's 1-based last active slot, handed to the core so
        branchless-variant routing skips its save-slot rescan; the
        (shape-level) signature is tallied in the plan cache — repeated
        rollback blocks of one shape reuse one cached jitted program."""
        core = self.core
        inputs, statuses, save_slots = self._next_stage()
        load, start_frame, count, saves, last_active, trailing_save = (
            parse_request_segment(
                requests,
                window=core.window,
                ring_len=core.ring_len,
                max_prediction=core.max_prediction,
                current_frame=self.current_frame,
                inputs=inputs,
                statuses=statuses,
                save_slots=save_slots,
            )
        )
        sig = (
            load is not None,
            count,
            last_active,
            trailing_save is not None,
        )
        self.plan_cache.note(sig, frame=start_frame)
        return (
            load, start_frame, count, inputs, statuses, save_slots, saves,
            last_active,
        )

    def _note_inflight(self, handle) -> None:
        """Fence an async dispatch: admit `handle` (any device array of the
        dispatch's result) to the in-flight window; once a dispatch beyond
        `async_inflight` would be outstanding, wait for the OLDEST — the
        host stays one-to-two batches ahead of the device instead of
        either running unboundedly ahead or draining after every batch.
        No-op in eager mode (eager callers rely on jax's own queue)."""
        if not self.async_dispatch:
            return
        self._inflight.append(handle)
        tel = GLOBAL_TELEMETRY
        if tel.enabled:
            self._m_inflight.set(len(self._inflight))
        while len(self._inflight) > self.async_inflight:
            oldest = self._inflight.popleft()
            with GLOBAL_TRACER.span("tpu/async_fence", absolute=True):
                t0 = time.perf_counter() if tel.enabled else 0.0
                jax.block_until_ready(oldest)
                if tel.enabled:
                    stall_ms = (time.perf_counter() - t0) * 1000.0
                    self._m_fence_stall.observe(stall_ms)
                    self._m_inflight.set(len(self._inflight))
                    tel.record(
                        "fence_stall",
                        frame=self.current_frame,
                        stall_ms=round(stall_ms, 4),
                        inflight=len(self._inflight),
                    )

    def _run_segment(self, requests: List[Request]) -> None:
        with GLOBAL_TRACER.span("tpu/host_parse", absolute=True):
            (
                load, start_frame, count, inputs, statuses, save_slots,
                saves, last_active,
            ) = self._parse_segment(requests)
        core = self.core

        his = los = None
        if load is not None:
            self.rollback_frames += count
        if load is not None and self._spec is not None:
            match = self._match_speculation(load.frame, inputs, statuses, count)
            if not self._spec_consulted and (
                self._tick_index - self._spec_tick <= 1
            ):
                # one value sample per FRESH consulted speculation (stale
                # specs — left standing by gated ticks — miss by shift
                # regardless of candidate quality and say nothing; their
                # launch cost stays in _launches_since_consult and rides
                # the next fresh sample), split by
                # WHO served: (branch_frames, member0_frames, launches
                # paid since the last consult) — superseded-unconsulted
                # launches count as cost without poisoning quiet
                # stretches. The split is the width decision's signal:
                # member-0 serves are what the width-1 history launch
                # provides at 1/B the rollout FLOPs (SyncTest-style replays,
                # where the corrected script IS played history), while
                # only branch-member adoptions justify the full width
                # (P2P toggles — there the load frame is the first
                # INCORRECT frame, so member 0's pinned rows mismatch at
                # offset 0 by construction and serve nothing)
                served = match[2] if match else 0
                is_branch = bool(match) and match[0] != 0
                self._launch_value.append(
                    (served if is_branch else 0,
                     0 if is_branch else served,
                     max(self._launches_since_consult, 1))
                )
                self._launches_since_consult = 0
                self._spec_consulted = True
            if match is not None:
                member, shift, matched = match
                if matched == count:
                    self.beam_hits += 1
                else:
                    self.beam_partial_hits += 1
                self.rollback_frames_adopted += matched
                # adoption reads the ring: buffered ticks must land first
                self.flush()
                with GLOBAL_TRACER.span("tpu/beam_adopt", absolute=True):
                    his, los = core.adopt(
                        self._spec[2],
                        member,
                        load.frame % core.ring_len,
                        save_slots,
                        count,
                        shift=shift,
                        load_frame=load.frame,
                        inputs=inputs,
                        statuses=statuses,
                        matched=matched,
                    )
                self._note_inflight(his)
            else:
                self.beam_misses += 1
        batch = None
        base_idx = 0
        if his is None and self.lazy_ticks > 0:
            # lazy tick batching: stage the packed row; the fused
            # multi-tick dispatch happens at flush() (buffer full or first
            # device-result need). Rollback rows buffer like any other —
            # the load executes in order inside the multi-tick scan.
            if self._tick_future is None:
                self._tick_future = _FutureChecksumBatch(self.flush)
            batch = self._tick_future
            self._buffered_last_active = max(
                self._buffered_last_active, last_active
            )
            if self.async_dispatch:
                # pack straight into the pooled multi-tick buffer: no
                # per-tick row allocation, no flush-time copy
                buf = self._acquire_multi_buf()
                base_idx = self._multi_count * core.window
                core.pack_tick_row_into(
                    buf[self._multi_count],
                    do_load=load is not None,
                    load_slot=(load.frame % core.ring_len)
                    if load is not None
                    else 0,
                    inputs=inputs,
                    statuses=statuses,
                    save_slots=save_slots,
                    advance_count=count,
                    start_frame=start_frame,
                )
                self._multi_count += 1
            else:
                row = core.pack_tick_row(
                    do_load=load is not None,
                    load_slot=(load.frame % core.ring_len)
                    if load is not None
                    else 0,
                    inputs=inputs,
                    statuses=statuses,
                    save_slots=save_slots,
                    advance_count=count,
                    start_frame=start_frame,
                )
                base_idx = len(self._tick_rows) * core.window
                self._tick_rows.append(row)
        elif his is None:
            with GLOBAL_TRACER.span("tpu/fused_tick", absolute=True):
                row = core.pack_tick_row(
                    do_load=load is not None,
                    load_slot=(load.frame % core.ring_len) if load is not None else 0,
                    inputs=inputs,
                    statuses=statuses,
                    save_slots=save_slots,
                    advance_count=count,
                    start_frame=start_frame,
                )
                his, los = core.tick_row(row, last_active)
            self._note_inflight(his)
        self.current_frame = start_frame + count

        if batch is None:
            batch = _ChecksumBatch(his, los, self.ledger)
        for idx, save in saves:
            ref = SnapshotRef(save.frame, save.frame % core.ring_len)
            save.cell.save_lazy(
                save.frame, ref, _LazyChecksum(batch, base_idx + idx)
            )
        if len(self._tick_rows) + self._multi_count >= self.lazy_ticks > 0:
            self.flush()

        if self.beam_width:
            # the speculation survives the tick UNLESS this rollback rewrote
            # history at or before its anchor (the anchor snapshot is then
            # stale); divergence after the anchor is handled by the played-
            # prefix match, since trajectories are deterministic in the
            # anchor state + candidate rows
            if (
                self._spec is not None
                and load is not None
                and load.frame <= self._spec[0]
            ):
                self._spec = None
            # only the shape survives the tick (the staging triple is
            # pooled and will be reused): the deferred launch needs the
            # frontier frame and count, nothing from the input rows
            self._last_segment = (load, start_frame, count)
            if load is not None:
                self._depth = count  # observed rollback depth
            for f in range(count):
                changed = (inputs[f] != self._last_inputs).any(axis=1)
                if changed.any():
                    self._prev_inputs[changed] = self._last_inputs[changed]
                    self._last_inputs[changed] = inputs[f][changed]
                self._played[start_frame + f] = (
                    inputs[f].copy(),
                    statuses[f].copy(),
                )
            # feed the input model every newly-FINALIZED frame, in order:
            # a rollback can load at most max_prediction behind the
            # current frame, so rows older than that are what really
            # happened — even rows played as predictions (never corrected
            # means correct). Disconnected cells break the run instead of
            # polluting the hold statistics with dummy inputs.
            final_horizon = self.current_frame - core.max_prediction
            f = self._finalized_to + 1
            # a gap (restored checkpoint, pre-beam history) can't be
            # learned from: jump past it, severing runs so stale run
            # state never bridges unobserved frames. `horizon` (below)
            # is the _played GC cutoff — the jump guard must use the
            # same expression or the two drift.
            horizon = self.current_frame - core.window - core.max_prediction
            oldest_kept = horizon
            if f < oldest_kept:
                f = oldest_kept
                for p in range(self.num_players):
                    self.input_model.break_run(p)
            while f < final_horizon:
                rec = self._played.get(f)
                if rec is None:
                    for p in range(self.num_players):
                        self.input_model.break_run(p)
                else:
                    pin, pst = rec
                    for p in range(self.num_players):
                        if pst[p] >= int(InputStatus.DISCONNECTED):
                            self.input_model.break_run(p)
                        else:
                            self.input_model.observe(p, pin[p].tobytes())
                self._finalized_to = f
                f += 1
            for key in [k for k in self._played if k < horizon]:
                del self._played[key]

    # ------------------------------------------------------------------
    # speculative beam
    # ------------------------------------------------------------------

    def _match_speculation(
        self, load_frame: Frame, inputs: np.ndarray, statuses: np.ndarray,
        count: int,
    ) -> Optional[Tuple[int, int, int]]:
        """Returns (member, shift, matched) of an adoptable speculation,
        else None. shift = load_frame - anchor_frame: the member must ALSO
        match the inputs actually played for frames anchor..load (its
        trajectory baked them in) — rollback depth jitter then lands inside
        the same speculated window instead of invalidating it. `matched`
        is the longest leading run of the corrected script the member's
        rows cover (src/input_queue.rs:167-204's localization, fused): the
        suffix past it resimulates in the same adopt dispatch."""
        from .beam import match_beam_longest

        anchor_frame, beam_inputs, _ = self._spec
        shift = load_frame - anchor_frame
        if shift < 0 or shift >= beam_inputs.shape[1]:
            return None
        # a disconnected player's dummy inputs were not speculated: the
        # adopted prefix must stop before the first disconnect row (the
        # resimulated suffix handles them like any plain tick)
        clean = 0
        while clean < count and (
            statuses[clean] < int(InputStatus.DISCONNECTED)
        ).all():
            clean += 1
        if clean == 0:
            return None
        prefix_rows = []
        for j in range(shift):
            rec = self._played.get(anchor_frame + j)
            if rec is None:
                return None
            pin, pst = rec
            if (pst >= int(InputStatus.DISCONNECTED)).any():
                return None
            prefix_rows.append(pin)
        prefix = (
            np.stack(prefix_rows)
            if prefix_rows
            else np.zeros((0,) + inputs.shape[1:], dtype=np.uint8)
        )
        matched, member = match_beam_longest(
            beam_inputs, prefix, inputs[:clean]
        )
        if member is None or matched == 0:
            return None
        return (member, shift, matched)

    def flush(self) -> None:
        """Dispatch buffered lazy ticks as ONE fused multi-tick program
        (no-op when the buffer is empty or lazy_ticks is 0). Pads to the
        configured buffer depth with no-op rows so one length compiles
        once; materializes the future checksum batch the buffered saves'
        cells already hold. A single-row buffer dispatches through the
        plain (warmup-compiled) tick program instead — a flush-heavy
        configuration (e.g. beam speculation forcing a flush every tick)
        then pays the one-tick program, not the T-deep scan, and never a
        mid-session compile."""
        rows, future = self._tick_rows, self._tick_future
        n_staged = self._multi_count
        if not rows and not n_staged:
            return
        if GLOBAL_TELEMETRY.enabled:
            self._m_batch.observe(n_staged or len(rows))
        self._tick_rows = []
        self._tick_future = None
        # depth routing: scan only the variant covering the buffer's
        # deepest row (None = the full-window reference program)
        max_active = (
            self._buffered_last_active
            if self.depth_routing and self._buffered_last_active
            else None
        )
        self._buffered_last_active = 0
        core = self.core
        if n_staged:  # async: rows were packed straight into the pool
            buf = self._multi_active
            self._multi_active = None
            self._multi_count = 0
            if n_staged == 1:
                with GLOBAL_TRACER.span("tpu/fused_tick", absolute=True):
                    his, los = core.tick_row(buf[0], max_active)
            else:
                buf[n_staged:] = self._pad_row
                with GLOBAL_TRACER.span("tpu/fused_multi_tick", absolute=True):
                    his, los = core.tick_multi(buf, last_active=max_active)
        elif len(rows) == 1:
            with GLOBAL_TRACER.span("tpu/fused_tick", absolute=True):
                his, los = core.tick_row(rows[0], max_active)
        else:
            # eager mode has no fence bounding when a dispatch's read of
            # host memory retires (jax may alias aligned buffers), so the
            # staging is allocated fresh per flush
            buf = np.tile(core.pad_tick_row(), (self.lazy_ticks, 1))
            for j, r in enumerate(rows):
                buf[j] = r
            with GLOBAL_TRACER.span("tpu/fused_multi_tick", absolute=True):
                his, los = core.tick_multi(buf, last_active=max_active)
        self._note_inflight(his)
        future.batch = _ChecksumBatch(his, los, self.ledger)

    def _acquire_multi_buf(self) -> np.ndarray:
        """The active [lazy_ticks, L] staging buffer the async lazy path
        packs tick rows into directly (pack_tick_row_into). Rotates
        async_inflight + 1 pooled buffers — reuse is safe because the
        fence proves the dispatch that read a buffer retired before the
        pool comes back around. Rows past the staged count keep stale
        bytes until flush() pads the tail."""
        if self._multi_active is not None:
            return self._multi_active
        if self._multi_bufs is None:
            pad = self.core.pad_tick_row()
            self._multi_bufs = [
                np.tile(pad, (self.lazy_ticks, 1))
                for _ in range(self.async_inflight + 1)
            ]
            self._pad_row = pad
        self._multi_flip = (self._multi_flip + 1) % len(self._multi_bufs)
        self._multi_active = self._multi_bufs[self._multi_flip]
        return self._multi_active

    def _ranked_predictions(self, anchor: Frame, rollout: int, width: int):
        """Likelihood-ranked (player, offset, value_row) switch specs for
        branching_beam's prediction stream. The per-player hazard clock
        starts at the CONFIRMED frontier — rows played after it repeat the
        last confirmed value by prediction, so the real switch (the thing
        a rollback corrects) can land at any not-yet-confirmed frame.
        Frontier and run length come from the recorded play-time statuses
        in _played; frames confirmed only implicitly (predicted, never
        corrected) keep the frontier conservative, which merely shifts
        probability toward earlier offsets."""
        frontiers = []
        for p in range(self.num_players):
            frontier = None
            for f in range(self.current_frame - 1, -1, -1):
                rec = self._played.get(f)
                if rec is None:
                    break
                if rec[1][p] == int(InputStatus.CONFIRMED):
                    frontier = f
                    break
            if frontier is None:
                frontiers.append(None)
                continue
            value = self._played[frontier][0][p].tobytes()
            run = 1
            f = frontier - 1
            while f >= 0:
                rec = self._played.get(f)
                if (
                    rec is None
                    or rec[1][p] != int(InputStatus.CONFIRMED)
                    or rec[0][p].tobytes() != value
                ):
                    break
                run += 1
                f -= 1
            frontiers.append((frontier, value, run))
        if all(fr is None for fr in frontiers):
            return None
        # cap the model's share at ~2/3 of the branch members: the
        # ranked specs come first, but the uniform offset families and
        # XOR novel-value perturbations must keep guaranteed coverage —
        # a confidently wrong model (opponent switches to a value the
        # transition table has never seen) would otherwise monopolize
        # every member and turn recoverable partial hits into full misses
        preds = self.input_model.rank_branches(
            frontiers, anchor, rollout,
            limit=max((width - 1) * 2 // 3, 1),
        )
        return preds or None

    def _launch_speculation(self, load: Optional[LoadGameState],
                            start_frame: Frame, count: int,
                            width: Optional[int] = None) -> None:
        """Anchor one frame DEEPER than the observed rollback depth
        predicts for the next tick, so the next load lands at shift 1 and
        depth jitter of ±1 still falls inside the member window (the
        shift-flexible match absorbs it). The anchor's snapshot is in the
        ring by dense-saving construction. Candidate scripts branch between
        each player's last and previous-distinct inputs at every plausible
        offset (see beam.branching_beam); member 0 is the reference's
        repeat-last prediction. `width` (default: the full beam_width) is
        the adaptive gate's launch width — the history width rolls out
        member 0 alone at 1/B the rollout FLOPs."""
        from .beam import branching_beam

        core = self.core
        if count == 0:
            return
        if width is None:
            width = self.beam_width
        # the rollout anchors on a ring snapshot: buffered ticks must land
        self.flush()
        current_after = start_frame + count
        anchor = current_after - self._depth
        # the anchor snapshot must still be live in the ring (and a frame
        # that actually exists)
        anchor = max(anchor, current_after - core.max_prediction, 0)
        anchor = min(anchor, current_after - 1)
        # consecutive depths coalesce to one length (5,5,7,7,...) so jit
        # compiles O(1) rollout-length variants as the depth jitters
        rollout = min(self._depth + 3 + (self._depth & 1), core.window)
        # pin known history (beam.branching_beam): the frames between the
        # anchor and now were already played, and their rows are recorded —
        # local inputs and confirmed remote inputs are ground truth every
        # member must reproduce verbatim (the played-prefix compatibility
        # check rejects anything else), while unconfirmed remote
        # predictions are exactly the cells worth branching on. Without
        # the pin, the local player's newest input (already folded into
        # _last_inputs) stamps over prefix frames where the old value was
        # played, and every family member dies on the prefix check.
        S = current_after - anchor
        base_rows = np.empty((S,) + self._last_inputs.shape, dtype=np.uint8)
        fixed = np.empty((S, self.num_players), dtype=bool)
        for j in range(S):
            rec = self._played.get(anchor + j)
            if rec is None:  # GC'd past the horizon: no context to pin
                base_rows = fixed = None
                break
            pin, pst = rec
            base_rows[j] = pin
            fixed[j] = pst != int(InputStatus.PREDICTED)
        beam_inputs = branching_beam(
            self._last_inputs,
            self._prev_inputs,
            core.window,
            width,
            # branches must cover prefix + script anywhere the rollout can
            # be matched (offset 0 first: the likeliest switch point)
            max_offset=rollout,
            base_rows=base_rows,
            fixed=fixed,
            # only full-width launches carry branch members; history
            # launches (width-1 / replicated member 0) would discard the
            # ranking, so don't pay the host-side scoring for them
            predictions=(
                self._ranked_predictions(anchor, rollout, width)
                if width == self.beam_width
                else None
            ),
        )
        if width != self.beam_width and width > 1:
            # sharded history launch: the minimal legal width is the beam
            # axis, but a history launch means MEMBER 0 SEMANTICS — so
            # replicate member 0 across the shard axis instead of letting
            # branching_beam fill the extra slots with branch candidates.
            # A serve from this launch then always attributes as a
            # member-0 (history) serve, matching what the launch paid for
            # (r4 advisor: branch serves from a history launch reopened
            # full width while crediting history-launch cost)
            beam_inputs[1:] = beam_inputs[0]
        # roll out only as deep as a rollback can reach while this
        # speculation stands (shift ~1 + depth + reuse/growth margin): on
        # big worlds the speculation's B*L step cost is the beam's
        # overhead, so L tracks need, not the window
        beam_inputs = beam_inputs[:, :rollout]
        beam_statuses = np.zeros(
            (width, rollout, self.num_players), dtype=np.int32
        )
        with GLOBAL_TRACER.span("tpu/beam_speculate", absolute=True):
            spec = core.speculate(anchor % core.ring_len, beam_inputs, beam_statuses)
        self._spec = (anchor, beam_inputs, spec)
        self._spec_consulted = False
        self._spec_tick = self._tick_index
        self._launches_since_consult += 1

    # ------------------------------------------------------------------

    def reset(self) -> None:
        """Fresh-session state without recompilation: the core returns to
        its initial world/ring, every counter and speculation artifact
        clears, but compiled programs and the measured speculation cost
        survive — back-to-back sessions (benchmark arms, rematches) skip
        the seconds-long compiles a new backend would pay."""
        # materialize any staged lazy ticks first: cells from the old
        # session already hold this buffer's future checksums, and an
        # orphaned future would turn their later reads into errors
        self.flush()
        self.core.reset()
        self.current_frame = 0
        self.ledger = ChecksumLedger()
        self._inflight.clear()
        self.dispatch_signatures.clear()
        self._spec = None
        self._last_segment = None
        self.beam_hits = 0
        self.beam_partial_hits = 0
        self.beam_misses = 0
        self.beam_gated = 0
        self.beam_history_launches = 0
        self.rollback_frames = 0
        self.rollback_frames_adopted = 0
        self._last_inputs[:] = 0
        self._prev_inputs[:] = 0
        self._played.clear()
        # the input model SURVIVES reset on purpose: hold/transition
        # statistics describe the players, not the session — a rematch
        # (or a benchmark arm) keeps what it learned, exactly like the
        # measured speculation costs. Frame bookkeeping restarts; the
        # jump-past-gap guard severs runs at the discontinuity.
        for p in range(self.num_players):
            self.input_model.break_run(p)
        self._finalized_to = -1
        self._depth = 2
        self._idle_ema_s = None
        self._last_tick_end = None
        self._launch_value.clear()
        self._spec_consulted = False
        self._launches_since_consult = 0
        self._value_gated_streak = 0
        self._tick_index = 0
        self._spec_tick = -10

    def warmup(self) -> None:
        """Compile every device program this backend can dispatch (tick,
        speculation, adoption) before entering a real-time loop: first
        compilation takes seconds — enough to trip peers' disconnect
        timeouts mid-session. Game state is left untouched."""
        with warmup_scope("TpuRollbackBackend.warmup"):
            self._warmup_impl()

    def _warmup_impl(self) -> None:
        import jax.numpy as jnp

        core = self.core
        W, P, I = core.window, self.num_players, self.input_size
        inputs = np.zeros((W, P, I), dtype=np.uint8)
        statuses = np.zeros((W, P), dtype=np.int32)
        scratch = np.full((W,), core.scratch_slot, dtype=np.int32)
        # tick/adopt DONATE their ring+state buffers (invalidated on real
        # devices), so both must be deep-copied before the dummy dispatches
        # and restored after
        ring0 = jax.tree.map(jnp.copy, core.ring)
        state0 = jax.tree.map(jnp.copy, core.state)
        core.tick(False, 0, inputs, statuses, scratch, 0)
        if core._tick_branchless_fn is not None:
            # row-content routing sends rollback rows to the branchless
            # program at a depth-coalesced slot variant — compile EVERY
            # variant, or the first rollback of a new depth pays the
            # mid-session compile stall warmup exists to prevent
            for v in core.branchless_variants():
                core.tick(True, 0, inputs, statuses, scratch, v)
            if core._t1_windowed:
                # trivial rows dispatch the WINDOWED cond program here
                # (the tick above compiled it at the smallest variant),
                # which leaves the full cond program cold — keep it
                # compiled too: it is still the route for full-depth
                # variants and the bit-parity reference, and a cold
                # program is a landmine
                row0 = core.pack_tick_row(
                    False, 0, inputs, statuses, scratch, 0
                )
                core.ring, core.state, core.verify, _, _ = core._tick_fn(
                    core.ring, core.state, row0, core.verify
                )
        if self.lazy_ticks:
            # compile the fused multi-tick program at the buffer depth
            # (all-padding rows: a true no-op on the game state). With
            # depth routing the live flush dispatches one scan body per
            # depth variant — compile EVERY variant, or the first flush
            # of a new max depth pays the mid-session compile stall
            # warmup exists to prevent. The pallas tick kernel route
            # (rows > 1) is depth-flat: one compile covers it.
            pad = np.tile(core.pad_tick_row(), (self.lazy_ticks, 1))
            if (
                self.depth_routing
                and self.lazy_ticks > 1
                and core._tick_pallas_fn is None
            ):
                for v in core.branchless_variants():
                    core.tick_multi(pad, last_active=v)
            core.tick_multi(pad)
        if self.beam_width:
            from .beam import branching_beam

            # compile EVERY (width, rollout length) the live path can
            # dispatch — widths: the full beam and the adaptive gate's
            # history-only width; lengths: depth coalescing yields
            # 5, 7, 9, ... up to the window. A mid-session width or depth
            # change must not pay the seconds-long speculate/adopt compile
            # stall warmup exists to prevent (adopt's jit keys on the
            # trajectory's member-axis shape, so BOTH widths need it)
            rollouts = sorted(
                {min(d + 3 + (d & 1), W) for d in range(1, W + 1)}
            )
            # only the adaptive gate ever dispatches the history width;
            # with gate='always' compiling+timing it would roughly double
            # warmup's beam section (seconds of compile per program) for
            # programs that never run (r4 advisor)
            widths = (
                sorted({self.beam_width, self._history_width})
                if self.speculation_gate == "adaptive"
                else [self.beam_width]
            )
            beams = {
                width: branching_beam(
                    np.zeros((P, I), dtype=np.uint8),
                    np.zeros((P, I), dtype=np.uint8),
                    W,
                    width,
                )
                for width in widths
            }
            for width in widths:
                for rollout in rollouts:
                    beam_statuses = np.zeros(
                        (width, rollout, P), dtype=np.int32
                    )
                    spec = core.speculate(
                        0, beams[width][:, :rollout], beam_statuses
                    )
                    # full hits route to the branchless adopt program and
                    # partial hits to the cond one (ResimCore.adopt):
                    # compile BOTH, or the first live partial hit pays a
                    # mid-session compile
                    core.adopt(spec, 0, 0, scratch, 1)
                    core.adopt(
                        spec, 0, 0, scratch, 2,
                        inputs=inputs, statuses=statuses, matched=1,
                    )
            # measure the post-compile speculation cost PER WIDTH for the
            # adaptive gate's budget conditions: a few amortized
            # dispatches at the mid rollout length, closed by a barrier
            rollout = rollouts[len(rollouts) // 2]
            costs = {}
            for width in widths:
                beam_statuses = np.zeros((width, rollout, P), dtype=np.int32)
                spec = core.speculate(
                    0, beams[width][:, :rollout], beam_statuses
                )
                jax.block_until_ready(spec[1])
                n = 10
                t0 = time.perf_counter()
                for _ in range(n):
                    spec = core.speculate(
                        0, beams[width][:, :rollout], beam_statuses
                    )
                jax.block_until_ready(spec[1])
                costs[width] = (time.perf_counter() - t0) / n
            self._spec_cost_s = costs[self.beam_width]
            # None when the history width wasn't timed (gate != adaptive);
            # _launch_width's conservative fallback covers that case
            self._spec_hist_cost_s = costs.get(self._history_width)
        core.ring, core.state = ring0, state0
        self.block_until_ready()

    def check(self) -> None:
        """Fetch the device-verify verdict (one small readback); raises
        MismatchedChecksum on the first recorded divergence. Requires
        device_verify=True."""
        from ..errors import MismatchedChecksum

        self.flush()
        mismatch, frame = self.core.check_device_verdict()
        if mismatch:
            raise MismatchedChecksum(frame)

    def state_numpy(self):
        """Host copy of the live game state (parity checks / rendering)."""
        self.flush()
        return self.core.fetch_state()

    def block_until_ready(self) -> None:
        self.flush()
        jax.block_until_ready(self.core.state)
        self._inflight.clear()  # everything older than the state retired

    # ------------------------------------------------------------------
    # durable checkpoint/resume (beyond the reference, SURVEY.md §5)
    # ------------------------------------------------------------------

    def save(self, path: str) -> None:
        from ..utils.checkpoint import save_device_checkpoint

        self.flush()
        tree = {"ring": self.core.ring, "state": self.core.state}
        if self.core.device_verify:
            # the accumulated first-seen history + mismatch latch resume
            # with the run: without it a restored device-verify run would
            # silently restart its history (and check() would trip on the
            # missing pytree)
            tree["verify"] = self.core.verify
        save_device_checkpoint(
            path,
            tree,
            {
                "kind": "TpuRollbackBackend",
                "current_frame": self.current_frame,
                "max_prediction": self.core.max_prediction,
                "num_players": self.num_players,
                "beam_width": self.beam_width,
                "device_verify": self.core.device_verify,
                # performance knobs ride the checkpoint too: a restored
                # backend must run with the characteristics of the session
                # that saved it, not silently revert to defaults (r3
                # advisor)
                "lazy_ticks": self.lazy_ticks,
                "async_dispatch": self.async_dispatch,
                "async_inflight": self.async_inflight,
                "depth_routing": self.depth_routing,
                "speculation_gate": self.speculation_gate,
                "defer_speculation": self.defer_speculation,
                "spec_backend": self.core.spec_backend,
                "tick_backend": self.core.tick_backend,
            },
        )

    @classmethod
    def restore(cls, path: str, game, mesh=None) -> "TpuRollbackBackend":
        from ..utils.checkpoint import load_device_checkpoint

        tree, meta = load_device_checkpoint(path)
        assert meta["kind"] == "TpuRollbackBackend"
        # saved backends resolved concrete spec/tick backends; a restore
        # onto a different topology (e.g. sharded -> unsharded or another
        # platform) may not support them, so restore the knob as a REQUEST
        # ("auto" when the checkpoint predates the fields) and let the
        # constructor re-resolve — the durable bits are the ring/state,
        # which are backend-agnostic by the bit-parity contract
        def _backend_knob(key):
            # "xla" is honored everywhere; a saved "pallas*" re-resolves
            # via "auto" (picks pallas wherever the restored platform and
            # mesh support it, xla otherwise)
            return "xla" if meta.get(key) == "xla" else "auto"

        backend = cls(
            game,
            max_prediction=meta["max_prediction"],
            num_players=meta["num_players"],
            beam_width=meta.get("beam_width", 0),
            mesh=mesh,
            device_verify=meta.get("device_verify", False),
            lazy_ticks=meta.get("lazy_ticks", 0),
            async_dispatch=meta.get("async_dispatch", False),
            async_inflight=meta.get("async_inflight", 2),
            depth_routing=meta.get("depth_routing", True),
            speculation_gate=meta.get("speculation_gate", "always"),
            defer_speculation=meta.get("defer_speculation", False),
            spec_backend=_backend_knob("spec_backend"),
            tick_backend=_backend_knob("tick_backend"),
        )
        # re-place onto the freshly-built core's shardings (sharded under a
        # mesh, single-device otherwise) — checkpoints are layout-agnostic
        backend.core.ring = jax.device_put(
            tree["ring"], jax.tree.map(lambda a: a.sharding, backend.core.ring)
        )
        backend.core.state = jax.device_put(
            tree["state"], jax.tree.map(lambda a: a.sharding, backend.core.state)
        )
        if meta.get("device_verify", False):
            backend.core.verify = jax.device_put(
                tree["verify"],
                jax.tree.map(lambda a: a.sharding, backend.core.verify),
            )
        backend.current_frame = meta["current_frame"]
        return backend


class MultiSessionDeviceCore:
    """N independent session worlds stacked on a leading `session` axis of
    one device-resident pytree, ticked by ONE fused cross-session
    megabatch dispatch — the batch-across-sessions entry point behind
    ggrs_tpu.serve.SessionHost.

    Every hosted session keeps the exact request/cell contract of
    TpuRollbackBackend (ordered Save/Load/Advance lists in, SnapshotRefs
    and lazy checksums out), but instead of one device dispatch per
    session per tick, the host collects each ready session's packed
    control row and executes them all as one program: gather the active
    slots' (ring, state) from the stacked pytrees, vmap the
    single-session packed tick over them, scatter the results back.
    Rows are DATA (the packed control-word layout), so sessions at
    different frames, mid-rollback or freshly attached all ride the same
    jitted program — only the row count shapes the jit key, and it pads
    to a small set of bucket sizes so the cache stays bounded at
    O(len(buckets)) programs regardless of fleet churn.

    Capacity is fixed at construction; slot `capacity` is a dummy world
    that padding rows no-op tick against (pad rows skip every save and
    advance, so the dummy never changes and duplicate pad scatters write
    identical values)."""

    def __init__(self, game, max_prediction: int, num_players: int,
                 capacity: int, *, async_inflight: int = 4,
                 plan_cache: Optional[DispatchPlanCache] = None,
                 buckets: Optional[Sequence[int]] = None,
                 depth_buckets: Optional[Sequence[int]] = None,
                 depth_routing: bool = True, speculation: bool = False,
                 sdc_audit: bool = False):
        """`num_players` is the HOST-WIDE player layout (the widest
        session the host admits): every hosted session's rows are packed
        at this width, with absent players padded as DISCONNECTED so the
        game model substitutes its deterministic dummy input — both peers
        of a match pad identically, so checksums still agree.

        `buckets`: megabatch row-count pad targets (default: powers of
        two up to capacity, plus capacity itself).

        `depth_buckets`: windowed-program pad targets for the 1-based
        last-active slot (default: powers of two up to the window, plus
        the window). A workload that only ever dispatches known shapes —
        the RL env, whose rows are zero-rollback steps plus last_active=1
        snapshot/restore rows — can restrict the grid (e.g. `(2,)`) so
        warmup compiles a fraction of the programs and the jit budget
        shrinks to match; `depth_bucket_for` raises past the coverage.

        `speculation`: enable the SPECULATIVE BUBBLE-FILLING programs —
        `draft()` rolls input-starved slots' futures forward from a ring
        anchor as a vmapped batch (a ring-parked branch: per-frame
        trajectories + checksums off to the side, confirmed state never
        touched), and `adopt_slot()` serves (a prefix of) a later session
        tick row from a standing draft through the proven
        ResimCore._adopt_impl route — one adopt instead of a full-window
        resim; the mispredicted suffix resimulates inside the same
        dispatch. One draft + one adopt program per row bucket, compiled
        at warmup and counted in dispatch_bucket_budget().

        `depth_routing`: dispatch one vmapped program per (row-count
        bucket x depth bucket) instead of always vmapping the full-window
        tick — under vmap the per-slot lax.cond lowers to selects, so a
        zero-rollback row in a full-window program executes the same
        device work as an 8-frame rollback. Depth buckets are powers of
        two up to the window (the jit cache stays
        O(log capacity x log window) programs), plus a dedicated
        ZERO-ROLLBACK FAST PATH for rows with no pending misprediction
        (no LoadGameState, i.e. first_incorrect_frame == NULL_FRAME at
        the session): no ring gather/scatter at all — one step, two
        checksums, per-slot ring writes — since those rows dominate real
        traffic. False pins the single full-window program (the parity
        suite's reference arm)."""
        import jax.numpy as jnp
        from collections import deque as _deque

        assert capacity >= 1
        # the template core supplies the packed-row layout and the
        # single-session tick program the megabatch vmaps; its own
        # (single) ring/state are only the stack's init template
        self.core = ResimCore(game, max_prediction, num_players)
        self.capacity = capacity
        self.num_players = num_players
        self.input_size = game.input_size
        self.async_inflight = async_inflight
        self.depth_routing = depth_routing
        self.plan_cache = plan_cache or DispatchPlanCache()
        self.ledger = ChecksumLedger()
        if buckets is None:
            buckets, b = {capacity}, 1
            while b < capacity:
                buckets.add(b)
                b *= 2
        self.buckets = tuple(sorted(set(buckets)))
        assert self.buckets[-1] >= capacity, (
            "largest bucket must cover a full-capacity megabatch"
        )
        # depth-bucket pad targets for the windowed megabatch program:
        # powers of two up to the window, window included — O(log W)
        # programs per row bucket
        W = self.core.window
        if depth_buckets is None:
            depths, d = {W}, 2
            while d < W:
                depths.add(d)
                d *= 2
        else:
            depths = set(int(d) for d in depth_buckets)
            assert depths and max(depths) <= W
        self.depth_buckets = tuple(sorted(depths))
        # stacked worlds: capacity live slots + >= 1 dummy pad slot (the
        # sharded subclass pads the dummy tail further so the session
        # mesh axis divides the stack, and places the trees on the mesh)
        S = self.stack_slots = self._stack_size()
        self.states = self._place_states(
            jax.tree.map(lambda x: jnp.stack([x] * S), self.core.state)
        )
        self.rings = self._place_rings(
            jax.tree.map(
                lambda x: jnp.zeros((S,) + x.shape, x.dtype), self.core.ring
            )
        )
        # logical slot -> physical stack index (identity on one device;
        # the sharded subclass interleaves live slots across the session
        # mesh shards and spreads the dummy padding, so every shard
        # carries its share of live worlds). `pad_slot` is the PHYSICAL
        # index pad rows no-op against.
        self._init_slot_layout()
        # one pristine world for the masked batch reset (the env
        # workload's auto-reset): built once, passed as a plain argument
        # so the reset program doesn't bake the init state in as a const
        self._init_state = self.core.game.init_state()
        self._dispatch_fn = jax.jit(
            self._dispatch_impl, static_argnums=(4,), donate_argnums=(0, 1)
        )
        self._dispatch_fast_fn = jax.jit(
            self._dispatch_fast_impl, donate_argnums=(0, 1)
        )
        self._reset_mask_fn = jax.jit(
            self._reset_masked_impl, donate_argnums=(0, 1)
        )
        # slot export/import (live migration): the slot index is TRACED
        # data, so one cached program covers every slot — an eager
        # `.at[slot].set` would bake the index in as a constant and pay
        # a fresh XLA compile per distinct migrated slot
        self._export_slot_fn = jax.jit(self._export_slot_impl)
        self._import_slot_fn = jax.jit(
            self._import_slot_impl, donate_argnums=(0, 1)
        )
        self._pad_row = self.core.pad_tick_row()
        # speculative bubble-filling programs (serve/speculation drives
        # them): the draft rollout reads rings only (no donation — the
        # confirmed worlds are reused untouched), the per-slot adopt
        # writes one slot through the proven ResimCore adopt body
        self.speculation = speculation
        self.drafts_launched = 0
        self.spec_adopts = 0
        if speculation:
            self._draft_fn = jax.jit(self._draft_impl)
            self._adopt_slot_fn = jax.jit(
                self._adopt_slot_impl, donate_argnums=(0, 1)
            )
            # draft packed row: [anchor_ring_slot] + statuses[P] +
            # inputs[W * P * I]. The per-player statuses are STATIC for
            # the whole rollout: CONFIRMED for the lane's real players
            # (the drafting contract) and DISCONNECTED for host-layout
            # pad columns, so a narrow session's draft substitutes the
            # same deterministic dummy inputs its resim would
            self._draft_len = (
                1
                + num_players
                + self.core.window * num_players * game.input_size
            )
            self._draft_pad_row = np.zeros((self._draft_len,), np.int32)
            self._draft_stage_pools: dict = {}
        # SDC audit lane (serve/host.py's sampled double-compute): ONE
        # read-only reference program per row bucket — gather sampled
        # slots, replay each from its ring anchor through the
        # full-window parity tick (the depth_routing=False reference),
        # and return the recomputed final-state checksum beside the live
        # world's, so silent corruption in either is a host-visible
        # mismatch. Compiled at warmup, counted in the bucket budget.
        self.sdc_audit = sdc_audit
        if sdc_audit:
            # NO donation: the audit must never touch the worlds it
            # checks — rings/states flow through untouched
            self._audit_fn = jax.jit(self._audit_impl)
        self.audit_dispatches = 0
        # deterministic fault-injection seam (serve/faults.py): consulted
        # at every dispatch/drive entry point BEFORE the program runs and
        # at mailbox staging. None (the default) costs one attribute read.
        self.fault_seam = None
        # device-resident serving loop (attach_mailbox builds all three):
        # the donated [S, K, L] input mailbox and the jitted
        # lax.while_loop virtual-tick driver that consumes it — one host
        # dispatch ticks the whole fleet for up to K virtual ticks
        self.mailbox = None
        self._driver_fn = None
        self._driver_fast_fn = None
        self.driver_dispatches = 0
        self.vticks_executed = 0
        # per-row-bucket pooled (idx, rows) staging, async_inflight + 1
        # deep — the dispatch compaction packs straight into these
        # instead of allocating + re-tiling pad rows every megabatch
        # (rows escape into jax, which may alias aligned host memory;
        # reuse is safe because the fence proves the dispatch that read
        # a buffer retired before the pool rotates back to it)
        self._stage_pools: dict = {}
        # async fence over megabatches: (result handle, live row count);
        # inflight_rows is the host's backpressure signal
        self._inflight: "_deque" = _deque()
        self.inflight_rows = 0
        self.megabatches = 0
        self.rows_dispatched = 0
        _reg = GLOBAL_TELEMETRY.registry
        self._m_batch_rows = _reg.histogram(
            "ggrs_host_megabatch_rows",
            "session tick rows fused into one cross-session dispatch",
            buckets=SESSION_COUNT_BUCKETS,
        )
        self._m_occupancy = _reg.gauge(
            "ggrs_host_megabatch_occupancy",
            "live rows / padded bucket size of the last megabatch",
        )
        self._m_fence_stall = fence_stall_histogram().labels()

    @classmethod
    def create(cls, game, max_prediction: int, num_players: int,
               capacity: int, *, mesh=None, **kw):
        """THE mesh-dispatching factory: `mesh=None` builds a
        single-device core, a session mesh builds
        ShardedMultiSessionDeviceCore — one site for the choice, so the
        host, the env and checkpoint restore can't drift on how the
        knob maps to a core class."""
        if mesh is not None:
            return ShardedMultiSessionDeviceCore(
                game, max_prediction, num_players, capacity,
                mesh=mesh, **kw,
            )
        return MultiSessionDeviceCore(
            game, max_prediction, num_players, capacity, **kw
        )

    # ------------------------------------------------------------------
    # stack-layout hooks (the sharded subclass overrides these three; the
    # dispatch/scheduling machinery above and below is layout-agnostic)
    # ------------------------------------------------------------------

    def _stack_size(self) -> int:
        """Slots in the stacked pytrees: capacity live + the dummy pad
        slot at index `capacity` that padding rows no-op against."""
        return self.capacity + 1

    def _place_states(self, tree):
        """Placement hook for the stacked states (identity on one
        device; the sharded subclass device_puts per the session-axis
        placement policy in parallel/sharded.py)."""
        return tree

    def _place_rings(self, tree):
        """Placement hook for the stacked rings — see `_place_states`."""
        return tree

    def _place_mailbox(self, rows):
        """Placement hook for the [S, K, L] mailbox row ring (identity on
        one device; the sharded subclass splits the slot axis over the
        session mesh via parallel/sharded.shard_mailbox)."""
        return rows

    def _init_slot_layout(self) -> None:
        """Build the logical-slot -> physical-stack-index map. One
        device: identity, the single dummy at index `capacity`. The
        public slot API (dispatch entries, reset/export/import, masks,
        checkpoints) is always LOGICAL; only this layout knows where a
        slot physically lives in the stack."""
        self._phys = np.arange(self.capacity, dtype=np.int32)
        # inverse: physical index -> logical slot (dummies -> capacity,
        # the checkpoint's canonical dummy row)
        self._phys_inverse = np.arange(self.stack_slots, dtype=np.int32)
        self._phys_inverse[self.capacity :] = self.capacity
        self.pad_slot = self.capacity
        self.session_shards = 1

    def shard_of(self, slot: int) -> int:
        """Session-mesh shard a logical slot's world lives on. One
        device: everything is shard 0. The host scheduler's slot->shard
        affinity (admission spreading, lane packing) reads THIS so the
        affinity policy can't drift from the physical layout."""
        return 0

    def phys_index(self, slots) -> np.ndarray:
        """Physical stack indices of logical slots — the gather indices
        any consumer reading `states`/`rings` directly (the env's
        obs/checksum passes) must use instead of the logical slot."""
        return self._phys[np.asarray(slots, dtype=np.int32)]

    # ------------------------------------------------------------------

    def _dispatch_impl(self, rings, states, idx, rows, nslots):
        """Gather [B] session worlds, vmap the packed tick windowed at
        the STATIC depth bucket `nslots` (= the window for the unrouted
        full program), scatter back. Duplicate pad indices (all pointing
        at the dummy slot) compute identical results, so the scatter
        stays deterministic."""
        g_ring = jax.tree.map(lambda a: a[idx], rings)
        g_state = jax.tree.map(lambda a: a[idx], states)

        def one(ring, state, row):
            ring, state, _, his, los = self.core._tick_windowed_impl(
                ring, state, row, {}, nslots
            )
            return ring, state, his, los

        new_ring, new_state, his, los = jax.vmap(one)(g_ring, g_state, rows)
        rings = jax.tree.map(lambda a, b: a.at[idx].set(b), rings, new_ring)
        states = jax.tree.map(
            lambda a, b: a.at[idx].set(b), states, new_state
        )
        return rings, states, his, los

    def _dispatch_fast_impl(self, rings, states, idx, rows):
        """The zero-rollback megabatch program: every row is guaranteed
        (dispatch asserts it) to carry no load, at most one advance and
        no active slot past 1 — the shape of a tick with no pending
        misprediction. So: NO per-row ring gather/scatter (the full
        program moves ring_len+1 world copies per row either way), no
        resim scan — one vmapped step, two checksums (slot 0 pre-step,
        slot 1 post-step for the trailing-save shape) and two masked
        single-slot ring writes addressed directly into the stacked
        rings. Masked (scratch) saves write the slot's OLD value back to
        ring slot 0 — the branchless trick — so even the ring's bytes
        stay bit-identical to the cond program; pad rows (advance 0) are
        inert. Checksums land at window slots 0/1 of a zero [B, W] batch,
        keeping the flat k*W + i indexing."""
        import jax.numpy as jnp

        core = self.core
        W, P, I = core.window, self.num_players, self.input_size
        B = rows.shape[0]

        def where_rows(pred, a, b):
            return jax.tree.map(
                lambda x, y: jnp.where(
                    pred.reshape((-1,) + (1,) * (x.ndim - 1)), x, y
                ),
                a,
                b,
            )

        g_state = jax.tree.map(lambda a: a[idx], states)
        advance = rows[:, 2]
        s0 = rows[:, core._off_save]
        s1 = rows[:, core._off_save + 1]
        statuses0 = rows[:, core._off_status : core._off_status + P]
        inputs0 = (
            rows[:, core._off_input : core._off_input + P * I]
            .astype(jnp.uint8)
            .reshape(B, P, I)
        )
        zero = jnp.uint32(0)
        # slot 0: masked save of the pre-step state
        hi0, lo0 = jax.vmap(core.game.checksum)(g_state)
        do0 = s0 < core.ring_len
        w0 = jnp.where(do0, s0, 0)
        old0 = jax.tree.map(lambda r: r[idx, w0], rings)
        rings = jax.tree.map(
            lambda r, v: r.at[idx, w0].set(v),
            rings,
            where_rows(do0, g_state, old0),
        )
        # the one advance (masked only so pad rows stay inert)
        nxt = jax.vmap(core.game.step)(g_state, inputs0, statuses0)
        new_state = where_rows(advance > 0, nxt, g_state)
        # slot 1: masked trailing save of the post-step state
        hi1, lo1 = jax.vmap(core.game.checksum)(new_state)
        do1 = s1 < core.ring_len
        w1 = jnp.where(do1, s1, 0)
        old1 = jax.tree.map(lambda r: r[idx, w1], rings)
        rings = jax.tree.map(
            lambda r, v: r.at[idx, w1].set(v),
            rings,
            where_rows(do1, new_state, old1),
        )
        states = jax.tree.map(
            lambda a, b: a.at[idx].set(b), states, new_state
        )
        his = jnp.zeros((B, W), dtype=hi0.dtype)
        los = jnp.zeros((B, W), dtype=lo0.dtype)
        his = his.at[:, 0].set(jnp.where(do0, hi0, zero))
        his = his.at[:, 1].set(jnp.where(do1, hi1, zero))
        los = los.at[:, 0].set(jnp.where(do0, lo0, zero))
        los = los.at[:, 1].set(jnp.where(do1, lo1, zero))
        return rings, states, his, los

    def bucket_for(self, n: int) -> int:
        """Smallest configured pad target covering n rows."""
        for b in self.buckets:
            if b >= n:
                return b
        raise ContractViolation(f"{n} rows exceed the largest bucket")

    def depth_bucket_for(self, last_active: int) -> int:
        """Smallest depth-bucket pad target covering a 1-based last
        active slot."""
        for d in self.depth_buckets:
            if d >= last_active:
                return d
        raise ContractViolation(
            f"{last_active} slots exceed the window ({self.core.window})"
        )

    def dispatch_bucket_budget(self) -> int:
        """The jit-cache bound depth routing guarantees: one program per
        (row bucket x depth bucket) plus the fast path per row bucket —
        O(log capacity x log window) — plus, under speculation, one
        draft rollout and one per-slot adopt program per row bucket.
        The soak tests pin the live signature population inside this."""
        base = len(self.buckets) * (len(self.depth_buckets) + 1)
        if self.speculation:
            base += 2 * len(self.buckets)
        if self.sdc_audit:
            # one read-only reference-recompute program per row bucket
            base += len(self.buckets)
        if self.mailbox is not None:
            # resident driver: one windowed variant per depth bucket
            # plus the all-fast variant, plus one commit scatter per
            # pow2 commit bucket
            base += len(self.depth_buckets) + 1
            base += len(self.mailbox.commit_buckets)
        return base

    def megabatch_programs(self) -> List[Tuple[int, Optional[int], int]]:
        """The plan cache's megabatch-program population as structured
        (row_bucket, depth, dispatch_count) records — depth 0 is the
        zero-rollback fast path, an int the windowed depth bucket, None
        the unrouted full-window program. THE accessor for benches,
        gates and tests: the raw signature tuple layout stays private to
        this module (it already changed shape once)."""
        out = []
        for sig, c in self.plan_cache.signatures.items():
            if isinstance(sig, tuple) and sig and sig[0] == "megabatch":
                out.append((sig[1], sig[2] if len(sig) > 2 else None, c))
        return out

    def fast_eligible(
        self, row: np.ndarray, last_active: Optional[int] = None
    ) -> bool:
        """May this packed row ride the zero-rollback fast program? No
        load, exactly one advance, no active slot past 1 (a save of the
        current frame and/or a trailing save of the advanced frame).
        `last_active` (the row's 1-based last active slot) skips the
        save-slot rescan when the caller's parse already knows it."""
        if int(row[0]) != 0 or int(row[2]) != 1:
            return False
        if last_active is None:
            core = self.core
            tail = row[core._off_save + 2 : core._off_status]
            return bool((np.asarray(tail) >= core.ring_len).all())
        return last_active <= 2

    def _acquire_stage(self, bucket: int):
        """Rotate the pooled (idx, rows) staging pair for one row-count
        bucket, restoring pad defaults only over the entries the LAST
        use of this buffer actually wrote (re-tiling the whole pad rows
        every megabatch is exactly the host copy depth bucketing set out
        to remove)."""
        pool = self._stage_pools.get(bucket)
        if pool is None:
            pool = {
                "flip": 0,
                "bufs": [
                    [
                        np.full((bucket,), self.pad_slot, dtype=np.int32),
                        np.tile(self._pad_row, (bucket, 1)),
                        0,  # rows written by this buffer's last use
                    ]
                    for _ in range(self.async_inflight + 1)
                ],
            }
            self._stage_pools[bucket] = pool
        pool["flip"] = (pool["flip"] + 1) % len(pool["bufs"])
        return pool["bufs"][pool["flip"]]

    def dispatch(
        self, entries, *, last_active: Optional[int] = None,
        fast: bool = False,
    ) -> Tuple[_ChecksumBatch, int]:
        """Run one cross-session megabatch. `entries` is a list of
        (slot, packed_row) with AT MOST ONE row per slot — a session's
        second staged row (sparse-saving keepalive) rides the next
        megabatch, preserving its in-session order. Returns
        (checksum_batch, bucket): entry k's window-slot i checksum lives
        at flat index k * window + i of the batch. Non-blocking beyond
        the async-inflight fence.

        Depth routing (the host's scheduler groups rows accordingly):
        `fast=True` runs the zero-rollback program — every row must be
        fast_eligible; `last_active` (the MAX 1-based last active slot
        across the rows) runs the windowed program at the depth bucket
        covering it; neither runs the legacy full-window program."""
        n = len(entries)
        assert 0 < n <= self.capacity
        assert len({slot for slot, _ in entries}) == n, (
            "one row per session slot per megabatch"
        )
        if self.fault_seam is not None:
            # BEFORE any state or staging changes: a raise here leaves
            # the stacked worlds untouched, so the host can retry or
            # re-dispatch survivors bit-exactly
            self.fault_seam.before_dispatch(
                "megabatch", [slot for slot, _ in entries]
            )
        bucket = self.bucket_for(n)
        staged = self._acquire_stage(bucket)
        idx, rows, used = staged
        for k, (slot, row) in enumerate(entries):
            assert 0 <= slot < self.capacity
            idx[k] = self._phys[slot]
            rows[k] = row
        for k in range(n, used):  # re-pad only what the last use dirtied
            idx[k] = self.pad_slot
            rows[k] = self._pad_row
        staged[2] = n
        if fast:
            assert all(
                self.fast_eligible(rows[k]) for k in range(n)
            ), (
                "fast dispatch carries a row with a load, a multi-advance "
                "or a save past window slot 1"
            )
        return self._dispatch_staged(
            staged, n, bucket, last_active=last_active, fast=fast
        )

    def dispatch_rows(
        self, idx_block: np.ndarray, rows_block: np.ndarray, *,
        last_active: Optional[int] = None, fast: bool = False,
    ) -> Tuple[_ChecksumBatch, int]:
        """dispatch() for callers that already hold a whole [n, L] packed
        row block with its [n] slot vector (the batched RL env builds its
        fleet's step rows vectorized): the per-row Python pack loop
        becomes two numpy block copies into the pooled bucket staging.
        Same contract as dispatch() — at most one row per slot, rows are
        host-copied before return, non-blocking beyond the fence."""
        n = int(idx_block.shape[0])
        assert 0 < n <= self.capacity
        assert rows_block.shape[0] == n
        if self.fault_seam is not None:
            self.fault_seam.before_dispatch(
                "megabatch_rows", [int(s) for s in idx_block]
            )
        bucket = self.bucket_for(n)
        staged = self._acquire_stage(bucket)
        idx, rows, used = staged
        idx[:n] = self._phys[idx_block]
        rows[:n] = rows_block
        if used > n:  # re-pad only what the last use dirtied
            idx[n:used] = self.pad_slot
            rows[n:used] = self._pad_row
        staged[2] = n
        if fast:
            # vectorized fast_eligible over the block: no load, exactly
            # one advance, no active slot past 1
            core = self.core
            tail = rows_block[:, core._off_save + 2 : core._off_status]
            assert (
                (rows_block[:, 0] == 0).all()
                and (rows_block[:, 2] == 1).all()
                and (tail >= core.ring_len).all()
            ), (
                "fast dispatch_rows block carries a row with a load, a "
                "multi-advance or a save past window slot 1"
            )
        return self._dispatch_staged(
            staged, n, bucket, last_active=last_active, fast=fast
        )

    def _dispatch_staged(
        self, staged, n: int, bucket: int, *,
        last_active: Optional[int], fast: bool,
    ) -> Tuple[_ChecksumBatch, int]:
        """Common dispatch tail over a filled bucket-staging buffer:
        program selection (fast / windowed depth bucket / full window),
        plan-cache tally, the sanitizer's jit-budget assertion, telemetry
        and the async fence."""
        idx, rows, _used = staged
        if fast:
            sig_depth, nslots, fn_args = 0, 1, ()
            fn = self._dispatch_fast_fn
        elif last_active is not None:
            nslots = self.depth_bucket_for(last_active)
            sig_depth, fn_args = nslots, (nslots,)
            fn = self._dispatch_fn
        else:
            nslots = self.core.window
            sig_depth, fn_args = None, (nslots,)
            fn = self._dispatch_fn
        # each (row bucket, depth bucket) is one cached jitted program:
        # tally it beside the per-row signatures, but OUT of the segment
        # hit/miss counters (a different cache population with its own
        # hit dynamics). sig_depth 0 = the fast path, None = unrouted
        # full window.
        self.plan_cache.note(("megabatch", bucket, sig_depth), metrics=False)
        with transfer_guard_scope("megabatch dispatch"):
            # no-op unless GGRS_SANITIZE armed the sanitizer AND warmup
            # froze it: then an implicit device->host read inside the
            # dispatch (a stray float()/.item() on a live buffer) raises
            # ImplicitHostTransfer with its call site instead of
            # silently serializing the pipeline
            self.rings, self.states, his, los = fn(
                self.rings, self.states, idx, rows, *fn_args
            )
        san = active_sanitizer()
        if san is not None:
            # GGRS_SANITIZE: the megabatch jit cache must stay on the
            # (row bucket x depth bucket) grid — a dispatch that just
            # compiled past the budget names its call site and raises
            # instead of silently growing the cache mid-serve
            san.check_dispatch_budget(
                self._budget_fns(),
                self.dispatch_bucket_budget(),
                context="MultiSessionDeviceCore.dispatch",
            )
        self.megabatches += 1
        self.rows_dispatched += n
        if GLOBAL_TELEMETRY.enabled:
            self._m_batch_rows.observe(n)
            self._m_occupancy.set(n / bucket)
            if fast or last_active is not None:
                # fast dispatches observe depth 1 (the le=1 bucket is
                # exactly the fast-path counter the smoke gate asserts)
                self.core._m_depth.observe(nslots)
                self.core._m_waste.inc((self.core.window - nslots) * n)
        self._note_inflight(his, n)
        return _ChecksumBatch(his, los, self.ledger), bucket

    def _note_inflight(self, handle, n_rows: int) -> None:
        """Same fence discipline as TpuRollbackBackend._note_inflight:
        admit the dispatch, then block on the OLDEST once more than
        async_inflight megabatches are outstanding."""
        self._inflight.append((handle, n_rows))
        self.inflight_rows += n_rows
        while len(self._inflight) > self.async_inflight:
            oldest, rows = self._inflight.popleft()
            self._fence_wait(oldest)
            self.inflight_rows -= rows

    def _fence_wait(self, handle) -> None:
        """Block on device work this core dispatched: the in-flight
        window's oldest entry, or all of it (retire_fence). Timed as a
        fence stall: span tpu/async_fence and ggrs_async_fence_stall_ms."""
        feed = self._m_fence_stall if GLOBAL_TELEMETRY.enabled else None
        with GLOBAL_TRACER.span("tpu/async_fence", absolute=True, feed=feed):
            jax.block_until_ready(handle)

    def poll_retired(self) -> int:
        """Drop already-retired megabatches from the fence without
        blocking; returns the rows still in flight (the host's
        backpressure budget reads this)."""
        while self._inflight and _array_is_ready(self._inflight[0][0]):
            _, rows = self._inflight.popleft()
            self.inflight_rows -= rows
        return self.inflight_rows

    # ------------------------------------------------------------------
    # speculative bubble-filling (serve/speculation.py drives this):
    # draft input-starved slots' futures into the megabatch, adopt on
    # arrival — the serving twin of the TpuRollbackBackend beam
    # ------------------------------------------------------------------

    def _budget_fns(self) -> dict:
        """Every jitted dispatch function whose cache the bucket budget
        bounds — THE one dict the sanitizer's budget assertion checks at
        every dispatch site, so the draft/adopt programs can never grow
        the cache invisibly."""
        fns = {
            "_dispatch_impl": self._dispatch_fn,
            "_dispatch_fast_impl": self._dispatch_fast_fn,
        }
        if self.speculation:
            fns["_draft_impl"] = self._draft_fn
            fns["_adopt_slot_impl"] = self._adopt_slot_fn
        if self.sdc_audit:
            fns["_audit_impl"] = self._audit_fn
        if self.mailbox is not None:
            fns["_driver_impl"] = self._driver_fn
            fns["_driver_fast_impl"] = self._driver_fast_fn
            fns["mailbox._commit_impl"] = self.mailbox._commit_fn
        return fns

    def _draft_impl(self, rings, idx, rows):
        """Vectorized speculative rollout over [B] input-starved slots:
        gather each slot's anchor snapshot from its ring, scan the
        drafted input script forward W frames with each row's STATIC
        per-player statuses — CONFIRMED for real players (the
        statuses_contract='disconnect-only' adoption contract),
        DISCONNECTED for host-layout pad columns — and return
        per-member per-frame trajectories plus
        post-step checksums — a ring-parked branch. rings are READ ONLY
        (no donation): a draft can never clobber confirmed state, and
        the confirmed worlds keep flowing through the ordinary megabatch
        programs while the draft stands."""
        import jax.numpy as jnp

        core = self.core
        W, P, I = core.window, self.num_players, self.input_size
        g_ring = jax.tree.map(lambda a: a[idx], rings)

        def one(ring, row):
            anchor_slot = row[0]
            statuses = row[1 : 1 + P]
            inputs = row[1 + P :].astype(jnp.uint8).reshape(W, P, I)
            anchor = jax.tree.map(
                lambda r: jax.lax.dynamic_index_in_dim(
                    r, anchor_slot, 0, keepdims=False
                ),
                ring,
            )
            a_hi, a_lo = core.game.checksum(anchor)

            def body(s, inp):
                nxt = core.game.step(s, inp, statuses)
                hi, lo = core.game.checksum(nxt)
                return nxt, (nxt, hi, lo)

            _, (traj, his, los) = jax.lax.scan(body, anchor, inputs)
            return traj, his, los, a_hi, a_lo

        return jax.vmap(one)(g_ring, rows)

    def _adopt_slot_impl(self, rings, states, slot, traj, his, los,
                         a_hi, a_lo, packed):
        """Serve one slot's tick row from a standing draft: gather the
        slot's ring, run the proven single-session adopt body (prefix
        states/checksums from the trajectory, mispredicted suffix
        resimulated in the same program), scatter back. packed is the
        ResimCore adopt layout; packed[0] (member) picks the draft-batch
        row this slot owns."""
        member = packed[0]
        ring = jax.tree.map(lambda a: a[slot], rings)
        ring, state, _, out_his, out_los = self.core._adopt_impl(
            ring, traj, his, los,
            jax.lax.dynamic_index_in_dim(a_hi, member, 0, keepdims=False),
            jax.lax.dynamic_index_in_dim(a_lo, member, 0, keepdims=False),
            {}, packed,
        )
        rings = jax.tree.map(lambda a, b: a.at[slot].set(b), rings, ring)
        states = jax.tree.map(
            lambda a, b: a.at[slot].set(b), states, state
        )
        return rings, states, out_his, out_los

    def pack_draft_row_into(self, out: np.ndarray, anchor_slot: int,
                            statuses: np.ndarray,
                            inputs: np.ndarray) -> np.ndarray:
        """Pack one slot's draft row ([anchor_ring_slot] + the static
        per-player i32[P] statuses + the u8[W,P,I] drafted input script)
        into a caller-owned int32 buffer."""
        P = self.num_players
        out[0] = anchor_slot
        out[1 : 1 + P] = statuses
        out[1 + P :] = inputs.reshape(-1)
        return out

    def _acquire_draft_stage(self, bucket: int):
        """Rotate the pooled (idx, rows) draft staging for one row-count
        bucket — the draft twin of _acquire_stage, under the same fence
        reuse guarantee."""
        pool = self._draft_stage_pools.get(bucket)
        if pool is None:
            pool = {
                "flip": 0,
                "bufs": [
                    [
                        np.full((bucket,), self.pad_slot, dtype=np.int32),
                        np.tile(self._draft_pad_row, (bucket, 1)),
                        0,
                    ]
                    for _ in range(self.async_inflight + 1)
                ],
            }
            self._draft_stage_pools[bucket] = pool
        pool["flip"] = (pool["flip"] + 1) % len(pool["bufs"])
        return pool["bufs"][pool["flip"]]

    def draft(self, entries) -> DraftBatch:
        """Launch one speculative draft megabatch: `entries` is a list of
        (slot, draft_row) — at most one per slot — packed into the same
        pow2 row buckets as ordinary dispatches, so the fleet's starved
        lanes fill device bubbles with ONE extra program per bucket.
        Returns the device-resident DraftBatch (member k = entry k);
        non-blocking beyond the async fence, confirmed state untouched."""
        assert self.speculation, "core built without speculation=True"
        n = len(entries)
        assert 0 < n <= self.capacity
        bucket = self.bucket_for(n)
        staged = self._acquire_draft_stage(bucket)
        idx, rows, used = staged
        for k, (slot, row) in enumerate(entries):
            assert 0 <= slot < self.capacity
            idx[k] = self._phys[slot]
            rows[k] = row
        for k in range(n, used):
            idx[k] = self.pad_slot
            rows[k] = self._draft_pad_row
        staged[2] = n
        self.plan_cache.note(("spec_draft", bucket), metrics=False)
        traj, his, los, a_hi, a_lo = self._draft_fn(self.rings, idx, rows)
        san = active_sanitizer()
        if san is not None:
            san.check_dispatch_budget(
                self._budget_fns(),
                self.dispatch_bucket_budget(),
                context="MultiSessionDeviceCore.draft",
            )
        self.drafts_launched += 1
        self._note_inflight(his, n)
        return DraftBatch(traj, his, los, a_hi, a_lo, bucket)

    def adopt_slot(self, slot: int, draft: DraftBatch,
                   packed: np.ndarray) -> _ChecksumBatch:
        """Serve (a prefix of) one session tick row from a standing
        draft instead of dispatching its resim: ring writes and saved
        checksums for the matched prefix come from the draft trajectory,
        the mispredicted suffix resimulates in the same program — a
        misprediction costs an adopt/truncate, never a full-window
        resim. `packed` is ResimCore.pack_adopt_row's layout with
        packed[0] = the slot's member index in `draft`. Returns the [W]
        checksum batch for the row's save bindings (flat index = window
        slot)."""
        assert self.speculation, "core built without speculation=True"
        assert 0 <= slot < self.capacity
        advance_count, matched = int(packed[2]), int(packed[5])
        assert 1 <= matched <= advance_count
        self.plan_cache.note(("spec_adopt", draft.bucket), metrics=False)
        self.rings, self.states, his, los = self._adopt_slot_fn(
            self.rings, self.states, np.int32(self._phys[slot]),
            draft.traj, draft.his, draft.los, draft.a_hi, draft.a_lo,
            packed,
        )
        san = active_sanitizer()
        if san is not None:
            san.check_dispatch_budget(
                self._budget_fns(),
                self.dispatch_bucket_budget(),
                context="MultiSessionDeviceCore.adopt_slot",
            )
        self.megabatches += 1
        self.rows_dispatched += 1
        self.spec_adopts += 1
        if GLOBAL_TELEMETRY.enabled:
            self._m_batch_rows.observe(1)
            # the depth histogram records what the device actually
            # resimulated: the mispredicted suffix (1 on a full hit) —
            # the "adopt, not full-window resim" acceptance surface
            depth = max(advance_count - matched, 1)
            self.core._m_depth.observe(depth)
            self.core._m_waste.inc(self.core.window - depth)
        self._note_inflight(his, 1)
        return _ChecksumBatch(his, los, self.ledger)

    # ------------------------------------------------------------------
    # SDC audit lane (serve/host.py's sampled double-compute drives it)
    # ------------------------------------------------------------------

    def _audit_impl(self, rings, states, idx, rows):
        """Reference recompute over [B] sampled slots, READ-ONLY: gather
        each slot's (ring, state), replay its audit row — load at the
        ring anchor, re-advance the recorded played inputs — through the
        FULL-WINDOW parity tick (the depth_routing=False reference
        program, deliberately a different compiled artifact from the
        fast/driver paths that produced the live bytes), and return the
        replayed final state's checksum beside the live world's. On an
        uncorrupted slot the two agree bitwise by the rollback
        contract; a flipped bit in the live world OR in the anchor ring
        row makes them diverge — either way a host-visible SDC verdict
        within the sampling cadence. Nothing is donated and nothing is
        scattered back: an audit can never perturb the worlds it
        checks."""
        g_ring = jax.tree.map(lambda a: a[idx], rings)
        g_state = jax.tree.map(lambda a: a[idx], states)

        def one(ring, state, row):
            _, replayed, _, _, _ = self.core._tick_windowed_impl(
                ring, state, row, {}, self.core.window
            )
            ref_hi, ref_lo = self.core.game.checksum(replayed)
            live_hi, live_lo = self.core.game.checksum(state)
            # every ring row's checksum recomputed at rest: the host
            # compares them against the values recorded when each row
            # was SAVED, so a bit that flipped in a stored snapshot is
            # caught before a future rollback can load and serve it
            ring_hi, ring_lo = jax.vmap(self.core.game.checksum)(ring)
            return ref_hi, ref_lo, live_hi, live_lo, ring_hi, ring_lo

        return jax.vmap(one)(g_ring, g_state, rows)

    def audit_rows(self, entries):
        """Launch one sampled SDC audit batch: `entries` is a list of
        (slot, packed audit row) — a row whose load slot is the lane's
        last ring anchor and whose advances replay the recorded played
        inputs up to the live frame, saves all scratch. Returns the
        device handles (ref_hi, ref_lo, live_hi, live_lo, ring_hi[R],
        ring_lo[R]), entry k at index k — the host resolves them lazily
        and quarantines any slot whose replay/live pair or recorded
        ring-row checksums mismatch. Pads to the megabatch row buckets;
        non-blocking (no fence admission needed: the audit allocates
        its own staging and touches no donated state)."""
        assert self.sdc_audit, "core built without sdc_audit=True"
        n = len(entries)
        assert 0 < n <= self.capacity
        bucket = self.bucket_for(n)
        # fresh staging per audit: audits are sampled (default one in
        # `sdc_audit_every` host ticks), so this is not a hot path and
        # pooling it would only grow the fence-protected surface
        idx = np.full((bucket,), self.pad_slot, dtype=np.int32)
        rows = np.tile(self._pad_row, (bucket, 1))
        for k, (slot, row) in enumerate(entries):
            assert 0 <= slot < self.capacity
            idx[k] = self._phys[slot]
            rows[k] = row
        self.plan_cache.note(("sdc_audit", bucket), metrics=False)
        out = self._audit_fn(self.rings, self.states, idx, rows)
        san = active_sanitizer()
        if san is not None:
            san.check_dispatch_budget(
                self._budget_fns(),
                self.dispatch_bucket_budget(),
                context="MultiSessionDeviceCore.audit_rows",
            )
        self.audit_dispatches += 1
        return out

    # ------------------------------------------------------------------
    # device-resident serving loop (serve/host.py's resident=True mode
    # drives this): a donated input mailbox the host feeds, and a jitted
    # lax.while_loop virtual-tick driver that consumes it — dispatch
    # cadence drops from one megabatch per host tick to one driver
    # dispatch per K virtual ticks, with checksums accumulating into
    # [K, S, W] output rings harvested lazily behind the async fence
    # ------------------------------------------------------------------

    def attach_mailbox(self, depth: int):
        """Build the device-resident input mailbox (tpu/mailbox.py) and
        the virtual-tick driver programs. `depth` = K, the maximum
        virtual ticks one driver dispatch executes per lane. Call before
        warmup() so the driver variants compile with the megabatch
        grid."""
        import jax

        from .mailbox import DeviceMailbox

        assert self.mailbox is None, "mailbox already attached"
        self.mailbox = DeviceMailbox(self, depth)
        self._driver_fn = jax.jit(
            self._driver_impl, static_argnums=(5,), donate_argnums=(0, 1)
        )
        self._driver_fast_fn = jax.jit(
            self._driver_fast_impl, donate_argnums=(0, 1)
        )
        return self.mailbox

    def _driver_impl(self, rings, states, mbox_rows, marks, vt_fast,
                     nslots):
        """The virtual-tick driver: a lax.while_loop over the mailbox's
        vtick axis, each iteration ticking the WHOLE stack — rollback
        rows load and resimulate in-loop, exactly the single-session
        tick body, without returning to Python between virtual ticks.
        Lane s consumes rows for vticks [0, marks[s]); rows above a
        lane's watermark (and every pad slot's rows) mask to the inert
        pad row, so lanes at different fill depths ride one program. The
        loop exits at the deepest watermark: a half-full mailbox pays
        for the vticks it actually has, not for K.

        Per-vtick depth routing rides INSIDE the loop: `vt_fast[t]`
        (host-computed: every row staged at vtick t was fast-eligible)
        conds each iteration between the vmapped zero-rollback fast step
        and the vmapped windowed scan at the STATIC depth bucket
        `nslots` — XLA executes only the taken branch, so one rollback
        row costs its own vtick the windowed scan, not the whole cycle.
        Bit-identical either way (the fast/windowed contract the
        megabatch depth routing already pins). Checksums land in
        [K, S, W] output rings (flat index j * S * W + s * W + i),
        harvested lazily by the host."""
        import jax.numpy as jnp

        K, S = mbox_rows.shape[1], mbox_rows.shape[0]
        W = self.core.window
        pad = jnp.asarray(self._pad_row)
        limit = jnp.max(marks)

        def one(ring, state, row):
            ring, state, _, hi, lo = self.core._tick_windowed_impl(
                ring, state, row, {}, nslots
            )
            return ring, state, hi, lo

        def cond(carry):
            return carry[0] < limit

        def body(carry):
            t, rings, states, his, los = carry
            rows_t = jax.lax.dynamic_index_in_dim(
                mbox_rows, t, 1, keepdims=False
            )
            valid = t < marks
            rows_t = jnp.where(valid[:, None], rows_t, pad[None, :])

            def fast_branch(args):
                rings, states = args
                return jax.vmap(self.core._tick_fast_impl)(
                    rings, states, rows_t
                )

            def windowed_branch(args):
                rings, states = args
                return jax.vmap(one)(rings, states, rows_t)

            rings, states, hi, lo = jax.lax.cond(
                vt_fast[t], fast_branch, windowed_branch, (rings, states)
            )
            his = jax.lax.dynamic_update_index_in_dim(his, hi, t, 0)
            los = jax.lax.dynamic_update_index_in_dim(los, lo, t, 0)
            return t + 1, rings, states, his, los

        his = jnp.zeros((K, S, W), dtype=jnp.uint32)
        los = jnp.zeros((K, S, W), dtype=jnp.uint32)
        _, rings, states, his, los = jax.lax.while_loop(
            cond, body, (jnp.int32(0), rings, states, his, los)
        )
        return rings, states, his, los

    def _driver_fast_impl(self, rings, states, mbox_rows, marks):
        """The driver's zero-rollback variant: when EVERY row of the fill
        cycle is fast-eligible (no load, one advance, no save past
        window slot 1 — the dominant live traffic), each iteration
        vmaps the per-slot zero-rollback fast tick
        (ResimCore._tick_fast_impl, the in-loop twin of the megabatch
        fast program) instead of the windowed scan body. Bit-identical
        to the windowed driver on eligible rows — masked saves write the
        old ring value back, pad rows are inert — by the same contract
        the megabatch fast path pins."""
        import jax.numpy as jnp

        K, S = mbox_rows.shape[1], mbox_rows.shape[0]
        W = self.core.window
        pad = jnp.asarray(self._pad_row)
        limit = jnp.max(marks)

        def cond(carry):
            return carry[0] < limit

        def body(carry):
            t, rings, states, his, los = carry
            rows_t = jax.lax.dynamic_index_in_dim(
                mbox_rows, t, 1, keepdims=False
            )
            valid = t < marks
            rows_t = jnp.where(valid[:, None], rows_t, pad[None, :])
            rings, states, hi, lo = jax.vmap(self.core._tick_fast_impl)(
                rings, states, rows_t
            )
            his = jax.lax.dynamic_update_index_in_dim(his, hi, t, 0)
            los = jax.lax.dynamic_update_index_in_dim(los, lo, t, 0)
            return t + 1, rings, states, his, los

        his = jnp.zeros((K, S, W), dtype=jnp.uint32)
        los = jnp.zeros((K, S, W), dtype=jnp.uint32)
        _, rings, states, his, los = jax.lax.while_loop(
            cond, body, (jnp.int32(0), rings, states, his, los)
        )
        return rings, states, his, los

    def stage_mailbox_row(self, slot: int, row: np.ndarray, *,
                          last_active: int, fast: bool):
        """Append one LOGICAL slot's packed tick row to the mailbox fill
        cycle; returns (checksum batch, base index) for the row's save
        bindings. A full lane — the host outran the virtual-tick depth —
        degrades to an EXTRA driver dispatch (counted in
        ggrs_mailbox_overflow_total), never a dropped input."""
        mbox = self.mailbox
        phys = int(self._phys[slot])
        storm = (
            self.fault_seam is not None and self.fault_seam.on_stage(phys)
        )
        if mbox.lane_full(phys) or storm:
            # a real full lane and an injected overflow storm take the
            # same path: degrade to an extra drive, never drop the row
            mbox.note_overflow()
            self.drive_mailbox()
        return mbox.stage(phys, row, last_active, fast)

    def commit_mailbox(self) -> None:
        """Land every row staged since the last commit on the device in
        ONE batched scatter (the host's one mailbox transfer per host
        tick); admits the write to the async fence so the pooled commit
        staging is provably reusable."""
        mbox = self.mailbox
        if mbox is None or mbox.staged_count == 0:
            return
        handle = mbox.commit()
        self._note_inflight(handle, 0)

    def drive_mailbox(self):
        """Consume the mailbox with ONE virtual-tick driver dispatch:
        commit any uncommitted rows, route the cycle to the fast or the
        depth-bucketed windowed driver variant, and fulfill the cycle's
        future checksum batch from the [K, S, W] output rings. Returns
        the batch (None when the mailbox is empty). Non-blocking beyond
        the async fence — the harvest stays lazy."""
        mbox = self.mailbox
        if mbox is None or (mbox.pending_rows == 0 and mbox.staged_count == 0):
            return None
        if self.fault_seam is not None:
            # every lane with rows this drive would execute, as LOGICAL
            # slots — consulted before commit/take so a raise leaves the
            # cycle intact for the host's retry/containment ladder
            phys_live = set(np.nonzero(mbox._counts)[0].tolist())
            phys_live.update(p for p, _, _ in mbox._staged)
            slots = sorted(
                int(self._phys_inverse[p])
                for p in phys_live
                if int(self._phys_inverse[p]) < self.capacity
            )
            self.fault_seam.before_dispatch("resident_drive", slots)
        self.commit_mailbox()
        marks, n_rows, max_la, all_fast, vt_fast, future = mbox.take_cycle()
        with transfer_guard_scope("resident drive"):
            # guards the driver dispatch only: `marks` is the mailbox's
            # host-side counts copy, so the `int(marks.max())` readback
            # below is host math, not a device sync
            if all_fast:
                nslots = 1
                self.plan_cache.note(("resident_drive", 0), metrics=False)
                self.rings, self.states, his, los = self._driver_fast_fn(
                    self.rings, self.states, mbox.rows_dev, marks
                )
            else:
                nslots = self.depth_bucket_for(max_la)
                self.plan_cache.note(
                    ("resident_drive", nslots), metrics=False
                )
                self.rings, self.states, his, los = self._driver_fn(
                    self.rings, self.states, mbox.rows_dev, marks, vt_fast,
                    nslots,
                )
        san = active_sanitizer()
        if san is not None:
            san.check_dispatch_budget(
                self._budget_fns(),
                self.dispatch_bucket_budget(),
                context="MultiSessionDeviceCore.drive_mailbox",
            )
        vticks = int(marks.max())
        self.driver_dispatches += 1
        self.vticks_executed += vticks
        self.rows_dispatched += n_rows
        if GLOBAL_TELEMETRY.enabled:
            mbox.observe_drive(n_rows, vticks)
            self.core._m_depth.observe(nslots)
            self.core._m_waste.inc((self.core.window - nslots) * n_rows)
        self._note_inflight(his, n_rows)
        batch = _ChecksumBatch(his, los, self.ledger)
        if future is not None:
            future.batch = batch
        return batch

    # ------------------------------------------------------------------
    # slot lifecycle
    # ------------------------------------------------------------------

    def reset_slot(self, slot: int) -> None:
        """Return one session slot to its initial world (attach/evict
        slot reuse): state back to init_state(), ring zeroed. Eager
        per-leaf updates — a lifecycle event, not a hot path."""
        import jax.numpy as jnp

        assert 0 <= slot < self.capacity
        # staged mailbox rows execute BEFORE any slot lifecycle event:
        # a reset must never race rows the ring still owes
        self.drive_mailbox()
        phys = int(self._phys[slot])
        init = self.core.game.init_state()
        self.states = jax.tree.map(
            lambda a, x: a.at[phys].set(x), self.states, init
        )
        self.rings = jax.tree.map(
            lambda a: a.at[phys].set(jnp.zeros(a.shape[1:], a.dtype)),
            self.rings,
        )

    def drop_mailbox_lane(self, slot: int) -> int:
        """QUARANTINE containment (resident mode): discard every row
        LOGICAL slot `slot` still owes the mailbox — its watermark drops
        to zero, so rows already committed to the device ring mask to
        the inert pad row and never execute, and its staged rows never
        commit. Survivor lanes' rows, watermarks and routing are
        untouched (a conservatively-wide depth bucket is bit-identical
        by the windowed contract). Returns the rows dropped."""
        if self.mailbox is None:
            return 0
        return self.mailbox.drop_lane(int(self._phys[slot]))

    def inject_slot_bitflip(self, slot: int, *, seed: int,
                            target: str = "ring",
                            ring_slot: Optional[int] = None) -> dict:
        """FAULT-INJECTION entry point (serve/faults.py's SDC arm; never
        called on a production path): flip ONE seeded bit of logical
        slot `slot`'s device residue — a snapshot-ring row
        (`target='ring'`, the at-rest corruption a future rollback
        would load and serve; `ring_slot` pins which row, default
        seeded over the real rows) or its live world
        (`target='state'`). Flushes the fence and the mailbox first so
        the flip lands on canonical bytes, then writes the flipped
        leaf back through an eager per-slot update, the reset_slot
        discipline. Survivors' slots are untouched. Returns a
        descriptor of what flipped, for the forensics bundle."""
        import jax.numpy as jnp
        from random import Random

        assert 0 <= slot < self.capacity
        assert target in ("state", "ring")
        self.block_until_ready()
        phys = int(self._phys[slot])
        tree = self.states if target == "state" else self.rings
        leaves = jax.tree_util.tree_leaves_with_path(tree)
        rng = Random(seed)
        path, leaf = leaves[rng.randrange(len(leaves))]
        if target == "ring":
            # confine the flip to ONE real ring row (never the scratch
            # row, which masked saves target and nothing ever loads)
            r = (
                int(ring_slot) % self.core.ring_len
                if ring_slot is not None
                else rng.randrange(self.core.ring_len)
            )
            row = np.array(jax.device_get(leaf[phys, r]), copy=True)
        else:
            r = None
            row = np.array(jax.device_get(leaf[phys]), copy=True)
        flat = row.reshape(-1).view(np.uint8)
        bit = rng.randrange(flat.size * 8)
        flat[bit // 8] ^= np.uint8(1 << (bit % 8))

        def patch(p, a):
            if p != path:
                return a
            if r is None:
                return a.at[phys].set(jnp.asarray(row))
            return a.at[phys, r].set(jnp.asarray(row))

        patched = jax.tree_util.tree_map_with_path(patch, tree)
        if target == "state":
            self.states = patched
        else:
            self.rings = patched
        return {
            "slot": slot,
            "target": target,
            "ring_slot": r,
            "leaf": jax.tree_util.keystr(path),
            "byte": bit // 8,
            "bit": bit % 8,
        }

    def _reset_masked_impl(self, rings, states, mask, init):
        """Masked batch reset over the stacked pytrees: every slot with
        mask[slot] set returns to the pristine init world, its ring
        zeroed; every other slot passes through untouched. mask is DATA
        (bool[stack_slots], the dummy tail always False), so one program
        covers every reset pattern — the env workload's auto-reset
        resets its whole done-set in one dispatch regardless of which
        episodes finished."""
        import jax.numpy as jnp

        def sel(a, x):
            m = mask.reshape((-1,) + (1,) * (a.ndim - 1))
            return jnp.where(m, x, a)

        states = jax.tree.map(sel, states, init)
        rings = jax.tree.map(
            lambda r: jnp.where(
                mask.reshape((-1,) + (1,) * (r.ndim - 1)),
                jnp.zeros((), r.dtype),
                r,
            ),
            rings,
        )
        return rings, states

    def reset_slots_masked(self, mask: np.ndarray) -> None:
        """Return every slot with mask[slot] == True to its initial world
        in ONE jitted masked pass (bool[capacity]). The batch twin of
        reset_slot: auto-resetting N finished episodes costs one program
        dispatch, not N eager per-leaf updates — and the mask is data,
        so the program compiles once (warmup covers it) no matter which
        slots finish."""
        assert mask.shape == (self.capacity,)
        self.drive_mailbox()  # lifecycle events drain the mailbox first
        m = np.zeros((self.stack_slots,), dtype=bool)
        m[self._phys[np.asarray(mask, dtype=bool)]] = True
        self.rings, self.states = self._reset_mask_fn(
            self.rings, self.states, m, self._init_state
        )

    def state_numpy(self, slot: int):
        """Host copy of one session slot's live world (parity checks)."""
        self.block_until_ready()
        phys = int(self._phys[slot])
        return jax.tree.map(
            lambda a: np.asarray(jax.device_get(a[phys])), self.states
        )

    # ------------------------------------------------------------------
    # per-slot export/import (live session migration rides this)
    # ------------------------------------------------------------------

    def _export_slot_impl(self, rings, states, slot):
        ring = jax.tree.map(lambda a: a[slot], rings)
        state = jax.tree.map(lambda a: a[slot], states)
        return ring, state

    def _import_slot_impl(self, rings, states, slot, ring, state):
        rings = jax.tree.map(lambda a, x: a.at[slot].set(x), rings, ring)
        states = jax.tree.map(
            lambda a, x: a.at[slot].set(x), states, state
        )
        return rings, states

    def export_slot(self, slot: int) -> dict:
        """Host copy of ONE slot's complete device residue — live world
        AND snapshot ring — as {"ring": tree, "state": tree} of numpy
        arrays: everything a sibling host needs to resume this session
        bit-exactly (the ring bytes matter — a post-migration rollback
        loads a pre-migration snapshot). Flushes the fence first so the
        copy observes every dispatched megabatch that wrote the slot."""
        assert 0 <= slot < self.capacity
        self.block_until_ready()
        ring, state = self._export_slot_fn(
            self.rings, self.states, np.int32(self._phys[slot])
        )
        return {
            "ring": jax.tree.map(
                lambda a: np.asarray(jax.device_get(a)), ring
            ),
            "state": jax.tree.map(
                lambda a: np.asarray(jax.device_get(a)), state
            ),
        }

    def import_slot(self, slot: int, payload: dict) -> None:
        """Adopt an export_slot() payload into one slot of THIS core —
        the receiving half of a live migration. Validates the payload's
        tree structure and per-leaf shapes/dtypes against this core's
        stacked layout and raises MigrationIncompatible naming the first
        mismatch (a different game config must fail at the handoff, not
        as an XLA shape error mid-megabatch). Eager per-leaf updates —
        a lifecycle event, not a hot path — behind a full fence flush,
        the same discipline as reset_slot."""
        from ..errors import MigrationIncompatible

        assert 0 <= slot < self.capacity
        for name, stacked in (("ring", self.rings), ("state", self.states)):
            flat_dst = jax.tree_util.tree_leaves_with_path(stacked)
            flat_src = jax.tree_util.tree_leaves_with_path(payload[name])
            if [p for p, _ in flat_dst] != [p for p, _ in flat_src]:
                raise MigrationIncompatible(
                    f"slot payload '{name}' tree does not match this "
                    f"core's layout (different game model?): "
                    f"{[jax.tree_util.keystr(p) for p, _ in flat_src]} vs "
                    f"{[jax.tree_util.keystr(p) for p, _ in flat_dst]}"
                )
            for (path, dst), (_, src) in zip(flat_dst, flat_src):
                want, got = dst.shape[1:], np.asarray(src).shape
                if want != got or dst.dtype != np.asarray(src).dtype:
                    raise MigrationIncompatible(
                        f"slot payload '{name}{jax.tree_util.keystr(path)}' "
                        f"is {got}/{np.asarray(src).dtype}, this core's "
                        f"slots are {want}/{dst.dtype} — the hosts run "
                        "different game configs"
                    )
        self.block_until_ready()
        self.rings, self.states = self._import_slot_fn(
            self.rings, self.states, np.int32(self._phys[slot]),
            payload["ring"], payload["state"],
        )

    def warmup(self) -> None:
        """Compile the megabatch program grid — every (row-count bucket x
        depth bucket) plus the zero-rollback fast path per row bucket —
        before serving: first compilation takes seconds, enough to stall
        every hosted session at once mid-tick, and depth routing must
        never trade the padding win for mid-serve compile stalls. All-pad
        dispatches are true no-ops on the stacked worlds (pad rows
        advance nothing and save nowhere, on the fast program included).
        With depth_routing=False only the full-window program per row
        bucket compiles, as before."""
        with warmup_scope("MultiSessionDeviceCore.warmup"):
            self._warmup_impl()

    def _warmup_impl(self) -> None:
        for b in self.buckets:
            idx = np.full((b,), self.pad_slot, dtype=np.int32)
            rows = np.tile(self._pad_row, (b, 1))
            if self.depth_routing:
                self.rings, self.states, _, _ = self._dispatch_fast_fn(
                    self.rings, self.states, idx, rows
                )
                for d in self.depth_buckets:
                    self.rings, self.states, _, _ = self._dispatch_fn(
                        self.rings, self.states, idx, rows, d
                    )
            else:
                self.rings, self.states, _, _ = self._dispatch_fn(
                    self.rings, self.states, idx, rows, self.core.window
                )
        if self.speculation:
            core = self.core
            W = core.window
            scratch = np.full((W,), core.scratch_slot, dtype=np.int32)
            statuses = np.zeros((W, self.num_players), dtype=np.int32)
            inputs = np.zeros(
                (W, self.num_players, self.input_size), dtype=np.uint8
            )
            for b in self.buckets:
                # draft rollout per bucket: pad rows anchor on the dummy
                # world's zeroed ring (discarded results, a pure compile)
                idx = np.full((b,), self.pad_slot, dtype=np.int32)
                rows = np.tile(self._draft_pad_row, (b, 1))
                traj, his, los, a_hi, a_lo = self._draft_fn(
                    self.rings, idx, rows
                )
                # per-slot adopt per bucket, against the DUMMY slot with
                # scratch-only saves: no ring bytes move, and the dummy
                # state the adopt steps is restored below — live slots
                # never observe the warmup
                packed = core.pack_adopt_row(
                    0, 0, 1, 1, 0, 1, scratch,
                    statuses=statuses, inputs=inputs,
                )
                self.rings, self.states, _, _ = self._adopt_slot_fn(
                    self.rings, self.states, np.int32(self.pad_slot),
                    traj, his, los, a_hi, a_lo, packed,
                )
            init = core.game.init_state()
            self.states = jax.tree.map(
                lambda a, x: a.at[self.pad_slot].set(x), self.states, init
            )
        if self.sdc_audit:
            # the audit lane's reference-recompute program per row
            # bucket: all-pad batches read the dummy slot only and
            # return discarded checksums — a pure compile, and the
            # worlds are untouched by construction (nothing is donated
            # or scattered)
            for b in self.buckets:
                self._audit_fn(
                    self.rings,
                    self.states,
                    np.full((b,), self.pad_slot, dtype=np.int32),
                    np.tile(self._pad_row, (b, 1)),
                )
        if self.mailbox is not None:
            # resident driver variants: compile the commit-bucket
            # scatters plus every driver program the live cycle router
            # can pick (fast + one windowed variant per depth bucket).
            # All-zero watermarks make each a true no-op — the
            # while_loop exits before its first virtual tick — so only
            # the compile happens, never a state change.
            self.mailbox.warmup()
            marks = np.zeros((self.stack_slots,), dtype=np.int32)
            vt_fast = np.ones((self.mailbox.depth,), dtype=bool)
            rows_dev = self.mailbox.rows_dev
            self.rings, self.states, _, _ = self._driver_fast_fn(
                self.rings, self.states, rows_dev, marks
            )
            for d in self.depth_buckets:
                self.rings, self.states, _, _ = self._driver_fn(
                    self.rings, self.states, rows_dev, marks, vt_fast, d
                )
        # the masked batch reset (env auto-reset) with an all-False mask:
        # a true no-op on the stacked worlds, but the program exists
        # before the first episode ever finishes mid-serve
        self.rings, self.states = self._reset_mask_fn(
            self.rings,
            self.states,
            np.zeros((self.stack_slots,), dtype=bool),
            self._init_state,
        )
        # one export->import round trip of slot 0 (same bytes back, a
        # true no-op): the eager per-leaf slot writes compile their XLA
        # programs HERE, so the first live migration pays a memcpy, not
        # a compile stall mid-serve
        self.import_slot(0, self.export_slot(0))
        self.block_until_ready()

    def block_until_ready(self) -> None:
        # "device state is current" includes the mailbox: rows the ring
        # still owes execute first, so exports/checkpoints/parity reads
        # always observe the canonical (fully ticked) worlds
        self.drive_mailbox()
        jax.block_until_ready(self.states)
        self._inflight.clear()
        self.inflight_rows = 0

    def retire_fence(self) -> None:
        """block_until_ready for the tick path: the same retirement of
        every in-flight dispatch, with the wait timed as a fence stall."""
        self.drive_mailbox()
        self._fence_wait(self.states)
        self.block_until_ready()  # nothing left to wait on: retires entries

    # ------------------------------------------------------------------
    # durable checkpoint (graceful drain rides this)
    # ------------------------------------------------------------------

    def stacked_canonical(self) -> Tuple[Any, Any]:
        """Host copy of the stacked worlds in the CANONICAL slot layout —
        `capacity` live slots in logical order plus ONE dummy row at
        index `capacity` — whatever the stack's physical layout
        (checkpoints and cross-host parity checks are always canonical,
        so a sharded host's bytes compare/restore against a
        single-device twin's directly). Returns (rings, states) numpy
        pytrees; `save()` writes exactly this and `load_stacked()`
        adopts it back."""
        self.block_until_ready()
        idx = np.append(self._phys, np.int32(self.pad_slot))
        canon = lambda a: np.asarray(jax.device_get(a))[idx]  # noqa: E731
        return (
            jax.tree.map(canon, self.rings),
            jax.tree.map(canon, self.states),
        )

    def checksum_slots(self) -> Tuple[np.ndarray, np.ndarray]:
        """(hi, lo) uint32[capacity] checksums of every live slot's
        world, logical slot order — the host-facing desync spot-check
        and the cross-layout parity witness (the sharded subclass
        overrides this with the EXPLICIT shard_map + psum pass from
        parallel/sharded.py; both must agree bitwise with vmapping the
        model's checksum). Not a hot path: flushes the fence."""
        self.block_until_ready()
        g = jax.tree.map(lambda a: a[self._phys], self.states)
        his, los = jax.vmap(self.core.game.checksum)(g)
        return (
            np.asarray(jax.device_get(his)),
            np.asarray(jax.device_get(los)),
        )

    def save(self, path: str) -> None:
        from ..utils.checkpoint import save_device_checkpoint

        rings, states = self.stacked_canonical()
        save_device_checkpoint(
            path,
            {"rings": rings, "states": states},
            {
                "kind": "MultiSessionDeviceCore",
                "capacity": self.capacity,
                "max_prediction": self.core.max_prediction,
                "num_players": self.num_players,
            },
        )

    @classmethod
    def restore(cls, path: str, game, mesh=None) -> "MultiSessionDeviceCore":
        """Rebuild a core from a save() checkpoint. Checkpoints are
        LAYOUT-AGNOSTIC: `mesh=` restores the same worlds onto a sharded
        core (and a sharded host's checkpoint restores single-device) —
        the serving twin of TpuRollbackBackend.restore's mesh knob."""
        from ..utils.checkpoint import load_device_checkpoint

        tree, meta = load_device_checkpoint(path)
        if meta.get("kind") != "MultiSessionDeviceCore":
            from ..errors import CheckpointIncompatible

            raise CheckpointIncompatible(
                f"checkpoint {path!r} holds a different core kind",
                found=meta.get("kind"), expected="MultiSessionDeviceCore",
            )
        core = cls.create(
            game,
            meta["max_prediction"],
            meta["num_players"],
            meta["capacity"],
            mesh=mesh,
        )
        core.load_stacked(tree["rings"], tree["states"])
        return core

    def load_stacked(self, rings, states) -> None:
        """Adopt checkpointed stacked worlds into THIS core (the env
        restore path: the env rebuilds its core from config, then loads
        the saved worlds) — the in-place twin of restore(). The trees
        carry the CANONICAL capacity + 1 slots (save() writes that
        layout whatever the stack's physical padding); this expands them
        into the core's own physical layout — dummy padding replicated
        from the canonical dummy row — and places per the layout's
        policy, so a single-device checkpoint restores onto a sharded
        core (and vice versa) bit-exactly."""
        self.block_until_ready()

        def expand(a):
            a = np.asarray(jax.device_get(a))
            assert a.shape[0] == self.capacity + 1, (
                f"stacked trees must be canonical (capacity + 1 = "
                f"{self.capacity + 1} slots; got {a.shape[0]})"
            )
            out = np.repeat(
                a[self.capacity : self.capacity + 1],
                self.stack_slots,
                axis=0,
            )
            out[self._phys] = a[: self.capacity]
            return out

        self.rings = self._place_rings(jax.tree.map(expand, rings))
        self.states = self._place_states(jax.tree.map(expand, states))


class ShardedMultiSessionDeviceCore(MultiSessionDeviceCore):
    """MultiSessionDeviceCore with the SESSION axis of the stacked
    pytrees split over the `session` axis of a device mesh (and, for big
    worlds, the entity axis over an `entity` mesh axis) — the serving
    megabatch GSPMD-partitioned across chips, so one host's capacity
    multiplies by the session-axis size instead of stacking the whole
    fleet on device 0.

    Placement is the ONE policy in parallel/sharded.py
    (`stacked_state_specs`/`stacked_ring_specs` via
    `shard_stacked_state`/`shard_stacked_ring`): sessions split over
    `session` on the stack's leading axis, entity arrays additionally
    over `entity` when the mesh carries one, ring-slot axes always
    local. The slot layout interleaves live slots round-robin across the
    session shards — logical slot i lives on shard i % n at local offset
    i // n — so a fleet that fills slots in admission order spreads over
    every chip, and the dummy pad tail is distributed so the session
    axis divides the stack. The public API stays LOGICAL-slot throughout
    (dispatch entries, reset masks, export/import, checkpoints — which
    stay canonical, so a sharded host's checkpoint restores on a
    single-device twin and vice versa).

    Every program of the base core — the (row-bucket x depth-bucket)
    megabatch grid, the zero-rollback fast path, `reset_slots_masked`,
    `dispatch_rows`, export/import, `load_stacked` — runs GSPMD-
    partitioned from the operand shardings; the dispatch impls
    additionally constrain the staged (idx, rows) batch onto the
    `session` axis, so the vmapped row work partitions across shards
    (the host's slot->shard affinity keeps most rows on the shard that
    owns their world, so the gather/scatter crosses ICI only for the
    stragglers). The per-megabatch [B, W] checksum reduction rides the
    models' concat-free partial sums (ops/fixed_point.
    weighted_checksum_parts — exact under any partitioning);
    `checksum_slots()` additionally pins the collective shape BY HAND
    via parallel/sharded.stacked_sharded_checksum (shard_map + psum over
    `entity`), the spot-check a partitioner regression is caught
    against.

    Bitwise contract (pinned by tests/test_sharded_serve.py and the
    dryrun's sharded-host stage): a sharded host produces bit-identical
    per-slot device state, ring bytes and checksum histories to a
    single-device twin fed the same traffic."""

    def __init__(self, game, max_prediction: int, num_players: int,
                 capacity: int, *, mesh, **kw):
        from jax.sharding import NamedSharding, PartitionSpec

        assert "session" in mesh.axis_names, (
            f"serving mesh needs a 'session' axis (got {mesh.axis_names};"
            " build it with parallel.mesh.make_session_mesh)"
        )
        self.mesh = mesh
        self.session_shards = int(mesh.shape["session"])
        self._row_sharding = NamedSharding(mesh, PartitionSpec("session"))
        super().__init__(game, max_prediction, num_players, capacity, **kw)
        _reg = GLOBAL_TELEMETRY.registry
        self._m_shard_rows = _reg.gauge(
            "ggrs_shard_rows",
            "live megabatch rows routed to this session-mesh shard in "
            "the last dispatch",
            labelnames=("shard",),
        )
        self._m_shard_imbalance = _reg.histogram(
            "ggrs_shard_imbalance",
            "max/mean live rows per session-mesh shard per megabatch "
            "dispatch (1.0 = perfectly balanced)",
            buckets=SHARD_IMBALANCE_BUCKETS,
        )
        # labeled children resolved once, not per dispatch: .labels() is
        # a str-key dict path and _dispatch_staged is the hot tick path
        self._shard_row_gauges = [
            self._m_shard_rows.labels(str(s))
            for s in range(self.session_shards)
        ]

    # ------------------------------------------------------------------
    # stack-layout hooks (see the base class: everything else — dispatch,
    # staging, fence, lifecycle — is layout-agnostic and inherited)
    # ------------------------------------------------------------------

    def _stack_size(self) -> int:
        """capacity live slots + a dummy tail padded so the session mesh
        axis divides the stack (>= 1 dummy total, so pad rows always
        have a world to no-op against)."""
        n = self.session_shards
        self._per_shard = -(-(self.capacity + 1) // n)  # ceil
        return self._per_shard * n

    def _place_states(self, tree):
        from ..parallel.sharded import shard_stacked_state

        return shard_stacked_state(tree, self.mesh)

    def _place_rings(self, tree):
        from ..parallel.sharded import shard_stacked_ring

        return shard_stacked_ring(tree, self.mesh)

    def _init_slot_layout(self) -> None:
        per, n = self._per_shard, self.session_shards
        slots = np.arange(self.capacity, dtype=np.int32)
        # round-robin: shard s owns physical rows [s*per, (s+1)*per) of
        # the equally-split stack; logical slot i -> shard i % n, local
        # offset i // n (< per by construction of _stack_size)
        self._phys = (slots % n) * per + slots // n
        self._phys_inverse = np.full(
            (self.stack_slots,), self.capacity, dtype=np.int32
        )
        self._phys_inverse[self._phys] = slots
        dummies = np.setdiff1d(
            np.arange(self.stack_slots, dtype=np.int32), self._phys
        )
        self.pad_slot = int(dummies[0])

    def shard_of(self, slot: int) -> int:
        return int(slot) % self.session_shards

    # ------------------------------------------------------------------
    # GSPMD dispatch: same impls, the staged batch constrained onto the
    # session axis so the row work partitions across shards
    # ------------------------------------------------------------------

    def _dispatch_impl(self, rings, states, idx, rows, nslots):
        idx = jax.lax.with_sharding_constraint(idx, self._row_sharding)
        rows = jax.lax.with_sharding_constraint(rows, self._row_sharding)
        return super()._dispatch_impl(rings, states, idx, rows, nslots)

    def _dispatch_fast_impl(self, rings, states, idx, rows):
        idx = jax.lax.with_sharding_constraint(idx, self._row_sharding)
        rows = jax.lax.with_sharding_constraint(rows, self._row_sharding)
        return super()._dispatch_fast_impl(rings, states, idx, rows)

    def _draft_impl(self, rings, idx, rows):
        # the draft batch partitions across the session shards like any
        # other staged row block (the host's slot->shard affinity orders
        # draft entries by owning shard, so the rollout's ring gathers
        # stay mostly shard-local); the per-slot adopt needs no
        # constraint — it is a single-slot gather/scatter GSPMD already
        # partitions from the operand shardings
        idx = jax.lax.with_sharding_constraint(idx, self._row_sharding)
        rows = jax.lax.with_sharding_constraint(rows, self._row_sharding)
        return super()._draft_impl(rings, idx, rows)

    def _audit_impl(self, rings, states, idx, rows):
        # the sampled audit batch partitions across the session shards
        # like any other staged row block; the replay itself is per-slot
        # local, so the constraint keeps the gathers shard-local
        idx = jax.lax.with_sharding_constraint(idx, self._row_sharding)
        rows = jax.lax.with_sharding_constraint(rows, self._row_sharding)
        return super()._audit_impl(rings, states, idx, rows)

    def _place_mailbox(self, rows):
        from ..parallel.sharded import shard_mailbox

        return shard_mailbox(rows, self.mesh)

    def _driver_impl(self, rings, states, mbox_rows, marks, vt_fast,
                     nslots):
        # the mailbox's slot axis is placed on the session mesh
        # (shard_mailbox); constrain it (and the watermarks) in-program
        # too so the vmapped vtick body partitions like every other
        # stacked computation — each shard walks its own lanes' rows
        # (vt_fast is a tiny replicated [K] routing vector)
        mbox_rows = jax.lax.with_sharding_constraint(
            mbox_rows, self._row_sharding
        )
        marks = jax.lax.with_sharding_constraint(marks, self._row_sharding)
        return super()._driver_impl(
            rings, states, mbox_rows, marks, vt_fast, nslots
        )

    def _driver_fast_impl(self, rings, states, mbox_rows, marks):
        mbox_rows = jax.lax.with_sharding_constraint(
            mbox_rows, self._row_sharding
        )
        marks = jax.lax.with_sharding_constraint(marks, self._row_sharding)
        return super()._driver_fast_impl(rings, states, mbox_rows, marks)

    def _dispatch_staged(self, staged, n, bucket, *, last_active, fast):
        if GLOBAL_TELEMETRY.enabled:
            # per-shard live-row census of THIS dispatch: the affinity
            # health surface (registry-driven, so both exporters and
            # host.telemetry() carry it with no extra code)
            counts = np.bincount(
                staged[0][:n] // self._per_shard,
                minlength=self.session_shards,
            )
            for s in range(self.session_shards):
                self._shard_row_gauges[s].set(int(counts[s]))
            self._m_shard_imbalance.observe(
                float(counts.max()) * self.session_shards / n
            )
        return super()._dispatch_staged(
            staged, n, bucket, last_active=last_active, fast=fast
        )

    # ------------------------------------------------------------------
    # the explicit cross-shard checksum pass
    # ------------------------------------------------------------------

    def checksum_slots(self) -> Tuple[np.ndarray, np.ndarray]:
        """(hi, lo) uint32[capacity], logical slot order, computed with
        the EXPLICIT shard_map + psum collective from
        parallel/sharded.stacked_sharded_checksum — bit-identical to the
        base class's vmapped model checksum (the parity tests pin both
        against each other), with the cross-shard word reduction's
        collective shape pinned by hand for entity-sharded worlds."""
        from ..parallel.sharded import stacked_sharded_checksum

        self.block_until_ready()
        his, los = stacked_sharded_checksum(
            self.states, self.mesh, keys=self.core.game.checksum_keys
        )
        his = np.asarray(jax.device_get(his))[self._phys]
        los = np.asarray(jax.device_get(los))[self._phys]
        return his, los

    def _warmup_impl(self) -> None:
        super()._warmup_impl()
        # the explicit cross-shard checksum pass compiles here too, so a
        # mid-serve desync spot-check never pays its first compile
        self.checksum_slots()
