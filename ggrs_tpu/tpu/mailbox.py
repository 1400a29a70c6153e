"""Device-resident input mailbox: the feed half of the resident serving
loop (the drive half is MultiSessionDeviceCore's `lax.while_loop`
virtual-tick driver in backend.py).

The dispatch-per-tick serving path pays the per-dispatch host floor
(a fixed host cost, any program content) once per host tick — the
device finishes a megabatch in microseconds and then idles waiting for
the host to hand it the next one. The mailbox retires that cadence: a
fixed [S, K, L] ring of packed tick rows lives ON DEVICE (S = stack
slots, K = virtual-tick depth, L = the packed control-word length), the
host's pump/stage pass appends each lane's decoded rows to a host-side
staging image as sessions advance, and ONE batched scatter per host tick
(`commit`) moves everything newly staged onto the device — the same
pooled-staging discipline as the PR 6 wire pump's decode buffers. Every
K host ticks (or on demand) the driver consumes the whole ring in one
dispatch, walking per-lane valid watermarks so lanes at different fill
depths each execute exactly their own staged rows, in order.

Watermark semantics: lane s's rows are valid for virtual ticks
[0, marks[s]); rows above the watermark are never consumed (the driver
masks them to the inert pad row), so a fill cycle only ever executes
rows written since the last drive. Overflow — the host outrunning K —
degrades to an EXTRA driver dispatch (`note_overflow` + drive), never a
dropped input: `stage` asserts the lane has room, and the core's
`stage_mailbox_row` entry point drives first when it doesn't.

Checksum harvest is lazy: each fill cycle owns one
`_FutureChecksumBatch`; staged saves bind `_LazyChecksum`s against it at
flat index j * S * W + phys * W + window_slot (the driver's [K, S, W]
output rings, raveled), and the first read of any of them forces the
drive — laziness composes with laziness, exactly like the single-session
lazy tick buffer.

Shared-state discipline: the pooled commit staging and the device row
ring are fence-protected state (reuse is safe only because the core's
async fence proves the dispatch that read a buffer retired) — the FEN001
policy for this module names the methods allowed to write them.
"""

from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from ..errors import InvariantViolation, MailboxLaneFull
from ..obs import GLOBAL_TELEMETRY, LOG2_BUCKETS


class DeviceMailbox:
    """Donated [S, K, L] device row ring + host staging + watermarks.

    Built by `MultiSessionDeviceCore.attach_mailbox`; the host never
    constructs one directly. All slot indices here are PHYSICAL stack
    indices (the core's `stage_mailbox_row` translates logical slots)."""

    def __init__(self, core, depth: int):
        import jax
        import jax.numpy as jnp

        if depth < 1:
            raise InvariantViolation(
                f"mailbox depth must be >= 1 (got {depth})",
                invariant="mailbox_depth",
            )
        self.core = core
        self.depth = depth
        self.stack_slots = core.stack_slots
        self.row_len = core.core._packed_len
        self.window = core.core.window
        # the device row ring, placed by the core's layout policy (the
        # sharded core splits the slot axis over the session mesh)
        self.rows_dev = core._place_mailbox(
            jnp.tile(
                jnp.asarray(core._pad_row), (self.stack_slots, depth, 1)
            )
        )
        # per-lane fill watermarks (host image; the drive hands the
        # device a fresh copy per dispatch)
        self._counts = np.zeros((self.stack_slots,), dtype=np.int32)
        # rows staged since the last commit: (phys, vtick, row ref)
        self._staged: List[Tuple[int, int, np.ndarray]] = []
        self.pending_rows = 0  # committed + staged, i.e. rows a drive owes
        # cycle bookkeeping for driver-program routing: the cycle's max
        # depth, whole-cycle fast eligibility, and the per-vtick fast
        # vector the mixed driver conds on in-loop
        self._cycle_max_last_active = 0
        self._cycle_all_fast = True
        self._vt_fast = np.ones((depth,), dtype=bool)
        self._future = None  # _FutureChecksumBatch of the open cycle
        # pooled (idx, vt, rows) commit staging per pow2 bucket,
        # async_inflight + 1 deep (the fence-reuse guarantee)
        self._pools: dict = {}
        b, buckets = 1, set()
        cap = max(2 * core.capacity, 1)
        while b < cap:
            buckets.add(b)
            b *= 2
        buckets.add(cap)
        self.commit_buckets = tuple(sorted(buckets))
        self._commit_fn = jax.jit(self._commit_impl, donate_argnums=(0,))
        self.overflows = 0
        _reg = GLOBAL_TELEMETRY.registry
        self._m_occupancy = _reg.gauge(
            "ggrs_mailbox_occupancy",
            "staged mailbox rows / (capacity x depth) at the last driver "
            "dispatch",
        )
        self._m_overflow = _reg.counter(
            "ggrs_mailbox_overflow_total",
            "mailbox fill cycles cut short because a lane outran the "
            "virtual-tick depth (degrades to an extra dispatch; inputs "
            "are never dropped)",
        )
        self._m_vticks = _reg.histogram(
            "ggrs_vticks_per_dispatch",
            "virtual ticks executed per resident driver dispatch (the "
            "dispatch-amortization factor)",
            buckets=LOG2_BUCKETS,
        )

    # ------------------------------------------------------------------
    # staging (host side)
    # ------------------------------------------------------------------

    def lane_full(self, phys: int) -> bool:
        return int(self._counts[phys]) >= self.depth

    def max_fill(self) -> int:
        return int(self._counts.max())

    def note_overflow(self) -> None:
        self.overflows += 1
        if GLOBAL_TELEMETRY.enabled:
            self._m_overflow.inc()

    def stage(self, phys: int, row: np.ndarray, last_active: int,
              fast: bool):
        """Append one packed tick row to lane `phys`'s fill cycle.
        Returns (checksum batch, base index) for the row's save bindings
        — the batch is the open cycle's future, fulfilled at drive time.
        The row reference must stay valid until the next `commit` (the
        lane row pools guarantee it: commits happen within the tick)."""
        j = int(self._counts[phys])
        if j >= self.depth:
            # a runtime scheduling bug, not an API misuse: typed so the
            # operator sees which lane wedged at what depth (the core's
            # stage_mailbox_row drives first and can never hit this)
            raise MailboxLaneFull(
                "stage() on a full mailbox lane (caller must drive)",
                lane=phys, depth=self.depth,
            )
        self._staged.append((phys, j, row))
        self._counts[phys] = j + 1
        self.pending_rows += 1
        self._cycle_max_last_active = max(
            self._cycle_max_last_active, last_active
        )
        self._cycle_all_fast = self._cycle_all_fast and fast
        if not fast:
            self._vt_fast[j] = False
        if self._future is None:
            # lazy import once per process (not per staged row — this is
            # the hot staging path): backend also imports this module
            # lazily from attach_mailbox, so a module-level import would
            # be cycle-prone depending on which side loads first
            from .backend import _FutureChecksumBatch

            self._future = _FutureChecksumBatch(self._force_drive)
        base = j * self.stack_slots * self.window + phys * self.window
        return self._future, base

    def _force_drive(self) -> None:
        """A lazy-checksum read forced the cycle: route through the
        core's drive entry point (which installs the real batch)."""
        self.core.drive_mailbox()

    # ------------------------------------------------------------------
    # commit (the one batched host->device transfer per host tick)
    # ------------------------------------------------------------------

    def _commit_impl(self, rows_dev, idx, vt, new_rows):
        """Scatter [n] freshly staged rows into the donated device ring.
        Duplicate pad entries (pad_slot, vtick 0) all write the identical
        pad row, so the scatter stays deterministic. The second output is
        a small NON-donated token the async fence can block on — the ring
        itself is donated to the next commit, so a fence handle aliasing
        it would be a deleted buffer by the time the fence waits."""
        import jax.numpy as jnp

        return rows_dev.at[idx, vt].set(new_rows), jnp.max(vt)

    def _acquire_commit_stage(self, bucket: int):
        pool = self._pools.get(bucket)
        if pool is None:
            pool = {
                "flip": 0,
                "bufs": [
                    [
                        np.full((bucket,), self.core.pad_slot, np.int32),
                        np.zeros((bucket,), np.int32),
                        np.tile(self.core._pad_row, (bucket, 1)),
                        0,
                    ]
                    for _ in range(self.core.async_inflight + 1)
                ],
            }
            self._pools[bucket] = pool
        pool["flip"] = (pool["flip"] + 1) % len(pool["bufs"])
        return pool["bufs"][pool["flip"]]

    @property
    def staged_count(self) -> int:
        return len(self._staged)

    def commit_bucket_for(self, n: int) -> int:
        for b in self.commit_buckets:
            if b >= n:
                return b
        return self.commit_buckets[-1]

    def commit(self):
        """Move every row staged since the last commit onto the device,
        bucketed scatters over pow2 pad targets (a batch past the
        largest bucket — a caller staging a whole fill cycle before its
        first commit — chunks through it; the steady host flow commits
        every tick, so one scatter per tick is the norm). Returns the
        last dispatch handle (None when nothing was staged). Called by
        the core's `commit_mailbox` entry point, which admits the handle
        to the async fence."""
        handle = None
        todo = self._staged
        while todo:
            chunk, todo = (
                todo[: self.commit_buckets[-1]],
                todo[self.commit_buckets[-1] :],
            )
            self._staged = todo
            n = len(chunk)
            bucket = self.commit_bucket_for(n)
            staged = self._acquire_commit_stage(bucket)
            idx, vt, rows, used = staged
            for k, (phys, j, row) in enumerate(chunk):
                idx[k] = phys
                vt[k] = j
                rows[k] = row
            for k in range(n, used):  # re-pad what the last use dirtied
                idx[k] = self.core.pad_slot
                vt[k] = 0
                rows[k] = self.core._pad_row
            staged[3] = n
            self.core.plan_cache.note(
                ("mailbox_commit", bucket), metrics=False
            )
            self.rows_dev, handle = self._commit_fn(
                self.rows_dev, idx, vt, rows
            )
        return handle

    def warmup(self) -> None:
        """Compile every commit-bucket scatter with all-pad entries — a
        true no-op on the ring (pad lanes' rows are never consumed), so
        the first live commit of any size pays a memcpy, not a compile
        stall mid-serve."""
        for bucket in self.commit_buckets:
            staged = self._acquire_commit_stage(bucket)
            idx, vt, rows, _used = staged
            idx.fill(self.core.pad_slot)
            vt.fill(0)
            rows[:] = self.core._pad_row
            staged[3] = bucket
            self.core.plan_cache.note(
                ("mailbox_commit", bucket), metrics=False
            )
            self.rows_dev, _ = self._commit_fn(self.rows_dev, idx, vt, rows)

    # ------------------------------------------------------------------
    # drive-side bookkeeping (the core's drive_mailbox consumes these)
    # ------------------------------------------------------------------

    def take_cycle(self):
        """Close the fill cycle for a driver dispatch: returns
        (marks i32[S], n_rows, max_last_active, all_fast, vt_fast
        bool[K], future) and resets the staging bookkeeping for the next
        cycle. `commit` must have landed every staged row first
        (drive_mailbox guarantees it)."""
        if self._staged:
            # a drive that would execute rows the device never received:
            # the watermark/row-ring invariant the resident loop's
            # correctness rests on, surfaced typed instead of asserted
            raise InvariantViolation(
                "take_cycle() with uncommitted staged rows",
                invariant="mailbox_uncommitted_rows",
            )
        marks = self._counts.copy()
        n = self.pending_rows
        max_la = self._cycle_max_last_active
        all_fast = self._cycle_all_fast
        vt_fast = self._vt_fast.copy()
        future = self._future
        self._counts.fill(0)
        self.pending_rows = 0
        self._cycle_max_last_active = 0
        self._cycle_all_fast = True
        self._vt_fast.fill(True)
        self._future = None
        return marks, n, max_la, all_fast, vt_fast, future

    def drop_lane(self, phys: int) -> int:
        """QUARANTINE containment: discard every row PHYSICAL lane
        `phys` still owes this fill cycle — staged entries are scrubbed
        before they can commit, and the lane's watermark drops to zero
        so rows already committed to the device ring mask to the inert
        pad row at the next drive. Other lanes' rows, watermarks and
        the cycle's routing flags are untouched (leftover conservative
        routing — a wider depth bucket, a windowed instead of fast
        drive — is bit-identical by the driver contract). Returns the
        rows dropped. Lazy checksums already bound against the cycle's
        future for the dropped rows resolve to pad values; the caller
        quarantined the owning session, so no live cell reads them."""
        n = int(self._counts[phys])
        if n == 0:
            return 0
        if self._staged:
            self._staged = [
                (p, j, row) for (p, j, row) in self._staged if p != phys
            ]
        self._counts[phys] = 0
        self.pending_rows -= n
        return n

    def observe_drive(self, n_rows: int, vticks: int) -> None:
        """Telemetry for one driver dispatch (behind the enabled check at
        the call site, the Tracer.span idiom)."""
        self._m_vticks.observe(vticks)
        self._m_occupancy.set(
            n_rows / float(self.core.capacity * self.depth)
        )
