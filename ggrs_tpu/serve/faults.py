"""Deterministic device-domain fault injection: the seam between the
chaos harness and the failure modes wire-level chaos can't reach.

The WAN chaos loadgen (serve/chaos.py) makes the *wire* lie and the
fleet chaos (fleet/chaos.py) makes *processes* die, but both leave the
device data plane perfect: every dispatch succeeds, every readback
returns, every byte the accelerator computes is correct. Real
accelerators break all three — XLA runtime failures, wedged readbacks,
and silent data corruption (SDC) — and a serving stack's answer to
them is a correctness surface. This module makes those failures
injectable, seeded and replayable:

  * `FaultPlan` — the schedule: a pure function of (seed, knobs) mapping
    host tick -> faults to fire, built once at construction so a fault
    run replays bit-identically per seed. `FaultPlan.smoke()` is the
    canonical "at least one of every kind" schedule the --fault-smoke
    gate and the acceptance soak drive.
  * `FaultInjector` — the arm: installs itself as the host's and the
    device core's `fault_seam` and fires the plan's faults at the
    boundaries the core/host consult (dispatch entry, resident drive,
    harvest/readback, mailbox staging, checkpoint write) plus direct
    state corruption (`inject_slot_bitflip`).

Fault kinds (docs/DESIGN.md "Device fault domains" has the table
of kinds and each kind's recovery ladder):

  dispatch_raise     a dispatch/drive raises DeviceDispatchFailed
                     BEFORE executing (worlds untouched) — one-shot
                     (transient: the host retries) or persistent on a
                     victim slot (the host quarantines the slot and
                     re-dispatches survivors)
  harvest_timeout    the next checksum harvest raises HarvestTimeout —
                     the host's drain pass skips a tick; checkpoint /
                     export block-and-retry
  mailbox_storm      the next N mailbox stages report their lane full —
                     a burst of forced early drives (commit overflow
                     storm); inputs are never dropped
  checkpoint_corrupt the next durable checkpoint write is truncated
                     after landing — restore must detect it as typed
                     CheckpointIncompatible, never a shape error
  slot_bitflip       one bit of a victim slot's live world (or a ring
                     row) flips on device — SDC; the sampled audit lane
                     must catch it within its sampling bound and
                     quarantine the slot

Every fault the injector fires is recorded (kind, tick, target) so a
soak can assert the blast radius: survivors bit-exact vs an unfaulted
twin, every quarantine surfaced as a typed SlotPoisoned + forensics
bundle.
"""

from __future__ import annotations

import os
import random
from typing import Any, Dict, List, Optional, Sequence

from ..errors import DeviceDispatchFailed, HarvestTimeout
from ..obs import GLOBAL_TELEMETRY

FAULT_KINDS = (
    "dispatch_raise",
    "harvest_timeout",
    "mailbox_storm",
    "checkpoint_corrupt",
    "slot_bitflip",
)

# opt-in extension kinds: not in the every-kind default schedule
# (FaultPlan()/FaultPlan.smoke() fire each of FAULT_KINDS, whose blast
# radii exist on every host), scheduled by passing `kinds=` explicitly:
#
#   journal_stall   the next N durable-journal appends are refused as
#                   if the disk were full (typed JournalStalled at the
#                   host tap) — the storage tier's ENOSPC arm; the host
#                   must degrade the lane to unjournaled with an
#                   invariant trip, never wedge. Vacuous on a host with
#                   no journaled lanes, hence opt-in.
EXTENSION_FAULT_KINDS = ("journal_stall",)
ALL_FAULT_KINDS = FAULT_KINDS + EXTENSION_FAULT_KINDS


class Fault:
    """One scheduled device fault: fire at `tick`, of `kind`, with
    kind-specific `params` (persist=, storm_len=, ...)."""

    __slots__ = ("tick", "kind", "params")

    def __init__(self, tick: int, kind: str, **params: Any):
        assert kind in ALL_FAULT_KINDS, f"unknown fault kind {kind!r}"
        self.tick = tick
        self.kind = kind
        self.params = params

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return f"Fault({self.tick}, {self.kind!r}, {self.params})"


class FaultPlan:
    """A seeded, replayable device-fault schedule. The schedule is fully
    materialized at construction — a pure function of (seed, knobs) —
    so two runs of the same plan fire identical faults at identical
    ticks whatever the host does in between."""

    def __init__(self, seed: int, ticks: int, *,
                 kinds: Sequence[str] = FAULT_KINDS,
                 events_per_kind: int = 1,
                 start: int = 1,
                 persist_dispatch: bool = True,
                 storm_len: int = 6):
        """`events_per_kind` faults of every kind in `kinds`, spread
        over [start, ticks) at seeded-jittered positions.
        `persist_dispatch`: dispatch_raise faults pin a victim slot and
        keep firing until it is quarantined (the containment story);
        False makes them one-shot transients (the retry story).
        `storm_len`: consecutive stages each mailbox_storm forces into
        the overflow path."""
        assert ticks > start >= 0
        self.seed = seed
        self.ticks = ticks
        self.kinds = tuple(kinds)
        rng = random.Random(seed ^ 0xFA17)
        faults: List[Fault] = []
        span = max(ticks - start, 1)
        for kind in self.kinds:
            assert kind in ALL_FAULT_KINDS, f"unknown fault kind {kind!r}"
            for i in range(events_per_kind):
                # one fault per evenly-sized stripe, jittered inside it,
                # so multiple events of a kind can't pile on one tick
                lo = start + (span * i) // events_per_kind
                hi = start + (span * (i + 1)) // events_per_kind
                t = rng.randrange(lo, max(hi, lo + 1))
                params: Dict[str, Any] = {}
                if kind == "dispatch_raise":
                    params["persist"] = persist_dispatch
                elif kind == "mailbox_storm":
                    params["storm_len"] = storm_len
                faults.append(Fault(t, kind, **params))
        self._by_tick: Dict[int, List[Fault]] = {}
        for f in sorted(faults, key=lambda f: f.tick):
            self._by_tick.setdefault(f.tick, []).append(f)

    @classmethod
    def smoke(cls, seed: int, ticks: int, **kw: Any) -> "FaultPlan":
        """The canonical gate schedule: >= 1 of EVERY fault kind."""
        return cls(seed, ticks, kinds=FAULT_KINDS, **kw)

    def at(self, tick: int) -> List[Fault]:
        return self._by_tick.get(tick, [])

    def all_faults(self) -> List[Fault]:
        return [f for fs in self._by_tick.values() for f in fs]

    def section(self) -> dict:
        return {
            "seed": self.seed,
            "ticks": self.ticks,
            "schedule": [
                {"tick": f.tick, "kind": f.kind, **f.params}
                for f in self.all_faults()
            ],
        }


def faults_injected_counter():
    """Get-or-create THE injected-fault counter — shared by the
    injector and the smoke gates that assert on it."""
    return GLOBAL_TELEMETRY.registry.counter(
        "ggrs_faults_injected_total",
        "device-domain faults fired by the deterministic injection seam",
        ("kind",),
    )


class FaultInjector:
    """Arms a FaultPlan against one SessionHost: installs itself as the
    host's and the device core's `fault_seam`, then `advance(tick)` —
    called once per host tick by the drive loop — fires that tick's
    faults. Victim slots draw from the injector's own seeded rng over
    `victims` (host keys; default: every p2p lane at arm time), so the
    blast radius is confinable and the whole run replays per seed."""

    def __init__(self, host, plan: FaultPlan, *,
                 victims: Optional[Sequence[Any]] = None):
        self.host = host
        self.plan = plan
        self.victims = list(victims) if victims is not None else None
        self._rng = random.Random(plan.seed ^ 0x51C)
        self.installed = False
        # armed state the seam callbacks consume
        self._dispatch_armed: List[dict] = []  # {slot, persist}
        self._harvest_armed = 0
        self._storm_remaining = 0
        self._checkpoint_armed = 0
        self._journal_armed = 0
        # observability: everything fired, for blast-radius assertions
        self.fired: Dict[str, int] = {k: 0 for k in ALL_FAULT_KINDS}
        self.bitflips: List[dict] = []  # {tick, key, slot, frame}
        self.corrupted_checkpoints: List[str] = []
        self._m_fired = faults_injected_counter()

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------

    def install(self) -> "FaultInjector":
        assert not self.installed
        assert self.host.fault_seam is None, "host already has a seam"
        self.host.fault_seam = self
        self.host.device.fault_seam = self
        self.installed = True
        return self

    def uninstall(self) -> None:
        if self.installed:
            self.host.fault_seam = None
            self.host.device.fault_seam = None
            self.installed = False

    # ------------------------------------------------------------------
    # the per-tick arm (the drive loop's on_tick hook calls this)
    # ------------------------------------------------------------------

    def _pick_victim(self):
        """One (key, lane) draw from the victim pool — seeded, so the
        run replays. Only lanes still ACTIVELY dispatching are
        eligible: a lane wedged at the prediction gate (e.g. because an
        EARLIER fault quarantined its match sibling) stages no rows, so
        a fault pinned on it could never fire. None when nothing is
        eligible."""
        lanes = self.host._lanes
        pool = [
            k for k in (
                self.victims if self.victims is not None else list(lanes)
            )
            if k in lanes and not lanes[k].failed
            and lanes[k].kind == "p2p" and not lanes[k].starved
        ]
        if not pool:
            return None
        key = pool[self._rng.randrange(len(pool))]
        return key, lanes[key]

    def advance(self, tick: int) -> None:
        for fault in self.plan.at(tick):
            arm = getattr(self, f"_arm_{fault.kind}")
            arm(tick, fault)

    def _note(self, kind: str) -> None:
        self.fired[kind] += 1
        if GLOBAL_TELEMETRY.enabled:
            self._m_fired.labels(kind).inc()
            GLOBAL_TELEMETRY.record("fault_injected", fault=kind)

    def _arm_dispatch_raise(self, tick: int, fault: Fault) -> None:
        victim = self._pick_victim()
        # a victimless fault is ALWAYS one-shot: an unattributed
        # persistent failure has no slot for dispatch_cleared to clear
        # and no culprit for the host to quarantine, so persisting it
        # would raise out of every future dispatch and take the whole
        # host down — exactly what the ladder exists to prevent
        self._dispatch_armed.append({
            "slot": victim[1].slot if victim is not None else None,
            "key": victim[0] if victim is not None else None,
            "persist": bool(fault.params.get("persist", False))
            and victim is not None,
        })

    def _arm_harvest_timeout(self, tick: int, fault: Fault) -> None:
        self._harvest_armed += 1

    def _arm_mailbox_storm(self, tick: int, fault: Fault) -> None:
        self._storm_remaining += int(fault.params.get("storm_len", 6))

    def _arm_checkpoint_corrupt(self, tick: int, fault: Fault) -> None:
        self._checkpoint_armed += 1

    def _arm_journal_stall(self, tick: int, fault: Fault) -> None:
        self._journal_armed += int(fault.params.get("appends", 1))

    def _arm_slot_bitflip(self, tick: int, fault: Fault) -> None:
        """SDC fires immediately: flip one seeded bit of the victim's
        device residue. Default target is a SETTLED snapshot-ring row —
        a few frames behind the live one, so the next rollbacks neither
        re-save (heal) nor load it immediately — which the audit lane's
        recorded-checksum sweep catches deterministically within its
        sampling cadence (live-world flips heal at the next full-state
        rollback resim, so 'state' targets race the healing; see
        docs/DESIGN.md for the cadence math)."""
        victim = self._pick_victim()
        if victim is None:
            return
        key, lane = victim
        target = fault.params.get("target", "ring")
        ring_len = self.host.device.core.ring_len
        ring_slot = None
        if target == "ring":
            ring_slot = max(lane.current_frame - 3, 0) % ring_len
        # suspend the dispatch seam while injecting: the flip's own
        # fence/mailbox flush drives the device, and an armed dispatch
        # fault firing INSIDE advance() would raise out of the injector
        # instead of at the host's recovery ladder
        self.host.device.fault_seam = None
        try:
            desc = self.host.device.inject_slot_bitflip(
                lane.slot, seed=self._rng.randrange(1 << 30),
                target=target, ring_slot=ring_slot,
            )
        finally:
            self.host.device.fault_seam = self
        self.bitflips.append({
            "tick": tick, "key": key, "slot": lane.slot,
            "frame": lane.current_frame, **desc,
        })
        self._note("slot_bitflip")

    # ------------------------------------------------------------------
    # seam callbacks — the device core / host consult these
    # ------------------------------------------------------------------

    def before_dispatch(self, op: str, slots: Sequence[int]) -> None:
        """Device-core seam, consulted at every dispatch/drive entry
        BEFORE the program runs (worlds untouched on raise). `slots` is
        the batch's live LOGICAL slots."""
        live = set(int(s) for s in slots)
        for armed in list(self._dispatch_armed):
            slot = armed["slot"]
            if slot is not None and slot not in live:
                continue
            if not armed["persist"]:
                self._dispatch_armed.remove(armed)
            self._note("dispatch_raise")
            raise DeviceDispatchFailed(
                "injected device runtime failure",
                op=op,
                slots=() if slot is None else (slot,),
                injected=True,
            )

    def dispatch_cleared(self, slot: int) -> None:
        """The host quarantined `slot`: persistent dispatch faults
        pinned on it stop firing (the fault 'lives in the slot')."""
        self._dispatch_armed = [
            a for a in self._dispatch_armed if a["slot"] != slot
        ]

    def before_journal_append(self, path: str) -> None:
        """Host seam, consulted before each journal frontier drain:
        raises the simulated disk refusal (the host tap degrades the
        lane to unjournaled — typed, with an invariant trip — and
        serving continues untouched)."""
        if self._journal_armed > 0:
            self._journal_armed -= 1
            self._note("journal_stall")
            from ..errors import JournalStalled

            raise JournalStalled(
                "injected filesystem refusal (ENOSPC)",
                path=path, errno=28,
            )

    def before_harvest(self, op: str, pending: int = 0) -> None:
        """Host seam, consulted before checksum readbacks resolve."""
        if self._harvest_armed > 0:
            self._harvest_armed -= 1
            self._note("harvest_timeout")
            raise HarvestTimeout(
                "injected readback timeout", op=op, pending=pending,
            )

    def on_stage(self, phys: int) -> bool:
        """Device-core seam, consulted per mailbox stage: True forces
        the overflow path (note_overflow + drive first) as if the lane
        were full — the commit overflow storm."""
        if self._storm_remaining > 0:
            self._storm_remaining -= 1
            self._note("mailbox_storm")
            return True
        return False

    def after_checkpoint(self, path: str) -> None:
        """Host seam, consulted after a durable checkpoint lands:
        truncates the file to simulate a torn/corrupted write that
        slipped past the filesystem. load_device_checkpoint's manifest
        check must surface it as typed CheckpointIncompatible."""
        if self._checkpoint_armed <= 0:
            return
        self._checkpoint_armed -= 1
        size = os.path.getsize(path)
        with open(path, "r+b") as f:
            f.truncate(max(size // 2, 1))
        self.corrupted_checkpoints.append(path)
        self._note("checkpoint_corrupt")

    # ------------------------------------------------------------------

    def section(self) -> dict:
        return {
            "seed": self.plan.seed,
            "fired": dict(self.fired),
            "bitflips": list(self.bitflips),
            "corrupted_checkpoints": list(self.corrupted_checkpoints),
            "armed_dispatch": len(self._dispatch_armed),
        }
