"""Device-domain fault tolerance: the deterministic fault seam
(serve/faults.py), slot quarantine with survivor bit-exactness, the
sampled SDC audit lane, the degradation ladder, and the fleet agent's
quarantine mini-failover.

The contract under test: one poisoned slot (or one failed dispatch, or
one wedged readback) costs exactly that slot — every surviving session
keeps ticking BIT-EXACTLY (state + ring bytes + checksum history) vs an
unfaulted twin, every quarantine surfaces as a typed SlotPoisoned with
a forensics bundle, and injected silent corruption is caught by the
audit lane within its sampling bound. Both serving arms (resident
mailbox loop and its dispatch-per-tick twin) and both layouts
(single-device and the 8-shard session mesh) are pinned.
"""

import numpy as np
import pytest

import jax

from ggrs_tpu.errors import (
    CheckpointIncompatible,
    DeviceDispatchFailed,
    InvalidRequest,
    InvariantViolation,
    MailboxLaneFull,
    SlotPoisoned,
)
from ggrs_tpu.models.ex_game import ExGame
from ggrs_tpu.network.sockets import InMemoryNetwork
from ggrs_tpu.obs import GLOBAL_TELEMETRY
from ggrs_tpu.serve import SessionHost
from ggrs_tpu.serve.faults import FAULT_KINDS, Fault, FaultInjector, FaultPlan
from ggrs_tpu.serve.loadgen import (
    FRAME_MS,
    build_matches,
    make_scripts,
    sync_fleet,
)
from ggrs_tpu.utils.clock import FakeClock

ENTITIES = 8


def _telemetry(tmp_path):
    GLOBAL_TELEMETRY.reset()
    GLOBAL_TELEMETRY.enabled = True
    GLOBAL_TELEMETRY.dump_dir = str(tmp_path)


def _telemetry_off():
    GLOBAL_TELEMETRY.enabled = False
    GLOBAL_TELEMETRY.dump_dir = None
    GLOBAL_TELEMETRY.reset()


def build_fleet(*, resident, sessions=16, ticks=60, seed=11, loss=0.0,
                plan=None, victims_matches=None, checkpoint_at=None,
                checkpoint_path=None, mesh=None, collect=None,
                **host_kw):
    """A seeded loadgen fleet with an optional FaultInjector. loss=0 by
    default: delivery is then deterministic regardless of rng draws, so
    a fault that changes the VICTIM match's traffic cannot perturb the
    survivors — the survivor-bitwise-parity arms rest on that."""
    clock = FakeClock()
    net = InMemoryNetwork(
        clock, latency_ms=20, jitter_ms=0, loss=loss, seed=seed
    )
    host = SessionHost(
        ExGame(num_players=4, num_entities=ENTITIES),
        max_prediction=8, num_players=4, max_sessions=sessions + 4,
        clock=clock, idle_timeout_ms=0, mesh=mesh,
        resident=resident, resident_ticks=8,
        max_inflight_rows=4 * (sessions + 4), **host_kw,
    )
    matches = build_matches(host, net, clock, sessions=sessions, seed=seed)
    sync_fleet(host, matches, clock)
    injector = None
    if plan is not None:
        victims = (
            [k for m in victims_matches for k in matches[m]]
            if victims_matches is not None
            else None
        )
        injector = FaultInjector(host, plan, victims=victims).install()
    scripts = make_scripts(matches, ticks, seed=seed)
    desyncs = []
    for t in range(ticks):
        if injector is not None:
            injector.advance(t)
        for m, keys in enumerate(matches):
            for k, key in enumerate(keys):
                if key in host._lanes:  # quarantined victims drop out
                    host.submit_input(key, k, bytes([scripts[(m, k)][t]]))
        for key, evs in host.tick().items():
            desyncs += [
                (key, e) for e in evs
                if type(e).__name__ == "DesyncDetected"
            ]
        if checkpoint_at is not None and t == checkpoint_at:
            host.checkpoint(checkpoint_path)
        if collect is not None:
            collect(t, host)
        clock.advance(FRAME_MS)
    audit_every = getattr(host, "_audit_every", 0)
    if audit_every:
        # audit cooldown: a fault injected on the run's last ticks must
        # still get its sampling bound's worth of audit passes (no
        # inputs are submitted, so no lane advances — read-only ticks)
        for _ in range(2 * audit_every + 2):
            host.tick()
            clock.advance(FRAME_MS)
    host.device.block_until_ready()
    host._resolve_audits()
    return host, matches, injector, desyncs


def survivor_desyncs(desyncs, host, matches, skip_matches):
    skip_keys = {
        k for m in skip_matches for k in matches[m]
    }
    return [(k, e) for k, e in desyncs if k not in skip_keys]


def assert_survivors_bitexact(host_f, host_t, matches, skip_matches):
    """Surviving sessions of the faulted arm vs the SAME keys on the
    unfaulted twin: frames, checksum histories, live world bytes AND
    ring bytes."""
    compared = 0
    for m, keys in enumerate(matches):
        if m in skip_matches:
            continue
        for key in keys:
            sf = host_f.session(key)
            st = host_t.session(key)
            assert sf.current_frame == st.current_frame > 0, (m, key)
            assert sf.local_checksum_history == st.local_checksum_history
            ex_f = host_f.device.export_slot(host_f._lanes[key].slot)
            ex_t = host_t.device.export_slot(host_t._lanes[key].slot)
            for part in ("state", "ring"):
                la = jax.tree_util.tree_leaves(ex_f[part])
                lb = jax.tree_util.tree_leaves(ex_t[part])
                for a, b in zip(la, lb):
                    np.testing.assert_array_equal(a, b)
            compared += 1
    assert compared > 0


# ----------------------------------------------------------------------
# the acceptance soak: every fault kind, survivors bit-exact, SDC
# caught, quarantines typed + forensics — resident and twin arms
# ----------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("resident", [True, False])
def test_fault_soak_every_kind_survivors_bitexact(tmp_path, resident):
    _telemetry(tmp_path)
    try:
        kinds = list(FAULT_KINDS)
        if not resident:
            # the mailbox seam does not exist on the dispatch-per-tick
            # arm: its storm kind is vacuous there
            kinds.remove("mailbox_storm")
        ticks = 70
        plan = FaultPlan(5, ticks, kinds=kinds, persist_dispatch=True)
        corrupt_ticks = [
            f.tick for f in plan.all_faults()
            if f.kind == "checkpoint_corrupt"
        ]
        ckpt = str(tmp_path / f"soak_{resident}.npz")
        host_f, matches, inj, desyncs = build_fleet(
            resident=resident, ticks=ticks, plan=plan,
            victims_matches=(0, 1),
            sdc_audit_every=2, checkpoint_at=corrupt_ticks[0],
            checkpoint_path=ckpt,
        )
        host_t, matches_t, _, desyncs_t = build_fleet(
            resident=resident, ticks=ticks,
            sdc_audit_every=2,
            checkpoint_at=corrupt_ticks[0],
            checkpoint_path=str(tmp_path / f"twin_{resident}.npz"),
        )
        # every armed kind actually fired
        for kind in kinds:
            assert inj.fired[kind] >= 1, (kind, inj.fired)
        # the injected SDC was caught by the audit lane and every
        # quarantine surfaced typed, with a forensics bundle
        poisoned = host_f.take_quarantines()
        assert poisoned, "no quarantines surfaced"
        reasons = {p.reason for p in poisoned}
        assert "sdc_audit" in reasons, reasons
        flipped_keys = {b["key"] for b in inj.bitflips}
        assert flipped_keys & {p.key for p in poisoned}
        for p in poisoned:
            assert isinstance(p, SlotPoisoned)
            assert p.forensics is not None
        assert host_f.audit_mismatches >= 1
        # the corrupted checkpoint is DETECTED, typed — never a shape
        # error or a silently-wrong restore
        assert inj.corrupted_checkpoints == [ckpt]
        from ggrs_tpu.utils.checkpoint import load_device_checkpoint

        with pytest.raises(CheckpointIncompatible):
            load_device_checkpoint(ckpt)
        # zero desyncs among survivors, and the survivors are BIT-EXACT
        # (state + ring + checksum history) vs the unfaulted twin
        assert not survivor_desyncs(desyncs, host_f, matches, {0, 1})
        assert not desyncs_t
        assert_survivors_bitexact(host_f, host_t, matches, {0, 1})
        # the fault counters flowed through both exporters
        prom = GLOBAL_TELEMETRY.prometheus()
        snap = host_f.telemetry()
        for name in (
            "ggrs_slot_quarantines_total",
            "ggrs_sdc_audits_total",
            "ggrs_sdc_mismatches_total",
            "ggrs_faults_injected_total",
        ):
            assert name in prom
            assert name in snap["metrics"]
        assert snap["host"]["quarantines"] == len(poisoned)
    finally:
        _telemetry_off()


@pytest.mark.slow
def test_fault_soak_sharded_resident(tmp_path):
    """The sharded acceptance arm: the same every-kind soak on an
    8-shard session mesh resident host, survivors bit-exact vs a
    SINGLE-DEVICE unfaulted twin (cross-layout and cross-fault at
    once)."""
    from ggrs_tpu.parallel.mesh import make_session_mesh

    _telemetry(tmp_path)
    try:
        ticks = 50
        plan = FaultPlan(9, ticks, persist_dispatch=True)
        host_f, matches, inj, desyncs = build_fleet(
            resident=True, mesh=make_session_mesh(8), ticks=ticks,
            plan=plan, victims_matches=(0, 1), sdc_audit_every=2, seed=23,
        )
        host_t, _, _, desyncs_t = build_fleet(
            resident=False, ticks=ticks, sdc_audit_every=2, seed=23,
        )
        for kind in FAULT_KINDS:
            if kind == "checkpoint_corrupt":
                continue  # needs a checkpoint call; covered above
            assert inj.fired[kind] >= 1, (kind, inj.fired)
        poisoned = host_f.take_quarantines()
        assert any(p.reason == "sdc_audit" for p in poisoned)
        assert not survivor_desyncs(desyncs, host_f, matches, {0, 1})
        assert not desyncs_t
        assert_survivors_bitexact(host_f, host_t, matches, {0, 1})
    finally:
        _telemetry_off()


# ----------------------------------------------------------------------
# focused arms (fast: tier-1)
# ----------------------------------------------------------------------


def test_fault_plan_is_pure_function_of_seed():
    a = FaultPlan(7, 100)
    b = FaultPlan(7, 100)
    assert a.section() == b.section()
    assert FaultPlan(8, 100).section() != a.section()
    kinds = {f.kind for f in a.all_faults()}
    assert kinds == set(FAULT_KINDS)
    many = FaultPlan(7, 100, events_per_kind=3)
    assert len(many.all_faults()) == 3 * len(FAULT_KINDS)


def test_transient_dispatch_raise_retries_bitexact():
    """A one-shot dispatch raise (worlds untouched) is absorbed by one
    retry: no quarantine, no desync, the WHOLE fleet bit-exact vs an
    unfaulted twin."""
    plan = FaultPlan(
        3, 30, kinds=("dispatch_raise",), events_per_kind=2,
        persist_dispatch=False,
    )
    host_f, matches, inj, desyncs = build_fleet(
        resident=False, sessions=8, ticks=30, plan=plan,
    )
    host_t, _, _, desyncs_t = build_fleet(
        resident=False, sessions=8, ticks=30,
    )
    assert inj.fired["dispatch_raise"] == 2
    assert host_f.device_faults >= 2
    assert host_f.quarantines_total == 0
    assert not desyncs and not desyncs_t
    assert_survivors_bitexact(host_f, host_t, matches, set())


def test_persistent_dispatch_raise_quarantines_culprit_only(tmp_path):
    """A fault pinned on one slot: the culprit is quarantined (typed,
    forensics), survivors re-dispatch bit-exactly."""
    _telemetry(tmp_path)
    try:
        plan = FaultPlan(
            4, 30, kinds=("dispatch_raise",), persist_dispatch=True,
        )
        host_f, matches, inj, desyncs = build_fleet(
            resident=False, sessions=8, ticks=30, plan=plan,
            victims_matches=(0,),
        )
        host_t, _, _, _ = build_fleet(resident=False, sessions=8, ticks=30)
        poisoned = host_f.take_quarantines()
        assert len(poisoned) == 1
        assert poisoned[0].reason == "dispatch_failed"
        assert poisoned[0].key in matches[0]
        assert poisoned[0].forensics is not None
        assert not survivor_desyncs(desyncs, host_f, matches, {0})
        assert_survivors_bitexact(host_f, host_t, matches, {0})
    finally:
        _telemetry_off()


def test_resident_drive_failures_degrade_to_dispatch_per_tick():
    """The degradation ladder's last rung: repeated drive failures flip
    the resident host to its dispatch-per-tick twin — still serving,
    still bit-exact — instead of crashing the fleet."""
    plan = FaultPlan(
        6, 40, kinds=("dispatch_raise",), events_per_kind=3,
        persist_dispatch=False,
    )
    host_f, matches, inj, desyncs = build_fleet(
        resident=True, sessions=8, ticks=40, plan=plan,
        drive_failure_limit=3,
    )
    host_t, _, _, _ = build_fleet(resident=True, sessions=8, ticks=40)
    assert inj.fired["dispatch_raise"] == 3
    assert host_f._resident_degraded
    assert host_f.degrades >= 1
    assert host_f.quarantines_total == 0
    assert not desyncs
    section = host_f._host_section()
    assert section["resident"]["degraded"] is True
    assert_survivors_bitexact(host_f, host_t, matches, set())


def test_sdc_bitflip_detected_within_sampling_bound(tmp_path):
    """One injected ring-row bit flip: the audit lane's at-rest sweep
    catches it within sdc_audit_every ticks of the flip and
    quarantines the slot with reason sdc_audit."""
    _telemetry(tmp_path)
    try:
        flip_tick = 12
        plan = FaultPlan(2, 13, kinds=())
        plan._by_tick = {flip_tick: [Fault(flip_tick, "slot_bitflip")]}
        caught_at = []

        def collect(t, host):
            if host.quarantines_total and not caught_at:
                caught_at.append(t)

        host, matches, inj, desyncs = build_fleet(
            resident=False, sessions=4, ticks=24, plan=plan,
            victims_matches=(0,), sdc_audit_every=2, collect=collect,
        )
        assert inj.fired["slot_bitflip"] == 1
        poisoned = host.take_quarantines()
        assert len(poisoned) == 1
        assert poisoned[0].reason == "sdc_audit"
        assert poisoned[0].key == inj.bitflips[0]["key"]
        assert caught_at and caught_at[0] - flip_tick <= 2 + 1
        assert host.audits_sampled > 0
        assert host.audit_mismatches == 1
    finally:
        _telemetry_off()


@pytest.mark.parametrize("resident", [True, False])
def test_kill_mid_harvest_checkpoint_completes_or_rolls_back(
    tmp_path, resident
):
    """A checkpoint racing an in-flight checksum batch under an injected
    harvest timeout: the export blocks-and-retries, so the checkpoint
    file is complete and loadable (never torn, never silently skipped)
    and the host keeps serving after."""
    plan = FaultPlan(2, 22, kinds=())
    # arm a harvest timeout right before the mid-run checkpoint fires,
    # while the resident arm's fill cycle holds an unforced
    # _FutureChecksumBatch
    plan._by_tick = {14: [Fault(14, "harvest_timeout")] * 2}
    path = str(tmp_path / f"mid_harvest_{resident}.npz")
    host, matches, inj, desyncs = build_fleet(
        resident=resident, sessions=4, ticks=24, plan=plan,
        checkpoint_at=14, checkpoint_path=path,
    )
    assert inj.fired["harvest_timeout"] >= 1
    assert host.harvest_timeouts >= 1
    assert not desyncs
    from ggrs_tpu.tpu.backend import MultiSessionDeviceCore

    restored = MultiSessionDeviceCore.restore(
        path, ExGame(num_players=4, num_entities=ENTITIES)
    )
    assert restored.capacity == host.device.capacity


def test_mailbox_storm_degrades_to_extra_drives_never_drops():
    """An injected commit overflow storm: every stormed stage degrades
    to an extra driver dispatch; inputs are never dropped and the fleet
    stays bit-exact vs the unstormed twin."""
    plan = FaultPlan(8, 30, kinds=("mailbox_storm",), storm_len=6)
    host_f, matches, inj, desyncs = build_fleet(
        resident=True, sessions=8, ticks=30, plan=plan,
    )
    host_t, _, _, _ = build_fleet(resident=True, sessions=8, ticks=30)
    assert inj.fired["mailbox_storm"] == 6
    assert host_f.device.mailbox.overflows >= 6
    assert not desyncs
    assert_survivors_bitexact(host_f, host_t, matches, set())


def test_typed_errors_replace_runtime_asserts():
    from ggrs_tpu.serve.migrate import HostGroup

    clock = FakeClock()
    host = SessionHost(
        ExGame(num_players=2, num_entities=ENTITIES),
        max_prediction=8, num_players=2, max_sessions=2, clock=clock,
        resident=True, resident_ticks=2,
    )
    mbox = host.device.mailbox
    row = host.device.core.pad_tick_row()
    mbox.stage(0, row, 1, True)
    mbox.stage(0, row, 1, True)
    with pytest.raises(MailboxLaneFull) as exc:
        mbox.stage(0, row, 1, True)
    assert exc.value.lane == 0 and exc.value.depth == 2
    group = HostGroup([host], clock=clock)
    with pytest.raises(InvalidRequest):
        group.restore_host(0, "/nonexistent.npz")  # never killed
    # typed DeviceDispatchFailed carries its containment context
    err = DeviceDispatchFailed("boom", op="megabatch", slots=(3,),
                              injected=True)
    assert err.slots == (3,) and err.injected and "megabatch" in str(err)


def test_invariant_monitor_trips_on_wedged_lane(tmp_path):
    """A RUNNING lane that stops advancing past wedge_limit_ticks trips
    the lane_wedged invariant: typed, with a forensics bundle — the
    PR 8 WAN-soak bug class, watched deliberately."""
    _telemetry(tmp_path)
    try:
        clock = FakeClock()
        net = InMemoryNetwork(clock, latency_ms=10, jitter_ms=0, loss=0.0)
        host = SessionHost(
            ExGame(num_players=4, num_entities=ENTITIES),
            max_prediction=8, num_players=4, max_sessions=6,
            clock=clock, idle_timeout_ms=0, wedge_limit_ticks=12,
        )
        matches = build_matches(host, net, clock, sessions=4, seed=3)
        sync_fleet(host, matches, clock)
        scripts = make_scripts(matches, 40, seed=3)
        for t in range(40):
            if t == 8:
                # blackhole peer 0 both ways: its match wedges at the
                # prediction gate while staying RUNNING
                net.set_blackhole([(0, 0)], True)
            for m, keys in enumerate(matches):
                for k, key in enumerate(keys):
                    host.submit_input(key, k, bytes([scripts[(m, k)][t]]))
            host.tick()
            clock.advance(FRAME_MS)
        trips = [
            e for e in host.invariant_trips
            if e.invariant == "lane_wedged"
        ]
        assert trips, "wedged lane never tripped the monitor"
        assert isinstance(trips[0], InvariantViolation)
        assert trips[0].forensics is not None
    finally:
        _telemetry_off()


# ----------------------------------------------------------------------
# fleet x resident (satellite): agents on the resident loop, SIGKILL
# restore + cross-process migration bit-exact, quarantine mini-failover
# ----------------------------------------------------------------------


def _fleet_rig(tmp_path, *, resident, n_agents=2, checkpoint_every=8):
    from ggrs_tpu.fleet.agent import AgentCore
    from ggrs_tpu.fleet.director import Director
    from ggrs_tpu.fleet.wire import conn_pair

    clock = FakeClock()
    game = ExGame(num_players=2, num_entities=ENTITIES)
    director = Director(
        clock=clock, base_dir=str(tmp_path), seed=1,
        hb_interval_ms=50, suspicion_misses=4,
    )
    agents = []
    for i in range(n_agents):
        a_conn, d_conn = conn_pair()
        core = AgentCore(
            game, base_dir=str(tmp_path), clock=clock,
            max_sessions=8, num_players=2, hb_interval_ms=50,
            checkpoint_every=checkpoint_every, label=f"a{i}",
            resident=resident if i == 0 else False, resident_ticks=4,
        )
        core.attach_conn(a_conn)
        director.attach_conn(d_conn)
        core.start()
        agents.append(core)

    def pump(n=1, adv=10):
        for _ in range(n):
            for a in agents:
                a.step()
            director.step()
            clock.advance(adv)

    director.on_wait = lambda: pump(1, 2)
    pump(10)
    assert len(director.hosts) == n_agents
    return clock, director, agents, pump


def _drive_done(agents, pump, max_steps=4000):
    for _ in range(max_steps):
        pump(1)
        if all(
            i.done or i.failed
            for c in agents if c.terminated is None
            for i in c.islands.values()
        ):
            return
    raise AssertionError("islands failed to finish")


@pytest.mark.slow
def test_agent_resident_twin_parity_and_migration(tmp_path):
    """Satellite: agent 0 runs resident=True. Both matches finish with
    histories bit-identical to the in-process unfaulted twin, and a
    cross-process migration OUT of the resident agent (mailbox drained
    into the ticket) is observationally neutral."""
    from ggrs_tpu.fleet.chaos import compare_with_twin
    from ggrs_tpu.fleet.island import MatchSpec

    clock, director, agents, pump = _fleet_rig(tmp_path, resident=True)
    specs = [
        MatchSpec(match_id=0, players=2, ticks=48, seed=100,
                  entities=ENTITIES, wan={}),
        MatchSpec(match_id=1, players=2, ticks=48, seed=101,
                  entities=ENTITIES),
    ]
    owners = {s.match_id: director.place_match(s) for s in specs}
    # both matches onto the RESIDENT agent, then migrate one off it
    if owners[0] != 0:
        director.migrate_match(0, 0)
    if owners[1] != 0:
        director.migrate_match(1, 0)
    for _ in range(20):
        pump(1)
    director.migrate_match(0, 1)  # resident -> non-resident, mid-match
    _drive_done(agents, pump)
    reports = director.collect_reports()
    parity = compare_with_twin(specs, reports, set())
    assert parity["clean_exact"], parity


@pytest.mark.slow
def test_agent_resident_sigkill_restore_bitexact(tmp_path):
    """Satellite: a resident agent's crash checkpoint restores on a
    FRESH (non-resident) agent bit-exactly — the SIGKILL-restore path
    out of resident mode, in-process twin of the process soak."""
    from ggrs_tpu.fleet.agent import AgentCore
    from ggrs_tpu.fleet.chaos import compare_with_twin
    from ggrs_tpu.fleet.island import MatchSpec
    from ggrs_tpu.fleet.ticket import loads_ticket, read_ticket_file

    clock, director, agents, pump = _fleet_rig(
        tmp_path, resident=True, n_agents=1, checkpoint_every=4
    )
    spec = MatchSpec(match_id=0, players=2, ticks=48, seed=42,
                     entities=ENTITIES, wan={})
    director.place_match(spec)
    for _ in range(30):
        pump(1)
    assert agents[0].checkpoints_written > 0
    # "SIGKILL": drop the agent; restore its islands from the on-disk
    # ticket into a fresh NON-resident host (cross-arm restore)
    path = agents[0].checkpoint_path()
    entries, meta = loads_ticket(read_ticket_file(path))
    fresh = AgentCore(
        ExGame(num_players=2, num_entities=ENTITIES),
        base_dir=str(tmp_path), clock=clock, max_sessions=8,
        num_players=2, label="fresh", resident=False,
    )
    from ggrs_tpu.fleet.ticket import import_islands

    for island in import_islands(fresh.host, entries):
        fresh.islands[island.spec.match_id] = island
    agents[0].islands.clear()  # the killed incarnation is gone
    for _ in range(4000):
        fresh.step()
        clock.advance(10)
        if all(i.done for i in fresh.islands.values()):
            break
    report = {
        0: {
            "islands": {
                "0": {
                    **fresh.islands[0].section(),
                    "histories": {
                        str(k): {str(f): c for f, c in h.items()}
                        for k, h in fresh.islands[0].histories().items()
                    },
                    "digest": fresh.islands[0].state_digest(fresh.host),
                    "spread": False,
                }
            }
        }
    }
    parity = compare_with_twin([spec], report, set())
    assert parity["clean_exact"], parity


@pytest.mark.slow
def test_agent_quarantine_mini_failover_rebuilds_from_ticket(tmp_path):
    """Tentpole x fleet: a quarantined slot on an agent tears down the
    owning match and REBUILDS it from the last crash-checkpoint ticket
    (the PR 11 adopt machinery as a mini-failover); the heartbeat
    reports the outcome to the director."""
    _telemetry(tmp_path)
    try:
        from ggrs_tpu.fleet.island import MatchSpec

        clock, director, agents, pump = _fleet_rig(
            tmp_path, resident=True, n_agents=1, checkpoint_every=4
        )
        agent = agents[0]
        agent.host._audit_every = 0  # quarantine via direct poison below
        spec = MatchSpec(match_id=0, players=2, ticks=64, seed=9,
                         entities=ENTITIES, wan={})
        director.place_match(spec)
        for _ in range(24):
            pump(1)
        assert agent.checkpoints_written > 0
        # poison one of the match's slots the direct way (the injector
        # path is pinned elsewhere): quarantine fires the mini-failover
        key = next(iter(agent.islands[0].keys.values()))
        agent.host.quarantine(key, "sdc_audit")
        pump(1)
        assert agent.quarantines.get(0) == "rebuilt"
        island = agent.islands[0]
        assert island.keys and not island.failed
        # the rebuilt match finishes clean
        _drive_done(agents, pump)
        assert island.desyncs == 0
        # ... and the director heard about it
        pump(20)
        hr = director.hosts[agent.host_id]
        assert hr.quarantines.get("0") == "rebuilt"
    finally:
        _telemetry_off()
