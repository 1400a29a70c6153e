"""Fleet operations: live session migration, HostGroup spillover /
kill→restore, mass-disconnect storms, and the WAN-chaos acceptance soak.

The parity discipline matches the serve suite: a migrated (or disturbed)
session must stay a BIT-EXACT replica of an undisturbed twin driven with
the same scripts — checksum histories agree frame-by-frame, and the live
device worlds compare equal byte-for-byte. Desync detection runs
throughout, so the zero-desync assertions are backed by real cross-peer
comparisons."""

import random

import numpy as np
import pytest

from ggrs_tpu import PlayerType, SessionBuilder, SessionState
from ggrs_tpu.errors import (
    CheckpointIncompatible,
    DrainStalled,
    GroupSaturated,
    HostFull,
    MigrationIncompatible,
)
from ggrs_tpu.models.ex_game import ExGame
from ggrs_tpu.network.sockets import InMemoryNetwork
from ggrs_tpu.obs import GLOBAL_TELEMETRY
from ggrs_tpu.serve import HostGroup, SessionHost, migrate_session
from ggrs_tpu.serve.migrate import export_session, import_session
from ggrs_tpu.types import DesyncDetection
from ggrs_tpu.utils.clock import FakeClock

ENTITIES = 16
FRAME_MS = 16


def make_host(clock, *, max_sessions=4, num_players=2, entities=ENTITIES,
              **kw):
    return SessionHost(
        ExGame(num_players=num_players, num_entities=entities),
        max_prediction=8,
        num_players=num_players,
        max_sessions=max_sessions,
        clock=clock,
        idle_timeout_ms=0,
        **kw,
    )


def peer(net, clock, addr, other, handle, *, seed=0, desync_interval=10,
         disconnect_timeout_ms=2000, sparse=False):
    """One half of a real 2-player P2P match over the virtual network."""
    return (
        SessionBuilder(input_size=1)
        .with_num_players(2)
        .with_max_prediction_window(8)
        .with_input_delay(1)
        .with_sparse_saving_mode(sparse)
        .with_desync_detection_mode(DesyncDetection.on(interval=desync_interval))
        .with_disconnect_timeout(disconnect_timeout_ms)
        .with_clock(clock)
        .with_rng(random.Random(seed * 131 + handle + 7))
        .add_player(PlayerType.local(), handle)
        .add_player(PlayerType.remote(other), 1 - handle)
        .start_p2p_session(net.socket(addr))
    )


def solo_session(net, addr, *, players=2):
    b = SessionBuilder(input_size=1).with_num_players(players)
    for h in range(players):
        b = b.add_player(PlayerType.local(), h)
    return b.start_p2p_session(net.socket(addr))


def sync_all(hosts, sessions, clock, max_ticks=600):
    for _ in range(max_ticks):
        for h in hosts:
            h.tick()
        clock.advance(FRAME_MS)
        if all(
            s.current_state() == SessionState.RUNNING for s in sessions
        ):
            return
    raise AssertionError("match failed to synchronize")


# ----------------------------------------------------------------------
# live migration: bitwise parity against an unmigrated twin
# ----------------------------------------------------------------------


def test_live_migration_bitwise_parity_vs_unmigrated_twin():
    """Two identical 2-player matches (same scripts) on host1; one peer
    of match A migrates to host2 mid-match. Peers keep exchanging
    checksums across the handoff (no resync, desync detection ON);
    afterwards the migrated session's world is BIT-IDENTICAL to the twin
    match's corresponding peer, and their published checksum histories
    agree frame by frame."""
    clock = FakeClock()
    net = InMemoryNetwork(clock, latency_ms=20, jitter_ms=0, loss=0.0)
    h1, h2 = make_host(clock), make_host(clock)

    a0 = peer(net, clock, "a0", "a1", 0, seed=1)
    a1 = peer(net, clock, "a1", "a0", 1, seed=2)
    b0 = peer(net, clock, "b0", "b1", 0, seed=3)
    b1 = peer(net, clock, "b1", "b0", 1, seed=4)
    ka0, ka1 = h1.attach(a0), h1.attach(a1)
    kb0, kb1 = h1.attach(b0), h1.attach(b1)
    sync_all([h1, h2], [a0, a1, b0, b1], clock)

    script = lambda h, t: (t * 3 + h * 5 + 1) % 16  # same for A and B
    desyncs = []

    def drive(keymap, t):
        # keymap: session -> (host, key); twin peers share the script
        for sess, (host, key), h in keymap:
            host.submit_input(key, h, bytes([script(h, t)]))
        for host in (h1, h2):
            for key, evs in host.tick().items():
                desyncs.extend(
                    e for e in evs if type(e).__name__ == "DesyncDetected"
                )
        clock.advance(FRAME_MS)

    keymap = [
        (a0, (h1, ka0), 0), (a1, (h1, ka1), 1),
        (b0, (h1, kb0), 0), (b1, (h1, kb1), 1),
    ]
    for t in range(24):
        drive(keymap, t)

    # --- the handoff: a0 moves to h2 mid-match
    new_ka0 = migrate_session(h1, h2, ka0)
    assert a0.host_key == new_ka0 and a0._host is h2
    keymap[0] = (a0, (h2, new_ka0), 0)
    for t in range(24, 90):
        drive(keymap, t)

    assert not desyncs, f"migration caused desyncs: {desyncs[:3]}"
    # both matches ran the same scripts: frame counters agree...
    assert a0.current_frame == b0.current_frame > 40
    # ...checksum exchange kept running across the handoff (non-vacuous)
    assert len(a0.local_checksum_history) > 2
    common = set(a0.local_checksum_history) & set(b0.local_checksum_history)
    assert common, "twin matches published no comparable frames"
    for f in common:
        assert (
            a0.local_checksum_history[f] == b0.local_checksum_history[f]
        ), f"frame {f}: migrated session diverged from its twin"
    # ...and the live device worlds are bit-identical
    migrated = h2.device.state_numpy(h2._lanes[new_ka0].slot)
    twin = h1.device.state_numpy(h1._lanes[kb0].slot)
    for k in migrated:
        np.testing.assert_array_equal(
            np.asarray(migrated[k]), np.asarray(twin[k]),
            err_msg=f"state[{k}]",
        )


def test_migration_rejects_incompatible_destination_and_rolls_back():
    """A destination running a different game config must refuse the
    ticket with the typed MigrationIncompatible — and the one-call
    migrate_session rolls the session back onto the source, so a failed
    migration degrades to 'nothing happened'."""
    clock = FakeClock()
    net = InMemoryNetwork(clock)
    src = make_host(clock)
    wrong = make_host(clock, entities=ENTITIES * 2)  # different world shape
    sess = solo_session(net, "m")
    key = src.attach(sess)
    for t in range(4):
        for h in (0, 1):
            src.submit_input(key, h, bytes([t % 16]))
        src.tick()
        clock.advance(FRAME_MS)
    with pytest.raises(MigrationIncompatible):
        migrate_session(src, wrong, key)
    # rolled back: still hosted on src, still advancing
    assert sess._host is src
    rolled_key = sess.host_key
    for h in (0, 1):
        src.submit_input(rolled_key, h, b"\x05")
    src.tick()
    assert src._lanes[rolled_key].current_frame == 5
    # a full destination raises HostFull from adopt, with the same rollback
    full = make_host(clock, max_sessions=1)
    full.attach(solo_session(net, "f"))
    with pytest.raises(HostFull):
        migrate_session(src, full, rolled_key)
    assert sess._host is src


def test_export_import_preserves_pending_inputs_and_frame():
    """A session exported BETWEEN submit and tick resumes on the new host
    with its pending-input bookkeeping intact: the first destination tick
    advances it, no input lost."""
    clock = FakeClock()
    net = InMemoryNetwork(clock)
    h1, h2 = make_host(clock), make_host(clock)
    sess = solo_session(net, "p")
    key = h1.attach(sess)
    for h in (0, 1):
        h1.submit_input(key, h, b"\x07")  # submitted, NOT ticked
    ticket = export_session(h1, key)
    assert ticket.current_frame == 0
    assert ticket.pending_inputs == frozenset({0, 1})
    new_key = import_session(h2, ticket)
    h2.tick()
    assert h2._lanes[new_key].current_frame == 1


def test_migration_carries_input_model_stats():
    """Speculating hosts: the migration ticket carries the lane's
    learned input statistics by value (MigrationTicket.input_stats), so
    the destination resumes WARM — its draft model ranks switch
    candidates immediately, where a stats-dropped control restarts cold
    below MIN_HOLDS — and a starved post-handoff drive keeps the
    speculation hit rate positive with zero desyncs: prediction
    continuity across the handoff, not a relearn-from-scratch."""
    clock = FakeClock()
    net = InMemoryNetwork(clock, latency_ms=10, jitter_ms=0, loss=0.0)
    h1 = make_host(clock, speculation=True)
    h2 = make_host(clock, speculation=True)
    h3 = make_host(clock, speculation=True)
    p0 = peer(net, clock, "i0", "i1", 0, seed=70)
    p1 = peer(net, clock, "i1", "i0", 1, seed=71)
    k0, k1 = h1.attach(p0), h1.attach(p1)
    sync_all([h1, h2, h3], [p0, p1], clock)

    desyncs = []

    def tick_all():
        for host in (h1, h2, h3):
            for _, evs in host.tick().items():
                desyncs.extend(
                    e for e in evs if type(e).__name__ == "DesyncDetected"
                )
        clock.advance(FRAME_MS)

    # 6-frame toggle holds: the shape the lane models learn from
    # finalized rows (frames beyond rollback reach)
    script = lambda h, t: (5 if (t // 6) % 2 == 0 else 9) + 4 * h
    for t in range(60):
        h1.submit_input(k0, 0, bytes([script(0, t)]))
        h1.submit_input(k1, 1, bytes([script(1, t)]))
        tick_all()

    # --- the handoff: the ticket carries the learned stats by value
    ticket = export_session(h1, k0)
    assert ticket.input_stats is not None
    assert ticket.input_stats["kind"] == "online"
    assert any(p["holds"] for p in ticket.input_stats["players"])
    new_k0 = import_session(h2, ticket)
    warm = h2.export_input_model_state(new_k0)
    assert warm == ticket.input_stats  # loaded, re-exported: identical
    warm_model = h2._spec._lanes[new_k0].model
    assert warm_model._stats[0].n_holds() >= warm_model.MIN_HOLDS
    # warm: ranks a switch candidate for the held value immediately
    probe = [(60, bytes([5]), 3), None]
    assert warm_model.rank_branches(probe, 60, 8, 6)

    # --- control: the same ticket with the stats dropped imports COLD
    ticket2 = export_session(h2, new_k0)
    stats2 = ticket2.input_stats
    ticket2.input_stats = None
    k3 = import_session(h3, ticket2)
    cold_model = h3._spec._lanes[k3].model
    assert cold_model._stats[0].n_holds() == 0
    assert cold_model.rank_branches(probe, 60, 8, 6) == []
    # restoring the dropped stats warms the lane back up
    assert h3.import_input_model_state(k3, stats2)
    assert h3._spec._lanes[k3].model._stats[0].n_holds() >= 3

    # --- hit-rate continuity: starve the migrated lane on its new home
    # (peer blackholed past the prediction window); held values make the
    # recovery a lineage full hit, so adoption must flow post-handoff
    for t in range(60, 130):
        if t == 70:
            net.set_blackhole({"i1"}, True)
        if t == 84:
            net.set_blackhole({"i1"}, False)
        h3.submit_input(k3, 0, bytes([5]))
        h1.submit_input(k1, 1, bytes([9]))
        tick_all()
    sec = h3._spec.section()
    assert sec["frames_adopted"] > 0 and sec["hit_rate"] > 0.0, sec
    assert not desyncs, f"handoff drive desynced: {desyncs[:3]}"
    assert p0.current_frame > 80 and p1.current_frame > 80


def test_sparse_saving_hosted_session_survives_wan_rtt():
    """Regression for the prediction-threshold gate under SPARSE SAVING:
    set_last_confirmed_frame clamps the watermark to last_saved_frame,
    but _check_last_saved_state repairs last_saved BEFORE the in-advance
    raise whenever the lag reaches the window — so the host's
    fresh-confirmed gate must keep sparse sessions advancing (never
    half-advancing into a PredictionThreshold raise, which the host
    would swallow while dropping the tick's save/rollback requests —
    silent divergence) even when RTT exceeds the prediction window."""
    clock = FakeClock()
    # ~200ms RTT = 12+ frames: every tick runs at the window edge
    net = InMemoryNetwork(clock, latency_ms=100, jitter_ms=0, loss=0.0)
    host = make_host(clock)
    p0 = peer(net, clock, "w0", "w1", 0, seed=60, sparse=True)
    p1 = peer(net, clock, "w1", "w0", 1, seed=61, sparse=True)
    k0, k1 = host.attach(p0), host.attach(p1)
    sync_all([host], [p0, p1], clock)
    desyncs = []
    for t in range(150):
        for key, h in ((k0, 0), (k1, 1)):
            host.submit_input(key, h, bytes([(t * 3 + h) % 16]))
        for _, evs in host.tick().items():
            desyncs.extend(
                e for e in evs if type(e).__name__ == "DesyncDetected"
            )
        clock.advance(FRAME_MS)
    assert not desyncs, f"sparse-saving WAN drive desynced: {desyncs[:3]}"
    # real progress at the window edge (RTT-bound, not wedged)...
    assert p0.current_frame > 60 and p1.current_frame > 60
    # ...and PredictionThreshold never leaked out of an advance (the
    # host records it as the lane's last_error when it does)
    for key in (k0, k1):
        assert host._lanes[key].last_error is None
    # the gate did real work: the session ran throttled at the edge
    assert host._lanes[k0].throttled_ticks > 0


# ----------------------------------------------------------------------
# HostGroup: spillover + bounded retry + typed saturation
# ----------------------------------------------------------------------


def test_hostgroup_spillover_and_typed_saturation():
    clock = FakeClock()
    net = InMemoryNetwork(clock)
    game = ExGame(num_players=2, num_entities=ENTITIES)
    group = HostGroup.build(
        game, 2, clock=clock, max_prediction=8, num_players=2,
        max_sessions=2, idle_timeout_ms=0, max_attempts=2, backoff_ms=16,
    )
    keys = [group.attach(solo_session(net, f"g{i}")) for i in range(4)]
    assert group.active_sessions == 4
    # load-balanced: both hosts carry sessions, and at least one attach
    # landed past a full first choice
    assert all(h.active_sessions == 2 for h in group.hosts)
    with pytest.raises(GroupSaturated) as exc_info:
        group.attach(solo_session(net, "overflow"))
    assert exc_info.value.attempts >= 2
    assert "host0" in exc_info.value.per_host
    assert group.saturations == 1
    # GroupSaturated IS a HostFull: catch-all admission handling works
    assert isinstance(exc_info.value, HostFull)
    # freeing capacity un-saturates the group
    host_idx = group.host_of(keys[0])
    group.hosts[host_idx].detach(group._records[keys[0]].hkey)
    group.tick()  # reconciles the detach into group bookkeeping
    group.attach(solo_session(net, "late"))
    assert group.active_sessions == 4


def test_hostgroup_drain_host_migrates_sessions_to_siblings():
    """Evicting a host from service routes its LIVE sessions through the
    migration handoff to siblings — then drains the empty host."""
    clock = FakeClock()
    net = InMemoryNetwork(clock)
    game = ExGame(num_players=2, num_entities=ENTITIES)
    group = HostGroup.build(
        game, 2, clock=clock, max_prediction=8, num_players=2,
        max_sessions=4, idle_timeout_ms=0,
    )
    keys = [group.attach(solo_session(net, f"d{i}")) for i in range(4)]
    for t in range(6):
        for k in keys:
            for h in (0, 1):
                group.submit_input(k, h, bytes([t % 16]))
        group.tick()
        clock.advance(FRAME_MS)
    victim = group.host_of(keys[0])
    n_victim = len(group.keys_on(victim))
    group.drain_host(victim)
    assert victim in group.dead
    assert not group.keys_on(victim)
    assert group.migrations >= n_victim
    # migrated sessions keep advancing on their new homes
    for t in range(6, 10):
        for k in keys:
            for h in (0, 1):
                group.submit_input(k, h, bytes([t % 16]))
        group.tick()
        clock.advance(FRAME_MS)
    assert all(group.session(k).current_frame == 10 for k in keys)


# ----------------------------------------------------------------------
# mass-disconnect storm (satellite): GC accounting + survivor parity
# ----------------------------------------------------------------------


def test_mass_disconnect_storm_gc_and_survivor_parity():
    """Drop ALL peers of half the fleet in one tick (network blackhole —
    the peers never say goodbye). Disconnect GC must reclaim every
    stormed slot, the eviction counter must account exactly, and the
    surviving match must stay a bitwise replica of an undisturbed twin
    driven with the same scripts."""
    GLOBAL_TELEMETRY.enabled = True
    try:
        clock = FakeClock()
        net = InMemoryNetwork(clock, latency_ms=10, jitter_ms=0, loss=0.0)
        host = make_host(clock, max_sessions=8)

        # M0/M1: the storm victims. M2 (survivor) and M3 (twin) run the
        # same scripts as each other.
        # short disconnect timeout so the storm's GC resolves in tens of
        # ticks instead of the default 2s / 125 ticks (same machinery)
        m = {}
        for i in range(4):
            m[i] = (
                peer(net, clock, f"s{i}a", f"s{i}b", 0, seed=10 + i,
                     disconnect_timeout_ms=480),
                peer(net, clock, f"s{i}b", f"s{i}a", 1, seed=20 + i,
                     disconnect_timeout_ms=480),
            )
        keys = {
            i: (host.attach(m[i][0]), host.attach(m[i][1]))
            for i in range(4)
        }
        sync_all([host], [s for pair in m.values() for s in pair], clock)
        free_slots_running = len(host._free_slots)
        evicted_before = host.sessions_evicted

        script = lambda h, t: (t * 7 + h * 3 + 2) % 16
        desyncs = []

        def drive_tick(t, alive):
            for i in alive:
                for h, key in enumerate(keys[i]):
                    host.submit_input(key, h, bytes([script(h, t)]))
            for _, evs in host.tick().items():
                desyncs.extend(
                    e for e in evs if type(e).__name__ == "DesyncDetected"
                )
            clock.advance(FRAME_MS)

        for t in range(20):
            drive_tick(t, alive=(0, 1, 2, 3))
        # THE STORM: all four stormed peers go dark in one tick
        net.set_blackhole({"s0a", "s0b", "s1a", "s1b"})
        t = 20
        # disconnect timeout is 480ms -> ~30 ticks of 16ms; give slack
        while t < 100 and any(
            k in host._lanes for i in (0, 1) for k in keys[i]
        ):
            drive_tick(t, alive=(2, 3))
            t += 1

        # every stormed session was reclaimed by disconnect GC...
        for i in (0, 1):
            for k in keys[i]:
                assert k not in host.keys(), f"stormed session {k} undead"
        assert host.sessions_gced >= 4
        # ...the counter accounts exactly (4 evictions, all disconnect GC)
        assert host.sessions_evicted - evicted_before == 4
        snap = GLOBAL_TELEMETRY.registry.get(
            "ggrs_host_sessions_evicted_total"
        ).snapshot()
        assert snap["values"][""] == 4
        # ...their device slots are free again
        assert len(host._free_slots) == free_slots_running + 4
        # ...and the survivors kept bitwise parity with the twin
        assert not desyncs, f"storm desynced the survivors: {desyncs[:3]}"
        s2, s3 = m[2][0], m[3][0]
        assert s2.current_frame == s3.current_frame > 20
        common = set(s2.local_checksum_history) & set(
            s3.local_checksum_history
        )
        assert common
        for f in common:
            assert (
                s2.local_checksum_history[f] == s3.local_checksum_history[f]
            )
        a = host.device.state_numpy(host._lanes[keys[2][0]].slot)
        b = host.device.state_numpy(host._lanes[keys[3][0]].slot)
        for k in a:
            np.testing.assert_array_equal(
                np.asarray(a[k]), np.asarray(b[k]), err_msg=f"state[{k}]"
            )
    finally:
        GLOBAL_TELEMETRY.enabled = False
        GLOBAL_TELEMETRY.reset()


# ----------------------------------------------------------------------
# host kill -> restore-from-checkpoint
# ----------------------------------------------------------------------


def test_host_kill_restore_from_checkpoint(tmp_path):
    """Kill a host mid-match (emergency drain→checkpoint), let its
    sessions sit dark for a few ticks, restore a fresh host from the
    checkpoint file, and keep playing: zero desyncs, every session
    resumes at its exact frame, old slots reclaimed in place."""
    clock = FakeClock()
    net = InMemoryNetwork(clock, latency_ms=10, jitter_ms=0, loss=0.0)
    game = ExGame(num_players=2, num_entities=ENTITIES)
    group = HostGroup.build(
        game, 2, clock=clock, max_prediction=8, num_players=2,
        max_sessions=4, idle_timeout_ms=0,
    )
    # a cross-host match: one peer on each host — the kill severs a live
    # protocol link, not just co-hosted twins
    p0 = peer(net, clock, "k0", "k1", 0, seed=40)
    p1 = peer(net, clock, "k1", "k0", 1, seed=41)
    g0, g1 = group.attach(p0), group.attach(p1)
    sync_all(group.hosts, [p0, p1], clock)

    desyncs = []

    def drive_tick(t):
        for g, h in ((g0, 0), (g1, 1)):
            group.submit_input(g, h, bytes([(t * 5 + h) % 16]))
        for _, evs in group.tick().items():
            desyncs.extend(
                e for e in evs if type(e).__name__ == "DesyncDetected"
            )
        clock.advance(FRAME_MS)

    for t in range(16):
        drive_tick(t)
    victim = group.host_of(g0)
    path = str(tmp_path / "kill.npz")
    frame_at_kill = p0.current_frame
    n = group.kill_host(victim, path)
    assert n == 1  # balanced attach put one peer on each host
    assert p0.host_key is None  # suspended, not pumped
    for t in range(16, 20):  # the blackout: inputs to the dead host drop
        drive_tick(t)
    assert group.inputs_dropped > 0
    resumed = group.restore_host(victim, path)
    assert resumed == n
    assert p0.host_key is not None
    # the restored lane resumes at the exact kill-time frame
    rec = group._records[g0]
    assert group.hosts[rec.host_idx]._lanes[rec.hkey].current_frame == (
        frame_at_kill
    )
    for t in range(20, 80):
        drive_tick(t)
    assert not desyncs, f"kill/restore desynced: {desyncs[:3]}"
    assert p0.current_frame > frame_at_kill + 40
    assert p1.current_frame > frame_at_kill + 40
    # real checksum comparisons backed the zero-desync claim
    assert len(p0.local_checksum_history) > 2

    # a checkpoint from a mismatched fleet is refused with the typed error
    wrong_group = HostGroup.build(
        game, 1, clock=clock, max_prediction=8, num_players=2,
        max_sessions=2, idle_timeout_ms=0,  # different capacity
    )
    wrong_group.dead.add(0)
    with pytest.raises(CheckpointIncompatible):
        wrong_group.restore_host(0, path)


# ----------------------------------------------------------------------
# DrainStalled: the typed flush-guard failure (satellite)
# ----------------------------------------------------------------------


def test_drain_stalled_is_typed_and_recorded():
    GLOBAL_TELEMETRY.enabled = True
    try:
        clock = FakeClock()
        net = InMemoryNetwork(clock)
        host = make_host(clock)
        key = host.attach(solo_session(net, "w"))
        for h in (0, 1):
            host.submit_input(key, h, b"\x01")
        # stage a row, then wedge the scheduler so it can never dispatch:
        # a row budget of zero admits nothing, however often the fence
        # retires
        budget = host.max_inflight_rows
        host.max_inflight_rows = 0
        host.tick()
        assert host.queue_depth == 1
        with pytest.raises(DrainStalled) as exc_info:
            host._flush_ready("test", max_passes=50)
        err = exc_info.value
        assert err.queue_depth == 1
        assert err.passes == 50
        assert "queue_depth=1" in str(err)
        events = [
            e for e in GLOBAL_TELEMETRY.recorder.to_json()
            if e["kind"] == "host_drain_stalled"
        ]
        assert events and events[-1]["queue_depth"] == 1
        # un-wedged, the same drain flushes clean
        host.max_inflight_rows = budget
        summary = host.drain()
        assert summary["queue_depth"] == 0
    finally:
        GLOBAL_TELEMETRY.enabled = False
        GLOBAL_TELEMETRY.reset()


# ----------------------------------------------------------------------
# the acceptance soak: >= 64 sessions, WAN profile, migrations + kill
# ----------------------------------------------------------------------


def test_chaos_soak_64_sessions_wan_profile():
    from ggrs_tpu.serve.chaos import run_chaos

    GLOBAL_TELEMETRY.enabled = True
    try:
        rep = run_chaos(
            sessions=64, ticks=60, hosts=2, entities=ENTITIES, seed=1,
            migrations=2, kill=True, kill_pause_ticks=4, flash_crowd=2,
        )
        group = rep.pop("_group")
        assert rep["sessions"] >= 64
        assert rep["desyncs"] == 0, f"chaos soak desynced: {rep}"
        # the zero-desync claim is backed by real comparisons
        assert rep["checksums_published"] > 0
        # the schedule actually ran: >= 2 live migrations, 1 kill+restore
        assert rep["migrations_done"] >= 2
        assert rep["kill"] and rep["kill"]["sessions_resumed"] == (
            rep["kill"]["sessions_suspended"]
        )
        assert group.kills == 1 and group.restores == 1
        # every migrated session resumed (its first post-handoff advance
        # was observed within the run)
        assert len(rep["migration_latency_ticks"]) == rep["migrations_done"]
        # bounded p99 queue wait under the WAN profile
        assert rep["p99_queue_wait_ticks"] <= 8, rep
        # steady-state ticks never blocked on a checksum drain
        assert rep["drain_blocked_ticks"] == 0
        # the fleet made real progress (WAN RTT throttles cross-region
        # matches below tick rate; a kill pause costs its ticks too)
        assert rep["max_frame"] >= rep["ticks"] - 8
        assert rep["min_frame"] >= rep["ticks"] // 4
        # the WAN profile actually did things
        prof = rep["profile"]
        assert prof["dropped"] > 0 and prof["reorder_spikes"] > 0
        # migration + group instruments visible through both exporters
        prom = GLOBAL_TELEMETRY.prometheus()
        snap = GLOBAL_TELEMETRY.snapshot()
        for name in ("ggrs_migrations_total", "ggrs_migration_ms"):
            assert name in prom
            assert name in snap["metrics"]
        assert snap["metrics"]["ggrs_migrations_total"]["values"][""] >= 2
    finally:
        GLOBAL_TELEMETRY.enabled = False
        GLOBAL_TELEMETRY.reset()


def test_hostgroup_backoff_jitter_schedule_pinned_by_seed():
    """The admission backoff is jittered-exponential from a SEEDED rng:
    a fixed schedule synchronizes every rejected admission in a flash
    crowd onto the same retry instants (a storm that re-collides
    forever); the seed keeps soaks reproducible. The FakeClock pins the
    exact virtual-time schedule a seed produces."""
    clock = FakeClock()
    game = ExGame(num_players=2, num_entities=ENTITIES)
    group = HostGroup.build(
        game, 1, clock=clock, max_prediction=8, num_players=2,
        max_sessions=2, idle_timeout_ms=0, max_attempts=4, backoff_ms=32,
        backoff_seed=9,
    )
    # the same seed replays the same draw sequence, each inside the
    # jittered-exponential envelope [base/2, base]
    twin = HostGroup.build(
        game, 1, clock=FakeClock(), max_prediction=8, num_players=2,
        max_sessions=2, idle_timeout_ms=0, max_attempts=4, backoff_ms=32,
        backoff_seed=9,
    )
    expected = [twin.backoff_delay_ms(a) for a in range(3)]
    for attempt, delay in enumerate(expected):
        base = 32 << attempt
        assert base // 2 <= delay <= base
    assert len(set(expected)) > 1  # jitter actually varies the draws

    net = InMemoryNetwork(clock)
    group.attach(solo_session(net, "a"))
    group.attach(solo_session(net, "b"))
    t0 = clock.now_ms()
    marks = []
    real_backoff = group._backoff

    def spying_backoff(attempt):
        real_backoff(attempt)
        marks.append(clock.now_ms() - t0)

    group._backoff = spying_backoff
    with pytest.raises(GroupSaturated):
        group.attach(solo_session(net, "overflow"))
    # the observed retry instants are exactly the seeded schedule's
    # cumulative sums — pinned, not merely bounded
    assert marks == [sum(expected[: i + 1]) for i in range(len(expected))]

    # a different seed decorrelates the schedule
    other = HostGroup.build(
        game, 1, clock=FakeClock(), max_prediction=8, num_players=2,
        max_sessions=2, idle_timeout_ms=0, max_attempts=4, backoff_ms=32,
        backoff_seed=10,
    )
    assert [other.backoff_delay_ms(a) for a in range(3)] != expected
