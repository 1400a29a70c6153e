"""Drive the rollback path once on a directly attached TPU and check it.

    python chip_smoke.py             # phases A, B, C on one chip
    python chip_smoke.py --chips 4   # the two sharded checks on four chips

A  fused SyncTest: ExGame(2, 4096) on the whole-batch pallas kernel vs the
   numpy oracle, then ExGame(2, 1048576) on the entity-tiled kernel vs the
   XLA scan, carry bit for bit.
B  P2P request path: two loopback-UDP P2P sessions, each fulfilled by a
   TpuRollbackBackend with an 8-wide speculation beam; rollbacks must
   happen and the checksum histories must agree.
C  serving: a resident SessionHost of 256 sessions under the seeded lossy
   loadgen, then one hosted lane vs a solo backend, bit for bit.
--chips 4: BASELINE.json configs[4] (ExGame(2, 13056), 16-frame rollback,
   entity-sharded tiled kernel) vs the same session on one chip, and a
   session-mesh SessionHost vs an unsharded host on the same traffic.

Every phase runs on the TPU or not at all: main() refuses any other
platform, and no phase catches its own failure. Each phase prints one
JSON line (kernels, compile seconds, cache hits, wall seconds, verdicts);
the last stdout line is the device record the driver reads.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import warnings

import numpy as np

PLAYERS = 2


class CompileMeter:
    """Sums JAX's backend-compile durations and persistent-cache hits and
    misses, so each phase can report what it compiled and what the cache
    served."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self):
        return self.seconds, self.hits, self.misses


def run_phase(name, fn, meter, platform, **kw):
    """Run one phase on `platform` and print its JSON line; a failure
    propagates (the script exits non-zero)."""
    import jax

    found = jax.devices()[0].platform
    assert found == platform, f"phase {name}: platform {found!r}, need {platform!r}"
    s0, h0, m0 = meter.snapshot()
    t0 = time.perf_counter()
    result = fn(**kw)
    s1, h1, m1 = meter.snapshot()
    line = {
        "phase": name,
        "wall_s": time.perf_counter() - t0,
        "compile_s": s1 - s0,
        "cache_hits": h1 - h0,
        "cache_misses": m1 - m0,
        **result,
    }
    print(json.dumps(line), flush=True)
    return line


def _tree_equal(a, b):
    import jax

    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb)
    )


def _devices_of(tree):
    import jax

    return set().union(*(x.sharding.device_set for x in jax.tree.leaves(tree)))


# ----------------------------------------------------------------------
# A. fused SyncTest
# ----------------------------------------------------------------------


def _run_synctest(entities, frames, check_distance, backend, mesh=None):
    from bench import input_script
    from ggrs_tpu.models.ex_game import ExGame
    from ggrs_tpu.tpu import TpuSyncTestSession

    sess = TpuSyncTestSession(
        ExGame(PLAYERS, entities),
        num_players=PLAYERS,
        check_distance=check_distance,
        backend=backend,
        mesh=mesh,
    )
    for start in range(0, frames, 60):
        sess.advance_frames(input_script(min(60, frames - start), start))
    sess.check()
    return sess


def phase_synctest_oracle(entities, frames, expect, backend="auto"):
    """The fused SyncTest session vs the numpy oracle, bit for bit."""
    from bench import input_script
    from ggrs_tpu.models.ex_game import ExGame, init_oracle, step_oracle

    sess = _run_synctest(entities, frames, 8, backend)
    assert sess.backend == expect, f"resolved {sess.backend!r}, want {expect!r}"
    dev = sess.state_numpy()
    state = init_oracle(PLAYERS, entities)
    statuses = np.zeros(PLAYERS, dtype=np.int32)
    script = input_script(frames)
    for f in range(frames):
        state = step_oracle(state, script[f].reshape(-1), statuses, PLAYERS)
    for k in list(ExGame.checksum_keys) + ["frame"]:
        assert np.array_equal(np.asarray(dev[k]), state[k]), f"state[{k}]"
    return {"entities": entities, "frames": frames, "kernel": sess.backend,
            "parity_vs_oracle": True}


def phase_synctest_pair(entities, frames, check_distance, arm, ref,
                        expect, devices=1):
    """Two fused SyncTest sessions on one script; full carries bit for
    bit. `arm`/`ref`: (backend, mesh) pairs; `devices`: how many devices
    the arm's state must span."""
    sess = _run_synctest(entities, frames, check_distance, *arm)
    assert sess.backend == expect, f"resolved {sess.backend!r}, want {expect!r}"
    placed = len(_devices_of(sess.carry["state"]))
    assert placed == devices, f"state spans {placed} devices, want {devices}"
    other = _run_synctest(entities, frames, check_distance, *ref)
    assert _tree_equal(sess.carry, other.carry), "carries differ"
    return {"entities": entities, "frames": frames, "kernel": sess.backend,
            "reference": other.backend, "state_devices": placed,
            "carry_bitwise": True}


# ----------------------------------------------------------------------
# B. P2P request path over loopback UDP
# ----------------------------------------------------------------------


def phase_p2p(entities, frames, expect_spec, beam_width=8,
              spec_backend="auto", tick_backend="auto"):
    """Two loopback-UDP P2P sessions with desync detection, each fulfilled
    by its own TpuRollbackBackend. Warnings are errors here, so a
    speculation demoted to XLA fails the phase."""
    from ggrs_tpu import (
        DesyncDetected,
        DesyncDetection,
        LoadGameState,
        PlayerType,
        SessionBuilder,
        SessionState,
    )
    from ggrs_tpu.models.ex_game import ExGame
    from ggrs_tpu.network.sockets import UdpNonBlockingSocket
    from ggrs_tpu.tpu import TpuRollbackBackend

    socks = [UdpNonBlockingSocket(0), UdpNonBlockingSocket(0)]
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            sessions, backends = [], []
            for h in range(2):
                other = ("127.0.0.1", socks[1 - h].local_port)
                sessions.append(
                    SessionBuilder(input_size=1)
                    .with_num_players(PLAYERS)
                    .with_max_prediction_window(8)
                    .with_desync_detection_mode(DesyncDetection.on(interval=10))
                    .add_player(PlayerType.local(), h)
                    .add_player(PlayerType.remote(other), 1 - h)
                    .start_p2p_session(socks[h])
                )
                backends.append(TpuRollbackBackend(
                    ExGame(PLAYERS, entities), max_prediction=8,
                    num_players=PLAYERS, beam_width=beam_width,
                    spec_backend=spec_backend, tick_backend=tick_backend,
                ))
            for _ in range(2000):
                for s in sessions:
                    s.poll_remote_clients()
                    s.events()
                if all(s.current_state() == SessionState.RUNNING for s in sessions):
                    break
                time.sleep(0.002)
            assert all(s.current_state() == SessionState.RUNNING for s in sessions)

            desyncs, loads = [], 0
            for f in range(frames):
                for h, (s, b) in enumerate(zip(sessions, backends)):
                    s.poll_remote_clients()
                    desyncs += [e for e in s.events() if isinstance(e, DesyncDetected)]
                    s.add_local_input(h, bytes([(f * (3 + 4 * h) + h) % 13]))
                    reqs = s.advance_frame()
                    loads += sum(isinstance(r, LoadGameState) for r in reqs)
                    b.handle_requests(reqs)
            for _ in range(50):  # let the last inputs and reports land
                for s in sessions:
                    s.poll_remote_clients()
                    desyncs += [e for e in s.events() if isinstance(e, DesyncDetected)]
                time.sleep(0.002)
            for b in backends:
                b.block_until_ready()
    finally:
        for sock in socks:
            sock.close()

    assert not desyncs, f"desyncs: {desyncs[:3]}"
    assert loads > 0, "no rollback happened"
    ha, hb = (s.local_checksum_history for s in sessions)
    common = sorted(set(ha) & set(hb))
    assert common and all(ha[f] == hb[f] for f in common), "histories differ"
    cores = [b.core for b in backends]
    assert all(c.spec_backend == expect_spec for c in cores), (
        f"speculation ran on {[c.spec_backend for c in cores]}, "
        f"want {expect_spec!r}"
    )
    assert all(c._beam_rollouts for c in cores), "no pallas rollout was built"
    return {"entities": entities, "frames": frames,
            "tick_backend": cores[0].tick_backend,
            "spec_backend": cores[0].spec_backend,
            "rollbacks": loads, "beam_hits": sum(b.beam_hits for b in backends),
            "checksum_frames_compared": len(common), "desyncs": 0}


# ----------------------------------------------------------------------
# C. serving
# ----------------------------------------------------------------------


def _serve(entities, sessions, ticks, mesh=None):
    from ggrs_tpu.models.ex_game import ExGame
    from ggrs_tpu.serve import SessionHost
    from ggrs_tpu.serve.loadgen import run_loadgen

    host = SessionHost(
        ExGame(PLAYERS, entities), max_sessions=sessions, resident=True,
        warmup=True, idle_timeout_ms=0, mesh=mesh,
    )
    rep = run_loadgen(sessions=sessions, entities=entities,
                      max_players=PLAYERS, ticks=ticks, host=host)
    host.device.block_until_ready()
    assert rep["desyncs"] == 0, f"{rep['desyncs']} desyncs"
    assert rep["checksums_published"] > 0, "no checksum reports"
    assert host.device.mailbox.overflows == 0, "mailbox overflowed"
    return host, rep


def _hosted_lane_vs_solo(entities, ticks):
    """tests/test_serve_host.py::test_hosted_checksums_match_solo_backend
    on a resident host: one lane beside a decoy, every saved frame's
    checksum and the final world equal to a solo backend's."""
    from ggrs_tpu import PlayerType, SaveGameState, SessionBuilder
    from ggrs_tpu.models.ex_game import ExGame
    from ggrs_tpu.network.sockets import InMemoryNetwork
    from ggrs_tpu.serve import SessionHost
    from ggrs_tpu.tpu import TpuRollbackBackend
    from ggrs_tpu.utils.clock import FakeClock

    clock = FakeClock()
    net = InMemoryNetwork(clock)

    def solo_session(addr):
        b = SessionBuilder(input_size=1).with_num_players(PLAYERS)
        for h in range(PLAYERS):
            b = b.add_player(PlayerType.local(), h)
        return b.start_p2p_session(net.socket(addr))

    def getters(reqs):
        return [(r.frame, r.cell.checksum_getter())
                for r in reqs if isinstance(r, SaveGameState)]

    def script(t, h):
        return bytes([(t * 3 + h) % 16])

    ref_sess = solo_session("ref")
    ref = TpuRollbackBackend(ExGame(PLAYERS, entities), max_prediction=8,
                             num_players=PLAYERS)
    ref_getters = []
    for t in range(ticks):
        for h in range(PLAYERS):
            ref_sess.add_local_input(h, script(t, h))
        reqs = ref_sess.advance_frame()
        ref.handle_requests(reqs)
        ref_getters += getters(reqs)

    host = SessionHost(ExGame(PLAYERS, entities), max_sessions=2,
                       clock=clock, resident=True)
    sess, decoy = solo_session("a"), solo_session("b")
    key, dkey = host.attach(sess), host.attach(decoy)
    tapped, got = [], []
    advance = sess.advance_frame

    def tapped_advance():
        reqs = advance()
        tapped.append(reqs)
        return reqs

    sess.advance_frame = tapped_advance
    for t in range(ticks):
        for h in range(PLAYERS):
            host.submit_input(key, h, script(t, h))
            host.submit_input(dkey, h, bytes([(t * 11 + 2 + h) % 16]))
        host.tick()
        clock.advance(16)
        for reqs in tapped:  # per tick: ring cells are rebound later
            got += getters(reqs)
        tapped.clear()
    assert [(f, g()) for f, g in ref_getters] == [(f, g()) for f, g in got]
    solo, lane = ref.state_numpy(), host.device.state_numpy(host._lanes[key].slot)
    assert all(np.array_equal(np.asarray(solo[k]), np.asarray(lane[k]))
               for k in solo), "hosted lane world differs from the solo backend"
    return len(got)


def phase_serve(entities, sessions, ticks, parity_ticks):
    host, rep = _serve(entities, sessions, ticks)
    dev = host.device
    frames = _hosted_lane_vs_solo(entities, parity_ticks)
    return {"entities": entities, "sessions": rep["sessions"],
            "matches": rep["matches"], "ticks": ticks, "desyncs": 0,
            "checksums_published": rep["checksums_published"],
            "min_frame": rep["min_frame"],
            "driver_dispatches": dev.driver_dispatches,
            "vticks_executed": dev.vticks_executed,
            "mailbox_overflows": dev.mailbox.overflows,
            "lane_vs_solo_frames": frames, "lane_vs_solo_bitwise": True}


def phase_serve_sharded(entities, sessions, ticks, devices):
    """A session-mesh host vs an unsharded host on the same loadgen
    traffic: checksum histories, canonical rings and worlds bit for bit."""
    from ggrs_tpu.parallel.mesh import make_session_mesh

    host_s, rep_s = _serve(entities, sessions, ticks, make_session_mesh(devices))
    placed = len(_devices_of(host_s.device.states))
    assert placed == devices, f"states span {placed} devices, want {devices}"
    host_p, _ = _serve(entities, sessions, ticks)
    for ka, kb in zip(host_s.keys(), host_p.keys()):
        sa, sb = host_s.session(ka), host_p.session(kb)
        assert sa.current_frame == sb.current_frame > 0
        assert sa.local_checksum_history == sb.local_checksum_history
    rs, ss = host_s.device.stacked_canonical()
    rp, sp = host_p.device.stacked_canonical()
    assert _tree_equal(rs, rp), "rings differ"
    assert _tree_equal(ss, sp), "worlds differ"
    return {"entities": entities, "sessions": rep_s["sessions"],
            "ticks": ticks, "state_devices": placed,
            "checksums_published": rep_s["checksums_published"],
            "sharded_vs_unsharded_bitwise": True}


# ----------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded checks, on four chips")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU; JAX found platform {dev.platform!r}",
              file=sys.stderr)
        return 1
    count = len(jax.devices())
    if count < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {count}", file=sys.stderr)
        return 1

    from ggrs_tpu.utils.compile_cache import enable_compile_cache

    print(json.dumps({"compile_cache": enable_compile_cache()}), flush=True)
    meter = CompileMeter()
    phase = lambda name, fn, **kw: run_phase(name, fn, meter, "tpu", **kw)

    if args.chips == 4:
        from ggrs_tpu.parallel.mesh import make_mesh

        phase("configs4_sharded_synctest", phase_synctest_pair,
              entities=13056, frames=120, check_distance=16,
              arm=("pallas-tiled", make_mesh(4)), ref=("pallas-tiled", None),
              expect="pallas-tiled", devices=4)
        phase("serve_sharded", phase_serve_sharded,
              entities=4096, sessions=256, ticks=240, devices=4)
    else:
        phase("A_synctest_4096", phase_synctest_oracle,
              entities=4096, frames=300, expect="pallas")
        phase("A_synctest_1m", phase_synctest_pair,
              entities=1 << 20, frames=120, check_distance=8,
              arm=("auto", None), ref=("xla", None), expect="pallas-tiled")
        phase("B_p2p_udp", phase_p2p,
              entities=4096, frames=300, expect_spec="pallas")
        phase("C_serve", phase_serve,
              entities=4096, sessions=256, ticks=240, parity_ticks=24)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count,
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
