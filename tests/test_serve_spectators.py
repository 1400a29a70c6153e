"""Spectators on the served path: two-player P2P matches whose peer 0
broadcasts its confirmed inputs to hosted SpectatorSessions, every lane on
one SessionHost over a seeded lossy InMemoryNetwork and a FakeClock.

Each spectator's slot world must equal the numpy oracle
(`models.ex_game.step_oracle`) after current_frame + 1 steps from genesis
(current_frame is the last frame whose inputs it played), and every input
it holds for a played frame must be the one its player sent. How many
ticks a lane advanced or waited depends on the device's completion time,
so nothing here asserts on it."""

import random

import numpy as np
import pytest

from ggrs_tpu import DesyncDetection, PlayerType, SessionBuilder, SessionState
from ggrs_tpu.models.ex_game import ExGame, init_oracle, step_oracle
from ggrs_tpu.network.sockets import InMemoryNetwork
from ggrs_tpu.obs import GLOBAL_TELEMETRY
from ggrs_tpu.serve import SessionHost
from ggrs_tpu.serve.loadgen import build_matches, sync_fleet
from ggrs_tpu.sessions.builder import SPECTATOR_BUFFER_SIZE
from ggrs_tpu.utils.clock import FakeClock
from ggrs_tpu.utils.tracing import GLOBAL_TRACER

PLAYERS, SPECTATORS, MATCHES, ENTITIES, DELAY = 2, 3, 2, 256, 2
HOLD = (1, 4, 2, 8, 5)
SEED = 26


def script(m, h, f):
    """The held input player h of match m sends for frame f (before
    input delay): each value held for 9 frames."""
    return HOLD[(f // 9 + m + 2 * h) % len(HOLD)]


def played(m, h, f):
    """What frame f plays for player h: the input sent DELAY frames
    earlier, blank before that."""
    return script(m, h, f - DELAY) if f >= DELAY else 0


class Links:
    """FaultProfile: 2 % independent loss on every link, then one-way
    latency + uniform jitter, 20 +- 5 ms between players and the
    spectator profile on any link that touches a spectator."""

    def __init__(self, spectator_ms, spectator_jitter_ms):
        self.spectator = (spectator_ms, spectator_jitter_ms)

    def link(self, src, dst, now_ms, rng):
        if rng.random() < 0.02:
            return []
        spec = len(src) == 3 or len(dst) == 3
        ms, jitter = self.spectator if spec else (20, 5)
        return [ms + rng.randint(-jitter, jitter)]


def spectated_fleet(links, *, max_frames_behind, catchup_speed):
    clock = FakeClock()
    net = InMemoryNetwork(clock, seed=SEED, profile=links)
    host = SessionHost(ExGame(num_players=PLAYERS, num_entities=ENTITIES),
                       max_prediction=8, num_players=PLAYERS,
                       max_sessions=MATCHES * (PLAYERS + SPECTATORS),
                       clock=clock)
    players, spectators = [], []
    for m in range(MATCHES):
        for k in range(PLAYERS):
            b = (SessionBuilder(input_size=1).with_num_players(PLAYERS)
                 .with_input_delay(DELAY)
                 .with_desync_detection_mode(DesyncDetection.on(interval=10))
                 .with_clock(clock)
                 .with_rng(random.Random(SEED * 1000 + m * 10 + k)))
            for h in range(PLAYERS):
                b = b.add_player(PlayerType.local() if h == k
                                 else PlayerType.remote((m, h)), h)
            if k == 0:
                for j in range(SPECTATORS):
                    b = b.add_player(PlayerType.spectator((m, "s", j)),
                                     PLAYERS + j)
            s = b.start_p2p_session(net.socket((m, k)))
            players.append((m, k, host.attach(s), s))
        for j in range(SPECTATORS):
            s = (SessionBuilder(input_size=1).with_num_players(PLAYERS)
                 .with_max_frames_behind(max_frames_behind)
                 .with_catchup_speed(catchup_speed)
                 .with_clock(clock)
                 .with_rng(random.Random(SEED * 1000 + m * 10 + PLAYERS + j))
                 .start_spectator_session((m, 0), net.socket((m, "s", j))))
            spectators.append((m, host.attach(s), s))
    return host, clock, players, spectators


def drive(host, clock, players, spectators, ticks):
    """Sync every lane, then `ticks` host ticks in which each player sends
    its script for its current frame; then one tick with no input, after
    the device drained, so rows queued behind the window reach the
    worlds."""
    lanes = [s for *_, s in players] + [s for *_, s in spectators]
    for _ in range(400):
        if all(s.current_state() == SessionState.RUNNING for s in lanes):
            break
        host.tick()
        clock.advance(16)
    else:
        raise AssertionError("fleet did not synchronize")
    for _ in range(ticks):
        for m, k, key, s in players:
            host.submit_input(key, k, bytes([script(m, k, s.current_frame)]))
        host.tick()
        clock.advance(16)
    host.device.block_until_ready()
    host.tick()
    host.device.block_until_ready()


def assert_spectators_match_oracle(host, spectators):
    worlds = {}
    for m, key, s in spectators:
        lane = host._lanes[key]
        assert not lane.failed and lane.last_error is None
        assert s.current_state() == SessionState.RUNNING
        assert s.current_frame > 30, "spectator barely advanced"
        # every input it holds for a played frame is the player's
        held = 0
        for f in range(max(0, s.current_frame - SPECTATOR_BUFFER_SIZE + 1),
                       s.current_frame + 1):
            for h, pi in enumerate(s.inputs[f % SPECTATOR_BUFFER_SIZE]):
                if pi.frame == f:
                    assert pi.buf == bytes([played(m, h, f)]), (m, h, f)
                    held += 1
        assert held > 0
        worlds[(m, key)] = (s.current_frame + 1, host.device.state_numpy(lane.slot))
    for m in range(MATCHES):
        want = {steps: None for (mm, _), (steps, _) in worlds.items() if mm == m}
        state = init_oracle(PLAYERS, ENTITIES)
        statuses = np.zeros(PLAYERS, np.int32)
        for f in range(max(want)):
            if f in want:
                want[f] = state
            inputs = np.array([played(m, h, f) for h in range(PLAYERS)], np.uint8)
            state = step_oracle(state, inputs, statuses, PLAYERS)
        want[max(want)] = state
        for (mm, key), (steps, got) in worlds.items():
            if mm != m:
                continue
            for name, v in want[steps].items():
                np.testing.assert_array_equal(np.asarray(got[name]), v,
                                              err_msg=f"{key} {name}")


@pytest.fixture
def telemetry():
    """Telemetry on for one test, from a zeroed registry."""
    GLOBAL_TELEMETRY.reset()
    GLOBAL_TELEMETRY.enabled = True
    try:
        yield GLOBAL_TELEMETRY.registry
    finally:
        GLOBAL_TELEMETRY.enabled = False
        GLOBAL_TELEMETRY.reset()


def _counter(reg, name, label=""):
    return reg.snapshot()[name]["values"].get(label, 0)


@pytest.mark.parametrize("spectator_link,max_frames_behind,catchup_speed", [
    ((20, 5), 10, 1),  # the builder's defaults (builder.rs:23-25)
    ((100, 80), 4, 2),  # bursty arrivals: the catch-up rule fires
], ids=["defaults", "catchup"])
def test_hosted_spectators_match_the_oracle(telemetry, spectator_link,
                                            max_frames_behind, catchup_speed):
    """The catch-up case's spectator link jitters by 80 ms, five host
    ticks, so the received frontier jumps past max_frames_behind and the
    host plays multi-row spectator advances. Five runs each counted 390
    catch-up frames of 1,412 (ggrs_spectator_frames_total{speed=
    "catchup"}), the lowest count being 390."""
    host, clock, players, spectators = spectated_fleet(
        Links(*spectator_link), max_frames_behind=max_frames_behind,
        catchup_speed=catchup_speed)
    drive(host, clock, players, spectators, 240)
    assert host.desyncs_observed == 0
    assert_spectators_match_oracle(host, spectators)
    frames = (_counter(telemetry, "ggrs_spectator_frames_total", "normal")
              + _counter(telemetry, "ggrs_spectator_frames_total", "catchup"))
    assert frames > 0
    assert _counter(telemetry, "ggrs_spectator_sends_total") > 0
    behind = telemetry.snapshot()["ggrs_spectator_frames_behind"]["values"]
    assert behind[""]["count"] > 0
    if catchup_speed > 1:
        assert _counter(telemetry, "ggrs_spectator_frames_total", "catchup") > 0


def test_fleet_without_spectators_records_no_spectator_span(telemetry):
    """The new spans and counters stay silent where no session has a
    spectator: the fan-out span sits behind num_spectators()."""
    clock = FakeClock()
    net = InMemoryNetwork(clock, latency_ms=20, jitter_ms=5, seed=SEED)
    host = SessionHost(ExGame(num_players=2, num_entities=64),
                       max_prediction=8, num_players=2, max_sessions=4,
                       clock=clock)
    matches = build_matches(host, net, clock, sessions=4, players_cycle=(2,),
                            seed=SEED)
    sync_fleet(host, matches, clock)
    GLOBAL_TRACER.enabled = True
    try:
        for t in range(30):
            for keys in matches:
                for key in keys:
                    for h in host._lanes[key].local_handles:
                        host.submit_input(key, h, bytes([(t // 7 + h) % 16]))
            host.tick()
            clock.advance(16)
    finally:
        GLOBAL_TRACER.enabled = False
    spans = telemetry.snapshot()["ggrs_span_ms"]["values"]
    assert spans["session/advance"]["count"] > 0
    assert "spectator/advance" not in spans
    assert "session/spectator_send" not in spans
    assert _counter(telemetry, "ggrs_spectator_sends_total") == 0
