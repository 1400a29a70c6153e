"""Spectate driver: serve.py's fleet of hosted P2P matches, where peer 0 of
each match also hosts `spectators_per_match` spectators, each a
SpectatorSession on the same SessionHost (GGRS ex_game_p2p listing
spectators; ex_game_spectator connecting to that one host address).

Only players submit inputs. A spectator advances whenever the host peer's
confirmed input for its next frame has arrived, and catches up by
`catchup_speed` frames when more than `max_frames_behind` behind. One
session-tick is one frame advanced by any lane: a player's sync-layer
frame, a spectator's current_frame.

The check keeps every count serve.py compares for the players and adds the
spectators': each sampled spectator's slot world against the reference
after current_frame + 1 steps (a spectator's current_frame is the last
frame whose inputs it played, from genesis), every input it holds for a
played frame against the script, and every lane RUNNING and advancing.
"""

from __future__ import annotations

import random
import time

import numpy as np

from benchmark import traffic_gen
from benchmark.drivers.serve import Cell as ServeCell


def build_spectated_matches(host, net, clock, *, matches: int, players: int,
                            spectators: int, max_prediction: int,
                            input_delay: int, desync_interval: int,
                            max_frames_behind: int, catchup_speed: int,
                            seed: int):
    """traffic_gen.build_matches' matches, with `spectators` spectators of
    peer 0 per match: spectator j of match m lives at (m, "s", j). Returns
    the players' and the spectators' host keys, by match."""
    from ggrs_tpu import DesyncDetection, PlayerType, SessionBuilder

    player_keys, spectator_keys = [], []
    for m in range(matches):
        keys = []
        for k in range(players):
            b = (
                SessionBuilder(input_size=host.game.input_size)
                .with_num_players(players)
                .with_max_prediction_window(max_prediction)
                .with_input_delay(input_delay)
                .with_desync_detection_mode(
                    DesyncDetection.on(interval=desync_interval)
                )
                .with_clock(clock)
                .with_rng(random.Random(seed * 7919 + m * 131 + k))
            )
            for h in range(players):
                kind = PlayerType.local() if h == k else PlayerType.remote((m, h))
                b = b.add_player(kind, h)
            if k == 0:
                for j in range(spectators):
                    b = b.add_player(PlayerType.spectator((m, "s", j)),
                                     players + j)
            keys.append(host.attach(b.start_p2p_session(net.socket((m, k)))))
        specs = []
        for j in range(spectators):
            b = (
                SessionBuilder(input_size=host.game.input_size)
                .with_num_players(players)
                .with_max_prediction_window(max_prediction)
                .with_max_frames_behind(max_frames_behind)
                .with_catchup_speed(catchup_speed)
                .with_clock(clock)
                .with_rng(random.Random(seed * 7919 + m * 131 + players + j))
            )
            specs.append(host.attach(b.start_spectator_session(
                (m, 0), net.socket((m, "s", j)))))
        player_keys.append(keys)
        spectator_keys.append(specs)
    return player_keys, spectator_keys


class Cell(ServeCell):
    def __init__(self, config, traffic, *, seed, devices, sizes):
        super().__init__(config, traffic, seed=seed, devices=devices,
                         sizes=sizes)
        self.spectators = config["assumed"]["spectators_per_match"]
        lanes = sizes.get("sessions", traffic["sessions"])
        self.matches = lanes // (self.players + self.spectators)
        self.sessions = self.matches * (self.players + self.spectators)

    def setup(self) -> None:
        from ggrs_tpu.models.ex_game import ExGame
        from ggrs_tpu.network.sockets import InMemoryNetwork
        from ggrs_tpu.serve import SessionHost
        from ggrs_tpu.sessions.builder import SPECTATOR_BUFFER_SIZE
        from ggrs_tpu.utils.clock import FakeClock

        cfg, tr = self.cfg, self.tr
        if cfg["spectator_buffer"] != SPECTATOR_BUFFER_SIZE:
            raise ValueError(
                f"the program's spectator buffer is {SPECTATOR_BUFFER_SIZE} "
                f"frames, the configuration states {cfg['spectator_buffer']}"
            )
        t0 = time.perf_counter()
        self.clock = FakeClock()
        link = tr["link"]
        self.net = InMemoryNetwork(
            self.clock, seed=self.seed,
            profile=traffic_gen.WanLink(link["latency_ms"], link["jitter_ms"],
                                        link["loss"]),
        )
        self.host = SessionHost(
            ExGame(self.players, self.entities),
            max_prediction=cfg["max_prediction"], num_players=self.players,
            max_sessions=self.sessions, clock=self.clock, warmup=True,
        )
        self.keys, self.spec_keys = build_spectated_matches(
            self.host, self.net, self.clock, matches=self.matches,
            players=self.players, spectators=self.spectators,
            max_prediction=cfg["max_prediction"], input_delay=self.delay,
            desync_interval=cfg["assumed"]["desync_interval"],
            max_frames_behind=cfg["max_frames_behind"],
            catchup_speed=cfg["catchup_speed"], seed=self.seed,
        )
        self.sess = [[self.host.session(k) for k in keys] for keys in self.keys]
        self.specs = [[self.host.session(k) for k in keys]
                      for keys in self.spec_keys]
        self.scripts = traffic_gen.held_scripts(
            self.seed, self.matches, self.players, self.script_frames
        )
        rows = self.scripts.tolist()
        self.peers = [
            (key, k, self.sess[m][k].sync_layer, rows[m][k])
            for m, keys in enumerate(self.keys) for k, key in enumerate(keys)
        ]
        self.desyncs, self.desynced = 0, set()
        t_host = time.perf_counter()
        flat = [s for ss in self.sess + self.specs for s in ss]
        sync_ticks = traffic_gen.sync_fleet(self.host, flat, self.clock,
                                            tr["sync_ticks"])
        t_sync = time.perf_counter()
        for _ in range(tr["warm_ticks"]):
            self._tick()
        self.host.device.block_until_ready()
        self.setup_parts = {"host_and_matches_s": t_host - t0,
                            "sync_s": t_sync - t_host, "sync_ticks": sync_ticks,
                            "warm_ticks_s": time.perf_counter() - t_sync}

    def _frames_by_session(self) -> dict:
        out = super()._frames_by_session()
        out.update({(m, "s", j): s.current_frame
                    for m, ss in enumerate(self.specs)
                    for j, s in enumerate(ss)})
        return out

    # ------------------------------------------------------------------
    # correctness

    def check(self, control: bool = False):
        attempted, failed, compared = super().check(control)
        host = self.host
        # one tick with no new player input: rows still queued behind the
        # device window reach the worlds, and the window, empty after the
        # block, takes every row staged in this tick too
        host.device.block_until_ready()
        host.tick()
        _, states = host.device.stacked_canonical()

        from ggrs_tpu import SessionState
        from ggrs_tpu.sessions.builder import SPECTATOR_BUFFER_SIZE

        failed_lanes, stalled = set(), set()
        for m, keys in enumerate(self.spec_keys):
            for j, key in enumerate(keys):
                lane = host._lanes.get(key)
                if (lane is None or lane.failed or lane.last_error
                        or self.specs[m][j].current_state()
                        != SessionState.RUNNING):
                    failed_lanes.add((m, "s", j))
                if self.window_frames[(m, "s", j)] <= 0:
                    stalled.add((m, "s", j))

        # the sample serve.py drew from the seed: every spectator of each
        # sampled match, its slot world and the inputs it played
        n_check = min(self.matches, self.tr["check_matches"])
        sample = sorted(np.random.default_rng([self.seed & (2**63 - 1), 9])
                        .choice(self.matches, n_check, replace=False).tolist())
        d, L, B = self.delay, self.script_frames, SPECTATOR_BUFFER_SIZE
        want_world, got_world = {}, {}
        inputs_wrong, unchecked, bad = 0, set(), set()
        for m in sample:
            for j, key in enumerate(self.spec_keys[m]):
                s, name = self.specs[m][j], (m, "s", j)
                lane = host._lanes.get(key)
                steps = s.current_frame + 1
                if lane is not None and steps > 0:
                    want_world.setdefault(m, set()).add(steps)
                    got_world[(m, j, steps)] = {n: v[lane.slot]
                                                for n, v in states.items()}
                n_inputs = 0
                for f in range(max(0, s.current_frame - B + 1),
                               s.current_frame + 1):
                    for h, pi in enumerate(s.inputs[f % B]):
                        if pi.frame != f:
                            continue
                        want = self.scripts[m, h, (f - d) % L] if f >= d else 0
                        if pi.buf[0] != want:
                            inputs_wrong += 1
                            bad.add(name)
                        n_inputs += 1
                if lane is None or steps <= 0 or not n_inputs:
                    unchecked.add(name)
        _, ref_world = self._replay(sample, {}, want_world, 32)
        if control:
            _, ctl = self._replay(sample, {}, want_world, 16)
            got_world = {(m, j, f): ctl[(m, f)] for (m, j, f) in got_world}
        worlds_wrong = {
            (m, "s", j) for (m, j, f), w in got_world.items()
            if any(not np.array_equal(np.asarray(w[n]), v)
                   for n, v in ref_world[(m, f)].items())
        }
        bad |= failed_lanes | stalled | unchecked | worlds_wrong
        failed += int(sum(self.window_frames[s] for s in bad))
        compared.update({
            "spectator_worlds_wrong": (len(worlds_wrong), 0),
            "spectator_inputs_wrong": (inputs_wrong, 0),
            "spectators_failed": (len(failed_lanes), 0),
            "spectators_stalled": (len(stalled), 0),
            "spectators_unchecked": (len(unchecked), 0),
        })
        self.checked["spectator_worlds"] = len(got_world)
        return attempted, failed, compared
