"""Serve driver: a SessionHost fleet of full P2P matches, every peer hosted,
closed loop in virtual time (16 ms per host tick, as fast as the host
pumps). The timed entries are `SessionHost.submit_input` and
`SessionHost.tick`.

Each peer submits, for its session's current frame f, script[f]; with input
delay d frame f plays script[f - d] (blank before d). So the inputs of every
frame are fixed by the seed whatever the timing, and the plain reference
(benchmark/reference/exgame.py, numpy on the host) replays them.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np

from benchmark import traffic_gen
from benchmark.reference import exgame as ref

BYTES = [bytes([v]) for v in range(256)]


def _no_span(name):
    return contextlib.nullcontext()


class Cell:
    def __init__(self, config, traffic, *, seed, devices, sizes):
        self.cfg, self.tr, self.seed = config, traffic, seed
        self.devices = devices
        self.players = config["num_players"]
        self.entities = sizes.get("entities", config["num_entities"])
        sessions = sizes.get("sessions", traffic["sessions"])
        self.matches = sessions // self.players
        self.sessions = self.matches * self.players
        self.delay = config["assumed"]["input_delay"]
        self.script_frames = traffic["script_frames"]

    # ------------------------------------------------------------------

    def setup(self) -> None:
        from ggrs_tpu.models.ex_game import ExGame
        from ggrs_tpu.network.sockets import InMemoryNetwork
        from ggrs_tpu.serve import SessionHost
        from ggrs_tpu.utils.clock import FakeClock

        cfg, tr = self.cfg, self.tr
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
        self.keys = traffic_gen.build_matches(
            self.host, self.net, self.clock, matches=self.matches,
            players=self.players, max_prediction=cfg["max_prediction"],
            input_delay=self.delay,
            desync_interval=cfg["assumed"]["desync_interval"], seed=self.seed,
        )
        self.sess = [[self.host.session(k) for k in keys] for keys in self.keys]
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
        flat = [s for ss in self.sess for s in ss]
        sync_ticks = traffic_gen.sync_fleet(self.host, flat, self.clock,
                                            tr["sync_ticks"])
        t_sync = time.perf_counter()
        for _ in range(tr["warm_ticks"]):
            self._tick()
        self.host.device.block_until_ready()
        self.setup_parts = {"host_and_matches_s": t_host - t0,
                            "sync_s": t_sync - t_host, "sync_ticks": sync_ticks,
                            "warm_ticks_s": time.perf_counter() - t_sync}

    def _tick(self, tracing=None) -> None:
        host, L = self.host, self.script_frames
        submit = host.submit_input
        span = tracing.span if tracing is not None else _no_span
        with span("bench/submit_input"):
            for key, k, layer, row in self.peers:
                submit(key, k, BYTES[row[layer.current_frame % L]])
        with span("bench/host_tick"):
            events = host.tick()
        for key, evs in events.items():
            for e in evs:
                if type(e).__name__ == "DesyncDetected":
                    self.desyncs += 1
                    self.desynced.add(key)
        self.clock.advance(traffic_gen.FRAME_MS)

    def _frames_by_session(self) -> dict:
        return {(m, k): s.sync_layer.current_frame
                for m, ss in enumerate(self.sess) for k, s in enumerate(ss)}

    def _dispatches(self) -> int:
        dev = self.host.device
        return dev.megabatches + dev.driver_dispatches

    def window(self, seconds: float, tracing) -> dict:
        dev = self.host.device
        start = self._frames_by_session()
        d0 = self._dispatches()
        tick_ms = []
        clock = time.perf_counter
        t0 = clock()
        end = t0 + seconds
        while clock() < end:
            tracing.tick(clock() - t0, host_ticks=1)
            a = clock()
            self._tick(tracing)
            tick_ms.append((clock() - a) * 1e3)
        tracing.stop(sync=dev.block_until_ready)
        dev.block_until_ready()
        window_s = clock() - t0
        end_frames = self._frames_by_session()
        self.window_frames = {s: end_frames[s] - start[s] for s in start}
        return {
            "window_s": window_s,
            "host_ticks": len(tick_ms),
            "tick_ms": tick_ms,
            "session_ticks": sum(self.window_frames.values()),
            "dispatches": self._dispatches() - d0,
        }

    # ------------------------------------------------------------------
    # correctness

    def _inputs(self, m: int, frames: int) -> np.ndarray:
        """u8[frames, P]: what every frame of match m plays."""
        out = np.zeros((frames, self.players), np.uint8)
        d, L = self.delay, self.script_frames
        f = np.arange(d, frames)
        out[d:] = self.scripts[m][:, (f - d) % L].T
        return out

    def check(self, control: bool = False):
        """Compare what the timed path produced with the plain reference.
        Returns (attempted, failed, {name: (value, limit)})."""
        tr, host = self.tr, self.host
        for _ in range(tr["cooldown_ticks"]):
            self._tick()
        host.device.block_until_ready()
        rings, _ = host.device.stacked_canonical()

        # the protocol plane and the host: every session alive and running,
        # no desync reported, every pair of peers agreeing on every frame
        # both checksummed
        from ggrs_tpu import SessionState

        live = set(host.keys())
        bad_sessions = set()
        for m, keys in enumerate(self.keys):
            for k, key in enumerate(keys):
                lane = host._lanes.get(key) if key in live else None
                if (lane is None or lane.failed or key in self.desynced
                        or self.sess[m][k].current_state() != SessionState.RUNNING):
                    bad_sessions.add((m, k))
        disagreements = 0
        for m, ss in enumerate(self.sess):
            hists = [s.local_checksum_history for s in ss]
            common = set(hists[0]).intersection(*hists[1:])
            split = sum(len({h[f] for h in hists}) > 1 for f in common)
            if split:
                disagreements += split
                bad_sessions.update((m, k) for k in range(len(ss)))

        # a sample of matches, drawn from the seed, against the reference:
        # checksum histories, ring worlds of confirmed frames, confirmed
        # inputs
        n_check = min(self.matches, tr["check_matches"])
        sample = sorted(np.random.default_rng([self.seed & (2**63 - 1), 9])
                        .choice(self.matches, n_check, replace=False).tolist())
        want_sum, want_world, inputs_wrong, unchecked = {}, {}, 0, 0
        got_sum, got_world = {}, {}
        for m in sample:
            for k, s in enumerate(self.sess[m]):
                layer = s.sync_layer
                confirmed = layer.last_confirmed_frame
                for f, c in s.local_checksum_history.items():
                    want_sum.setdefault(m, set()).add(f)
                    got_sum[(m, k, f)] = c
                slot = host._lanes[self.keys[m][k]].slot
                ring = {name: leaf[slot] for name, leaf in rings.items()}
                n_ring = 0
                for j in range(host.device.core.ring_len):
                    f = int(ring["frame"][j])
                    if 0 < f <= confirmed:
                        want_world.setdefault(m, set()).add(f)
                        got_world[(m, k, f)] = {n: ring[n][j] for n in ring}
                        n_ring += 1
                n_inputs = 0
                for h, q in enumerate(layer.input_queues):
                    for pi in q.inputs:
                        if 0 <= pi.frame <= confirmed:
                            want = (self.scripts[m, h, (pi.frame - self.delay)
                                                 % self.script_frames]
                                    if pi.frame >= self.delay else 0)
                            if pi.buf[0] != want:
                                inputs_wrong += 1
                                bad_sessions.add((m, k))
                            n_inputs += 1
                if not (n_ring and n_inputs and s.local_checksum_history):
                    unchecked += 1
                    bad_sessions.add((m, k))
        ref_sum, ref_world = self._replay(sample, want_sum, want_world, 32)
        if control:
            got_sum, got_world = self._control(sample, want_sum, want_world,
                                               got_sum, got_world)
        sums_wrong = [(m, k) for (m, k, f), c in got_sum.items()
                      if c != ref_sum[(m, f)]]
        worlds_wrong = [
            (m, k) for (m, k, f), w in got_world.items()
            if any(not np.array_equal(np.asarray(w[n]), v)
                   for n, v in ref_world[(m, f)].items())
        ]
        bad_sessions.update(sums_wrong, worlds_wrong)
        attempted = int(sum(self.window_frames.values()))
        failed = int(sum(self.window_frames[s] for s in bad_sessions))
        compared = {
            "desyncs": (self.desyncs, 0),
            "sessions_failed": (len(bad_sessions), 0),
            "peer_disagreements": (disagreements, 0),
            "checksums_wrong": (len(sums_wrong), 0),
            "ring_worlds_wrong": (len(worlds_wrong), 0),
            "inputs_wrong": (inputs_wrong, 0),
            "sessions_unchecked": (unchecked, 0),
        }
        self.checked = {"checksums": len(got_sum), "ring_worlds": len(got_world),
                        "matches": len(sample)}
        return attempted, failed, compared

    def _replay(self, sample, want_sum, want_world, store_bits):
        """Step the sampled matches' worlds together (numpy, host) from
        frame 0, keeping the checksums and worlds asked for."""
        last = max([max(v) for v in list(want_sum.values())
                    + list(want_world.values())], default=0)
        inputs = np.stack([self._inputs(m, last) for m in sample])
        world = ref.init_world(self.entities)
        world = {k: (np.broadcast_to(v, (len(sample),) + v.shape).copy()
                     if k != "frame" else v) for k, v in world.items()}
        statuses = np.zeros((len(sample), self.players), np.int32)
        sums, worlds = {}, {}
        with np.errstate(over="ignore"):
            for f in range(last + 1):
                for i, m in enumerate(sample):
                    if f in want_sum.get(m, ()):
                        hi, lo = ref.checksum(
                            {k: (v[i] if k != "frame" else v)
                             for k, v in world.items()}, np)
                        sums[(m, f)] = ref.combine(hi, lo)
                    if f in want_world.get(m, ()):
                        worlds[(m, f)] = ref.to_program_layout(
                            {k: (v[i] if k != "frame" else v)
                             for k, v in world.items()})
                if f < last:
                    world = ref.step(world, inputs[:, f], statuses, np,
                                     store_bits)
        return sums, worlds

    def _control(self, sample, want_sum, want_world, got_sum, got_world):
        """The control in the program's place: the reference at int16."""
        sums, worlds = self._replay(sample, want_sum, want_world, 16)
        return ({(m, k, f): sums[(m, f)] for (m, k, f) in got_sum},
                {(m, k, f): worlds[(m, f)] for (m, k, f) in got_world})
