"""The one general traffic generator: every mix under benchmark/traffic/ is
parameters for the functions here.

Copied from the program so that no later PR can move the yardstick:
- `synctest_batches` is bench.py's `input_script` fed in 60-frame batches,
  with the inputs drawn from the seed instead of a fixed formula;
- `held_scripts`, `build_matches` and `sync_fleet` are
  ggrs_tpu/serve/loadgen.py's, with the hold runs drawn in bulk by numpy;
- `WanLink` is the seeded fault profile plugged into InMemoryNetwork's
  `profile` seam (one-way latency, uniform jitter, independent loss).
The originals stay where they are (PERF.md, Open questions).
"""

from __future__ import annotations

import random

import numpy as np

FRAME_MS = 16  # virtual milliseconds per host tick (60 Hz, as loadgen)
HOLD_CYCLE = (1, 4, 2, 8, 5)  # loadgen's held values: up, left, down, right, up+left


def _rng(seed: int, *keys: int) -> np.random.Generator:
    return np.random.default_rng([seed & (2**63 - 1), *keys])


def synctest_batches(seed: int, players: int, batch: int, batches: int,
                     mod: int = 16) -> np.ndarray:
    """u8[batches, batch, players, 1]: a table of input batches; frame f
    plays table[(f // batch) % batches][f % batch]."""
    return _rng(seed, 1).integers(
        0, mod, (batches, batch, players, 1), dtype=np.uint8
    )


def held_scripts(seed: int, matches: int, players: int,
                 frames: int) -> np.ndarray:
    """u8[matches, players, frames]: human-shaped held inputs. Each peer
    holds each value of a fixed per-peer cycle for a seeded 6-18 frames."""
    out = np.empty((matches, players, frames), np.uint8)
    runs = frames // 6 + 1
    for m in range(matches):
        for k in range(players):
            cycle = HOLD_CYCLE[(m + k) % 3:][:3]
            lengths = _rng(seed, 2, m, k).integers(6, 19, runs)
            values = np.resize(np.asarray(cycle, np.uint8), runs)
            out[m, k] = np.repeat(values, lengths)[:frames]
    return out


class WanLink:
    """FaultProfile: each datagram is lost with probability `loss`, else
    delivered once after latency_ms + uniform[-jitter_ms, jitter_ms]. Draws
    only from the network's seeded rng."""

    def __init__(self, latency_ms: int, jitter_ms: int, loss: float):
        self.latency_ms = latency_ms
        self.jitter_ms = jitter_ms
        self.loss = loss

    def link(self, src, dst, now_ms: int, rng: random.Random):
        if rng.random() < self.loss:
            return []
        return [self.latency_ms + rng.randint(-self.jitter_ms, self.jitter_ms)]


def build_matches(host, net, clock, *, matches: int, players: int,
                  max_prediction: int, input_delay: int, desync_interval: int,
                  seed: int):
    """`matches` full P2P matches of `players` peers, every peer a session
    attached to `host`; peer k of match m lives at address (m, k). Returns
    the host keys by match."""
    from ggrs_tpu import DesyncDetection, PlayerType, SessionBuilder

    out = []
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
            keys.append(host.attach(b.start_p2p_session(net.socket((m, k)))))
        out.append(keys)
    return out


def sync_fleet(host, sessions, clock, max_ticks: int) -> int:
    """Pump the host until every session is RUNNING; returns the ticks."""
    from ggrs_tpu import SessionState

    for t in range(1, max_ticks + 1):
        host.tick()
        clock.advance(FRAME_MS)
        if all(s.current_state() == SessionState.RUNNING for s in sessions):
            return t
    raise RuntimeError(f"fleet failed to synchronize in {max_ticks} ticks")
