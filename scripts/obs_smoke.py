#!/usr/bin/env python
"""Observability smoke gate (scripts/check.sh --obs-smoke): run a short
2-player P2P session with telemetry enabled over the virtual network,
force rollbacks with latency, then validate that

  1. session.telemetry() returns one JSON-serializable snapshot whose
     metrics/events/tracer sections are populated,
  2. the Prometheus text export parses line-by-line (exposition 0.0.4),
  3. a forced desync writes a forensics bundle containing the divergent
     frame, both checksums, and at least one preceding rollback event.

Pure host code — no jax import, runs in a couple hundred milliseconds.
Exits nonzero with a reason on any failure.
"""

import json
import os
import random
import re
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

from ggrs_tpu import (  # noqa: E402
    DesyncDetection,
    PlayerType,
    SessionBuilder,
    SessionState,
    enable_global_telemetry,
)
from ggrs_tpu.obs import GLOBAL_TELEMETRY  # noqa: E402
from ggrs_tpu.network.sockets import InMemoryNetwork  # noqa: E402
from ggrs_tpu.types import AdvanceFrame, LoadGameState, SaveGameState  # noqa: E402
from ggrs_tpu.utils.clock import FakeClock  # noqa: E402
from ggrs_tpu.utils.tracing import GLOBAL_TRACER  # noqa: E402


class Stub:
    """Minimal request fulfiller; `salt` desynchronizes checksums."""

    def __init__(self, salt=0):
        self.frame = 0
        self.state = 0
        self.salt = salt

    def handle_requests(self, requests):
        for req in requests:
            if isinstance(req, SaveGameState):
                checksum = (self.frame * 31 + self.state * 7 + self.salt) % (1 << 32)
                req.cell.save(req.frame, (self.frame, self.state), checksum)
            elif isinstance(req, LoadGameState):
                self.frame, self.state = req.cell.load()
            elif isinstance(req, AdvanceFrame):
                self.frame += 1
                for buf, _ in req.inputs:
                    self.state += buf[0] + 1


def fail(reason):
    print(f"obs-smoke FAIL: {reason}")
    sys.exit(1)


def validate_prometheus(text):
    sample = re.compile(
        r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
        r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(\\.|[^"\\])*"'
        r'(,[a-zA-Z_][a-zA-Z0-9_]*="(\\.|[^"\\])*")*\})?'
        r" -?[0-9.eE+-]+$"  # '-' inside too: scientific negatives like 8e-05
    )
    comment = re.compile(r"^# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]*( .*)?$")
    n = 0
    for line in text.strip().splitlines():
        if not (comment.match(line) if line.startswith("#") else sample.match(line)):
            fail(f"unparseable prometheus line: {line!r}")
        n += 1
    if n < 10:
        fail(f"prometheus export suspiciously small ({n} lines)")
    return n


def main():
    dump_dir = tempfile.mkdtemp(prefix="ggrs_obs_smoke_")
    enable_global_telemetry(dump_dir=dump_dir)
    GLOBAL_TRACER.enabled = True

    clock = FakeClock()
    # latency forces mispredictions -> rollbacks precede the desync
    net = InMemoryNetwork(clock, latency_ms=40, seed=7)

    def build(my, other, handle):
        return (
            SessionBuilder(input_size=1)
            .with_num_players(2)
            .with_max_prediction_window(8)
            .with_clock(clock)
            .with_rng(random.Random(hash(my) & 0xFFFF))
            .with_desync_detection_mode(DesyncDetection.on(10))
            .add_player(PlayerType.local(), handle)
            .add_player(PlayerType.remote(other), 1 - handle)
            .start_p2p_session(net.socket(my))
        )

    s1, s2 = build("a", "b", 0), build("b", "a", 1)
    for _ in range(400):
        for s in (s1, s2):
            s.poll_remote_clients()
            s.events()
        clock.advance(20)
        if all(s.current_state() == SessionState.RUNNING for s in (s1, s2)):
            break
    else:
        fail("sessions never synchronized")

    g1, g2 = Stub(salt=0), Stub(salt=99)  # salted checksums -> forced desync
    for frame in range(150):
        s1.add_local_input(0, bytes([frame % 7]))
        g1.handle_requests(s1.advance_frame())
        s2.add_local_input(1, bytes([(frame * 3) % 5]))
        g2.handle_requests(s2.advance_frame())
        s1.events()
        s2.events()
        clock.advance(16)

    # 1. one structured snapshot, JSON round-trippable
    snap = s1.telemetry()
    try:
        snap = json.loads(json.dumps(snap))
    except (TypeError, ValueError) as exc:
        fail(f"telemetry snapshot not JSON-serializable: {exc}")
    for section in ("metrics", "events", "tracer", "session"):
        if section not in snap:
            fail(f"snapshot missing section {section!r}")
    if snap["metrics"].get("ggrs_rollback_depth_frames", {}).get("values", {}).get(
        "", {}
    ).get("count", 0) == 0:
        fail("no rollbacks recorded — latency harness broken")
    spans = snap["metrics"].get("ggrs_span_ms", {}).get("values", {})
    if spans.get("session/advance", {}).get("count", 0) == 0:
        fail("tracer spans did not reach the registry (ggrs_span_ms)")
    if snap["tracer"].get("session/advance", {}).get("count") != (
        spans["session/advance"]["count"]
    ):
        fail("the snapshot's tracer section disagrees with ggrs_span_ms")

    # 2. prometheus export parses, spans as an ordinary histogram
    prom = GLOBAL_TELEMETRY.prometheus()
    n_lines = validate_prometheus(prom)
    if 'ggrs_span_ms_count{span="session/advance"}' not in prom:
        fail("ggrs_span_ms missing from the prometheus export")

    # 3. desync forensics bundle landed and is diagnosable
    dumps = sorted(os.listdir(dump_dir))
    if not dumps:
        fail("forced desync produced no forensics dump")
    bundle = json.load(open(os.path.join(dump_dir, dumps[0])))
    if bundle["local_checksum"] == bundle["remote_checksum"]:
        fail("forensics bundle checksums do not diverge")
    if not [e for e in bundle["events"] if e["kind"].startswith("rollback")]:
        fail("forensics bundle carries no preceding rollback events")

    print(
        f"obs-smoke OK: {len(snap['metrics'])} metrics, "
        f"{len(snap['events'])} recorded events, {n_lines} prometheus lines, "
        f"{len(dumps)} forensics dump(s) in {dump_dir}"
    )


if __name__ == "__main__":
    main()
