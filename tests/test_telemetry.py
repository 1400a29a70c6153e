"""Session telemetry subsystem: metrics registry, flight recorder, exporters
and desync forensics (ggrs_tpu/obs)."""

import json
import os
import random
import re

import pytest

from ggrs_tpu import (
    DesyncDetection,
    PlayerType,
    SessionBuilder,
    SessionState,
)
from ggrs_tpu.obs import (
    GLOBAL_TELEMETRY,
    FlightRecorder,
    MetricsRegistry,
    Telemetry,
)
from ggrs_tpu.network.sockets import InMemoryNetwork
from ggrs_tpu.utils.clock import FakeClock
from stubs import GameStub, RandomChecksumGameStub


@pytest.fixture
def telemetry(tmp_path):
    """Enable the process-global telemetry for one test, clean slate, and
    guarantee it is disabled and zeroed again afterwards."""
    tel = GLOBAL_TELEMETRY
    tel.reset()
    tel.enabled = True
    tel.dump_dir = str(tmp_path)
    try:
        yield tel
    finally:
        tel.enabled = False
        tel.dump_dir = None
        tel.reset()


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------


def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("c_total", "a counter", ("peer",))
    c.labels("a").inc()
    c.labels("a").inc(2)
    c.labels("b").inc()
    assert c.labels("a").value == 3
    assert c.labels("b").value == 1

    g = reg.gauge("g", "a gauge")
    g.set(5)
    g.inc()
    g.dec(2)
    assert g.value == 4

    h = reg.histogram("h", "log2 buckets")
    for v in (0.5, 1, 3, 1000, 10**6):
        h.observe(v)
    snap = h.snapshot()["values"][""]
    assert snap["count"] == 5
    assert snap["buckets"]["1"] == 2  # 0.5 and 1.0 both land in le=1
    assert snap["buckets"]["4"] == 1
    assert snap["buckets"]["+Inf"] == 1  # 10**6 overflows the fixed buckets


def test_registry_kind_clash_raises():
    reg = MetricsRegistry()
    reg.counter("x", "")
    with pytest.raises(ValueError):
        reg.gauge("x", "")


def test_reset_keeps_bound_children_valid():
    reg = MetricsRegistry()
    bound = reg.counter("c_total", "", ("peer",)).labels("a")
    bound.inc(7)
    reg.reset()
    assert bound.value == 0
    bound.inc()  # the pre-bound child must still feed the registry
    assert reg.counter("c_total", "", ("peer",)).labels("a").value == 1


def test_flight_recorder_is_bounded_ring():
    rec = FlightRecorder(capacity=4)
    for i in range(10):
        rec.record("tick", frame=i)
    assert len(rec) == 4
    assert rec.total_recorded == 10
    frames = [e.frame for e in rec.tail()]
    assert frames == [6, 7, 8, 9]  # oldest dropped, order preserved
    assert rec.to_json(2)[-1]["frame"] == 9


def test_prometheus_text_format_is_parseable():
    reg = MetricsRegistry()
    reg.counter("a_total", "with \"quotes\"", ("peer",)).labels('x"y').inc()
    reg.gauge("b", "").set(1.5)
    reg.histogram("h_ms", "").observe(3)
    sample = re.compile(
        r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z_][a-zA-Z0-9_]*="(\\.|[^"\\])*"'
        r'(,[a-zA-Z_][a-zA-Z0-9_]*="(\\.|[^"\\])*")*\})? -?[0-9.eE+-]+$'
    )
    for line in reg.prometheus_lines():
        if line.startswith("#"):
            assert re.match(r"^# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]*( .*)?$", line)
        else:
            assert sample.match(line), f"unparseable sample line: {line!r}"


def test_disabled_telemetry_records_nothing():
    tel = GLOBAL_TELEMETRY
    assert not tel.enabled  # process default
    before = tel.recorder.total_recorded
    session = (
        SessionBuilder(input_size=1)
        .with_num_players(2)
        .with_check_distance(2)
        .start_synctest_session()
    )
    game = GameStub()
    for frame in range(20):
        session.add_local_input(0, bytes([frame % 3]))
        session.add_local_input(1, bytes([frame % 5]))
        game.handle_requests(session.advance_frame())
    assert tel.recorder.total_recorded == before
    loads = tel.registry.get("ggrs_state_loads_total")
    assert loads is None or all(
        v == 0 for v in loads.snapshot()["values"].values()
    )


# ---------------------------------------------------------------------------
# session surfaces
# ---------------------------------------------------------------------------


def test_sync_test_session_telemetry(telemetry):
    session = (
        SessionBuilder(input_size=1)
        .with_num_players(2)
        .with_check_distance(2)
        .start_synctest_session()
    )
    game = GameStub()
    for frame in range(20):
        session.add_local_input(0, bytes([frame % 3]))
        session.add_local_input(1, bytes([frame % 5]))
        game.handle_requests(session.advance_frame())

    snap = session.telemetry()
    json.dumps(snap)  # JSON-serializable end to end
    assert snap["session"]["type"] == "sync_test"
    assert snap["session"]["current_frame"] == 20
    # forced rollbacks every deep-enough tick: metrics + flight events
    loads = snap["metrics"]["ggrs_state_loads_total"]["values"][""]
    assert loads > 0
    kinds = {e["kind"] for e in snap["events"]}
    assert {"rollback_begin", "rollback_end"} <= kinds
    depth = snap["metrics"]["ggrs_rollback_depth_frames"]["values"][""]
    assert depth["count"] == loads


def _p2p_pair(clock, net, desync=None):
    def build(my, other, handle):
        b = (
            SessionBuilder(input_size=1)
            .with_num_players(2)
            .with_max_prediction_window(8)
            .with_clock(clock)
            .with_rng(random.Random(hash(my) & 0xFFFF))
        )
        if desync is not None:
            b = b.with_desync_detection_mode(desync)
        b = b.add_player(PlayerType.local(), handle)
        b = b.add_player(PlayerType.remote(other), 1 - handle)
        return b.start_p2p_session(net.socket(my))

    s1, s2 = build("a", "b", 0), build("b", "a", 1)
    for _ in range(400):
        for s in (s1, s2):
            s.poll_remote_clients()
            s.events()
        clock.advance(20)
        if all(s.current_state() == SessionState.RUNNING for s in (s1, s2)):
            return s1, s2
    raise AssertionError("sessions failed to synchronize")


def test_p2p_session_telemetry_snapshot(telemetry):
    clock = FakeClock()
    net = InMemoryNetwork(clock, latency_ms=40, seed=5)
    s1, s2 = _p2p_pair(clock, net)
    g1, g2 = GameStub(), GameStub()
    for frame in range(60):
        s1.add_local_input(0, bytes([frame % 7]))
        g1.handle_requests(s1.advance_frame())
        s2.add_local_input(1, bytes([(frame * 3) % 5]))
        g2.handle_requests(s2.advance_frame())
        s1.events()
        s2.events()
        clock.advance(16)

    snap = s1.telemetry()
    json.dumps(snap)
    sess = snap["session"]
    assert sess["type"] == "p2p" and sess["state"] == "running"
    assert sess["current_frame"] == 60
    # 40ms latency at 16ms frames: predictions must have missed -> accuracy < 1
    assert sess["prediction_accuracy"] and all(
        0.0 <= v < 1.0 for v in sess["prediction_accuracy"].values()
    )
    # per-peer network section carries the extended stats
    stats = sess["network"]["1"]
    assert stats["kbps_recv"] >= 0 and "jitter_ms" in stats and "packets_lost" in stats
    # wire counters moved in both directions
    m = snap["metrics"]
    assert m["ggrs_peer_bytes_sent_total"]["values"]["b"] > 0
    assert m["ggrs_peer_bytes_recv_total"]["values"]["b"] > 0
    # frame-advantage distribution recorded per peer
    assert m["ggrs_frame_advantage"]["values"]["b"]["count"] > 0
    # rollbacks happened under latency and were recorded
    assert m["ggrs_rollback_depth_frames"]["values"][""]["count"] > 0
    # prometheus export of the full live registry parses
    for line in GLOBAL_TELEMETRY.prometheus().strip().splitlines():
        assert line.startswith("#") or re.match(
            r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{.*\})? -?[0-9.eE+-]+$", line
        ), f"unparseable: {line!r}"


def test_spectator_session_telemetry(telemetry):
    clock = FakeClock()
    net = InMemoryNetwork(clock)
    host = (
        SessionBuilder(input_size=1)
        .with_num_players(1)
        .with_clock(clock)
        .with_rng(random.Random(21))
        .add_player(PlayerType.local(), 0)
        .add_player(PlayerType.spectator("spec"), 1)
        .start_p2p_session(net.socket("host"))
    )
    spec = (
        SessionBuilder(input_size=1)
        .with_num_players(1)
        .with_clock(clock)
        .with_rng(random.Random(22))
        .start_spectator_session("host", net.socket("spec"))
    )
    for _ in range(60):
        host.poll_remote_clients()
        spec.poll_remote_clients()
        host.events()
        spec.events()
        clock.advance(20)
        if (
            host.current_state() == SessionState.RUNNING
            and spec.current_state() == SessionState.RUNNING
        ):
            break
    snap = spec.telemetry()
    json.dumps(snap)
    assert snap["session"]["type"] == "spectator"
    assert snap["session"]["state"] == "running"
    assert "network" in snap["session"]


def test_tracer_stats_fold_into_snapshot():
    from ggrs_tpu.utils.tracing import Tracer

    tel = Telemetry(enabled=True)
    t = Tracer(enabled=True, registry=tel.registry)
    with t.span("tick"):
        pass
    snap = tel.snapshot(tracer=t)
    assert snap["tracer"]["tick"]["count"] == 1
    # the span table is an ordinary registry histogram in both exporters
    assert snap["metrics"]["ggrs_span_ms"]["values"]["tick"]["count"] == 1
    assert snap["tracer"]["tick"]["total_ms"] == (
        snap["metrics"]["ggrs_span_ms"]["values"]["tick"]["sum"]
    )
    text = tel.prometheus()
    assert 'ggrs_span_ms_count{span="tick"} 1' in text
    assert "ggrs_tracer_span" not in text


# ---------------------------------------------------------------------------
# desync forensics
# ---------------------------------------------------------------------------


def test_forced_desync_emits_forensics_bundle(telemetry, tmp_path):
    clock = FakeClock()
    # latency forces mispredictions/rollbacks BEFORE the desync fires, so
    # the bundle's flight-recorder tail has rollback context to show
    net = InMemoryNetwork(clock, latency_ms=40, seed=17)
    s1, s2 = _p2p_pair(clock, net, desync=DesyncDetection.on(10))
    g1 = GameStub()
    g2 = RandomChecksumGameStub()  # checksums never agree -> guaranteed desync
    for frame in range(150):
        s1.add_local_input(0, bytes([frame % 7]))
        g1.handle_requests(s1.advance_frame())
        s2.add_local_input(1, bytes([(frame * 3) % 5]))
        g2.handle_requests(s2.advance_frame())
        s1.events()
        s2.events()
        clock.advance(16)

    dumps = sorted(os.listdir(tmp_path))
    assert dumps, "expected at least one desync forensics dump"
    bundle = json.load(open(os.path.join(tmp_path, dumps[0])))
    assert bundle["kind"] == "desync_forensics"
    assert bundle["frame"] >= 0
    assert bundle["local_checksum"] != bundle["remote_checksum"]
    assert isinstance(bundle["pending_predicted_inputs"], list)
    rollback_events = [
        e for e in bundle["events"] if e["kind"].startswith("rollback")
    ]
    assert rollback_events, "bundle must carry preceding rollback events"
    assert bundle["session"]["type"] == "p2p"
    # one dump per (peer, frame) per session: comparison intervals
    # re-detect the same divergence every pass but must not re-dump it.
    # Both sessions of the pair live in this process, so a frame may
    # appear at most twice (once per session), never more.
    frames_dumped = [
        json.load(open(os.path.join(tmp_path, d)))["frame"] for d in dumps
    ]
    assert all(frames_dumped.count(f) <= 2 for f in set(frames_dumped))


def test_forensics_dump_cap(telemetry, tmp_path):
    telemetry.MAX_FORENSICS_DUMPS  # class attr exists
    for i in range(Telemetry.MAX_FORENSICS_DUMPS + 5):
        telemetry.write_desync_forensics(
            frame=i, local_checksum=1, remote_checksum=2, addr="x"
        )
    assert len(os.listdir(tmp_path)) == Telemetry.MAX_FORENSICS_DUMPS


def test_session_events_have_typed_dict_forms():
    from ggrs_tpu.types import (
        DesyncDetected,
        Disconnected,
        Event,
        NetworkInterrupted,
        Synchronizing,
        WaitRecommendation,
    )
    from typing import get_args

    members = get_args(Event)
    assert Disconnected in members and DesyncDetected in members
    d = DesyncDetected(
        frame=7, local_checksum=1, remote_checksum=2, addr=("h", 9999)
    )
    out = d.to_dict()
    assert out["kind"] == "desync_detected" and out["frame"] == 7
    json.dumps(out)  # addr degraded to a JSON-able form
    assert Synchronizing(addr="a", total=5, count=1).to_dict()["kind"] == "synchronizing"
    assert NetworkInterrupted(addr="a", disconnect_timeout_ms=5).to_dict()[
        "disconnect_timeout_ms"
    ] == 5
    assert WaitRecommendation(skip_frames=3).to_dict()["skip_frames"] == 3
