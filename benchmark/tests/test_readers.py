"""Each metric reader on recorded counters and a recorded trace summary:
the arithmetic, and nothing returned where there is nothing to read."""

from types import SimpleNamespace

import pytest

from benchmark import harness

SERVE_RAW = {"window_s": 2.0, "host_ticks": 4, "tick_ms": [10.0, 20.0, 30.0, 40.0],
             "session_ticks": 1000, "dispatches": 6}
SYNC_RAW = {"window_s": 2.0, "batches": 100, "frames": 6000, "check_distance": 8,
            "entities": 4096, "ring_len": 10, "players": 2, "backend": "pallas"}
COUNTERS = {
    "ggrs_host_tax_ms": {"values": {
        "pump": {"sum": 4.0}, "endpoint": {"sum": 2.0}, "encode": {"sum": 2.0},
        "parse": {"sum": 12.0}, "drain": {"sum": 4.0}}},
    "ggrs_rollback_depth_frames": {"values": {"": {"sum": 250.0}}},
}
TRACE = {"busy_s": 0.5, "window_s": 2.0,
         "ops": {"%fusion.1 fusion": 0.25, "%all-reduce.2 all-reduce": 0.05,
                 "%batch.3 custom-call": 0.2}}


def run(raw, counters=None, trace=None, traced=None):
    return SimpleNamespace(raw=raw, counters=counters or {}, trace=trace,
                           traced=traced or {}, setup_s=12.5, config={},
                           traffic={}, device_kind="TPU v5 lite", chips=1)


@pytest.mark.parametrize("name,raw,counters,trace,traced,want", [
    ("setup_s", SERVE_RAW, None, None, None, 12.5),
    ("session_ticks_per_s", SERVE_RAW, None, None, None, 500.0),
    ("host_tick_ms_p95", SERVE_RAW, None, None, None, 38.5),
    ("host_tick_ms_p50", SERVE_RAW, None, None, None, 25.0),
    ("rollback_frames_per_s", SYNC_RAW, None, None, None, 24000.0),
    ("wire_ms_per_tick", SERVE_RAW, COUNTERS, None, None, 2.0),
    ("host_stage_ms_per_tick", SERVE_RAW, COUNTERS, None, None, 4.0),
    ("resim_frames_per_session_tick", SERVE_RAW, COUNTERS, None, None, 0.25),
    ("dispatches_per_tick", SERVE_RAW, None, None, None, 1.5),
    ("device_idle_share.serve", SERVE_RAW, None, TRACE, None, 75.0),
    ("device_idle_share.synctest", SYNC_RAW, None, TRACE, None, 75.0),
    ("collective_ms_per_frame", SYNC_RAW, None, TRACE, {"frames": 50}, 1.0),
    ("device_idle_share.mesh", SYNC_RAW, None, TRACE, None, 75.0),
    ("rollback_frames_per_s.mesh", SYNC_RAW, None, None, None, 24000.0),
])
def test_reader_arithmetic(name, raw, counters, trace, traced, want):
    got = harness.reader(name)(run(raw, counters, trace, traced))
    assert got == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "wire_ms_per_tick", "host_stage_ms_per_tick", "resim_frames_per_session_tick",
    "device_idle_share.serve",
    "device_idle_share.synctest", "synctest_kernel_us_per_frame",
    "synctest_kernel_hbm_roofline", "collective_ms_per_frame",
    "tiled_kernel_us_per_frame", "tiled_kernel_hbm_roofline",
])
def test_reader_without_its_source_reads_nothing(name):
    assert harness.reader(name)(run(SERVE_RAW)) is None


def test_kernel_readers_on_recorded_trace():
    trace = {"busy_s": 0.5, "window_s": 2.0,
             "ops": {"%batch.1 custom-call": 0.4, "%copy.1 copy": 0.1}}
    r = run(SYNC_RAW, trace=trace, traced={"frames": 6000, "batches": 100})
    us = harness.reader("synctest_kernel_us_per_frame")(r)
    assert us == pytest.approx(0.4 / 6000 * 1e6)
    share = harness.reader("synctest_kernel_hbm_roofline")(r)
    from benchmark.bytes_model import synctest_batch_bytes

    want = 100 * synctest_batch_bytes(4096, 2, 8, 60) * 100 / 0.4 / 819e9
    assert share == pytest.approx(want)
    assert 0 < share < 100
    mesh = run({**SYNC_RAW, "entity_shards": 2}, trace=trace,
               traced={"frames": 6000, "batches": 100})
    half = harness.reader("tiled_kernel_hbm_roofline")(mesh)
    assert half == pytest.approx(
        100 * synctest_batch_bytes(4096, 2, 8, 60, 2) * 100 / 0.4 / 819e9)
    assert half < share
