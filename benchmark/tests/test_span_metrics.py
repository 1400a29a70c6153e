"""The span readers (session_advance_ms_per_tick, dispatch_host_ms_per_tick,
fence_stall_ms_per_tick, gc_pause_ms_per_tick, synctest_host_ms_per_batch):
the arithmetic on recorded counters, nothing returned without their
source, and a value from each in a traced CPU run of the cells that list
it."""

import time
from types import SimpleNamespace

import jax
import pytest

from benchmark import harness
from benchmark.tests import cells

SERVE_RAW = {"window_s": 2.0, "host_ticks": 4, "tick_ms": [10.0, 20.0, 30.0, 40.0],
             "session_ticks": 1000, "dispatches": 6}
SYNC_RAW = {"window_s": 2.0, "batches": 100, "frames": 6000, "check_distance": 8,
            "entities": 4096, "ring_len": 10, "players": 2, "backend": "pallas"}
SPANS = {"ggrs_span_ms": {"values": {
    "host/tick": {"sum": 40.0}, "session/advance": {"sum": 8.0},
    "host/dispatch": {"sum": 2.0}, "synctest/advance": {"sum": 50.0}}}}
COUNTERS = {
    **SPANS,
    "ggrs_async_fence_stall_ms": {"values": {"": {"sum": 1.0}}},
    "ggrs_gc_pause_ms": {"values": {"0": {"sum": 1.0}, "1": {"sum": 0.5},
                                    "2": {"sum": 2.5}}},
}
READERS = ["session_advance_ms_per_tick", "dispatch_host_ms_per_tick",
           "fence_stall_ms_per_tick", "gc_pause_ms_per_tick",
           "synctest_host_ms_per_batch"]


def run(raw, counters=None):
    return SimpleNamespace(raw=raw, counters=counters or {}, trace=None,
                           traced={}, setup_s=12.5, config={}, traffic={},
                           device_kind="TPU v5 lite", chips=1)


@pytest.mark.parametrize("name,raw,want", [
    ("session_advance_ms_per_tick", SERVE_RAW, 2.0),
    ("dispatch_host_ms_per_tick", SERVE_RAW, 0.5),
    ("fence_stall_ms_per_tick", SERVE_RAW, 0.25),
    ("gc_pause_ms_per_tick", SERVE_RAW, 1.0),
    ("synctest_host_ms_per_batch", SYNC_RAW, 0.5),
])
def test_span_reader_arithmetic(name, raw, want):
    assert harness.reader(name)(run(raw, COUNTERS)) == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_span_reader_without_its_source_reads_nothing(name):
    raw = SYNC_RAW if name.startswith("synctest") else SERVE_RAW
    assert harness.reader(name)(run(raw)) is None
    # a program that keeps no span table in its registry: the histograms
    # it may still register are not this metric's source
    older = {k: v for k, v in COUNTERS.items() if k != "ggrs_span_ms"}
    assert harness.reader(name)(run(raw, older)) is None


def test_zero_fence_stalls_read_zero_not_nothing():
    counters = {**SPANS,
                "ggrs_async_fence_stall_ms": {"values": {"": {"sum": 0.0}}}}
    assert harness.reader("fence_stall_ms_per_tick")(
        run(SERVE_RAW, counters)) == 0.0


@pytest.mark.parametrize("workload,names", [
    ("exgame_2p_4096.serve_wan", READERS[:4]),
    ("exgame_4p_w12.serve_deep", READERS[:4]),
    ("exgame_2p_4096.synctest_d8", READERS[4:]),
])
def test_traced_cpu_run_reports_each_span_metric(workload, names):
    c = harness.resolve(harness.load_spec(), workload)
    assert set(names) <= {m["name"] for m in c.per_layer}
    r = harness.run_cell(c, seed=2**31 + 11, seconds=1.0, trace=True,
                         t_process=time.perf_counter(),
                         devices=jax.devices()[: c.cell["chips"]],
                         sizes=cells.SIZES[c.traffic["driver"]])
    assert r["correct"], r["compared"]
    for name in names:
        assert r["metrics"].get(name, {}).get("value") is not None, name
        assert r["metrics"][name]["value"] >= 0
