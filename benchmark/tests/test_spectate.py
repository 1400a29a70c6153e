"""The spectator cell and the LAN control cell at a test size on the CPU:
sound runs come out correct, their controls (the reference at int16 in the
program's place) do not, and neither does a spectate run with one
spectator broken: its slot world altered, the inputs it receives altered,
or its lane frozen. The spectator readers give nothing where the program
keeps no span row or histogram for them, and a value in a traced run."""

import time
from types import SimpleNamespace

import jax
import pytest

from benchmark import harness

SPECTATE = "exgame_2p_spec6_4096.spectate_wan"
LAN = "exgame_2p_4096.serve_lan"
SIZES = {SPECTATE: {"entities": 128, "sessions": 16},  # 2 matches x 8 lanes
         LAN: {"entities": 128, "sessions": 8}}
READERS = ["spectator_advance_ms_per_tick", "spectator_send_ms_per_tick",
           "spectator_frames_behind_mean"]


def run(workload, *, control=False, trace=False, fault=None):
    """One run of the cell; `fault(Cell)` breaks the driver's cell class
    (the harness loads a fresh copy of the driver each time)."""
    c = harness.resolve(harness.load_spec(), workload)
    if fault is not None:
        fault(c.driver.Cell)
    return harness.run_cell(c, seed=2**31 + 26, seconds=1.0, trace=trace,
                            t_process=time.perf_counter(), control=control,
                            devices=jax.devices()[:1], sizes=SIZES[workload])


def failing(r):
    return {k for k, v in r["compared"].items() if v["value"] > v["limit"]}


@pytest.mark.parametrize("workload", [SPECTATE, LAN])
def test_sound_run_is_correct(workload):
    r = run(workload)
    assert r["correct"], r["compared"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert all(v["limit"] == 0 for v in r["compared"].values())


@pytest.mark.parametrize("workload", [SPECTATE, LAN])
def test_control_is_not_correct(workload):
    r = run(workload, control=True)
    assert not r["correct"] and failing(r)
    if workload == SPECTATE:
        assert "spectator_worlds_wrong" in failing(r)


def _before_window(act):
    """Break the first spectator of match 0 as the window starts."""
    def fault(Cell):
        orig = Cell.window

        def window(self, seconds, tracing):
            act(self, self.specs[0][0], self.spec_keys[0][0])
            return orig(self, seconds, tracing)

        Cell.window = window
    return fault


def _world_altered(cell, spectator, key):
    """Its slot world back to genesis: a spectator never loads a state,
    so the world stays wrong."""
    cell.host.device.reset_slot(cell.host._lanes[key].slot)


def _inputs_altered(cell, spectator, key):
    """Every input it receives from now on has its low bit flipped."""
    from ggrs_tpu.frame_info import PlayerInput
    from ggrs_tpu.network.protocol import EvInput

    orig = spectator._handle_event

    def handle(event, addr):
        if isinstance(event, EvInput):
            buf = bytes([event.input.buf[0] ^ 1])
            event = EvInput(input=PlayerInput(event.input.frame, buf),
                            player=event.player)
        orig(event, addr)

    spectator._handle_event = handle


def _lane_frozen(cell, spectator, key):
    """It never advances again: every advance finds no input."""
    from ggrs_tpu.errors import PredictionThreshold

    def starve():
        raise PredictionThreshold()

    spectator.advance_frame = starve


@pytest.mark.parametrize("act,count", [
    (_world_altered, "spectator_worlds_wrong"),
    (_inputs_altered, "spectator_inputs_wrong"),
    (_lane_frozen, "spectators_stalled"),
])
def test_spectator_fault_is_not_correct(act, count):
    r = run(SPECTATE, fault=_before_window(act))
    assert not r["correct"]
    assert count in failing(r), r["compared"]


SERVE_RAW = {"window_s": 2.0, "host_ticks": 4, "tick_ms": [10.0] * 4,
             "session_ticks": 1000, "dispatches": 6}
COUNTERS = {
    "ggrs_span_ms": {"values": {
        "session/advance": {"sum": 8.0}, "spectator/advance": {"sum": 6.0},
        "session/spectator_send": {"sum": 2.0}}},
    "ggrs_spectator_frames_behind": {"values": {"": {"count": 4, "sum": 6.0}}},
}


def _run(counters):
    return SimpleNamespace(raw=SERVE_RAW, counters=counters, trace=None,
                           traced={}, setup_s=1.0, config={}, traffic={},
                           device_kind="TPU v5 lite", chips=1)


@pytest.mark.parametrize("name,want", zip(READERS, [1.5, 0.5, 1.5]))
def test_reader_arithmetic(name, want):
    assert harness.reader(name)(_run(COUNTERS)) == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_reader_without_its_source_reads_nothing(name):
    assert harness.reader(name)(_run({})) is None
    # a program with a span table but no spectator rows or histogram (the
    # parent of the spans), and one whose histogram saw no advance
    parent = {"ggrs_span_ms": {"values": {"session/advance": {"sum": 8.0}}}}
    assert harness.reader(name)(_run(parent)) is None
    idle = {**parent, "ggrs_spectator_frames_behind":
            {"values": {"": {"count": 0, "sum": 0.0}}}}
    assert harness.reader(name)(_run(idle)) is None


def test_traced_run_reports_the_spectator_metrics():
    from ggrs_tpu.obs import GLOBAL_TELEMETRY

    r = run(SPECTATE, trace=True)
    assert r["correct"], r["compared"]
    for name in READERS:
        assert r["metrics"].get(name, {}).get("value") is not None, name
    assert r["metrics"]["spectator_advance_ms_per_tick"]["value"] > 0
    # the window's counters are still in the registry after the run
    snap = GLOBAL_TELEMETRY.registry.snapshot()
    assert sum(snap["ggrs_spectator_sends_total"]["values"].values()) > 0
    assert sum(snap["ggrs_spectator_frames_total"]["values"].values()) > 0
