"""The SyncTest dispatch reader (synctest_batches_per_dispatch and its .mesh
twin): batches over device programs from the session's two counters,
nothing returned where the program keeps neither or dispatched nothing, and
a value from each in a traced CPU run of the cell that lists it."""

import time
from types import SimpleNamespace

import jax
import pytest

from benchmark import harness
from benchmark.tests import cells

NAMES = ["synctest_batches_per_dispatch", "synctest_batches_per_dispatch.mesh"]
MESH_SIZES = {"entities": 512, "backend": "pallas-tiled-interpret"}


def run(counters):
    return SimpleNamespace(raw={"batches": 64}, counters=counters,
                           trace=None, traced={}, setup_s=12.5, config={},
                           traffic={}, device_kind="TPU v5 lite", chips=1)


def counters(batches, dispatches):
    return {
        "ggrs_synctest_batches_total": {"values": {"": batches}},
        "ggrs_synctest_dispatches_total": {"values": {"": dispatches}},
    }


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("batches,dispatches,want", [
    (64, 8, 8.0), (67, 11, 67 / 11), (5, 5, 1.0),
])
def test_reader_arithmetic(name, batches, dispatches, want):
    got = harness.reader(name)(run(counters(batches, dispatches)))
    assert got == pytest.approx(want)


@pytest.mark.parametrize("name", NAMES)
@pytest.mark.parametrize("have", [
    {},  # a program without the counters
    {"ggrs_synctest_batches_total": {"values": {"": 64.0}}},
    counters(0, 0),  # nothing dispatched in the window
])
def test_reader_without_its_source_reads_nothing(name, have):
    assert harness.reader(name)(run(have)) is None


@pytest.mark.parametrize("workload,name,sizes", [
    ("exgame_2p_4096.synctest_d8", NAMES[0], None),
    ("ecs_13056_d16.synctest_mesh4", NAMES[1], MESH_SIZES),
])
def test_traced_cpu_run_reports_a_full_group_per_dispatch(workload, name,
                                                          sizes):
    """The cells' loop reads the verdict once per 64 batches of 60 frames,
    which is 8 whole groups: every dispatch of the window carries 8."""
    from ggrs_tpu.tpu.sync_test import COALESCE_FRAMES

    c = harness.resolve(harness.load_spec(), workload)
    assert name in {m["name"] for m in c.per_layer}
    r = harness.run_cell(c, seed=2**33 + 5, seconds=0.5, trace=True,
                         t_process=time.perf_counter(),
                         devices=jax.devices()[: c.cell["chips"]],
                         sizes=sizes or cells.SIZES[c.traffic["driver"]])
    assert r["correct"], r["compared"]
    want = COALESCE_FRAMES // c.traffic["batch_frames"]
    assert r["metrics"][name]["value"] == want
