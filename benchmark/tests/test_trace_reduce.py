"""trace_reduce on a small trace: a synthetic XSpace with known intervals,
and the trace recorded on the chip (tests/data) when it is there."""

import glob
import os

import pytest
from jax.profiler import ProfileData

from benchmark import trace_reduce

XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000000 }
    events { metadata_id: 2 offset_ps: 1000000000 duration_ps: 2000000000 }
    events { metadata_id: 3 offset_ps: 5000000000 duration_ps: 1000000000 }
    events { metadata_id: 1 offset_ps: 9000000000 duration_ps: 1000000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 0 duration_ps: 10000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = s32[8]{0} fusion(s32[8]{0} %p)" } }
  event_metadata { key: 2 value { id: 2 name: "%batch.2 = (s32[8]{0}, s32[]) custom-call(s32[8]{0} %p)" } }
  event_metadata { key: 3 value { id: 3 name: "%all-reduce.3 = u32[2]{0:T(128)} all-reduce(u32[2]{0} %x)" } }
  event_metadata { key: 4 value { id: 4 name: "jit_step" } }
}
planes {
  id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = s32[8]{0} fusion(s32[8]{0} %p)" } }
}
planes {
  id: 3 name: "/host:CPU"
  lines { id: 1 name: "main" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 2500000000 duration_ps: 3000000000 }
    events { metadata_id: 2 offset_ps: 3000000000 duration_ps: 1500000000 }
    events { metadata_id: 3 offset_ps: 6500000000 duration_ps: 2000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "host/tick" } }
  event_metadata { key: 2 value { id: 2 name: "host/pump" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(step)" } }
}
"""
# device 0 busy [0,3) [5,6) [9,10) ms -> 5 ms; device 1 busy [0,4) -> 4 ms


def planes():
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(XSPACE)).planes


def test_busy_ops_and_gaps():
    t = trace_reduce.reduce_planes(planes(), [0, 1], window_s=20e-3)
    assert t["devices"] == 2
    assert t["busy_s"] == pytest.approx(4.5e-3)
    assert t["ops"]["%fusion.1 fusion"] == pytest.approx(3.5e-3)
    assert t["ops"]["%batch.2 custom-call"] == pytest.approx(1e-3)
    assert t["device_ops"][0] == ["%fusion.1 fusion", pytest.approx(3.5e-3)]
    # gaps on device 0: [3,5) -> inside host/pump (innermost), [6,9) -> none
    # with a "/" (PjitFunction is not a span)
    assert t["idle_gaps"] == [["no_host_span", pytest.approx(3e-3)],
                              ["host/pump", pytest.approx(2e-3)]]
    coll = trace_reduce.op_seconds(t, trace_reduce.is_collective)
    assert coll == pytest.approx(0.5e-3)


def test_only_the_cells_devices_count():
    t = trace_reduce.reduce_planes(planes(), [1], window_s=20e-3)
    assert t["devices"] == 1 and t["busy_s"] == pytest.approx(4e-3)
    assert trace_reduce.reduce_planes(planes(), [7], window_s=1.0) is None


RECORDED = sorted(glob.glob(os.path.join(os.path.dirname(__file__), "data",
                                         "*.xplane.pb")))


@pytest.mark.skipif(not RECORDED, reason="no recorded chip trace")
@pytest.mark.parametrize("path", RECORDED)
def test_recorded_chip_trace(path):
    t = trace_reduce.reduce_file(path, [0], window_s=10.0)
    assert t is not None and 0 < t["busy_s"] < 10.0
    assert t["device_ops"] and len(t["idle_gaps"]) <= 10
