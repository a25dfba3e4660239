"""The trace reduction and the byte count of the kernel roofline."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import devtrace  # noqa: E402
import roofline  # noqa: E402

# one device plane and the host's annotations; times in ns, offsets in ps
TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 3000000 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 3000000 }
    events { metadata_id: 1 offset_ps: 15000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 25000000 duration_ps: 1000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 1000000 duration_ps: 20000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "copy.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit_merge" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 500000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 19000000 }
    events { metadata_id: 3 offset_ps: 9000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.submit" } }
  event_metadata { key: 2 value { id: 2 name: "bench.run_all" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(_linear_pallas)" } }
}
"""


def _profile(text):
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(text)


def test_busy_idle_and_ops_of_a_recorded_trace():
    got = devtrace.reduce(_profile(TRACE))
    # window: first annotation (1 us) to the end of the last (21 us); the
    # op at 25 us lies outside it; ops at 3-6 and 5-8 us overlap
    assert got["window_s"] == pytest.approx(20e-6)
    assert got["busy_s"] == pytest.approx(6e-6)
    assert got["device_ops"] == [["fusion.1", pytest.approx(4e-6)],
                                 ["copy.2", pytest.approx(3e-6)]]
    assert got["idle_gaps"] == [["bench.run_all", pytest.approx(7e-6)],
                                ["bench.run_all", pytest.approx(5e-6)],
                                ["bench.run_all", pytest.approx(2e-6)]]


def test_busy_is_averaged_over_device_planes():
    second = TRACE.replace('id: 2 name: "/host:CPU"', 'id: 9 name: "/host:CPU"')
    extra = """
planes {
  id: 3 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 2000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
}
"""
    got = devtrace.reduce(_profile(second + extra))
    assert got["busy_s"] == pytest.approx((6e-6 + 2e-6) / 2)


def test_a_trace_without_a_device_reduces_to_nothing():
    host_only = TRACE[TRACE.index('planes {\n  id: 2'):]
    assert devtrace.reduce(_profile(host_only)) is None


def test_union_and_clip():
    assert devtrace.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    assert devtrace.clip([(0, 3), (4, 9)], 2, 6) == [(2, 3), (4, 6)]


def test_merge_bytes_counts_merged_blocks_only():
    # tensor t: blocks of 4096, 4096 and a ragged 100 bytes; u: one block
    # of 50 bytes; v: never selected, so it passes through
    sizes = {"t": 2 * 4096 + 100, "u": 50, "v": 4096}
    selection = {"e0": {"t": [0, 2]}, "e1": {"t": [2], "u": [0]}}
    # (t,0): base + 1 expert + out; (t,2): base + 2 experts + out on the
    # ragged tail; (u,0): base + 1 expert + out; (t,1) passes through
    want = 3 * 4096 + 4 * 100 + 3 * 50
    assert roofline.merge_bytes(selection, sizes, 4096) == want


def test_merge_bytes_refuses_a_block_outside_its_tensor():
    with pytest.raises(ValueError):
        roofline.merge_bytes({"e0": {"t": [3]}}, {"t": 3 * 4096}, 4096)


def test_peaks_are_known_only_for_listed_chips():
    assert roofline.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peak("TPU v99")
