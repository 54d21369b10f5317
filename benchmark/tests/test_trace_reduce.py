"""trace_reduce on a small synthetic trace whose answer is worked by hand."""

from __future__ import annotations

import pytest

from benchmark import trace_reduce

# two device ops overlap in [2, 3] µs, a third runs alone in [11, 12] µs;
# a host thread covers 6 of the 7 µs device gap
TRACE = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Modules"
    timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 11000000 }
  }
  lines {
    id: 2
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "copy.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit_cube" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 7
    name: "bench-http"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 3000000 duration_ps: 6000000 }
    events { metadata_id: 2 offset_ps: 9500000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "ingest" } }
  event_metadata { key: 2 value { id: 2 name: "finalize" } }
}
"""


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(TRACE)


def test_busy_ops_and_gaps(profile):
    out = trace_reduce.reduce(profile)
    # the window spans every event: [1000, 12000] ns
    assert out["window_s"] == pytest.approx(11e-6)
    # union of [1000, 3000], [2000, 4000], [11000, 12000]
    assert out["busy_s"] == pytest.approx(4e-6)
    assert out["device_ops"] == [["fusion.1", pytest.approx(3e-6)],
                                 ["copy.2", pytest.approx(2e-6)]]
    (name, gap), = out["idle_gaps"]
    assert gap == pytest.approx(7e-6)
    assert name == "bench-http: ingest (85.7% of the gap)"


def test_window_clips(profile):
    out = trace_reduce.reduce(profile, window_ns=(2000, 11500))
    assert out["window_s"] == pytest.approx(9.5e-6)
    assert out["busy_s"] == pytest.approx(2.5e-6)
    assert [g for _, g in out["idle_gaps"]] == [pytest.approx(7e-6)]


def test_no_device_plane_is_no_reduction():
    from jax.profiler import ProfileData

    host_only = TRACE[TRACE.index("planes {\n  id: 2"):]
    assert trace_reduce.reduce(ProfileData.from_text_proto(host_only)) is None


def test_describe_names_planes_and_lines(profile):
    text = trace_reduce.describe(profile)
    assert "/device:TPU:0: XLA Modules=1@[1000,12000] XLA Ops=3@[1000,12000]" in text
    assert "/host:CPU: bench-http=2@[4000,11500]" in text


def test_window_from_the_harness_annotation(profile):
    assert trace_reduce.window_of(profile, "ingest") == (4000, 10000)
    assert trace_reduce.window_of(profile, "absent") is None


def test_op_names_drop_the_instruction_text():
    assert trace_reduce.op_name(
        "%fusion.156 = s32[10240]{0} fusion(s32[8] %p), kind=kLoop"
    ) == "fusion.156"
    assert trace_reduce.op_name("copy.2") == "copy.2"


def test_the_window_annotation_names_no_gap(profile):
    out = trace_reduce.reduce(profile, window_event="ingest")
    assert out["idle_gaps"][0][0] == "bench-http: finalize (7.1% of the gap)"
