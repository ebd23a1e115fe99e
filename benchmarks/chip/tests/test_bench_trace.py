"""The reduction from a profiler trace to busy time, idle share, top device
operations and named idle gaps, on small XSpace traces written here in
the profiler's own text format: one TPU plane with an ``XLA Ops`` line and
one host plane with spans."""
import pytest
from jax.profiler import ProfileData

from smoke import BENCH  # also puts the harness on sys.path

from harness import trace

# Times in picoseconds from the line's start at 1,000 ns: ops run over
# [1000, 3000) and [4000, 5000) ns; the host sleeps over [2500, 4500) ns.
XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "convolution.2" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 1500000 duration_ps: 2000000 }
    %s }
  event_metadata { key: 1 value { id: 1 name: "bench.sleep" } }
  event_metadata { key: 2 value { id: 2 name: "bench.window" } }
}
"""
# the window span, over [500, 4500) ns
WINDOW = "events { metadata_id: 2 offset_ps: -500000 duration_ps: 4000000 }"


def _reduce(tmp_path, text):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return trace.reduce_xspace(str(path))


def test_intervals_merge_and_clip():
    merged = trace._union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert merged == [[0, 3], [5, 9]]
    assert trace._clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]


def test_trace_without_window_span_spans_the_device_ops(tmp_path):
    s = _reduce(tmp_path, XSPACE % "")
    assert s.n_device_ops == 2
    assert s.window_s == pytest.approx(4000e-9)
    assert s.busy_s == pytest.approx(3000e-9)
    assert s.idle_share == pytest.approx(0.25)
    assert s.device_ops == [("fusion.1", pytest.approx(2e-6)),
                            ("convolution.2", pytest.approx(1e-6))]
    # the one gap, [3000, 4000), is named by the host span open in it
    assert s.idle_gaps == [("bench.sleep", pytest.approx(1e-6))]


def test_window_span_clips_busy_time_and_names_edges(tmp_path):
    s = _reduce(tmp_path, XSPACE % WINDOW)
    assert s.window_s == pytest.approx(4000e-9)
    # ops clipped to [500, 4500): [1000, 3000) and [4000, 4500)
    assert s.busy_s == pytest.approx(2500e-9)
    assert s.idle_share == pytest.approx(1 - 2500 / 4000)
    gaps = dict((round(t * 1e9), name) for name, t in s.idle_gaps)
    assert gaps == {500: "host:untraced", 1000: "bench.sleep"}


def test_trace_without_device_ops_reads_nothing(tmp_path):
    host_only = XSPACE.split("planes {", 2)
    text = "planes {" + host_only[2]
    assert _reduce(tmp_path, text % "") is None


def test_trace_recorded_on_a_v5e_chip():
    """A trace that ``tools/record_trace_fixture.py`` recorded on one TPU v5
    lite: four calls of two matrix products, with a host sleep of 20 ms
    inside a ``bench.sleep`` span between the third and the fourth."""
    path = BENCH / "tests" / "fixtures" / "v5e_small.xplane.pb"
    s = trace.reduce_xspace(str(path))
    assert s.n_device_ops == 16
    assert 0 < s.busy_s < s.window_s
    assert "convolution_tanh_fusion" in s.device_ops[0][0]
    name, gap = s.idle_gaps[0]
    assert name == "bench.sleep" and 0.02 <= gap < 0.03
