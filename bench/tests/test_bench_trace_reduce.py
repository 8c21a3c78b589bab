"""The trace reducer: on hand-made intervals, on a small trace laid out as
a TPU's (a ``/device:TPU:0`` plane with an ``XLA Ops`` line and the
profile's start), and on a trace recorded on a TPU v5e
(``fixtures/darknet19_v5e_window.*``)."""
import collections
import json
import os
import types

import pytest

from bench import cells
from bench import trace_reduce as T


def test_union_merges_overlaps():
    assert T._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_gap_attribution_by_innermost_span():
    into = collections.defaultdict(float)
    spans = [(0, 100, "bench.tick"), (40, 60, "bench.submit")]
    T._attribute([(10, 50), (90, 120)], spans, into)
    assert into == {"bench.tick": 30 + 10, "bench.submit": 10, "other": 20}


def test_op_names_lose_their_suffix():
    assert T.op_name("fq_conv2d.12") == "fq_conv2d"
    assert T.op_name("fusion") == "fusion"


def test_op_names_from_the_tpu_instruction_text():
    # as a v5e's "XLA Ops" line names its events (jax 0.9, libtpu 0.0.34)
    assert T.op_name(
        "%fq_conv2d.17 = s8[32,798,64]{2,1,0:T(8,128)(4,1)S(1)} custom-call("
        "f32[1,1]{1,0:T(1,128)} %constant.30), custom_call_target="
        '"tpu_custom_call"') == "fq_conv2d"
    assert T.op_name("%fusion.12 = f32[8,224,224,32]{3,0,2,1:T(8,128)} "
                     "fusion(f32[8,224,224,3] %copy.45), kind=kOutput"
                     ) == "fusion"


# times in ps from each line's timestamp_ns (1000 ns after the profile's
# start at BASE): ops 2-4 us and 5-6 us; the harness's window is 1-11 us
# and its one bench.tick span 1.5-4.5 us, in wall-clock ns
BASE = 1_700_000_000_000_000_000
WINDOW = (BASE + 1000, BASE + 11000)
SPANS = [(BASE + 1500, BASE + 4500, "bench.tick")]
TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines {
    id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 4000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fq_conv2d.3" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.1" } }
}
planes {
  id: 2 name: "Task Environment"
  stats { metadata_id: 1 uint64_value: %d }
  stat_metadata { key: 1 value { id: 1 name: "profile_start_time" } }
}
""" % BASE

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "darknet19_v5e_window")


def _pd(text=TRACE):
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(text)


def test_reduce_small_trace():
    r = T.reduce_profile(_pd(), WINDOW, SPANS)
    assert r["window_s"] == pytest.approx(10e-6)
    assert r["busy_s"] == pytest.approx(3e-6)
    assert r["op_s"] == pytest.approx({"fq_conv2d": 2e-6, "fusion": 1e-6})
    assert r["idle_s"] == pytest.approx({"bench.tick": 1e-6, "other": 6e-6})
    assert T.top(r["op_s"], 1) == [["fq_conv2d", pytest.approx(2e-6)]]


def test_reduce_needs_the_window_and_a_device():
    no_start = TRACE.replace('"profile_start_time"', '"other_stat"')
    with pytest.raises(ValueError, match="profile_start_time"):
        T.reduce_profile(_pd(no_start), WINDOW, SPANS)
    no_tpu = TRACE.replace('"/device:TPU:0"', '"/device:CPU:0"')
    with pytest.raises(ValueError, match="TPU"):
        T.reduce_profile(_pd(no_tpu), WINDOW, SPANS)
    # a window on another clock holds none of the ops
    with pytest.raises(ValueError, match="no device operation"):
        T.reduce_profile(_pd(), (1000, 11000), SPANS)


def test_reduce_refuses_a_device_without_ops():
    no_line = TRACE.replace('"XLA Ops"', '"XLA Modules"')
    with pytest.raises(ValueError, match="XLA Ops"):
        T.reduce_profile(_pd(no_line), WINDOW, SPANS)
    # both ops start after the window closes at 11 us
    late = TRACE.replace("offset_ps: 1000000 duration_ps: 2000000",
                         "offset_ps: 20000000 duration_ps: 2000000").replace(
                         "offset_ps: 4000000 duration_ps: 1000000",
                         "offset_ps: 30000000 duration_ps: 1000000")
    with pytest.raises(ValueError, match="no device operation"):
        T.reduce_profile(_pd(late), WINDOW, SPANS)


def _fixture():
    with open(FIXTURE + ".json") as f:
        meta = json.load(f)
    r = T.reduce(FIXTURE + ".xplane.pb", tuple(meta["window_ns"]),
                 [tuple(s) for s in meta["spans"]])
    return meta, r


def test_reduce_chip_recorded_trace():
    """Two flushes of 8 DarkNet-19 requests, traced on a TPU v5e."""
    meta, r = _fixture()
    assert r["window_s"] == pytest.approx(0.055974897)
    assert r["busy_s"] == pytest.approx(0.006599737)
    assert all("%" not in k and " " not in k for k in r["op_s"])
    assert r["op_s"]["fq_conv2d"] == pytest.approx(0.000950467)
    assert r["op_s"]["fusion"] == pytest.approx(0.004519255)
    assert "pad" in r["op_s"] and "quantize_codes" in r["op_s"]
    assert sum(r["idle_s"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])
    assert set(r["idle_s"]) <= {"bench.tick", "bench.submit",
                                "bench.client", "other"}


def test_roofline_on_the_chip_recorded_trace():
    """The fused conv's roofline share on the recorded flushes: every
    fq_conv2d call's least time over its device time, below 100%."""
    meta, r = _fixture()
    run = types.SimpleNamespace(
        cell=cells.find_cell("darknet19-saturate"), trace=r,
        peaks=cells.peaks("TPU v5 lite"),
        window=types.SimpleNamespace(flush_slots=meta["flush_slots"]))
    share = cells.metric_reader("fq_conv2d_roofline.saturate")(run)
    assert 20.0 < share < 30.0
