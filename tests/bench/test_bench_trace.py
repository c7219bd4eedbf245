"""trace_reduce on a small trace recorded on a TPU v5e
(tests/bench/record_trace.py): three steps of a Pallas matmul and an XLA
tanh, with a 20 ms host sleep (bench.sample) after each."""

import pytest
from benchlib import CHIP  # noqa: F401

import harness
import trace_reduce
import work
from benchlib import ROOT

TRACE = ROOT / "tests" / "bench" / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce_trace(str(TRACE))


def test_busy_is_the_union_of_device_ops(reduced):
    # three steps, each a 256x512x256 matmul (~6.2 us) and a tanh (~1 us)
    assert reduced["chips"] == 1
    assert 18e-6 < reduced["busy_s"] < 25e-6
    assert reduced["window_s"] == pytest.approx(0.0649, rel=0.01)
    idle = 1 - reduced["busy_s"] / reduced["window_s"]
    assert 0.99 < idle < 1.0


def test_pallas_events_are_picked_out(reduced):
    (row,) = reduced["pallas"]
    assert row["kind"] == "dense" and row["count"] == 3
    assert row["operands"] == [[256, 256], [256, 512]]
    assert (row["flops"], row["bytes"]) == work.dense(256, 512, 256)
    assert reduced["ops_top"][0][0] == "pallas dense 256x256 256x512"
    assert all(not name.startswith("while") for name, _ in reduced["ops_top"])


def test_gaps_are_named_by_the_host_span_over_them(reduced):
    longest = reduced["gaps_top"][:3]
    assert [name for name, _ in longest] == ["bench.sample"] * 3
    assert all(0.015 < s < 0.03 for _, s in longest)
    assert all(s >= 1e-6 for _, s in reduced["gaps_top"])


def test_device_clock_is_shifted_onto_the_host_clock(reduced):
    # the device ran a millisecond or two behind; every op lies inside
    # the window once shifted
    assert 0.001 < reduced["clock_shift_s"] < 0.004
    assert reduced["pallas"][0]["count"] == 3


def test_no_share_exceeds_one_hundred_percent(reduced):
    obs = {"job": "prefill", "trace": reduced,
           "peak": harness.peaks()["TPU v5 lite"]}
    roof = harness.pallas_roofline(obs)
    assert 0 < roof <= 100
    assert 0 <= harness.idle_share(obs) <= 100


@pytest.mark.parametrize("text, want", [
    ("%fn.44 = f32[8192,2560]{1,0:T(8,128)} custom-call(f32[8192,2560]{1,0:T(8,128)}"
     " %bitcast.102, f32[2560,2560]{1,0:T(8,128)S(1)} %convert_bitcast_fusion.19),"
     ' custom_call_target="tpu_custom_call", operand_layout_constraints={f32[8192,'
     "2560]{1,0}, f32[2560,2560]{1,0}}",
     ("custom-call", (8192, 2560), [(8192, 2560), (2560, 2560)], True)),
    ("%tanh.1 = f32[256,512]{1,0:T(8,128)} tanh(f32[256,512]{1,0:T(8,128)S(1)} %x)",
     ("tanh", (256, 512), [(256, 512)], False)),
])
def test_parse_op(text, want):
    op = trace_reduce.parse_op(text)
    assert (op["opcode"], op["result"], op["operands"], op["pallas"]) == want
