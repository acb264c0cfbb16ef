"""The reduction from a profiler trace to the per-layer numbers, on a
small trace recorded on one v5e (``data/small.xplane.pb``: the
``syc30.amp`` traffic mix on a 12-qubit circuit of the Sycamore recipe)."""

import os

import pytest

from bench import trace_reduce as tr

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "small.xplane.pb.gz")


def test_union_merges_overlaps():
    assert tr._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


@pytest.mark.parametrize("text,want", [
    ("all-reduce.3", "collective"),
    ("transpose.12", "transpose"),
    ("%copy.897 = f32[2,2,4,2,32]{4,3,2,1,0:T(2,128)S(1)} copy("
     "f32[2,2,4,2,32]{4,1,3,0,2:T(2,128)S(1)} %bitcast_add_fusion.70)",
     "transpose"),
    ("%reshape.65 = f32[4,2,2,2,32]{4,3,2,1,0:T(2,128)S(1)} reshape("
     "f32[1024]{0:T(1024)S(1)} %custom-call.36)", "transpose"),
    ("%copy_bitcast_fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), "
     "kind=kLoop", "transpose"),
    ("%fusion.170 = s32[2,10]{0,1:T(8,128)S(1)} fusion(s32[2]{0:T(128)} "
     "%copy.1), kind=kLoop", "other"),
    ("%vmap_jit_tiled_matmul__.33 = f32[2,8192,8192]{2,1,0:T(8,128)} "
     "custom-call(f32[2,8192,8192]{2,1,0:T(8,128)} %bitcast.482), "
     'custom_call_target="tpu_custom_call"', "matmul"),
    ("%custom-call.36 = f32[1024]{0:T(1024)S(1)} custom-call("
     'c64[1024]{0:T(1024)} %h), custom_call_target="X64SplitHigh"', "other"),
    ("%convolution_add_fusion.2 = f32[8]{0} fusion(f32[8]{0} %a)", "matmul"),
    ("%all-reduce-start.1 = f32[] all-reduce-start(f32[] %x)", "collective"),
    ("%copy-start.49 = (s32[2,10]{0,1:T(8,128)}) copy-start(s32[2] %f)",
     "other"),
])
def test_kinds(text, want):
    assert tr.kind(text) == want


def test_summary_of_the_recorded_trace():
    s = tr.summarize(TRACE)
    assert s["chips"] == 1
    assert 0 < s["busy_s"][0] <= s["window_s"]
    assert sum(s["op_s"].values()) == pytest.approx(s["busy_s"][0], rel=0.2)
    assert 0 < len(s["top_ops"]) <= 10 and len(s["gaps"]) <= 10
    assert all(g[1] > 0 for g in s["gaps"])
    # the numbers this reduction read from the recorded trace
    assert s["window_s"] == pytest.approx(0.302574866, rel=1e-9)
    assert s["busy_s"][0] == pytest.approx(0.003901286, rel=1e-9)
    assert s["op_s"]["transpose"] == pytest.approx(0.001882009, rel=1e-6)
    assert s["op_s"]["matmul"] == pytest.approx(0.000193554, rel=1e-6)
