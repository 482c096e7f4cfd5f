"""The trace reduction on a small synthetic trace (times in ns)."""
import pytest

from chipbench import trace

# two device ops overlap at [30, 40); one straddles the window's end
DEV = [("fusion.1", 10, 40), ("qmatmul.2", 30, 60), ("qdwconv.3", 70, 80),
       ("qmatmul.2", 95, 120)]
HOST = [("traced", 0, 100), ("flush", 60, 70), ("submit", 80, 84),
        ("flush", 84, 95)]
TR = {"device": {"/device:TPU:0": DEV}, "host": HOST}


def test_union_and_busy_time_clip_to_the_window():
    assert trace.union([(5, 9), (1, 3), (2, 4)]) == [(1, 4), (5, 9)]
    # busy: [10, 60) + [70, 80) + [95, 100) = 65 of the window [0, 100)
    assert trace.busy_ns(DEV, 0, 100) == 65


def test_class_time_matches_kernel_names():
    assert trace.class_ns(DEV, {"qmatmul.2"}, 0, 100) == 30 + 5
    assert trace.class_ns(DEV, {"qdwconv.3", "fusion.1"}, 0, 100) == 10 + 30
    assert trace.class_ns(DEV, set(), 0, 100) == 0
    names = {e[0] for e in DEV}
    assert trace.charges(names, {}, {"qmatmul": ("qmatmul",)}) == {
        "qmatmul.2": "qmatmul"}


# a conv through im2col, a depthwise conv, and what follows the last kernel:
# param -> pad -> slice.1, slice.2 -> concatenate -> bitcast -> qmatmul.1
#       -> pad.2 -> qdwconv.1 -> bitcast.2 -> qmatmul.2 -> softmax
# and a constant fed to both classes
GRAPH = {"pad": ["param"], "slice.1": ["pad"], "slice.2": ["pad"],
         "concatenate": ["slice.1", "slice.2"], "bitcast": ["concatenate"],
         "qmatmul.1": ["bitcast", "copy-done", "broadcast"],
         "copy-done": ["copy-start"],
         "pad.2": ["qmatmul.1"], "qdwconv.1": ["pad.2", "broadcast"],
         "bitcast.2": ["qdwconv.1"], "qmatmul.2": ["bitcast.2"],
         "softmax": ["qmatmul.2"]}
CLASSES = {"qmatmul": ("qmatmul",), "qdwconv": ("qdwconv",)}


def test_ops_between_a_kernels_producer_and_the_kernel_are_charged_to_it():
    names = set(GRAPH) | {"param", "copy-start", "broadcast"}
    got = trace.charges(names, GRAPH, CLASSES)
    assert {n for n, c in got.items() if c == "qmatmul"} == {
        "pad", "slice.1", "slice.2", "concatenate", "bitcast", "qmatmul.1",
        "copy-done", "copy-start", "bitcast.2", "qmatmul.2", "param"}
    assert {n for n, c in got.items() if c == "qdwconv"} == {
        "pad.2", "qdwconv.1"}
    # after the last kernel, or feeding two classes: charged to none
    assert "softmax" not in got and "broadcast" not in got


# the shape of a bucket executable's HLO text: a bitcast, which takes no
# device time and so is missing from the trace, joins the im2col to the
# kernel; names inside a fused computation stay inside it
HLO = """
%fused_computation (param_0.2: s8[B,9]) -> s8[B,8] {
  %param_0.2 = s8[B,9]{1,0} parameter(0)
  ROOT %slice.9 = s8[B,8]{1,0} slice(%param_0.2), slice={[0:B], [0:8]}
}

ENTRY %main.2 (x.1: s8[B,4]) -> s8[B,8] {
  %x.1 = s8[B,4]{1,0} parameter(0), metadata={op_name="x"}
  %slice.1 = s8[B,2]{1,0} slice(s8[B,4]{1,0} %x.1), slice={[0:B], [0:2]}
  %slice.2 = s8[B,2]{1,0} slice(s8[B,4]{1,0} %x.1), slice={[0:B], [1:3]}
  %concatenate.1 = s8[B,4]{1,0} concatenate(%slice.1, %slice.2)
  %bitcast.1 = s8[B,4]{1,0} bitcast(%concatenate.1)
  %qmatmul.15 = s8[B,8]{1,0} custom-call(s8[B,4]{1,0} %bitcast.1), custom_call_target="tpu_custom_call"
  ROOT %softmax_fusion = s8[B,8]{1,0} fusion(%qmatmul.15), kind=kLoop, calls=%fused_computation
}
"""
# the same names at batch 1 in another bucket's executable, where slice.1
# feeds a depthwise kernel instead
HLO_1 = """
ENTRY %main.3 (x.1: s8[1,4]) -> s8[1,4] {
  %x.1 = s8[1,4]{1,0} parameter(0)
  %slice.1 = s8[1,4]{1,0} slice(s8[1,4]{1,0} %x.1), slice={[0:1], [0:4]}
  ROOT %qdwconv.1 = s8[1,4]{1,0} custom-call(%slice.1)
}
"""


def test_hlo_text_gives_each_op_its_operands():
    inputs = trace.hlo_inputs([HLO.replace("B", "64"), HLO_1])
    assert inputs["bitcast.1 s8[64,4]"] == ["concatenate.1 s8[64,4]"]
    assert inputs["concatenate.1 s8[64,4]"] == ["slice.1 s8[64,2]",
                                                "slice.2 s8[64,2]"]
    assert inputs["qmatmul.15 s8[64,8]"] == ["bitcast.1 s8[64,4]"]
    assert inputs["qdwconv.1 s8[1,4]"] == ["slice.1 s8[1,4]"]
    # events: the slices, the concatenate and the kernel; no bitcast
    names = {"slice.1 s8[64,2]", "slice.2 s8[64,2]", "concatenate.1 s8[64,4]",
             "qmatmul.15 s8[64,8]", "softmax_fusion s8[64,8]",
             "slice.1 s8[1,4]"}
    got = trace.charges(names, inputs, CLASSES)
    assert got == {n: "qmatmul" for n in names - {"softmax_fusion s8[64,8]",
                                                  "slice.1 s8[1,4]"}} | {
        "slice.1 s8[1,4]": "qdwconv"}
    assert trace.op_key("qmatmul.15", " s8[64,8]{1,0} custom-call(...)") == \
        "qmatmul.15 s8[64,8]"


def test_folding_im2col_into_the_kernel_lowers_the_class_time_it_is_charged():
    # the same conv twice: im2col copies then the kernel, or one kernel
    # that is slower alone but faster in all
    split = {"device": {"/device:TPU:0": [("slice.1", 0, 30),
                                           ("qmatmul.1", 30, 50)]},
             "host": [], "inputs": {"qmatmul.1": ["slice.1"],
                                    "slice.1": ["param"]}}
    fused = {"device": {"/device:TPU:0": [("qmatmul.1", 0, 40)]},
             "host": [], "inputs": {"qmatmul.1": ["param"]}}
    cls = {"qmatmul": ("qmatmul",)}
    t_split = trace.reduce(split, 0, 100, cls)["class_s"]["qmatmul"]
    t_fused = trace.reduce(fused, 0, 100, cls)["class_s"]["qmatmul"]
    assert t_split == pytest.approx(50e-9)
    assert t_fused == pytest.approx(40e-9) and t_fused < t_split


def test_top_ops_sum_per_name():
    top = trace.top_ops(DEV, 0, 100, k=2)
    assert top[0][0] == "qmatmul.2"
    assert top[0][1] == pytest.approx(35e-9)
    assert len(top) == 2


def test_idle_gaps_are_labelled_by_the_covering_host_span():
    gaps = trace.idle_gaps(DEV, HOST, 0, 100)
    # gaps: [0, 10) none, [60, 70) flush, [80, 95) flush 11 vs submit 4
    assert gaps[0] == ["flush", pytest.approx(15e-9)]
    assert ["flush", pytest.approx(10e-9)] in gaps
    assert ["no host span", pytest.approx(10e-9)] in gaps


def test_reduce_averages_chips_and_returns_none_without_device_ops():
    two = {"device": {"/device:TPU:0": DEV, "/device:TPU:1": DEV[:1]},
           "host": HOST}
    red = trace.reduce(two, 0, 100, {"qmatmul": ("qmatmul",)})
    assert red["busy_s"] == pytest.approx((65 + 30) / 2 * 1e-9)
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["class_s"]["qmatmul"] == pytest.approx(35 / 2 * 1e-9)
    assert trace.reduce({"device": {}, "host": []}, 0, 100, {}) is None
