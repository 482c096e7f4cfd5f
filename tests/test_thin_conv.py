"""Thin-input convolutions planned at their real channel width.

A k×k conv whose input arrives in logical layout (a graph input) is planned
with ``in_lanes == Cin``: its taps are flattened at the real width and only
the im2col contraction K is rounded up to 128, so the graph input is staged
unpadded. Convs fed by a planned producer, and 1×1 convs, keep the
lane-padded layout. Every route stays bit-identical: the planned route, the
per-call route and the interpreter, single-call and through the served
``staged_infer`` path with partial buckets.
"""
import numpy as np
import pytest

from repro.analysis import conv_contractions
from repro.core import CompiledModel, Interpreter
from repro.core.builder import GraphBuilder
from repro.core.ops_ref import round_up
from repro.core.quantize import quantize_graph

#: name -> (input H, W, Cin, entry kernel (kh, kw), stride)
CASES = {
    "3x3s2_cin1": (13, 11, 1, (3, 3), 2),
    "3x3s2_cin3": (13, 11, 3, (3, 3), 2),
    "10x8s2_cin1": (21, 16, 1, (10, 8), 2),
}


def _entry_conv_net(h, w, cin, kernel, stride, rng):
    """Thin entry conv (SAME) -> 1x1 conv -> 3x3 conv fed by a planned op."""
    b = GraphBuilder("thin_entry")
    x = b.input("x", (1, h, w, cin))
    y = b.conv2d(x, rng.normal(0, .4, kernel + (cin, 8)).astype("f"),
                 rng.normal(0, .1, 8).astype("f"), stride=(stride, stride),
                 padding="SAME", fused="RELU6", name="entry")
    y = b.conv2d(y, rng.normal(0, .4, (1, 1, 8, 16)).astype("f"),
                 rng.normal(0, .1, 16).astype("f"), fused="RELU6", name="pw")
    y = b.conv2d(y, rng.normal(0, .3, (3, 3, 16, 8)).astype("f"),
                 rng.normal(0, .1, 8).astype("f"), padding="SAME",
                 fused="RELU6", name="inner")
    b.output(y)
    return b.build()


def _quantized(g, shape, rng):
    # inputs in [0, 2): an asymmetric range, so the input zero point is not 0
    # and the SAME border carries z_X
    qg = quantize_graph(g, [rng.uniform(0, 2, shape).astype("f")
                            for _ in range(2)])
    qp = qg.tensor(qg.inputs[0]).qparams
    assert int(np.asarray(qp.zero_point)) != 0
    xb = rng.uniform(0, 2, (5,) + shape).astype("f")
    return qg, np.asarray(qp.quantize(xb))


@pytest.fixture(scope="module", params=list(CASES))
def case(request):
    h, w, cin, kernel, stride = CASES[request.param]
    rng = np.random.default_rng(11)
    g = _entry_conv_net(h, w, cin, kernel, stride, rng)
    qg, qxb = _quantized(g, (1, h, w, cin), rng)
    return (qg, qxb, cin, kernel,
            CompiledModel(qg, use_pallas=True),
            CompiledModel(qg, use_pallas=True, layout_plan=False))


def test_thin_entry_conv_is_planned_at_its_real_width(case):
    qg, _, cin, (kh, kw), planned, _ = case
    plan, tid = planned.plan, qg.inputs[0]
    lay = plan.layouts[0]
    k = kh * kw * cin
    assert lay.in_lanes == cin
    assert lay.w_phys.shape == (round_up(k, 128), 128)
    # tap-major / channel-minor rows, zero K and N padding
    f = np.asarray(qg.tensor(qg.ops[0].inputs[1]).data)
    np.testing.assert_array_equal(lay.w_phys[:k, :8], f.reshape(k, 8))
    assert not lay.w_phys[k:].any() and not lay.w_phys[:, 8:].any()
    # the graph input is staged in its logical layout
    assert tid not in plan.entry_phys
    assert planned.exec_plan.entry_shape(tid) == qg.tensor(tid).shape
    # the 1x1 conv and the 3x3 conv fed by a planned op keep 128 lanes
    assert plan.layouts[1].in_lanes == 128
    assert plan.layouts[2].in_lanes == 128
    assert conv_contractions(planned.exec_plan) == [
        {"op": 0, "k_true": k, "k_phys": round_up(k, 128), "in_lanes": cin},
        {"op": 1, "k_true": 8, "k_phys": 128, "in_lanes": 128},
        {"op": 2, "k_true": 144, "k_phys": 9 * 128, "in_lanes": 128}]


def test_thin_entry_conv_bit_identical_per_call(case):
    qg, qxb, _, _, planned, percall = case
    ref = Interpreter(qg)
    for x in qxb[:2]:
        y_ref = np.asarray(ref.invoke_q(x))
        np.testing.assert_array_equal(np.asarray(planned.predict_q(x)), y_ref)
        np.testing.assert_array_equal(np.asarray(percall.predict_q(x)), y_ref)


@pytest.mark.parametrize("rows", [1, 3, 5])
def test_thin_entry_conv_bit_identical_staged(case, rows):
    """Served path: rows staged unpadded into partial buckets (1 -> 1,
    3 -> 4, 5 -> 8) on both routes, against the interpreter row by row."""
    qg, qxb, _, _, planned, percall = case
    ref = Interpreter(qg)
    want = np.stack([np.asarray(ref.invoke_q(x)) for x in qxb[:rows]])
    batch = list(qxb[:rows])
    np.testing.assert_array_equal(np.asarray(planned.staged_infer(batch)),
                                  want)
    np.testing.assert_array_equal(np.asarray(percall.staged_infer(batch)),
                                  want)


def test_entry_stays_logical_when_consumers_disagree():
    """A graph input read by a thin 3x3 conv (Cin lanes) and by a depthwise
    conv (128 lanes) is staged logical; each consumer pads its own view."""
    rng = np.random.default_rng(12)
    b = GraphBuilder("two_readers")
    x = b.input("x", (1, 9, 9, 3))
    b.output(b.conv2d(x, rng.normal(0, .4, (3, 3, 3, 4)).astype("f"),
                      rng.normal(0, .1, 4).astype("f"), name="conv"))
    b.output(b.depthwise_conv2d(x, rng.normal(0, .4, (3, 3, 3, 1)).astype("f"),
                                rng.normal(0, .1, 3).astype("f"), name="dw"))
    qg, qxb = _quantized(b.build(), (1, 9, 9, 3), rng)
    cm = CompiledModel(qg, use_pallas=True)
    assert [cm.plan.layouts[i].in_lanes for i in (0, 1)] == [3, 128]
    assert cm.plan.entry_phys == {}
    ref = Interpreter(qg)
    got = cm.staged_infer(list(qxb[:3]))
    for r, x in enumerate(qxb[:3]):
        for y, y_ref in zip(got, ref.invoke_q(x)):
            np.testing.assert_array_equal(np.asarray(y)[r], np.asarray(y_ref))
        for y, y_ref in zip(cm.predict_q(x), ref.invoke_q(x)):
            np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))
