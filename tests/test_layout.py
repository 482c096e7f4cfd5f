"""Graph-level padded-layout planning: interior pad/slice churn is gone
(verified on the jaxpr), weights/consts are pre-padded at compile time, and
the planned engine stays bit-exact on the paper's flagship conv workload
(person detection) end to end."""
import numpy as np
import pytest
import jax

from repro.core import CompiledModel, Interpreter, build_graph_fn
from repro.core import graph as G
from repro.core.builder import GraphBuilder
from repro.core.introspect import prim_counts as _prim_counts
from repro.core.preprocess import plan_layout, preprocess_graph
from repro.core.quantize import quantize_graph
from repro.configs.paper_models import build_person


def _mlp(rng):
    b = GraphBuilder("mlp")
    x = b.input("x", (2, 8))
    h = b.fully_connected(x, rng.normal(0, 0.5, (8, 16)).astype("f"),
                          rng.normal(size=16).astype("f"), fused="RELU")
    h = b.fully_connected(h, rng.normal(0, 0.5, (16, 12)).astype("f"),
                          rng.normal(size=12).astype("f"), fused="RELU")
    h = b.fully_connected(h, rng.normal(0, 0.5, (12, 4)).astype("f"), None)
    h = b.softmax(h)
    b.output(h)
    return b.build()


def test_planned_fc_chain_has_no_interior_pad_slice():
    """Three chained Pallas FC layers: ONE pad at graph entry, ONE slice at
    the non-Pallas boundary (softmax) — zero layout churn in between."""
    rng = np.random.default_rng(0)
    qg = quantize_graph(_mlp(rng), [rng.normal(size=(2, 8)).astype("f")
                                    for _ in range(4)])
    cm = CompiledModel(qg, use_pallas=True)
    spec = jax.ShapeDtypeStruct((2, 8), np.int8)
    planned = _prim_counts(
        build_graph_fn(qg, cm.folded, use_pallas=True, plan=cm.plan), spec)
    percall = _prim_counts(
        build_graph_fn(qg, cm.folded, use_pallas=True, plan=None), spec)
    assert planned.get("pad", 0) == 1, planned
    assert planned.get("slice", 0) == 1, planned
    assert planned.get("dynamic_slice", 0) == 0
    # and the per-call route really was paying the layout tax
    assert percall.get("pad", 0) > 3 * planned.get("pad", 0)


def test_plan_pre_pads_weights_and_consts_on_host():
    rng = np.random.default_rng(1)
    qg = quantize_graph(_mlp(rng), [rng.normal(size=(2, 8)).astype("f")
                                    for _ in range(4)])
    plan = plan_layout(qg, preprocess_graph(qg))
    assert set(plan.layouts) == {0, 1, 2}
    lay = plan.layouts[0]  # FC (8, 16) -> physical (128, 128)
    assert lay.kind == "fc" and lay.w_phys.shape == (128, 128)
    assert lay.w_phys.dtype == np.int8
    assert not lay.w_phys[8:, :].any() and not lay.w_phys[:, 16:].any()
    for c in lay.consts:
        assert c.shape == (128,) and not np.asarray(c[16:]).any()
    # every planned activation records its physical (padded) shape
    assert plan.phys[qg.ops[0].outputs[0]] == (128, 128)


@pytest.fixture(scope="module")
def person_q():
    rng = np.random.default_rng(2)
    g = build_person()
    qg = quantize_graph(g, [rng.normal(0, 1, (1, 96, 96, 1)).astype("f")
                            for _ in range(2)])
    x = rng.normal(0, 1, (1, 96, 96, 1)).astype("f")
    qx = np.asarray(qg.tensor(qg.inputs[0]).qparams.quantize(x))
    return qg, qx


def test_person_planned_pallas_bit_exact(person_q):
    """End-to-end padded layout on the person model: every conv/dw/fc layer
    runs the Pallas route in planned layout, output equals the interpreter
    bit for bit."""
    qg, qx = person_q
    cm = CompiledModel(qg, use_pallas=True)
    # the whole MobileNet body is pallas-routed: conv0 + 13x(dw+pw) + fc
    assert len(cm.plan.layouts) == 28
    ref = np.asarray(Interpreter(qg).invoke_q(qx))
    out = np.asarray(cm.predict_q(qx))
    np.testing.assert_array_equal(ref, out)


def test_person_plan_kills_interior_layout_churn(person_q):
    """Layer trace of the person model: no pad/slice between consecutive
    Pallas-routed layers. Remaining pads are structural — ONE entry pad
    (the final FC's rows, after the reshape; conv0 reads its one real
    input lane), one SAME halo pad per spatially-padded conv, and the
    im2col alignment of patch matrices whose rows or K miss 128."""
    qg, qx = person_q
    cm = CompiledModel(qg, use_pallas=True)
    spec = jax.ShapeDtypeStruct((1, 96, 96, 1), np.int8)
    planned = _prim_counts(
        build_graph_fn(qg, cm.folded, use_pallas=True, plan=cm.plan), spec)
    percall = _prim_counts(
        build_graph_fn(qg, cm.folded, use_pallas=True, plan=None), spec)
    same_halo = sum(1 for op in qg.ops
                    if op.op in (G.CONV_2D, G.DEPTHWISE_CONV_2D)
                    and op.attrs["padding"] == "SAME"
                    and qg.tensor(op.inputs[1]).shape[0] > 1)
    layouts = cm.plan.layouts
    im2col_pads = sum(
        1 for i, op in enumerate(qg.ops) if op.op == G.CONV_2D
        and (np.prod(qg.tensor(op.outputs[0]).shape[:3]) % 128
             or np.prod(qg.tensor(op.inputs[1]).shape[:2])
             * layouts[i].in_lanes % 128))
    producer = {op.outputs[0]: i for i, op in enumerate(qg.ops)}

    def arrives_planned(i):  # logical input already in the planned shape
        shape, lay = tuple(qg.tensor(qg.ops[i].inputs[0]).shape), layouts[i]
        if lay.kind == "fc":
            return shape == (lay.out_shape[0], lay.in_lanes)
        return shape[-1] == lay.in_lanes

    entry_pads = sum(  # pallas op fed by graph entry or a non-pallas op
        1 for i in layouts
        if producer.get(qg.ops[i].inputs[0]) not in layouts
        and not arrives_planned(i))
    assert layouts[0].in_lanes == 1  # conv0 reads the graph input as is
    assert entry_pads == 1  # the final FC (after reshape)
    # entry pads + geometric halo pads + im2col alignment —
    # NOTHING between consecutive pallas layers.
    assert planned.get("pad", 0) == entry_pads + same_halo + im2col_pads, \
        planned
    # the per-call route additionally re-padded every layer's operands
    assert percall.get("pad", 0) > 4 * planned.get("pad", 0)
    assert planned.get("slice", 0) < percall.get("slice", 0)


def test_mixed_boundaries_pallas_paged_batched():
    """Non-Pallas consumers (paged FC) of planned producers get logical
    slices; the batched route (no plan) stays row-identical."""
    rng = np.random.default_rng(5)
    qg = quantize_graph(_mlp(rng), [rng.normal(size=(2, 8)).astype("f")
                                    for _ in range(4)])
    ref = Interpreter(qg)
    x = rng.normal(size=(2, 8)).astype("f")
    mixed = CompiledModel(qg, use_pallas=True, paged={1: 4})
    assert set(mixed.plan.layouts) == {0, 2}  # op 1 routed paged, unplanned
    np.testing.assert_array_equal(np.asarray(ref.invoke(x)),
                                  np.asarray(mixed.predict(x)))
    cm = CompiledModel(qg, use_pallas=True)
    xb = rng.normal(size=(5, 2, 8)).astype("f")
    yb = np.asarray(cm.predict(xb))
    for i in range(5):
        np.testing.assert_array_equal(yb[i], np.asarray(cm.predict(xb[i])))


def test_pad_budget_reproduces_person_pin(person_q):
    """The plan auditor derives the same structural pad count the test
    above pins by hand (entry pads + SAME halos + im2col alignment) — the
    hand-derived formula now has a single authoritative derivation in
    ``repro.analysis.budget`` that the traced jaxpr must match exactly."""
    from repro.analysis import measured_pads, pad_budget
    qg, _ = person_q
    cm = CompiledModel(qg, use_pallas=True)
    budget = pad_budget(cm.exec_plan)
    assert budget.enforceable and not budget.missed
    assert budget.total == measured_pads(cm.exec_plan) == 28
