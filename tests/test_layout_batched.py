"""Batched planned-layout execution: every serving bucket lowers through
the same compile-time ``ExecutionPlan`` (graph + folded constants +
``LayoutPlan`` + paging + route flags) as the single-call trace — bit-exact
vs the per-call route and vs stacked batch-1 rows, with the batched-trace
pad-op churn pinned the way ``tests/test_layout.py`` pins the single-call
trace."""
import numpy as np
import pytest

from repro.core import CompiledModel, ExecutionPlan, bucket_floor
from repro.core import graph as G
from repro.core.builder import GraphBuilder
from repro.core.introspect import prim_counts as _prim_counts
from repro.core.quantize import quantize_graph
from repro.configs.paper_models import build_sine, build_speech, build_person


def _mlp(rng):
    """FC chain with non-lane-multiple widths (8/16/12/4) and multi-row
    per-sample inputs (m=2) — exercises the batched row-merge path."""
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


_SPECS = {
    "mlp": (lambda: _mlp(np.random.default_rng(0)),
            lambda rng: rng.normal(size=(2, 8)).astype("f")),
    "sine": (build_sine,
             lambda rng: rng.uniform(0, 2 * np.pi, (1, 1)).astype("f")),
    "speech": (lambda: build_speech(),
               lambda rng: rng.normal(0, 1, (1, 49, 40, 1)).astype("f")),
    "person": (build_person,
               lambda rng: rng.normal(0, 1, (1, 96, 96, 1)).astype("f")),
}


def _quantized(name):
    builder, gen = _SPECS[name]
    rng = np.random.default_rng(7)
    g = builder()
    qg = quantize_graph(g, [gen(rng) for _ in range(2)])
    qp = qg.tensor(qg.inputs[0]).qparams
    xb = np.stack([gen(rng) for _ in range(3)])
    return qg, np.asarray(qp.quantize(xb))


@pytest.mark.parametrize("name", ["mlp", "sine", "speech", "person"])
def test_batched_planned_bit_exact(name):
    """Per-bucket parity for every model (non-lane-multiple channel counts
    included): the planned batched route equals the per-call batched route
    AND stacked batch-1 predict_q rows, for an exact bucket (2) and a
    bucket-padded batch (3 -> bucket 4, staged fused entry pad)."""
    qg, qxb = _quantized(name)
    planned = CompiledModel(qg, use_pallas=True)
    percall = CompiledModel(qg, use_pallas=True, layout_plan=False)
    assert planned.plan is not None and percall.plan is None
    for batch in (2, 3):
        xb = qxb[:batch]
        y_pl = np.asarray(planned.predict_q(xb))
        y_pc = np.asarray(percall.predict_q(xb))
        rows = np.stack([np.asarray(planned.predict_q(xb[i]))
                         for i in range(batch)])
        np.testing.assert_array_equal(y_pl, y_pc)
        np.testing.assert_array_equal(y_pl, rows)


def test_entry_phys_fuses_bucket_and_lane_pad():
    """Graph inputs consumed by planned ops are staged pre-padded: the plan
    records their lane-padded entry layout, the staged pad covers bucket
    fill + lanes in ONE device pad, and the bucket executable's input spec
    is the physical shape."""
    qg, qxb = _quantized("mlp")
    cm = CompiledModel(qg, use_pallas=True)
    tid = qg.inputs[0]
    assert cm.plan.entry_phys == {tid: (2, 128)}
    assert cm.exec_plan.entry_shape(tid) == (2, 128)
    # batch 3 -> bucket 4: one fused pad (batch 3->4, lanes 8->128)
    assert cm._entry_widths(tid, 3) == ((0, 1), (0, 0), (0, 120))
    # per-call model keeps the logical entry
    pc = CompiledModel(qg, use_pallas=True, layout_plan=False)
    assert pc.exec_plan.entry_shape(tid) == (2, 8)


def test_warmup_precompiles_staged_pads():
    """After warmup_batched, no batch size <= max_batch creates a new
    staged-pad executable or bucket at request time (the serving-path
    everything-at-compile-time rule, fused entry pad included)."""
    qg, qxb = _quantized("sine")
    cm = CompiledModel(qg, use_pallas=True)
    cm.warmup_batched(4)
    n_pads, n_buckets = len(cm._stage_pad), len(cm._batched_aot)
    for batch in (1, 2, 3, 4):
        np.asarray(cm.predict_q(qxb[:1].repeat(batch, axis=0)))
    assert len(cm._stage_pad) == n_pads
    assert len(cm._batched_aot) == n_buckets


@pytest.fixture(scope="module")
def person_batched():
    qg, qxb = _quantized("person")
    return qg, CompiledModel(qg, use_pallas=True)


def test_person_batched_trace_pad_ops_pinned(person_batched):
    """The batched person bucket trace keeps only structural pads — SAME
    halo pads, im2col alignment (rows or K off the 128 multiple: conv0
    reads its one real input lane, so its K = 9 rounds up to 128), and the
    final FC's row alignment;
    entry pads are fused into the staged device pad, so interior
    Pallas->Pallas edges carry the padded block untouched. The per-call
    batched route (what every serving flush paid before the shared
    ExecutionPlan) pays ~7x more pad ops on the same bucket."""
    qg, cm = person_batched
    B = 4
    ep = cm.exec_plan
    planned = _prim_counts(ep.lower(batched=True),
                           *ep.batched_input_specs(B))
    percall_ep = ExecutionPlan(qg, cm.folded, None, {}, True)
    assert percall_ep.batched_input_specs(B)[0].shape == (B, 1, 96, 96, 1)
    percall = _prim_counts(percall_ep.lower(batched=True),
                           *percall_ep.batched_input_specs(B))

    same_halo = sum(1 for op in qg.ops
                    if op.op in (G.CONV_2D, G.DEPTHWISE_CONV_2D)
                    and op.attrs["padding"] == "SAME"
                    and qg.tensor(op.inputs[1]).shape[0] > 1)
    im2col_pads = sum(
        1 for i, op in enumerate(qg.ops) if op.op == G.CONV_2D
        and ((B * np.prod(qg.tensor(op.outputs[0]).shape[:3])) % 128
             or np.prod(qg.tensor(op.inputs[1]).shape[:2])
             * cm.plan.layouts[i].in_lanes % 128))
    fc_row_pads = sum(1 for i, op in enumerate(qg.ops)
                      if op.op == G.FULLY_CONNECTED and i in cm.plan.layouts)
    assert planned.get("pad", 0) == same_halo + im2col_pads + fc_row_pads, \
        planned
    # the per-call batched route re-padded every layer's operands — the
    # same ~7x churn the single-call plan removed, now per served bucket
    assert percall.get("pad", 0) >= 7 * planned.get("pad", 0)
    assert planned.get("slice", 0) < percall.get("slice", 0)


def test_batched_fc_full_bucket_has_zero_row_pads():
    """When B*m is a lane multiple the planned batched FC chain needs NO
    trace-time pads at all: entry is staged outside, rows align exactly."""
    qg, _ = _quantized("sine")
    cm = CompiledModel(qg, use_pallas=True)
    ep = cm.exec_plan
    counts = _prim_counts(ep.lower(batched=True),
                          *ep.batched_input_specs(128))
    assert counts.get("pad", 0) == 0, counts


def test_predict_q_many_splits_on_bucket_boundaries():
    """A non-power-of-two max_batch chunks by its bucket floor: max_batch=6
    drains as exact 4-buckets (never padding every chunk up to 8); only the
    final partial chunk pads, to its own smaller bucket."""
    assert [bucket_floor(b) for b in (1, 2, 3, 4, 5, 6, 7, 8, 9)] == \
        [1, 2, 2, 4, 4, 4, 4, 8, 8]
    qg, _ = _quantized("sine")
    qp = qg.tensor(qg.inputs[0]).qparams
    rng = np.random.default_rng(3)
    x = rng.uniform(0, 2 * np.pi, (20, 1, 1)).astype("f")
    qx = np.asarray(qp.quantize(x))
    cm = CompiledModel(qg)
    # the serving-flush case: a full max_batch=6 drain splits 4+2 exact —
    # the 8-bucket is never compiled, no flush pads past its bucket
    y6 = np.asarray(cm.predict_q_many(qx[:6], max_batch=6))
    assert cm.bucket_sizes() == (2, 4)
    rows6 = np.stack([np.asarray(cm.predict_q(qx[i])) for i in range(6)])
    np.testing.assert_array_equal(y6, rows6.reshape(y6.shape))
    y = np.asarray(cm.predict_q_many(qx, max_batch=6))
    # 20 rows: five exact 4-row chunks, still only the {2, 4} buckets
    assert cm.bucket_sizes() == (2, 4)
    rows = np.stack([np.asarray(cm.predict_q(qx[i])) for i in range(20)])
    np.testing.assert_array_equal(y, rows.reshape(y.shape))
    # 21 rows: tail chunk of 1 goes through its own bucket
    qx21 = np.concatenate([qx, qx[:1]])
    y21 = np.asarray(cm.predict_q_many(qx21, max_batch=6))
    assert cm.bucket_sizes() == (1, 2, 4)
    np.testing.assert_array_equal(y21[:20], y)


def test_pad_budget_reproduces_batched_person_pins(person_batched):
    """Auditor-derived pad budgets for the batched person buckets equal
    the traced pad counts for every served bucket — including the b=1 vs
    b>=2 split the hand-derived formula above only pins at one bucket.
    b=1: 14 SAME halos + 13 im2col pads (conv0's K of 9 -> 128, and the 12
    pointwise convs whose 576/144/36/9 rows miss 128) + 1 FC row pad = 28;
    b>=2: the rows of pw1 and pw2 (2 x 576) align, leaving 11 im2col pads
    and 26 in all."""
    from repro.analysis import measured_pads, pad_budget
    qg, cm = person_batched
    ep = cm.exec_plan
    for bucket in (1, 2, 4):
        budget = pad_budget(ep, batched=True, bucket=bucket)
        assert budget.enforceable and not budget.missed
        assert budget.total == measured_pads(ep, batched=True,
                                             bucket=bucket), \
            (bucket, budget.items)
    assert pad_budget(ep, batched=True, bucket=1).total == 28
    assert pad_budget(ep, batched=True, bucket=4).total == 26
