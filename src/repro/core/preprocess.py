"""Compile-time pre-processing — the *parser* half of each operator (Sec. 3.3.3).

For every weighted operator, the four constant terms of Eqs. (4), (7), (10)
are computed here, once, on the host, and baked into the compiled executable.
The runtime kernel (ops_ref / kernels) then only computes the input-dependent
terms. This is the paper's central compiler-based optimization.

:func:`plan_layout` extends the same principle to TPU tiling: one walk over
the graph at compile time assigns every Pallas-routed op a lane-padded
physical layout — weights and per-channel constants are pre-padded here, on
the host, and activations stay in padded layout across consecutive
Pallas-routed layers (padding only at graph entry, slicing only at graph
outputs and non-Pallas boundaries). Without the plan, every kernel call
pays a pad→slice round trip on its operands.

The plan is **batch-aware**: a leading batch dimension is layout-neutral,
so the same :class:`OpLayout` objects (same pre-padded weights and folded
constants, computed once on the host) drive both the single-call trace and
every batched bucket executable — buckets never re-plan. ``entry_phys``
records the lane-padded physical shape of each graph input consumed by a
planned op, which lets the batched engine fuse the bucket zero-fill pad and
the layout entry pad into one staged device pad outside the trace.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from . import graph as G
from . import registry
from .ops_ref import (FoldedConsts, MXU_LANES, clamp_bounds,
                      requant_consts, round_up)


def _scalar_or_channel(qp: G.QParams):
    return qp.scale, qp.zero_point


def fold_weighted_op(g: G.Graph, op: G.OpNode) -> FoldedConsts:
    """Compute the constant terms for FC / Conv2D / DepthwiseConv2D."""
    x_t = g.tensor(op.inputs[0])
    w_t = g.tensor(op.inputs[1])
    b_t = g.tensor(op.inputs[2]) if len(op.inputs) > 2 and op.inputs[2] >= 0 else None
    y_t = g.tensor(op.outputs[0])

    s_x, z_x = _scalar_or_channel(x_t.qparams)
    s_w, z_w = _scalar_or_channel(w_t.qparams)
    s_y, z_y = _scalar_or_channel(y_t.qparams)

    # ΣW (Eq. 4/7/10, third term) and the n·z_X·z_W count come from the
    # registry's per-op weight-reduction spec — FC sums the contraction dim,
    # convs the kh/kw/cin taps, depthwise the kh/kw taps per channel.
    desc = registry.get(op.op)
    if desc.w_sum_axes is None:
        raise ValueError(f"{op.op} has no folded form")
    w = w_t.data.astype(np.int64)
    sum_w = w.sum(axis=desc.w_sum_axes)
    count = int(np.prod([w.shape[a] for a in desc.w_count_axes]))

    if b_t is not None:
        s_b, z_b = _scalar_or_channel(b_t.qparams)
        bias_term, rescale = requant_consts(s_x, s_w, s_y, z_y, b_t.data,
                                            s_b, z_b)
    else:
        bias_term, rescale = requant_consts(s_x, s_w, s_y, z_y)
    w_sum_zx = (np.asarray(z_x, np.int64) * sum_w).astype(np.int32)
    const_off = (count * np.asarray(z_x, np.int64) * z_w).astype(np.int32)

    return FoldedConsts(
        bias_term=bias_term,
        rescale=rescale,
        w_sum_zx=w_sum_zx,
        const_off=const_off,
        z_w=np.asarray(z_w, np.int32),
        z_y=np.asarray(z_y, np.int32),
        s_y=np.asarray(s_y, np.float32),
        z_x=np.asarray(z_x, np.int32),
    )


def preprocess_graph(g: G.Graph) -> dict:
    """op index -> FoldedConsts, for every quantized weighted op."""
    folded = {}
    for i, op in enumerate(g.ops):
        if registry.get(op.op).w_sum_axes is not None:
            if g.tensor(op.inputs[0]).dtype == "int8":
                folded[i] = fold_weighted_op(g, op)
    return folded


# ---------------------------------------------------------------------------
# Graph-level padded-layout planning
# ---------------------------------------------------------------------------

def _grow_const(v, n: int, n_pad: int, dtype) -> np.ndarray:
    """Broadcast a scalar/per-channel folded constant to ``n`` channels and
    zero-pad to the planned lane width — on the host, once."""
    out = np.zeros(n_pad, dtype)
    out[:n] = np.broadcast_to(np.asarray(v, dtype).reshape(-1), (n,))
    return out


@dataclasses.dataclass(frozen=True)
class OpLayout:
    """Compile-time physical layout of one Pallas-routed op.

    ``w_phys``/``consts`` are the kernel-ready, lane-padded weights and
    folded Eq. (4)/(7)/(10) constants, padded HERE on the host instead of
    inside every traced call. ``in_lanes``/``out_shape`` describe the padded
    activation layout the op consumes/produces; ``n_true`` is the logical
    channel count (the kernels zero everything beyond it, which is what
    makes chained padded layers exact). A k×k conv whose input arrives
    logical (graph input, unplanned producer) reads it at its real channel
    width (``in_lanes == c_true``) and only its im2col K is rounded up.
    """

    kind: str            # "fc" | "conv" | "dwconv"
    w_phys: np.ndarray   # fc, conv: (K', N') (conv K = kh*kw*in_lanes); dw: (kh, kw, C')
    consts: tuple        # 5 × (N',) per-channel folded constants
    lo: float            # fused-activation clamp bounds (static)
    hi: float
    n_true: int          # logical output channels / FC columns
    in_lanes: int        # physical lane width expected on the activation input
    out_shape: tuple     # physical (padded) output shape
    c_true: int          # logical input channels (border-fill mask for conv)
    z_x: int             # input zero point (SAME border fill)


@dataclasses.dataclass(frozen=True)
class LayoutPlan:
    """op index -> OpLayout, plus tensor id -> physical shape for every
    activation stored in padded layout (all others stay logical).

    ``phys`` describes the single-call trace (FC activations additionally
    keep their MXU row padding between ops). ``entry_phys`` maps graph-input
    tensor ids to their lane-padded per-sample physical shape whenever the
    planned Pallas ops consuming them read them wider than logical — the
    batched engine stages those inputs pre-padded (one fused device pad
    covers bucket fill + entry lanes), so the batched trace contains no
    entry lane pads at all."""

    layouts: dict
    phys: dict
    entry_phys: dict = dataclasses.field(default_factory=dict)


def plan_layout(g: G.Graph, folded: dict, paged=None) -> LayoutPlan:
    """One compile-time walk assigning lane-padded physical layouts.

    An op is planned iff it would take the Pallas route in the compiled
    engine (quantized + folded + a registered ``lower_pallas`` + not paged
    — paging wins, exactly as in ``registry.run_compiled``). Exactness of
    the padded layouts rests on two invariants: (a) planned kernels zero
    their padding lanes, so a downstream contraction's K-padding contributes
    nothing to Σ X W or Σ X; (b) SAME borders carry z_X only on real lanes.
    """
    paged = paged or {}
    layouts, phys = {}, {}
    planned_out = set()  # tensors a planned op produces (padded layout)
    for i, op in enumerate(g.ops):
        fc = folded.get(i)
        if fc is None or paged.get(i):
            continue
        if registry.get(op.op).lower_pallas is None:
            continue
        w_t = g.tensor(op.inputs[1])
        y_t = g.tensor(op.outputs[0])
        lo, hi = clamp_bounds(fc, op.attrs.get("fused", "NONE"))
        z_x = int(np.asarray(fc.z_x))
        w = w_t.data

        if op.op == G.FULLY_CONNECTED:
            if len(g.tensor(op.inputs[0]).shape) != 2:
                continue  # rank-folding FC stays on the per-call route
            k, n = w.shape
            m = g.tensor(op.inputs[0]).shape[0]
            kp, np_, mp = (round_up(d, MXU_LANES) for d in (k, n, m))
            w_phys = np.zeros((kp, np_), np.int8)
            w_phys[:k, :n] = w
            lay = OpLayout("fc", w_phys, _planned_consts(fc, n, np_),
                           lo, hi, n, kp, (mp, np_), k, z_x)
        elif op.op == G.CONV_2D:
            kh, kw, cin, cout = w.shape
            np_ = round_up(cout, MXU_LANES)
            if kh * kw > 1 and op.inputs[0] not in planned_out:
                # Input arrives logical: flatten the taps at the real
                # channel width, then round K up (tap-major/channel-minor,
                # as im2col_q builds the rows) — no lane pad of the input.
                in_lanes = cin
            else:
                in_lanes = round_up(cin, MXU_LANES)
            f = np.zeros((kh, kw, in_lanes, cout), np.int8)
            f[:, :, :cin, :] = w
            k = kh * kw * in_lanes
            w_phys = np.zeros((round_up(k, MXU_LANES), np_), np.int8)
            w_phys[:k, :cout] = f.reshape(k, cout)
            lay = OpLayout("conv", w_phys, _planned_consts(fc, cout, np_),
                           lo, hi, cout, in_lanes, y_t.shape[:3] + (np_,),
                           cin, z_x)
        else:  # DEPTHWISE_CONV_2D
            assert w.shape[3] == 1, (
                "depth multiplier 1 only (matches the kernel contract)")
            kh, kw, c, _ = w.shape
            cp = round_up(c, MXU_LANES)
            w_phys = np.zeros((kh, kw, cp), np.int8)
            w_phys[:, :, :c] = w[..., 0]
            lay = OpLayout("dwconv", w_phys, _planned_consts(fc, c, cp),
                           lo, hi, c, cp, y_t.shape[:3] + (cp,), c, z_x)

        layouts[i] = lay
        planned_out.add(op.outputs[0])
        if tuple(lay.out_shape) != tuple(y_t.shape):
            phys[op.outputs[0]] = tuple(lay.out_shape)

    # Graph inputs consumed by planned ops: record the lane-padded entry
    # layout so the batched path can stage inputs pre-padded (fusing the
    # bucket zero-fill with the entry lane pad in ONE device pad) — only
    # where every planned consumer reads the input at the same lane width;
    # otherwise it stays logical and each consumer pads its own view.
    entry_lanes = {}
    input_ids = set(g.inputs)
    for i, lay in layouts.items():
        tid = g.ops[i].inputs[0]
        if tid in input_ids:
            entry_lanes.setdefault(tid, set()).add(lay.in_lanes)
    entry_phys = {}
    for tid, lanes in entry_lanes.items():
        shape = tuple(g.tensor(tid).shape)
        if len(lanes) == 1 and shape[-1] not in lanes:
            entry_phys[tid] = shape[:-1] + tuple(lanes)
    return LayoutPlan(layouts, phys, entry_phys)


def _planned_consts(fc: FoldedConsts, n: int, n_pad: int) -> tuple:
    return (_grow_const(fc.bias_term, n, n_pad, np.float32),
            _grow_const(fc.rescale, n, n_pad, np.float32),
            _grow_const(fc.w_sum_zx, n, n_pad, np.int32),
            _grow_const(fc.const_off, n, n_pad, np.int32),
            _grow_const(fc.z_w, n, n_pad, np.int32))


def folded_const_bytes(folded: dict) -> int:
    """Bytes of compile-time constants baked into the executable."""
    total = 0
    for fc in folded.values():
        for arr in (fc.bias_term, fc.rescale, fc.w_sum_zx, fc.const_off):
            total += np.asarray(arr).nbytes
    return total
