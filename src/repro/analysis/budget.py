"""Pad/copy budget — pass 4 of the plan auditor.

Every pad primitive in a lowered trace is a data movement the compile-time
layout plan exists to avoid; a pad that sneaks back in (a layout regression)
is invisible to correctness tests and only shows up as lost bandwidth. The
tests used to pin hard-coded totals (28 pads for person, etc.) — this pass
derives the number instead, from the ``LayoutPlan`` and the kernels' pad
predicates, so the budget moves with the plan and a mismatch against the
traced count (``measured_pads``) localizes WHICH op regressed.

Derivation mirrors the lowering exactly (``repro.kernels.ops``):

* plain route: ``pad_input_q`` emits one pad for every SAME conv/dwconv
  (unconditionally — a zero-width ``jnp.pad`` still emits the primitive),
  and each PAD op is one pad; pools lower to ``reduce_window`` (no pads).
* planned route: entry lane pads only where the producer's physical shape
  differs from the consumer's planned ``in_lanes``; SAME halo pads
  (``_pad_border_planned`` skips zero-width halos, ``pad_input_q`` does
  not); one im2col alignment pad per conv whose row/contraction dims miss
  the 128 multiple; one row-alignment pad per batched FC whose ``B*m``
  rows miss it.

The budget is *enforceable* only when every folded op actually takes the
planned route — an unplanned-folded or paged op on the Pallas route pads
its weights and the five folded constants at trace time (a different,
known-costly regime the plan should have avoided), so the pass flags it
instead of pretending to count it.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Tuple

import numpy as np

from repro.core import graph as G
from repro.core import registry as R
from repro.core.engine import ExecutionPlan
from repro.core.ops_ref import MXU_LANES, same_pads

from .report import ERROR, Finding, WARNING


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


@dataclasses.dataclass
class PadBudget:
    """Derived pad allowance for one route of one plan."""

    route: str
    total: int
    items: List[Tuple[str, int, str]]   # (where, count, why)
    enforceable: bool                    # False: route pads at trace time
    notes: List[str] = dataclasses.field(default_factory=list)
    missed: List[str] = dataclasses.field(default_factory=list)  # plannable
    # ops the layout plan should have covered but did not — the definitive
    # over-budget regression (weights + five folded consts pad per trace)

    def as_dict(self) -> Dict[str, Any]:
        return {"route": self.route, "budget": self.total,
                "enforceable": self.enforceable,
                "items": [{"where": w, "pads": c, "why": y}
                          for w, c, y in self.items],
                "notes": list(self.notes),
                "missed_plan": list(self.missed)}


def _conv_dims(g: G.Graph, op: G.OpNode) -> Tuple[int, int, tuple, str]:
    w = g.tensor(op.inputs[1])
    kh, kw = w.shape[0], w.shape[1]  # HWIO conv / (kh, kw, c, 1) depthwise
    stride = tuple(op.attrs.get("stride", (1, 1)))
    padding = op.attrs.get("padding", "VALID")
    return kh, kw, stride, padding


def _halo_nonzero(x_shape: tuple, kh: int, kw: int, stride: tuple) -> bool:
    h, w = x_shape[-3], x_shape[-2]
    (pt, pb), (pl, pr) = same_pads(h, w, kh, kw, stride)
    return bool(pt or pb or pl or pr)


def pad_budget(plan: ExecutionPlan, batched: bool = False,
               bucket: int = 1) -> PadBudget:
    """Derive the exact pad-primitive count ``plan.lower(batched=...)``
    is allowed to trace on this route (``measured_pads`` checks it)."""
    g = plan.graph
    layouts = plan.layout.layouts if plan.layout is not None else {}
    items: List[Tuple[str, int, str]] = []
    notes: List[str] = []
    missed: List[str] = []
    enforceable = True

    # physical shape each tensor has in the engine's value env (leading
    # batch dim excluded — it is layout-neutral)
    phys: Dict[int, tuple] = {}
    for tid in g.inputs:
        phys[tid] = plan.entry_shape(tid) if batched \
            else tuple(g.tensor(tid).shape)

    for i, op in enumerate(g.ops):
        where = f"op {i} ({op.op})"
        lay = layouts.get(i)
        folded = i in plan.folded
        y = g.tensor(op.outputs[0])
        out_phys = tuple(y.shape)

        if lay is not None:
            # -- planned Pallas route ---------------------------------
            in_phys = phys.get(op.inputs[0], tuple(g.tensor(op.inputs[0]).shape))
            if lay.kind == "fc":
                out_phys = tuple(lay.out_shape)
                if batched:
                    m = tuple(g.tensor(op.inputs[0]).shape)[0]
                    rows = bucket * m
                    lane_short = in_phys[-1] != lay.in_lanes
                    if _round_up(rows, MXU_LANES) != rows or lane_short:
                        items.append((where, 1,
                                      f"batched FC row/lane alignment "
                                      f"({rows} rows, lanes "
                                      f"{in_phys[-1]}->{lay.in_lanes})"))
                    out_phys = (m, lay.out_shape[-1])
                else:
                    mp = lay.out_shape[0]
                    if tuple(in_phys) != (mp, lay.in_lanes):
                        items.append((where, 1,
                                      f"FC entry pad {tuple(in_phys)} -> "
                                      f"({mp}, {lay.in_lanes})"))
            else:
                kh, kw, stride, padding = _conv_dims(g, op)
                if in_phys[-1] != lay.in_lanes:
                    items.append((where, 1,
                                  f"entry lane pad {in_phys[-1]} -> "
                                  f"{lay.in_lanes}"))
                if padding == "SAME":
                    if lay.kind == "dwconv":
                        # pad_input_q emits even a zero-width SAME halo
                        items.append((where, 1, "SAME halo (depthwise)"))
                    elif _halo_nonzero(in_phys, kh, kw, stride):
                        items.append((where, 1, "SAME halo"))
                if lay.kind == "conv":
                    b_eff = (bucket if batched else 1) * \
                        int(np.prod(lay.out_shape[:-3], dtype=np.int64))
                    m = b_eff * int(np.prod(lay.out_shape[-3:-1],
                                            dtype=np.int64))
                    k = kh * kw * lay.in_lanes
                    if m % MXU_LANES or k % MXU_LANES:
                        items.append((where, 1,
                                      f"im2col alignment ({m} rows x {k})"))
                out_phys = tuple(lay.out_shape)
            phys[op.outputs[0]] = out_phys
            continue

        # -- unplanned routes -----------------------------------------
        if folded and (plan.use_pallas or plan.paged.get(i)):
            # qmatmul_folded/qconv_folded/qdwconv_folded pad weights AND
            # the five folded constants inside the trace — a budget here
            # would legitimize the regression the plan exists to prevent.
            enforceable = False
            desc = R._REGISTRY.get(op.op)
            plannable = (plan.use_pallas and not plan.paged.get(i)
                         and desc is not None
                         and desc.lower_pallas is not None
                         and not (op.op == G.FULLY_CONNECTED and
                                  len(g.tensor(op.inputs[0]).shape) != 2))
            if plannable:
                missed.append(where)
            else:
                notes.append(f"{where}: folded op legitimately off the "
                             f"planned route (paged / rank-folding) — "
                             f"pads at trace time")
        elif op.op in (G.CONV_2D, G.DEPTHWISE_CONV_2D):
            _, _, _, padding = _conv_dims(g, op)
            if padding == "SAME":
                items.append((where, 1, "SAME halo (reference kernel)"))
        elif op.op == G.PAD:
            items.append((where, 1, "explicit PAD op"))
        phys[op.outputs[0]] = tuple(y.shape)

    total = sum(c for _, c, _ in items)
    route = f"batched[b={bucket}]" if batched else "per-call"
    return PadBudget(route=route, total=total, items=items,
                     enforceable=enforceable, notes=notes, missed=missed)


def conv_contractions(plan: ExecutionPlan) -> List[Dict[str, Any]]:
    """Per planned Conv2D, the im2col contraction the plan lays out: its
    true K (kh·kw·Cin real values), the physical K' of the pre-padded
    filter matrix, and the lane width the conv reads its input at. A k×k
    conv on a logical input shows ``in_lanes == Cin`` and K' = K rounded
    up to 128; one on a lane-padded input, K' = kh·kw·in_lanes."""
    if plan.layout is None:
        return []
    g = plan.graph
    rows = []
    for i, lay in sorted(plan.layout.layouts.items()):
        if lay.kind != "conv":
            continue
        kh, kw, cin, _ = g.tensor(g.ops[i].inputs[1]).shape
        rows.append({"op": i, "k_true": int(kh * kw * cin),
                     "k_phys": int(lay.w_phys.shape[0]),
                     "in_lanes": int(lay.in_lanes)})
    return rows


def measured_pads(plan: ExecutionPlan, batched: bool = False,
                  bucket: int = 1) -> int:
    """Pad primitives actually traced on this route (recursively, through
    nested jaxprs), for cross-checking the derived budget."""
    import jax

    from repro.core.introspect import prim_counts

    if batched:
        specs = plan.batched_input_specs(bucket)
    else:
        specs = [jax.ShapeDtypeStruct(tuple(plan.graph.tensor(t).shape),
                                      np.dtype(plan.graph.tensor(t).dtype))
                 for t in plan.graph.inputs]
    counts = prim_counts(plan.lower(batched=batched), *specs)
    return int(counts.get("pad", 0))


def audit_pads(plan: ExecutionPlan, batched: bool = False,
               bucket: int = 1) -> Tuple[Dict[str, Any], List[Finding]]:
    """Budget + traced count + findings for one route."""
    budget = pad_budget(plan, batched=batched, bucket=bucket)
    findings: List[Finding] = []
    info = budget.as_dict()
    if not budget.enforceable:
        for where in budget.missed:
            findings.append(Finding(
                ERROR, "B004", where,
                "folded op fell off the planned route — weights and all "
                "five folded constants now pad on every trace (pad over "
                "budget by construction)"))
        if budget.notes:
            findings.append(Finding(
                WARNING, "B001", budget.route, "; ".join(budget.notes)))
        info["traced"] = None
        return info, findings
    traced = measured_pads(plan, batched=batched, bucket=bucket)
    info["traced"] = traced
    if traced > budget.total:
        findings.append(Finding(
            ERROR, "B002", budget.route,
            f"traced {traced} pad ops, budget allows {budget.total} — "
            f"a layout regression reintroduced data movement"))
    elif traced < budget.total:
        findings.append(Finding(
            WARNING, "B003", budget.route,
            f"traced {traced} pad ops under budget {budget.total} — "
            f"budget model is stale (tighten it)"))
    return info, findings
