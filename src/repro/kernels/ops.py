"""Public jit'd wrappers for the Pallas kernels.

Two families of entry points:

* ``*_folded`` — the original per-call route: logical-shape int8 in/out.
  Each call pads its operands to MXU-aligned tiles (lanes 128) and slices
  the result back, so consecutive layers pay a pad→slice→pad round trip.
* ``*_planned`` — the graph-planned route (``preprocess.plan_layout``):
  weights and folded constants arrive pre-padded from compile time, the
  activation input is consumed in lane-padded physical layout (padded only
  if it arrives logical, i.e. at graph entry), and the output is *kept*
  padded with its padding lanes zeroed by the kernel. Chained Pallas layers
  therefore stay tile-resident — layout work happens once, at compile time,
  the MicroFlow/TFLM principle applied to TPU tiling. The planned route is
  batch-aware: the conv/dwconv wrappers are batch-native (NHWC batch) and
  ``qmatmul_planned_batched`` merges a leading batch dim into the MXU rows,
  so the engine's batched bucket executables lower through the same
  compile-time layouts as the single-call trace.

Both families handle fused-activation bounds, SAME→VALID border pre-padding
with the input zero point, and interpret-mode selection (interpret=True off
TPU — the kernel body then executes in Python for validation; on TPU it
compiles to Mosaic).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.ops_ref import (FoldedConsts, MXU_LANES, clamp_bounds,
                                pad_input_q, round_up, same_pads)
from . import qmatmul as _qm
from . import paged_matmul as _pm
from . import qdwconv as _dw
from . import qconv as _qc

LANE = MXU_LANES


#: Tri-state override for interpret mode: None = auto (backend-derived),
#: True/False = forced. The tuned bench lane (``benchmarks/run.py
#: --no-interpret``) forces False after :func:`can_lower_noninterpret`
#: proves the backend lowers Pallas natively.
_INTERPRET_OVERRIDE = None

#: Cached (supported, reason) result of the non-interpret lowering probe.
_NONINTERPRET_PROBE = None


def set_interpret(mode) -> None:
    """Force (``True``/``False``) or restore automatic (``None``)
    interpret-mode selection for every Pallas kernel call. Forcing
    ``False`` on a backend that cannot lower Mosaic/Triton makes kernel
    calls raise — gate it behind :func:`can_lower_noninterpret`."""
    global _INTERPRET_OVERRIDE
    _INTERPRET_OVERRIDE = mode


def _interpret() -> bool:
    if _INTERPRET_OVERRIDE is not None:
        return _INTERPRET_OVERRIDE
    return jax.default_backend() != "tpu"


def interpret_mode() -> bool:
    """True when the Pallas kernels execute with ``interpret=True`` (the
    CPU validation fallback) rather than compiling to Mosaic. Benchmarks
    record this per measurement so committed pallas numbers are
    interpretable across backends."""
    return _interpret()


def can_lower_noninterpret():
    """Probe (once, cached) whether this backend can lower and run a
    Pallas kernel with ``interpret=False`` — i.e. a real Mosaic/Triton
    compile, not the interpreter. Returns ``(supported, reason)``:
    ``(True, None)`` on success, else ``(False, "<error summary>")`` so
    the bench lane can degrade gracefully with an explicit skip reason
    instead of crashing the run."""
    global _NONINTERPRET_PROBE
    if _NONINTERPRET_PROBE is not None:
        return _NONINTERPRET_PROBE
    try:
        from jax.experimental import pallas as pl

        def kern(x_ref, o_ref):
            o_ref[...] = x_ref[...] + 1

        fn = pl.pallas_call(
            kern, out_shape=jax.ShapeDtypeStruct((8, LANE), jnp.float32),
            interpret=False, name="lower_probe")
        out = jax.jit(fn)(jnp.zeros((8, LANE), jnp.float32))
        jax.block_until_ready(out)
        _NONINTERPRET_PROBE = (True, None)
    except Exception as e:  # NotImplementedError / Mosaic unavailable / ...
        msg = f"{type(e).__name__}: {e}"
        _NONINTERPRET_PROBE = (False, " ".join(msg.split())[:200])
    return _NONINTERPRET_PROBE


def _pad2(a, m0, m1, value=0):
    p0 = round_up(a.shape[0], m0) - a.shape[0]
    p1 = round_up(a.shape[1], m1) - a.shape[1]
    if p0 or p1:
        a = jnp.pad(a, ((0, p0), (0, p1)), constant_values=value)
    return a


def _pad_channel_consts(fc: FoldedConsts, n: int, n_pad: int):
    def grow(v, dtype):
        v = jnp.broadcast_to(jnp.asarray(v, dtype).reshape(-1), (n,))
        return jnp.pad(v, (0, n_pad - n))
    return (grow(fc.bias_term, jnp.float32), grow(fc.rescale, jnp.float32),
            grow(fc.w_sum_zx, jnp.int32), grow(fc.const_off, jnp.int32),
            grow(fc.z_w, jnp.int32))


def _lane_pad(x, lanes: int):
    """Zero-pad the trailing (lane) dimension to the planned physical width.
    A no-op when the producer already emitted padded layout."""
    if x.shape[-1] != lanes:
        x = jnp.pad(x, ((0, 0),) * (x.ndim - 1)
                    + ((0, lanes - x.shape[-1]),))
    return x


# ---------------------------------------------------------------------------
# FULLY_CONNECTED
# ---------------------------------------------------------------------------

def qmatmul_folded(x_q, w_q, fc: FoldedConsts, fused: str = "NONE",
                   *, paged: bool = False, page: int = LANE):
    """Engine entry point: folded Eq. (3) on the MXU-tiled Pallas kernel.
    Pads (M, K, N) to 128 multiples with zeros — zero K-padding contributes
    nothing to either Σ X W or Σ X, so the result is exact after slicing.
    Accepts any leading x rank (rows are independent): (..., K) @ (K, N)
    collapses the leading dims through the 2-D kernel and restores them."""
    lead = x_q.shape[:-1]
    if x_q.ndim != 2:
        x_q = x_q.reshape((-1, x_q.shape[-1]))
    m, k = x_q.shape
    _, n = w_q.shape
    lo, hi = clamp_bounds(fc, fused)
    xp = _pad2(x_q, LANE, LANE)
    wp = _pad2(w_q, LANE, LANE)
    consts = _pad_channel_consts(fc, n, wp.shape[1])
    if paged:
        out = _pm.paged_qmatmul(xp, wp, *consts, page=page, lo=lo, hi=hi,
                                interpret=_interpret())
    else:
        out = _qm.qmatmul(xp, wp, *consts, lo=lo, hi=hi,
                          interpret=_interpret())
    return out[:m, :n].reshape(lead + (n,))


def qmatmul_planned(x_q, lay):
    """Planned-layout FC: x arrives logical (graph entry) or already in the
    (M', K') padded physical layout; the output STAYS padded, its padding
    lanes zeroed by the kernel."""
    mp, np_lanes = lay.out_shape
    if x_q.shape != (mp, lay.in_lanes):
        x_q = _pad2(x_q, LANE, LANE)
    return _qm.qmatmul(x_q, jnp.asarray(lay.w_phys),
                       *(jnp.asarray(c) for c in lay.consts),
                       lo=lay.lo, hi=lay.hi,
                       n_true=lay.n_true if np_lanes != lay.n_true else None,
                       interpret=_interpret())


def qmatmul_planned_batched(x_q, lay):
    """Planned-layout FC with one leading batch dimension.

    ``x_q`` is ``(B, m, K)`` logical (non-Pallas producer) or ``(B, m, K')``
    lane-padded (upstream planned op / fused entry pad); the batch dim is
    layout-neutral, so the same compile-time ``OpLayout`` serves every
    bucket. The batch merges into the MXU row dimension; the only trace-time
    layout work is the row alignment of ``B*m`` (fused with the lane pad
    when the input arrives logical) — it disappears entirely when ``B*m``
    is a lane multiple. Output is ``(B, m, N')`` with padding lanes zeroed
    by the kernel (same ``n_true`` contract as the single-call route)."""
    b, m = x_q.shape[0], x_q.shape[1]
    rows = b * m
    x2 = x_q.reshape(rows, x_q.shape[-1])
    mp = round_up(rows, LANE)
    lane_pad = lay.in_lanes - x2.shape[-1]
    if mp != rows or lane_pad:
        x2 = jnp.pad(x2, ((0, mp - rows), (0, lane_pad)))
    np_lanes = lay.out_shape[-1]
    out = _qm.qmatmul(x2, jnp.asarray(lay.w_phys),
                      *(jnp.asarray(c) for c in lay.consts),
                      lo=lay.lo, hi=lay.hi,
                      n_true=lay.n_true if np_lanes != lay.n_true else None,
                      interpret=_interpret())
    if mp != rows:
        out = out[:rows]
    return out.reshape(b, m, np_lanes)


def fmatmul(x, w):
    """Float matmul on the Pallas kernel (dtype sweeps / float FC path)."""
    m, k = x.shape
    _, n = w.shape
    out = _qm.fmatmul(_pad2(x, LANE, LANE), _pad2(w, LANE, LANE),
                      interpret=_interpret())
    return out[:m, :n]


# ---------------------------------------------------------------------------
# CONV_2D — Eq. (7) via im2col on the same MXU contraction
# ---------------------------------------------------------------------------

def qconv_folded(x_q, f_q, fc: FoldedConsts, *, stride, padding,
                 fused: str = "NONE"):
    """Engine entry point: folded Eq. (7) on the im2col/MXU kernel.
    Logical int8 NHWC in/out; SAME borders pre-padded with z_X."""
    stride = tuple(stride)
    kh, kw, cin, cout = f_q.shape
    lo, hi = clamp_bounds(fc, fused)
    x_q = pad_input_q(x_q, kh, kw, stride, padding, fc.z_x)
    w_mat = _pad2(f_q.reshape(kh * kw * cin, cout), LANE, LANE)
    consts = _pad_channel_consts(fc, cout, w_mat.shape[1])
    out = _qc.qconv2d(x_q, w_mat, *consts, kh=kh, kw=kw, stride=stride,
                      lo=lo, hi=hi, interpret=_interpret())
    return out[..., :cout]


def _pad_border_planned(x_q, kh, kw, stride, padding, z_x: int, c_true: int):
    """SAME→VALID pre-pad in padded-lane layout.

    Border entries must carry the input zero point on the ``c_true`` real
    lanes (so (X - z_X) vanishes there, keeping the folded ΣW term exact)
    but ZERO on the padding lanes (so they contribute nothing to the im2col
    rows' Σ X). A plain ``pad_input_q`` would leak z_X into padding lanes.
    """
    if padding == "VALID":
        return x_q
    b, h, w, lanes = x_q.shape
    (pt, pb), (pl_, pr) = same_pads(h, w, kh, kw, stride)
    if not (pt or pb or pl_ or pr):
        return x_q
    xp = jnp.pad(x_q, ((0, 0), (pt, pb), (pl_, pr), (0, 0)))
    if z_x == 0 or c_true == 0:
        return xp
    row = jnp.arange(h + pt + pb)
    col = jnp.arange(w + pl_ + pr)
    border = (((row < pt) | (row >= pt + h))[:, None]
              | ((col < pl_) | (col >= pl_ + w))[None, :])
    fill = jnp.where(jnp.arange(lanes) < c_true, z_x, 0).astype(x_q.dtype)
    return jnp.where(border[None, :, :, None], fill, xp)


def qconv_planned(x_q, lay, *, kh, kw, stride, padding):
    """Planned-layout Conv2D: NHWC in at the plan's ``in_lanes`` (a k×k
    conv reading a logical input is planned at its real channel width, so
    nothing pads here), lane-padded NHWC out with padding lanes zeroed."""
    stride = tuple(stride)
    x_q = _lane_pad(x_q, lay.in_lanes)
    x_q = _pad_border_planned(x_q, kh, kw, stride, padding, lay.z_x,
                              lay.c_true)
    np_lanes = lay.out_shape[-1]
    return _qc.qconv2d(x_q, jnp.asarray(lay.w_phys),
                       *(jnp.asarray(c) for c in lay.consts),
                       kh=kh, kw=kw, stride=stride, lo=lay.lo, hi=lay.hi,
                       n_true=lay.n_true if np_lanes != lay.n_true else None,
                       interpret=_interpret())


# ---------------------------------------------------------------------------
# DEPTHWISE_CONV_2D
# ---------------------------------------------------------------------------

def qdwconv_folded(x_q, w_q, fc: FoldedConsts, *, stride, padding,
                   fused: str = "NONE", bc: int = LANE):
    """Engine entry point: folded Eq. (9) on the channel-blocked Pallas
    kernel. SAME borders are pre-padded with z_X (see ops_ref.pad_input_q);
    channels are padded to the lane width."""
    stride = tuple(stride)
    kh, kw, c, mult = w_q.shape
    assert mult == 1
    lo, hi = clamp_bounds(fc, fused)
    x_q = pad_input_q(x_q, kh, kw, stride, padding, fc.z_x)
    b, H, W, _ = x_q.shape
    sh, sw = stride
    oh = (H - kh) // sh + 1
    ow = (W - kw) // sw + 1

    bc = min(bc, round_up(c, 8))
    c_pad = round_up(c, bc)
    if c_pad != c:
        x_q = jnp.pad(x_q, ((0, 0), (0, 0), (0, 0), (0, c_pad - c)))
    w3 = jnp.pad(w_q[..., 0], ((0, 0), (0, 0), (0, c_pad - c)))

    def grow(v, dtype):
        v = jnp.broadcast_to(jnp.asarray(v, dtype).reshape(-1), (c,))
        return jnp.pad(v, (0, c_pad - c))

    consts = (grow(fc.bias_term, jnp.float32), grow(fc.rescale, jnp.float32),
              grow(fc.w_sum_zx, jnp.int32), grow(fc.const_off, jnp.int32),
              grow(fc.z_w, jnp.int32))
    out = _dw.qdwconv(x_q, w3, *consts, stride=stride, out_hw=(oh, ow),
                      bc=bc, lo=lo, hi=hi, interpret=_interpret())
    return out[..., :c]


def qdwconv_planned(x_q, lay, *, stride, padding):
    """Planned-layout DepthwiseConv2D: lane-padded NHWC in/out. Depthwise
    math never mixes lanes, so borders may carry z_X on padding lanes too —
    those outputs are zero-masked by the kernel (``c_true``)."""
    stride = tuple(stride)
    kh, kw, _ = lay.w_phys.shape
    x_q = _lane_pad(x_q, lay.in_lanes)
    x_q = pad_input_q(x_q, kh, kw, stride, padding, lay.z_x)
    b, H, W, _ = x_q.shape
    sh, sw = stride
    oh = (H - kh) // sh + 1
    ow = (W - kw) // sw + 1
    cp = lay.out_shape[-1]
    return _dw.qdwconv(x_q, jnp.asarray(lay.w_phys),
                       *(jnp.asarray(c) for c in lay.consts),
                       stride=stride, out_hw=(oh, ow), bc=min(LANE, cp),
                       lo=lay.lo, hi=lay.hi,
                       c_true=lay.n_true if cp != lay.n_true else None,
                       interpret=_interpret())
