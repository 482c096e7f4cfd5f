"""Runtime operator kernels — the paper's quantized formulae, in pure jnp.

Each function implements the *kernel* half of a MicroFlow operator (Fig. 7).
The "unfolded" entry points compute every term of Eqs. (3), (6), (9), (12),
(14), (16), (18) at call time — this is what the interpreter engine runs.
The compiled engine instead passes ``FoldedConsts`` produced at compile time
by :mod:`repro.core.preprocess` (the *parser* half), so only the input-dependent
terms remain (see Eq. (4) and friends).

Conventions (TFLite-compatible): activations int8 per-tensor, weights int8
per-tensor or per-channel (axis = output channel, z_W = 0 for per-channel),
bias int32 with s_b = s_X*s_W and z_b = 0 — but the formulas below keep the
general scale/zero-point terms of the paper.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

I8_MIN, I8_MAX = -128, 127

# MXU lane width — the layout quantum shared by the Pallas kernels
# (repro.kernels) and the compile-time layout planner (preprocess.plan_layout).
MXU_LANES = 128


def round_up(x: int, m: int) -> int:
    """Round x up to a multiple of m (lane/tile alignment)."""
    return -(-x // m) * m


@dataclasses.dataclass(frozen=True)
class FoldedConsts:
    """The compile-time constants of Eq. (4)/(7)/(10)/(13).

    bias_term : z_Y + (s_b/s_Y)(b_q - z_b)           float32 (per out channel)
    rescale   : (s_X s_W)/s_Y                         float32 (per out channel)
    w_sum_zx  : z_X * Σ W_q                           int32   (per out channel)
    const_off : n z_X z_W  (count * z_X * z_W)        int32   (per out channel)
    z_w       : weight zero point (needed for the input-dependent z_W ΣX term)
    z_y       : output zero point (for fused activation clamping)
    s_y       : output scale      (for fused RELU6 upper bound)
    """

    bias_term: jnp.ndarray
    rescale: jnp.ndarray
    w_sum_zx: jnp.ndarray
    const_off: jnp.ndarray
    z_w: jnp.ndarray
    z_y: jnp.ndarray
    s_y: jnp.ndarray
    z_x: jnp.ndarray  # input zero point — needed to pad SAME borders with
                      # the quantized representation of real 0, which is what
                      # makes the folded ΣW term exact at the borders


def _saturate_i8(y):
    return jnp.clip(jnp.round(y), I8_MIN, I8_MAX).astype(jnp.int8)


def requant_consts(s_x, s_w, s_y, z_y, b_q=None, s_b=None, z_b=None):
    """Per-channel ``(bias_term, rescale)`` of Eqs. (4)/(7)/(10), float32.

    Computed in float64 and rounded to float32 once. The compile-time fold
    (``preprocess.fold_weighted_op``) and the unfolded ops below both take
    them from here, so the interpreter and the compiled engine requantize
    with the same constants and agree bit for bit. The same formula taken
    in float32 steps can land an ulp away, which flips the rounding of an
    accumulator that sits near a half step."""
    if b_q is None:
        bias_term = np.asarray(z_y, np.float64)
    else:
        bias_term = z_y + (s_b / s_y) * (np.asarray(b_q).astype(np.float64)
                                         - z_b)
    rescale = (np.asarray(s_x, np.float64) * s_w) / s_y
    return np.asarray(bias_term, np.float32), np.asarray(rescale, np.float32)


def _fused_bounds(fused: str, z_y, s_y):
    """Quantized clamp bounds for fused activations (Eqs. (15), (17))."""
    lo = -jnp.inf
    hi = jnp.inf
    if fused == "RELU":
        lo = z_y.astype(jnp.float32)
    elif fused == "RELU6":
        lo = z_y.astype(jnp.float32)
        hi = z_y.astype(jnp.float32) + 6.0 / s_y
    elif fused != "NONE":
        raise ValueError(fused)
    return lo, hi


def clamp_bounds(fc: "FoldedConsts", fused: str):
    """Static (python float) clamp bounds of a fused activation — the
    compile-time form of :func:`_fused_bounds`, consumed by the Pallas
    kernel wrappers and the layout planner."""
    z_y = float(np.asarray(fc.z_y))
    s_y = float(np.asarray(fc.s_y))
    if fused == "RELU":
        return z_y, float("inf")
    if fused == "RELU6":
        return z_y, z_y + 6.0 / s_y
    if fused == "NONE":
        return float("-inf"), float("inf")
    raise ValueError(fused)


def _apply_fused_float(y, fused: str):
    if fused == "RELU":
        return jnp.maximum(y, 0.0)
    if fused == "RELU6":
        return jnp.clip(y, 0.0, 6.0)
    if fused == "NONE":
        return y
    raise ValueError(fused)


# ---------------------------------------------------------------------------
# FullyConnected — Eq. (3)
# ---------------------------------------------------------------------------

def fully_connected_q(
    x_q,  # (m, n) int8
    w_q,  # (n, p) int8
    b_q,  # (p,) int32 or None
    *,
    s_x, z_x, s_w, z_w, s_b, z_b, s_y, z_y,
    fused: str = "NONE",
):
    """Unfolded Eq. (3): every constant term computed at call time."""
    x32 = x_q.astype(jnp.int32)
    w32 = w_q.astype(jnp.int32)
    n = x_q.shape[-1]
    acc = x32 @ w32                               # Σ_k X W
    sum_x = jnp.sum(x32, axis=-1, keepdims=True)  # Σ_k X   (m, 1)
    sum_w = jnp.sum(w32, axis=0)                  # Σ_k W   (p,)
    z_x = jnp.asarray(z_x, jnp.int32)
    z_w = jnp.asarray(z_w, jnp.int32)
    inner = acc - z_w * sum_x - z_x * sum_w + n * z_x * z_w
    bias_term, rescale = requant_consts(s_x, s_w, s_y, z_y, b_q, s_b,
                                        z_b)
    y = bias_term + rescale * inner.astype(jnp.float32)
    lo, hi = _fused_bounds(fused, jnp.asarray(z_y), jnp.asarray(s_y, jnp.float32))
    return _saturate_i8(jnp.clip(y, lo, hi))


def fully_connected_folded(x_q, w_q, fc: FoldedConsts, fused: str = "NONE"):
    """Folded Eq. (3): only the input-dependent terms remain (Eq. (4))."""
    x32 = x_q.astype(jnp.int32)
    acc = x32 @ w_q.astype(jnp.int32)
    sum_x = jnp.sum(x32, axis=-1, keepdims=True)
    inner = acc - fc.z_w * sum_x - fc.w_sum_zx + fc.const_off
    y = fc.bias_term + fc.rescale * inner.astype(jnp.float32)
    lo, hi = _fused_bounds(fused, fc.z_y, fc.s_y)
    return _saturate_i8(jnp.clip(y, lo, hi))


def fully_connected_f(x, w, b, fused: str = "NONE"):
    """Float path, Eq. (2)."""
    y = x @ w
    if b is not None:
        y = y + b
    return _apply_fused_float(y, fused)


# ---------------------------------------------------------------------------
# Conv2D — Eq. (6).  NHWC inputs, HWIO filters.
# ---------------------------------------------------------------------------

_DN = ("NHWC", "HWIO", "NHWC")


def same_pads(h, w, kh, kw, stride):
    """TF-style SAME padding amounts per spatial dim."""
    sh, sw = stride
    oh, ow = -(-h // sh), -(-w // sw)
    ph = max((oh - 1) * sh + kh - h, 0)
    pw = max((ow - 1) * sw + kw - w, 0)
    return (ph // 2, ph - ph // 2), (pw // 2, pw - pw // 2)


def pad_input_q(x_q, kh, kw, stride, padding, z_x):
    """Pad a quantized NHWC input so the conv can run VALID.

    Padded entries carry the INPUT ZERO POINT — the quantized value of real
    zero — so that (X_q - z_X) vanishes on the border and the compile-time
    folded ΣW term (Eqs. 7/10) stays exact for every output position.
    """
    if padding == "VALID":
        return x_q
    (pt, pb), (plft, prgt) = same_pads(x_q.shape[1], x_q.shape[2], kh, kw,
                                       stride)
    return jnp.pad(x_q, ((0, 0), (pt, pb), (plft, prgt), (0, 0)),
                   constant_values=np.int8(z_x) if x_q.dtype == jnp.int8
                   else z_x)


def _conv(x32, f32, stride):
    return jax.lax.conv_general_dilated(
        x32, f32, window_strides=stride, padding="VALID",
        dimension_numbers=_DN, preferred_element_type=jnp.int32)


def conv2d_q(
    x_q,  # (b, h, w, cin) int8
    f_q,  # (kh, kw, cin, cout) int8
    b_q,  # (cout,) int32 or None
    *,
    stride, padding,
    s_x, z_x, s_f, z_f, s_b, z_b, s_y, z_y,
    fused: str = "NONE",
):
    kh, kw, cin, cout = f_q.shape
    x_q = pad_input_q(x_q, kh, kw, stride, padding, z_x)
    x32 = x_q.astype(jnp.int32)
    f32 = f_q.astype(jnp.int32)
    count = kh * kw * cin                       # m·n·c in Eq. (6)
    acc = _conv(x32, f32, stride)               # ΣΣΣ X F
    ones = jnp.ones((kh, kw, cin, 1), jnp.int32)
    sum_x = _conv(x32, ones, stride)            # ΣΣΣ X per position, (b,H,W,1)
    sum_f = jnp.sum(f32, axis=(0, 1, 2))        # ΣΣΣ F per out channel (cout,)
    z_x = jnp.asarray(z_x, jnp.int32)
    z_f = jnp.asarray(z_f, jnp.int32)
    inner = acc - z_f * sum_x - z_x * sum_f + count * z_x * z_f
    bias_term, rescale = requant_consts(s_x, s_f, s_y, z_y, b_q, s_b,
                                        z_b)
    y = bias_term + rescale * inner.astype(jnp.float32)
    lo, hi = _fused_bounds(fused, jnp.asarray(z_y), jnp.asarray(s_y, jnp.float32))
    return _saturate_i8(jnp.clip(y, lo, hi))


def conv2d_folded(x_q, f_q, fc: FoldedConsts, *, stride, padding,
                  fused: str = "NONE"):
    kh, kw, cin, cout = f_q.shape
    x_q = pad_input_q(x_q, kh, kw, stride, padding, fc.z_x)
    x32 = x_q.astype(jnp.int32)
    acc = _conv(x32, f_q.astype(jnp.int32), stride)
    ones = jnp.ones((kh, kw, cin, 1), jnp.int32)
    sum_x = _conv(x32, ones, stride)
    inner = acc - fc.z_w * sum_x - fc.w_sum_zx + fc.const_off
    y = fc.bias_term + fc.rescale * inner.astype(jnp.float32)
    lo, hi = _fused_bounds(fused, fc.z_y, fc.s_y)
    return _saturate_i8(jnp.clip(y, lo, hi))


def conv2d_f(x, f, b, *, stride, padding, fused: str = "NONE"):
    y = jax.lax.conv_general_dilated(
        x, f, window_strides=stride, padding=padding, dimension_numbers=_DN)
    if b is not None:
        y = y + b
    return _apply_fused_float(y, fused)


# ---------------------------------------------------------------------------
# DepthwiseConv2D — Eq. (9).  Filters (kh, kw, c, 1).
# ---------------------------------------------------------------------------

def _dwconv(x32, f32, stride):
    c = x32.shape[-1]
    # HWIO with feature_group_count=c: filter (kh, kw, 1, c)
    return jax.lax.conv_general_dilated(
        x32, f32, window_strides=stride, padding="VALID",
        dimension_numbers=_DN, feature_group_count=c,
        preferred_element_type=jnp.int32)


def depthwise_conv2d_q(
    x_q,  # (b, h, w, c) int8
    w_q,  # (kh, kw, c, 1) int8 — depth multiplier 1
    b_q,  # (c,) int32 or None
    *,
    stride, padding,
    s_x, z_x, s_w, z_w, s_b, z_b, s_y, z_y,
    fused: str = "NONE",
):
    kh, kw, c, mult = w_q.shape
    assert mult == 1, "depth multiplier 1 only"
    x_q = pad_input_q(x_q, kh, kw, stride, padding, z_x)
    x32 = x_q.astype(jnp.int32)
    w32 = w_q.astype(jnp.int32).transpose(0, 1, 3, 2)  # (kh, kw, 1, c)
    count = kh * kw                                     # m·n in Eq. (9)
    acc = _dwconv(x32, w32, stride)                     # ΣΣ X W per channel
    ones = jnp.ones((kh, kw, 1, c), jnp.int32)
    sum_x = _dwconv(x32, ones, stride)                  # ΣΣ X per channel
    sum_w = jnp.sum(w32, axis=(0, 1, 2))                # ΣΣ W per channel (c,)
    z_x = jnp.asarray(z_x, jnp.int32)
    z_w = jnp.asarray(z_w, jnp.int32)
    inner = acc - z_w * sum_x - z_x * sum_w + count * z_x * z_w
    bias_term, rescale = requant_consts(s_x, s_w, s_y, z_y, b_q, s_b,
                                        z_b)
    y = bias_term + rescale * inner.astype(jnp.float32)
    lo, hi = _fused_bounds(fused, jnp.asarray(z_y), jnp.asarray(s_y, jnp.float32))
    return _saturate_i8(jnp.clip(y, lo, hi))


def depthwise_conv2d_folded(x_q, w_q, fc: FoldedConsts, *, stride, padding,
                            fused: str = "NONE"):
    kh, kw, c, _ = w_q.shape
    x_q = pad_input_q(x_q, kh, kw, stride, padding, fc.z_x)
    x32 = x_q.astype(jnp.int32)
    w32 = w_q.astype(jnp.int32).transpose(0, 1, 3, 2)
    acc = _dwconv(x32, w32, stride)
    ones = jnp.ones((kh, kw, 1, c), jnp.int32)
    sum_x = _dwconv(x32, ones, stride)
    inner = acc - fc.z_w * sum_x - fc.w_sum_zx + fc.const_off
    y = fc.bias_term + fc.rescale * inner.astype(jnp.float32)
    lo, hi = _fused_bounds(fused, fc.z_y, fc.s_y)
    return _saturate_i8(jnp.clip(y, lo, hi))


def depthwise_conv2d_f(x, w, b, *, stride, padding, fused: str = "NONE"):
    c = x.shape[-1]
    w_ = w.transpose(0, 1, 3, 2)
    y = jax.lax.conv_general_dilated(
        x, w_, window_strides=stride, padding=padding,
        dimension_numbers=_DN, feature_group_count=c)
    if b is not None:
        y = y + b
    return _apply_fused_float(y, fused)


# ---------------------------------------------------------------------------
# AveragePool2D — Eq. (12)
# ---------------------------------------------------------------------------

def _pool_sum_and_count(x32, window, stride, padding):
    wh, ww = window
    zero = jnp.zeros((), x32.dtype)  # init must match the operand dtype
    sums = jax.lax.reduce_window(
        x32, zero, jax.lax.add, (1, wh, ww, 1), (1,) + tuple(stride) + (1,),
        padding)
    ones = jnp.ones(x32.shape[:3] + (1,), x32.dtype)
    counts = jax.lax.reduce_window(
        ones, zero, jax.lax.add, (1, wh, ww, 1), (1,) + tuple(stride) + (1,),
        padding)
    return sums, counts


def average_pool2d_q(x_q, *, window, stride, padding,
                     s_x, z_x, s_y, z_y, fused: str = "NONE"):
    x32 = x_q.astype(jnp.int32)
    sums, counts = _pool_sum_and_count(x32, window, stride, padding)
    mean = sums.astype(jnp.float32) / counts.astype(jnp.float32)
    y = z_y + (s_x / s_y) * (mean - z_x)                     # Eq. (12)
    lo, hi = _fused_bounds(fused, jnp.asarray(z_y), jnp.asarray(s_y, jnp.float32))
    return _saturate_i8(jnp.clip(y, lo, hi))


def average_pool2d_f(x, *, window, stride, padding, fused: str = "NONE"):
    sums, counts = _pool_sum_and_count(x.astype(jnp.float32), window, stride,
                                       padding)
    return _apply_fused_float(sums / counts, fused)


# ---------------------------------------------------------------------------
# MaxPool2D — max commutes with the (monotone) affine quantization map, so
# the pool runs directly on q-values, then requantizes:
#   y_q = z_y + (s_x/s_y)(max(X_q) - z_x)
# ---------------------------------------------------------------------------

def max_pool2d_q(x_q, *, window, stride, padding, s_x, z_x, s_y, z_y,
                 fused: str = "NONE"):
    wh, ww = window
    x32 = x_q.astype(jnp.int32)
    init = jnp.int32(I8_MIN)  # identity for max over int8 values
    mx = jax.lax.reduce_window(
        x32, init, jax.lax.max, (1, wh, ww, 1), (1,) + tuple(stride) + (1,),
        padding)
    y = z_y + (s_x / s_y) * (mx.astype(jnp.float32) - z_x)
    lo, hi = _fused_bounds(fused, jnp.asarray(z_y), jnp.asarray(s_y,
                                                                jnp.float32))
    return _saturate_i8(jnp.clip(y, lo, hi))


def max_pool2d_f(x, *, window, stride, padding, fused: str = "NONE"):
    wh, ww = window
    mx = jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, wh, ww, 1),
        (1,) + tuple(stride) + (1,), padding)
    return _apply_fused_float(mx, fused)


# ---------------------------------------------------------------------------
# ADD (residual) — two quantized operands with independent scales:
#   y_q = z_y + (s_a/s_y)(a_q - z_a) + (s_b/s_y)(b_q - z_b)
# ---------------------------------------------------------------------------

def add_q(a_q, b_q, *, s_a, z_a, s_b, z_b, s_y, z_y, fused: str = "NONE"):
    y = (z_y
         + (s_a / s_y) * (a_q.astype(jnp.float32) - z_a)
         + (s_b / s_y) * (b_q.astype(jnp.float32) - z_b))
    lo, hi = _fused_bounds(fused, jnp.asarray(z_y), jnp.asarray(s_y,
                                                                jnp.float32))
    return _saturate_i8(jnp.clip(y, lo, hi))


def add_f(a, b, fused: str = "NONE"):
    return _apply_fused_float(a + b, fused)


# ---------------------------------------------------------------------------
# PAD — spatial padding; quantized zero is the zero point (see pad_input_q)
# ---------------------------------------------------------------------------

def pad_q(x_q, *, pads, z_x):
    return jnp.pad(x_q, pads, constant_values=np.int8(z_x))


def pad_f(x, *, pads):
    return jnp.pad(x, pads)


# ---------------------------------------------------------------------------
# Standalone activations — Eqs. (14), (16), (18)
# ---------------------------------------------------------------------------

def relu_q(x_q, *, s_x, z_x, s_y, z_y):
    """Eq. (14)."""
    y = jnp.where(
        x_q < z_x,
        jnp.asarray(z_y, jnp.float32),
        z_y + (s_x / s_y) * (x_q.astype(jnp.float32) - z_x))
    return _saturate_i8(y)


def relu6_q(x_q, *, s_x, z_x, s_y, z_y):
    """Eq. (16)."""
    upper_in = z_x + 6.0 / s_x
    y_relu = jnp.where(
        x_q < z_x,
        jnp.asarray(z_y, jnp.float32),
        z_y + (s_x / s_y) * (x_q.astype(jnp.float32) - z_x))
    y = jnp.where(x_q.astype(jnp.float32) >= upper_in, z_y + 6.0 / s_y, y_relu)
    return _saturate_i8(y)


def softmax_q(x_q, *, s_x, z_x, s_y, z_y, axis=-1):
    """Eq. (18) — note z_x cancels (Appendix A.6); computed with a max-shift
    for numerical stability (an exact rewriting of the same expression)."""
    x = s_x * x_q.astype(jnp.float32)
    x = x - jnp.max(x, axis=axis, keepdims=True)
    e = jnp.exp(x)
    p = e / jnp.sum(e, axis=axis, keepdims=True)
    y = z_y + p / s_y
    return _saturate_i8(y)


def relu_f(x):
    return jnp.maximum(x, 0.0)


def relu6_f(x):
    return jnp.clip(x, 0.0, 6.0)


def softmax_f(x, axis=-1):
    return jax.nn.softmax(x, axis=axis)
