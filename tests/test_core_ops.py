"""Unit + property tests for the quantized operator math (paper Sec. 5)."""
import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import ops_ref as K
from repro.core.graph import QParams

settings.register_profile("ci", max_examples=25, deadline=None)
settings.load_profile("ci")


def _act_qp(rng, lo=-4.0, hi=4.0):
    scale = np.float32((hi - lo) / 255.0)
    zp = np.int32(round(-128 - lo / scale))
    return scale, zp


def _quant(r, s, z):
    return np.clip(np.round(r / s) + z, -128, 127).astype(np.int8)


def _dequant(q, s, z):
    return (q.astype(np.float32) - z) * s


@given(m=st.integers(1, 5), n=st.integers(1, 24), p=st.integers(1, 24),
       seed=st.integers(0, 2**31 - 1),
       fused=st.sampled_from(["NONE", "RELU", "RELU6"]))
def test_fully_connected_q_matches_float(m, n, p, seed, fused):
    """Quantized Eq. (3) tracks float Eq. (2) within quantization error."""
    rng = np.random.default_rng(seed)
    x = rng.uniform(-2, 2, (m, n)).astype(np.float32)
    w = rng.uniform(-1, 1, (n, p)).astype(np.float32)
    b = rng.uniform(-1, 1, p).astype(np.float32)

    s_x, z_x = _act_qp(rng, -2, 2)
    s_w = np.abs(w).max(0) / 127.0 + 1e-9
    z_w = np.zeros(p, np.int32)
    y_f = np.asarray(K.fully_connected_f(x, w, b, fused))
    lo = min(y_f.min() - 0.1, 0.0)   # zero must be representable
    hi = max(y_f.max() + 0.1, 0.0)
    s_y = np.float32(max(hi - lo, 1e-3) / 255.0)
    z_y = np.int32(np.clip(round(-128 - lo / s_y), -128, 127))
    s_b = s_x * s_w

    x_q = _quant(x, s_x, z_x)
    w_q = np.clip(np.round(w / s_w), -127, 127).astype(np.int8)
    b_q = np.round(b / s_b).astype(np.int32)

    y_q = np.asarray(K.fully_connected_q(
        x_q, w_q, b_q, s_x=s_x, z_x=z_x, s_w=s_w, z_w=z_w,
        s_b=s_b, z_b=np.int32(0), s_y=s_y, z_y=z_y, fused=fused))
    y_deq = _dequant(y_q, s_y, z_y)
    # error bound: input quant err * L1 weight row norm + output step
    tol = s_x * np.abs(w).sum(0).max() + 2 * s_y + 1e-3
    assert np.abs(y_deq - y_f).max() <= tol


@given(seed=st.integers(0, 2**31 - 1), same=st.booleans(),
       stride=st.sampled_from([(1, 1), (2, 2)]),
       fused=st.sampled_from(["NONE", "RELU", "RELU6"]))
def test_conv2d_folded_equals_unfolded(seed, same, stride, fused):
    """Compile-time folding (Eq. 7) is an exact rewriting of Eq. (6)."""
    rng = np.random.default_rng(seed)
    x_q = rng.integers(-128, 128, (2, 7, 7, 3)).astype(np.int8)
    f_q = rng.integers(-128, 128, (3, 3, 3, 4)).astype(np.int8)
    b_q = rng.integers(-1000, 1000, 4).astype(np.int32)
    s_x, z_x = np.float32(0.02), np.int32(-5)
    s_f = (rng.random(4).astype(np.float32) * 0.01 + 1e-4)
    z_f = np.zeros(4, np.int32)
    s_b = s_x * s_f
    s_y, z_y = np.float32(0.05), np.int32(3)
    padding = "SAME" if same else "VALID"

    y1 = np.asarray(K.conv2d_q(
        x_q, f_q, b_q, stride=stride, padding=padding, s_x=s_x, z_x=z_x,
        s_f=s_f, z_f=z_f, s_b=s_b, z_b=np.int32(0), s_y=s_y, z_y=z_y,
        fused=fused))

    from repro.core.graph import (Graph, TensorSpec, OpNode, QParams,
                                  CONV_2D)
    from repro.core.preprocess import fold_weighted_op
    g = Graph(
        tensors=[
            TensorSpec("x", x_q.shape, "int8", QParams(s_x, z_x)),
            TensorSpec("f", f_q.shape, "int8", QParams(s_f, z_f, axis=3),
                       data=f_q),
            TensorSpec("b", b_q.shape, "int32",
                       QParams(s_b, np.zeros(4, np.int32), axis=0), data=b_q),
            TensorSpec("y", y1.shape, "int8", QParams(s_y, z_y)),
        ],
        ops=[OpNode(CONV_2D, [0, 1, 2], [3],
                    {"stride": stride, "padding": padding, "fused": fused})],
        inputs=[0], outputs=[3])
    fc = fold_weighted_op(g, g.ops[0])
    y2 = np.asarray(K.conv2d_folded(x_q, f_q, fc, stride=stride,
                                    padding=padding, fused=fused))
    np.testing.assert_array_equal(y1, y2)


@given(seed=st.integers(0, 2**31 - 1), same=st.booleans(),
       stride=st.sampled_from([(1, 1), (2, 2)]))
def test_depthwise_folded_equals_unfolded(seed, same, stride):
    rng = np.random.default_rng(seed)
    c = 5
    x_q = rng.integers(-128, 128, (1, 8, 8, c)).astype(np.int8)
    w_q = rng.integers(-128, 128, (3, 3, c, 1)).astype(np.int8)
    b_q = rng.integers(-500, 500, c).astype(np.int32)
    s_x, z_x = np.float32(0.03), np.int32(7)
    s_w = (rng.random(c).astype(np.float32) * 0.01 + 1e-4)
    z_w = np.zeros(c, np.int32)
    s_b = s_x * s_w
    s_y, z_y = np.float32(0.04), np.int32(-2)
    padding = "SAME" if same else "VALID"

    y1 = np.asarray(K.depthwise_conv2d_q(
        x_q, w_q, b_q, stride=stride, padding=padding, s_x=s_x, z_x=z_x,
        s_w=s_w, z_w=z_w, s_b=s_b, z_b=np.int32(0), s_y=s_y, z_y=z_y))

    from repro.core.graph import (Graph, TensorSpec, OpNode, QParams,
                                  DEPTHWISE_CONV_2D)
    from repro.core.preprocess import fold_weighted_op
    g = Graph(
        tensors=[
            TensorSpec("x", x_q.shape, "int8", QParams(s_x, z_x)),
            TensorSpec("w", w_q.shape, "int8", QParams(s_w, z_w, axis=2),
                       data=w_q),
            TensorSpec("b", b_q.shape, "int32",
                       QParams(s_b, np.zeros(c, np.int32), axis=0), data=b_q),
            TensorSpec("y", y1.shape, "int8", QParams(s_y, z_y)),
        ],
        ops=[OpNode(DEPTHWISE_CONV_2D, [0, 1, 2], [3],
                    {"stride": stride, "padding": padding, "fused": "NONE"})],
        inputs=[0], outputs=[3])
    fc = fold_weighted_op(g, g.ops[0])
    y2 = np.asarray(K.depthwise_conv2d_folded(x_q, w_q, fc, stride=stride,
                                              padding=padding))
    np.testing.assert_array_equal(y1, y2)


def test_fc_folded_equals_unfolded_where_float32_steps_would_not():
    """The rescale taken in float32 steps, ``(s_x*s_w)/s_y``, is one ulp away
    from the fold's (float64, rounded once) for these scales, and at this
    accumulator (-19369) the two round to different int8 values (-48 and
    -49). Both paths take their constants from ``requant_consts``, so the
    interpreter's unfolded op and the compiled fold still agree."""
    from repro.core.graph import FULLY_CONNECTED, Graph, OpNode, TensorSpec
    from repro.core.preprocess import fold_weighted_op
    s_x, s_w, s_y = (np.float32(0.033772003), np.float32(0.004041201),
                     np.float32(0.05132952))
    z0, z_y = np.int32(0), np.int32(3)
    assert np.float32(np.float32(s_x * s_w) / s_y) != \
        K.requant_consts(s_x, s_w, s_y, z_y)[1]
    x_q = np.array([[127, 127, 1]], np.int8)
    w_q = np.array([[-127], [-25], [-65]], np.int8)  # Σ X W = -19369

    y1 = np.asarray(K.fully_connected_q(
        x_q, w_q, None, s_x=s_x, z_x=z0, s_w=s_w, z_w=z0, s_b=np.float32(1),
        z_b=z0, s_y=s_y, z_y=z_y))
    g = Graph(
        tensors=[TensorSpec("x", x_q.shape, "int8", QParams(s_x, z0)),
                 TensorSpec("w", w_q.shape, "int8", QParams(s_w, z0),
                            data=w_q),
                 TensorSpec("y", (1, 1), "int8", QParams(s_y, z_y))],
        ops=[OpNode(FULLY_CONNECTED, [0, 1], [2], {"fused": "NONE"})],
        inputs=[0], outputs=[2])
    y2 = np.asarray(K.fully_connected_folded(x_q, w_q,
                                             fold_weighted_op(g, g.ops[0])))
    np.testing.assert_array_equal(y1, y2)
    assert y2.item() == -49


def test_relu_eq14_piecewise():
    s_x, z_x = np.float32(0.1), np.int32(10)
    s_y, z_y = np.float32(0.1), np.int32(-20)
    x_q = np.arange(-128, 128, dtype=np.int8)
    y = np.asarray(K.relu_q(x_q, s_x=s_x, z_x=z_x, s_y=s_y, z_y=z_y))
    # below the input zero point, output must be exactly z_y (Eq. 14)
    assert (y[x_q < z_x] == z_y).all()
    deq = (y.astype(np.float32) - z_y) * s_y
    ref = np.maximum((x_q.astype(np.float32) - z_x) * s_x, 0)
    assert np.abs(deq - ref).max() <= s_y


def test_relu6_upper_bound():
    s_x, z_x = np.float32(0.06), np.int32(-30)
    s_y, z_y = np.float32(0.03), np.int32(-128)
    x_q = np.arange(-128, 128, dtype=np.int8)
    y = np.asarray(K.relu6_q(x_q, s_x=s_x, z_x=z_x, s_y=s_y, z_y=z_y))
    deq = (y.astype(np.float32) - z_y) * s_y
    ref = np.clip((x_q.astype(np.float32) - z_x) * s_x, 0, 6)
    assert np.abs(deq - ref).max() <= s_y + 1e-5


@given(seed=st.integers(0, 2**31 - 1), n=st.integers(2, 16))
def test_softmax_q_probabilities(seed, n):
    rng = np.random.default_rng(seed)
    x_q = rng.integers(-128, 128, (3, n)).astype(np.int8)
    s_x, z_x = np.float32(0.05), np.int32(0)
    s_y, z_y = np.float32(1 / 256), np.int32(-128)
    y = np.asarray(K.softmax_q(x_q, s_x=s_x, z_x=z_x, s_y=s_y, z_y=z_y))
    p = (y.astype(np.float32) - z_y) * s_y
    ref = np.asarray(K.softmax_f(s_x * (x_q.astype(np.float32) - z_x)))
    assert np.abs(p - ref).max() <= 1 / 256 + 1e-6
    assert (p >= 0).all() and (p.sum(-1) <= 1.0 + n / 256).all()


def test_qparams_roundtrip():
    qp = QParams(np.float32(0.05), np.int32(3))
    r = np.linspace(-5, 5, 100).astype(np.float32)
    r2 = qp.dequantize(qp.quantize(r))
    mask = (r > -6.5) & (r < 6.2)  # representable range
    assert np.abs(r2[mask] - r[mask]).max() <= 0.05 / 2 + 1e-6
