"""DEPTHWISE_CONV_2D with depth multiplier 1, bias and a fused activation.

Layer keys: ``kernel`` [kh, kw], ``stride``, ``padding``, ``act``,
``w_std``, ``b_std``.
"""
import math

import jax

from chipbench.ops import FUSED, act_ref, out_hw

WEIGHT_AXIS = 2  # channels of the (kh, kw, c, 1) filter


def shape(layer, x_shape):
    h, w, c = x_shape
    kh, kw = layer["kernel"]
    oh, ow = out_hw(h, w, kh, kw, layer["stride"], layer["padding"])
    return (oh, ow, c)


def ops(layer, x_shape, y_shape):
    """Two per multiply-add: one window per output element."""
    kh, kw = layer["kernel"]
    return 2 * math.prod(y_shape) * kh * kw


def init(rng, layer, x_shape):
    kh, kw = layer["kernel"]
    c = x_shape[2]
    w = rng.normal(0, layer["w_std"], (kh, kw, c, 1))
    b = rng.normal(0, layer["b_std"], c)
    return {"w": w.astype("float32"), "b": b.astype("float32")}


def build(gb, x, layer, p):
    s = layer["stride"]
    return gb.depthwise_conv2d(x, p["w"], p["b"], stride=(s, s),
                               padding=layer["padding"],
                               fused=FUSED[layer["act"]], name=layer["name"])


def ref(x, layer, p):
    s = layer["stride"]
    c = x.shape[-1]
    y = jax.lax.conv_general_dilated(
        x, p["w"].reshape(p["w"].shape[:2] + (1, c)), (s, s),
        layer["padding"], dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=c, precision=jax.lax.Precision.HIGHEST)
    return act_ref(y + p["b"], layer["act"])
