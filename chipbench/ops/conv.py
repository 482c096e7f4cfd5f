"""CONV_2D: a 2-D convolution with bias and a fused activation.

Layer keys: ``kernel`` [kh, kw], ``cout``, ``stride``, ``padding``
("SAME" or "VALID"), ``act`` ("none", "relu", "relu6"), ``w_std`` and
``b_std`` (the spread of the random float weights).
"""
import math

import jax

from chipbench.ops import FUSED, act_ref, out_hw

WEIGHT_AXIS = 3  # output channels of the HWIO filter


def shape(layer, x_shape):
    h, w, _ = x_shape
    kh, kw = layer["kernel"]
    oh, ow = out_hw(h, w, kh, kw, layer["stride"], layer["padding"])
    return (oh, ow, layer["cout"])


def ops(layer, x_shape, y_shape):
    """Two per multiply-add over the real input channels."""
    kh, kw = layer["kernel"]
    return 2 * math.prod(y_shape) * kh * kw * x_shape[-1]


def init(rng, layer, x_shape):
    kh, kw = layer["kernel"]
    w = rng.normal(0, layer["w_std"], (kh, kw, x_shape[2], layer["cout"]))
    b = rng.normal(0, layer["b_std"], layer["cout"])
    return {"w": w.astype("float32"), "b": b.astype("float32")}


def build(gb, x, layer, p):
    s = layer["stride"]
    return gb.conv2d(x, p["w"], p["b"], stride=(s, s),
                     padding=layer["padding"], fused=FUSED[layer["act"]],
                     name=layer["name"])


def ref(x, layer, p):
    s = layer["stride"]
    y = jax.lax.conv_general_dilated(
        x, p["w"], (s, s), layer["padding"],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)
    return act_ref(y + p["b"], layer["act"])
