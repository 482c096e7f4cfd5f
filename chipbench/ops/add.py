"""ADD of two outputs of one shape, element by element, with a fused
activation: the join of a residual block and its skip connection.

Layer keys: ``inputs`` [a, b] (the two layers added), ``act`` ("none",
"relu", "relu6"). No weights.
"""
import math

from chipbench.ops import FUSED, act_ref

INPUTS = 2


def shape(layer, x_shapes):
    a, b = map(tuple, x_shapes)
    if a != b:
        raise ValueError(f"add needs inputs of one shape, not {a} and {b}")
    return a


def ops(layer, x_shapes, y_shape):
    """One add per output element."""
    return math.prod(y_shape)


def init(rng, layer, x_shapes):
    return {}


def build(gb, xs, layer, p):
    a, b = xs
    return gb.add(a, b, fused=FUSED[layer["act"]], name=layer["name"])


def ref(xs, layer, p):
    a, b = xs
    return act_ref(a + b, layer["act"])
