"""AVERAGE_POOL_2D, VALID, with the stride equal to the window.

Layer keys: ``window`` [wh, ww].
"""
import math

import jax.numpy as jnp


def shape(layer, x_shape):
    h, w, c = x_shape
    wh, ww = layer["window"]
    return (h // wh, w // ww, c)


def ops(layer, x_shape, y_shape):
    """One add per pooled element and one multiply per output."""
    wh, ww = layer["window"]
    return math.prod(y_shape) * (wh * ww + 1)


def init(rng, layer, x_shape):
    return {}


def build(gb, x, layer, p):
    return gb.average_pool2d(x, tuple(layer["window"]), name=layer["name"])


def ref(x, layer, p):
    n, h, w, c = x.shape
    wh, ww = layer["window"]
    oh, ow = h // wh, w // ww
    x = x[:, :oh * wh, :ow * ww].reshape(n, oh, wh, ow, ww, c)
    return jnp.mean(x, axis=(2, 4))
