"""FULLY_CONNECTED on a flat row, with bias and a fused activation.

Layer keys: ``cout``, ``act``, ``w_std``, ``b_std``.
"""
import jax
import jax.numpy as jnp

from chipbench.ops import FUSED, act_ref

WEIGHT_AXIS = 1  # output units of the (cin, cout) matrix


def shape(layer, x_shape):
    return (layer["cout"],)


def ops(layer, x_shape, y_shape):
    """Two per multiply-add."""
    return 2 * x_shape[-1] * layer["cout"]


def init(rng, layer, x_shape):
    (cin,) = x_shape
    w = rng.normal(0, layer["w_std"], (cin, layer["cout"]))
    b = rng.normal(0, layer["b_std"], layer["cout"])
    return {"w": w.astype("float32"), "b": b.astype("float32")}


def build(gb, x, layer, p):
    return gb.fully_connected(x, p["w"], p["b"], fused=FUSED[layer["act"]],
                              name=layer["name"])


def ref(x, layer, p):
    y = jnp.dot(x, p["w"], precision=jax.lax.Precision.HIGHEST)
    return act_ref(y + p["b"], layer["act"])
