"""Depthwise convolutions: DEPTHWISE_CONV_2D layers run on the ``qdwconv``
Pallas kernel.

True work is counted on the unpadded layer: its operations (``ops/dw.py``:
kh*kw multiply-adds per output element over the real channels), and bytes
as int8 activations in and out once per row plus int8 weights and an int32
bias and multiplier per channel once per call. Channel padding to 128
lanes is not work.
"""
import math

from chipbench.model import op_module

KERNELS = ("qdwconv",)
LAYERS = ("dw",)


def work(layer, x_shape, y_shape) -> tuple:
    """(operations per row, activation bytes per row, bytes per call)."""
    kh, kw = layer["kernel"]
    c = x_shape[-1]
    return (op_module("dw").ops(layer, x_shape, y_shape),
            math.prod(x_shape) + math.prod(y_shape), kh * kw * c + 8 * c)
