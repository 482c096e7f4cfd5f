"""Int8 matrix products: FULLY_CONNECTED layers and CONV_2D layers (which
the program lowers through im2col) run on the ``qmatmul`` Pallas kernel.

True work is counted on the layer as the graph states it, unpadded: the
layer's operations (``ops/<kind>.py``: a direct convolution's multiply-adds
over its real input channels), and bytes as int8 activations in and out
once per row plus int8 weights and an int32 bias and multiplier per output
channel once per call. Lane padding, the im2col copy and the padded
contraction are not work, so they lower the share.

The residual ADD of two outputs (``ops/add.py``) belongs here too. Its
sum feeds the next block's 1x1 expand conv, a ``qmatmul``, so the device
time of the elementwise op is charged to this class (``trace.charges``),
and it would be so too were the add fused into the projection's epilogue;
its work must then be counted here, or the share would read too high. Its
work: one operation per output element, and bytes of its two int8 inputs
and its int8 output per row; it has no weights, so no bytes per call.
"""
import math

from chipbench.model import op_module

# op names of this class's kernels in the device trace (found by substring)
KERNELS = ("qmatmul",)
LAYERS = ("conv", "fc", "add")


def work(layer, x_shape, y_shape) -> tuple:
    """(operations per row, activation bytes per row, bytes per call)."""
    if layer["op"] == "add":
        return (op_module("add").ops(layer, x_shape, y_shape),
                sum(map(math.prod, x_shape)) + math.prod(y_shape), 0)
    cout = y_shape[-1]
    kh, kw = layer["kernel"] if layer["op"] == "conv" else (1, 1)
    return (op_module(layer["op"]).ops(layer, x_shape, y_shape),
            math.prod(x_shape) + math.prod(y_shape),
            kh * kw * x_shape[-1] * cout + 8 * cout)
