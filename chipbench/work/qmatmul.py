"""Int8 matrix products: FULLY_CONNECTED layers and CONV_2D layers (which
the program lowers through im2col) run on the ``qmatmul`` Pallas kernel.

True work is counted on the layer as the graph states it, unpadded: the
layer's operations (``ops/<kind>.py``: a direct convolution's multiply-adds
over its real input channels), and bytes as int8 activations in and out
once per row plus int8 weights and an int32 bias and multiplier per output
channel once per call. Lane padding, the im2col copy and the padded
contraction are not work, so they lower the share.
"""
import math

from chipbench.model import op_module

# op names of this class's kernels in the device trace (found by substring)
KERNELS = ("qmatmul",)
LAYERS = ("conv", "fc")


def work(layer, x_shape, y_shape) -> tuple:
    """(operations per row, activation bytes per row, bytes per call)."""
    cout = y_shape[-1]
    kh, kw = layer["kernel"] if layer["op"] == "conv" else (1, 1)
    return (op_module(layer["op"]).ops(layer, x_shape, y_shape),
            math.prod(x_shape) + math.prod(y_shape),
            kh * kw * x_shape[-1] * cout + 8 * cout)
