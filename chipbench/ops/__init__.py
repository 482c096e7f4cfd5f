"""One module per layer kind of a configuration's ``layers`` list.

Each module gives, for its layer kind:

* ``shape(layer, x_shape)``: the per-row output shape;
* ``ops(layer, x_shape, y_shape)``: the true arithmetic operations of one
  row on the unpadded shapes (a multiply-add counts two);
* ``init(rng, layer, x_shape)``: the float32 weights, drawn from ``rng``
  (a dict of arrays, empty for layers without weights);
* ``build(gb, x, layer, params)``: the layer added to the program's
  ``GraphBuilder`` ``gb``, returning the output tensor id;
* ``ref(x, layer, params)``: the plain float32 forward pass in
  ``jax.numpy`` over a batch of rows, which imports nothing of the program;
* ``WEIGHT_AXIS``: for layers with weights, the output-channel axis of
  ``params["w"]``.

A layer reads the previous layer's output, or the outputs its ``inputs``
key names: earlier layers' ``name``s, or ``"input"`` for the graph input
(``chipbench.model.graph``). So a skip connection is a layer whose
``inputs`` name the block's output and the block's input, and a
projection shortcut a 1x1 ``conv`` whose ``inputs`` name the block's
input. A kind that takes several inputs sets ``INPUTS`` to their number;
its functions then get the tuple of input shapes (``x_shapes``), tensor
ids (``xs`` in ``build``) or values (``xs`` in ``ref``), in the order of
``inputs``, where a kind of one input gets the one (``add.py``). A layer
naming another number of inputs than its kind takes is refused.

A configuration that needs a new layer kind adds a module here.
"""
import jax.numpy as jnp

FUSED = {"none": "NONE", "relu": "RELU", "relu6": "RELU6"}


def out_hw(h, w, kh, kw, stride, padding):
    """Output height and width of a TF-style SAME or VALID window."""
    if padding == "SAME":
        return -(-h // stride), -(-w // stride)
    return (h - kh) // stride + 1, (w - kw) // stride + 1


def act_ref(y, act):
    if act == "relu":
        return jnp.maximum(y, 0.0)
    if act == "relu6":
        return jnp.clip(y, 0.0, 6.0)
    if act == "none":
        return y
    raise ValueError(f"unknown activation {act!r}")
