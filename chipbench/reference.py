"""The plain reference: a configuration's float32 forward pass in
``jax.numpy``, layer by layer, run on the host CPU.

It imports nothing of the program and takes nothing the program made: the
weights come from ``chipbench.model.make_weights`` and the inputs are the
float rows the load generator drew before it quantized them.

``bits`` turns the same pass into the control: weights quantized per output
channel and every activation per tensor to ``bits``-bit integers
(asymmetric, over the ranges the calibration rows reach), which is how an
int8 program computes, at a lower precision. The control with ``bits=4``
is the int4 computation that the comparison in ``chipbench.check`` must
refuse.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.model import graph, op_module, walk

BLOCK_ROWS = 256  # rows per reference call, so the host never holds more


def _fq_act(x, lo, hi, bits):
    lo, hi = min(lo, 0.0), max(hi, 0.0)
    levels = 2 ** bits - 1
    scale = max(hi - lo, 1e-6) / levels
    zp = np.round(-lo / scale)
    q = jnp.clip(jnp.round(x / scale) + zp, 0, levels)
    return (q - zp) * scale


def _fq_weight(w, axis, bits):
    qmax = 2 ** (bits - 1) - 1
    red = tuple(i for i in range(w.ndim) if i != axis)
    scale = np.maximum(np.abs(w).max(axis=red, keepdims=True), 1e-9) / qmax
    return np.clip(np.round(w / scale), -qmax - 1, qmax) * scale


class Reference:
    """The float32 forward pass of ``cfg`` with weights ``params``."""

    def __init__(self, cfg, params):
        self.cfg, self.params = cfg, params
        self.layers = [(layer, op_module(layer["op"]))
                       for layer, _ in graph(cfg)]
        self._fns: dict = {}

    def _fn(self, bits, ranges):
        key = (bits, None if ranges is None else tuple(map(tuple, ranges)))
        if key not in self._fns:
            self._fns[key] = jax.jit(functools.partial(
                self._forward, bits=bits, ranges=ranges))
        return self._fns[key]

    def _forward(self, params, x, *, bits, ranges):
        # with ``bits``, each output is quantized as it is made, and its
        # later readers get the quantized value, as in an int8 program
        acts = [x]
        if bits:
            x = _fq_act(x, *ranges[0], bits)
        weights = {layer["name"]: p
                   for (layer, _), p in zip(self.layers, params)}

        def step(layer, mod, xin):
            y = mod.ref(xin, layer, weights[layer["name"]])
            if bits:
                y = _fq_act(y, *ranges[len(acts)], bits)
            acts.append(y)
            return y
        return walk(self.cfg, x, step)[-1][2], acts

    def _run(self, fn, params, x):
        cpu = jax.devices("cpu")[0]
        with jax.default_device(cpu):
            return fn(jax.device_put(params, cpu), jax.device_put(x, cpu))

    def calibrate(self, x) -> list:
        """(lo, hi) of the input and of every layer's output over rows
        ``x``."""
        _, acts = self._run(self._fn(0, None), self.params, x)
        return [(float(jnp.min(a)), float(jnp.max(a))) for a in acts]

    def forward(self, x, *, bits: int = 0, ranges=None) -> np.ndarray:
        """Float outputs for float rows ``x``, in blocks of
        ``BLOCK_ROWS``. ``bits`` > 0 computes the ``bits``-bit control over
        the activation ``ranges`` that :meth:`calibrate` gives."""
        params = self.params
        if bits:
            params = [dict(p, w=_fq_weight(p["w"], mod.WEIGHT_AXIS, bits)
                           .astype(np.float32)) if "w" in p else p
                      for (_, mod), p in zip(self.layers, params)]
        fn = self._fn(bits, ranges)
        outs = [np.asarray(self._run(fn, params, x[i:i + BLOCK_ROWS])[0])
                for i in range(0, len(x), BLOCK_ROWS)]
        return np.concatenate(outs)
