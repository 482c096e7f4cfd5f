"""A configuration's model: its layer shapes, its float weights, and the
program's int8 graph built from them.

The float weights are drawn by the benchmark from the configuration's own
weight seed, so the plain reference (``chipbench.reference``) and the
program start from the same numbers without the reference taking anything
the program made.
"""
import importlib

import numpy as np


def op_module(kind: str):
    return importlib.import_module(f"chipbench.ops.{kind}")


def layer_shapes(cfg) -> list:
    """[(layer, in_shape, out_shape)] per row, in order."""
    out, shp = [], tuple(cfg["input_shape"])
    for layer in cfg["layers"]:
        nxt = tuple(op_module(layer["op"]).shape(layer, shp))
        out.append((layer, shp, nxt))
        shp = nxt
    return out


def ops_per_row(cfg) -> int:
    """True arithmetic operations of one row over every layer, unpadded."""
    return sum(op_module(layer["op"]).ops(layer, x_shape, y_shape)
               for layer, x_shape, y_shape in layer_shapes(cfg))


def make_weights(cfg) -> list:
    """Float32 weights of every layer, drawn from ``cfg["weight_seed"]``."""
    rng = np.random.default_rng(cfg["weight_seed"])
    return [op_module(layer["op"]).init(rng, layer, x_shape)
            for layer, x_shape, _ in layer_shapes(cfg)]


def draw_inputs(cfg, rng, n: int) -> np.ndarray:
    """``n`` float32 rows of the configuration's input distribution."""
    dist = cfg["input_dist"]
    shape = (n,) + tuple(cfg["input_shape"])
    if dist["kind"] == "normal":
        return rng.normal(dist["mean"], dist["std"], shape).astype("float32")
    if dist["kind"] == "normal_per_row":
        # each row its own level and contrast, as frames of different
        # scenes and exposures have: the answers then differ row by row
        lead = (n,) + (1,) * len(cfg["input_shape"])
        mean = rng.uniform(*dist["mean"], lead)
        std = rng.uniform(*dist["std"], lead)
        return (mean + std * rng.standard_normal(shape)).astype("float32")
    raise ValueError(f"unknown input distribution {dist['kind']!r}")


def build_int8(cfg, params):
    """The program's int8 graph (batch 1), quantized by the program's own
    calibration over ``cfg["calibration"]`` samples."""
    from repro.core.builder import GraphBuilder
    from repro.core.quantize import quantize_graph

    gb = GraphBuilder(cfg["name"])
    x = gb.input("x", (1,) + tuple(cfg["input_shape"]))
    for (layer, _, _), p in zip(layer_shapes(cfg), params):
        x = op_module(layer["op"]).build(gb, x, layer, p)
    gb.output(x)
    cal = cfg["calibration"]
    rng = np.random.default_rng(cal["seed"])
    rep = [draw_inputs(cfg, rng, 1) for _ in range(cal["samples"])]
    return quantize_graph(gb.build(), rep)
