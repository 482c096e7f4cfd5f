"""A configuration's model: its layer shapes, its float weights, and the
program's int8 graph built from them.

The float weights are drawn by the benchmark from the configuration's own
weight seed, so the plain reference (``chipbench.reference``) and the
program start from the same numbers without the reference taking anything
the program made.

The layers form a graph: a layer reads the outputs its ``inputs`` key
names, or the previous layer's output without it (see :func:`graph`), so a
branch and its skip connection are written as layers that name their
inputs. Every walk over the layers (shapes, weights, the program's graph,
the reference) goes through :func:`walk`.
"""
import importlib

import numpy as np


INPUT = "input"  # the name by which a layer reads the graph input


def op_module(kind: str):
    return importlib.import_module(f"chipbench.ops.{kind}")


def arity(mod) -> int:
    """Inputs a layer kind takes: ``INPUTS`` of its module, 1 by default."""
    return getattr(mod, "INPUTS", 1)


def graph(cfg) -> list:
    """[(layer, names of the outputs it reads)] in order.

    A layer reads the outputs its ``inputs`` key names: earlier layers'
    ``name``s, or ``INPUT`` for the graph input. Without the key it reads
    the previous layer's output (the first layer, the graph input). The
    last layer's output is the graph's. A name that is no earlier layer's,
    a name used twice and a count of inputs its kind does not take are
    refused, naming the layer."""
    seen, prev, out = {INPUT}, INPUT, []
    for i, layer in enumerate(cfg["layers"]):
        name = layer.get("name")
        if not isinstance(name, str) or name in seen:
            raise ValueError(f"layer {i} ({name!r}): every layer needs a "
                             f"name of its own, other than {INPUT!r}")
        names = layer.get("inputs", [prev])
        if not isinstance(names, list) or not names:
            raise ValueError(f"layer {name!r}: 'inputs' must be a list of "
                             f"names")
        for n in names:
            if n not in seen:
                raise ValueError(f"layer {name!r} reads {n!r}, which is not "
                                 f"an earlier layer or {INPUT!r}")
        want = arity(op_module(layer["op"]))
        if len(names) != want:
            raise ValueError(f"layer {name!r} reads {len(names)} inputs; "
                             f"{layer['op']!r} takes {want}")
        seen.add(name)
        prev = name
        out.append((layer, names))
    return out


def walk(cfg, x, step) -> list:
    """[(layer, its input, its output)] in order, where ``step(layer, mod,
    input)`` gives a layer's output and ``x`` is the graph input's. A
    layer's input is the one output it reads, or the tuple of them for a
    kind of several inputs; the values may be shapes, tensors or arrays."""
    values, out = {INPUT: x}, []
    for layer, names in graph(cfg):
        mod = op_module(layer["op"])
        args = tuple(values[n] for n in names)
        xin = args[0] if arity(mod) == 1 else args
        y = values[layer["name"]] = step(layer, mod, xin)
        out.append((layer, xin, y))
    return out


def layer_shapes(cfg) -> list:
    """[(layer, in_shape, out_shape)] per row, in order; ``in_shape`` is a
    tuple of shapes for a kind of several inputs."""
    def shape(layer, mod, x_shape):
        try:
            return tuple(mod.shape(layer, x_shape))
        except ValueError as e:
            raise ValueError(f"layer {layer['name']!r}: {e}") from None
    return walk(cfg, tuple(cfg["input_shape"]), shape)


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
    weights = {layer["name"]: p
               for (layer, _), p in zip(graph(cfg), params)}
    built = walk(cfg, gb.input("x", (1,) + tuple(cfg["input_shape"])),
                 lambda layer, mod, x: mod.build(gb, x, layer,
                                                 weights[layer["name"]]))
    gb.output(built[-1][2])
    cal = cfg["calibration"]
    rng = np.random.default_rng(cal["seed"])
    rep = [draw_inputs(cfg, rng, 1) for _ in range(cal["samples"])]
    return quantize_graph(gb.build(), rep)
