"""RESHAPE of a row to one flat vector, in row-major (NHWC) order."""
import math


def shape(layer, x_shape):
    return (math.prod(x_shape),)


def ops(layer, x_shape, y_shape):
    """A reshape does no arithmetic."""
    return 0


def init(rng, layer, x_shape):
    return {}


def build(gb, x, layer, p):
    shp = gb.g.tensor(x).shape
    return gb.reshape(x, (shp[0], math.prod(shp[1:])), name=layer["name"])


def ref(x, layer, p):
    return x.reshape(x.shape[0], -1)
