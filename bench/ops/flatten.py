"""``flatten``: every axis after the batch into one.

    {"op": "flatten"}
"""
import math


def shape(layer: dict, in_shapes: list) -> tuple:
    return (math.prod(in_shapes[0]),)


def macs(layer: dict, in_shapes: list) -> int:
    return 0


def graph(b, layer: dict, inputs: list, qweight) -> str:
    (h,) = b.add_node("Flatten", inputs, 1, {"axis": 1})
    return h


def reference(layer: dict, xs: list, w, control):
    return xs[0].reshape(xs[0].shape[0], -1)
