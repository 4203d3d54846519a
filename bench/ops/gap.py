"""``gap``: global average pooling to (C, 1, 1).

    {"op": "gap"}
"""
import jax.numpy as jnp


def shape(layer: dict, in_shapes: list) -> tuple:
    return (in_shapes[0][0], 1, 1)


def macs(layer: dict, in_shapes: list) -> int:
    return 0


def graph(b, layer: dict, inputs: list, qweight) -> str:
    (h,) = b.add_node("GlobalAveragePool", inputs, 1)
    return h


def reference(layer: dict, xs: list, w, control):
    return jnp.mean(xs[0], axis=(2, 3), keepdims=True)
