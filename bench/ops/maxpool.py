"""``maxpool``: a square max-pool, its border padded with -inf.

    {"op": "maxpool", "k", "stride", "pad" (default 0)}

The graph writes ``pads`` only where ``pad`` is not 0.
"""
import jax.numpy as jnp
from jax import lax


def shape(layer: dict, in_shapes: list) -> tuple:
    c, h, w = in_shapes[0]
    k, s, p = layer["k"], layer["stride"], layer.get("pad", 0)
    return (c, (h + 2 * p - k) // s + 1, (w + 2 * p - k) // s + 1)


def macs(layer: dict, in_shapes: list) -> int:
    return 0


def graph(b, layer: dict, inputs: list, qweight) -> str:
    k, s, p = layer["k"], layer["stride"], layer.get("pad", 0)
    attrs = {"kernel_shape": [k, k], "strides": [s, s]}
    if p:
        attrs["pads"] = [p, p, p, p]
    (h,) = b.add_node("MaxPool", inputs, 1, attrs)
    return h


def reference(layer: dict, xs: list, w, control):
    k, s, p = layer["k"], layer["stride"], layer.get("pad", 0)
    return lax.reduce_window(xs[0], -jnp.inf, lax.max, (1, 1, k, k),
                             (1, 1, s, s), ((0, 0), (0, 0), (p, p), (p, p)))
