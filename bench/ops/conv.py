"""``conv``: a 2-D convolution (NCHW, square kernel).

    {"op": "conv", "cin", "cout", "k", "stride", "pad", "group",
     "w_bits", "w_scale_log2", "act"}

Weights (``weight_shape``) are OIHW.
"""
import functools

import jax.numpy as jnp
from jax import lax

from bench.reference.forward import HIGHEST, contract


def weight_shape(layer: dict) -> tuple:
    return (layer["cout"], layer["cin"] // layer["group"],
            layer["k"], layer["k"])


def shape(layer: dict, in_shapes: list) -> tuple:
    _, h, w = in_shapes[0]
    k, s, p = layer["k"], layer["stride"], layer["pad"]
    return (layer["cout"], (h + 2 * p - k) // s + 1, (w + 2 * p - k) // s + 1)


def macs(layer: dict, in_shapes: list) -> int:
    c, h, w = shape(layer, in_shapes)
    return c * h * w * (layer["cin"] // layer["group"]) * layer["k"] ** 2


def graph(b, layer: dict, inputs: list, qweight: str) -> str:
    k, p, s = layer["k"], layer["pad"], layer["stride"]
    attrs = {"strides": [s, s], "pads": [p, p, p, p], "kernel_shape": [k, k]}
    if layer["group"] > 1:
        attrs["group"] = layer["group"]
    (h,) = b.add_node("Conv", [inputs[0], qweight], 1, attrs)
    return h


def reference(layer: dict, xs: list, w, control):
    s, p = layer["stride"], layer["pad"]
    fn = functools.partial(
        lax.conv_general_dilated, window_strides=(s, s),
        padding=[(p, p), (p, p)], dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=layer["group"], precision=HIGHEST,
        preferred_element_type=jnp.float32)
    return contract(fn, xs[0], w, control)
