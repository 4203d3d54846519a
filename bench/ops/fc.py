"""``fc``: a ``MatMul`` of the flat input with a (cin, cout) weight.

    {"op": "fc", "cin", "cout", "w_bits", "w_scale_log2", "act"}
"""
import functools

import jax.numpy as jnp

from bench.reference.forward import HIGHEST, contract


def weight_shape(layer: dict) -> tuple:
    return (layer["cin"], layer["cout"])


def shape(layer: dict, in_shapes: list) -> tuple:
    return (layer["cout"],)


def macs(layer: dict, in_shapes: list) -> int:
    return layer["cin"] * layer["cout"]


def graph(b, layer: dict, inputs: list, qweight: str) -> str:
    (h,) = b.add_node("MatMul", [inputs[0], qweight], 1)
    return h


def reference(layer: dict, xs: list, w, control):
    fn = functools.partial(jnp.dot, precision=HIGHEST,
                           preferred_element_type=jnp.float32)
    return contract(fn, xs[0], w, control)
