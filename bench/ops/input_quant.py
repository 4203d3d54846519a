"""``input_quant``: the network input's quantizer.

    {"op": "input_quant", "bits", "signed", "scale_log2"}
"""
from bench.reference.forward import quant


def shape(layer: dict, in_shapes: list) -> tuple:
    return tuple(in_shapes[0])


def macs(layer: dict, in_shapes: list) -> int:
    return 0


def graph(b, layer: dict, inputs: list, qweight) -> str:
    return b.quant(inputs[0], 2.0 ** layer["scale_log2"], 0.0,
                   layer["bits"], signed=layer["signed"])


def reference(layer: dict, xs: list, w, control):
    return quant(xs[0], layer["bits"], layer["scale_log2"], layer["signed"],
                 control=control)
