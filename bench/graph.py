"""The QONNX graph the program serves, built from the generic layer list.

Weights are ``code * 2**w_scale_log2`` with codes from
``layers.draw_weights``; each goes through a narrow ``Quant`` (or a
``BipolarQuant`` at 1 bit) of the same scale, as a Brevitas export writes
it, so the graph's quantized weights are exactly the drawn ones.
"""
from __future__ import annotations

from repro.core.graph import GraphBuilder, QonnxGraph

from bench import layers as L


def _act(b: GraphBuilder, h: str, act) -> str:
    if act is None:
        return h
    if act["relu"]:
        (h,) = b.add_node("Relu", [h], 1)
    scale = 2.0 ** act["scale_log2"]
    if act["bits"] == 1:
        return b.bipolar_quant(h, scale)
    return b.quant(h, scale, 0.0, act["bits"], signed=act["signed"])


def build_graph(name: str, layers: list[dict], codes: list,
                input_shape) -> QonnxGraph:
    b = GraphBuilder(name)
    h = b.add_input("x", (1,) + tuple(input_shape))
    for layer, code in zip(layers, codes):
        op = layer["op"]
        if op == "input_quant":
            h = b.quant(h, 2.0 ** layer["scale_log2"], 0.0, layer["bits"],
                        signed=layer["signed"])
        elif op in ("conv", "fc"):
            w = b.add_initializer("w", L.float_weights(layer, code))
            scale = 2.0 ** layer["w_scale_log2"]
            if layer["w_bits"] == 1:
                qw = b.bipolar_quant(w, scale)
            else:
                qw = b.quant(w, scale, 0.0, layer["w_bits"], narrow=True)
            if op == "conv":
                k, p, s = layer["k"], layer["pad"], layer["stride"]
                attrs = {"strides": [s, s], "pads": [p, p, p, p],
                         "kernel_shape": [k, k]}
                if layer["group"] > 1:
                    attrs["group"] = layer["group"]
                (h,) = b.add_node("Conv", [h, qw], 1, attrs)
            else:
                (h,) = b.add_node("MatMul", [h, qw], 1)
            h = _act(b, h, layer["act"])
        elif op == "maxpool":
            k, s = layer["k"], layer["stride"]
            (h,) = b.add_node("MaxPool", [h], 1,
                              {"kernel_shape": [k, k], "strides": [s, s]})
        elif op == "gap":
            (h,) = b.add_node("GlobalAveragePool", [h], 1)
        elif op == "flatten":
            (h,) = b.add_node("Flatten", [h], 1, {"axis": 1})
        else:
            raise ValueError(f"unknown layer op {op!r}")
    b.mark_output(h)
    return b.build()
