"""The QONNX graph the program serves, built from the generic layer list.

Each layer's nodes come from its op file (``bench/ops/<op>.py``), fed the
tensors of the layers it reads (``layers.walk``), and end in the layer's
``act`` where it has one.  Weights are ``code * 2**w_scale_log2`` with
codes from ``layers.draw_weights``; each goes through a narrow ``Quant``
(or a ``BipolarQuant`` at 1 bit) of the same scale, as a Brevitas export
writes it, so the graph's quantized weights are exactly the drawn ones.
"""
from __future__ import annotations

from repro.core.graph import GraphBuilder, QonnxGraph

from bench import layers as L


def _act(b: GraphBuilder, h: str, params) -> str:
    """A layer's ``act``: ``[Relu ->] Quant`` or ``BipolarQuant``."""
    if params is None:
        return h
    if params["relu"]:
        (h,) = b.add_node("Relu", [h], 1)
    scale = 2.0 ** params["scale_log2"]
    if params["bits"] == 1:
        return b.bipolar_quant(h, scale)
    return b.quant(h, scale, 0.0, params["bits"], signed=params["signed"])


def _qweight(b: GraphBuilder, layer: dict, code) -> str:
    """The layer's weight initializer through its quantizer."""
    w = b.add_initializer("w", L.float_weights(layer, code))
    scale = 2.0 ** layer["w_scale_log2"]
    if layer["w_bits"] == 1:
        return b.bipolar_quant(w, scale)
    return b.quant(w, scale, 0.0, layer["w_bits"], narrow=True)


def build_graph(name: str, layers: list[dict], codes: list,
                input_shape) -> QonnxGraph:
    b = GraphBuilder(name)

    def step(i, layer, ins):
        qw = None if codes[i] is None else _qweight(b, layer, codes[i])
        h = L.op(layer["op"]).graph(b, layer, ins, qw)
        return _act(b, h, layer.get("act"))
    outs = L.walk(layers, b.add_input("x", (1,) + tuple(input_shape)), step)
    b.mark_output(outs[-1])
    return b.build()
