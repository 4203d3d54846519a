"""The layer table every part of the benchmark reads.

A configuration file (``bench/configs/<name>.json``) names a ``family``;
``bench/models/<family>.py`` expands the file's compact table into the
generic layer list below (``spec.Bench.layers``).  From that one list
come the QONNX graph the program serves (``bench/graph.py``), the plain
reference
(``bench/reference/forward.py``), the weights drawn from the seed, and the
operation counts behind ``plan_mfu``.

Generic layers (dicts, in order):

    {"op": "input_quant", "bits", "signed", "scale_log2"}
    {"op": "conv", "cin", "cout", "k", "stride", "pad", "group",
     "w_bits", "w_scale_log2", "act"}
    {"op": "fc", "cin", "cout", "w_bits", "w_scale_log2", "act"}
    {"op": "maxpool", "k", "stride"}
    {"op": "gap"}
    {"op": "flatten"}

``act`` is None (float output) or {"relu": bool, "bits", "signed",
"scale_log2"}; ``bits == 1`` means ``BipolarQuant`` with that scale.
Every scale is a power of two, 2**scale_log2.
"""
from __future__ import annotations

import numpy as np


def weight_shape(layer: dict) -> tuple:
    if layer["op"] == "conv":
        return (layer["cout"], layer["cin"] // layer["group"],
                layer["k"], layer["k"])
    return (layer["cin"], layer["cout"])          # MatMul (K, N)


def draw_weights(layers: list[dict], seed: int) -> list:
    """Integer weight codes per layer (None where a layer has no weights).

    1-bit layers draw {-1, +1}; b-bit layers draw uniformly from the
    narrow signed range [-(2**(b-1) - 1), 2**(b-1) - 1], which is what a
    narrow ``Quant`` keeps, so quantizing ``code * scale`` is exact.
    """
    rng = np.random.default_rng([int(seed), 0])
    out = []
    for layer in layers:
        if layer["op"] not in ("conv", "fc"):
            out.append(None)
            continue
        shape, bits = weight_shape(layer), layer["w_bits"]
        if bits == 1:
            codes = rng.integers(0, 2, shape, dtype=np.int8) * 2 - 1
        else:
            q = 2 ** (bits - 1) - 1
            codes = rng.integers(-q, q + 1, shape, dtype=np.int8)
        out.append(codes.astype(np.int8))
    return out


def float_weights(layer: dict, codes: np.ndarray) -> np.ndarray:
    return codes.astype(np.float32) * np.float32(2.0 ** layer["w_scale_log2"])


def shapes(layers: list[dict], input_shape) -> list[tuple]:
    """Per-sample output shape of every layer."""
    shp = tuple(input_shape)
    out = []
    for layer in layers:
        op = layer["op"]
        if op == "conv":
            c, h, w = shp
            k, s, p = layer["k"], layer["stride"], layer["pad"]
            shp = (layer["cout"], (h + 2 * p - k) // s + 1,
                   (w + 2 * p - k) // s + 1)
        elif op == "maxpool":
            c, h, w = shp
            k, s = layer["k"], layer["stride"]
            shp = (c, (h - k) // s + 1, (w - k) // s + 1)
        elif op == "gap":
            shp = (shp[0], 1, 1)
        elif op == "flatten":
            shp = (int(np.prod(shp)),)
        elif op == "fc":
            shp = (layer["cout"],)
        out.append(shp)
    return out


def macs_per_layer(layers: list[dict], input_shape) -> list[int]:
    """Multiply-accumulates per sample of each layer (0 for non-MAC ops)."""
    out = []
    for layer, shp in zip(layers, shapes(layers, input_shape)):
        if layer["op"] == "conv":
            k = layer["k"]
            out.append(shp[0] * shp[1] * shp[2] *
                       (layer["cin"] // layer["group"]) * k * k)
        elif layer["op"] == "fc":
            out.append(layer["cin"] * layer["cout"])
        else:
            out.append(0)
    return out


def count(layers: list[dict], input_shape) -> dict:
    """MACs and weights per sample: all of them, and those beyond the
    first MAC layer (the 8-bit-input layer Table III counts apart)."""
    mac_layers = [i for i, layer in enumerate(layers)
                  if layer["op"] in ("conv", "fc")]
    macs = macs_per_layer(layers, input_shape)
    weights = [int(np.prod(weight_shape(layers[i]))) for i in mac_layers]
    return {"macs": sum(macs), "macs_beyond_first": sum(macs) -
            macs[mac_layers[0]], "weights": sum(weights),
            "weights_beyond_first": sum(weights[1:])}
