"""The layer list every part of the benchmark reads.

A configuration file (``bench/configs/<name>.json``) names a ``family``;
``bench/models/<family>.py`` expands the file's compact table into the
generic layer list below (``spec.Bench.layers``).  From that one list
come the QONNX graph the program serves (``bench/graph.py``), the plain
reference (``bench/reference/forward.py``), the weights drawn from the
seed, and the operation counts behind ``plan_mfu``.

Generic layers are dicts, in order.  Each names its ``op``, and
``bench/ops/<op>.py`` defines it (an op without that file is an error):

    {"op": "input_quant", "bits", "signed", "scale_log2"}
    {"op": "conv", "cin", "cout", "k", "stride", "pad", "group",
     "w_bits", "w_scale_log2", "act"}
    {"op": "fc", "cin", "cout", "w_bits", "w_scale_log2", "act"}
    {"op": "maxpool", "k", "stride", "pad" (default 0)}
    {"op": "gap"}
    {"op": "flatten"}
    {"op": "add", "act"}             the sum of its two inputs

An op file gives ``shape(layer, in_shapes)`` (per-sample output shape),
``macs(layer, in_shapes)``, ``graph(builder, layer, inputs, qweight)``
(writes its QONNX nodes, returns the output tensor) and
``reference(layer, xs, w, control)`` (plain fp32 ``jax.numpy``); an op
with a weight also gives ``weight_shape(layer)``, and its layer carries
``w_bits`` and ``w_scale_log2``.

A layer reads the previous layer's output (the first reads the network
input), or, where it carries ``"in": [i, ...]``, the outputs of those
earlier layers, so that the list can branch and merge; index -1 is the
network input.  The list's output is the last layer's.

A layer that carries ``act`` ends in it (the walkers add it after the
op): None (float output) or {"relu": bool, "bits", "signed",
"scale_log2"}; ``bits == 1`` means ``BipolarQuant`` with that scale.
Every scale is a power of two, 2**scale_log2.
"""
from __future__ import annotations

import functools
import os

import numpy as np

from bench import spec

OPS_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "ops")


@functools.lru_cache(maxsize=None)
def op(name: str):
    """The module of ``bench/ops/<name>.py``."""
    path = os.path.join(OPS_DIR, f"{name}.py")
    if not os.path.isfile(path):
        raise ValueError(f"unknown layer op {name!r}: no file "
                         f"bench/ops/{name}.py")
    return spec.load_module(path, spec.module_name("op", name))


def sources(i: int, layer: dict) -> list[int]:
    """Indices of the layers whose outputs layer ``i`` reads."""
    src = list(layer.get("in", [i - 1]))
    if not src or not all(-1 <= j < i for j in src):
        raise ValueError(f"layer {i} ({layer['op']}) reads {src}: each "
                         f"input must be an earlier layer or -1")
    return src


def walk(layers: list[dict], x, step) -> list:
    """Every layer's output, ``step(i, layer, ins)``: ``ins`` are the
    outputs it reads, ``x`` standing for the network input."""
    outs = []
    for i, layer in enumerate(layers):
        ins = [x if j < 0 else outs[j] for j in sources(i, layer)]
        outs.append(step(i, layer, ins))
    return outs


def has_weight(layer: dict) -> bool:
    return hasattr(op(layer["op"]), "weight_shape")


def weight_shape(layer: dict) -> tuple:
    return tuple(op(layer["op"]).weight_shape(layer))


def draw_weights(layers: list[dict], seed: int) -> list:
    """Integer weight codes per layer (None where a layer has no weights).

    1-bit layers draw {-1, +1}; b-bit layers draw uniformly from the
    narrow signed range [-(2**(b-1) - 1), 2**(b-1) - 1], which is what a
    narrow ``Quant`` keeps, so quantizing ``code * scale`` is exact.
    """
    rng = np.random.default_rng([int(seed), 0])
    out = []
    for layer in layers:
        if not has_weight(layer):
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


def _shape(i: int, layer: dict, ins: list) -> tuple:
    return tuple(op(layer["op"]).shape(layer, ins))


def shapes(layers: list[dict], input_shape) -> list[tuple]:
    """Per-sample output shape of every layer."""
    return walk(layers, tuple(input_shape), _shape)


def macs_per_layer(layers: list[dict], input_shape) -> list[int]:
    """Multiply-accumulates per sample of each layer (0 for non-MAC ops)."""
    out = []

    def step(i, layer, ins):
        out.append(int(op(layer["op"]).macs(layer, ins)))
        return _shape(i, layer, ins)
    walk(layers, tuple(input_shape), step)
    return out


def count(layers: list[dict], input_shape) -> dict:
    """MACs and weights per sample: all of them, and those beyond the
    first layer with a weight (the 8-bit-input layer Table III counts
    apart)."""
    mac_layers = [i for i, layer in enumerate(layers) if has_weight(layer)]
    macs = macs_per_layer(layers, input_shape)
    weights = [int(np.prod(weight_shape(layers[i]))) for i in mac_layers]
    return {"macs": sum(macs), "macs_beyond_first": sum(macs) -
            macs[mac_layers[0]], "weights": sum(weights),
            "weights_beyond_first": sum(weights[1:])}
