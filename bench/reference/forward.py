"""Plain fp32 ``jax.numpy`` forward of the QONNX semantics.

It walks the generic layer list of ``bench/layers.py`` with nothing of
the program, each op computed by its file's ``reference`` (``bench/ops/``)
and then the layer's ``act``, from the helpers here: ``Quant`` is clip(round_half_even(x / s), lo, hi)
* s (zero point 0), ``BipolarQuant`` is s * sign (with sign(0) = +1), and
every contraction runs at ``Precision.HIGHEST``: on a TPU an fp32 matmul
otherwise runs in bf16 passes.

``control`` names a lower-precision copy that the ``correct`` comparison
has to reject (each configuration's ``check.control`` names its own):

* ``"int4"``: every 8-bit quantizer computed at 4 bits (same range,
  scale * 16), the int4-for-int8 step;
* ``"bf16"``: every contraction's operands rounded to bfloat16 and summed
  in fp32, as an fp32 matmul at default precision runs on a TPU;
* ``"high"``: every contraction in three bf16 passes summed in fp32
  (hi*hi + hi*lo + lo*hi, each operand split into a bf16 head and a bf16
  tail), as ``Precision.HIGH`` runs on a TPU; written out, so that it
  reads the same on a CPU.  The head is cut from the fp32 bits, because
  XLA drops an fp32 -> bf16 -> fp32 round trip as excess precision.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench import layers as L

HIGHEST = lax.Precision.HIGHEST
CONTROLS = (None, "int4", "bf16", "high")


def _range(bits: int, signed: bool, narrow: bool) -> tuple:
    if signed:
        return -(2 ** (bits - 1)) + int(narrow), 2 ** (bits - 1) - 1
    return 0, 2 ** bits - 1 - int(narrow)


def _lowered(bits: int, scale_log2: int, control) -> tuple:
    if control == "int4" and bits == 8:
        return 4, scale_log2 + 4
    return bits, scale_log2


def quant(x, bits: int, scale_log2: int, signed: bool, narrow: bool = False,
          control=None):
    bits, scale_log2 = _lowered(bits, scale_log2, control)
    s = jnp.float32(2.0 ** scale_log2)
    lo, hi = _range(bits, signed, narrow)
    return jnp.clip(jnp.round(x / s), lo, hi) * s


def bipolar(x, scale_log2: int):
    return jnp.float32(2.0 ** scale_log2) * jnp.where(x >= 0, 1.0, -1.0)


def _act(h, params, control):
    """A layer's ``act``: optional ReLU, then its quantizer."""
    if params is None:
        return h
    if params["relu"]:
        h = jnp.maximum(h, 0.0)
    if params["bits"] == 1:
        return bipolar(h, params["scale_log2"])
    return quant(h, params["bits"], params["scale_log2"], params["signed"],
                 control=control)


def _qweight(layer: dict, w, control):
    """The layer's float weight through its quantizer."""
    if layer["w_bits"] == 1:
        return bipolar(w, layer["w_scale_log2"])
    return quant(w, layer["w_bits"], layer["w_scale_log2"], True,
                 narrow=True, control=control)


def _split(a) -> tuple:
    bits = lax.bitcast_convert_type(a, jnp.uint32) & jnp.uint32(0xFFFF0000)
    head = lax.bitcast_convert_type(bits, jnp.float32)   # exact in bf16
    return head.astype(jnp.bfloat16), (a - head).astype(jnp.bfloat16)


def contract(fn, h, wq, control):
    """``fn(a, b)`` (an fp32-accumulating contraction) under ``control``."""
    if control == "bf16":
        return fn(h.astype(jnp.bfloat16), wq.astype(jnp.bfloat16))
    if control == "high":
        (h1, h2), (w1, w2) = _split(h), _split(wq)
        return fn(h1, w1) + fn(h1, w2) + fn(h2, w1)
    return fn(h, wq)


def forward(layers: list[dict], weights: list, x, *, control=None):
    """Logits of the batch ``x`` (N, C, H, W); ``weights`` as
    ``layers.float_weights`` gives them (None for weightless layers)."""
    if control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}")

    def step(i, layer, ins):
        w = weights[i]
        if w is not None:
            w = _qweight(layer, w, control)
        y = L.op(layer["op"]).reference(layer, ins, w, control)
        return _act(y, layer.get("act"), control)
    return L.walk(layers, x.astype(jnp.float32), step)[-1]


class Reference:
    """The forward, jitted once per row block, run block by block so that
    it fits beside nothing else on the device once the program is gone."""

    def __init__(self, layers: list[dict], codes: list, *, block: int = 256,
                 control=None):
        self.block = block
        self.weights = [None if c is None else
                        jnp.asarray(L.float_weights(layer, c))
                        for layer, c in zip(layers, codes)]
        self._fn = jax.jit(functools.partial(forward, layers,
                                             control=control))

    def __call__(self, xs: np.ndarray) -> np.ndarray:
        outs = []
        with jax.default_matmul_precision("highest"):
            for i in range(0, len(xs), self.block):
                chunk = xs[i:i + self.block]
                n = len(chunk)
                if n < self.block:          # one compiled block shape
                    chunk = np.concatenate(
                        [chunk, np.zeros((self.block - n,) + chunk.shape[1:],
                                         chunk.dtype)])
                outs.append(np.asarray(self._fn(self.weights,
                                                jnp.asarray(chunk)))[:n])
        return np.concatenate(outs)
