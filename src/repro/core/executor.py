"""Node-level execution engine for QonnxGraph.

Mirrors the paper's §V utility: "model execution is based on a node-level
execution in Python ... not meant to provide high performance, but to ensure
that model outputs can be verified through execution."  Every op is executed
with jnp, which buys us two things for free:

  * the engine doubles as the *oracle* for lowering passes and kernels, and
  * running it under ``jax.eval_shape`` gives whole-graph shape inference
    (see transforms.infer_shapes) with zero extra per-op shape logic.

Channels-last execution: shape-dependent ops (Conv, pools, BatchNormalization)
honor an optional ``data_layout`` attribute ("NCHW" default, "NHWC" after the
channels-last transform) — the paper's "wrapper nodes ... so that channels
last networks can be executed" (§V).

This engine is the *interpreted tier*; the hot path is ``compile.py``,
which partitions a graph into fused segments over the Pallas kernels and
jits the whole plan, using this registry only as its fallback (and as the
parity oracle — see tests/test_compile.py).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from . import quant_ops
from .graph import QonnxGraph, Node

OpFn = Callable[..., object]
_OP_REGISTRY: dict[tuple[str, str], OpFn] = {}


def register_op(op_type: str, domain: str = ""):
    def deco(fn):
        _OP_REGISTRY[(op_type, domain)] = fn
        return fn
    return deco


def lookup_op(node: Node) -> OpFn:
    key = (node.op_type, node.domain)
    if key in _OP_REGISTRY:
        return _OP_REGISTRY[key]
    # fall back to domain-less registration (QONNX ops are sometimes exported
    # with an empty domain by frontends)
    if (node.op_type, "") in _OP_REGISTRY:
        return _OP_REGISTRY[(node.op_type, "")]
    # last resort: any-domain match, lowest domain string wins so the choice
    # is deterministic rather than dict-insertion-order dependent
    candidates = sorted(dom for (op, dom) in _OP_REGISTRY
                        if op == node.op_type)
    if candidates:
        return _OP_REGISTRY[(node.op_type, candidates[0])]
    raise NotImplementedError(f"no executor for op {node.op_type!r} (domain {node.domain!r})")


def execute(graph: QonnxGraph, inputs: dict[str, jnp.ndarray],
            return_all: bool = False) -> dict[str, jnp.ndarray]:
    """Execute the graph node-by-node; returns {output_name: value}."""
    env: dict[str, object] = {k: jnp.asarray(v) for k, v in graph.initializers.items()}
    for t in graph.inputs:
        if t.name not in inputs:
            raise ValueError(f"missing graph input {t.name!r}")
    env.update({k: jnp.asarray(v) for k, v in inputs.items()})
    for node in graph.toposort():
        fn = lookup_op(node)
        args = [env[i] if i else None for i in node.inputs]
        out = fn(node, *args)
        if not isinstance(out, tuple):
            out = (out,)
        for name, val in zip(node.outputs, out):
            env[name] = val
    if return_all:
        return env
    return {name: env[name] for name in graph.output_names}


# --------------------------------------------------------------------------
# QONNX domain ops (the paper's contribution)
# --------------------------------------------------------------------------

@register_op("Quant", "qonnx.custom_op.general")
def _quant(node, x, scale, zero_point, bit_width):
    return quant_ops.quant(
        x, scale, zero_point, bit_width,
        signed=bool(node.attrs.get("signed", 1)),
        narrow=bool(node.attrs.get("narrow", 0)),
        rounding_mode=node.attrs.get("rounding_mode", "ROUND"))


@register_op("BipolarQuant", "qonnx.custom_op.general")
def _bipolar_quant(node, x, scale):
    return quant_ops.bipolar_quant(x, scale)


@register_op("Trunc", "qonnx.custom_op.general")
def _trunc(node, x, scale, zero_point, in_bits, out_bits):
    return quant_ops.trunc(
        x, scale, zero_point, in_bits, out_bits,
        rounding_mode=node.attrs.get("rounding_mode", "FLOOR"),
        signed=bool(node.attrs.get("signed", 1)))


@register_op("MultiThreshold", "finn.custom_op.general")
def _multithreshold(node, x, thresholds):
    """FINN-style multistep activation: y = sum_i (x >= T[c, i]).

    thresholds: (channels, n_steps).  out = out_scale * y + out_bias.
    """
    layout = node.attrs.get("data_layout", "NCHW")
    c_axis = 1 if layout == "NCHW" else x.ndim - 1
    shape = [1] * x.ndim
    shape[c_axis] = thresholds.shape[0]
    acc = jnp.zeros_like(x)
    for i in range(thresholds.shape[1]):
        t = thresholds[:, i].reshape(shape)
        acc = acc + (x >= t).astype(x.dtype)
    scale = node.attrs.get("out_scale", 1.0)
    bias = node.attrs.get("out_bias", 0.0)
    return scale * acc + bias


# --------------------------------------------------------------------------
# Standard ONNX ops (the subset the zoo + transforms need)
# --------------------------------------------------------------------------

@register_op("QuantizeLinear")
def _quantize_linear(node, x, scale, zero_point=None):
    zp = 0 if zero_point is None else zero_point
    signed = (zero_point is not None and
              np.issubdtype(np.dtype(jnp.asarray(zp).dtype), np.signedinteger))
    qmin, qmax = (-128, 127) if signed else (0, 255)
    y = jnp.round(x / scale) + jnp.asarray(zp, x.dtype)
    y = jnp.clip(y, qmin, qmax)
    return y.astype(jnp.int8 if signed else jnp.uint8)


@register_op("DequantizeLinear")
def _dequantize_linear(node, y, scale, zero_point=None):
    zp = 0 if zero_point is None else zero_point
    return (y.astype(jnp.float32) - jnp.asarray(zp, jnp.float32)) * scale


@register_op("Clip")
def _clip(node, x, lo=None, hi=None):
    if lo is None:
        lo = node.attrs.get("min", -jnp.inf)
    if hi is None:
        hi = node.attrs.get("max", jnp.inf)
    return jnp.clip(x, jnp.asarray(lo, x.dtype), jnp.asarray(hi, x.dtype))


@register_op("Constant")
def _constant(node):
    return jnp.asarray(node.attrs["value"])


@register_op("Identity")
def _identity(node, x):
    return x


@register_op("Cast")
def _cast(node, x):
    return x.astype(np.dtype(node.attrs.get("to", "float32")))


def _binary(fn):
    def op(node, a, b):
        return fn(a, b)
    return op


register_op("Add")(_binary(jnp.add))
register_op("Sub")(_binary(jnp.subtract))
register_op("Mul")(_binary(jnp.multiply))
register_op("Div")(_binary(jnp.divide))
# f32 contractions pin full precision: on a TPU the default is one bf16
# pass, and the oracle must stay the plain fp32 reference
_HIGHEST = jax.lax.Precision.HIGHEST
register_op("MatMul")(_binary(functools.partial(jnp.matmul,
                                                precision=_HIGHEST)))
register_op("Pow")(_binary(jnp.power))


@register_op("Gemm")
def _gemm(node, a, b, c=None):
    alpha = node.attrs.get("alpha", 1.0)
    beta = node.attrs.get("beta", 1.0)
    if node.attrs.get("transA", 0):
        a = a.T
    if node.attrs.get("transB", 0):
        b = b.T
    y = alpha * jnp.matmul(a, b, precision=_HIGHEST)
    if c is not None:
        y = y + beta * c
    return y


@register_op("MatMulInteger")
def _matmul_integer(node, a, b, a_zp=None, b_zp=None):
    a32 = a.astype(jnp.int32) - (0 if a_zp is None else a_zp.astype(jnp.int32))
    b32 = b.astype(jnp.int32) - (0 if b_zp is None else b_zp.astype(jnp.int32))
    return a32 @ b32


@register_op("Relu")
def _relu(node, x):
    return jax.nn.relu(x)


@register_op("Sigmoid")
def _sigmoid(node, x):
    return jax.nn.sigmoid(x)


@register_op("Tanh")
def _tanh(node, x):
    return jnp.tanh(x)


@register_op("Erf")
def _erf(node, x):
    return jax.scipy.special.erf(x)


@register_op("Softmax")
def _softmax(node, x):
    return jax.nn.softmax(x, axis=node.attrs.get("axis", -1))


@register_op("Reshape")
def _reshape(node, x, shape):
    target = list(np.asarray(shape).astype(np.int64))
    # ONNX semantics: 0 = copy dim from input
    target = [int(x.shape[i]) if d == 0 else int(d) for i, d in enumerate(target)]
    return jnp.reshape(x, target)


@register_op("Transpose")
def _transpose(node, x):
    perm = node.attrs.get("perm")
    return jnp.transpose(x, perm)


@register_op("Flatten")
def _flatten(node, x):
    axis = node.attrs.get("axis", 1)
    lead = int(np.prod(x.shape[:axis])) if axis > 0 else 1
    return jnp.reshape(x, (lead, -1))


@register_op("Concat")
def _concat(node, *xs):
    return jnp.concatenate(xs, axis=node.attrs.get("axis", 0))


@register_op("Shape")
def _shape(node, x):
    return jnp.asarray(x.shape, jnp.int64)


@register_op("Gather")
def _gather(node, x, idx):
    return jnp.take(x, idx.astype(jnp.int32), axis=node.attrs.get("axis", 0))


@register_op("Unsqueeze")
def _unsqueeze(node, x, axes=None):
    ax = node.attrs.get("axes") if axes is None else np.asarray(axes).tolist()
    if not isinstance(ax, (list, tuple)):
        ax = [int(ax)]
    y = x
    for a in sorted(int(v) for v in ax):
        y = jnp.expand_dims(y, a)
    return y


@register_op("Squeeze")
def _squeeze(node, x, axes=None):
    ax = node.attrs.get("axes") if axes is None else np.asarray(axes).tolist()
    if ax is None:
        return jnp.squeeze(x)
    if not isinstance(ax, (list, tuple)):
        ax = [int(ax)]
    return jnp.squeeze(x, axis=tuple(int(v) for v in ax))


@register_op("ReduceMean")
def _reduce_mean(node, x):
    axes = node.attrs.get("axes")
    keep = bool(node.attrs.get("keepdims", 1))
    return jnp.mean(x, axis=tuple(axes) if axes else None, keepdims=keep)


@register_op("BatchNormalization")
def _batchnorm(node, x, gamma, beta, mean, var):
    eps = node.attrs.get("epsilon", 1e-5)
    layout = node.attrs.get("data_layout", "NCHW")
    c_axis = 1 if layout == "NCHW" else x.ndim - 1
    shape = [1] * x.ndim
    shape[c_axis] = x.shape[c_axis]
    g, b = gamma.reshape(shape), beta.reshape(shape)
    m, v = mean.reshape(shape), var.reshape(shape)
    return g * (x - m) / jnp.sqrt(v + eps) + b


def _conv_dims(layout: str, ndim_spatial: int = 2):
    if layout == "NCHW":
        return ("NCHW", "OIHW", "NCHW") if ndim_spatial == 2 else ("NCW", "OIW", "NCW")
    return ("NHWC", "HWIO", "NHWC") if ndim_spatial == 2 else ("NWC", "WIO", "NWC")


@register_op("Conv")
def _conv(node, x, w, b=None):
    layout = node.attrs.get("data_layout", "NCHW")
    nsp = x.ndim - 2
    strides = tuple(node.attrs.get("strides", [1] * nsp))
    dil = tuple(node.attrs.get("dilations", [1] * nsp))
    group = int(node.attrs.get("group", 1))
    pads = node.attrs.get("pads", [0] * (2 * nsp))
    pad_pairs = [(int(pads[i]), int(pads[i + nsp])) for i in range(nsp)]
    if layout == "NHWC" and w.ndim == x.ndim:
        # weights stay OIHW in the model; convert for NHWC execution
        w = jnp.transpose(w, (2, 3, 1, 0)) if nsp == 2 else jnp.transpose(w, (2, 1, 0))
        dn = jax.lax.conv_dimension_numbers(x.shape, w.shape, _conv_dims("NHWC", nsp))
    else:
        dn = jax.lax.conv_dimension_numbers(x.shape, w.shape, _conv_dims("NCHW", nsp))
    y = jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), strides, pad_pairs, lhs_dilation=None,
        rhs_dilation=dil, dimension_numbers=dn, feature_group_count=group,
        precision=_HIGHEST)
    if b is not None:
        c_axis = 1 if layout == "NCHW" else x.ndim - 1
        shape = [1] * y.ndim
        shape[c_axis] = y.shape[c_axis]
        y = y + b.reshape(shape).astype(y.dtype)
    return y


def _pool(node, x, reducer, init, is_avg=False):
    layout = node.attrs.get("data_layout", "NCHW")
    nsp = x.ndim - 2
    k = tuple(node.attrs.get("kernel_shape", [1] * nsp))
    strides = tuple(node.attrs.get("strides", list(k)))
    pads = node.attrs.get("pads", [0] * (2 * nsp))
    pad_pairs = [(int(pads[i]), int(pads[i + nsp])) for i in range(nsp)]
    if layout == "NCHW":
        window = (1, 1) + k
        wstrides = (1, 1) + strides
        padding = [(0, 0), (0, 0)] + pad_pairs
    else:
        window = (1,) + k + (1,)
        wstrides = (1,) + strides + (1,)
        padding = [(0, 0)] + pad_pairs + [(0, 0)]
    y = jax.lax.reduce_window(x, init, reducer, window, wstrides, padding)
    if is_avg:
        # divisors are kept runtime-derived (never constants) so the
        # division stays a true IEEE division when this op is traced into
        # a jitted plan — a constant divisor gets reciprocal-rewritten by
        # XLA, drifting one ulp from eager execution on non-power-of-two
        # counts (see kernels/quant_pool.py for the full rationale)
        if any(p != 0 for pair in pad_pairs for p in pair) and \
                not bool(node.attrs.get("count_include_pad", 0)):
            # ONNX default count_include_pad=0: padded positions do not
            # count toward the divisor, so edge windows divide by the
            # number of *real* elements they cover
            ones = (x == x).astype(jnp.float32)
            counts = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window,
                                           wstrides, padding)
            y = y / counts.astype(y.dtype)
        else:
            y = y / ((y == y).astype(y.dtype) * y.dtype.type(np.prod(k)))
    return y


@register_op("MaxPool")
def _maxpool(node, x):
    return _pool(node, x, jax.lax.max, -jnp.inf)


@register_op("AveragePool")
def _avgpool(node, x):
    return _pool(node, x, jax.lax.add, 0.0, is_avg=True)


@register_op("GlobalAveragePool")
def _gap(node, x):
    layout = node.attrs.get("data_layout", "NCHW")
    axes = tuple(range(2, x.ndim)) if layout == "NCHW" else tuple(range(1, x.ndim - 1))
    return jnp.mean(x, axis=axes, keepdims=True)


@register_op("Pad")
def _pad(node, x, pads=None, value=None):
    p = np.asarray(node.attrs.get("pads") if pads is None else pads).astype(int)
    n = x.ndim
    pairs = [(int(p[i]), int(p[i + n])) for i in range(n)]
    v = 0.0 if value is None else float(np.asarray(value))
    return jnp.pad(x, pairs, constant_values=v)
