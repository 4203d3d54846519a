"""Lowering rule: quantized Conv -> im2col onto the integer matmul kernels.

Pattern (anchored at the Conv):

    Quant|BipolarQuant|QCDQ(w) -> Conv [-> Relu] [-> Quant(act)]

This is the lowering the conv-dominated Table III workloads need: CNV is
57.9M MACs of 3x3 convs and MobileNet-w4a4 is 557M MACs of depthwise +
pointwise convs, and until this rule every one of them ran on the
interpreted fallback.

How it lowers (FINN-R / TVM-quantization style):

  * the integer conv weights (O, I/g, kH, kW) are reshaped **at compile
    time** into a (kH·kW·C, O) matmul operand, rows ordered (kh, kw, c)
    with the channel fastest (``kernels.im2col_weights``) — block-diagonal
    over the groups within each tap for grouped/depthwise convs, so the
    MXU kernels see one dense int8/int4 carrier;
  * at trace time the activation is unfolded into im2col patches in the
    same feature order and fed through ``kernels.quant_conv2d`` ->
    ``quant_matmul[_int4]``; stride, padding, dilation and 1x1-pointwise
    all reduce to how the patches are sliced, and the segment's
    ``meta["patch_layout"]`` names the build (``kernels.patch_layout``);
  * a trailing Relu fuses as a max(0, ·) epilogue, and a trailing
    per-tensor activation Quant fuses as a ``quant_dequant`` kernel call on
    the still-2D matmul output — the common Conv->Relu->Quant block of the
    zoo models becomes exactly one segment;
  * the accumulator dtype comes from the analysis tier's zero-padding-aware
    conv dot-product bound (``GraphAnalysis.kernel_accumulator`` with the
    *conv-shaped* integer weights — border windows replace taps with 0 and
    the bound accounts for it).

Grouped/depthwise convs normally lower through the dedicated per-group /
depthwise kernels (``lowering/grouped_conv.py``, priority 15, i.e. tried
first); this dense rule's block-diagonal carrier is the **fallback** for
group counts those kernels decline — correct for any ``group``, at
O(groups) extra MACs/carrier bytes.

``match_conv_common`` holds the shared half of the pattern — attribute
gates, the Quant/BipolarQuant/QCDQ weight-chain resolution
(``lowering/weights.py``), scale-granularity checks, bias, and the
[-> Relu] [-> Quant] epilogue absorption — so the grouped rule matches the
exact same graph neighbourhoods and differs only in carrier layout and
kernel choice.

Unsupported shapes (NHWC layout, auto_pad, per-input-channel scales,
non-constant weights/bias, 1-D/3-D convs) simply don't match and stay on
the interpreted path — the registry makes that fallback free.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import jax.numpy as jnp
import numpy as np

from ..graph import Node, QonnxGraph
from .base import (LoweringContext, LoweringRule, Segment, conv_channel_scale,
                   conv_out_rows, register_rule, select_accumulator,
                   select_operand, sole_consumer, static_value)
from .qdq import stage_qdq_epilogue, static_act_quant_params
from .requant import select_requant
from .weights import (KernelMatch, QuantWeight, chain_absorbable,
                      resolve_quant_weight, stage_kernel_carriers)


@dataclass
class ActQuantParams:
    """Static per-tensor activation-Quant params fused as an epilogue."""
    scale: np.ndarray
    zero_point: np.ndarray
    bit_width: float
    signed: bool
    narrow: bool
    rounding_mode: str


@dataclass
class ConvNeighbourhood:
    """Shared result of ``match_conv_common``: the resolved weight chain,
    normalized conv attributes, and the absorbed epilogue — everything a
    conv-lowering rule needs except its carrier layout."""
    qw: QuantWeight
    nodes: list[Node]            # covered nodes (chain? + conv + epilogue)
    out: str                     # tensor the fused segment produces
    scale: np.ndarray            # () or per-output-channel (O,)
    bias: Optional[np.ndarray]
    kernel_shape: tuple
    strides: tuple
    pads: tuple
    dilations: tuple
    group: int
    relu: bool
    act: Optional[ActQuantParams]


def _act_quant_params(g: QonnxGraph, node: Node) -> Optional[ActQuantParams]:
    """Fusable activation Quant epilogue: the QDQ rule's static-param gate
    (qdq.static_act_quant_params) tightened to *per-tensor* scale/zp —
    channelwise act scales would sit on the non-minor channel axis of NCHW,
    those stay on the QDQ rule / interp path."""
    params = static_act_quant_params(g, node)
    if params is None:
        return None
    s, z, nb, signed, narrow, rmode = params
    if s.size != 1 or z.size != 1:
        return None
    return ActQuantParams(
        np.asarray(s, np.float32).reshape(-1),
        np.asarray(z, np.float32).reshape(-1), nb, signed, narrow, rmode)


def match_conv_common(g: QonnxGraph, node: Node,
                      ctx: LoweringContext) -> Optional[ConvNeighbourhood]:
    """The carrier-agnostic half of the quantized-Conv pattern.

    Resolves the weight chain, validates attributes/granularities, and
    absorbs the [-> Relu] [-> Quant] epilogue.  Returns None when the Conv
    can't lower onto *any* integer-carrier kernel; the caller decides the
    carrier layout (dense im2col, per-group, depthwise taps)."""
    if node.attrs.get("data_layout", "NCHW") != "NCHW":
        return None
    if node.attrs.get("auto_pad", "NOTSET") != "NOTSET":
        return None
    qw = resolve_quant_weight(g, node.inputs[1], ctx.analysis)
    if qw is None or qw.w_int.ndim != 4:
        return None                           # 2-D convs only
    o, ipg, kh, kw = qw.w_int.shape
    group = int(node.attrs.get("group", 1))
    if group < 1 or o % group:
        return None
    ks = tuple(int(v) for v in node.attrs.get("kernel_shape", (kh, kw)))
    if ks != (kh, kw):
        return None
    strides = tuple(int(v) for v in node.attrs.get("strides", (1, 1)))
    pads = tuple(int(v) for v in node.attrs.get("pads", (0, 0, 0, 0)))
    dilations = tuple(int(v) for v in node.attrs.get("dilations", (1, 1)))
    if len(strides) != 2 or len(pads) != 4 or len(dilations) != 2:
        return None
    scale = conv_channel_scale(qw.scale, qw.w_int.shape)
    if scale is None:
        return None
    bias = None
    if len(node.inputs) > 2 and node.inputs[2]:
        b = static_value(g, node.inputs[2])
        if b is None or b.size != o:
            return None
        bias = np.asarray(b, np.float32).reshape(-1)

    nodes = list(qw.chain) + [node] if chain_absorbable(g, qw.chain, node) \
        else [node]

    # epilogue absorption: [-> Relu] [-> Quant(act)]
    out = node.outputs[0]
    relu = False
    act = None
    nxt = sole_consumer(g, out)
    if nxt is not None and nxt.op_type == "Relu":
        relu = True
        nodes.append(nxt)
        out = nxt.outputs[0]
        nxt = sole_consumer(g, out)
    if nxt is not None and nxt.op_type == "Quant":
        act = _act_quant_params(g, nxt)
        if act is not None:
            nodes.append(nxt)
            out = nxt.outputs[0]

    return ConvNeighbourhood(
        qw, nodes, out, np.asarray(scale, np.float32), bias,
        ks, strides, pads, dilations, group, relu, act)


@dataclass
class QuantConvMatch(KernelMatch):
    kernel_shape: tuple = (1, 1)
    strides: tuple = (1, 1)
    pads: tuple = (0, 0, 0, 0)
    dilations: tuple = (1, 1)
    group: int = 1
    relu: bool = False
    act: Optional[ActQuantParams] = None


@register_rule
class QuantConvRule(LoweringRule):
    name = "quant_conv"
    anchor_ops = ("Conv",)
    priority = 20

    def match(self, g: QonnxGraph, node: Node,
              ctx: LoweringContext) -> Optional[QuantConvMatch]:
        from repro.kernels.quant_conv import im2col_weights

        nb = match_conv_common(g, node, ctx)
        if nb is None:
            return None
        w2 = im2col_weights(nb.qw.w_int, nb.group)     # (kH·kW·C, O) int8
        int4_ok = nb.qw.int4_values and w2.shape[0] % 2 == 0

        m = QuantConvMatch(
            nb.nodes, node.inputs[0], nb.out, w2, nb.scale, nb.bias, int4_ok,
            rows=conv_out_rows(g, node),
            kernel_shape=nb.kernel_shape, strides=nb.strides, pads=nb.pads,
            dilations=nb.dilations, group=nb.group, relu=nb.relu, act=nb.act)
        # zero-padding-aware bound wants the conv-shaped weights, not the
        # staged im2col matrix
        select_accumulator(ctx, node, m, w_int=nb.qw.w_int)
        select_requant(ctx, g, node, m,
                       w_absum=np.abs(nb.qw.w_int.astype(np.int64))
                       .sum(axis=(1, 2, 3)),
                       relu=nb.relu, act=nb.act)
        select_operand(ctx, m)
        if getattr(ctx, "use_fusion", True):
            from . import fusion
            m.carrier_accepts = (m.x,)
            if nb.act is not None:
                m.carrier_out = fusion.carrier_from_act(nb.act)
        return m

    def emit(self, idx: int, m: QuantConvMatch, consts: dict,
             ctx: LoweringContext) -> Segment:
        from repro.kernels import ops as kernel_ops
        from . import fusion

        cin, cout = fusion.fusion_carriers(ctx, m.x, m.out)
        kind, use_int4, w_key, s_key, b_key, meta, blocks = \
            stage_kernel_carriers(
                idx, m, consts, ctx, ("quant_conv", "quant_conv_int4"))
        conv = functools.partial(
            kernel_ops.quant_conv2d, kernel_shape=m.kernel_shape,
            strides=m.strides, pads=m.pads, dilations=m.dilations,
            packed=use_int4, interpret=ctx.interpret, acc_dtype=m.acc_dtype,
            requant=None if m.requant is None else m.requant.spec,
            **({} if blocks is None else {"blocks": tuple(blocks)}))

        keys = [w_key, s_key] + ([b_key] if b_key else [])
        qdq = None
        if m.act is not None and m.requant is None:
            qdq, (qs_key, qz_key), _ = stage_qdq_epilogue(
                idx, consts, ctx, scale=m.act.scale,
                zero_point=m.act.zero_point, bit_width=m.act.bit_width,
                signed=m.act.signed, narrow=m.act.narrow,
                rounding_mode=m.act.rounding_mode,
                emit_codes=cout is not None)
            keys += [qs_key, qz_key]
        x_name, out_name = m.x, m.out
        # integer path: relu and the activation Quant are folded into the
        # kernel's IntRequant epilogue; only the exact x / s_x remains here
        relu = m.relu and m.requant is None
        in_scale = None if m.requant is None else m.requant.in_scale
        x_int8 = m.x_int8
        # integer-boundary output off the requant path: the kernel emitted
        # s_a*(q - z_a) with a proven power-of-two s_a = 2**-T_a, so the
        # codes are recovered exactly as q = y*2**T_a + z_a
        code_mul = code_zp = None
        if cout is not None and m.requant is not None:
            code_mul = np.float32(2.0 ** m.requant.spec.act_out_shift)
            code_zp = np.float32(m.requant.spec.act_zp)

        def run(consts, env):
            x = env.get(x_name, consts.get(x_name))
            if cin is not None:
                x = fusion.boundary_values(x, cin)
            if in_scale is not None:
                x = x.astype(jnp.float32) / in_scale
            if x_int8:          # proven integral within int8: exact cast
                x = x.astype(jnp.int8)
            y = conv(x, consts[w_key], consts[s_key],
                     consts[b_key] if b_key else None)
            if relu:
                y = jnp.maximum(y, 0.0)
            if qdq is not None:
                # still elementwise: run the QDQ kernel on a 2-D view
                y2 = qdq(y.reshape(y.shape[0], -1),
                         consts[qs_key], consts[qz_key])
                y = y2.reshape(y.shape)
            if cout is not None:
                if code_mul is not None:
                    y = jnp.round(y * code_mul + code_zp).astype(jnp.int8)
                y = fusion.boundary_out(y, cout)
            env[out_name] = y

        meta["patch_layout"] = kernel_ops.patch_layout(m.kernel_shape, m.pads)
        if m.group > 1:
            meta["group"] = m.group
        if cin is not None or cout is not None:
            fusion._carrier_meta(meta, cin, cout)
        return Segment(kind, m.nodes, [x_name], [out_name], run,
                       tuple(keys), meta)
