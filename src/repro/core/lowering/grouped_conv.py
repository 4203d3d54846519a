"""Lowering rule: grouped/depthwise quantized Conv onto dedicated kernels.

Same graph pattern as the dense conv rule (``lowering/conv.py``):

    Quant|BipolarQuant|QCDQ(w) -> Conv [-> Relu] [-> Quant(act)]

but anchored *before* it (priority 15 < 20), claiming the ``group > 1``
convs the dense rule would otherwise lower through a block-diagonal im2col
carrier at O(groups) wasted MACs and carrier bytes.  Two kernel targets:

  * ``group == cin`` with multiplier 1 (MobileNet's depthwise layers) —
    ``kernels.quant_depthwise_conv2d``: a VPU per-channel kH·kW
    tap-accumulate with the whole dequant -> bias -> ReLU -> requant
    epilogue fused in-kernel (the trailing Quant's constants are staged by
    the same ``stage_qdq_epilogue`` helper the QDQ rule uses, so the
    realization is bit-identical);
  * moderate group counts (2..``MAX_BLOCKED_GROUPS``) —
    ``kernels.quant_grouped_conv2d``: group-outermost K/N-blocked integer
    matmul where each group's patch slice contracts only against its own
    (I/g·kH·kW, O/g) weight block, int4 packing threaded per group.

Both reuse the shared weight-chain resolution (``match_conv_common`` /
``lowering/weights.py``) and the analysis tier's zero-padding-aware
``GraphAnalysis.kernel_accumulator`` bound — the bound already contracts
per output channel over the true I/g·kH·kW receptive field, so the
accumulator width is group-exact too.

Group counts neither kernel takes (``group > MAX_BLOCKED_GROUPS`` with a
channel multiplier) simply decline: the dense rule's block-diagonal carrier
remains the correct fallback.  Each emitted segment records the MACs and
carrier bytes reclaimed vs that fallback in its meta
(``reclaimed_macs`` / ``carrier_bytes_saved``), which
``CompiledPlan.grouped_conv_stats`` aggregates for the cost report, the
serving engine's load telemetry, and the bench_compile ``--check-grouped``
CI gate.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional

import jax.numpy as jnp
import numpy as np

from ..graph import Node, QonnxGraph
from .base import (LoweringContext, LoweringRule, Segment, conv_out_rows,
                   register_rule, select_accumulator, select_operand)
from .conv import ActQuantParams, QuantConvMatch, match_conv_common
from .qdq import stage_qdq_epilogue
from .requant import select_requant
from .weights import stage_kernel_carriers

# beyond this the per-group blocked kernel's group-outermost grid stops
# being a win over one dense block-diagonal matmul (tiny per-group tiles,
# G× grid steps); such convs decline and keep the dense fallback — except
# depthwise, whose VPU kernel is O(C) and scales to any channel count
MAX_BLOCKED_GROUPS = 64


@dataclass
class GroupedConvMatch(QuantConvMatch):
    """Dense conv match payload + the grouped-carrier bookkeeping.

    ``w_int`` holds the per-group carrier (G, Kg, Ng) — or the depthwise
    tap matrix (kH·kW, C) when ``depthwise``."""
    depthwise: bool = False
    reclaimed_macs: int = 0          # vs the block-diagonal dense carrier
    dense_int4_ok: bool = False      # would the dense fallback have packed?


def _out_spatial(g: QonnxGraph, node: Node) -> int:
    """Output positions of one sample (OH·OW), 0 when shapes are unknown."""
    shape = g.get_shape(node.outputs[0])
    if shape is None or len(shape) < 3:
        return 0
    n = 1
    for d in shape[2:]:
        if d is None:
            return 0
        n *= int(d)
    return n


@register_rule
class GroupedConvRule(LoweringRule):
    name = "quant_grouped_conv"
    anchor_ops = ("Conv",)
    priority = 15                    # tried before the dense conv rule

    def match(self, g: QonnxGraph, node: Node,
              ctx: LoweringContext) -> Optional[GroupedConvMatch]:
        from repro.kernels.quant_grouped_conv import (depthwise_weights,
                                                      grouped_weights)

        nb = match_conv_common(g, node, ctx)
        if nb is None or nb.group <= 1:
            return None              # dense rule's territory
        o, ipg, kh, kw = nb.qw.w_int.shape
        depthwise = ipg == 1 and o == nb.group
        if not depthwise and nb.group > MAX_BLOCKED_GROUPS:
            return None              # block-diagonal dense fallback

        if depthwise:
            w_carrier = depthwise_weights(nb.qw.w_int)     # (kH·kW, C)
            int4_ok = False          # kH·kW taps: nothing worth packing
        else:
            w_carrier = grouped_weights(nb.qw.w_int, nb.group)  # (G, Kg, Ng)
            int4_ok = nb.qw.int4_values and (ipg * kh * kw) % 2 == 0

        # what the dense block-diagonal fallback would spend extra: each of
        # the g-1 foreign groups contributes ipg·kH·kW zero rows per output
        # channel — both carrier entries and (per output position) MACs.
        # The fallback's int4 eligibility (dense K = C·kH·kW evenness, the
        # quant_conv rule's own gate) prices its carrier bytes honestly.
        saved_entries = (nb.group - 1) * ipg * kh * kw * o
        dense_int4_ok = nb.qw.int4_values and \
            (ipg * nb.group * kh * kw) % 2 == 0
        m = GroupedConvMatch(
            nb.nodes, node.inputs[0], nb.out, w_carrier, nb.scale, nb.bias,
            int4_ok, rows=conv_out_rows(g, node),
            kernel_shape=nb.kernel_shape, strides=nb.strides,
            pads=nb.pads, dilations=nb.dilations, group=nb.group,
            relu=nb.relu, act=nb.act, depthwise=depthwise,
            reclaimed_macs=saved_entries * _out_spatial(g, node),
            dense_int4_ok=dense_int4_ok)
        # conv-shaped weights: the bound contracts the true I/g·kH·kW field
        select_accumulator(ctx, node, m, w_int=nb.qw.w_int)
        # per-channel |w| sums in natural O order == the group-major order
        # of the (O,) scale (ONNX grouped convs number channels group-major)
        select_requant(ctx, g, node, m,
                       w_absum=np.abs(nb.qw.w_int.astype(np.int64))
                       .sum(axis=(1, 2, 3)),
                       relu=nb.relu, act=nb.act)
        if not depthwise:        # the depthwise kernel never uses the MXU
            select_operand(ctx, m)
        if getattr(ctx, "use_fusion", True):
            from . import fusion
            m.carrier_accepts = (m.x,)
            # the depthwise fp32 path realizes the act Quant *inside* the
            # kernel (no emit_codes hook) — only the requant path and the
            # blocked kernel's external epilogue can produce codes
            if nb.act is not None and (m.requant is not None
                                       or not depthwise):
                m.carrier_out = fusion.carrier_from_act(nb.act)
        return m

    def emit(self, idx: int, m: GroupedConvMatch, consts: dict,
             ctx: LoweringContext) -> Segment:
        from repro.kernels import ops as kernel_ops
        from . import fusion

        cin, cout = fusion.fusion_carriers(ctx, m.x, m.out)
        kinds = ("quant_conv_dw",) * 2 if m.depthwise else \
            ("quant_conv_grouped", "quant_conv_grouped_int4")
        kind, use_int4, w_key, s_key, b_key, meta, blocks = \
            stage_kernel_carriers(
                idx, m, consts, ctx, kinds, pack=kernel_ops.pack_int4_grouped)
        keys = [w_key, s_key] + ([b_key] if b_key else [])

        act: Optional[ActQuantParams] = m.act
        qs_key = qz_key = None
        qdq = None
        if act is not None and m.requant is None:
            # identical staging to the QDQ rule; the depthwise kernel
            # consumes the staged consts in its fused epilogue instead of a
            # separate quant_dequant call
            qdq, (qs_key, qz_key), _ = stage_qdq_epilogue(
                idx, consts, ctx, scale=act.scale, zero_point=act.zero_point,
                bit_width=act.bit_width, signed=act.signed, narrow=act.narrow,
                rounding_mode=act.rounding_mode,
                emit_codes=cout is not None)
            keys += [qs_key, qz_key]

        x_name, out_name = m.x, m.out
        # integer path: relu + act Quant live inside the IntRequant spec;
        # the run closure only performs the exact x / s_x division
        relu = m.relu and m.requant is None
        spec = None if m.requant is None else m.requant.spec
        in_scale = None if m.requant is None else m.requant.in_scale
        # requant-path carrier output: exact code recovery off the proven
        # power-of-two act grid (see conv.py)
        code_mul = code_zp = None
        if cout is not None and spec is not None:
            code_mul = np.float32(2.0 ** spec.act_out_shift)
            code_zp = np.float32(spec.act_zp)
        if m.depthwise:
            conv = functools.partial(
                kernel_ops.quant_depthwise_conv2d,
                kernel_shape=m.kernel_shape, strides=m.strides, pads=m.pads,
                dilations=m.dilations, relu=relu, interpret=ctx.interpret,
                acc_dtype=m.acc_dtype, requant=spec,
                act_bits=None if act is None or spec is not None
                else act.bit_width,
                act_signed=act.signed if act else True,
                act_narrow=act.narrow if act else False,
                act_rounding=act.rounding_mode if act else "ROUND",
                **({} if blocks is None else {"block": tuple(blocks)}))

            def run(consts, env):
                x = env.get(x_name, consts.get(x_name))
                if cin is not None:
                    x = fusion.boundary_values(x, cin)
                if in_scale is not None:
                    x = x.astype(jnp.float32) / in_scale
                y = conv(
                    x, consts[w_key], consts[s_key],
                    consts[b_key] if b_key else None,
                    consts[qs_key] if qs_key else None,
                    consts[qz_key] if qz_key else None)
                if cout is not None:
                    y = fusion.boundary_out(
                        jnp.round(y * code_mul + code_zp).astype(jnp.int8),
                        cout)
                env[out_name] = y
        else:
            x_int8 = m.x_int8
            conv = functools.partial(
                kernel_ops.quant_grouped_conv2d, groups=m.group,
                kernel_shape=m.kernel_shape, strides=m.strides, pads=m.pads,
                dilations=m.dilations, packed=use_int4,
                interpret=ctx.interpret, acc_dtype=m.acc_dtype, requant=spec,
                **({} if blocks is None else {"blocks": tuple(blocks)}))

            def run(consts, env):
                x = env.get(x_name, consts.get(x_name))
                if cin is not None:
                    x = fusion.boundary_values(x, cin)
                if in_scale is not None:
                    x = x.astype(jnp.float32) / in_scale
                if x_int8:      # proven integral within int8: exact cast
                    x = x.astype(jnp.int8)
                y = conv(x, consts[w_key], consts[s_key],
                         consts[b_key] if b_key else None)
                if relu:
                    y = jnp.maximum(y, 0.0)
                if qdq is not None:
                    y2 = qdq(y.reshape(y.shape[0], -1),
                             consts[qs_key], consts[qz_key])
                    y = y2.reshape(y.shape)
                if cout is not None:
                    if code_mul is not None:
                        y = jnp.round(y * code_mul + code_zp).astype(jnp.int8)
                    y = fusion.boundary_out(y, cout)
                env[out_name] = y

            meta["patch_layout"] = kernel_ops.patch_layout(m.kernel_shape,
                                                           m.pads)
        meta["group"] = m.group
        meta["reclaimed_macs"] = m.reclaimed_macs
        # bytes = dense fallback's carrier (C·kH·kW·O entries at *its* int4
        # eligibility) minus this segment's (the true per-group entries at
        # the staged width); never negative since dense entries = g× ours
        own_entries = m.w_int.size
        meta["carrier_bytes_saved"] = int(
            own_entries * m.group * (0.5 if m.dense_int4_ok else 1.0) -
            own_entries * (0.5 if use_int4 else 1.0))
        if cin is not None or cout is not None:
            fusion._carrier_meta(meta, cin, cout)
        return Segment(kind, m.nodes, [x_name], [out_name], run,
                       tuple(keys), meta)
