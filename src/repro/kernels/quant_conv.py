"""Quantized 2-D convolution on the Pallas quant-matmul tier (im2col).

The MXU has no native convolution: the TPU-idiomatic lowering (and the one
FINN-R / Jain-et-al. use for their quantized compilers) is im2col — turn
every conv into a matmul whose contraction axis is the flattened receptive
field, then reuse the integer weight-carrier kernels that already exist:

  * **compile time** (``im2col_weights``): the integer conv weights
    (O, I/g, kH, kW) are reshaped once into a (C·kH·kW, O) matmul operand.
    Grouped / depthwise convs (MobileNet's ``group=cin`` layers) become a
    block-diagonal matrix — the off-block zeros contribute nothing to the
    dot product and pack to zero nibbles on the int4 path, so the carrier
    stays a plain dense operand the MXU kernels understand.  That trades
    O(groups) extra MACs/carrier bytes for kernel reuse; a dedicated
    grouped kernel is a ROADMAP item and slots in as a rule swap.
  * **trace time** (``extract_patches``): the activation is unfolded into a
    (N·OH·OW, C·kH·kW) patch matrix with one strided slice per kernel tap —
    kH·kW static slices that XLA fuses into the producing kernel, keeping
    the data movement on-chip rather than materializing a gather.  Zero
    padding is applied before slicing, which is exactly the padding
    convention the zero-padding-aware accumulator bound in
    ``repro.analysis`` models.
  * the patch matrix then rides ``quant_matmul`` / ``quant_matmul_int4``
    unchanged: packed sub-nibble weights unpack inside the kernel, the
    accumulator dtype is analysis-selected, and the per-output-channel
    dequant scale applies at the last K step.

``quant_conv2d`` is the fused wrapper the compiled executor's Conv lowering
rule (core/lowering/conv.py) emits; it accepts NCHW activations and returns
NCHW, so the segment slots into the graph exactly where the Conv node was.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .quant_matmul import DEFAULT_BLOCKS, quant_matmul, quant_matmul_int4


def im2col_weights(w, groups: int = 1) -> np.ndarray:
    """Conv weights (O, I/g, kH, kW) -> matmul operand (I·kH·kW, O).

    Row order is (c, kh, kw) with the input channel varying slowest — the
    same order ``extract_patches`` emits its feature axis in.  For grouped
    convolution the result is block-diagonal over the groups: group ``gi``'s
    input-channel rows only connect to its own output-channel columns, all
    other entries are exactly 0 (offline, dtype-preserving — int8 carriers
    stay int8).
    """
    w = np.asarray(w)
    o, ipg, kh, kw = w.shape
    if o % groups:
        raise ValueError(f"output channels {o} not divisible by groups {groups}")
    wm = w.reshape(o, ipg * kh * kw)
    if groups == 1:
        return np.ascontiguousarray(wm.T)
    cin = ipg * groups
    opg = o // groups
    kg = ipg * kh * kw
    out = np.zeros((cin * kh * kw, o), w.dtype)
    for gi in range(groups):
        out[gi * kg:(gi + 1) * kg, gi * opg:(gi + 1) * opg] = \
            wm[gi * opg:(gi + 1) * opg].T
    return out


def conv_tap_slices(x, kernel_shape, strides=(1, 1), pads=(0, 0, 0, 0),
                    dilations=(1, 1)):
    """Zero-pad NCHW ``x`` and take its kH·kW strided/dilated tap slices.

    The one implementation of the conv unfold geometry — the dense im2col
    path (``extract_patches``) and the depthwise path
    (``quant_grouped_conv.extract_depthwise_taps``) differ only in how they
    lay the taps out afterwards.  Returns ``(taps, (OH, OW))`` with taps a
    list of kH·kW arrays, each (N, C, OH, OW), in (kh, kw) row-major
    order.  ``pads`` is ONNX [top, left, bottom, right]; padded positions
    are exactly 0, matching both the interpreted Conv and the analysis
    tier's zero-pad-widened dot-product bound.
    """
    kh, kw = (int(v) for v in kernel_shape)
    sh, sw = (int(v) for v in strides)
    dh, dw = (int(v) for v in dilations)
    pt, pl, pb, pr = (int(v) for v in pads)
    xp = jnp.pad(x, ((0, 0), (0, 0), (pt, pb), (pl, pr)))
    hp, wp = xp.shape[2], xp.shape[3]
    oh = (hp - (dh * (kh - 1) + 1)) // sh + 1
    ow = (wp - (dw * (kw - 1) + 1)) // sw + 1
    taps = []
    for i in range(kh):
        for j in range(kw):
            taps.append(xp[:, :,
                           i * dh: i * dh + sh * (oh - 1) + 1: sh,
                           j * dw: j * dw + sw * (ow - 1) + 1: sw])
    return taps, (oh, ow)


@jax.named_scope("im2col")
def extract_patches(x, kernel_shape, strides=(1, 1), pads=(0, 0, 0, 0),
                    dilations=(1, 1)):
    """Unfold NCHW ``x`` into an im2col patch matrix.

    Returns ``(patches, (OH, OW))`` where patches has shape
    (N·OH·OW, C·kH·kW), feature axis ordered (c, kh, kw) with c slowest —
    matching ``im2col_weights``.  Its ops carry the ``im2col`` named scope
    in their HLO metadata, which the benchmark's trace reduction reads.
    """
    n, c, h, w = x.shape
    kh, kw = (int(v) for v in kernel_shape)
    sh, sw = (int(v) for v in strides)
    if kh == kw == 1 and tuple(int(v) for v in pads) == (0, 0, 0, 0):
        # pointwise fast path: no unfold, just (optional) stride subsampling
        xs = x[:, :, ::sh, ::sw]
        oh, ow = xs.shape[2], xs.shape[3]
        return (jnp.transpose(xs, (0, 2, 3, 1)).reshape(n * oh * ow, c),
                (oh, ow))
    taps, (oh, ow) = conv_tap_slices(x, kernel_shape, strides, pads,
                                     dilations)
    p = jnp.stack(taps, axis=2)                  # (N, C, kH·kW, OH, OW)
    p = jnp.transpose(p, (0, 3, 4, 1, 2))        # (N, OH, OW, C, kH·kW)
    return p.reshape(n * oh * ow, c * kh * kw), (oh, ow)


def mxu_operand(x):
    """Activations as the matmul kernels take them: int8 codes unchanged
    (the MXU's int8 path), anything else as f32."""
    x = jnp.asarray(x)
    return x if x.dtype == jnp.int8 else x.astype(jnp.float32)


def quant_conv2d(x, w2, w_scale, bias=None, *, kernel_shape, strides=(1, 1),
                 pads=(0, 0, 0, 0), dilations=(1, 1), packed=False,
                 blocks=DEFAULT_BLOCKS, interpret=None,
                 out_dtype=jnp.float32, acc_dtype=jnp.float32, requant=None):
    """Fused quantized conv: im2col patches through the integer matmul kernels.

    x        — (N, C, H, W) activations (any float dtype, cast to f32;
               int8 codes pass through to the MXU's int8 path)
    w2       — im2col'd integer weights: (C·kH·kW, O) int8, or the int4
               packing thereof (C·kH·kW // 2, O) when ``packed``
    w_scale  — dequant scale, scalar or per-output-channel (O,)
    bias     — optional (O,) f32, applied per output channel
    requant  — optional ``IntRequant``: integer dyadic epilogue; ``w_scale``
               then carries int32 multipliers (see ``quant_matmul``)
    Returns (N, O, OH, OW) in ``out_dtype``.
    """
    x = mxu_operand(x)
    patches, (oh, ow) = extract_patches(x, kernel_shape, strides, pads,
                                        dilations)
    mm = quant_matmul_int4 if packed else quant_matmul
    y = mm(patches, w2, w_scale, bias, blocks=blocks, interpret=interpret,
           out_dtype=out_dtype, acc_dtype=acc_dtype, requant=requant)
    y = y.reshape(x.shape[0], oh, ow, y.shape[-1])
    return jnp.transpose(y, (0, 3, 1, 2))
