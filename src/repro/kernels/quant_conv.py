"""Quantized 2-D convolution on the Pallas quant-matmul tier (im2col).

The MXU has no native convolution: the TPU-idiomatic lowering (and the one
FINN-R / Jain-et-al. use for their quantized compilers) is im2col — turn
every conv into a matmul whose contraction axis is the flattened receptive
field, then reuse the integer weight-carrier kernels that already exist:

  * **compile time** (``im2col_weights``): the integer conv weights
    (O, I/g, kH, kW) are reshaped once into a (kH·kW·C, O) matmul operand,
    rows ordered (kh, kw, c) with the channel fastest.
    Grouped / depthwise convs (MobileNet's ``group=cin`` layers) become a
    block-diagonal matrix — the off-block zeros contribute nothing to the
    dot product and pack to zero nibbles on the int4 path, so the carrier
    stays a plain dense operand the MXU kernels understand.  That trades
    O(groups) extra MACs/carrier bytes for kernel reuse; a dedicated
    grouped kernel is a ROADMAP item and slots in as a rule swap.
  * **trace time** (``extract_patches``): the activation is unfolded into a
    (N·OH·OW, kH·kW·C) patch matrix with one strided slice per kernel tap,
    concatenated on the channel axis — kH·kW static slices that XLA fuses
    into one pass, with the channel on the lanes throughout.  Zero
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
    """Conv weights (O, I/g, kH, kW) -> matmul operand (kH·kW·I, O).

    Row order is (kh, kw, c): the tap varies slowest and the input channel
    fastest — the same order ``extract_patches`` emits its feature axis in.
    For grouped convolution the result is block-diagonal over the groups
    within every tap: row (t, c) only connects to the output-channel
    columns of ``c``'s own group, all other entries are exactly 0 (offline,
    dtype-preserving — int8 carriers stay int8).
    """
    w = np.asarray(w)
    o, ipg, kh, kw = w.shape
    if o % groups:
        raise ValueError(f"output channels {o} not divisible by groups {groups}")
    wt = np.transpose(w, (2, 3, 1, 0))           # (kH, kW, I/g, O)
    if groups == 1:
        return np.ascontiguousarray(wt.reshape(kh * kw * ipg, o))
    opg = o // groups
    out = np.zeros((kh, kw, ipg * groups, o), w.dtype)
    for gi in range(groups):
        out[:, :, gi * ipg:(gi + 1) * ipg, gi * opg:(gi + 1) * opg] = \
            wt[..., gi * opg:(gi + 1) * opg]
    return out.reshape(kh * kw * ipg * groups, o)


def conv_tap_slices(x, kernel_shape, strides=(1, 1), pads=(0, 0, 0, 0),
                    dilations=(1, 1)):
    """Zero-pad NCHW ``x`` and take its kH·kW strided/dilated tap slices.

    The unfold geometry of the depthwise path
    (``quant_grouped_conv.extract_depthwise_taps``); ``extract_patches``
    takes the same taps from a (H, W, N, C) view.  Returns
    ``(taps, (OH, OW))`` with taps a list of kH·kW arrays, each
    (N, C, OH, OW), in (kh, kw) row-major order.  ``pads`` is ONNX [top,
    left, bottom, right]; padded positions are exactly 0, matching both the
    interpreted Conv and the analysis tier's zero-pad-widened dot-product
    bound.
    """
    kh, kw = (int(v) for v in kernel_shape)
    sh, sw = (int(v) for v in strides)
    dh, dw = (int(v) for v in dilations)
    pt, pl, pb, pr = (int(v) for v in pads)
    xp = jnp.pad(x, ((0, 0), (0, 0), (pt, pb), (pl, pr)))
    hp, wp = xp.shape[2], xp.shape[3]
    oh, ow = conv_out_size(hp, kh, sh, dh), conv_out_size(wp, kw, sw, dw)
    taps = []
    for i in range(kh):
        for j in range(kw):
            taps.append(xp[:, :,
                           i * dh: i * dh + sh * (oh - 1) + 1: sh,
                           j * dw: j * dw + sw * (ow - 1) + 1: sw])
    return taps, (oh, ow)


def conv_out_size(padded: int, k: int, stride: int, dilation: int) -> int:
    """Output length of a conv axis ``padded`` long (padding included)."""
    return (padded - (dilation * (k - 1) + 1)) // stride + 1


def patch_layout(kernel_shape, pads) -> str:
    """The patch build ``extract_patches`` takes for a conv geometry.

    ``"pointwise"`` (1×1, unpadded): no unfold, rows ordered (N, OH, OW).
    ``"tap_major"`` (every other conv): features (kh, kw, c), rows ordered
    (OH, OW, N).
    """
    if tuple(int(v) for v in kernel_shape) == (1, 1) and \
            tuple(int(v) for v in pads) == (0, 0, 0, 0):
        return "pointwise"
    return "tap_major"


@jax.named_scope("im2col")
def extract_patches(x, kernel_shape, strides=(1, 1), pads=(0, 0, 0, 0),
                    dilations=(1, 1)):
    """Unfold NCHW ``x`` into an im2col patch matrix.

    Returns ``(patches, (OH, OW))`` where patches has shape
    (N·OH·OW, kH·kW·C), feature axis ordered (kh, kw, c) with the channel
    fastest — matching ``im2col_weights`` — and rows in the order
    ``patch_layout`` names (``patch_rows_to_nchw`` folds them back).

    The tap-major build keeps the channel on the minor (lane) axis at every
    step: the input moves to (H, W, N, C) once, each tap is a slice of its
    leading axes (strides split H and W into blocks and phases, so no tap
    needs a strided slice, which the TPU compiler turns into gathers and
    lane-sparse copies when C is small), and the taps concatenate on the
    channel axis, so no intermediate carries kH·kW or OW on its lanes.  The
    batch sits on the sublanes just above, so the (OH, OW, N, K)
    concatenation reshapes into the (M, K) matmul operand without a
    relayout, and the matmul's (M, O) rows fold back the same way.  Its
    ops carry the ``im2col`` named scope in their HLO metadata, which the
    benchmark's trace reduction reads.
    """
    n, c, h, w = x.shape
    kh, kw = (int(v) for v in kernel_shape)
    sh, sw = (int(v) for v in strides)
    if patch_layout(kernel_shape, pads) == "pointwise":
        # no unfold, just (optional) stride subsampling
        xs = x[:, :, ::sh, ::sw]
        oh, ow = xs.shape[2], xs.shape[3]
        return (jnp.transpose(xs, (0, 2, 3, 1)).reshape(n * oh * ow, c),
                (oh, ow))
    dh, dw = (int(v) for v in dilations)
    pt, pl, pb, pr = (int(v) for v in pads)
    oh = conv_out_size(h + pt + pb, kh, sh, dh)
    ow = conv_out_size(w + pl + pr, kw, sw, dw)
    # A strided conv splits H and W into (block, phase), so that every tap
    # is an unstrided slice of leading axes: tap offset a reads rows
    # a + sh·r, i.e. phase a % sh of blocks a // sh + r.  hb·sh rows cover
    # every tap's reach; the zero rows past the padding are never read.  At
    # stride 1 the split's unit axes would only steer the TPU compiler into
    # laying the taps out with OW on the lanes, so plain slices are taken.
    hb = dh * (kh - 1) // sh + oh
    wb = dw * (kw - 1) // sw + ow
    xt = jax.lax.pad(jnp.transpose(x, (2, 3, 0, 1)),      # (H, W, N, C)
                     jnp.zeros((), x.dtype),
                     ((pt, hb * sh - h - pt, 0), (pl, wb * sw - w - pl, 0),
                      (0, 0, 0), (0, 0, 0)))
    if (sh, sw) == (1, 1):
        taps = [xt[i * dh: i * dh + oh, j * dw: j * dw + ow]
                for i in range(kh) for j in range(kw)]
    else:
        xt = xt.reshape(hb, sh, wb, sw, n, c)
        taps = [xt[i * dh // sh: i * dh // sh + oh, i * dh % sh,
                   j * dw // sw: j * dw // sw + ow, j * dw % sw]
                for i in range(kh) for j in range(kw)]
    p = jnp.concatenate(taps, axis=-1)           # (OH, OW, N, kH·kW·C)
    return p.reshape(oh * ow * n, kh * kw * c), (oh, ow)


def patch_rows_to_nchw(y, n, oh, ow, layout):
    """(M, O) rows in ``extract_patches``'s row order -> (N, O, OH, OW)."""
    if layout == "pointwise":
        return jnp.transpose(y.reshape(n, oh, ow, -1), (0, 3, 1, 2))
    return jnp.transpose(y.reshape(oh, ow, n, -1), (2, 3, 0, 1))


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
    w2       — im2col'd integer weights: (kH·kW·C, O) int8, or the int4
               packing thereof (kH·kW·C // 2, O) when ``packed``
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
    return patch_rows_to_nchw(y, x.shape[0], oh, ow,
                              patch_layout(kernel_shape, pads))
