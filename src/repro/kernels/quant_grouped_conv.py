"""Grouped / depthwise quantized convolution Pallas kernels.

``kernels/quant_conv.py`` lowers every conv onto the dense MXU matmul
kernels through a block-diagonal im2col carrier.  That is correct for any
``group`` attribute, but the off-block zeros are real operand bytes and
real MACs: a ``group=g`` conv pays ``g``× the true ``I/g·kH·kW``
contraction, which on MobileNet's ``group=cin`` layers is exactly the
O(groups) inefficiency the QONNX cost analysis (paper Table III, BOPs/Eq. 5)
is built to expose.  FINN-R (Blott et al. 2018) and the Jain et al.
quantized-compiler work both give depthwise layers a dedicated dataflow
instead of dense-matmul reuse; this module is that dataflow on TPU:

  * ``quant_grouped_matmul`` — per-group K/N-blocked integer matmul for
    *moderate* group counts.  The group index is the outermost grid
    dimension: grid ``(G, M/bm, Ng/bn, Kg/bk)``, so each group's patch
    slice (M, Kg) contracts only against its own ``(Kg, Ng)`` weight block —
    no zero padding anywhere, carrier bytes and MACs are exactly the true
    contraction.  An int4 variant unpacks two-per-byte packed weights
    inside the kernel (``pack_int4_grouped`` packs along each group's Kg,
    pairing row r with row r + Kg/2 so each nibble plane meets a
    contiguous half of the patches).
  * ``quant_depthwise_conv2d`` — the ``group=cin`` case has a K dimension
    of only ``kH·kW`` taps, far too skinny for the 128×128 MXU; it is a
    VPU multiply-reduce instead.  Channels ride the 128-wide lane axis,
    the kH·kW taps are accumulated elementwise in an analysis-selected
    accumulator dtype, and the whole per-channel dequant → bias → ReLU →
    requant epilogue (matching ``quant_matmul``'s scale-at-last-step +
    the fused QDQ kernel's rounding semantics) runs in the same VMEM
    round trip.

Both wrappers accept NCHW activations and return NCHW, mirroring
``quant_conv2d`` so the lowering rule (core/lowering/grouped_conv.py) is a
drop-in sibling of the dense conv rule.  Group counts the rules decline
(``group > 1`` but too many groups for the blocked kernel and not
depthwise) keep the block-diagonal dense fallback.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._blocks import (resolve_interpret as _resolve_interpret,
                      round_up as _round_up)
from .quant_conv import conv_tap_slices, extract_patches, mxu_operand
from .quant_dequant import _round_kernel_body, _static_bounds
from .quant_matmul import DEFAULT_BLOCKS, _unpack_lo_hi, mxu_dot
from .ref import pack_int4_ref, unpack_int4_ref
from .requant import int_epilogue

DEFAULT_DW_BLOCK = (256, 128)     # (bm rows, bc channels) — lane-axis = C


# --------------------------------------------------- weight-layout helpers

def grouped_weights(w, groups: int) -> np.ndarray:
    """Conv weights (O, I/g, kH, kW) -> per-group carrier (G, Kg, Ng).

    Group ``gi``'s slice ``[gi]`` is the ``(I/g·kH·kW, O/g)`` matmul operand
    of that group alone — the block-diagonal zeros of ``im2col_weights``
    never exist.  Row order within a group is (c, kh, kw) with the channel
    varying slowest, matching ``extract_patches``'s feature axis.
    """
    w = np.asarray(w)
    o, ipg, kh, kw = w.shape
    if o % groups:
        raise ValueError(f"output channels {o} not divisible by groups {groups}")
    opg = o // groups
    wm = w.reshape(groups, opg, ipg * kh * kw)
    return np.ascontiguousarray(np.transpose(wm, (0, 2, 1)))


def depthwise_weights(w) -> np.ndarray:
    """Depthwise conv weights (C, 1, kH, kW) -> tap matrix (kH·kW, C).

    Tap order is (kh, kw) row-major; channels ride the minor (lane) axis,
    which is what the VPU kernel broadcasts against.
    """
    w = np.asarray(w)
    c, one, kh, kw = w.shape
    if one != 1:
        raise ValueError(f"depthwise weights need I/g == 1, got {one}")
    return np.ascontiguousarray(w.reshape(c, kh * kw).T)


def pack_int4_grouped(wg):
    """Pack (G, Kg, Ng) int4-valued int8 into (G, Kg//2, Ng) carriers.

    Same nibble scheme as ``ref.pack_int4_ref`` applied per group: packed
    row r holds original rows r (low nibble) and r + Kg//2 (high nibble).
    Each group's Kg must be even — the lowering rule only selects the int4
    path when ``(I/g)·kH·kW`` is.
    """
    assert jnp.shape(wg)[1] % 2 == 0, \
        "per-group K must be even for int4 packing"
    return pack_int4_ref(wg, axis=1)


def unpack_int4_grouped(wg_packed):
    """Inverse of ``pack_int4_grouped``: (G, Kg//2, Ng) -> (G, Kg, Ng)."""
    return unpack_int4_ref(wg_packed, axis=1)


def extract_depthwise_taps(x, kernel_shape, strides=(1, 1), pads=(0, 0, 0, 0),
                           dilations=(1, 1)):
    """Unfold NCHW ``x`` into per-tap channel-minor slices.

    Returns ``(taps, (OH, OW))`` where taps has shape (kH·kW, N·OH·OW, C):
    the same strided slices ``extract_patches`` takes
    (``quant_conv.conv_tap_slices`` is the shared unfold geometry), but the
    channel axis stays whole (moved to the minor/lane position) instead of
    being folded into a dense feature axis — depthwise never mixes
    channels, so there is nothing to contract across.
    """
    n, c, h, w = x.shape
    kh, kw = (int(v) for v in kernel_shape)
    taps, (oh, ow) = conv_tap_slices(x, kernel_shape, strides, pads,
                                     dilations)
    p = jnp.stack(taps, axis=0)                  # (T, N, C, OH, OW)
    p = jnp.transpose(p, (0, 1, 3, 4, 2))        # (T, N, OH, OW, C)
    return p.reshape(kh * kw, n * oh * ow, c), (oh, ow)


# ------------------------------------------------- per-group blocked matmul

def _pad3(a, rows: int, cols: int, value=0):
    """Pad the two trailing dims of a (G, rows, cols) operand."""
    pr, pc = rows - a.shape[1], cols - a.shape[2]
    if pr == 0 and pc == 0:
        return a
    return jnp.pad(a, ((0, 0), (0, pr), (0, pc)), constant_values=value)


def _gqmm_kernel(*refs, nk, acc_dtype, packed, requant=None):
    if packed:
        xlo_ref, xhi_ref, w_ref, s_ref, o_ref, acc_ref = refs
    else:
        x_ref, w_ref, s_ref, o_ref, acc_ref = refs
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if packed:
        # packed row r holds rows r (lo) and r + Kg/2 (hi) of this group
        lo, hi = _unpack_lo_hi(w_ref[0])         # each (bk2, bn)
        acc_ref[...] += mxu_dot(xlo_ref[0], lo, acc_dtype)
        acc_ref[...] += mxu_dot(xhi_ref[0], hi, acc_dtype)
    else:
        acc_ref[...] += mxu_dot(x_ref[0], w_ref[0], acc_dtype)

    @pl.when(k == nk - 1)
    def _finish():
        if requant is None:
            o_ref[0] = (acc_ref[...].astype(jnp.float32) *
                        s_ref[0].astype(jnp.float32)).astype(o_ref.dtype)
        else:
            # integer path: s_ref carries int32 (M_x * M_w) multipliers and
            # the whole relu/requant epilogue runs inside the kernel
            o_ref[0] = int_epilogue(acc_ref[...], s_ref[0], requant,
                                    o_ref.dtype)


def _norm_group_scale(w_scale, g: int, ng: int, dtype=jnp.float32):
    """Scale () or (O,) (group-major output channels) -> (G, 1, Ng)."""
    s = jnp.asarray(w_scale, dtype)
    if s.ndim == 0 or s.size == 1:
        return jnp.full((g, 1, ng), s.reshape(()))
    return s.reshape(g, 1, ng)


@functools.partial(jax.jit, static_argnames=("packed", "blocks", "interpret",
                                             "out_dtype", "acc_dtype",
                                             "requant"))
def quant_grouped_matmul(xg, wg, w_scale, *, packed=False,
                         blocks=DEFAULT_BLOCKS, interpret=None,
                         out_dtype=jnp.float32, acc_dtype=jnp.float32,
                         requant=None):
    """Per-group integer matmul: out[g] = xg[g] @ (scale[g] * wg[g]).

    xg: (G, M, Kg) f32 per-group activations/patches, or int8 codes;
    wg: (G, Kg, Ng) int8, or its per-group int4 packing (G, Kg//2, Ng)
        when ``packed`` (``blocks[2]`` then counts unpacked rows per step,
        half from each nibble plane);
    w_scale: scalar or (G·Ng,) group-major per-output-channel scale.
    requant: optional ``IntRequant`` — integer dyadic epilogue; ``w_scale``
    then carries int32 multipliers (acc_dtype must be int32).
    Returns (G, M, Ng) in ``out_dtype``.  The group index is the outermost
    grid dim — every group runs the standard K-innermost blocked matmul on
    its own slice, so MACs and carrier bytes are the true per-group
    contraction (no block-diagonal zeros).
    """
    interpret = _resolve_interpret(interpret)
    g, m, kdim = xg.shape
    gw, kw_rows, n = wg.shape
    assert gw == g, (xg.shape, wg.shape)
    assert kdim == (2 * kw_rows if packed else kw_rows), (xg.shape, wg.shape)
    bm, bn = min(blocks[0], m), min(blocks[1], n)
    # bk: weight-carrier rows per K step (packed rows when packed)
    bk = min((blocks[2] + 1) // 2 if packed else blocks[2], kw_rows)
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(kw_rows, bk)
    if packed:      # each half of Kg pads on its own (0x00 = zero nibbles)
        xs = [_pad3(xg[:, :, :kw_rows], mp, kp),
              _pad3(xg[:, :, kw_rows:], mp, kp)]
    else:
        xs = [_pad3(xg, mp, kp)]
    wq = _pad3(wg, kp, np_)
    s_dtype = jnp.int32 if requant is not None else jnp.float32
    s3 = _pad3(_norm_group_scale(w_scale, g, n, s_dtype), 1, np_)
    grid = (g, mp // bm, np_ // bn, kp // bk)
    x_spec = pl.BlockSpec((1, bm, bk), lambda gi, i, j, k: (gi, i, k))

    out = pl.pallas_call(
        functools.partial(_gqmm_kernel, nk=grid[3], acc_dtype=acc_dtype,
                          packed=packed, requant=requant),
        grid=grid,
        in_specs=[x_spec] * len(xs) + [
            pl.BlockSpec((1, bk, bn), lambda gi, i, j, k: (gi, k, j)),
            pl.BlockSpec((1, 1, bn), lambda gi, i, j, k: (gi, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda gi, i, j, k: (gi, i, j)),
        out_shape=jax.ShapeDtypeStruct((g, mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        interpret=interpret,
    )(*xs, wq, s3)
    return out[:, :m, :n]


def quant_grouped_conv2d(x, wg, w_scale, bias=None, *, groups, kernel_shape,
                         strides=(1, 1), pads=(0, 0, 0, 0), dilations=(1, 1),
                         packed=False, blocks=DEFAULT_BLOCKS, interpret=None,
                         out_dtype=jnp.float32, acc_dtype=jnp.float32,
                         requant=None):
    """Fused grouped quantized conv: per-group im2col onto the blocked kernel.

    x        — (N, C, H, W) activations (cast to f32; int8 codes pass
               through to the MXU's int8 path)
    wg       — per-group integer weights (G, Kg, Ng) int8 with
               Kg = (C/G)·kH·kW and Ng = O/G, or the per-group int4 packing
               (G, Kg//2, Ng) when ``packed`` (``grouped_weights`` /
               ``pack_int4_grouped``)
    w_scale  — dequant scale, scalar or group-major per-output-channel (O,)
    bias     — optional (O,) f32
    requant  — optional ``IntRequant``: integer dyadic epilogue; ``w_scale``
               then carries int32 multipliers (see ``quant_grouped_matmul``)
    Returns (N, O, OH, OW) in ``out_dtype``.
    """
    x = mxu_operand(x)
    patches, (oh, ow) = extract_patches(x, kernel_shape, strides, pads,
                                        dilations)
    m, feat = patches.shape
    kg = feat // groups
    # channel is the slowest feature axis, so group gi's columns are the
    # contiguous slice [gi·Kg, (gi+1)·Kg): one reshape, no gather
    xg = jnp.transpose(patches.reshape(m, groups, kg), (1, 0, 2))
    y = quant_grouped_matmul(xg, wg, w_scale, packed=packed, blocks=blocks,
                             interpret=interpret, out_dtype=out_dtype,
                             acc_dtype=acc_dtype,
                             requant=requant)              # (G, M, Ng)
    o = groups * y.shape[-1]
    y = jnp.transpose(y, (1, 0, 2)).reshape(m, o)
    if bias is not None:
        y = y + bias.astype(y.dtype)
    y = y.reshape(x.shape[0], oh, ow, o)
    return jnp.transpose(y, (0, 3, 1, 2))


# ---------------------------------------------- depthwise VPU tap-reduce

def _dw_kernel(*refs, relu, act, acc_dtype, has_bias, requant=None):
    """taps (T, bm, bc) × weights (T, bc) -> (bm, bc) with fused epilogue.

    ``act`` is None or the static (lo, hi, rounding_mode) of a fused
    per-tensor activation requant; its scale/zp arrive as (1, 1) operands.
    On the integer path (``requant``), s_ref carries int32 multipliers and
    the full relu/requant epilogue runs in ``int_epilogue`` — ``relu``/
    ``act``/``has_bias`` are all folded into the spec or proven absent.
    """
    it = iter(refs)
    x_ref, w_ref, s_ref = next(it), next(it), next(it)
    b_ref = next(it) if has_bias else None
    qs_ref, qz_ref = (next(it), next(it)) if act is not None else (None, None)
    o_ref = next(it)

    x = x_ref[...].astype(acc_dtype)             # (T, bm, bc)
    w = w_ref[...].astype(acc_dtype)             # (T, bc)
    acc = jnp.sum(x * w[:, None, :], axis=0)     # per-channel tap accumulate
    if requant is not None:
        o_ref[...] = int_epilogue(acc, s_ref[...], requant, o_ref.dtype)
        return
    y = acc.astype(jnp.float32) * s_ref[...].astype(jnp.float32)
    if b_ref is not None:
        y = y + b_ref[...].astype(jnp.float32)
    if relu:
        y = jnp.maximum(y, 0.0)
    if act is not None:
        lo, hi, rounding_mode = act
        qs = qs_ref[0, 0].astype(jnp.float32)
        qz = qz_ref[0, 0].astype(jnp.float32)
        q = jnp.clip(_round_kernel_body(y / qs + qz, rounding_mode), lo, hi)
        y = (q - qz) * qs
    o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "kernel_shape", "strides", "pads", "dilations", "relu", "act_bits",
    "act_signed", "act_narrow", "act_rounding", "block", "interpret",
    "out_dtype", "acc_dtype", "requant"))
def quant_depthwise_conv2d(x, w_taps, w_scale, bias=None, act_scale=None,
                           act_zero_point=None, *, kernel_shape,
                           strides=(1, 1), pads=(0, 0, 0, 0),
                           dilations=(1, 1), relu=False, act_bits=None,
                           act_signed=True, act_narrow=False,
                           act_rounding="ROUND", block=DEFAULT_DW_BLOCK,
                           interpret=None, out_dtype=jnp.float32,
                           acc_dtype=jnp.float32, requant=None):
    """Fused depthwise quantized conv (``group == cin``, multiplier 1).

    x          — (N, C, H, W) activations (cast to f32)
    w_taps     — (kH·kW, C) int8 tap matrix (``depthwise_weights``)
    w_scale    — per-channel dequant scale, scalar or (C,)
    bias       — optional (C,) f32, fused
    act_*      — optional fused per-tensor activation requant (the trailing
                 Quant of a Conv->Relu->Quant block): ``act_bits`` is the
                 static bit width (None disables), ``act_scale`` /
                 ``act_zero_point`` are scalar operands.  Rounding/bounds
                 semantics are exactly the fused QDQ kernel's.
    relu       — fuse max(0, ·) between dequant and requant
    requant    — optional ``IntRequant``: integer dyadic epilogue;
                 ``w_scale`` then carries int32 multipliers, the spec's own
                 relu/act fields replace ``relu``/``act_*`` (pass those as
                 False/None), and ``acc_dtype`` must be int32
    Returns (N, C, OH, OW) in ``out_dtype``.

    The kernel is a VPU elementwise multiply-reduce over the kH·kW taps with
    channels on the 128-lane axis: grid (M/bm, C/bc), no MXU involvement,
    accumulation in the analysis-selected ``acc_dtype`` (int32 exact when the
    lowering proves it sound), and per-channel dequant applied once like
    ``quant_matmul``'s last-K-step scale.
    """
    interpret = _resolve_interpret(interpret)
    x = jnp.asarray(x, jnp.float32)
    taps, (oh, ow) = extract_depthwise_taps(x, kernel_shape, strides, pads,
                                            dilations)
    t, m, c = taps.shape
    bm, bc = min(block[0], m), min(block[1], c)
    mp, cp = _round_up(m, bm), _round_up(c, bc)
    if mp != m or cp != c:
        taps = jnp.pad(taps, ((0, 0), (0, mp - m), (0, cp - c)))
    w2 = jnp.asarray(w_taps)
    if cp != c:
        w2 = jnp.pad(w2, ((0, 0), (0, cp - c)))
    s_dtype = jnp.int32 if requant is not None else jnp.float32
    s = jnp.asarray(w_scale, s_dtype)
    s2 = jnp.broadcast_to(s.reshape(1, -1), (1, c)) if s.size > 1 \
        else jnp.full((1, c), s.reshape(()))
    # fp scale pads with 1.0 so the requant's q = y/qs stays finite
    # off-slice; the integer path has no division, any pad value works
    if cp != c:
        pad_value = 0 if requant is not None else 1.0
        s2 = jnp.pad(s2, ((0, 0), (0, cp - c)), constant_values=pad_value)
    grid = (mp // bm, cp // bc)

    operands = [taps, w2, s2]
    in_specs = [
        pl.BlockSpec((t, bm, bc), lambda i, j: (0, i, j)),
        pl.BlockSpec((t, bc), lambda i, j: (0, j)),
        pl.BlockSpec((1, bc), lambda i, j: (0, j)),
    ]
    has_bias = bias is not None
    if has_bias:
        b2 = jnp.asarray(bias, jnp.float32).reshape(1, -1)
        if cp != c:
            b2 = jnp.pad(b2, ((0, 0), (0, cp - c)))
        operands.append(b2)
        in_specs.append(pl.BlockSpec((1, bc), lambda i, j: (0, j)))
    act = None
    if act_bits is not None:
        lo, hi = _static_bounds(act_signed, act_narrow, act_bits)
        act = (lo, hi, act_rounding)
        operands.append(jnp.asarray(act_scale, jnp.float32).reshape(1, 1))
        operands.append(jnp.asarray(act_zero_point, jnp.float32).reshape(1, 1))
        in_specs += [pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
                     pl.BlockSpec((1, 1), lambda i, j: (0, 0))]

    out = pl.pallas_call(
        functools.partial(_dw_kernel, relu=relu, act=act, acc_dtype=acc_dtype,
                          has_bias=has_bias, requant=requant),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, cp), out_dtype),
        interpret=interpret,
    )(*operands)
    out = out[:m, :c].reshape(x.shape[0], oh, ow, c)
    return jnp.transpose(out, (0, 3, 1, 2))
