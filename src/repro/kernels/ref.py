"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernels are validated against (interpret=True
on CPU, real TPU in production).  They are intentionally written with plain
jnp — no tiling, no layout tricks.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import quant_ops


def quant_dequant_ref(x, scale, zero_point, bit_width, *, signed=True,
                      narrow=False, rounding_mode="ROUND"):
    """Oracle for the fused QDQ elementwise kernel == core Quant op."""
    return quant_ops.quant(x, scale, zero_point, bit_width, signed=signed,
                           narrow=narrow, rounding_mode=rounding_mode)


def quant_matmul_ref(x, w_int, w_scale, bias=None):
    """Oracle for the weight-quantized matmul.

    x:       (M, K) float32/bfloat16 activations
    w_int:   (K, N) int8 quantized weights (symmetric, zero_point = 0)
    w_scale: (N,) or scalar per-output-channel scale
    out:     (M, N) float32  — x @ (w_scale * w_int), fp32 accumulation
    """
    acc = jnp.dot(x.astype(jnp.float32), w_int.astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    out = acc * jnp.asarray(w_scale, jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out


def pack_int4_ref(w_int, axis=0):
    """Pack (K, N) int4-valued int8 into (K//2, N) int8 carriers.

    Row r goes to the low nibble and row r + K//2 to the high nibble, so
    each nibble plane pairs with a contiguous half of the contraction.
    ``axis`` names the K axis (the grouped carrier packs axis 1).
    """
    lo, hi = jnp.split(jnp.asarray(w_int).astype(jnp.int8), 2, axis=axis)
    return ((hi.astype(jnp.uint8) << 4) | (lo.astype(jnp.uint8) & 0xF)).astype(jnp.int8)


def unpack_int4_ref(w_packed, axis=0):
    """Inverse of pack_int4_ref: (K//2, N) int8 -> (K, N) int4-valued int8."""
    w_packed = jnp.asarray(w_packed).astype(jnp.int8)
    lo = (w_packed << 4) >> 4                           # sign-extend low nibble
    hi = w_packed >> 4                                  # arithmetic shift
    return jnp.concatenate([lo, hi], axis=axis)


def quant_matmul_int4_ref(x, w_packed, w_scale, bias=None):
    """Oracle for the packed-int4 matmul: unpack then quant_matmul."""
    w_int = unpack_int4_ref(w_packed)
    return quant_matmul_ref(x, w_int, w_scale, bias)


def quant_grouped_matmul_ref(xg, wg, w_scale):
    """Oracle for the per-group blocked matmul.

    xg: (G, M, Kg) f32;  wg: (G, Kg, Ng) int8;  w_scale: scalar or (G·Ng,)
    group-major.  out: (G, M, Ng) f32 — per group, x[g] @ (s[g] * w[g]).
    """
    g, _, _ = xg.shape
    ng = wg.shape[-1]
    s = jnp.asarray(w_scale, jnp.float32)
    s = jnp.full((g, 1, ng), s.reshape(())) if s.size == 1 \
        else s.reshape(g, 1, ng)
    acc = jnp.einsum("gmk,gkn->gmn", xg.astype(jnp.float32),
                     wg.astype(jnp.float32))
    return acc * s


def quant_depthwise_conv_ref(taps, w_taps, w_scale, bias=None, *,
                             relu=False, act=None):
    """Oracle for the depthwise tap-reduce kernel (pre-unfolded taps).

    taps: (T, M, C) f32;  w_taps: (T, C) int8;  w_scale: scalar or (C,).
    ``act`` is None or (scale, zero_point, bit_width, signed, narrow,
    rounding_mode) for the fused requant epilogue.
    """
    acc = jnp.sum(taps.astype(jnp.float32) *
                  w_taps.astype(jnp.float32)[:, None, :], axis=0)
    out = acc * jnp.asarray(w_scale, jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    if relu:
        out = jnp.maximum(out, 0.0)
    if act is not None:
        s, z, nb, signed, narrow, rmode = act
        out = quant_ops.quant(out, s, z, nb, signed=signed, narrow=narrow,
                              rounding_mode=rmode)
    return out
