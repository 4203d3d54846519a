"""Weight-quantized matmul Pallas kernels (serving hot path).

Two variants:

  * ``quant_matmul``       — int8 weights (K, N) + per-channel scales.
  * ``quant_matmul_int4``  — int4 weights packed two-per-byte along K
                             (K//2, N), unpacked *inside* the kernel.

TPU adaptation of the paper's arbitrary-precision weights: sub-byte weights
live packed in HBM — the int4 variant halves weight HBM traffic, which is
exactly what matters for the memory-bound decode shapes — and are expanded
to the MXU-native operand width in VMEM, inside the kernel, so the unpack
cost is overlapped with the matmul pipeline.  These kernels are reached two
ways: directly through ``kernels.ops`` (serving checkpoints), and from the
graph path via ``core/compile.py``, which lowers ``Quant(w) -> MatMul``
segments of a QonnxGraph onto them with offline weight packing.

Blocking: grid (M/bm, N/bn, K/bk), K innermost so each (i, j) output tile
stays resident in VMEM across the K loop (revision dims semantics); fp32
accumulation; per-output-channel dequant scale applied once at the last K
step.  Block defaults are MXU-aligned multiples of 128.

MXU operands (``mxu_dot``): Mosaic multiplies int8 x int8 into int32 and
f32 x f32 into f32, but refuses int32 x int32.  An int8 activation operand
(integer codes the lowering proved to fit) therefore takes the int8 path;
any float operand multiplies the weights widened to f32 at full precision.

int4 packing pairs row ``r`` with row ``r + K/2`` (low / high nibble), so
each nibble plane contracts against a contiguous half of ``x``: the kernel
takes those halves through two BlockSpecs and never slices lanes with a
stride.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._blocks import (pad2 as _pad2, resolve_interpret as _resolve_interpret,
                      round_up as _round_up)
from .requant import int_epilogue

DEFAULT_BLOCKS = (256, 256, 512)  # (bm, bn, bk)


def _qmm_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, nk, acc_dtype,
                requant=None):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # acc_dtype is analysis-selected (core/compile.py): f32 by default;
    # int32 when the activations are provably integer-valued and the
    # worst-case dot-product bound fits (exact integer accumulation)
    acc_ref[...] += mxu_dot(x_ref[...], w_ref[...], acc_dtype)

    @pl.when(k == nk - 1)
    def _finish():
        if requant is None:
            o_ref[...] = (acc_ref[...].astype(jnp.float32) *
                          s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)
        else:
            # integer path: s_ref carries the int32 (M_x * M_w) multipliers
            o_ref[...] = int_epilogue(acc_ref[...], s_ref[...], requant,
                                      o_ref.dtype)


def mxu_dot(x, w, acc_dtype):
    """``x @ w`` in an operand type Mosaic accepts, cast to ``acc_dtype``.

    int8 ``x`` meets the int8 weights on the MXU's integer path, exact in
    int32.  Float ``x`` meets the weights widened to f32 at full precision
    (``HIGHEST``: no single bf16 pass).  On an int32 accumulator that float
    partial sum is integral and exact, because the lowering selects int32
    with float operands only when every sum stays within 2**24.
    """
    if x.dtype == jnp.int8:
        y = jnp.dot(x, w.astype(jnp.int8), preferred_element_type=jnp.int32)
    else:
        y = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST,
                    preferred_element_type=jnp.float32)
    return y.astype(acc_dtype)


def _unpack_lo_hi(packed):
    """int8 carrier -> two sign-extended int4 planes (low/high nibble).

    The shifts run in int32: Mosaic has no int8 vector shifts."""
    w = packed.astype(jnp.int32)
    return (w << 28) >> 28, w >> 4


def _qmm4_kernel(xlo_ref, xhi_ref, wp_ref, s_ref, o_ref, acc_ref, *, nk,
                 acc_dtype, requant=None):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # packed row r holds original rows r (lo) and r + K/2 (hi), so each
    # plane contracts against its own contiguous half of x
    lo, hi = _unpack_lo_hi(wp_ref[...])         # each (bk2, bn)
    acc_ref[...] += mxu_dot(xlo_ref[...], lo, acc_dtype)
    acc_ref[...] += mxu_dot(xhi_ref[...], hi, acc_dtype)

    @pl.when(k == nk - 1)
    def _finish():
        if requant is None:
            o_ref[...] = (acc_ref[...].astype(jnp.float32) *
                          s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)
        else:
            o_ref[...] = int_epilogue(acc_ref[...], s_ref[...], requant,
                                      o_ref.dtype)


def _norm_scale(w_scale, n, dtype=jnp.float32):
    s = jnp.asarray(w_scale, dtype)
    if s.ndim == 0 or s.size == 1:
        return jnp.full((1, n), s.reshape(()))
    return s.reshape(1, n)


@functools.partial(jax.jit, static_argnames=("blocks", "interpret",
                                             "out_dtype", "acc_dtype",
                                             "requant"))
def quant_matmul(x, w_int, w_scale, bias=None, *, blocks=DEFAULT_BLOCKS,
                 interpret=None, out_dtype=jnp.float32,
                 acc_dtype=jnp.float32, requant=None):
    """out = x @ (w_scale * w_int) [+ bias].

    x: (M, K) f32/bf16, or int8 codes;  w_int: (K, N) int8;
    w_scale: scalar or (N,).
    acc_dtype: f32 (default) or int32 — int32 requires integer-valued x
    and a dot-product bound < 2^31, and < 2^24 unless x is int8 (the
    compile tier proves these via range analysis before selecting it).
    requant: optional ``IntRequant`` — switches the epilogue to the
    integer dyadic path; ``w_scale`` then carries the int32 per-channel
    multipliers instead of fp32 scales (acc_dtype must be int32).
    interpret: None = backend default (interpreter on CPU, compiled
    Mosaic on GPU/TPU); an explicit bool overrides.
    """
    interpret = _resolve_interpret(interpret)
    m, kdim = x.shape
    k2, n = w_int.shape
    assert kdim == k2, (x.shape, w_int.shape)
    bm, bn, bk = (min(blocks[0], m), min(blocks[1], n), min(blocks[2], kdim))
    # pad every dim to a block multiple: partial blocks read out-of-bounds
    # garbage (NaN under interpret); zero-padding K contributes 0 to the dot
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(kdim, bk)
    xq = _pad2(x, mp, kp)
    wq = _pad2(w_int, kp, np_)
    s_dtype = jnp.int32 if requant is not None else jnp.float32
    s2 = _pad2(_norm_scale(w_scale, n, s_dtype), 1, np_)
    grid = (mp // bm, np_ // bn, kp // bk)

    out = pl.pallas_call(
        functools.partial(_qmm_kernel, nk=grid[2], acc_dtype=acc_dtype,
                          requant=requant),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        interpret=interpret,
    )(xq, wq, s2)
    out = out[:m, :n]
    if bias is not None:
        out = out + bias.astype(out.dtype)
    return out


@functools.partial(jax.jit, static_argnames=("blocks", "interpret",
                                             "out_dtype", "acc_dtype",
                                             "requant"))
def quant_matmul_int4(x, w_packed, w_scale, bias=None, *, blocks=DEFAULT_BLOCKS,
                      interpret=None, out_dtype=jnp.float32,
                      acc_dtype=jnp.float32, requant=None):
    """out = x @ (w_scale * unpack(w_packed)) with in-kernel int4 unpack.

    x: (M, K);  w_packed: (K//2, N) int8, row r carrying rows r and
    r + K//2 of the int4 weights (``ops.pack_int4``).  ``blocks[2]``
    counts unpacked K rows per step, half from each nibble plane.
    acc_dtype / requant / interpret: as in ``quant_matmul``.
    """
    interpret = _resolve_interpret(interpret)
    m, kdim = x.shape
    k2, n = w_packed.shape
    assert kdim == 2 * k2, (x.shape, w_packed.shape)
    bm, bn = min(blocks[0], m), min(blocks[1], n)
    bk2 = min((blocks[2] + 1) // 2, k2)      # packed rows per K step
    mp, np_, kp2 = _round_up(m, bm), _round_up(n, bn), _round_up(k2, bk2)
    # each half pads on its own: a 0x00 pad byte is two zero nibbles
    x_lo = _pad2(x[:, :k2], mp, kp2)
    x_hi = _pad2(x[:, k2:], mp, kp2)
    wq = _pad2(w_packed, kp2, np_)
    s_dtype = jnp.int32 if requant is not None else jnp.float32
    s2 = _pad2(_norm_scale(w_scale, n, s_dtype), 1, np_)
    grid = (mp // bm, np_ // bn, kp2 // bk2)

    out = pl.pallas_call(
        functools.partial(_qmm4_kernel, nk=grid[2], acc_dtype=acc_dtype,
                          requant=requant),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk2), lambda i, j, k: (i, k)),
            pl.BlockSpec((bm, bk2), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk2, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        interpret=interpret,
    )(x_lo, x_hi, wq, s2)
    out = out[:m, :n]
    if bias is not None:
        out = out + bias.astype(out.dtype)
    return out
