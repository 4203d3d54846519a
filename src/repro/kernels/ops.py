"""Public jit'd wrappers around the Pallas kernels.

``interpret`` defaults to None = auto-detect (``_blocks.default_interpret``,
resolved once per process): the Pallas interpreter on CPU, the compiled
Mosaic pipeline on GPU/TPU.  Pass an explicit bool to override (e.g.
interpret=True to validate kernel logic on an accelerator).  Weight
packing/unpacking are offline operations (done once at model-load), so they
are plain jnp here — the *in-kernel* unpack lives in quant_matmul_int4.
"""
from __future__ import annotations

import jax.numpy as jnp

from ._blocks import default_interpret, resolve_interpret  # noqa: F401
from .quant_conv import (  # noqa: F401  (public re-exports)
    extract_patches, im2col_weights, patch_layout, patch_rows_to_nchw,
    quant_conv2d)
from .quant_dequant import quant_dequant  # noqa: F401
from .quant_grouped_conv import (  # noqa: F401
    depthwise_weights, extract_depthwise_taps, grouped_weights,
    pack_int4_grouped, quant_depthwise_conv2d, quant_grouped_conv2d,
    quant_grouped_matmul, unpack_int4_grouped)
from .quant_matmul import quant_matmul, quant_matmul_int4  # noqa: F401
from .quant_pool import (  # noqa: F401  (fused boundary pooling + packers)
    avgpool2d, avgpool2d_codes, maxpool2d, maxpool2d_codes, pack_codes_int4,
    unpack_codes_int4)
from . import ref


def pack_int4(w_int):
    """Offline packing: (K, N) int4-valued int8 -> (K//2, N) int8 carriers."""
    assert w_int.shape[0] % 2 == 0, "K must be even for int4 packing"
    return ref.pack_int4_ref(jnp.asarray(w_int))


def unpack_int4(w_packed):
    return ref.unpack_int4_ref(jnp.asarray(w_packed))


def quantize_weights_int8(w, *, narrow=True):
    """Symmetric per-output-channel int8 quantization of a (K, N) weight.

    Returns (w_int8, scale[N]) such that w ~= scale * w_int8 — the paper's
    §II convention (symmetric weights, channel-wise scale).
    """
    amax = jnp.max(jnp.abs(w), axis=0)
    bound = 127.0
    scale = jnp.maximum(amax / bound, 1e-8)
    q = jnp.clip(jnp.round(w / scale), -127 if narrow else -128, 127)
    return q.astype(jnp.int8), scale.astype(jnp.float32)


def quantize_weights_int4(w):
    """Symmetric per-channel int4 quantization + packing.

    Returns (w_packed[K//2, N], scale[N]).
    """
    amax = jnp.max(jnp.abs(w), axis=0)
    scale = jnp.maximum(amax / 7.0, 1e-8)
    q = jnp.clip(jnp.round(w / scale), -7, 7).astype(jnp.int8)
    return pack_int4(q), scale.astype(jnp.float32)
