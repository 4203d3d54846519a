"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracle,
swept over shapes and dtypes as required."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _rand(shape, dtype, seed=0, scale=2.0):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32) * scale
    return x.astype(dtype)


# ------------------------------------------------------------ quant_dequant

QDQ_SHAPES = [(8, 128), (16, 256), (3, 100), (257, 384), (2, 5, 128)]


@pytest.mark.parametrize("shape", QDQ_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quant_dequant_matches_ref(shape, dtype):
    x = _rand(shape, dtype, seed=1)
    out = ops.quant_dequant(x, 0.07, 0.0, bit_width=8)
    want = ref.quant_dequant_ref(x.astype(jnp.float32), 0.07, 0.0, 8)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want),
                               atol=(1e-5 if dtype == jnp.float32 else 0.05))


@pytest.mark.parametrize("bits,signed,narrow", [
    (2, True, True), (3, True, False), (4, False, False), (5.5, True, False),
    (8, True, True), (6, False, True),
])
def test_quant_dequant_bit_widths(bits, signed, narrow):
    x = _rand((64, 128), jnp.float32, seed=2, scale=5.0)
    out = ops.quant_dequant(x, 0.2, 1.0 if not signed else 0.0,
                            bit_width=bits, signed=signed, narrow=narrow)
    want = ref.quant_dequant_ref(x, 0.2, 1.0 if not signed else 0.0, bits,
                                 signed=signed, narrow=narrow)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("mode", ["ROUND", "ROUND_TO_ZERO", "CEIL", "FLOOR"])
def test_quant_dequant_rounding_modes(mode):
    x = _rand((32, 128), jnp.float32, seed=3)
    out = ops.quant_dequant(x, 0.11, 0.0, bit_width=6, rounding_mode=mode)
    want = ref.quant_dequant_ref(x, 0.11, 0.0, 6, rounding_mode=mode)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


def test_quant_dequant_channelwise():
    x = _rand((64, 256), jnp.float32, seed=4)
    s = jnp.linspace(0.01, 0.5, 256)
    z = jnp.round(jnp.linspace(-3, 3, 256))
    out = ops.quant_dequant(x, s, z, bit_width=8)
    want = ref.quant_dequant_ref(x, s, z, 8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


def test_quant_dequant_small_blocks_match_large():
    """Block shape must not affect results (pure tiling)."""
    x = _rand((300, 500), jnp.float32, seed=5)
    a = ops.quant_dequant(x, 0.05, 0.0, bit_width=4, block=(64, 128))
    b = ops.quant_dequant(x, 0.05, 0.0, bit_width=4, block=(256, 256))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------------- quant_matmul

MM_SHAPES = [(8, 128, 128), (32, 256, 512), (128, 512, 256), (256, 384, 1024)]


@pytest.mark.parametrize("m,k,n", MM_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quant_matmul_matches_ref(m, k, n, dtype):
    x = _rand((m, k), dtype, seed=6, scale=0.5)
    w = np.random.RandomState(0).randint(-127, 128, size=(k, n)).astype(np.int8)
    s = jnp.linspace(0.001, 0.02, n)
    out = ops.quant_matmul(x, jnp.asarray(w), s)
    want = ref.quant_matmul_ref(x, jnp.asarray(w), s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-4)


def test_quant_matmul_bias_and_scalar_scale():
    x = _rand((16, 256), jnp.float32, seed=7)
    w = np.random.RandomState(1).randint(-127, 128, size=(256, 128)).astype(np.int8)
    b = _rand((128,), jnp.float32, seed=8)
    out = ops.quant_matmul(x, jnp.asarray(w), 0.01, bias=b)
    want = ref.quant_matmul_ref(x, jnp.asarray(w), 0.01, bias=b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5,
                               atol=2e-4)


def test_quant_matmul_blocking_invariance():
    x = _rand((64, 512), jnp.float32, seed=9)
    w = np.random.RandomState(2).randint(-127, 128, size=(512, 256)).astype(np.int8)
    s = jnp.full((256,), 0.02)
    a = ops.quant_matmul(x, jnp.asarray(w), s, blocks=(32, 128, 128))
    b = ops.quant_matmul(x, jnp.asarray(w), s, blocks=(64, 256, 512))
    # fp32 accumulation order differs across K-blockings — tolerance, not exact
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-3)


# ----------------------------------------------------------------- int4

def test_pack_unpack_roundtrip():
    w = np.random.RandomState(3).randint(-7, 8, size=(64, 128)).astype(np.int8)
    packed = ops.pack_int4(jnp.asarray(w))
    assert packed.shape == (32, 128) and packed.dtype == jnp.int8
    back = ops.unpack_int4(packed)
    np.testing.assert_array_equal(np.asarray(back), w)


@pytest.mark.parametrize("m,k,n", [(8, 128, 128), (32, 512, 256), (64, 256, 384)])
def test_quant_matmul_int4_matches_ref(m, k, n):
    x = _rand((m, k), jnp.float32, seed=10, scale=0.5)
    w = np.random.RandomState(4).randint(-7, 8, size=(k, n)).astype(np.int8)
    packed = ops.pack_int4(jnp.asarray(w))
    s = jnp.linspace(0.01, 0.1, n)
    out = ops.quant_matmul_int4(x, packed, s)
    want = ref.quant_matmul_int4_ref(x, packed, s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5,
                               atol=2e-4)
    # and against the unpacked int8 path (same math, different layout)
    want2 = ref.quant_matmul_ref(x, jnp.asarray(w), s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want2), rtol=2e-5,
                               atol=2e-4)


def test_quantize_weights_int8_accuracy():
    w = _rand((256, 128), jnp.float32, seed=11)
    q, s = ops.quantize_weights_int8(w)
    err = jnp.abs(w - q.astype(jnp.float32) * s)
    assert float(err.max()) <= float(s.max()) / 2 + 1e-6


def test_quantize_weights_int4_end_to_end():
    w = _rand((256, 128), jnp.float32, seed=12)
    x = _rand((8, 256), jnp.float32, seed=13, scale=0.3)
    packed, s = ops.quantize_weights_int4(w)
    out = ops.quant_matmul_int4(x, packed, s)
    # exact vs. the fake-quant (QDQ) weights — the kernel must equal the
    # QONNX semantics of the quantized weight, not the fp32 original
    w_fq = ops.unpack_int4(packed).astype(jnp.float32) * s
    np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w_fq),
                               rtol=2e-5, atol=2e-4)
    # and int4 noise vs fp32 stays within the analytic expectation
    exact = x @ w
    rel = float(jnp.linalg.norm(out - exact) / jnp.linalg.norm(exact))
    assert rel < 0.25, rel


# ------------------------------------------- int4 blocking edge cases

def test_pack_int4_rejects_odd_k():
    w = np.random.RandomState(0).randint(-7, 8, size=(7, 8)).astype(np.int8)
    with pytest.raises(AssertionError, match="K must be even"):
        ops.pack_int4(jnp.asarray(w))


@pytest.mark.parametrize("m,k,n,blocks", [
    (3, 6, 5, (2, 2, 3)),      # odd bk -> the bk % 2 += 1 adjustment path
    (2, 10, 4, (2, 2, 3)),     # odd bk AND K not a multiple of adjusted bk
    (5, 14, 9, (4, 4, 6)),     # K=14 not a block multiple: padded nibbles
    (1, 2, 1, (8, 8, 7)),      # degenerate tiny shapes, odd block request
    (4, 258, 3, (4, 4, 129)),  # large odd bk adjusted to 130, kp=260
], ids=["odd_bk", "odd_bk_partial_k", "partial_k", "tiny", "large_odd_bk"])
def test_quant_matmul_int4_odd_blocks_and_partial_k(m, k, n, blocks):
    """The bk%2 adjustment and K zero-nibble padding must stay exact: the
    packed path must agree with the int8 path on non-block-multiple and
    odd-block shapes (padding bytes hold two zero nibbles, contributing 0)."""
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(m, k).astype(np.float32))
    w = rng.randint(-8, 8, size=(k, n)).astype(np.int8)
    packed = ops.pack_int4(jnp.asarray(w))
    s = jnp.linspace(0.02, 0.09, n)
    out = ops.quant_matmul_int4(x, packed, s, blocks=blocks)
    want = ops.quant_matmul(x, jnp.asarray(w), s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-4)


def test_quant_matmul_int4_odd_blocks_with_bias():
    rng = np.random.RandomState(6)
    x = jnp.asarray(rng.randn(3, 10).astype(np.float32))
    w = rng.randint(-8, 8, size=(10, 5)).astype(np.int8)
    bias = jnp.asarray(rng.randn(5).astype(np.float32))
    out = ops.quant_matmul_int4(x, ops.pack_int4(jnp.asarray(w)), 0.05, bias,
                                blocks=(2, 2, 5))
    want = ops.quant_matmul(x, jnp.asarray(w), 0.05, bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-4)


# ---------------------------------------------------------- quant conv

def _conv_ref(x, w, strides, pads, dilations, groups):
    dn = jax.lax.conv_dimension_numbers(x.shape, w.shape,
                                        ("NCHW", "OIHW", "NCHW"))
    pad_pairs = [(pads[0], pads[2]), (pads[1], pads[3])]
    return jax.lax.conv_general_dilated(
        x, w, strides, pad_pairs, rhs_dilation=dilations,
        dimension_numbers=dn, feature_group_count=groups,
        precision=jax.lax.Precision.HIGHEST)


@pytest.mark.parametrize("cin,cout,img,k,stride,pads,dil,groups", [
    (4, 6, 8, 3, 1, (0, 0, 0, 0), 1, 1),
    (4, 6, 9, 3, 2, (1, 1, 1, 1), 1, 1),
    (6, 8, 7, 1, 1, (0, 0, 0, 0), 1, 1),     # pointwise
    (6, 8, 7, 1, 2, (0, 0, 0, 0), 1, 1),     # strided pointwise
    (4, 4, 8, 3, 1, (1, 1, 1, 1), 1, 4),     # depthwise
    (6, 9, 8, 3, 1, (1, 1, 1, 1), 1, 3),     # grouped, cout != cin
    (4, 6, 10, 3, 1, (0, 0, 0, 0), 2, 1),    # dilated
    (4, 6, 8, 3, 1, (2, 0, 1, 1), 1, 1),     # asymmetric pads
], ids=["3x3", "stride_pad", "pw", "pw_s2", "dw", "grouped", "dilated",
        "asym"])
def test_quant_conv2d_matches_lax_conv(cin, cout, img, k, stride, pads, dil,
                                       groups):
    """im2col weights + patch extraction + integer matmul == the real conv
    over the dequantized weights (exactly, modulo fp32 reassociation)."""
    rng = np.random.RandomState(7)
    w_int = rng.randint(-8, 8, size=(cout, cin // groups, k, k)) \
        .astype(np.int8)
    scale = np.linspace(0.02, 0.08, cout).astype(np.float32)
    x = jnp.asarray(rng.randn(2, cin, img, img).astype(np.float32))
    w2 = ops.im2col_weights(w_int, groups)
    assert w2.shape == (cin * k * k, cout) and w2.dtype == np.int8
    out = ops.quant_conv2d(x, jnp.asarray(w2), jnp.asarray(scale),
                           kernel_shape=(k, k), strides=(stride, stride),
                           pads=pads, dilations=(dil, dil))
    w_fq = jnp.asarray(w_int, jnp.float32) * scale.reshape(-1, 1, 1, 1)
    want = _conv_ref(x, w_fq, (stride, stride), pads, (dil, dil), groups)
    assert out.shape == want.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-4)


_PATCH_GEOMETRIES = [  # (stride, pad, dilation, groups)
    (1, 0, 1, 1), (2, 1, 1, 1), (1, 1, 2, 1), (1, 1, 1, 2), (2, 0, 2, 2),
    (3, 1, 1, 1)]   # the last: rows past every tap's reach are cut off


@pytest.mark.parametrize("stride,pad,dil,groups", _PATCH_GEOMETRIES,
                         ids=["plain", "s2_p1", "dil2", "g2", "s2_dil2_g2",
                              "s3_p1"])
@pytest.mark.parametrize("k", [1, 3, 5])
@pytest.mark.parametrize("cin", [3, 64, 128])
@pytest.mark.parametrize("dtype", ["int8", "f32"])
def test_tap_major_patches_times_weights_match_lax_conv(dtype, cin, k, stride,
                                                        pad, dil, groups):
    """``extract_patches`` and ``im2col_weights`` agree on the (kh, kw, c)
    feature order: their product is the conv at ``HIGHEST``, exactly for
    int8 codes (every sum fits f32's integers)."""
    if cin % groups:
        groups = 3                                 # C = 3: its own groups
    rng = np.random.RandomState(cin + k)
    cout = 2 * groups
    w_int = rng.randint(-8, 8, size=(cout, cin // groups, k, k)) \
        .astype(np.int8)
    if dtype == "int8":
        x = jnp.asarray(rng.randint(-128, 128, size=(2, cin, 9, 9))
                        .astype(np.int8))
    else:
        x = jnp.asarray(rng.randn(2, cin, 9, 9).astype(np.float32))
    pads = (pad,) * 4
    patches, (oh, ow) = ops.extract_patches(x, (k, k), (stride, stride),
                                            pads, (dil, dil))
    w2 = ops.im2col_weights(w_int, groups)
    assert patches.dtype == x.dtype
    assert patches.shape == (2 * oh * ow, k * k * cin) == (2 * oh * ow,
                                                           w2.shape[0])
    if dtype == "int8":
        y = jnp.dot(patches, jnp.asarray(w2),
                    preferred_element_type=jnp.int32)
    else:
        y = jnp.dot(patches, jnp.asarray(w2, jnp.float32),
                    precision=jax.lax.Precision.HIGHEST)
    got = ops.patch_rows_to_nchw(y, 2, oh, ow,
                                 ops.patch_layout((k, k), pads))
    want = _conv_ref(x.astype(jnp.float32), jnp.asarray(w_int, jnp.float32),
                     (stride, stride), pads, (dil, dil), groups)
    assert got.shape == want.shape
    if dtype == "int8":
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(want).astype(np.int32))
    else:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-4)


def test_quant_conv2d_int4_packed_path_matches_int8():
    rng = np.random.RandomState(8)
    w_int = rng.randint(-8, 8, size=(6, 4, 3, 3)).astype(np.int8)
    x = jnp.asarray(rng.randn(2, 4, 8, 8).astype(np.float32))
    w2 = ops.im2col_weights(w_int)                  # K = 36, even
    kw = dict(kernel_shape=(3, 3), strides=(1, 1), pads=(1, 1, 1, 1))
    out8 = ops.quant_conv2d(x, jnp.asarray(w2), 0.05, **kw)
    out4 = ops.quant_conv2d(x, ops.pack_int4(jnp.asarray(w2)), 0.05,
                            packed=True, **kw)
    np.testing.assert_allclose(np.asarray(out4), np.asarray(out8),
                               rtol=2e-5, atol=2e-4)


def test_quant_conv2d_bias():
    rng = np.random.RandomState(9)
    w_int = rng.randint(-8, 8, size=(5, 3, 3, 3)).astype(np.int8)
    bias = jnp.asarray(rng.randn(5).astype(np.float32))
    x = jnp.asarray(rng.randn(1, 3, 6, 6).astype(np.float32))
    w2 = jnp.asarray(ops.im2col_weights(w_int))
    out = ops.quant_conv2d(x, w2, 0.1, bias, kernel_shape=(3, 3))
    plain = ops.quant_conv2d(x, w2, 0.1, kernel_shape=(3, 3))
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(plain) +
        np.asarray(bias).reshape(1, 5, 1, 1), rtol=1e-6, atol=1e-6)


def test_im2col_weights_block_diagonal_structure():
    """Grouped weights: rows are (kh, kw, c) with the channel fastest; within
    every tap the off-block entries are exactly zero and group gi's block
    is that tap's slice of its own weights."""
    rng = np.random.RandomState(10)
    w = rng.randint(-8, 8, size=(4, 2, 3, 3)).astype(np.int8)   # groups=2
    w2 = ops.im2col_weights(w, groups=2)
    assert w2.shape == (4 * 9, 4)                  # cin=4 -> 36 rows
    ipg, opg = 2, 2
    taps = w2.reshape(9, 4, 4)                     # (kH·kW, C, O)
    for t in range(9):
        for gi in range(2):
            rows = slice(gi * ipg, (gi + 1) * ipg)
            cols = slice(gi * opg, (gi + 1) * opg)
            np.testing.assert_array_equal(
                taps[t, rows, cols], w[cols, :, t // 3, t % 3].T)
        assert np.all(taps[t, :ipg, opg:] == 0)
        assert np.all(taps[t, ipg:, :opg] == 0)
    w2[2:4, :2] = 1                                 # scribble on a block
    w2 = ops.im2col_weights(w, groups=2)            # rebuild
    assert np.all(w2.reshape(9, 4, 4)[:, ipg:, :opg] == 0)
