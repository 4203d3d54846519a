"""Cross-segment fusion: differential suites + integer-boundary proofs.

Four layers of evidence that the fusion pass (core/lowering/fusion.py) is
semantics-preserving and actually keeps boundaries integer:

  * differential per absorbed pattern — residual ``Add [->Relu] [->Quant]``,
    ``MaxPool``/``AveragePool`` (padded/strided/count_include_pad variants),
    ``Concat`` and the CNV-style ``BipolarQuant`` chain each compile to a
    plan that matches the interpreted oracle **bit-exactly** on power-of-two
    scale corpora (every conv on the int32 requant path, every boundary
    codec bit-same by construction);
  * boundary dtypes — stepping the plan's segments one by one proves every
    negotiated carrier tensor materializes as int8 codes / uint8 nibble
    pairs, with a ``use_fusion=False`` positive control where the same
    tensors are fp32;
  * jaxpr inspection — ``maxpool2d_codes`` traces to an all-integer jaxpr
    (no float aval anywhere), while the fp32 variant trips the detector;
  * kernel-level — the ``AveragePool`` integer code-sum path equals the
    oracle's fp32 expression on every pad/stride/count_include_pad corner
    (the PR-1 divisor rule, now exercised on codes), both checked against
    an independent NumPy loop reference; nibble pack/unpack round-trips.

Plus the CNV-w1a1 regression the issue pins: with fusion on, the plan
interprets **zero** MaxPool/Add nodes; disabling fusion restores them.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import execute
from repro.core.compile import compile_graph
from repro.core.graph import GraphBuilder
from repro.core.passes import run_pipeline
from repro.kernels.quant_pool import (avgpool2d, avgpool2d_codes, maxpool2d,
                                      maxpool2d_codes, pack_codes_int4,
                                      unpack_codes_int4)
from repro.models import zoo


# ------------------------------------------------------------------ helpers

def _oracle(g, x):
    gc = run_pipeline(g, "compile_prep")
    return np.asarray(execute(gc, {"x": x})[gc.output_names[0]])


def _run(plan, x):
    return np.asarray(plan({"x": x})[plan.graph.output_names[0]])


def _check_exact(g, x):
    """Compile with and without fusion; both must match the oracle
    bit-exactly (the builders below use power-of-two scales only, so every
    conv takes the int32 requant path — asserted, it is the exactness
    precondition)."""
    want = _oracle(g, x)
    plan = compile_graph(g)
    assert plan.requant_stats()["fp32_segments"] == 0, plan.describe()
    np.testing.assert_array_equal(_run(plan, x), want,
                                  err_msg=plan.describe())
    off = compile_graph(g, use_fusion=False)
    assert off.fusion_stats()["fused_boundary_segments"] == 0
    np.testing.assert_array_equal(_run(off, x), want,
                                  err_msg=off.describe())
    return plan


def _conv(b, rng, h, cin, cout, k=3, pad=1, w_bits=4):
    """Conv with a power-of-two per-tensor weight quantizer (zoo idiom)."""
    w = (rng.randn(cout, cin, k, k) * 0.1).astype(np.float32)
    qw = b.quant(b.add_initializer("w", w), 0.125 / 2 ** (w_bits - 1), 0.0,
                 w_bits, narrow=True)
    (y,) = b.add_node("Conv", [h, qw], 1,
                      {"strides": [1, 1], "pads": [pad] * 4,
                       "kernel_shape": [k, k]})
    return y


def _act(b, h, bits):
    (h,) = b.add_node("Relu", [h], 1)
    return b.quant(h, 1.0 / 2 ** (bits - 1), 0.0, bits, signed=False)


def _x(seed, shape=(1, 4, 8, 8)):
    return np.random.RandomState(seed).randn(*shape).astype(np.float32)


# ------------------------------------------------- differential: residual

def build_residual(bits=4, relu=True, act=True, tail_conv=True, seed=0):
    """quant -> two convs -> Add [-> Relu] [-> Quant] [-> conv]."""
    rng = np.random.RandomState(seed)
    b = GraphBuilder("residual")
    x = b.add_input("x", (1, 4, 8, 8))
    h = b.quant(x, 1.0 / 128, 0.0, 8)
    a1 = _act(b, _conv(b, rng, h, 4, 8), bits)
    a2 = _act(b, _conv(b, rng, h, 4, 8), bits)
    (y,) = b.add_node("Add", [a1, a2], 1)
    if relu:
        (y,) = b.add_node("Relu", [y], 1)
    if act:
        y = b.quant(y, 0.25, 0.0, bits, signed=False)
    if tail_conv:
        y = _conv(b, rng, y, 8, 4)
    b.mark_output(y)
    return b.build()


@pytest.mark.parametrize("relu,act,tail_conv", [
    (True, True, True),      # full residual block, carrier consumed by conv
    (True, True, False),     # quantized add is the graph output (no carrier)
    (False, True, True),     # no relu between add and quant
    (True, False, False),    # bare add+relu tail, fp32 out
    (False, False, False),   # bare add
])
def test_residual_add_bit_exact(relu, act, tail_conv):
    g = build_residual(relu=relu, act=act, tail_conv=tail_conv)
    plan = _check_exact(g, _x(0))
    assert "Add" not in plan.interp_op_counts(), plan.describe()
    assert plan.fusion_stats()["fused_boundary_segments"] > 0
    if act and tail_conv:
        # the absorbed activation Quant's grid travels as integer codes
        assert plan.fusion_stats()["integer_boundaries"] > 0, plan.describe()


# ----------------------------------------------------- differential: pools

def build_pool(op, k=2, stride=2, pad=0, cip=0, bits=4, tail_conv=True,
               seed=1):
    rng = np.random.RandomState(seed)
    b = GraphBuilder("pool")
    x = b.add_input("x", (1, 4, 9, 9))
    h = b.quant(x, 1.0 / 128, 0.0, 8)
    h = _act(b, _conv(b, rng, h, 4, 8), bits)
    attrs = {"kernel_shape": [k, k], "strides": [stride, stride],
             "pads": [pad] * 4}
    if op == "AveragePool":
        attrs["count_include_pad"] = cip
    (h,) = b.add_node(op, [h], 1, attrs)
    if tail_conv:
        h = _conv(b, rng, h, 8, 4, k=1, pad=0)
    b.mark_output(h)
    return b.build()


@pytest.mark.parametrize("k,stride,pad,tail_conv", [
    (2, 2, 0, True),         # CNV shape; carrier passes through to the conv
    (2, 2, 0, False),        # pool output is the graph output
    (3, 1, 1, True),         # padded, overlapping windows
    (3, 2, 1, False),
    (2, 1, 1, True),         # pad == kernel-1: codes path still legal
])
def test_maxpool_bit_exact(k, stride, pad, tail_conv):
    g = build_pool("MaxPool", k, stride, pad, tail_conv=tail_conv)
    plan = _check_exact(g, _x(1, (1, 4, 9, 9)))
    assert "MaxPool" not in plan.interp_op_counts(), plan.describe()
    # the quantized activation feeding the pool travels as codes
    assert plan.fusion_stats()["integer_boundaries"] > 0, plan.describe()


@pytest.mark.parametrize("k,stride,pad,cip", [
    (2, 2, 0, 0),            # unpadded: divisor is kH*kW
    (2, 2, 1, 0),            # padded + count_include_pad=0: real-count div
    (2, 2, 1, 1),            # padded + count_include_pad=1: kH*kW divisor
    (3, 1, 1, 0),
    (3, 2, 0, 0),
    (3, 3, 2, 1),
])
def test_avgpool_bit_exact(k, stride, pad, cip):
    g = build_pool("AveragePool", k, stride, pad, cip, tail_conv=False)
    plan = _check_exact(g, _x(2, (1, 4, 9, 9)))
    assert "AveragePool" not in plan.interp_op_counts(), plan.describe()
    seg = next(s for s in plan.segments if s.kind == "quant_pool")
    # pow2 carrier scale + tiny windows always satisfy the dyadic gate
    assert seg.meta.get("avg_path") == "int32", plan.describe()


# ---------------------------------------------------- differential: concat

def test_concat_bit_exact():
    rng = np.random.RandomState(3)
    b = GraphBuilder("concat")
    x = b.add_input("x", (1, 4, 8, 8))
    h = b.quant(x, 1.0 / 128, 0.0, 8)
    a1 = _act(b, _conv(b, rng, h, 4, 8, k=1, pad=0), 4)
    a2 = _act(b, _conv(b, rng, h, 4, 8, k=1, pad=0), 4)
    # concat is the graph output: the range analysis does not propagate
    # grids through Concat, so a trailing conv would fall to the fp32 path
    (y,) = b.add_node("Concat", [a1, a2], 1, {"axis": 1})
    b.mark_output(y)
    g = b.build()
    plan = _check_exact(g, _x(3))
    assert "Concat" not in plan.interp_op_counts(), plan.describe()
    # both branch activations reach the concat as integer codes
    assert plan.fusion_stats()["integer_boundaries"] >= 2, plan.describe()


# -------------------------------------------- differential: bipolar chain

def build_bipolar_chain(seed=4):
    """CNV in miniature: conv -> Relu -> BipolarQuant -> MaxPool -> conv."""
    rng = np.random.RandomState(seed)
    b = GraphBuilder("bipolar-chain")
    x = b.add_input("x", (1, 3, 8, 8))
    h = b.quant(x, 1.0 / 128, 0.0, 8)
    h = _conv(b, rng, h, 3, 8, k=3, pad=0)
    (h,) = b.add_node("Relu", [h], 1)
    h = b.bipolar_quant(h, 1.0)
    (h,) = b.add_node("MaxPool", [h], 1,
                      {"kernel_shape": [2, 2], "strides": [2, 2]})
    h = _conv(b, rng, h, 8, 4, k=3, pad=0)
    b.mark_output(h)
    return b.build()


def test_bipolar_chain_bit_exact():
    g = build_bipolar_chain()
    plan = _check_exact(g, _x(4, (1, 3, 8, 8)))
    counts = plan.interp_op_counts()
    assert "MaxPool" not in counts and "BipolarQuant" not in counts, \
        plan.describe()


# ------------------------------------------------- boundary dtype proof

def test_boundary_tensors_carry_integer_dtypes():
    """Step the plan segment by segment: every negotiated carrier tensor
    must materialize as int8 codes (uint8 when nibble-packed) — the HBM
    traffic claim, checked on the actual arrays, not the stats."""
    g = build_bipolar_chain()
    plan = compile_graph(g)
    assert plan.fusion is not None and plan.fusion.carriers, plan.describe()
    carried = plan.fusion.carriers
    # the 1-bit bipolar boundary has an even last dim -> nibble-packed
    assert any(c.packed for c in carried.values()), carried

    env = {"x": jnp.asarray(_x(4, (1, 3, 8, 8)))}
    for seg in plan.segments:
        seg.run(plan.consts, env)
    for name, c in carried.items():
        dt = env[name].dtype
        want = jnp.uint8 if c.packed else jnp.int8
        assert dt == want, f"{name}: {dt} != {want} (carrier {c})"

    # positive control: without fusion the same tensors are fp32 boundaries
    off = compile_graph(g, use_fusion=False)
    env = {"x": jnp.asarray(_x(4, (1, 3, 8, 8)))}
    for seg in off.segments:
        seg.run(off.consts, env)
    for name in carried:
        assert env[name].dtype == jnp.float32, (name, env[name].dtype)


def _avals(jaxpr):
    out = []
    for eqn in jaxpr.eqns:
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "dtype"):
                out.append(aval.dtype)
    return out


def test_maxpool_codes_jaxpr_is_all_integer():
    """jaxpr inspection: the code-domain pool never touches a float —
    with the fp32 variant as the positive control for the detector."""
    fn = functools.partial(maxpool2d_codes, kernel_shape=(2, 2))
    jx = jax.make_jaxpr(fn)(jnp.zeros((1, 2, 4, 4), jnp.int8))
    dts = _avals(jx.jaxpr)
    assert dts and not any(jnp.issubdtype(d, jnp.floating) for d in dts), dts
    fn32 = functools.partial(maxpool2d, kernel_shape=(2, 2))
    jx32 = jax.make_jaxpr(fn32)(jnp.zeros((1, 2, 4, 4), jnp.float32))
    assert any(jnp.issubdtype(d, jnp.floating) for d in _avals(jx32.jaxpr))


def test_pack_unpack_jaxpr_is_all_integer():
    jx = jax.make_jaxpr(pack_codes_int4)(jnp.zeros((3, 4), jnp.int8))
    dts = _avals(jx.jaxpr)
    assert dts and not any(jnp.issubdtype(d, jnp.floating) for d in dts)


# ------------------------------------- kernel-level: avgpool divisor rule

def _np_avgpool(x, k, s, pads, cip):
    """Independent NumPy loop reference for the ONNX AveragePool divisor
    rule: real-element count per window when pads are present and
    count_include_pad=0, else kH*kW."""
    n, c, h, w = x.shape
    ho = (h + pads[0] + pads[2] - k[0]) // s[0] + 1
    wo = (w + pads[1] + pads[3] - k[1]) // s[1] + 1
    out = np.zeros((n, c, ho, wo), np.float64)
    padded = any(p != 0 for p in pads)
    for i in range(ho):
        for j in range(wo):
            r0, c0 = i * s[0] - pads[0], j * s[1] - pads[1]
            rs = slice(max(r0, 0), min(r0 + k[0], h))
            cs = slice(max(c0, 0), min(c0 + k[1], w))
            win = x[:, :, rs, cs].astype(np.float64)
            div = win.shape[2] * win.shape[3] if padded and not cip \
                else k[0] * k[1]
            out[:, :, i, j] = win.sum(axis=(2, 3)) / div
    return out


@pytest.mark.parametrize("k,s,pads,cip,zp", [
    ((2, 2), (2, 2), (0, 0, 0, 0), 0, 0),
    ((2, 2), (1, 1), (1, 1, 1, 1), 0, 0),   # real-count divisor
    ((2, 2), (1, 1), (1, 1, 1, 1), 1, 0),   # count_include_pad divisor
    ((3, 3), (2, 2), (1, 0, 1, 0), 0, 3),   # asymmetric pads + zero point
    ((3, 2), (1, 2), (0, 1, 0, 1), 1, 3),
    ((3, 3), (3, 3), (2, 2, 2, 2), 0, -2),
])
def test_avgpool_kernels_match_numpy_reference(k, s, pads, cip, zp):
    """Satellite fix: the count_include_pad divisor rule on *integer
    carriers* — avgpool2d_codes must equal the oracle-form fp32 pool
    bit-for-bit (dyadic scale), and both must match the NumPy loops."""
    rng = np.random.RandomState(7)
    codes = rng.randint(-8, 8, size=(2, 3, 7, 9)).astype(np.int8)
    scale = np.float32(2.0 ** -3)
    vals = (codes.astype(np.float32) - np.float32(zp)) * scale

    ref = _np_avgpool(vals, k, s, pads, cip)
    got_fp = np.asarray(avgpool2d(jnp.asarray(vals), kernel_shape=k,
                                  strides=s, pads=pads,
                                  count_include_pad=cip))
    np.testing.assert_allclose(got_fp, ref, atol=1e-6, rtol=1e-6)

    got_codes = np.asarray(avgpool2d_codes(
        jnp.asarray(codes), scale, float(zp), kernel_shape=k, strides=s,
        pads=pads, count_include_pad=cip))
    np.testing.assert_array_equal(got_codes, got_fp)


def test_maxpool_codes_matches_dequantized_pool():
    rng = np.random.RandomState(8)
    codes = rng.randint(-128, 128, size=(1, 4, 6, 6)).astype(np.int8)
    s, z = np.float32(0.03), np.float32(1.0)     # any scale family
    vals = (codes.astype(np.float32) - z) * s
    q = np.asarray(maxpool2d_codes(jnp.asarray(codes), kernel_shape=(2, 2)))
    got = (q.astype(np.float32) - z) * s
    want = np.asarray(maxpool2d(jnp.asarray(vals), kernel_shape=(2, 2)))
    np.testing.assert_array_equal(got, want)


def test_pack_unpack_roundtrip():
    rng = np.random.RandomState(9)
    for shape in [(6,), (2, 3, 4), (1, 8, 5, 6), (2, 10)]:
        codes = rng.randint(-8, 8, size=shape).astype(np.int8)
        packed = pack_codes_int4(jnp.asarray(codes))
        assert packed.dtype == jnp.uint8
        assert packed.shape == shape[:-1] + (shape[-1] // 2,)
        np.testing.assert_array_equal(
            np.asarray(unpack_codes_int4(packed)), codes)


# --------------------------------------------------- CNV-w1a1 regression

def test_cnv_w1a1_zero_interpreted_pool_and_add():
    """The issue's acceptance pin: with fusion on, CNV-w1a1 interprets no
    MaxPool/Add at all; disabling fusion restores the old counts — and
    both plans stay bit-exact vs the oracle."""
    g = zoo.ZOO["CNV-w1a1"]()
    plan = compile_graph(g)
    counts = plan.interp_op_counts()
    assert counts.get("MaxPool", 0) == 0, counts
    assert counts.get("Add", 0) == 0, counts
    fs = plan.fusion_stats()
    assert fs["fused_boundary_segments"] > 0
    assert fs["integer_boundaries"] > 0
    assert fs["boundary_bytes_saved"] > 0, fs

    off = compile_graph(g, use_fusion=False)
    assert off.interp_op_counts().get("MaxPool", 0) == 2
    assert off.fusion_stats()["fused_boundary_segments"] == 0

    x = _x(0, (1, 3, 32, 32))
    want = _oracle(g, x)
    np.testing.assert_array_equal(_run(plan, x), want,
                                  err_msg=plan.describe())
    np.testing.assert_array_equal(_run(off, x), want)


# ------------------------------------------------ residual joins (ResNet)

def test_resnet50_residual_join_stats_and_gauges():
    """The benchmark's ResNet-50 w1a2 plan at 32x32 (compiled, not run):
    its 16 joins counted by their inputs' carrier, and the skip bytes one
    sample keeps alive across segments, against a hand count."""
    from bench import layers as L, spec
    from bench.graph import build_graph
    from repro.obs.metrics import default_registry
    bench = spec.Bench()
    cfg = bench.config("resnet50-w1a2-224")
    cfg["input_shape"] = [3, 32, 32]
    layers = bench.layers(cfg)
    g = build_graph("resnet50-w1a2-32", layers, L.draw_weights(layers, 0),
                    cfg["input_shape"])
    plan = compile_graph(g)
    fs = plan.fusion_stats()
    # stages 1-3 end on 8x8, 4x4 and 2x2 maps (an even width: nibble-
    # packed carriers); stage 4's 1x1 map cannot be packed and stays int8
    assert fs["residual_joins"] == {"int4x2": 3 + 4 + 6, "int8": 3,
                                    "fp32": 0}
    # a projection block's join reads its branch's last conv two segments
    # back (the projection runs in between); an identity block's join
    # reads the previous join's output four back: one tensor per block,
    # stage by stage (blocks x channels x H x W x bytes per code)
    assert fs["skip_bytes_per_sample"] == (3 * 256 * 8 * 8 // 2 +
                                           4 * 512 * 4 * 4 // 2 +
                                           6 * 1024 * 2 * 2 // 2 +
                                           3 * 2048 * 1 * 1)
    reg = default_registry()
    model = {"model": "resnet50-w1a2-32"}
    for carrier, n in fs["residual_joins"].items():
        assert reg.get("compile_residual_joins",
                       dict(model, carrier=carrier)).value == n
    assert reg.get("compile_skip_bytes_per_sample", model).value == \
        fs["skip_bytes_per_sample"]

    off = compile_graph(g, use_fusion=False)
    assert off.fusion_stats()["residual_joins"] == {"int4x2": 0, "int8": 0,
                                                    "fp32": 0}
