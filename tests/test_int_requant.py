"""Integer-only dyadic requantization: unit + end-to-end pinning.

Four layers of evidence that the int32 multiplier+shift epilogue is exact:

  * ``round_shift`` vs an exact rational (``fractions.Fraction``) reference
    across every QONNX rounding mode, signed/unsigned values, and the
    INT32_MAX/INT32_MIN-adjacent edge (the floor-decomposition formulas
    must be overflow-free over the full int32 domain);
  * ``int_epilogue`` (per-channel multipliers, zero-point fold, clamp) vs
    the same rational oracle of Eq. 1 on a power-of-two activation grid;
  * kernel-level: ``quant_matmul`` on the integer path vs the fp32
    reference it must reproduce bit-for-bit, plus a jaxpr inspection
    proving the emitted Pallas kernel contains **no** fp32
    divide/round/clamp chain (only the final exact power-of-two output
    conversion touches f32);
  * zoo end-to-end: TFC/CNV (power-of-two scales by construction) compile
    at 100% integer-path coverage and match the interpreted oracle
    bit-exactly; ``use_integer_requant=False`` restores the fp32 path; the
    dyadic scale constants survive a QCDQ round trip untouched.
"""
import functools
import math
from fractions import Fraction

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.ranges import dyadic_decompose
from repro.core import execute
from repro.core.compile import compile_graph
from repro.core.passes import run_pipeline
from repro.core.quant_ops import ROUNDING_MODES, round_shift
from repro.kernels import ops as kernel_ops
from repro.kernels.quant_dequant import _static_bounds
from repro.kernels.requant import IntRequant, int_epilogue
from repro.models import zoo

INT32_MAX = 2 ** 31 - 1
INT32_MIN = -2 ** 31


# ------------------------------------------------ exact rational reference

def _ref_round(v: Fraction, mode: str) -> int:
    """QONNX rounding of an exact rational — the independent oracle
    (mirrors quant_ops.round_with_mode, but with no floating point)."""
    if mode == "FLOOR":
        return math.floor(v)
    if mode == "CEIL":
        return math.ceil(v)
    if mode in ("DOWN", "ROUND_TO_ZERO"):
        return int(v)                        # Fraction truncates toward 0
    if mode == "UP":                         # away from zero
        return math.ceil(v) if v >= 0 else math.floor(v)
    if mode == "ROUND":                      # ties to even
        return round(v)                      # Fraction.__round__ is half-even
    neg = v < 0
    av = -v if neg else v
    if mode == "HALF_UP":                    # ties away from zero
        r = math.floor(av + Fraction(1, 2))
    else:                                    # HALF_DOWN: ties toward zero
        r = math.ceil(av - Fraction(1, 2))
    return -r if neg else r


# ------------------------------------------- round_shift (satellite suite)

@pytest.mark.parametrize("mode", ROUNDING_MODES)
def test_round_shift_matches_rational_reference(mode):
    rng = np.random.RandomState(0)
    edges = np.array([0, 1, -1, 2, -2, 3, -3,
                      INT32_MAX, INT32_MAX - 1, INT32_MIN, INT32_MIN + 1,
                      2 ** 30, -(2 ** 30), 2 ** 24, -(2 ** 24),
                      12345678, -87654321], np.int64)
    for shift in (1, 2, 3, 5, 8, 15, 23, 31):
        rand = rng.randint(INT32_MIN, INT32_MAX, size=200, dtype=np.int64)
        # exact .5 ties: q * 2**shift + half — where the modes disagree
        half = 1 << (shift - 1)
        ties = (rng.randint(-1000, 1000, size=64, dtype=np.int64)
                << shift) + half
        p = np.concatenate([edges, rand, ties])
        p = p[(p >= INT32_MIN) & (p <= INT32_MAX)].astype(np.int32)
        got = np.asarray(round_shift(jnp.asarray(p), shift, mode),
                         dtype=np.int64)
        want = np.array([_ref_round(Fraction(int(v), 1 << shift), mode)
                         for v in p], np.int64)
        np.testing.assert_array_equal(got, want,
                                      err_msg=f"{mode} shift={shift}")


def test_round_shift_zero_is_identity_and_negative_rejected():
    p = jnp.asarray([3, -7, INT32_MAX], jnp.int32)
    np.testing.assert_array_equal(np.asarray(round_shift(p, 0)),
                                  np.asarray(p))
    with pytest.raises(ValueError):
        round_shift(p, -1)


# ---------------------------------------------- int_epilogue vs rational

@pytest.mark.parametrize("mode", ROUNDING_MODES)
@pytest.mark.parametrize("signed,narrow", [(True, False), (True, True),
                                           (False, False)])
def test_int_epilogue_matches_rational_quant_reference(mode, signed, narrow):
    """Per-channel (mult, shift) + fused activation Quant vs Eq. 1 computed
    in exact rational arithmetic — pins the zero-point fold (before the
    rounding shift) and the static clamp."""
    rng = np.random.RandomState(3)
    n = 8
    acc = rng.randint(-5000, 5000, size=(6, n)).astype(np.int32)
    mult = (2 * rng.randint(0, 50, size=n) + 1).astype(np.int32)
    shift, t_a = 12, 4                       # s_x*s_w = 2**-12, s_a = 2**-4
    s = shift - t_a
    bits = 5
    lo, hi = _static_bounds(signed, narrow, bits)
    zp = 1 if signed else 2
    rq = IntRequant(shift=shift, has_act=True, act_shift=s, act_zp=zp,
                    act_lo=int(lo), act_hi=int(hi), act_out_shift=t_a,
                    rounding_mode=mode)
    got = np.asarray(int_epilogue(jnp.asarray(acc),
                                  jnp.asarray(mult).reshape(1, n),
                                  rq, jnp.float32))
    want = np.empty_like(got)
    for i in range(acc.shape[0]):
        for j in range(n):
            p = int(acc[i, j]) * int(mult[j])
            # Eq. 1 on x = p*2**-shift, s_a = 2**-t_a:
            # x/s_a + z = (p + z*2**s) / 2**s
            q = _ref_round(Fraction(p + zp * (1 << s), 1 << s), mode)
            q = min(max(q, int(lo)), int(hi))
            want[i, j] = np.float32((q - zp) * 2.0 ** -t_a)
    np.testing.assert_array_equal(got, want)


def test_int_epilogue_no_act_and_relu():
    acc = jnp.asarray([[-300, 5], [40, -1]], jnp.int32)
    mult = jnp.asarray([[3, 5]], jnp.int32)
    got = np.asarray(int_epilogue(acc, mult, IntRequant(shift=6),
                                  jnp.float32))
    want = np.asarray(acc) * np.asarray(mult) * np.float32(2.0 ** -6)
    np.testing.assert_array_equal(got, want.astype(np.float32))
    got_relu = np.asarray(int_epilogue(
        acc, mult, IntRequant(shift=6, relu=True), jnp.float32))
    np.testing.assert_array_equal(got_relu, np.maximum(want, 0.0))


# ------------------------------------------------- kernel-level parity

def test_quant_matmul_integer_path_bit_exact():
    """int8 and packed-int4 matmul kernels on the integer path reproduce
    the exact fp32 result (all quantities < 2**24, so the fp32 reference
    itself is exact)."""
    rng = np.random.RandomState(5)
    m, k, n = 9, 24, 6
    x_int = rng.randint(-64, 64, size=(m, k)).astype(np.float32)
    w = rng.randint(-7, 8, size=(k, n)).astype(np.int8)
    m_w = (2 * rng.randint(0, 8, size=n) + 1).astype(np.int64)   # odd
    t_w = 9
    scale = (m_w * 2.0 ** -t_w).astype(np.float32)
    acc = x_int.astype(np.int64) @ w.astype(np.int64)
    ref = (acc * m_w * 2.0 ** -t_w).astype(np.float32)

    rq = IntRequant(shift=t_w)               # T_x = 0: x already integral
    out = kernel_ops.quant_matmul(
        jnp.asarray(x_int), jnp.asarray(w), jnp.asarray(m_w, jnp.int32),
        acc_dtype=jnp.int32, requant=rq)
    np.testing.assert_array_equal(np.asarray(out), ref)
    # fp32 path on the same operands agrees too (sanity on the comparison)
    out_fp = kernel_ops.quant_matmul(jnp.asarray(x_int), jnp.asarray(w),
                                     jnp.asarray(scale))
    np.testing.assert_array_equal(np.asarray(out_fp), ref)

    packed = kernel_ops.pack_int4(np.asarray(w))
    out4 = kernel_ops.quant_matmul_int4(
        jnp.asarray(x_int), jnp.asarray(packed),
        jnp.asarray(m_w, jnp.int32), acc_dtype=jnp.int32, requant=rq)
    np.testing.assert_array_equal(np.asarray(out4), ref)


# -------------------------------------------- jaxpr epilogue inspection

def _sub_jaxprs(params):
    found = []

    def add(v):
        if hasattr(v, "eqns"):               # Jaxpr
            found.append(v)
        elif hasattr(v, "jaxpr"):            # ClosedJaxpr
            found.append(v.jaxpr)

    for v in params.values():
        add(v)
        if isinstance(v, (tuple, list)):
            for u in v:
                add(u)
    return found


def _kernel_eqns(fn, *args):
    """Every eqn nested (at any depth) inside a pallas_call's kernel."""
    closed = jax.make_jaxpr(fn)(*args)
    out = []

    def walk(jx, inside):
        for eqn in jx.eqns:
            if inside:
                out.append(eqn)
            now = inside or eqn.primitive.name == "pallas_call"
            for sub in _sub_jaxprs(eqn.params):
                walk(sub, now)

    walk(closed.jaxpr, False)
    return out


def _f32_violations(eqns, allow):
    bad = []
    for eqn in eqns:
        touches_f32 = any(
            "float32" in str(getattr(v, "aval", ""))
            for v in list(eqn.invars) + list(eqn.outvars))
        if touches_f32 and eqn.primitive.name not in allow:
            bad.append(str(eqn))
    return bad

# f32 may only flow through the final grid->value conversion (cast + mul
# by the exact power-of-two output scale) and structural/memory ops — any
# f32 arithmetic beyond that means the fp32 requant chain leaked back in
_F32_ALLOW = {"mul", "convert_element_type", "cond", "get", "swap",
              "broadcast_in_dim", "reshape", "squeeze", "transpose",
              "slice", "pad", "concatenate", "copy", "pjit", "iota"}


def test_integer_epilogue_emits_no_fp32_requant_ops():
    rq = IntRequant(shift=10, relu=True, has_act=True, act_shift=6,
                    act_zp=1, act_lo=-8, act_hi=7, act_out_shift=4,
                    rounding_mode="ROUND")
    # int8 codes: the operand the lowering feeds an integer-requant segment
    x = jnp.zeros((8, 16), jnp.int8)
    w = jnp.zeros((16, 4), jnp.int8)
    mult = jnp.ones((4,), jnp.int32)
    fn = functools.partial(kernel_ops.quant_matmul, acc_dtype=jnp.int32,
                           requant=rq)
    eqns = _kernel_eqns(fn, x, w, mult)
    assert eqns, "no pallas kernel found in the jaxpr"
    names = {e.primitive.name for e in eqns}
    assert "div" not in names, sorted(names)
    bad = _f32_violations(eqns, _F32_ALLOW)
    assert not bad, "fp32 arithmetic in the integer epilogue:\n" + \
        "\n".join(bad)


def test_fp32_requant_kernel_trips_the_detector():
    """Positive control: the fused fp32 QDQ kernel must contain the very
    div/round chain the allowlist rejects — otherwise the inspection
    above could pass vacuously."""
    fn = functools.partial(kernel_ops.quant_dequant, bit_width=4)
    x = jnp.zeros((4, 8), jnp.float32)
    eqns = _kernel_eqns(fn, x, jnp.float32(0.1), jnp.float32(0.0))
    assert eqns
    assert _f32_violations(eqns, _F32_ALLOW), \
        "detector failed to flag the fp32 requant chain"


# ------------------------------------------------------ zoo end-to-end

def _oracle(g, x):
    gc = run_pipeline(g, "compile_prep")
    return np.asarray(execute(gc, {"x": x})[gc.output_names[0]])


@pytest.mark.parametrize("name,shape", [
    ("TFC-w1a1", (1, 784)),
    ("TFC-w2a2", (1, 784)),
    ("CNV-w1a1", (1, 3, 32, 32)),
])
def test_zoo_full_integer_coverage_and_bit_exact(name, shape):
    g = zoo.ZOO[name]()
    plan = compile_graph(g)
    stats = plan.requant_stats()
    assert stats["fp32_segments"] == 0, plan.describe()
    assert stats["coverage"] == 1.0 and stats["kernel_segments"] >= 4
    assert stats["fp32_ops_eliminated"] > 0
    # every kernel multiplies int8 codes on the MXU's int8 path
    assert {s.meta["operand"] for s in plan.segments
            if "operand" in s.meta} == {"int8"}, plan.describe()
    x = np.random.RandomState(0).randn(*shape).astype(np.float32)
    out = np.asarray(plan({"x": x})[plan.graph.output_names[0]])
    np.testing.assert_array_equal(_oracle(g, x), out,
                                  err_msg=plan.describe())


@pytest.mark.parametrize("a_bits,k,operand,acc", [
    (8, 512, "int8", "int32"),      # codes fit int8: the MXU int8 path
    (16, 8, "f32", "int32"),        # wide codes, sums < 2**24: exact in f32
    (16, 512, "f32", "float32"),    # sums may pass 2**24: f32 accumulator
], ids=["int8", "wide_exact", "wide_f32_acc"])
def test_kernel_operand_selection(a_bits, k, operand, acc):
    """select_operand feeds the MXU int8 codes only when they fit, and
    keeps an int32 accumulator on f32 operands only while it is exact."""
    from repro.core import GraphBuilder
    rng = np.random.RandomState(a_bits + k)
    b = GraphBuilder("operand")
    h = b.quant(b.add_input("x", (4, k)), 1.0, 0.0, a_bits)  # integral
    w = b.quant(b.add_initializer("w", rng.randn(k, 6).astype(np.float32)),
                0.5, 0.0, 4, narrow=True)
    (y,) = b.add_node("MatMul", [h, w], 1)
    b.mark_output(y)
    g = b.build()
    plan = compile_graph(g)
    (seg,) = [s for s in plan.segments if s.kind.startswith("quant_matmul")]
    assert (seg.meta["operand"], seg.meta["acc"]) == (operand, acc)
    lim = 2 ** (a_bits - 1)
    x = rng.randint(-lim, lim, size=(4, k)).astype(np.float32)
    out = np.asarray(plan({"x": x})[plan.graph.output_names[0]])
    np.testing.assert_allclose(_oracle(g, x), out, rtol=1e-6)


def test_use_integer_requant_false_restores_fp32_path():
    g = zoo.build_tfc(2, 2)
    plan = compile_graph(g, use_integer_requant=False)
    stats = plan.requant_stats()
    assert stats["int32_segments"] == 0
    assert stats["fp32_segments"] == stats["kernel_segments"] >= 1
    x = np.random.RandomState(1).randn(1, 784).astype(np.float32)
    out = np.asarray(plan({"x": x})[plan.graph.output_names[0]])
    np.testing.assert_allclose(_oracle(g, x), out, atol=2e-4, rtol=2e-4)


def test_zoo_dyadic_scales_survive_qcdq_round_trip():
    """Satellite fix regression: zoo scale constants are exact dyadics
    (0.125-style); converting to QCDQ and back must keep them
    bit-identical — and still dyadic-decomposable — or the integer path
    silently degrades to fp32 after a format round trip."""
    from repro.core.formats import qcdq_to_qonnx, qonnx_to_qcdq

    g = run_pipeline(zoo.build_tfc(2, 2), "compile_prep")

    def scale_bytes(graph):
        out = []
        for node in graph.nodes:
            if node.op_type in ("Quant", "QuantizeLinear"):
                s = graph.initializers.get(node.inputs[1])
                if s is not None:
                    out.append(np.asarray(s, np.float32).tobytes())
        return sorted(out)

    orig = scale_bytes(g)
    assert orig, "no static Quant scales found"
    back = qcdq_to_qonnx(qonnx_to_qcdq(g))
    assert scale_bytes(back) == orig
    for node in back.nodes:
        if node.op_type == "Quant":
            s = back.initializers.get(node.inputs[1])
            assert s is not None and \
                dyadic_decompose(np.asarray(s, np.float32)) is not None
    # and the round-tripped graph still reaches full integer coverage
    plan = compile_graph(back)
    stats = plan.requant_stats()
    assert stats["kernel_segments"] >= 1 and stats["fp32_segments"] == 0, \
        plan.describe()
