"""The trace reduction: intervals, gap labels, and Pallas call costs.

Costs are read from the Pallas custom calls of both configurations'
plans as the TPU compiler emits them (``data/*.custom_calls.hlo``,
compiled for a described v5e).
"""
import os

import pytest

from bench import trace_reduce as T

DATA = os.path.join(os.path.dirname(__file__), "data")


def _calls(name):
    with open(os.path.join(DATA, f"{name}.custom_calls.hlo")) as f:
        return T.hlo_custom_calls(f.read())


@pytest.fixture(scope="module")
def cnv():
    return _calls("cnv-w1a1.slot256")


@pytest.fixture(scope="module")
def mobilenet():
    return _calls("mobilenet-w4a4-224.slot64")


def test_union_length_and_gaps():
    covered, gaps = T.union_length([(2, 4), (3, 6), (8, 9), (12, 20)], 0, 10)
    assert covered == 5
    assert gaps == [(0, 2), (6, 8), (9, 10)]
    assert T.union_length([], 0, 10) == (0.0, [(0, 10)])


def test_gap_label_is_the_innermost_annotation():
    label = T.GapLabels([("window", 0, 100), ("engine_call", 10, 20),
                         ("engine_call", 30, 32), ("submit", 50, 51)])
    assert label((12, 14)) == "engine_call"
    assert label((30, 31)) == "engine_call"
    assert label((21, 29)) == "window"
    assert label((200, 210)) == "outside"


def test_matmul_cost(cnv):
    # CNV's first conv as im2col: M = 256 * 30 * 30, K = 3 * 3 * 3, N = 64
    ops, nbytes = T.kernel_cost(cnv["quant_matmul.1"])
    m, k, n = 230400, 27, 64
    assert ops == 2 * m * k * n
    assert nbytes == m * n * 4 + m * k + k * n + n * 4


def test_int4_matmul_counts_k_unpacked(cnv):
    # CNV's second conv: K = 576 arrives as two halves padded to 512 each
    ops, _ = T.kernel_cost(cnv["quant_matmul_int4.8"])
    assert ops == 2 * 200704 * 1024 * 64


def test_depthwise_and_elementwise_costs(cnv, mobilenet):
    ops, _ = T.kernel_cost(mobilenet["quant_depthwise_conv2d.9"])
    assert ops == 2 * 9 * (64 * 112 * 112) * 32
    ops, nbytes = T.kernel_cost(cnv["quant_dequant.1"])
    assert ops == 0 and nbytes == 24576 * 32 * (1 + 4) + 2 * 4


def test_every_call_has_bytes(cnv, mobilenet):
    assert (len(cnv), len(mobilenet)) == (10, 29)
    for line in list(cnv.values()) + list(mobilenet.values()):
        assert T.kernel_cost(line)[1] > 0


class _Event:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class _Profile:
    def __init__(self, planes):
        self.planes = planes


def test_reduce_arithmetic_on_a_synthetic_trace(cnv):
    """Two chips, a 100 ns window: chip 0 busy 60 ns (a Pallas call of
    40 ns and an XLA copy of 20 ns), chip 1 busy 20 ns."""
    matmul = cnv["quant_matmul.1"].split(", custom_call_target")[0]
    copy = "%copy.5 = s8[4,4]{1,0} copy(s8[4,4]{0,1} %p)"
    profile = _Profile([
        _Plane("/host:CPU", [_Line("python", [
            _Event("window", 1000, 100), _Event("engine_call", 1000, 50),
            _Event("fetch", 1070, 30)])]),
        _Plane("/device:TPU:0", [_Line("XLA Ops", [
            _Event(matmul, 1000, 40), _Event(copy, 1040, 20)])]),
        _Plane("/device:TPU:1", [_Line("XLA Ops", [
            _Event(copy, 1080, 20)])]),
        _Plane("/device:TPU:2", [_Line("XLA Ops", [])]),
    ])
    r = T.reduce(profile, chips=2, hlo_text=cnv["quant_matmul.1"])
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx((60 + 20) / 2 * 1e-9)
    assert r["pallas_s"] == pytest.approx(40 / 2 * 1e-9)
    (call,) = r["pallas_calls"]
    assert call["name"] == "quant_matmul.1" and call["s"] == \
        pytest.approx(40e-9)
    assert call["ops"] == T.kernel_cost(cnv["quant_matmul.1"])[0]
    ops = dict(r["breakdown"]["device_ops"])
    assert ops["copy.5 s8[4,4]"] == pytest.approx(40 / 2 * 1e-9)
    idle = dict(r["breakdown"]["idle_gaps"])
    # a gap goes whole to the innermost span over its midpoint: chip 0
    # idles 1060-1100 (midpoint under fetch), chip 1 1000-1080 (midpoint
    # 1040, under engine_call)
    assert idle == pytest.approx({"fetch": 40 / 2 * 1e-9,
                                  "engine_call": 80 / 2 * 1e-9})


def test_device_event_names_join_the_compiled_calls(cnv):
    """On the chip an op event is named by its instruction's text, with
    the operand shapes inline and no call target (as recorded on a v5e)."""
    event = ("%quant_matmul_int4.8 = f32[200704,64]{1,0:T(8,128)S(1)} "
             "custom-call(s8[200704,512]{1,0:T(8,128)(4,1)} %copy.645, "
             "s8[200704,512]{1,0:T(8,128)(4,1)} %copy.646, "
             "s8[512,64]{1,0:T(8,128)(4,1)} %p.1, s32[1,64]{1,0} %p.2)")
    assert T.op_name(event) == "quant_matmul_int4.8"
    assert T.short_name(event) == "quant_matmul_int4.8 f32[200704,64]"
    assert T._call_text(event, cnv) == cnv["quant_matmul_int4.8"]
    copy = ("%copy.625 = s8[256,12,12,64,9]{4,3,2,1,0} "
            "copy(s8[256,12,12,64,9]{0,3,2,1,4} %x)")
    assert T._call_text(copy, cnv) is None
    assert T.short_name(copy) == "copy.625 s8[256,12,12,64,9]"


def test_recorded_chip_trace(cnv):
    """A 0.3 s traced window of ``cnv-w1a1.offline`` recorded on a TPU v5e
    (seed 77, call of 1024 images): its reduction gives what that run
    printed."""
    from jax.profiler import ProfileData

    from bench import spec
    bench = spec.Bench()
    pd = ProfileData.from_file(
        os.path.join(DATA, "cnv-w1a1.offline.v5e.xplane.pb"))
    hlo = "\n".join(cnv.values())
    r = T.reduce(pd, chips=1, hlo_text=hlo)
    assert r["window_s"] == pytest.approx(0.32879382, rel=1e-6)
    assert r["busy_s"] == pytest.approx(0.293329023, rel=1e-6)
    assert len(r["pallas_calls"]) == 200          # 20 slots x 10 kernels
    assert {c["name"] for c in r["pallas_calls"]} == set(cnv)
    record = {"trace": r, "peaks": bench.peaks("TPU v5 lite")}
    read = lambda name: bench.metric_reader(name).read(record)
    assert read("device_idle_share.offline") == \
        pytest.approx(10.7863, abs=1e-3)
    assert read("pallas_time_share") == pytest.approx(7.6207, abs=1e-3)
    assert 0 < read("pallas_roofline") < 100
    assert read("pallas_roofline") == pytest.approx(48.528, abs=1e-2)
    assert [name for name, _ in r["breakdown"]["idle_gaps"]] == \
        ["engine_call"]
    ops = r["breakdown"]["device_ops"]
    assert len(ops) == 10 and ops[0][0] == "reshape.26 s8[200704,576]"
