"""The engine's spans and the plan's scopes in a profiler trace: idle time
put down to engine steps, device time to plan segments, and the three
readers that use them.

Two traces recorded on a v5e are read: an older one from a program
without the spans and scopes (``data/cnv-w1a1.offline.v5e.xplane.pb``),
and a 1 s window of ``cnv-w1a1.offline`` with them
(``data/cnv-w1a1.offline.spans.v5e.xplane.pb``, seed 2147500013, four
calls of 4096 images).
"""
import os
import re

import pytest

from bench import spec, trace_reduce as T, trace_scopes as S
from bench.tests.test_bench_trace import _Event, _Line, _Plane, _Profile

DATA = os.path.join(os.path.dirname(__file__), "data")
OLD = os.path.join(DATA, "cnv-w1a1.offline.v5e.xplane.pb")
SPANS = os.path.join(DATA, "cnv-w1a1.offline.spans.v5e.xplane.pb")
NEW_METRICS = ("engine_input_idle_share", "engine_output_idle_share",
               "im2col_time_share")


def _record(path):
    """What the harness gives the readers after a traced run that wrote
    ``path``: ``trace_reduce``'s reading of it (no compiled program: the
    readers here need none) and the cell's chips."""
    from jax.profiler import ProfileData
    return {"trace": T.reduce(ProfileData.from_file(path), 1), "chips": 1}


def _readings(monkeypatch, path, record=None):
    monkeypatch.setattr(S, "run_xplane", lambda: path)
    record = _record(path) if record is None else record
    bench = spec.Bench()
    return record, {name: bench.metric_reader(name).read(record)
                    for name in NEW_METRICS + ("device_idle_share.offline",)}


def test_span_pieces_take_the_innermost_span():
    spans = [("call", 10, 50), ("stage", 10, 20), ("fetch", 40, 60),
             ("flush", 70, 80)]
    pieces = S.span_pieces(spans, 0, 75)
    assert pieces == [(0, 10, "outside"), (10, 20, "stage"),
                      (20, 40, "call"), (40, 50, "fetch"), (50, 60, "fetch"),
                      (60, 70, "outside"), (70, 75, "flush")]
    assert S.span_pieces([], 0, 5) == [(0, 5, "outside")]
    idle = S.split_gaps([(5, 15), (30, 45), (74, 75)], pieces)
    assert idle == {"outside": 5, "stage": 5, "call": 10, "fetch": 5,
                    "flush": 1}


def test_an_op_without_op_name_takes_its_first_operands_one_hop():
    ops = {(9, "%reshape.26 = s8[4,9]{1,0} reshape(s8[4,9]{0,1} %p.1)"):
           "jit(plan)/seg03_quant_conv_int4/im2col/reshape"}
    by_instr = S.by_instruction(ops)
    slice_ = "%slice.153 = s8[4,4]{1,0} slice(s8[4,9]{1,0} %reshape.26)"
    copy = "%copy.645 = s8[4,4]{1,0} copy(s8[4,4]{0,1} %slice.153)"
    assert S.op_scope(slice_, 9, ops, by_instr) == \
        "jit(plan)/seg03_quant_conv_int4/im2col/reshape"
    assert S.op_scope(copy, 9, ops, by_instr) == ""       # one hop only
    assert S.op_scope(slice_, 7, ops, by_instr) == ""     # another program


def test_xplane_op_names_are_the_recorded_programs_op_names():
    """The ``tf_op`` stats of the recorded trace's event metadata, read
    from the protobuf wire format: each op keyed by its program, which
    the ``XLA Modules`` line names."""
    from jax.profiler import ProfileData
    with open(SPANS, "rb") as f:
        buf = f.read()
    names = S.xplane_op_names(buf)
    assert list(names) == ["/device:TPU:0"]
    ops = names["/device:TPU:0"]
    plane = ProfileData.from_serialized_xspace(buf).find_plane_with_name(
        "/device:TPU:0")
    _, mods = S.module_intervals(plane)
    programs = {name: pid for _, _, name, pid in mods}
    assert set(programs) == {"jit_plan", "jit_copy", "jit_dynamic_slice"}
    plan = programs["jit_plan"]
    assert sum(pid == plan for pid, _ in ops) == 167
    # every op_name of the plan's program is under a segment scope, but
    # those of the copies of its arguments, which name the argument
    assert sorted(op for (pid, _), op in ops.items() if pid == plan
                  and not op.startswith("jit(plan)/seg")) == \
        ["consts['__seg20_w']", "consts['__seg3_w']", "inputs['x']"]
    assert [op for (pid, _), op in ops.items() if pid != plan] == \
        ["jit(dynamic_slice)/dynamic_slice"]
    # the same op_names as the compiled program's, instruction for
    # instruction
    with open(os.path.join(DATA, "cnv-w1a1.slot256.op_names.hlo")) as f:
        compiled = {m.group(1): m.group(2).replace("\\'", "'")
                    for m in re.finditer(r'%([\w.\-]+) = .*op_name="([^"]*)"',
                                         f.read())}
    by_instr = S.by_instruction(ops)
    assert {name: op for (pid, name), op in by_instr.items()
            if pid == plan} == {name: compiled[name] for pid, name in by_instr
                                if pid == plan}
    assert by_instr[(plan, "pad.30")] == "jit(plan)/seg06_quant_conv_int4/" \
        "jit(quant_matmul_int4)/jit(_pad)/pad"
    assert sum("/im2col/" in op for op in ops.values()) == 21


def test_reduce_attributes_idle_to_engine_steps_and_ops_to_segments():
    """One chip, a 100 ns window under one engine ``call``: 45 ns busy in
    two programs, 55 ns idle under its steps."""
    fusion = "%fusion.1 = s8[4]{0} fusion(s8[4]{0} %p)"
    copy2 = "%copy.2 = s8[4,9]{1,0} copy(s8[4,9]{0,1} %x)"
    copy3 = "%copy.3 = s8[4]{0} copy(s8[4]{0} %copy.2)"
    add4 = "%add.4 = f32[4]{0} add(f32[4]{0} %p.1)"
    op_names = {"/device:TPU:0": {
        (9, copy2): "jit(plan)/seg01_quant_conv/im2col/transpose",
        (9, fusion): "jit(plan)/seg02_bipolar_act/sign"}}
    profile = _Profile([
        _Plane("/host:CPU", [_Line("python", [
            _Event("window", 1000, 100), _Event("engine_call", 1000, 100),
            _Event("call", 1000, 100), _Event("stage", 1000, 20),
            _Event("dispatch", 1020, 20), _Event("sync", 1040, 40),
            _Event("fetch", 1080, 20)])]),
        _Plane("/device:TPU:0", [
            _Line("XLA Modules", [_Event("jit_dynamic_slice(7)", 1010, 5),
                                  _Event("jit_plan(9)", 1020, 50)]),
            _Line("XLA Ops", [_Event(fusion, 1010, 5),
                              _Event(copy2, 1020, 20),
                              _Event(copy3, 1040, 10),
                              _Event(add4, 1050, 10)])]),
    ])
    r = S.reduce(profile, 1, op_names)
    assert r["busy_s"] == pytest.approx(45e-9)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["engine_idle_ns"] == pytest.approx(
        {"stage": 15, "sync": 20, "fetch": 20})
    assert r["segment_s"] == pytest.approx(
        {"outside_plan:jit_dynamic_slice": 5e-9,
         "seg01_quant_conv": 30e-9, "unattributed": 10e-9})
    assert r["im2col_s"] == pytest.approx(30e-9)
    assert dict(r["breakdown"]["idle_by_engine_span"]) == pytest.approx(
        {"stage": 15e-9, "sync": 20e-9, "fetch": 20e-9})
    assert r["breakdown"]["segments"][0][0] == "seg01_quant_conv"
    old = T.reduce(profile, 1)
    assert (r["window_s"], r["busy_s"]) == (old["window_s"], old["busy_s"])


def test_reduce_reads_nothing_without_spans_or_scopes():
    profile = _Profile([
        _Plane("/host:CPU", [_Line("python", [
            _Event("window", 0, 100), _Event("engine_call", 0, 100)])]),
        _Plane("/device:TPU:0", [
            _Line("XLA Modules", [_Event("jit_plan(9)", 0, 50)]),
            _Line("XLA Ops", [_Event(
                "%copy.5 = s8[4,4]{1,0} copy(s8[4,4]{0,1} %p)", 0, 50)])]),
    ])
    r = S.reduce(profile, 1, {})
    assert r["engine_idle_ns"] == {} and r["segment_s"] == {}
    assert r["im2col_s"] is None
    assert r["breakdown"] == {"segments": [], "idle_by_engine_span": []}


def test_new_readers_are_silent_on_a_program_without_spans_or_scopes(
        monkeypatch):
    """The parent's program, recorded on the chip, has no engine span and
    no scope: the new metrics read nothing there, do not raise, and add
    nothing to the run's breakdown."""
    record, got = _readings(monkeypatch, OLD)
    assert [got[name] for name in NEW_METRICS] == [None, None, None]
    assert got["device_idle_share.offline"] == pytest.approx(10.78633,
                                                             abs=1e-4)
    assert set(record["trace"]["breakdown"]) == {"device_ops", "idle_gaps"}


def test_readers_refuse_a_trace_of_another_window(monkeypatch):
    """The newest trace under the trace directory is read only where it
    is the one ``trace_reduce`` read for this run."""
    record = _record(OLD)
    _, got = _readings(monkeypatch, SPANS, record)
    assert [got[name] for name in NEW_METRICS] == [None, None, None]
    monkeypatch.setattr(S, "run_xplane", lambda: None)
    assert S.for_record(_record(SPANS)) is None
    assert S.for_record({}) is None


def test_run_xplane_is_the_newest_under_the_trace_directory(monkeypatch,
                                                             tmp_path):
    from bench import harness
    monkeypatch.setattr(spec, "ROOT", str(tmp_path))
    assert S.run_xplane() is None
    cells = tmp_path / harness.TRACE_DIR
    for i, cell in enumerate(["b", "a"]):
        d = cells / cell / "plugins" / "profile" / "run"
        d.mkdir(parents=True)
        (d / "host.xplane.pb").write_bytes(b"")
        os.utime(d / "host.xplane.pb", ns=(i * 10**9, i * 10**9))
    assert S.run_xplane() == str(cells / "a" / "plugins" / "profile" / "run"
                                 / "host.xplane.pb")


@pytest.mark.parametrize("config", ["cnv-w1a1", "mobilenet-w4a4-224"])
def test_plan_ops_carry_segment_and_im2col_scopes(config):
    """The plan compiled from the configuration's graph (32x32 input,
    batch 2, on the CPU): every kernel segment's ops carry its
    ``segNN_kind`` scope in their ``op_name``, every 3x3 conv's patch or
    tap ops carry ``im2col`` beneath it, and no other op does."""
    import jax
    import jax.numpy as jnp

    from bench import layers as L
    from bench.graph import build_graph
    from repro.core.compile import compile_graph
    bench = spec.Bench()
    cfg = dict(bench.config(config), input_shape=[3, 32, 32])
    layers = bench.layers(cfg)
    g = build_graph(config, layers, L.draw_weights(layers, 3),
                    cfg["input_shape"])
    plan = compile_graph(g)
    x = {g.input_names[0]: jax.ShapeDtypeStruct((2, 3, 32, 32),
                                                 jnp.float32)}
    text = plan._jitted.lower(plan.consts, x).compile().as_text()
    assert text.startswith("HloModule jit_plan")
    op_names = re.findall(r'op_name="([^"]*)"', text)
    by_scope = {}
    for name in op_names:
        seg = S._SEGMENT.search(name)
        by_scope.setdefault(seg.group(1) if seg else None, []).append(name)
    n_im2col = 0
    for i, seg in enumerate(plan.segments):
        ops = by_scope.get(f"seg{i:02d}_{seg.kind}", [])
        if seg.kind != "interp":
            assert ops, (i, seg.kind)
        convs = [n for n in seg.nodes if n.op_type == "Conv"]
        unfolded = [o for o in ops if "/im2col/" in o]
        if convs and max(convs[0].attrs["kernel_shape"]) > 1:
            assert unfolded, (i, seg.kind)
        assert not unfolded or seg.kind.startswith("quant_conv")
        n_im2col += len(unfolded)
    assert n_im2col == sum("/im2col/" in o for o in op_names)


def test_recorded_chip_trace_keeps_every_existing_reading():
    """Every key ``trace_reduce`` reads on the old trace: the values it
    gave before the engine's spans and the plan's scopes, so that a
    changed reading shows here."""
    from jax.profiler import ProfileData
    with open(os.path.join(DATA, "cnv-w1a1.slot256.custom_calls.hlo")) as f:
        hlo = f.read()
    r = T.reduce(ProfileData.from_file(OLD), chips=1, hlo_text=hlo)
    assert (r["window_s"], r["busy_s"], r["pallas_s"], r["devices"]) == \
        pytest.approx((0.32879382, 0.293329023, 0.022353842, 1), rel=1e-9)
    calls = r["pallas_calls"]
    assert len(calls) == 200
    assert sum(c["s"] for c in calls) == pytest.approx(0.022353842,
                                                       rel=1e-9)
    assert sum(c["ops"] for c in calls) == 986372874240
    assert sum(c["bytes"] for c in calls) == 8884402880
    expected = [["reshape.26 s8[200704,576]", 0.046109382],
                ["copy.625 s8[256,12,12,64,9]", 0.038833612],
                ["copy.580 s8[256,28,28,64,9]", 0.030124531],
                ["reshape.33 s8[25600,1152]", 0.027640469],
                ["reshape.455 s8[36864,576]", 0.020611393],
                ["copy.595 s8[256,10,10,128,9]", 0.017874253],
                ["quant_matmul_int4.8 f32[200704,64]", 0.013321202],
                ["copy.645 s8[200704,512]", 0.008678715],
                ["reshape.191 s32[256,30,30,64]", 0.007452236],
                ["fusion.29 s32[230400,64]", 0.007180696]]
    for key, want in (("device_ops", expected),
                      ("idle_gaps", [["engine_call", 0.035464797]])):
        got = r["breakdown"][key]
        assert [k for k, _ in got] == [k for k, _ in want]
        assert [v for _, v in got] == pytest.approx([v for _, v in want],
                                                    rel=1e-9)


@pytest.fixture(scope="module")
def spans_trace():
    from jax.profiler import ProfileData
    with open(SPANS, "rb") as f:
        buf = f.read()
    pd = ProfileData.from_serialized_xspace(buf)
    return pd, S.reduce(pd, 1, S.xplane_op_names(buf))


def test_recorded_engine_steps_nest_under_the_harness_call(spans_trace):
    pd, _ = spans_trace
    events = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for p in pd.planes if p.name.startswith("/host:")
                    for line in p.lines for e in line.events
                    if e.name in S.ENGINE_SPANS + ("engine_call",))
    outer = [e for e in events if e[2] == "engine_call"]
    assert len(outer) == 4
    for a, b, _ in outer:
        inner = [e[2] for e in events if a <= e[0] and e[1] <= b
                 and e[2] != "engine_call"]
        assert inner == ["call", "stage", "dispatch", "sync", "fetch"]


def test_recorded_engine_idle_adds_up_to_the_device_idle(monkeypatch,
                                                         spans_trace):
    _, r = spans_trace
    idle = r["engine_idle_ns"]
    assert set(idle) <= set(S.ENGINE_SPANS) | {"outside"}
    record, got = _readings(monkeypatch, SPANS)
    rest = 100 * (idle["call"] + idle["outside"]) * 1e-9 / r["window_s"]
    assert got["engine_input_idle_share"] + \
        got["engine_output_idle_share"] + rest == \
        pytest.approx(got["device_idle_share.offline"], rel=1e-9)
    assert got["engine_input_idle_share"] == pytest.approx(2.43188,
                                                           abs=1e-4)
    assert got["engine_output_idle_share"] == pytest.approx(4.13885,
                                                            abs=1e-4)
    assert got["device_idle_share.offline"] == pytest.approx(6.78271,
                                                             abs=1e-4)
    breakdown = record["trace"]["breakdown"]
    assert breakdown["idle_by_engine_span"][0][0] == "fetch"
    assert breakdown["segments"] == r["breakdown"]["segments"]


def test_recorded_segments_add_up_to_busy(monkeypatch, spans_trace):
    _, r = spans_trace
    seg = r["segment_s"]
    assert sum(seg.values()) == pytest.approx(r["busy_s"], rel=1e-9)
    assert {k for k in seg if not k.startswith("seg")} == {
        "unattributed", "outside_plan:jit_copy",
        "outside_plan:jit_dynamic_slice"}
    assert r["breakdown"]["segments"][0][0] == "seg03_quant_conv_int4"
    # XLA's own fusions carry no op_name in the trace: what their first
    # operand does not place stays unattributed
    assert seg["unattributed"] == pytest.approx(0.05607689 * r["busy_s"],
                                                rel=1e-6)
    _, got = _readings(monkeypatch, SPANS)
    assert got["im2col_time_share"] == pytest.approx(67.53756, abs=1e-4)
