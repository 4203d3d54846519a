"""Compile rehearsal for the TPU: the main-path kernels at real widths.

Each test compiles one Pallas kernel (``interpret=False``), or one whole
zoo plan body, for a described TPU v5e with the TPU compiler installed
here.  Nothing runs: a pass says Mosaic accepts the kernel's operand types,
slices and VMEM use, which interpret mode cannot show.  The topology is
described inside a module fixture, never at import, so every test worker
collects the same tests and only the worker given this file loads the TPU
library; where it cannot be described, every test here skips.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.requant import IntRequant

# an integer epilogue with every stage on: relu, a fused 4-bit act Quant
_RQ = IntRequant(shift=10, relu=True, has_act=True, act_shift=6, act_zp=0,
                 act_lo=-8, act_hi=7, act_out_shift=4)
M, K, N = 1024, 1152, 256          # CNV's widest conv as an im2col matmul


@pytest.fixture(scope="module")
def one_chip():
    """One described v5e chip; the persistent cache is off meanwhile (an
    executable compiled for a described chip cannot be read back here)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:      # no TPU compiler: nothing to rehearse
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)


def _compile(one_chip, fn, *args):
    """Compile ``fn`` for the described chip from (shape, dtype) pairs."""
    specs = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in args]
    return jax.jit(fn).lower(*specs).compile()


@pytest.mark.parametrize("epilogue", ["f32", "int32"])
def test_quant_matmul_compiles(one_chip, epilogue):
    if epilogue == "f32":
        fn = functools.partial(ops.quant_matmul, interpret=False)
        args = [((M, K), jnp.float32), ((K, N), jnp.int8),
                ((N,), jnp.float32)]
    else:
        fn = functools.partial(ops.quant_matmul, interpret=False,
                               acc_dtype=jnp.int32, requant=_RQ)
        args = [((M, K), jnp.int8), ((K, N), jnp.int8), ((N,), jnp.int32)]
    _compile(one_chip, fn, *args)


@pytest.mark.parametrize("epilogue", ["f32", "int32"])
def test_quant_matmul_int4_compiles(one_chip, epilogue):
    if epilogue == "f32":
        fn = functools.partial(ops.quant_matmul_int4, interpret=False)
        args = [((M, K), jnp.float32), ((K // 2, N), jnp.int8),
                ((N,), jnp.float32)]
    else:
        fn = functools.partial(ops.quant_matmul_int4, interpret=False,
                               acc_dtype=jnp.int32, requant=_RQ)
        args = [((M, K), jnp.int8), ((K // 2, N), jnp.int8),
                ((N,), jnp.int32)]
    _compile(one_chip, fn, *args)


@pytest.mark.parametrize("epilogue", ["f32", "int32"])
def test_quant_grouped_matmul_packed_compiles(one_chip, epilogue):
    g, kg, ng = 4, 288, 64
    if epilogue == "f32":
        fn = functools.partial(ops.quant_grouped_matmul, packed=True,
                               interpret=False)
        args = [((g, M, kg), jnp.float32), ((g, kg // 2, ng), jnp.int8),
                ((g * ng,), jnp.float32)]
    else:
        fn = functools.partial(ops.quant_grouped_matmul, packed=True,
                               interpret=False, acc_dtype=jnp.int32,
                               requant=_RQ)
        args = [((g, M, kg), jnp.int8), ((g, kg // 2, ng), jnp.int8),
                ((g * ng,), jnp.int32)]
    _compile(one_chip, fn, *args)


@pytest.mark.parametrize("epilogue", ["f32", "int32"])
def test_quant_depthwise_conv2d_compiles(one_chip, epilogue):
    c = 256                                  # a MobileNet-224 middle layer
    common = dict(kernel_shape=(3, 3), pads=(1, 1, 1, 1), interpret=False)
    if epilogue == "f32":
        fn = functools.partial(ops.quant_depthwise_conv2d, relu=True,
                               act_bits=4, act_signed=False, **common)
        args = [((8, c, 28, 28), jnp.float32), ((9, c), jnp.int8),
                ((c,), jnp.float32), ((c,), jnp.float32), ((), jnp.float32),
                ((), jnp.float32)]
    else:
        fn = functools.partial(ops.quant_depthwise_conv2d,
                               acc_dtype=jnp.int32, requant=_RQ, **common)
        args = [((8, c, 28, 28), jnp.float32), ((9, c), jnp.int8),
                ((c,), jnp.int32)]
    _compile(one_chip, fn, *args)


def test_quant_dequant_codes_compiles(one_chip):
    fn = functools.partial(ops.quant_dequant, bit_width=4, signed=False,
                           interpret=False, emit_codes=True)
    _compile(one_chip, fn, ((8, 64 * 30 * 30), jnp.float32),
             ((), jnp.float32), ((), jnp.float32))


def test_cnv_w1a1_plan_body_compiles(one_chip):
    """The whole default CNV-w1a1 plan at the serving slot (batch 8):
    integer requant, int8 MXU operands, packed int4, fused pools."""
    from repro.core.compile import compile_graph
    from repro.models import zoo
    plan = compile_graph(zoo.build_cnv(1, 1), interpret=False)
    assert plan.requant_stats()["coverage"] == 1.0
    consts = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        plan.consts)
    inputs = {plan.graph.input_names[0]: jax.ShapeDtypeStruct(
        (8, 3, 32, 32), jnp.float32, sharding=one_chip)}
    lowered = jax.jit(plan._plan).lower(consts, inputs)
    n_kernels = sum(1 for s in plan.segments
                    if s.kind.startswith(("quant_conv", "quant_matmul",
                                          "quant_dequant")))
    assert lowered.as_text().count("tpu_custom_call") == n_kernels
    lowered.compile()


def _lane_sizes(line):
    """Sizes of the lane (most minor, non-degenerate) axis of every array
    an HLO instruction line yields, read from its layouts."""
    out = []
    head = line.split(" = ", 1)[1].split("metadata=")[0]
    for dims, layout in re.findall(r"\w+\[([\d,]*)\]\{([\d,]*)", head):
        dims = [int(d) for d in dims.split(",") if d]
        minor = [dims[a] for a in map(int, filter(None, layout.split(",")))
                 if dims[a] > 1]
        if minor:
            out.append(minor[0])
    return out


def test_cnv_w1a1_patches_keep_channels_on_the_lanes(one_chip):
    """The benchmark's CNV-w1a1 plan at its offline slot (256): every conv
    unfolds through the tap-major build, and no instruction the compiler
    emits for an ``im2col`` scope lays kH·kW or OW along its lanes.  (The
    zoo's CNV answers constant logits, so its convs compile away.)"""
    from bench import layers as L, spec
    from bench.graph import build_graph
    from repro.core.compile import compile_graph
    slot = 256
    cfg = spec.Bench().config("cnv-w1a1")
    layers = spec.Bench().layers(cfg)
    graph = build_graph("cnv-w1a1", layers, L.draw_weights(layers, 3),
                        cfg["input_shape"])
    plan = compile_graph(graph, interpret=False)
    assert plan.patch_layouts == {"tap_major": 6}
    banned = {}
    for i, seg in enumerate(plan.segments):
        for node in seg.nodes:
            if node.op_type == "Conv":
                kh, kw = node.attrs["kernel_shape"]
                ow = plan.graph.get_shape(node.outputs[0])[3]
                banned[f"seg{i:02d}_{seg.kind}"] = {kh * kw, ow}
    assert len(banned) == 6
    consts = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        plan.consts)
    inputs = {plan.graph.input_names[0]: jax.ShapeDtypeStruct(
        (slot, 3, 32, 32), jnp.float32, sharding=one_chip)}
    text = jax.jit(plan._plan).lower(consts, inputs).compile().as_text()
    seen = set()
    for line in text.splitlines():
        m = re.search(r'op_name="[^"]*/(seg\d\d_\w+)/im2col/', line)
        if m is None or " = " not in line:
            continue
        seen.add(m.group(1))
        lanes = _lane_sizes(line)
        assert lanes and not banned[m.group(1)] & set(lanes), line.strip()
    assert seen == set(banned)


def _plan_compiles(one_chip, name, layers, input_shape, slot):
    """The benchmark's graph of ``layers`` compiled into a plan, its body
    compiled for the described chip at ``slot`` rows; the plan."""
    from bench import layers as L
    from bench.graph import build_graph
    from repro.core.compile import compile_graph
    plan = compile_graph(build_graph(name, layers, L.draw_weights(layers, 3),
                                     input_shape), interpret=False)
    consts = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        plan.consts)
    inputs = {plan.graph.input_names[0]: jax.ShapeDtypeStruct(
        (slot,) + tuple(input_shape), jnp.float32, sharding=one_chip)}
    lowered = jax.jit(plan._plan).lower(consts, inputs)
    n_kernels = sum(1 for s in plan.segments
                    if s.kind.startswith(("quant_conv", "quant_matmul",
                                          "quant_dequant", "eltwise_add")))
    assert lowered.as_text().count("tpu_custom_call") == n_kernels
    lowered.compile()
    return plan


def test_residual_bottleneck_plan_compiles(one_chip):
    """The benchmark's test bottleneck family (7x7/2 stem, padded 3x3/2
    max-pool on packed int4 codes, 1x1 -> 3x3/2 -> 1x1 with a 1x1/2
    projection, a join on packed carriers) at a slot of 64."""
    from bench.tests import residual
    cfg = residual.config()
    plan = _plan_compiles(one_chip, cfg["name"], residual.layers(cfg),
                          cfg["input_shape"], 64)
    (pool,) = [s for s in plan.segments if s.kind == "quant_pool"]
    assert pool.meta["carrier_in"] == "int4x2"
    assert plan.fusion_stats()["residual_joins"]["int4x2"] == 1
    assert plan.patch_layouts == {"tap_major": 2, "pointwise": 3}


def test_resnet50_stage4_downsampling_block_compiles(one_chip):
    """ResNet-50 w1a2's first stage-4 block at its real widths on a 14x14
    input at the offline slot (64): the 3x3/2 conv at 512 channels
    contracts K = 4,608 (2,304 packed int4 rows), the 1x1/2 projection
    takes the strided pointwise slice, and the join at 7x7 (an odd width)
    reads int8 carriers."""
    from bench import spec
    cfg = spec.Bench().config("resnet50-w1a2-224")
    (cin, mid, cout, stride, scales), = [b for b in cfg["blocks"]
                                         if b[2] == 2048 and b[3] == 2]
    act = {"bits": cfg["act_bits"], "scale_log2": cfg["act_scale_log2"]}
    relu = dict(act, relu=True, signed=False)
    signed = dict(act, relu=False, signed=True)

    def conv(ci, co, k, s, scale, a, **kw):
        return dict(op="conv", cin=ci, cout=co, k=k, stride=s, pad=k // 2,
                    group=1, w_bits=cfg["weight_bits"], w_scale_log2=scale,
                    act=a, **kw)
    layers = [dict(op="input_quant", bits=cfg["act_bits"], signed=False,
                   scale_log2=cfg["act_scale_log2"]),
              conv(cin, mid, 1, 1, scales[0], relu),
              conv(mid, mid, 3, stride, scales[1], relu),
              conv(mid, cout, 1, 1, scales[2], signed),
              conv(cin, cout, 1, stride, scales[3], signed, **{"in": [0]}),
              {"op": "add", "in": [3, 4], "act": relu}]
    plan = _plan_compiles(one_chip, "resnet50-stage4-block", layers,
                          (cin, 14, 14), 64)
    assert plan.fused_counts == {"quant_dequant": 1, "quant_conv_int4": 4,
                                 "eltwise_add": 1}
    assert plan.fusion_stats()["residual_joins"]["int8"] == 1
    assert plan.patch_layouts == {"tap_major": 1, "pointwise": 3}
    assert any(v.shape == (9 * mid // 2, mid)        # packed 3x3/2 weights
               for v in plan.consts.values())
