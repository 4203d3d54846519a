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
