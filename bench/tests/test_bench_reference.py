"""The plain reference against the program's interpreter, and the control.

``bench/reference`` shares no code with ``core/executor.py``; on the same
graph and inputs the two must agree exactly (every value lies on a
power-of-two grid, so fp32 is exact).  Each configuration's control (the
reference in the next precision down) must be far outside the
comparison's limit.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from bench import check, inputs, layers as L, spec
from bench.graph import build_graph
from bench.reference.forward import Reference
from bench.tests import residual
from repro.core import execute, transforms

BENCH = spec.Bench()


def _case(name, img, seed, n=4):
    """A configuration of the benchmark, or the residual one that
    ``bench/tests/residual.py`` adds by files alone."""
    ours = name == residual.NAME
    cfg = residual.config() if ours else BENCH.config(name)
    if img:
        cfg["input_shape"] = [3, img, img]
    layers = residual.layers(cfg) if ours else BENCH.layers(cfg)
    codes = L.draw_weights(layers, seed)
    x = inputs.images(seed, 1, n, cfg["input_shape"])
    return cfg, layers, codes, x


@pytest.mark.parametrize("name,img", [("cnv-w1a1", None),
                                      ("mobilenet-w4a4-224", 32),
                                      (residual.NAME, None)])
@pytest.mark.parametrize("seed", [0, 2**31 + 7])
def test_reference_matches_the_interpreter(name, img, seed):
    cfg, layers, codes, x = _case(name, img, seed)
    g = transforms.cleanup(build_graph(name, layers, codes,
                                       cfg["input_shape"]))
    oracle = np.asarray(execute(g, {g.input_names[0]: x})[g.output_names[0]])
    ref = Reference(layers, codes, block=4)(x)
    assert ref.shape == oracle.shape
    assert check.logit_gap(oracle, ref) == 0.0
    assert np.std(ref, axis=0).mean() > 0       # answers depend on the input


# MobileNet's bf16 and high controls need an average pool that bfloat16
# cannot hold exactly: a 3x3 pool (1/9 steps) at 96x96, as 7x7 (1/49) at 224
@pytest.mark.parametrize("name,img,control", [
    ("cnv-w1a1", None, "int4"), ("mobilenet-w4a4-224", 32, "int4"),
    (residual.NAME, None, "int4"),
    ("mobilenet-w4a4-224", 96, "bf16"), ("mobilenet-w4a4-224", 96, "high")])
def test_control_fails_the_comparison(name, img, control):
    cfg, layers, codes, x = _case(name, img, 11)
    ref = Reference(layers, codes, block=4)(x)
    ctl = Reference(layers, codes, block=4, control=control)(x)
    limit = cfg["check"]["logit_gap_limit"]
    assert check.logit_gap(ctl, ref) > 2 * limit
    assert not check.judge(limit, check.logit_gap(ctl, ref), 0, len(x))[0]


def test_reference_imports_nothing_of_the_program():
    """Every op file's reference runs with no ``repro`` module loaded."""
    code = (
        "import sys, os\n"
        "from bench import inputs, layers as L, spec\n"
        "from bench.reference.forward import Reference\n"
        "cfg = spec.Bench().config('cnv-w1a1')\n"
        "layers = spec.Bench().layers(cfg)\n"
        "for f in os.listdir(L.OPS_DIR):\n"
        "    if f.endswith('.py'):\n"
        "        L.op(f[:-3])\n"
        "x = inputs.images(0, 1, 2, cfg['input_shape'])\n"
        "Reference(layers, L.draw_weights(layers, 0), block=2)(x)\n"
        "print(sorted(m for m in sys.modules if m.split('.')[0] == 'repro'))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.ROOT)
    proc = subprocess.run([sys.executable, "-c", code], cwd=spec.ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout.strip().splitlines()[-1] == "[]"


def test_logit_gap_reads_inf_on_bad_answers():
    ref = np.ones((2, 3), np.float32)
    assert check.logit_gap(ref, ref) == 0.0
    assert check.logit_gap(ref[:1], ref) == float("inf")
    assert check.logit_gap(np.full((2, 3), np.nan), ref) == float("inf")
    assert not check.judge(1.0, 0.0, 1, 2)[0]       # a missing answer
    assert not check.judge(1.0, 0.0, 0, 0)[0]       # nothing compared
