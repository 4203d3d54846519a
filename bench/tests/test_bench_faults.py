"""A run with the timed path broken underneath reports ``correct: false``.

The harness runs on the CPU here (its look for a TPU skipped) at a small
slot, with the plan's answers corrupted where they are produced.  Each
fault an offline cell can have is planted once:

* ``answer_altered``: one logit of every slot's first answer changed;
* ``half_batch_left_out``: the second half of every slot answered with
  zeros, as if those rows were never computed;
* ``rows_misplaced``: every slot's rows rotated by a quarter, as when
  answers are scattered to other requests.

And each configuration's control (``check.control``: the reference in the
next precision down), put in the program's place, fails the comparison.
"""
import types

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, layers as L, spec
from bench.reference.forward import Reference
from repro.core.compile import CompiledPlan

SEED = 2**31 + 11
FAULTS = {
    "answer_altered": lambda y: y.at[0, 0].add(8.0),
    "half_batch_left_out": lambda y: y.at[y.shape[0] // 2:].set(0.0),
    "rows_misplaced": lambda y: jnp.roll(y, y.shape[0] // 4, axis=0),
}


@pytest.fixture
def small_traffic(monkeypatch):
    orig = spec.Bench.traffic

    def traffic(self, name):
        doc = orig(self, name)
        doc.update(max_batch=8, call_batch=16, pool_calls=2)
        return doc
    monkeypatch.setattr(spec.Bench, "traffic", traffic)


def _run(cell, seconds=0.5):
    args = types.SimpleNamespace(workload=cell, seed=SEED, seconds=seconds,
                                 trace=0)
    return harness.run(args, require_tpu=False)


def _break(monkeypatch, fault):
    orig = CompiledPlan.__call__

    def broken(self, inputs, **kw):
        out = orig(self, inputs, **kw)
        name = self.graph.output_names[0]
        return dict(out, **{name: FAULTS[fault](out[name])})
    monkeypatch.setattr(CompiledPlan, "__call__", broken)


def _assert_incorrect(result, capsys):
    assert result["correct"] is False
    assert result["checks"]["logit_gap"]["value"] > \
        result["checks"]["logit_gap"]["limit"]
    err = capsys.readouterr().err.strip().splitlines()
    assert err[-3].startswith("check logit_gap:")    # last stderr lines


def test_sound_run_is_correct(small_traffic):
    result = _run("cnv-w1a1.offline")
    assert result["correct"]
    assert set(result["metrics"]) == {"setup_s", "images_per_s"}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_makes_the_run_incorrect(small_traffic, monkeypatch, fault,
                                       capsys):
    _break(monkeypatch, fault)
    _assert_incorrect(_run("cnv-w1a1.offline"), capsys)


# MobileNet at 96x96: its 3x3 average pool sums on 1/9 steps, which
# bfloat16 does not hold exactly, as the 7x7 pool's 1/49 steps at 224
@pytest.mark.parametrize("cell,img", [("cnv-w1a1.offline", None),
                                      ("mobilenet-w4a4-224.offline", 96)])
def test_control_in_the_programs_place_is_incorrect(small_traffic,
                                                    monkeypatch, cell, img,
                                                    capsys):
    """The configuration's control answers every slot of the window in
    the program's place; the harness's comparison rejects it."""
    bench = spec.Bench()
    cfg = bench.config(bench.workload(cell)["config"])
    if img:
        orig_config = spec.Bench.config

        def config(self, name):
            doc = orig_config(self, name)
            doc["input_shape"] = [3, img, img]
            return doc
        monkeypatch.setattr(spec.Bench, "config", config)
        cfg["input_shape"] = [3, img, img]
    layers = bench.layers(cfg)
    control = Reference(layers, L.draw_weights(layers, SEED), block=8,
                        control=cfg["check"]["control"])

    def answer(self, inputs, **kw):
        x = np.asarray(inputs[self.graph.input_names[0]])
        return {self.graph.output_names[0]: jnp.asarray(control(x))}
    monkeypatch.setattr(CompiledPlan, "__call__", answer)
    _assert_incorrect(_run(cell), capsys)
