"""A residual network is new files and entries, and no edit.

The twin of ``test_a_new_cell_needs_only_new_files_and_entries`` for a
layer list that branches and merges: a copy of the benchmark gains a
bottleneck family, its configuration and an offline mix
(``bench/tests/residual.py``), and runs correct on the CPU with every
file that was there unchanged; the configuration's control, put in the
program's place, is rejected.
"""
import types

import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, layers as L, spec
from bench.graph import build_graph
from bench.reference.forward import Reference
from bench.tests import residual
from bench.tests.test_bench_spec import _copy_bench
from repro.core.compile import CompiledPlan

SEED = 2**31 + 3


@pytest.fixture
def checkout(tmp_path):
    root = _copy_bench(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    residual.add_to(str(root))
    yield root
    after = {p: p.read_bytes() for p in before}
    assert after == before                  # no file that was there changed


def _run(root):
    args = types.SimpleNamespace(workload=residual.CELL, seed=SEED,
                                 seconds=0.5, trace=0)
    return harness.run(args, require_tpu=False, root=str(root))


def test_a_residual_family_needs_only_new_files_and_entries(checkout):
    bench = spec.Bench(str(checkout))
    cfg = bench.config(residual.NAME)
    layers = bench.layers(cfg)
    graph = build_graph(cfg["name"], layers, L.draw_weights(layers, SEED),
                        cfg["input_shape"])
    ops = [n.op_type for n in graph.nodes]
    assert ops.count("Add") == 1 and ops.count("Conv") == 5
    (pool,) = [n for n in graph.nodes if n.op_type == "MaxPool"]
    assert pool.attrs["pads"] == [1, 1, 1, 1]
    assert L.shapes(layers, cfg["input_shape"])[-1] == (10,)
    result = _run(checkout)
    assert result["correct"] and result["attempted"] >= 1
    assert set(result["metrics"]) == {"setup_s", "images_per_s"}


def test_residual_control_in_the_programs_place_is_incorrect(checkout,
                                                              monkeypatch):
    cfg = residual.config()
    layers = residual.layers(cfg)
    control = Reference(layers, L.draw_weights(layers, SEED), block=8,
                        control=cfg["check"]["control"])

    def answer(self, inputs, **kw):
        x = np.asarray(inputs[self.graph.input_names[0]])
        return {self.graph.output_names[0]: jnp.asarray(control(x))}
    monkeypatch.setattr(CompiledPlan, "__call__", answer)
    result = _run(checkout)
    assert result["correct"] is False
    assert result["checks"]["logit_gap"]["value"] > \
        result["checks"]["logit_gap"]["limit"]
