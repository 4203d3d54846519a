"""ResNet-50 w1a2 at 224x224: the layer table, the reference, the program.

* The layer table at the published widths counts what ResNet-50 has: the
  MACs and weights of torchvision's ``resnet50`` without its batch norms
  and classifier bias, 53 convs, 16 joins and 4 projections.
* The reference at full widths on a small image answers logits that
  depend on the input, and no layer's codes collapse onto one value, so
  the comparison that decides ``correct`` is not vacuous.
* Through the harness on the CPU, on a copy of the benchmark that gains a
  reduced configuration of the same family (one block per stage, widths
  / 8, 96x96, so that the last map is 3x3 and its average leaves the
  bfloat16 grid, as 7x7 does at 224), the program is correct and the
  configuration's control in its place is not.
* ``residual_add_time_share`` reads the joins' segments over busy time,
  and nothing from a plan without joins.
"""
import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, inputs, layers as L, spec, trace_scopes
from bench.graph import build_graph
from bench.reference import forward as F
from bench.reference.forward import Reference
from bench.tests.test_bench_spec import _copy_bench
from repro.core.compile import CompiledPlan

NAME = "resnet50-w1a2-224"
SMALL = "resnet-w1a2-small"
MIX = "offline-b8-c16"
CELL = f"{SMALL}.{MIX}"
SEED = 2**31 + 13
BENCH = spec.Bench()


def _config(img=None):
    cfg = BENCH.config(NAME)
    if img:
        cfg["input_shape"] = [3, img, img]
    return cfg


def small_config() -> dict:
    """The first block of each stage at widths / 8, 96x96; each conv's
    weight scale 2**2 larger, as its fan-in is 8 times smaller."""
    cfg = _config(96)
    firsts = [b for i, b in enumerate(cfg["blocks"]) if i == 0 or b[3] != 1]
    cfg.update(name=SMALL, stem=dict(cfg["stem"],
                                     cout=cfg["stem"]["cout"] // 8),
               blocks=[[ci // 8, m // 8, co // 8, s, [v + 2 for v in sc]]
                       for ci, m, co, s, sc in firsts])
    return cfg


def test_layer_table_counts_resnet50():
    cfg = _config()
    layers = BENCH.layers(cfg)
    c = L.count(layers, cfg["input_shape"])
    assert c["macs"] == 4_089_184_256
    assert c["macs_beyond_first"] == 3_971_170_304
    # torchvision's 25,557,032 parameters less 53 batch norms' 53,120 and
    # the classifier's 1,000 biases
    assert c["weights"] == 25_502_912
    assert cfg["reduced"] == []
    assert L.shapes(layers, cfg["input_shape"])[-1] == (1000,)
    graph = build_graph(NAME, layers, L.draw_weights(layers, 0),
                        cfg["input_shape"])
    ops = [n.op_type for n in graph.nodes]
    assert ops.count("Conv") == 53 and ops.count("Add") == 16
    assert ops.count("BipolarQuant") == 52       # every conv past the stem
    projections = [ly for ly in layers if "in" in ly and ly["op"] == "conv"]
    assert [(p["cin"], p["cout"], p["stride"]) for p in projections] == \
        [(64, 256, 1), (256, 512, 2), (512, 1024, 2), (1024, 2048, 2)]


def _forward_every_layer(layers, codes, x):
    weights = [None if c is None else jnp.asarray(L.float_weights(ly, c))
               for ly, c in zip(layers, codes)]

    def step(i, layer, ins):
        w = weights[i]
        if w is not None:
            w = F._qweight(layer, w, None)
        y = L.op(layer["op"]).reference(layer, ins, w, None)
        return F._act(y, layer.get("act"), None)
    with jax.default_matmul_precision("highest"):
        return L.walk(layers, jnp.asarray(x), step)


@pytest.mark.parametrize("seed", [1, 2**31 + 7])
def test_reference_answers_depend_on_the_input(seed):
    cfg = _config(32)
    layers = BENCH.layers(cfg)
    outs = _forward_every_layer(layers, L.draw_weights(layers, seed),
                                inputs.images(seed, 1, 8,
                                              cfg["input_shape"]))
    for layer, y in zip(layers, outs):
        if layer.get("act"):
            _, n = np.unique(np.asarray(y), return_counts=True)
            assert n.max() < 0.95 * n.sum(), layer
    logits = np.asarray(outs[-1])
    rms = np.sqrt(np.mean(logits ** 2))
    assert np.std(logits, axis=0).mean() >= 0.1 * rms


@pytest.fixture
def checkout(tmp_path):
    """A copy of the benchmark with the reduced configuration, a small
    mix and their cell added as files and entries."""
    root = _copy_bench(tmp_path)
    cfg = small_config()
    (root / f"bench/configs/{SMALL}.json").write_text(json.dumps(cfg))
    (root / f"bench/traffic/{MIX}.json").write_text(json.dumps(
        {"driver": "offline", "why": "test", "max_batch": 8,
         "call_batch": 16, "pool_calls": 2}))
    doc = json.loads((root / "BENCHMARK.json").read_text())
    doc["configs"].append({"name": SMALL, "source": "test",
                           "file": f"bench/configs/{SMALL}.json",
                           "reduced": [], "why": "test"})
    doc["workloads"].append({"name": CELL, "config": SMALL, "traffic": MIX,
                             "chips": 1, "why": "test"})
    for m in doc["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return root


def _run(root):
    args = types.SimpleNamespace(workload=CELL, seed=SEED, seconds=0.5,
                                 trace=0)
    return harness.run(args, require_tpu=False, root=str(root))


def test_program_matches_the_reference_through_the_harness(checkout):
    result = _run(checkout)
    assert result["correct"] and result["attempted"] >= 1
    assert result["checks"]["answers_compared"]["value"] >= 16


def test_control_in_the_programs_place_is_incorrect(checkout, monkeypatch):
    cfg = small_config()
    assert cfg["check"]["control"] == _config()["check"]["control"]
    layers = BENCH.layers(cfg)
    control = Reference(layers, L.draw_weights(layers, SEED), block=8,
                        control=cfg["check"]["control"])

    def answer(self, inputs, **kw):
        x = np.asarray(inputs[self.graph.input_names[0]])
        return {self.graph.output_names[0]: jnp.asarray(control(x))}
    monkeypatch.setattr(CompiledPlan, "__call__", answer)
    result = _run(checkout)
    assert result["correct"] is False
    gap = result["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def test_residual_add_time_share_reads_the_join_segments(monkeypatch):
    reader = BENCH.metric_reader("residual_add_time_share")
    reading = {"busy_s": 8.0, "segment_s": {
        "seg07_eltwise_add": 0.5, "seg11_eltwise_add": 0.3,
        "seg03_quant_conv_int4": 6.0, "seg100_eltwise_add": 0.2,
        "outside_plan:jit_copy": 1.0}}
    monkeypatch.setattr(trace_scopes, "for_record", lambda record: reading)
    assert reader.read({}) == pytest.approx(100.0 * 1.0 / 8.0)
    reading["segment_s"] = {"seg03_quant_conv_int4": 6.0}
    assert reader.read({}) is None                  # a plan without joins
    monkeypatch.setattr(trace_scopes, "for_record", lambda record: None)
    assert reader.read({}) is None                  # no trace with scopes


@pytest.mark.parametrize("trace", ["cnv-w1a1.offline.v5e.xplane.pb",
                                   "cnv-w1a1.offline.spans.v5e.xplane.pb"])
def test_residual_add_time_share_is_silent_on_cnv(monkeypatch, trace):
    """Two v5e traces of CNV, a network without joins, one of a program
    without scopes: the reader reads nothing and does not raise."""
    from jax.profiler import ProfileData
    from bench import trace_reduce
    path = os.path.join(os.path.dirname(__file__), "data", trace)
    monkeypatch.setattr(trace_scopes, "run_xplane", lambda: path)
    record = {"trace": trace_reduce.reduce(ProfileData.from_file(path), 1),
              "chips": 1}
    assert BENCH.metric_reader("residual_add_time_share").read(record) is None
