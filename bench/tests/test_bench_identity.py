"""The accepted cells build and read what they did before the layer list
could branch.

``data/identity.json`` and ``data/identity_logits.npz`` were recorded
from the chain-only walkers (``bench/graph.py``, ``bench/layers.py`` and
``bench/reference/forward.py`` before ``bench/ops/``) with ``describe``
and ``logits`` below, for both configurations at seeds 0-2:

* the QONNX graph node for node (op type, name, inputs, outputs,
  attributes, domain) and every initializer (name, shape, dtype and a
  SHA-256 of its bytes);
* ``layers.count`` (MACs and weights);
* the reference's logits on two images, bit for bit.

Regenerate only on purpose: ``python -m bench.tests.test_bench_identity``.
"""
import hashlib
import json
import os

import numpy as np
import pytest

from bench import inputs, layers as L, spec
from bench.graph import build_graph
from bench.reference.forward import Reference

DATA = os.path.join(os.path.dirname(__file__), "data")
GOLDEN = os.path.join(DATA, "identity.json")
LOGITS = os.path.join(DATA, "identity_logits.npz")
CONFIGS = ("cnv-w1a1", "mobilenet-w4a4-224")
SEEDS = (0, 1, 2)
N_IMAGES = 2
BENCH = spec.Bench()


def _built(name, seed):
    cfg = BENCH.config(name)
    layers = BENCH.layers(cfg)
    codes = L.draw_weights(layers, seed)
    return cfg, layers, codes


def describe(name: str, seed: int) -> dict:
    """The graph and counts of one configuration at one seed, as JSON."""
    cfg, layers, codes = _built(name, seed)
    g = build_graph(cfg["name"], layers, codes, cfg["input_shape"])
    return {"nodes": [n.to_json() for n in g.nodes],
            "inputs": [t.to_json() for t in g.inputs],
            "outputs": [t.to_json() for t in g.outputs],
            "initializers": [[k, list(v.shape), str(v.dtype),
                              hashlib.sha256(v.tobytes()).hexdigest()]
                             for k, v in g.initializers.items()],
            "count": L.count(layers, cfg["input_shape"])}


def logits(name: str, seed: int) -> np.ndarray:
    cfg, layers, codes = _built(name, seed)
    x = inputs.images(seed, 1, N_IMAGES, cfg["input_shape"])
    return Reference(layers, codes, block=N_IMAGES)(x)


@pytest.fixture(scope="module")
def golden():
    with open(GOLDEN) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", CONFIGS)
def test_graph_and_counts_are_the_chains(golden, name, seed):
    want = golden[name][str(seed)]
    got = json.loads(json.dumps(describe(name, seed)))
    assert len(got["nodes"]) == len(want["nodes"])
    for i, (g, w) in enumerate(zip(got["nodes"], want["nodes"])):
        assert g == w, f"node {i}"
    for key in ("inputs", "outputs", "initializers", "count"):
        assert got[key] == want[key], key


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", CONFIGS)
def test_reference_logits_are_the_chains(name, seed):
    with np.load(LOGITS) as f:
        want = f[f"{name}/{seed}"]
    got = logits(name, seed)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert np.array_equal(got, want)


def _record() -> None:
    doc = {name: {str(s): describe(name, s) for s in SEEDS}
           for name in CONFIGS}
    with open(GOLDEN, "w") as f:
        json.dump(doc, f, separators=(",", ":"))
    np.savez_compressed(LOGITS, **{f"{name}/{s}": logits(name, s)
                                   for name in CONFIGS for s in SEEDS})


if __name__ == "__main__":
    _record()
