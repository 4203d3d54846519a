"""The offline mixes offer the same work on every seed."""
import numpy as np
import pytest

from bench import inputs, spec

BENCH = spec.Bench()
OFFLINE = sorted({w["traffic"] for w in BENCH.doc["workloads"]})


@pytest.mark.parametrize("seed", [5, 2**31 + 5])
def test_inputs_are_seeded_and_in_the_quantizer_range(seed):
    a = inputs.images(seed, 1, 64, (3, 4, 4))
    assert isinstance(a, np.ndarray) and a.dtype == np.float32
    assert np.array_equal(a, inputs.images(seed, 1, 64, (3, 4, 4)))
    assert a.min() >= -1.0 and a.max() < 1.0 and a.std() > 0.5
    for other in (inputs.images(seed, 2, 64, (3, 4, 4)),      # stream
                  inputs.images(seed + 2**32, 1, 64, (3, 4, 4))):  # high bits
        assert not np.array_equal(a, other)


@pytest.mark.parametrize("name", OFFLINE)
def test_offline_mix_fills_whole_slots(name):
    """Every call is whole slots, so the window runs one slot shape with
    no padding, and the pool repeats the same sizes for every seed; a mesh
    mix's slot splits evenly over a four-chip host."""
    doc = BENCH.traffic(name)
    assert doc["driver"] == "offline"
    assert set(doc) - {"mesh"} == {"driver", "why", "max_batch",
                                   "call_batch", "pool_calls"}
    assert doc.get("mesh", "auto") == "auto"
    if "mesh" in doc:
        assert doc["max_batch"] % 4 == 0
    assert doc["call_batch"] % doc["max_batch"] == 0
    assert doc["pool_calls"] >= 2
