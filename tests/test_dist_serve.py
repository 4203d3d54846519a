"""Multi-virtual-device suite: mesh-sharded plans + split-merge serving.

The interesting tests need more than one device, so the module is run
twice: on a normal 1-CPU host every inner test skips and the single
``test_multidevice_suite_in_subprocess`` wrapper re-runs this file in a
subprocess with ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(the flag must be set before the JAX backend initialises, hence the
subprocess).  Inside that run ``REPRO_MULTIDEV_INNER=1`` skips the wrapper
so it cannot recurse.

What must hold on the 8-device mesh (the ISSUE-10 acceptance bar):

  * a mesh-sharded ``CompiledPlan`` is **bit-identical** to the
    single-device plan on the fully integer-requantized zoo models
    (TFC-w1a1 / CNV-w1a1 — their dyadic requant pipeline is exact, so
    equality is ``==``, not allclose);
  * non-divisible batches (the pad-and-slice remainder path) stay exact;
  * the split-merge front spreads a wave over all 8 per-device workers,
    merges in submission order, and an injected mid-shard worker fault
    loses zero requests.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

multidev = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs 8 (virtual) devices; the subprocess wrapper provides them")


# ------------------------------------------------------------ the wrapper

@pytest.mark.skipif(os.environ.get("REPRO_MULTIDEV_INNER") == "1",
                    reason="already inside the multi-device subprocess")
@pytest.mark.skipif(jax.device_count() >= 8,
                    reason="host already has >=8 devices; inner tests run "
                           "directly")
def test_multidevice_suite_in_subprocess():
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=8").strip()
    env["REPRO_MULTIDEV_INNER"] = "1"
    env["JAX_PLATFORMS"] = "cpu"       # a child must never contend for a chip
    env.setdefault("PYTHONPATH", "src")
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q", __file__],
        env=env, capture_output=True, text=True, timeout=1800)
    assert proc.returncode == 0, (
        f"multi-device suite failed:\n{proc.stdout}\n{proc.stderr}")
    assert "passed" in proc.stdout


# --------------------------------------------------- mesh-sharded parity

def _plan(graph, **kw):
    from repro.core.compile import compile_graph
    return compile_graph(graph, **kw)


def _inputs(graph, batch, seed=0):
    rng = np.random.RandomState(seed)
    shape = (batch,) + tuple(graph.inputs[0].shape[1:])
    return {graph.input_names[0]: rng.randn(*shape).astype(np.float32)}


@multidev
@pytest.mark.parametrize("model", ["TFC-w1a1", "CNV-w1a1"])
def test_mesh_sharded_plan_bit_identical(model):
    from repro.models import zoo
    g = zoo.ZOO[model]()
    base = _plan(g)
    sharded = _plan(zoo.ZOO[model](), mesh="auto")
    assert sharded.n_devices == 8
    assert sharded.placement()["kind"] == "mesh"
    x = _inputs(g, 16)
    ref = base(x)
    out = sharded(x)
    for k in ref:
        a, b = np.asarray(ref[k]), np.asarray(out[k])
        assert a.dtype == b.dtype and np.array_equal(a, b), \
            f"{model}/{k}: sharded plan diverged"


@multidev
def test_mesh_output_actually_spans_all_devices():
    from repro.models import zoo
    sharded = _plan(zoo.ZOO["TFC-w1a1"](), mesh="auto")
    out = sharded(_inputs(sharded.graph, 64))
    y = out[sharded.graph.output_names[0]]
    devs = {d for shard in y.addressable_shards for d in [shard.device]}
    assert len(devs) == 8


@multidev
@pytest.mark.parametrize("batch", [1, 5, 13])
def test_mesh_remainder_batches_exact(batch):
    """Batches not divisible by the data-parallel degree go through the
    pad-and-slice path and must stay bit-exact with the full rows."""
    from repro.models import zoo
    g = zoo.ZOO["TFC-w1a1"]()
    base, sharded = _plan(g), _plan(zoo.ZOO["TFC-w1a1"](), mesh="auto")
    x = _inputs(g, batch, seed=batch)
    ref, out = base(x), sharded(x)
    for k in ref:
        a, b = np.asarray(ref[k]), np.asarray(out[k])
        assert a.shape == b.shape and np.array_equal(a, b)


@multidev
def test_device_pinned_plan_matches():
    from repro.models import zoo
    g = zoo.ZOO["TFC-w1a1"]()
    base = _plan(g)
    pinned = _plan(zoo.ZOO["TFC-w1a1"](), device=jax.devices()[3])
    assert pinned.placement() == {"kind": "device", "devices": 1,
                                  "device": str(jax.devices()[3])}
    x = _inputs(g, 8)
    for k, v in base(x).items():
        assert np.array_equal(np.asarray(v), np.asarray(pinned(x)[k]))


@multidev
def test_elastic_mesh_pure_data_parallel():
    from repro.dist.fault import elastic_mesh
    m = elastic_mesh(prefer_model=1)
    assert dict(m.shape) == {"data": 8, "model": 1}


# ------------------------------------------------- split-merge over devices

@multidev
def test_splitmerge_wave_spans_all_devices_and_survives_fault():
    from repro import obs
    from repro.models import zoo
    from repro.serve import CompiledGraphEngine, SplitMergeFront, \
        device_workers

    reg = obs.MetricsRegistry()
    workers = device_workers(zoo.ZOO["TFC-w1a1"], metrics_registry=reg,
                             report_cost=False, max_batch=8)
    assert len(workers) == 8
    oracle_eng = CompiledGraphEngine(zoo.ZOO["TFC-w1a1"](),
                                     report_cost=False, max_batch=8)
    rng = np.random.RandomState(0)
    xs = [rng.randn(784).astype(np.float32) for _ in range(37)]
    oracle = oracle_eng(np.stack(xs))

    with SplitMergeFront(workers, metrics_registry=reg) as front:
        out = front(xs)
        assert np.array_equal(out, oracle)        # deterministic merge
        disp = {s["labels"]["worker"]: s["value"]
                for s in reg.snapshot()
                ["splitmerge_dispatch_total"]["series"]}
        assert len(disp) == 8 and all(v >= 1 for v in disp.values())

        # chaos: one worker dies mid-shard; the wave still completes with
        # every request answered correctly (re-dispatched, not lost)
        workers[5].inject_fault()
        out2 = front(xs)
        assert np.array_equal(out2, oracle)
        s = front.stats()
        assert s["failed"] == ["dev5"]
        assert s["redispatched_shards"] == 1
