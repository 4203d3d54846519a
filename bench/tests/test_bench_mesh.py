"""The offline driver's mesh plan, on four virtual CPU devices.

``cnv-w1a1.offline-mesh4``'s mix (``"mesh": "auto"``) at a slot of 8:
the driver's plan spans 4 devices and answers what the single-device
engine answers, row for row; ``harness.compiled_hlo`` lowers the program
the mesh plan runs, with each device's 2 rows; a whole run of the cell is
correct; and a run whose plan drops every chip's rows but the first
(the gather from the other chips left out) is not.

The devices must exist before JAX starts, so the test runs this file as
a script in a child process with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
"""
import contextlib
import json
import os
import subprocess
import sys
import types

CELL = "cnv-w1a1.offline-mesh4"
SEED = 2**31 + 13
SLOT, CALL = 8, 16
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_mesh_driver_matches_one_device_and_runs_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"), ROOT]))
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4").strip()
    proc = subprocess.run([sys.executable, os.path.abspath(__file__)],
                          cwd=ROOT, env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-4000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got == {"devices": 4, "plan_devices": 4, "equal": True,
                   "per_device_rows": True, "sound_correct": True,
                   "dropped_shards_correct": False}


def _small(traffic: dict) -> dict:
    return dict(traffic, max_batch=SLOT, call_batch=CALL)


def _harness_run(harness, spec, monkeypatched):
    orig = spec.Bench.traffic
    spec.Bench.traffic = lambda self, name: _small(orig(self, name))
    try:
        with monkeypatched:
            args = types.SimpleNamespace(workload=CELL, seed=SEED,
                                         seconds=0.5, trace=0)
            return harness.run(args, require_tpu=False)
    finally:
        spec.Bench.traffic = orig


@contextlib.contextmanager
def _first_shard_only():
    """The mesh plan answers its first device's rows and zeros for the
    rest, as if the other chips' results were never gathered."""
    from repro.core.compile import CompiledPlan
    orig = CompiledPlan._call_sharded

    def dropped(self, inputs):
        out = orig(self, inputs)
        name = self.graph.output_names[0]
        y = out[name]
        keep = y.shape[0] // self.n_devices
        return dict(out, **{name: y.at[keep:].set(0.0)})
    CompiledPlan._call_sharded = dropped
    try:
        yield
    finally:
        CompiledPlan._call_sharded = orig


def main() -> None:
    import jax
    import numpy as np

    from bench import harness, layers as L, spec
    from bench.graph import build_graph
    from repro.serve import CompiledGraphEngine

    bench = spec.Bench()
    cell = bench.workload(CELL)
    cfg = bench.config(cell["config"])
    traffic = _small(bench.traffic(cell["traffic"]))
    layers = bench.layers(cfg)
    graph = build_graph(cfg["name"], layers, L.draw_weights(layers, SEED),
                        cfg["input_shape"])
    drv = bench.driver(traffic).Driver(graph, traffic, SEED,
                                       cfg["input_shape"])
    drv.warm()
    drv.run(0.2, lambda name: contextlib.nullcontext())
    xs, index, outs, _ = drv.answers()
    one = CompiledGraphEngine(graph, max_batch=SLOT, report_cost=False)
    hlo = harness.compiled_hlo(drv.plan, SLOT, cfg["input_shape"])
    rows = SLOT // drv.plan.n_devices
    entry = [ln for ln in hlo.splitlines() if ln.startswith("ENTRY")][0]
    result = {"devices": jax.device_count(),
              "plan_devices": drv.plan.n_devices,
              "equal": bool(np.array_equal(outs, one(xs)[index])),
              "per_device_rows": f"f32[{rows},3,32,32]" in entry and
              f"f32[{SLOT},3,32,32]" not in entry}
    drv.release()
    result["sound_correct"] = _harness_run(
        harness, spec, contextlib.nullcontext())["correct"]
    result["dropped_shards_correct"] = _harness_run(
        harness, spec, _first_shard_only())["correct"]
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    sys.path[0:0] = [ROOT, os.path.join(ROOT, "src")]
    main()
