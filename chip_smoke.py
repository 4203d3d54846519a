"""Chip smoke test: the compiled QONNX serving path, end to end on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the multi-chip serving paths only

One chip: CNV-w1a1 (32x32) and MobileNet-w4a4 at its published 224x224
are compiled (``compile_graph`` -> ``CompiledGraphEngine``), registered in
one ``EngineRegistry`` and answer requests through ``ServeScheduler.submit``
at ``max_batch=8`` with a remainder slot.  Every answer is compared with
the interpreted oracle (``core.executor.execute``): exactly for CNV-w1a1,
whose kernels all run the integer-requant path, and within the tie-flip
envelope of ``tests/test_compile.py`` for MobileNet.  The kernels must run
as compiled Mosaic, and each plan's segment census must match the one the
CPU tests pin.

``--chips 4``: CNV-w1a1 through a mesh-sharded plan
(``compile_graph(mesh="auto")``) and through split-merge replicas
(``device_workers``), each compared bit for bit with the single-device
plan, with one worker fault injected into the split-merge front.

Weights and inputs come from ``--seed``.  Lines starting ``smoke:`` are
smoke output (compile seconds, requests answered), not a benchmark.  Any
failed check raises, so the exit code is non-zero; without a TPU the script
exits non-zero before any phase.  The last line on success is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

from repro.core import execute, transforms  # noqa: E402
from repro.core.compile import compile_graph  # noqa: E402
from repro.kernels.ops import default_interpret, resolve_interpret  # noqa: E402
from repro.models import zoo  # noqa: E402
from repro.serve import (CompiledGraphEngine, EngineRegistry,  # noqa: E402
                         ServeScheduler, SplitMergeFront, device_workers)
from repro.tune.cache import (JAX_CACHE_DIR,  # noqa: E402
                              configure_jax_persistent_cache)

MAX_BATCH = 8
N_REQUESTS = 20          # two full slots and a remainder slot of 4


def say(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def assert_within_envelope(ref, out, act_step=0.5, atol=1e-4,
                           mean_steps=1.5):
    """The exact-or-tie-flip envelope of tests/test_compile.py
    (``assert_zoo_parity``) for dyadic-scale graphs with float layers."""
    diff = np.abs(ref - out)
    if diff.max() <= atol:
        return
    assert diff.max() <= 3 * act_step + atol, \
        f"diff {diff.max():.4f} exceeds the tie-flip envelope"
    assert np.mean(diff) <= mean_steps * act_step, \
        f"mean diff {np.mean(diff):.4f} is not a measure-zero tie effect"


def oracle(graph, xs):
    g = transforms.cleanup(graph)
    return np.asarray(execute(g, {g.input_names[0]: xs})[g.output_names[0]])


def mosaic_kernels(plan, batch: int) -> int:
    """tpu_custom_call sites (compiled Pallas kernels) in the plan body."""
    import jax
    shape = (batch,) + tuple(plan.graph.inputs[0].shape[1:])
    spec = {plan.graph.input_names[0]: jax.ShapeDtypeStruct(shape,
                                                            np.float32)}
    return plan._jitted.lower(plan.consts, spec).as_text().count(
        "tpu_custom_call")


def cache_entries() -> int:
    import jax
    d = jax.config.jax_compilation_cache_dir
    return len(os.listdir(d)) if d and os.path.isdir(d) else 0


def phase_one_chip(seed: int) -> None:
    """Two models behind one registry, served and checked on one chip."""
    rng = np.random.default_rng(seed)
    registry = EngineRegistry(max_batch=MAX_BATCH, report_cost=False)
    graphs = {"CNV-w1a1": zoo.build_cnv(1, 1, seed=seed),
              "MobileNet-w4a4-224": zoo.build_mobilenet(4, 4, seed=seed,
                                                        img=224)}
    engines, xs = {}, {}
    for name, graph in graphs.items():
        t0 = time.perf_counter()
        eng = registry.register(name, graph)
        t1 = time.perf_counter()
        xs[name] = rng.standard_normal((N_REQUESTS,) + eng.sample_shape,
                                       dtype=np.float32)
        eng(xs[name][:1])                   # compiles the slot shape
        t2 = time.perf_counter()
        engines[name] = eng
        n_mosaic = mosaic_kernels(eng.plan, MAX_BATCH)
        say(f"compile {name}: compile_graph {t1 - t0:.2f} s, first call "
            f"{t2 - t1:.2f} s,"
            f" {len(eng.plan.segments)} segments, {n_mosaic} Mosaic kernel "
            f"calls")
        assert n_mosaic > 0, f"{name}: no compiled Pallas kernel in the plan"

    # census: what the CPU tests pin (test_fusion, test_grouped_conv)
    cnv = engines["CNV-w1a1"].plan
    interp = cnv.interp_op_counts()
    assert all(interp.get(op, 0) == 0 for op in ("Conv", "MaxPool", "Add")), \
        f"CNV-w1a1 interprets {interp}"
    rq = cnv.requant_stats()
    assert rq["coverage"] == 1.0, f"CNV-w1a1 requant coverage {rq}"
    mn = engines["MobileNet-w4a4-224"].plan
    n_convs = sum(1 for n in mn.graph.nodes if n.op_type == "Conv")
    fused_convs = sum(v for k, v in mn.fused_counts.items()
                      if k.startswith("quant_conv"))
    n_dw = mn.fused_counts.get("quant_conv_dw", 0)
    assert (n_convs, fused_convs, n_dw) == (27, 27, 13), \
        f"MobileNet census: {n_convs} convs, {fused_convs} fused, {n_dw} dw"
    assert mn.interp_op_counts().get("Conv", 0) == 0
    say(f"census CNV-w1a1: interpreted {interp}, integer-requant coverage "
        f"{rq['coverage']}; MobileNet-w4a4-224: {fused_convs}/{n_convs} "
        f"convs fused, {n_dw} depthwise")

    # serve both models at once, one scheduler each, interleaved submits
    scheds = {name: ServeScheduler(eng, window_ms=2.0, max_queue=64)
              for name, eng in engines.items()}
    reqs = {name: [] for name in engines}
    t0 = time.perf_counter()
    with scheds["CNV-w1a1"], scheds["MobileNet-w4a4-224"]:
        for i in range(N_REQUESTS):
            for name, sched in scheds.items():
                reqs[name].append(sched.submit(xs[name][i]))
        outs = {name: np.stack([r.wait(timeout=600) for r in rs])
                for name, rs in reqs.items()}
    dt = time.perf_counter() - t0
    for name, out in outs.items():
        say(f"served {name}: {len(out)} requests answered "
            f"({engines[name].latency_stats()['flushes']} flushes) in "
            f"{dt:.2f} s wall for both models")

    for name, out in outs.items():
        ref = oracle(graphs[name], xs[name])
        diff = float(np.max(np.abs(ref - out)))
        if name == "CNV-w1a1":
            assert ref.dtype == out.dtype and np.array_equal(ref, out), \
                f"CNV-w1a1 differs from the oracle (max |diff| {diff})"
        else:
            assert_within_envelope(ref, out)
        say(f"oracle {name}: max |diff| {diff} over {out.size} values "
            f"({'exact' if diff == 0 else 'within envelope'})")


def phase_four_chips(seed: int, chips: int) -> None:
    """Mesh-sharded plan and split-merge replicas vs the one-device plan."""
    rng = np.random.default_rng(seed)
    base = compile_graph(zoo.build_cnv(1, 1, seed=seed))
    t0 = time.perf_counter()
    mesh_plan = compile_graph(zoo.build_cnv(1, 1, seed=seed), mesh="auto")
    assert mesh_plan.n_devices == chips, mesh_plan.placement()
    in_name, out_name = base.graph.input_names[0], base.graph.output_names[0]
    for batch in (16, 13):                  # divides by 4; remainder path
        x = rng.standard_normal((batch, 3, 32, 32), dtype=np.float32)
        ref = np.asarray(base({in_name: x})[out_name])
        out = np.asarray(mesh_plan({in_name: x})[out_name])
        assert out.shape == ref.shape and np.array_equal(ref, out), \
            f"mesh plan differs from the single-device plan at batch {batch}"
        say(f"mesh plan over {mesh_plan.n_devices} chips, batch {batch}: "
            f"bit-identical to the single-device plan")
    say(f"mesh phase took {time.perf_counter() - t0:.2f} s")

    t0 = time.perf_counter()
    single = CompiledGraphEngine(zoo.build_cnv(1, 1, seed=seed),
                                 max_batch=MAX_BATCH, report_cost=False)
    workers = device_workers(lambda: zoo.build_cnv(1, 1, seed=seed),
                             max_batch=MAX_BATCH, report_cost=False)
    assert len(workers) == chips
    xs = list(rng.standard_normal((37, 3, 32, 32), dtype=np.float32))
    ref = single(np.stack(xs))
    with SplitMergeFront(workers) as front:
        out = front(xs, timeout=600)
        assert np.array_equal(out, ref), "split-merge differs"
        workers[2].inject_fault()
        out2 = front(xs, timeout=600)
        assert len(out2) == len(xs) and np.array_equal(out2, ref), \
            "split-merge lost or changed requests under a worker fault"
        stats = front.stats()
    assert stats["failed"] == ["dev2"] and stats["redispatched_shards"] == 1, \
        stats
    say(f"split-merge over {len(workers)} workers: 2 waves of {len(xs)} "
        f"requests bit-identical to the single-device plan; fault on dev2 "
        f"re-dispatched, 0 requests lost ({stats}); "
        f"{time.perf_counter() - t0:.2f} s")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip serving paths")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and inputs")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices)}
    say(f"device {device}")
    if d0.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {d0.platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              f"device(s)", file=sys.stderr)
        return 2
    assert default_interpret() is False and resolve_interpret(None) is False, \
        "Pallas kernels would run interpreted on this backend"
    say("Pallas kernels resolve to compiled Mosaic (interpret=False)")

    cache_dir = configure_jax_persistent_cache()   # what compile_graph does
    say(f"compile cache {cache_dir} (fixed default {JAX_CACHE_DIR}, unless "
        f"$JAX_COMPILATION_CACHE_DIR is set): {cache_entries()} entries "
        f"before the run")
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_four_chips(args.seed, args.chips)
    else:
        phase_one_chip(args.seed)
    say(f"all phases passed in {time.perf_counter() - t0:.2f} s; compile "
        f"cache now holds {cache_entries()} entries")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
