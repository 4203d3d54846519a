"""One run of one cell: set up, measure a window, check the answers.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Steps, in order:

1. Refuse to run without a TPU, or with fewer chips than the cell asks
   for: a non-zero exit before any phase, and no result.
2. Set-up (``setup_s``, from process start): the configuration's weights
   drawn from the seed, its QONNX graph, the engine the traffic names
   (``compile_graph`` inside), the input pool, and a warm-up of every
   shape the window uses.  Its parts are printed on an earlier line
   (``setup_parts``).
3. The window: the traffic's driver for ``--seconds``.  With
   ``--trace 1`` the JAX profiler records it (at most ``TRACE_WINDOW_S``
   of it, which keeps the trace and its reading short), the engine's own
   spans are on, and the per-layer metrics are read from both; otherwise
   the end-to-end metrics are taken with every tracer off.
4. ``memory_peak_bytes`` is read, the program's state is dropped, and
   the plain reference runs over every distinct input of the window; each
   answer is compared with it (``bench/check.py``).
5. The last stdout line is the result object; the compared numbers with
   their limits are the last stderr lines too.
"""
from __future__ import annotations

import contextlib
import gc
import json
import math
import os
import shutil
import sys
import time

TRACE_DIR = os.path.join(".cache", "bench", "trace")
TRACE_WINDOW_S = 10.0     # a traced run profiles at most this long a window


def process_age_s() -> float:
    """Seconds since this process started (Linux ``/proc``)."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - int(fields[19]) / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


def say(msg: str) -> None:
    print(f"bench: {msg}", flush=True)


class CompileCounter:
    """Counts backend compiles while ``active`` (none belong in a window)."""

    def __init__(self):
        import jax.monitoring
        self.active = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if self.active and "backend_compile" in event:
            self.n += 1


def check_devices(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devs)}
    say(f"platform={d0.platform} device_kind={d0.device_kind!r} "
        f"device_count={len(devs)}")
    if require_tpu and d0.platform != "tpu":
        raise SystemExit(f"bench: needs a TPU; JAX found {d0.platform}")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips; JAX found "
                         f"{len(devs)}")
    return device


def memory_peak_bytes() -> int:
    """Peak on the fullest chip: the allocator's buffers at their peak
    plus the region the TPU runtime reserves for the programs' temporaries
    (``peak_bytes_reserved``), which ``peak_bytes_in_use`` leaves out."""
    import jax
    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)) +
                   int(stats.get("peak_bytes_reserved", 0)))
    return peak


def compiled_hlo(plan, slot_rows: int, sample_shape) -> str:
    """Text of the per-device program the window ran on a slot of
    ``slot_rows``, compiled again after the window (the persistent cache
    usually has it), for the Pallas calls' shapes.  A mesh plan's program
    is its ``shard_map`` over the slot split as its calls split it, so
    the text holds each device's rows.  The plan offers no public handle
    on its executable, so this lowers its jitted body."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding
    from repro.dist import sharding as dsh
    x = jax.ShapeDtypeStruct((slot_rows,) + tuple(sample_shape), np.float32)
    fn = plan._jitted
    if plan.n_devices > 1:            # as ``CompiledPlan._call_sharded``
        fn = plan._jitted_spmd
        x = jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=NamedSharding(
            plan.mesh, dsh.batch_pspecs(x, plan.mesh)))
    return fn.lower(plan.consts,
                    {plan.graph.input_names[0]: x}).compile().as_text()


def run(args, *, require_tpu: bool = True, t_start: float | None = None,
        root: str | None = None) -> dict:
    """The whole run; returns the result object (also printed)."""
    from bench import check, layers as L, spec
    from bench.graph import build_graph

    t_start = time.monotonic() if t_start is None else t_start
    age0 = process_age_s()
    clock = lambda: age0 + time.monotonic() - t_start  # noqa: E731
    bench = spec.Bench(root or spec.ROOT)
    cell = bench.workload(args.workload)
    cfg = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    parts = {"python_s": clock()}     # interpreter and imports
    device = check_devices(int(cell["chips"]), require_tpu)
    peaks = bench.peaks(device["kind"]) if require_tpu else None
    parts["devices_s"] = clock() - parts["python_s"]   # JAX's backend

    import jax
    from repro.obs import ListSink, Tracer

    layers = bench.layers(cfg)
    codes = L.draw_weights(layers, args.seed)
    graph = build_graph(cfg["name"], layers, codes, cfg["input_shape"])
    parts["graph_s"] = clock() - sum(parts.values())
    sink = ListSink() if args.trace else None
    tracer = Tracer(sink) if args.trace else None
    drv = bench.driver(traffic).Driver(graph, traffic, args.seed,
                                      cfg["input_shape"], tracer=tracer)
    drv.warm()
    setup_s = clock()
    parts.update(getattr(drv, "setup_parts", {}))
    say(f"setup_s={setup_s:.3f} cell={cell['name']} seed={args.seed} "
        f"plan_devices={drv.plan.n_devices} segments={drv.plan.fused_counts}")
    say("setup_parts=" + json.dumps({k: round(v, 3)
                                     for k, v in parts.items()}))

    compiles = CompileCounter()
    traces_before = drv.plan.trace_count
    annotate = (jax.profiler.TraceAnnotation if args.trace
                else lambda name: contextlib.nullcontext())
    trace_dir = os.path.join(bench.root, TRACE_DIR, cell["name"])
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    seconds = float(args.seconds)
    if args.trace:
        seconds = min(seconds, TRACE_WINDOW_S)
    compiles.active = True
    with annotate("window"):
        rec = drv.run(seconds, annotate)
    compiles.active = False
    if args.trace:
        t0 = time.monotonic()
        jax.profiler.stop_trace()
        say(f"profiler stopped in {time.monotonic() - t0:.2f} s")
    say(f"plan.trace_count before={traces_before} after="
        f"{drv.plan.trace_count}; backend compiles in window={compiles.n}")
    for k, v in rec.get("host_lines", {}).items():
        say(f"{k}={v}")
    mem = memory_peak_bytes()
    if args.trace:
        t0 = time.monotonic()
        eng = drv.engine
        hlo = compiled_hlo(eng.plan, eng.max_batch, eng.sample_shape)
        say(f"compiled HLO read in {time.monotonic() - t0:.2f} s")

    # the program's answers, then its state freed before the reference
    xs, index, outs, missing = drv.answers()
    drv.release()
    del drv
    gc.collect()
    from bench.reference.forward import Reference
    t_ref = time.monotonic()
    ref = Reference(layers, codes)(xs)
    gap = check.logit_gap(outs, ref[index])
    say(f"reference over {len(xs)} distinct inputs, {len(index)} answers "
        f"compared, in {time.monotonic() - t_ref:.2f} s")
    correct, checks = check.judge(cfg["check"]["logit_gap_limit"], gap,
                                  missing, len(index))

    metrics = {}
    result = {"correct": bool(correct), "attempted": rec["attempted"],
              "failed": rec["failed"], "metrics": metrics,
              "device": dict(device, memory_peak_bytes=mem)}
    if not args.trace:
        e2e = dict(rec["end_to_end"], setup_s=setup_s)
        for m in bench.metrics_for(cell["name"], "end_to_end"):
            if m["name"] not in e2e:
                raise KeyError(f"{cell['name']}: the driver measured no "
                               f"{m['name']}")
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        from bench import trace_reduce
        t0 = time.monotonic()
        reduced = trace_reduce.reduce_dir(trace_dir, int(cell["chips"]), hlo)
        say(f"trace reduced in {time.monotonic() - t0:.2f} s: "
            f"{len(reduced['pallas_calls'])} Pallas calls")
        record = dict(rec, chips=int(cell["chips"]), peaks=peaks,
                      macs_per_image=L.count(layers, cfg["input_shape"])
                      ["macs"], spans=list(sink), trace=reduced)
        for m in bench.metrics_for(cell["name"], "per_layer"):
            value = bench.metric_reader(m["name"]).read(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["device"].update(busy_s=reduced["busy_s"],
                                window_s=reduced["window_s"])
        result["breakdown"] = reduced["breakdown"]
    result["checks"] = {name: {k: v if math.isfinite(v) else str(v)
                               for k, v in c.items()}
                        for name, c in checks.items()}
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return result
