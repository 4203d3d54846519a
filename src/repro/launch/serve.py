"""Production serving launcher: sharded LM engine or compiled-graph tier.

LM generation (default):

  python -m repro.launch.serve --arch qwen2-1.5b --smoke --requests 8

Compiled-QONNX-graph serving (the scheduler/registry stack — submit ->
future lifecycle over the pipelined engine, p50/p99 report at the end):

  python -m repro.launch.serve --graph TFC-w2a2 --requests 64
  python -m repro.launch.serve --graph TFC-w2a2 --requests 64 --no-pipeline

Distributed serving (compiled-graph path):

  --devices N           force N virtual host devices (XLA_FLAGS; must be
                        set before the backend initialises — the flag does
                        this for you)
  --mesh                compile the served plan data-parallel over an
                        elastic_mesh() of all local devices
  --splitmerge          shard each request wave across one single-device
                        engine per local device (SplitMergeFront):
                        deterministic merge order, failed workers
                        re-dispatched

Observability (compiled-graph path):

  --metrics-port 9100   serve the process-wide metrics registry over HTTP
                        (GET /metrics Prometheus text, /metrics.json)
  --trace-jsonl PATH    write one JSON span per line for the full request
                        lifecycle (submit -> queue -> flush -> dispatch ->
                        sync -> complete)
"""
from __future__ import annotations

import argparse
import logging
import os
import time

import jax
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.dist.fault import elastic_mesh
from repro.models import api
from repro.quantize.config import FP32, QuantRecipe
from repro.serve import EngineRegistry, GenerationEngine, ServeScheduler

log = logging.getLogger("repro.launch.serve")


def serve_graph(args) -> None:
    """Serve a zoo graph behind EngineRegistry + ServeScheduler."""
    from repro import obs
    from repro.models import zoo

    server = tracer = sink = None
    if args.metrics_port is not None:
        server = obs.http.start_metrics_server(port=args.metrics_port)
        log.info("metrics on http://0.0.0.0:%d/metrics", server.port)
    if args.trace_jsonl:
        sink = obs.JsonlSink(args.trace_jsonl)
        tracer = obs.Tracer(sink)
        log.info("tracing spans to %s", args.trace_jsonl)

    if args.splitmerge:
        from repro.serve import SplitMergeFront, device_workers
        workers = device_workers(zoo.ZOO[args.graph],
                                 metrics_registry=obs.default_registry(),
                                 max_batch=args.max_batch,
                                 pipeline=not args.no_pipeline,
                                 report_cost=False, tune=args.tune,
                                 tune_cache_dir=args.tune_cache_dir)
        front = SplitMergeFront(workers,
                                metrics_registry=obs.default_registry())
        rng = np.random.default_rng(0)
        eng0 = workers[0].engine
        xs = [rng.standard_normal(eng0.sample_shape, dtype=np.float32)
              for _ in range(args.requests)]
        front(xs[:len(workers)])               # warm every worker's plan
        t0 = time.monotonic()
        wave = front.submit_wave(xs, deadline_ms=args.deadline_ms)
        wave.wait(timeout=300)
        dt = time.monotonic() - t0
        log.info("splitmerge %s: %d requests over %d workers in %.2fs "
                 "(%.1f req/s), %s",
                 args.graph, len(xs), len(workers), dt, len(xs) / dt,
                 front.stats())
        front.close()
        if sink is not None:
            sink.close()
        return

    # engines share the process-wide registry (distinct model labels), so
    # the HTTP endpoint exports the whole fleet from one snapshot
    registry = EngineRegistry(max_batch=args.max_batch,
                              pipeline=not args.no_pipeline,
                              metrics_registry=obs.default_registry(),
                              tracer=tracer, tune=args.tune,
                              tune_cache_dir=args.tune_cache_dir,
                              mesh="auto" if args.mesh else None)
    eng = registry.register(args.graph, zoo.ZOO[args.graph]())
    if args.mesh:
        log.info("mesh-sharded plan spans %d device(s)",
                 eng.plan.n_devices)
    rng = np.random.default_rng(0)
    xs = [rng.standard_normal(eng.sample_shape, dtype=np.float32)
          for _ in range(args.requests)]
    eng(xs[0])                                 # warm the jitted slot shape

    with ServeScheduler(eng, window_ms=args.window_ms,
                        max_queue=max(args.max_batch * 4,
                                      args.requests)) as sched:
        t0 = time.monotonic()       # interval math never uses wall clock
        reqs = [sched.submit(x, deadline_ms=args.deadline_ms)
                for x in xs]
        for r in reqs:
            r.wait(timeout=300)
        dt = time.monotonic() - t0
    stats = sched.stats()
    log.info(
        "graph %s (%s): %d requests in %.2fs (%.1f req/s), "
        "latency p50=%.2fms p99=%.2fms, queued p50=%.2fms, "
        "%d flushes, %d deadline miss(es)",
        args.graph, "pipelined" if not args.no_pipeline else "per-chunk sync",
        len(reqs), dt, len(reqs) / dt,
        stats["latency_p50_ms"], stats["latency_p99_ms"],
        stats["queued_p50_ms"], stats["flushes"], stats["deadline_misses"])
    if sink is not None:
        sink.close()
    if server is not None:
        from repro.obs.report import render
        print(render(obs.default_registry().snapshot(), "serve_"))
        if args.hold:
            log.info("holding metrics endpoint open on port %d (Ctrl-C to "
                     "exit)", server.port)
            try:
                while True:
                    time.sleep(3600)
            except KeyboardInterrupt:
                pass


def serve_lm(args) -> None:
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    recipe = (QuantRecipe.w_a(args.wbits, args.abits,
                              kv_cache_bits=args.kv_bits)
              if args.wbits else FP32)
    cfg = cfg.replace(quant=recipe, shard_activations=True)
    mesh = elastic_mesh()
    log.info("mesh %s, recipe %s", dict(mesh.shape), recipe.tag())

    with mesh:
        params = api.init_params(jax.random.PRNGKey(0), cfg)
        eng = GenerationEngine(params, cfg, max_batch=4)
        rng = np.random.default_rng(0)
        t0 = time.monotonic()
        reqs = [eng.submit(rng.integers(1, cfg.vocab,
                                        size=rng.integers(4, 12)),
                           args.max_new_tokens)
                for _ in range(args.requests)]
        eng.run_pending()
        dt = time.monotonic() - t0
        n_tok = sum(r.result.shape[0] for r in reqs)
        log.info("%d requests, %d tokens in %.2fs (%.1f tok/s)",
                 len(reqs), n_tok, dt, n_tok / dt)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--wbits", type=float, default=8)
    ap.add_argument("--abits", type=float, default=8)
    ap.add_argument("--kv-bits", type=float, default=8)
    # compiled-graph serving tier
    ap.add_argument("--graph", metavar="MODEL",
                    help="serve a zoo graph (e.g. TFC-w2a2) behind the "
                         "scheduler/registry stack instead of the LM engine")
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--window-ms", type=float, default=2.0)
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline passed to submit()")
    ap.add_argument("--no-pipeline", action="store_true",
                    help="per-chunk-sync dispatch (the benchmark baseline)")
    # distributed serving
    ap.add_argument("--devices", type=int, default=None, metavar="N",
                    help="force N virtual host devices (CPU testing; sets "
                         "XLA_FLAGS before the backend initialises)")
    ap.add_argument("--mesh", action="store_true",
                    help="compile the served plan data-parallel over an "
                         "elastic mesh of all local devices")
    ap.add_argument("--splitmerge", action="store_true",
                    help="shard request waves across one engine per local "
                         "device (SplitMergeFront)")
    ap.add_argument("--tune", choices=("off", "cached", "search"),
                    default="cached",
                    help="per-segment kernel tilings: 'cached' reads the "
                         "on-disk tune cache (defaults on miss), 'search' "
                         "measures and persists unseen workloads, 'off' "
                         "keeps module defaults (default: cached)")
    ap.add_argument("--tune-cache-dir", metavar="PATH", default=None,
                    help="tune-cache root (default $REPRO_TUNE_CACHE_DIR "
                         "or <checkout>/.cache/tune)")
    # observability
    ap.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                    help="expose the metrics registry over HTTP: GET "
                         "/metrics (Prometheus text) and /metrics.json")
    ap.add_argument("--trace-jsonl", metavar="PATH",
                    help="write request-lifecycle spans to PATH, one JSON "
                         "object per line")
    ap.add_argument("--hold", action="store_true",
                    help="with --metrics-port: keep the endpoint up after "
                         "the run until Ctrl-C (for scraping)")
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)

    if args.devices:
        # must land in XLA_FLAGS before the first backend query; jax was
        # only *imported* so far, which does not initialise the backend
        flags = os.environ.get("XLA_FLAGS", "")
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{args.devices}").strip()
        if jax.device_count() < args.devices:
            raise SystemExit(
                f"requested --devices {args.devices} but only "
                f"{jax.device_count()} present (backend already "
                f"initialised?)")

    if args.graph:
        serve_graph(args)
    else:
        serve_lm(args)


if __name__ == "__main__":
    main()
