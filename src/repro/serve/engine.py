"""Compiled-QONNX-graph serving engine: slot-batched, pipelined dispatch.

``CompiledGraphEngine`` serves QonnxGraph inference on the *compiled* tier
(core/compile.py): the graph is partitioned onto the quantized Pallas
kernels once at load, requests are batched into fixed-size slots (padding
to ``max_batch`` keeps a single jitted shape), and per-node Python dispatch
never appears on the request path.

Dispatch is **pipelined**: a multi-slot flush (or a multi-chunk
``__call__``) enqueues every slot-shaped plan call device-side before any
host sync — JAX's async dispatch lets chunk *k+1*'s Python dispatch overlap
chunk *k*'s compute — and forces results once, in a single trailing
``block_until_ready`` pass.  ``pipeline=False`` restores the old
per-chunk ``np.asarray`` stall (the benchmark baseline;
benchmarks/bench_serve.py measures the gap).  On accelerator backends the
padded slot buffers are donated to XLA (``donate="auto"``) so each chunk's
input memory is reusable for its outputs.

Thread safety: ``submit`` / ``run_pending`` / ``reload`` / ``__call__``
coordinate through one engine lock, so a background flush loop
(``serve.scheduler.ServeScheduler``) and hot model swaps
(``serve.registry.EngineRegistry``) can race callers safely.  ``reload``
compiles the new plan *outside* the lock — in-flight traffic keeps being
answered by the old plan during compilation — then atomically flushes the
still-queued old-model requests through the old plan and swaps.
"""
from __future__ import annotations

import contextlib
import logging
import threading
import time
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import MetricsRegistry, nearest_rank

log = logging.getLogger("repro.serve")

# slot occupancy is a fraction of max_batch — linear buckets, not the
# default exponential latency layout
_OCCUPANCY_BUCKETS = tuple(i / 8 for i in range(1, 9))

# what a step's ``with`` enters when tracing is off: one shared, stateless
# context, so an untraced call allocates nothing for its spans
_NO_SPAN = contextlib.nullcontext()


def percentile_ms(values, pct: float) -> float:
    """Nearest-rank percentile over a latency sample (ms); nan when empty.

    Kept as the historical public name; the implementation is the shared
    ``repro.obs.nearest_rank`` every telemetry path now uses.
    """
    return nearest_rank(values, pct)


@dataclass
class GraphRequest:
    """One in-flight inference request — a lightweight future.

    ``submit`` returns it immediately; a flush (caller-driven
    ``run_pending`` or the ``ServeScheduler`` loop) fills ``result`` and
    fires the completion event.  ``wait()`` blocks for the result
    (re-raising a flush-side error); ``latency_ms`` / ``queued_ms`` are the
    per-request telemetry the engine aggregates into p50/p99 at flush.

    **Clocks:** every interval/deadline timestamp (``submitted``,
    ``started``, ``completed``, ``deadline``) is ``time.monotonic()`` — an
    NTP step must never fire every deadline at once or make a latency
    negative.  ``submitted_at`` is the one wall-clock stamp, kept purely
    for human-readable logs/exports; no arithmetic ever touches it.
    """
    x: jnp.ndarray                       # one sample, graph input minus batch
    submitted: float = field(default_factory=time.monotonic)
    submitted_at: float = field(default_factory=time.time)  # wall, logs only
    deadline: Optional[float] = None     # absolute monotonic time it's due
    started: Optional[float] = None      # when the slot was dispatched
    completed: Optional[float] = None
    result: Optional[np.ndarray] = None
    error: Optional[BaseException] = None
    trace_id: Optional[str] = None       # set at submit when tracing is on:
    queue_depth: Optional[int] = None    # the request's trace context + the
                                         # queue depth it joined behind
    _event: threading.Event = field(default_factory=threading.Event,
                                    repr=False, compare=False)

    def done(self) -> bool:
        return self._event.is_set()

    def wait(self, timeout: Optional[float] = None) -> np.ndarray:
        """Block until the request completes; returns the result row."""
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"request not completed within {timeout}s "
                f"(is a scheduler running / was run_pending called?)")
        if self.error is not None:
            raise self.error
        return self.result

    @property
    def latency_ms(self) -> Optional[float]:
        """submit -> result, ms; None while in flight."""
        if self.completed is None:
            return None
        return (self.completed - self.submitted) * 1e3

    @property
    def queued_ms(self) -> Optional[float]:
        """submit -> slot dispatch, ms; None while queued."""
        if self.started is None:
            return None
        return (self.started - self.submitted) * 1e3

    def _finish(self, result=None, error: Optional[BaseException] = None):
        self.completed = time.monotonic()
        self.result = result
        self.error = error
        self.x = None          # drop the input: a held future must not pin
        self._event.set()      # the device buffer past completion


class CompiledGraphEngine:
    """Slot-batched, pipelined inference over a compiled QonnxGraph.

    The graph is compiled once (fused Quant segments -> Pallas kernels,
    interpreted fallback for the rest); each flush stacks up to
    ``max_batch`` requests along the leading dim, pads to exactly
    ``max_batch`` so the jitted plan sees one static shape, dispatches
    every slot device-side, syncs once, and scatters the rows back to the
    requests.
    """

    def __init__(self, graph, *, max_batch: int = 8, use_kernels: bool = True,
                 use_int4: bool = True, interpret: Optional[bool] = None,
                 report_cost: bool = True, pipeline: bool = True,
                 donate="auto", telemetry_window: int = 2048,
                 metrics_registry: Optional[MetricsRegistry] = None,
                 metrics_labels: Optional[dict] = None,
                 tracer=None, observability: bool = True,
                 tune: str = "off", tune_cache_dir: Optional[str] = None,
                 mesh=None, device=None):
        self.max_batch = max_batch
        self.queue: list[GraphRequest] = []
        self._lock = threading.RLock()
        self.pipeline = pipeline
        # buffer donation only pays (and is only implemented) off-CPU — the
        # backend gate applies to explicit True as well, so donate=True on
        # CPU doesn't buy a useless defensive copy per full slot; when on,
        # the engine always hands XLA a fresh slot buffer, never a caller's.
        # A mesh-sharded plan reshards the slot itself and ignores donation.
        self._donate = (mesh is None and
                        jax.default_backend() in ("gpu", "tpu") and
                        (donate == "auto" or bool(donate)))
        self._compile_kw = dict(use_kernels=use_kernels, use_int4=use_int4,
                                interpret=interpret, tune=tune,
                                tune_cache_dir=tune_cache_dir,
                                mesh=mesh, device=device)
        self._report_cost = report_cost
        self.n_completed = 0
        self.n_flushes = 0
        self.n_deadline_misses = 0
        self._closed = False
        # --- observability (repro.obs) ---------------------------------
        # A private registry per engine by default, so a fresh engine's
        # counters start at zero; pass a shared ``metrics_registry`` (plus
        # per-model ``metrics_labels``, which EngineRegistry injects) to
        # export a whole fleet from one ``--metrics-port`` endpoint.
        # ``observability=False`` keeps only the plain-int lifetime
        # counters — the pre-obs baseline the bench_serve overhead gate
        # measures against.  ``tracer`` (repro.obs.Tracer) records each
        # flush and each ``__call__`` as live spans of their steps
        # (flush|call -> stage/dispatch/sync/fetch, on the profiler's
        # clock too) and each request's request->queued/compute spans;
        # None/disabled adds zero work to the hot path.
        self.metrics = metrics_registry or MetricsRegistry()
        self._metric_labels = dict(metrics_labels or
                                   {"model": getattr(graph, "name", "graph")})
        self._tracer = tracer
        self._obs_on = bool(observability)
        self.telemetry_window = telemetry_window
        m, lbl = self.metrics, self._metric_labels
        self._m_submitted = m.counter(
            "serve_requests_submitted_total",
            help="requests admitted by submit()", labels=lbl)
        self._m_completed = m.counter(
            "serve_requests_completed_total",
            help="requests completed (result or error)", labels=lbl)
        self._m_flushes = m.counter(
            "serve_flushes_total", help="run_pending flushes", labels=lbl)
        self._m_misses = m.counter(
            "serve_deadline_misses_total",
            help="requests completed after their deadline", labels=lbl)
        self._m_lat = m.histogram(
            "serve_request_latency_ms", unit="ms",
            help="submit -> result latency", window=telemetry_window,
            labels=lbl)
        self._m_queued = m.histogram(
            "serve_request_queued_ms", unit="ms",
            help="submit -> slot dispatch wait", window=telemetry_window,
            labels=lbl)
        self._m_qdepth = m.gauge(
            "serve_queue_depth", help="requests waiting for a flush",
            labels=lbl)
        self._m_occupancy = m.histogram(
            "serve_slot_occupancy",
            help="real requests per dispatched slot / max_batch",
            buckets=_OCCUPANCY_BUCKETS, window=telemetry_window, labels=lbl)
        # serializes whole reload() calls (compile included) so two racing
        # hot-swaps can't interleave into last-compile-wins
        self._reload_lock = threading.Lock()
        self.plan = None
        self.reload(graph)

    # ------------------------------------------------------------- loading

    def reload(self, graph) -> None:
        """(Re)compile ``graph`` and atomically swap it in as the served plan.

        The compile runs *outside* the engine lock, so requests keep being
        submitted to — and flushed through — the old plan while the new one
        builds.  The swap itself is atomic and brief: under the lock the
        still-queued requests (submitted *for the old model*) are popped
        together with a snapshot of the old serving state, and the plan,
        input/output names, sample shape and the lazy empty-batch
        ``_out_spec`` are replaced together; the popped requests are then
        drained through the *old* plan outside the lock — never silently
        answered by the new one, and never stalling concurrent submits for
        the drain's compute.  Whole reloads serialize on a dedicated
        mutex, so racing hot-swaps apply in order instead of
        last-compile-wins.  Telemetry properties read through to whatever
        plan is current, so monitoring never sees a stale snapshot of the
        previous model.
        """
        from repro.core.compile import compile_graph
        with self._reload_lock:
            new_plan = compile_graph(graph, **self._compile_kw)
            g = new_plan.graph
            if new_plan.tune_mode != "off":
                ts = new_plan.tuning_stats()
                self.metrics.counter(
                    "serve_tune_cache_hits_total",
                    help="segment tilings answered from the tune cache at "
                         "engine load/reload",
                    labels=self._metric_labels).inc(ts.get("hits", 0))
                self.metrics.counter(
                    "serve_tune_cache_misses_total",
                    help="segment tilings that fell back to defaults at "
                         "engine load/reload",
                    labels=self._metric_labels).inc(ts.get("misses", 0))
                log.info(
                    "tune[%s] %s: %d/%d segments tuned (cache hits=%d "
                    "misses=%d searched=%d, graph manifest %s)",
                    new_plan.tune_mode, g.name, ts["tuned_segments"],
                    ts["kernel_segments"], ts.get("hits", 0),
                    ts.get("misses", 0), ts.get("searched", 0),
                    "hit" if ts.get("graph_hit") else "miss")
            self.metrics.gauge(
                "serve_plan_devices",
                help="devices the served plan spans (1 = single-device)",
                labels=self._metric_labels).set(new_plan.n_devices)
            if len(g.inputs) != 1:
                raise ValueError(
                    "CompiledGraphEngine serves single-input graphs")
            with self._lock:
                if self._closed:
                    raise RuntimeError(
                        "engine is closed (unregistered); cannot reload")
                pending, self.queue = self.queue, []
                old_state = (self._serving_state()
                             if self.plan is not None else None)
                self.plan = new_plan
                self.input_name = g.input_names[0]
                self.output_name = g.output_names[0]
                self.sample_shape = tuple(g.inputs[0].shape[1:])
                self._out_spec = None  # lazy eval_shape result (empty batch)
            if pending and old_state is not None:
                self._run_requests(pending, old_state)
            # cost telemetry stays inside the reload mutex so racing
            # hot-swaps can't leave cost_report describing a retired model
            self.cost_report = None
            if not self._report_cost:
                return
            # analysis-tier inference cost of the served model, logged once
            # at load (the compile_prep graph keeps quantizers unfolded, so
            # the datatype inference sees the real bit widths)
            try:
                from repro.analysis import infer_cost
                # reuse the GraphAnalysis the compiler already ran
                self.cost_report = infer_cost(g, ga=new_plan.analysis)
                gstats = new_plan.grouped_conv_stats()
                log.info(
                    "loaded %s: %d layers, %s MACs, %.3g BOPs, "
                    "%s weight bits, %.1f KiB traffic/inference, fused=%s "
                    "(%d conv segments on kernels, %d grouped/depthwise "
                    "reclaiming %s MACs + %s carrier bytes vs block-diagonal,"
                    " interp=%s)",
                    g.name, len(self.cost_report.layers),
                    f"{self.cost_report.macs:,}", self.cost_report.bops,
                    f"{int(self.cost_report.total_weight_bits):,}",
                    self.cost_report.total_mem_bytes / 1024,
                    self.fused_counts, self.conv_segments_fused,
                    gstats["grouped_segments"],
                    f"{gstats['reclaimed_macs']:,}",
                    f"{gstats['carrier_bytes_saved']:,}",
                    new_plan.interp_op_counts())
            except Exception:                  # cost is telemetry, not a gate
                log.exception("cost analysis failed for %s", g.name)

    def _serving_state(self) -> tuple:
        """Consistent (plan, names, shape) snapshot — callers hold the lock
        only long enough to take it, then compute outside, so a concurrent
        ``reload`` can never hand half-swapped state to a flush."""
        return (self.plan, self.input_name, self.output_name,
                self.sample_shape)

    # fused-segment telemetry (includes the conv lowerings): how much of
    # the served graph actually runs on the kernel tier.  Read-through
    # properties of the *current* plan — a reload() is reflected
    # immediately, no snapshot to invalidate.
    @property
    def fused_counts(self) -> dict:
        return dict(self.plan.fused_counts)

    @property
    def conv_segments_fused(self) -> int:
        return sum(v for k, v in self.plan.fused_counts.items()
                   if k.startswith("quant_conv"))

    @property
    def grouped_conv_stats(self) -> dict:
        return self.plan.grouped_conv_stats()

    # ------------------------------------------------------------ requests

    def submit(self, x, *, deadline_ms: Optional[float] = None
               ) -> GraphRequest:
        """Queue one sample; returns its ``GraphRequest`` future.

        ``deadline_ms`` (relative to now) marks when the result is due —
        the ``ServeScheduler`` flushes early to honor it and the engine
        counts misses in ``latency_stats()``.
        """
        x = jnp.asarray(x, jnp.float32)
        tr = self._tracer
        tracing = tr is not None and tr.enabled
        with self._lock:
            if self._closed:
                raise RuntimeError(
                    "engine is closed (unregistered); no new submits")
            if x.shape == (1,) + self.sample_shape:  # accept pre-batched rows
                x = x[0]
            if x.shape != self.sample_shape:
                raise ValueError(
                    f"sample shape {x.shape} != {self.sample_shape}")
            r = GraphRequest(x)
            if deadline_ms is not None:
                r.deadline = r.submitted + deadline_ms / 1e3
            self.queue.append(r)
            depth = len(self.queue)
        r.queue_depth = depth
        if tracing:
            r.trace_id = tr.new_trace_id()
        if self._obs_on:
            self._m_submitted.inc()
            self._m_qdepth.set(depth)
        return r

    def pending(self) -> int:
        return len(self.queue)

    def close(self) -> None:
        """Stop admitting requests (already-queued ones can still flush).
        Used by ``EngineRegistry.unregister`` so a racing submit errors
        loudly instead of stranding a request on an orphaned engine."""
        with self._lock:
            self._closed = True

    def flush_signals(self) -> tuple:
        """(pending, oldest_submitted, min_deadline) snapshot under the
        engine lock — the only queue view a flush loop needs, so
        schedulers don't reach into the queue representation."""
        with self._lock:
            q = self.queue
            oldest = q[0].submitted if q else None
            deadline = min((r.deadline for r in q if r.deadline is not None),
                           default=None)
            return len(q), oldest, deadline

    def _pad_to_slot(self, x, sample_shape=None, *, owned=False):
        """Zero-pad a (<=max_batch, ...) chunk to the one static slot shape
        every plan call uses — shared by run_pending and __call__ so both
        paths hit the same jitted executable.  With donation on, a full
        chunk is copied unless the caller ``owned`` the buffer (a fresh
        stack) — XLA must never consume memory the caller still holds."""
        if sample_shape is None:
            sample_shape = self.sample_shape
        if x.shape[0] == self.max_batch:
            if self._donate and not owned:
                return jnp.array(x, copy=True)
            return x
        pad = self.max_batch - x.shape[0]
        return jnp.concatenate(
            [x, jnp.zeros((pad,) + sample_shape, x.dtype)])

    def run_pending(self, *, only_full_slots: bool = False) -> int:
        """Flush the queue in max_batch-sized slots; returns #requests run.

        All slots are dispatched before the single trailing sync (see
        module docstring); per-request completion timestamps and the
        aggregate p50/p99 log happen after the sync.

        ``only_full_slots=True`` leaves the partial tail slot queued (the
        scheduler's full-slot trigger uses it so a request submitted a
        millisecond ago keeps batching through its flush window instead of
        riding out in a mostly-padded slot).
        """
        with self._lock:
            n = len(self.queue)
            if only_full_slots:
                n = (n // self.max_batch) * self.max_batch
            if n == 0:
                return 0
            reqs, self.queue = self.queue[:n], self.queue[n:]
            depth = len(self.queue)
            state = self._serving_state()
        if self._obs_on:
            self._m_qdepth.set(depth)
        return self._run_requests(reqs, state)

    def _run_requests(self, reqs: list, state: tuple) -> int:
        tr = self._tracer
        span = _NO_SPAN
        if tr is not None and tr.enabled:
            n_slots = -(-len(reqs) // self.max_batch)
            span = tr.span("flush", n_requests=len(reqs), n_slots=n_slots,
                           slot_occupancy=round(
                               len(reqs) / (n_slots * self.max_batch), 4),
                           pipeline=self.pipeline)
        # one call site, traced or not: a Pallas kernel's compiled payload
        # records its callers' source lines, so two sites would compile and
        # cache the same program twice
        with span as root:
            return self._flush(reqs, state, tr, root)

    def _flush(self, reqs: list, state: tuple, tr, root) -> int:
        """``_run_requests``'s steps; with a root span (tracing on) each is
        a live child span of it: ``dispatch``, ``sync``, ``fetch``."""
        plan, in_name, out_name, sample_shape = state
        tracing = root is not None
        dispatched = []
        try:
            with (tr.span("dispatch", parent=root,
                          n_slots=root.attrs["n_slots"])
                  if tracing else _NO_SPAN):
                for i in range(0, len(reqs), self.max_batch):
                    batch = reqs[i:i + self.max_batch]
                    t_dispatch = time.monotonic()
                    for r in batch:
                        r.started = t_dispatch
                    x = self._pad_to_slot(jnp.stack([r.x for r in batch]),
                                          sample_shape, owned=True)
                    out = plan({in_name: x}, donate=self._donate)[out_name]
                    dispatched.append((batch, out))
                    if self._obs_on:
                        self._m_occupancy.observe(len(batch) / self.max_batch)
                    if not self.pipeline:      # per-slot host sync: baseline
                        jax.block_until_ready(out)
            with tr.span("sync", parent=root) if tracing else _NO_SPAN:
                if self.pipeline:              # single trailing sync
                    jax.block_until_ready([o for _, o in dispatched])
        except Exception as e:
            # scope the failure: every dispatched slot whose compute
            # actually succeeded still completes (the scatter forces it) and
            # still counts in telemetry; only requests in failing or
            # never-dispatched slots carry the error
            completed = []
            for batch, out in dispatched:
                try:
                    self._scatter(batch, out)
                    completed.extend(batch)
                except Exception:              # this slot really failed
                    pass
            for r in reqs:
                if not r.done():
                    r._finish(error=e)
            if completed:
                self._record(completed)
            raise
        with tr.span("fetch", parent=root) if tracing else _NO_SPAN:
            for batch, out in dispatched:
                self._scatter(batch, out)
        self._record(reqs)
        return len(reqs)

    @staticmethod
    def _scatter(batch: list, out) -> None:
        rows = np.asarray(out)
        for j, r in enumerate(batch):
            # copy the row out of the slot so a held future pins one row,
            # not the whole padded (max_batch, ...) output buffer
            r._finish(rows[j].copy())

    def _record(self, reqs: list) -> None:
        n_miss = 0
        for r in reqs:
            if r.deadline is not None and r.completed is not None and \
                    r.completed > r.deadline:
                n_miss += 1
        with self._lock:
            self.n_deadline_misses += n_miss
            self.n_completed += len(reqs)
            self.n_flushes += 1
        if self._obs_on:
            for r in reqs:
                if r.latency_ms is not None:
                    self._m_lat.observe(r.latency_ms)
                if r.queued_ms is not None:
                    self._m_queued.observe(r.queued_ms)
            self._m_completed.inc(len(reqs))
            self._m_flushes.inc()
            if n_miss:
                self._m_misses.inc(n_miss)
        tr = self._tracer
        if tr is not None and tr.enabled:
            for r in reqs:
                if r.trace_id is None or r.completed is None:
                    continue
                missed = (r.deadline is not None and
                          r.completed > r.deadline)
                root = tr.emit(
                    "request", r.submitted, r.completed,
                    trace_id=r.trace_id, queue_depth=r.queue_depth,
                    deadline_missed=missed,
                    error=type(r.error).__name__ if r.error else None)
                if r.started is not None:
                    tr.emit("queued", r.submitted, r.started,
                            trace_id=r.trace_id, parent_id=root)
                    tr.emit("compute", r.started, r.completed,
                            trace_id=r.trace_id, parent_id=root)
        # percentile computation + formatting stay off the engine lock, and
        # are skipped entirely when nobody listens at INFO
        if log.isEnabledFor(logging.INFO):
            stats = self.latency_stats()
            log.info(
                "flush: %d request(s) (%d total over %d flushes) "
                "latency p50=%.2fms p99=%.2fms, queued p50=%.2fms "
                "p99=%.2fms, %d deadline miss(es)",
                len(reqs), stats["completed"], stats["flushes"],
                stats["latency_p50_ms"], stats["latency_p99_ms"],
                stats["queued_p50_ms"], stats["queued_p99_ms"],
                stats["deadline_misses"])

    def latency_stats(self) -> dict:
        """Aggregate request telemetry.

        ``*_total`` keys are explicit lifetime counters; the percentile
        keys are computed over the rolling ``telemetry_window`` (the shared
        histogram's exact windowed view — see ``repro.obs.metrics``).  The
        unsuffixed ``completed``/``flushes``/``deadline_misses`` keys are
        the historical names for the same lifetime totals, kept for
        callers of the original PR-5 dict shape.  With
        ``observability=False`` the histograms are idle and every
        percentile is nan.
        """
        with self._lock:
            completed, flushes = self.n_completed, self.n_flushes
            misses = self.n_deadline_misses
        lat = self._m_lat.snapshot()
        qd = self._m_queued.snapshot()
        return {
            "completed": completed,
            "flushes": flushes,
            "deadline_misses": misses,
            "completed_total": completed,
            "flushes_total": flushes,
            "deadline_misses_total": misses,
            "telemetry_window": self.telemetry_window,
            "window_observations": len(lat.window),
            "latency_p50_ms": lat.percentile(50),
            "latency_p99_ms": lat.percentile(99),
            "queued_p50_ms": qd.percentile(50),
            "queued_p99_ms": qd.percentile(99),
        }

    # ---------------------------------------------------- synchronous path

    def __call__(self, x) -> np.ndarray:
        """Synchronous convenience path.

        Routes through the same padded ``max_batch`` slot shape as
        ``run_pending``: the batch is split into max_batch-sized chunks and
        the tail chunk is zero-padded, so ad-hoc batch sizes reuse the one
        jitted plan shape instead of each triggering a fresh retrace.  With
        ``pipeline=True`` every chunk is dispatched device-side before the
        single trailing sync — chunk *k+1*'s dispatch overlaps chunk *k*'s
        compute; ``pipeline=False`` forces each chunk to host before
        dispatching the next (the measured baseline).

        With tracing on, the call is a ``call`` span (images, slots) whose
        children are its steps: ``stage`` (the batch to the device, with
        its bytes), ``dispatch`` (slice, pad and enqueue every slot),
        ``sync`` (the trailing ``block_until_ready``) and ``fetch`` (every
        slot to the host and the concatenate, with the result's bytes).
        """
        tr = self._tracer
        span = tr.span("call") if tr is not None and tr.enabled else _NO_SPAN
        with span as root:        # one call site, as in ``_run_requests``
            return self._call(x, tr, root)

    def _call(self, x, tr, root) -> np.ndarray:
        tracing = root is not None
        with tr.span("stage", parent=root) if tracing else _NO_SPAN as sp:
            x = jnp.asarray(x, jnp.float32)
            if tracing:
                sp.set(bytes=x.nbytes)
        with self._lock:
            plan, in_name, out_name, sample_shape = self._serving_state()
        unbatched = x.shape == sample_shape
        if unbatched:
            x = x[None]
        if x.shape[1:] != sample_shape:
            raise ValueError(
                f"sample shape {x.shape[1:]} != {sample_shape}")
        if x.shape[0] == 0:
            # empty batch: abstract-eval the plan once for the output
            # shape/dtype (no compute), return 0 rows of it.  The cache is
            # read/written under the lock and keyed to the snapshotted plan
            # so a racing reload() can never leave a retired model's spec
            # poisoning the hot-swapped engine.
            with self._lock:
                spec = self._out_spec if self.plan is plan else None
            if spec is None:
                sd = jax.eval_shape(
                    lambda inp: plan(inp, jit=False),
                    {in_name: jax.ShapeDtypeStruct(
                        (self.max_batch,) + sample_shape, x.dtype)})
                spec = sd[out_name]
                with self._lock:
                    if self.plan is plan:
                        self._out_spec = spec
            return np.zeros((0,) + tuple(spec.shape[1:]), spec.dtype)
        if tracing:
            root.set(images=x.shape[0],
                     slots=-(-x.shape[0] // self.max_batch))
        outs = []
        with tr.span("dispatch", parent=root) if tracing else _NO_SPAN:
            for i in range(0, x.shape[0], self.max_batch):
                chunk = x[i:i + self.max_batch]
                out = plan({in_name: self._pad_to_slot(chunk, sample_shape)},
                           donate=self._donate)[out_name]
                outs.append(out[:chunk.shape[0]])  # lazy slice, on device
                if not self.pipeline:
                    jax.block_until_ready(out)     # per-chunk stall: baseline
        with tr.span("sync", parent=root) if tracing else _NO_SPAN:
            if self.pipeline:
                jax.block_until_ready(outs)        # one sync for all chunks
        with tr.span("fetch", parent=root) if tracing else _NO_SPAN as sp:
            result = np.concatenate([np.asarray(o) for o in outs], axis=0)
            if tracing:
                sp.set(bytes=result.nbytes)
        return result[0] if unbatched else result
