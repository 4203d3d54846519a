"""repro.obs — unified observability: metrics, tracing, timing.

Three layers over the compile/serve tiers:

  * ``metrics``  — thread-safe ``MetricsRegistry`` of counters, gauges and
                   exponential-bucket histograms with labels; JSON +
                   Prometheus exporters; process-wide default registry.
                   ``python -m repro.obs.report`` renders a snapshot.
  * ``trace``    — ``Span``/``Tracer`` (context-manager or retroactive
                   ``emit``), parent/child links, JSONL sink; live spans
                   also annotate a running JAX profiler trace.  Wired
                   through the serving engine's steps (call|flush ->
                   stage/dispatch/sync/fetch) and each request's lifecycle.
                   Disabled tracing adds zero allocations to the hot path.
  * ``profile``  — ``time_fn``/``time_fns``, the shared best-of-N timing
                   harness (the autotuner's candidate search).

``http.start_metrics_server`` serves the Prometheus text format from a
stdlib HTTP server (``python -m repro.launch.serve --metrics-port``).
"""
from .metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    HistogramSnapshot,
    MetricsRegistry,
    default_registry,
    exponential_buckets,
    nearest_rank,
)
from .http import MetricsServer, start_metrics_server  # noqa: F401
from .profile import time_fn, time_fns  # noqa: F401
from .trace import JsonlSink, ListSink, Span, Tracer  # noqa: F401
from . import http  # noqa: F401

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "HistogramSnapshot",
    "JsonlSink",
    "ListSink",
    "MetricsRegistry",
    "MetricsServer",
    "Span",
    "Tracer",
    "default_registry",
    "exponential_buckets",
    "nearest_rank",
    "start_metrics_server",
    "time_fn",
    "time_fns",
]
