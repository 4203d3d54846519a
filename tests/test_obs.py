"""Observability subsystem tests: metrics, tracing, profiling, wiring.

Covers repro.obs (registry/counter/gauge/histogram semantics, exporters,
span parent/child links, the JSONL sink, the report CLI, the HTTP
endpoint) and its integration into the compile and serve tiers — the
``*_total`` stats keys, the shared-registry fleet export, the
disabled-tracer zero-allocation guarantee on the submit and call paths,
and the engine's live spans read back from a JAX profiler trace.
"""
import glob
import json
import os
import threading
import tracemalloc
import urllib.request

import numpy as np
import pytest

from repro import obs
from repro.core import GraphBuilder
from repro.obs import (Histogram, JsonlSink, ListSink, MetricsRegistry,
                       Tracer, exponential_buckets, nearest_rank)
from repro.serve import CompiledGraphEngine, ServeScheduler


def _mlp(seed=0, out_dim=6, in_dim=16):
    """Tiny quantized MLP (same shape as the serve tests' fixture)."""
    rng = np.random.RandomState(seed)
    b = GraphBuilder(f"obs_mlp_s{seed}")
    x = b.add_input("x", (1, in_dim))
    h = b.quant(x, 0.0973, 0.0, 4, signed=True)
    w = b.add_initializer("w", rng.randn(in_dim, out_dim)
                          .astype(np.float32) * 0.4)
    qw = b.quant(w, 0.0517, 0.0, 4, narrow=True)
    (h,) = b.add_node("MatMul", [h, qw], 1)
    b.mark_output(h)
    return b.build()


def _engine(**kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("report_cost", False)
    return CompiledGraphEngine(_mlp(), **kw)


# ------------------------------------------------------------ primitives

def test_counter_inc_and_negative_rejected():
    reg = MetricsRegistry()
    c = reg.counter("c_total")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_set_inc_dec():
    g = MetricsRegistry().gauge("g")
    g.set(10)
    g.inc(5)
    g.dec(2)
    assert g.value == 13.0


def test_registry_children_idempotent_and_label_separated():
    reg = MetricsRegistry()
    a = reg.counter("reqs_total", labels={"model": "a"})
    a2 = reg.counter("reqs_total", labels={"model": "a"})
    b = reg.counter("reqs_total", labels={"model": "b"})
    assert a is a2 and a is not b
    a.inc(3)
    b.inc(1)
    series = reg.snapshot()["reqs_total"]["series"]
    assert {s["labels"]["model"]: s["value"] for s in series} == \
        {"a": 3.0, "b": 1.0}


def test_registry_kind_conflict_rejected():
    reg = MetricsRegistry()
    reg.counter("m")
    with pytest.raises(ValueError):
        reg.gauge("m")


def test_exponential_buckets_validation():
    bs = exponential_buckets(0.5, 2.0, 4)
    assert bs == (0.5, 1.0, 2.0, 4.0)
    with pytest.raises(ValueError):
        exponential_buckets(start=0)
    with pytest.raises(ValueError):
        exponential_buckets(factor=1.0)


# ------------------------------------------------------------ histograms

def test_histogram_bucket_boundaries_le_semantics():
    h = Histogram({}, buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 1.5, 2.0, 4.0, 5.0):
        h.observe(v)
    s = h.snapshot()
    # le semantics: a value equal to a bound lands in that bound's bucket
    assert s.counts == (2, 2, 1, 1)        # (<=1, <=2, <=4, +Inf)
    assert s.count == 6 and s.sum == pytest.approx(14.0)
    h.observe(float("nan"))                # nan observations are dropped
    assert h.count == 6


def test_histogram_rejects_unsorted_buckets():
    with pytest.raises(ValueError):
        Histogram({}, buckets=(2.0, 1.0))
    with pytest.raises(ValueError):
        Histogram({}, buckets=(1.0, 1.0))


def test_estimate_percentile_tracks_numpy_within_bucket_resolution():
    rng = np.random.RandomState(7)
    values = np.abs(rng.lognormal(mean=1.0, sigma=1.2, size=4000))
    h = Histogram({}, buckets=exponential_buckets(0.001, 2.0, 28))
    for v in values:
        h.observe(float(v))
    s = h.snapshot()
    for pct in (50.0, 90.0, 99.0):
        est = s.estimate_percentile(pct)
        true = float(np.percentile(values, pct))
        # bucket-interpolated accuracy is bounded by the factor-2 bucket
        # width: the estimate must land in the true value's bucket or its
        # immediate neighbors
        assert true / 2.0 <= est <= true * 2.0, (pct, est, true)


def test_windowed_percentile_is_exact_nearest_rank():
    values = [float(v) for v in np.random.RandomState(3).randn(500) ** 2]
    h = Histogram({}, buckets=exponential_buckets(), window=1000)
    for v in values:
        h.observe(v)
    for pct in (0, 50, 90, 99, 100):
        assert h.percentile(pct) == nearest_rank(values, pct)
    # window smaller than the stream: only the most recent N are ranked
    h2 = Histogram({}, buckets=exponential_buckets(), window=100)
    for v in values:
        h2.observe(v)
    assert h2.percentile(50) == nearest_rank(values[-100:], 50)
    # bucket totals still cover the full stream
    assert h2.count == 500


def test_empty_histogram_percentiles_are_nan():
    h = Histogram({}, buckets=(1.0,), window=8)
    assert np.isnan(h.percentile(50))
    assert np.isnan(h.snapshot().estimate_percentile(99))
    assert np.isnan(h.snapshot().mean())


def test_concurrent_counter_increments_are_exact():
    reg = MetricsRegistry()
    c = reg.counter("hits_total")
    h = reg.histogram("obs_ms", window=64)
    n_threads, per_thread = 8, 10_000

    def worker():
        for _ in range(per_thread):
            c.inc()
            h.observe(1.0)

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * per_thread
    assert h.count == n_threads * per_thread


# ------------------------------------------------------------- exporters

def test_snapshot_and_prometheus_text():
    reg = MetricsRegistry()
    reg.counter("reqs_total", help="requests", labels={"model": "m"}).inc(2)
    reg.histogram("lat_ms", unit="ms", buckets=(1.0, 10.0),
                  labels={"model": "m"}).observe(5.0)
    snap = reg.snapshot()
    assert snap["reqs_total"]["type"] == "counter"
    hs = snap["lat_ms"]["series"][0]
    assert hs["count"] == 1 and hs["buckets"] == [[1.0, 0], [10.0, 1],
                                                  ["+Inf", 0]]
    text = reg.to_prometheus()
    assert '# TYPE reqs_total counter' in text
    assert 'reqs_total{model="m"} 2.0' in text
    # histogram exposition: cumulative le buckets + _sum/_count
    assert 'lat_ms_bucket{model="m",le="1.0"} 0' in text
    assert 'lat_ms_bucket{model="m",le="10.0"} 1' in text
    assert 'lat_ms_bucket{model="m",le="+Inf"} 1' in text
    assert 'lat_ms_sum{model="m"} 5.0' in text
    assert 'lat_ms_count{model="m"} 1' in text
    # JSON export round-trips
    assert json.loads(reg.to_json())["reqs_total"]["series"][0]["value"] == 2


def test_report_render_table():
    from repro.obs.report import render
    reg = MetricsRegistry()
    reg.counter("reqs_total", labels={"model": "m"}).inc(7)
    reg.histogram("lat_ms", unit="ms", window=16).observe(3.0)
    out = render(json.loads(reg.to_json()))
    assert "reqs_total" in out and "model=m" in out and "7" in out
    assert "p50=3" in out
    assert render(reg.snapshot(), "nomatch") == "(no metrics matched)"


def test_report_cli_main(tmp_path, capsys):
    from repro.obs.report import main
    reg = MetricsRegistry()
    reg.gauge("depth", labels={"model": "m"}).set(4)
    p = tmp_path / "snap.json"
    p.write_text(reg.to_json())
    assert main([str(p)]) == 0
    out = capsys.readouterr().out
    assert "depth" in out and "model=m" in out
    with pytest.raises(SystemExit):       # exactly one source required
        main([])


def test_http_endpoint_serves_prometheus_and_json():
    reg = MetricsRegistry()
    reg.counter("up_total").inc()
    with obs.start_metrics_server(reg, port=0, host="127.0.0.1") as srv:
        base = f"http://127.0.0.1:{srv.port}"
        text = urllib.request.urlopen(f"{base}/metrics", timeout=5) \
            .read().decode()
        assert "up_total 1.0" in text
        snap = json.loads(urllib.request.urlopen(
            f"{base}/metrics.json", timeout=5).read().decode())
        assert snap["up_total"]["series"][0]["value"] == 1.0
        assert urllib.request.urlopen(
            f"{base}/healthz", timeout=5).status == 200
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{base}/nope", timeout=5)


# --------------------------------------------------------------- tracing

def test_span_parent_child_links_and_sink_ordering(tmp_path):
    path = tmp_path / "spans.jsonl"
    with JsonlSink(path) as sink:
        tr = Tracer(sink)
        with tr.span("flush", n_requests=3) as root:
            with tr.span("dispatch", parent=root):
                pass
            with tr.span("sync", parent=root):
                pass
    recs = [json.loads(line) for line in
            path.read_text().strip().splitlines()]
    by_name = {r["name"]: r for r in recs}
    assert [r["name"] for r in recs] == ["dispatch", "sync", "flush"]
    # children close before the root reaches the sink, share its trace id
    # and point at its span id
    assert by_name["dispatch"]["parent"] == by_name["flush"]["span"]
    assert by_name["sync"]["parent"] == by_name["flush"]["span"]
    assert len({r["trace"] for r in recs}) == 1
    assert by_name["flush"]["n_requests"] == 3
    assert all(r["dur_ms"] >= 0 for r in recs)
    # timestamps nest: the root covers its children
    assert by_name["flush"]["t0"] <= by_name["dispatch"]["t0"]
    assert by_name["dispatch"]["t1"] <= by_name["flush"]["t1"]


def test_retroactive_emit_and_disabled_tracer():
    sink = ListSink()
    tr = Tracer(sink)
    root = tr.emit("request", 10.0, 10.5, queue_depth=2)
    tr.emit("queued", 10.0, 10.2, parent_id=root)
    assert len(sink) == 2 and sink[1]["parent"] == root
    assert sink[0]["dur_ms"] == pytest.approx(500.0)
    assert tr.n_spans == 2


def test_jsonl_sink_rejects_writes_after_close(tmp_path):
    sink = JsonlSink(tmp_path / "s.jsonl")
    sink({"name": "a"})
    sink.close()
    with pytest.raises(ValueError):
        sink({"name": "b"})


# --------------------------------------------------- serve-tier wiring

def test_engine_stats_report_totals_and_windowed_percentiles():
    eng = _engine()
    rng = np.random.RandomState(0)
    for _ in range(6):
        eng.submit(rng.randn(16).astype(np.float32))
    eng.run_pending()
    s = eng.latency_stats()
    # historical keys and the explicit *_total aliases agree
    assert s["completed"] == s["completed_total"] == 6
    assert s["flushes"] == s["flushes_total"] == 1
    assert s["deadline_misses"] == s["deadline_misses_total"] == 0
    assert s["window_observations"] == 6
    assert s["telemetry_window"] == eng.telemetry_window
    assert s["latency_p99_ms"] >= s["latency_p50_ms"] >= 0


def test_engine_metrics_registry_series():
    reg = MetricsRegistry()
    eng = _engine(metrics_registry=reg, metrics_labels={"model": "m1"})
    rng = np.random.RandomState(1)
    for _ in range(5):
        eng.submit(rng.randn(16).astype(np.float32))
    assert reg.get("serve_queue_depth", {"model": "m1"}).value == 5
    eng.run_pending()
    snap = reg.snapshot()
    get = {name: snap[name]["series"][0] for name in snap}
    assert get["serve_requests_submitted_total"]["value"] == 5
    assert get["serve_requests_completed_total"]["value"] == 5
    assert get["serve_flushes_total"]["value"] == 1
    assert get["serve_request_latency_ms"]["count"] == 5
    assert get["serve_queue_depth"]["value"] == 0
    # 5 requests over max_batch=4 slots: one full + one 1/4 slot
    occ = reg.get("serve_slot_occupancy", {"model": "m1"}).snapshot()
    assert occ.count == 2 and sorted(occ.window) == [0.25, 1.0]
    # prometheus export carries the model label on every family
    assert 'serve_flushes_total{model="m1"} 1.0' in reg.to_prometheus()


def test_observability_off_keeps_stats_but_idles_registry():
    eng = _engine(observability=False)
    rng = np.random.RandomState(2)
    for _ in range(3):
        eng.submit(rng.randn(16).astype(np.float32))
    eng.run_pending()
    s = eng.latency_stats()
    assert s["completed_total"] == 3            # plain ints still count
    assert np.isnan(s["latency_p50_ms"])        # histograms never observed
    assert eng.metrics.get("serve_requests_submitted_total",
                           eng._metric_labels).value == 0


def test_engine_emits_request_and_flush_spans():
    sink = ListSink()
    eng = _engine(tracer=Tracer(sink))
    rng = np.random.RandomState(3)
    reqs = [eng.submit(rng.randn(16).astype(np.float32)) for _ in range(5)]
    eng.run_pending()
    by_name = {}
    for r in sink:
        by_name.setdefault(r["name"], []).append(r)
    assert len(by_name["request"]) == 5
    assert len(by_name["flush"]) == 1
    assert len(by_name["queued"]) == len(by_name["compute"]) == 5
    flush = by_name["flush"][0]
    assert flush["n_requests"] == 5 and flush["n_slots"] == 2
    assert by_name["dispatch"][0]["parent"] == flush["span"]
    assert by_name["sync"][0]["parent"] == flush["span"]
    # each request span carries its submit-time context and its children
    # link to it within its own trace
    for req, rec in zip(reqs, by_name["request"]):
        assert rec["trace"] == req.trace_id
        assert rec["queue_depth"] == req.queue_depth
        kids = [r for r in sink if r.get("parent") == rec["span"]]
        assert {k["name"] for k in kids} == {"queued", "compute"}


def _tracer_allocations(eng, step, n=50) -> int:
    """Bytes allocated in repro.obs.trace over ``n`` calls of ``step``."""
    import repro.obs.trace as trace_mod
    tracemalloc.start()
    try:
        for _ in range(n):
            step()
        snap = tracemalloc.take_snapshot()
    finally:
        tracemalloc.stop()
    eng.run_pending()
    trace_allocs = snap.filter_traces(
        [tracemalloc.Filter(True, trace_mod.__file__)]).statistics("lineno")
    return sum(s.size for s in trace_allocs)


def test_disabled_tracer_adds_zero_allocations_to_submit():
    eng = _engine(tracer=Tracer(ListSink(), enabled=False))
    x = np.zeros(16, np.float32)
    for _ in range(4):                       # warm every lazy path
        eng.submit(x)
    eng.run_pending()
    assert _tracer_allocations(eng, lambda: eng.submit(x)) == 0


@pytest.mark.parametrize("tracer", ["none", "disabled"])
def test_disabled_tracer_adds_zero_allocations_to_call(tracer):
    """``__call__`` with ``tracer=None`` or a disabled tracer takes no
    span path: its steps enter the shared no-op context."""
    eng = _engine(tracer=None if tracer == "none" else
                  Tracer(ListSink(), enabled=False))
    x = np.zeros((6, 16), np.float32)
    eng(x)                                   # warm every lazy path
    assert _tracer_allocations(eng, lambda: eng(x), n=20) == 0


# ------------------------------------------- spans on the profiler's clock

ENGINE_STEPS = ("call", "flush", "stage", "dispatch", "sync", "fetch")


@pytest.fixture(scope="module")
def profiled_engine(tmp_path_factory):
    """One ``__call__`` (two slots) and one ``run_pending`` flush of a
    traced engine under ``jax.profiler.trace``; the engine's live spans
    read back from the host plane of the recorded ``.xplane.pb``."""
    import jax
    from jax.profiler import ProfileData
    sink = ListSink()
    eng = _engine(tracer=Tracer(sink))
    x = np.random.RandomState(5).randn(6, 16).astype(np.float32)
    eng(x)                                   # compile outside the trace
    sink.clear()
    trace_dir = str(tmp_path_factory.mktemp("profile"))
    with jax.profiler.trace(trace_dir):
        eng(x)
        for row in x[:5]:
            eng.submit(row)
        eng.run_pending()
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                events += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events
                           if e.name in ENGINE_STEPS + ("request",)]
    return sink, sorted(events, key=lambda e: e[1])


@pytest.mark.parametrize("root", ["call", "flush"])
def test_engine_steps_nest_on_the_profiler_trace(profiled_engine, root):
    sink, events = profiled_engine
    (top,) = [e for e in events if e[0] == root]
    kids = [e for e in events if top[1] <= e[1] and e[2] <= top[2]
            and e is not top]
    steps = ("stage", "dispatch", "sync", "fetch") if root == "call" \
        else ("dispatch", "sync", "fetch")
    assert [e[0] for e in kids] == list(steps)      # in order, each once
    assert all(a[2] <= b[1] for a, b in zip(kids, kids[1:]))
    # the sink's records are the same spans, linked to the root
    (rec,) = [r for r in sink if r["name"] == root]
    assert sorted(r["name"] for r in sink
                  if r["parent"] == rec["span"]) == sorted(steps)
    if root == "call":
        assert rec["images"] == 6 and rec["slots"] == 2
        stage, fetch = (next(r for r in sink if r["name"] == n
                             and r["parent"] == rec["span"])
                        for n in ("stage", "fetch"))
        assert stage["bytes"] == 6 * 16 * 4 and fetch["bytes"] == 6 * 6 * 4


def test_retroactive_spans_stay_off_the_profiler_trace(profiled_engine):
    sink, events = profiled_engine
    assert sum(r["name"] == "request" for r in sink) == 5
    assert not [e for e in events if e[0] == "request"]


def test_scheduler_stats_totals_and_rejection_counter():
    from repro.serve import QueueFull
    eng = _engine()
    sched = ServeScheduler(eng, max_queue=2, block=False)
    xs = np.random.RandomState(4).randn(3, 16).astype(np.float32)
    with sched:
        sched.submit(xs[0])
        sched.submit(xs[1])
        with pytest.raises(QueueFull):
            sched.submit(xs[2])
        s = sched.stats()
        assert s["submitted"] == s["submitted_total"] == 2
        assert s["rejected"] == s["rejected_total"] == 1
    assert eng.metrics.get("serve_scheduler_rejected_total",
                           eng._metric_labels).value == 1
    assert "admission_wait_p99_ms" in s


def test_registry_injects_model_labels_and_merges_snapshots():
    from repro.serve import EngineRegistry
    reg = EngineRegistry(max_batch=4, report_cost=False)
    reg.register("a", _mlp(seed=1))
    reg.register("b", _mlp(seed=2))
    reg("a", np.zeros(16, np.float32))
    merged = reg.metrics_snapshot()
    series = merged["serve_requests_completed_total"]["series"]
    assert {s["labels"]["model"] for s in series} == {"a", "b"}


# ---------------------------------------------- compile-tier instrumentation

def test_compile_records_wall_time_and_plan_gauges():
    from repro.core.compile import compile_graph
    from repro.obs import default_registry
    reg = default_registry()
    g = _mlp(seed=9)
    before = reg.get("compile_wall_ms", {"model": g.name})
    n0 = before.count if before is not None else 0
    plan = compile_graph(g)
    lbl = {"model": plan.graph.name}
    assert reg.get("compile_wall_ms", lbl).count == n0 + 1
    assert reg.get("compile_segments",
                   {**lbl, "kind": "total"}).value == len(plan.segments)
    assert reg.get("compile_integer_requant_coverage", lbl).value == \
        plan.requant_stats()["coverage"]
    # the retrace counter follows the trace-count probe
    retrace = reg.get("compile_plan_retraces_total", lbl)
    r0, t0 = retrace.value, plan.trace_count
    plan({"x": np.zeros((1, 16), np.float32)})
    plan({"x": np.zeros((1, 16), np.float32)})      # same shape: no retrace
    assert plan.trace_count == t0 + 1
    assert retrace.value == r0 + 1
