"""Compiled vs interpreted executor: wall-time over the zoo graphs.

The headline number for the compile tier (core/compile.py): steady-state
µs/call of the single jitted plan vs node-by-node Python dispatch, plus the
fused-segment census.  With the lowering-rule registry both the quantized
matmuls (TFC family) and the convolutions (CNV / MobileNet) dispatch onto
the integer Pallas kernels; only shape-shuffles and pooling remain on the
interpreted fallback.

``--json PATH`` writes the same measurements machine-readably (per-model
wall times, speedup, fused-segment counts) so the perf trajectory is
tracked across PRs; ``--check-conv MODEL`` is the CI regression gate that
asserts the conv lowering still fires (≥1 conv segment fused, 0 Conv nodes
left interpreted); ``--check-grouped MODEL`` additionally gates the
grouped/depthwise kernel tier (every group>1 conv on the dedicated
kernels, 0 block-diagonal carriers, cost-report MACs below the
dense-equivalent block-diagonal count by exactly the reclaimed amount);
``--check-integer-requant MODEL`` gates the integer-only dyadic
requantization path (every kernel segment on the int32 multiplier+shift
epilogue, coverage recorded in the JSON artifact);
``--check-fusion MODEL`` gates cross-segment fusion (≥1 fused boundary
segment on an integer inter-segment carrier, positive boundary
bytes-saved, 0 interpreted MaxPool/Add, fused output bit-identical to
the ``use_fusion=False`` plan).  Each per-model JSON record also carries
``fusion``: the plan's boundary census (``CompiledPlan.fusion_stats``).

Per model the JSON record also carries ``requant``: the plan's
integer-path coverage (``CompiledPlan.requant_stats``) plus the measured
epilogue speedup vs the same plan compiled with
``use_integer_requant=False`` (the fp32 dequant->round->requant chain).
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.core import execute, transforms
from repro.core.compile import compile_graph
from repro.models import zoo
from repro.obs.profile import time_fn, time_fns

CASES = [
    ("TFC-w2a2", (1, 784)),
    ("TFC-w1a1", (1, 784)),
    ("CNV-w2a2", (1, 3, 32, 32)),
]

QUICK_CASES = [("TFC-w2a2", (1, 784)), ("TFC-w1a1", (1, 784))]


def _time(fn, n=5):
    """Best-of-``n`` µs/call via the shared obs.profile harness."""
    return time_fn(fn, n) * 1e6


def run_detailed(cases=None) -> tuple[list[str], dict]:
    """Benchmark ``cases``; returns (CSV rows, per-model record dict)."""
    rows, records = [], {}
    for name, shape in (CASES if cases is None else cases):
        g = zoo.ZOO[name]()
        gc = transforms.cleanup(g)
        t0 = time.perf_counter()
        plan = compile_graph(g)
        compile_us = (time.perf_counter() - t0) * 1e6
        x = np.random.RandomState(0).randn(*shape).astype(np.float32)
        out_name = gc.output_names[0]

        # block_until_ready, not np.asarray: the plan returns un-forced
        # device arrays (async dispatch — what the serving tier pipelines
        # on), so timing must wait for the *compute*, not just the enqueue;
        # a host copy would also pollute the measurement
        us_interp = _time(lambda: jax.block_until_ready(
            execute(gc, {"x": x})[out_name]))
        us_comp = _time(lambda: jax.block_until_ready(
            plan({"x": x})[plan.graph.output_names[0]]))
        fused = ";".join(f"{k}={v}" for k, v in sorted(
            plan.fused_counts.items()))
        rows.append(
            f"compile/{name}_interpreted,{us_interp:.0f},node_by_node_oracle")
        rows.append(
            f"compile/{name}_compiled,{us_comp:.0f},"
            f"speedup={us_interp / us_comp:.1f}x;{fused};"
            f"compile_us={compile_us:.0f}")

        # integer-requant coverage + epilogue speedup vs the fp32 baseline:
        # the same graph compiled with the integer path disabled isolates
        # the dequant->round->requant chain the dyadic path eliminates
        rq = plan.requant_stats()
        plan_fp32 = compile_graph(g, use_integer_requant=False)
        us_fp32 = _time(lambda: jax.block_until_ready(
            plan_fp32({"x": x})[plan_fp32.graph.output_names[0]]))
        rows.append(
            f"compile/{name}_fp32_requant,{us_fp32:.0f},"
            f"int_coverage={rq['coverage']:.2f};"
            f"epilogue_speedup={us_fp32 / us_comp:.2f}x;"
            f"fp32_ops_eliminated={rq['fp32_ops_eliminated']}")

        # batched serving amortizes the fixed per-call overhead further
        xb = np.random.RandomState(1).randn(8, *shape[1:]).astype(np.float32)
        us_b = _time(lambda: jax.block_until_ready(
            plan({"x": xb})[plan.graph.output_names[0]]))
        rows.append(f"compile/{name}_compiled_b8,{us_b:.0f},"
                    f"us_per_sample={us_b / 8:.0f}")
        records[name] = {
            "interp_us": round(us_interp, 1),
            "compiled_us": round(us_comp, 1),
            "speedup": round(us_interp / us_comp, 2),
            "compile_us": round(compile_us, 1),
            "fused_counts": dict(sorted(plan.fused_counts.items())),
            "interp_op_counts": dict(sorted(plan.interp_op_counts().items())),
            "batch8_us": round(us_b, 1),
            "batch8_us_per_sample": round(us_b / 8, 1),
            "requant": {
                **rq,
                "fp32_requant_us": round(us_fp32, 1),
                "epilogue_speedup": round(us_fp32 / us_comp, 3),
            },
            # cross-segment fusion census: fused boundary segments, integer
            # carriers, inter-segment bytes saved per call vs fp32
            "fusion": plan.fusion_stats(),
        }
    return rows, records


def run(cases=None) -> list[str]:
    return run_detailed(cases)[0]


def check_conv_lowering(name: str) -> dict:
    """Regression gate: ``name`` must compile with its convs on the kernel
    tier (≥1 conv segment fused, 0 Conv nodes on the interpreted fallback).
    Returns a record; record["ok"] is the verdict."""
    plan = compile_graph(zoo.ZOO[name]())
    conv_fused = sum(v for k, v in plan.fused_counts.items()
                     if k.startswith("quant_conv"))
    conv_interp = plan.interp_op_counts().get("Conv", 0)
    return {
        "model": name,
        "conv_segments_fused": conv_fused,
        "conv_nodes_interpreted": conv_interp,
        "fused_counts": dict(sorted(plan.fused_counts.items())),
        "ok": conv_fused >= 1 and conv_interp == 0,
    }


def check_grouped_lowering(name: str) -> dict:
    """Regression gate for the grouped/depthwise kernel tier.

    ``name`` (MobileNet-w4a4 in CI) must compile with

      * every Conv fused on the kernel tier (0 interpreted),
      * every group>1 conv on the dedicated grouped/depthwise kernels —
        0 block-diagonal dense carriers left for grouped layers,
      * a positive reclaimed-MAC count whose analysis-side mirror agrees:
        the cost report's MAC total (true I/g·kH·kW contraction, no
        O(groups) inflation) must sit below the dense-equivalent
        block-diagonal number by exactly the plan's reclaimed MACs.
    """
    from repro.analysis import infer_cost

    g = zoo.ZOO[name]()
    plan = compile_graph(g)
    n_convs = sum(1 for n in plan.graph.nodes if n.op_type == "Conv")
    conv_fused = sum(v for k, v in plan.fused_counts.items()
                     if k.startswith("quant_conv"))
    conv_interp = plan.interp_op_counts().get("Conv", 0)
    stats = plan.grouped_conv_stats()
    report = infer_cost(plan.graph, ga=plan.analysis)
    macs_drop = report.dense_equiv_macs - report.macs
    return {
        "model": name,
        "conv_nodes": n_convs,
        "conv_segments_fused": conv_fused,
        "conv_nodes_interpreted": conv_interp,
        "fused_counts": dict(sorted(plan.fused_counts.items())),
        "grouped_stats": stats,
        "report_macs": report.macs,
        "dense_equiv_macs": report.dense_equiv_macs,
        "ok": (conv_fused == n_convs and conv_interp == 0 and
               stats["grouped_segments"] >= 1 and
               stats["block_diagonal_grouped"] == 0 and
               stats["reclaimed_macs"] > 0 and
               macs_drop == stats["reclaimed_macs"]),
    }


def check_integer_requant(name: str) -> dict:
    """Regression gate for the integer-only dyadic requantization path.

    ``name`` (TFC-w1a1 / CNV-w1a1 in CI) must compile with **every**
    kernel-family segment on the int32 multiplier+shift epilogue —
    coverage 1.0, zero fp32-requant segments, and a positive count of
    eliminated fp32 epilogue ops.  The zoo's scales are exact powers of
    two by construction, so anything less means the dyadic detection or
    the exactness proof regressed.
    """
    plan = compile_graph(zoo.ZOO[name]())
    stats = plan.requant_stats()
    return {
        "model": name,
        "requant_stats": stats,
        "fused_counts": dict(sorted(plan.fused_counts.items())),
        "ok": (stats["kernel_segments"] >= 1 and
               stats["fp32_segments"] == 0 and
               stats["coverage"] == 1.0 and
               stats["fp32_ops_eliminated"] > 0),
    }


def check_fusion(name: str) -> dict:
    """Regression gate for cross-segment fusion with integer carriers.

    ``name`` (CNV-w1a1 in CI) must compile with

      * ≥1 fused boundary segment (an epilogue-absorbed MaxPool / Add /
        Concat successor) and ≥1 integer inter-segment carrier,
      * a positive inter-segment bytes-saved count (the HBM round-trips
        the integer carriers eliminate vs fp32 boundaries),
      * **zero** interpreted MaxPool and Add nodes — CNV's pooling and any
        residual adds must ride inside fused segments, not the fallback,
      * the fused plan bit-identical to the same graph compiled with
        ``use_fusion=False`` on a fixed input (fusion is a layout
        optimization, never a numerics change).
    """
    g = zoo.ZOO[name]()
    plan = compile_graph(g)
    fs = plan.fusion_stats()
    interp = plan.interp_op_counts()
    shape = tuple(1 if d is None else int(d) for d in plan.graph.inputs[0].shape)
    x = np.random.RandomState(0).randn(*shape).astype(np.float32)
    out = plan.graph.output_names[0]
    plan_off = compile_graph(g, use_fusion=False)
    bit_exact = bool(np.array_equal(
        np.asarray(plan({"x": x})[out]),
        np.asarray(plan_off({"x": x})[out])))
    return {
        "model": name,
        "fusion_stats": fs,
        "interp_op_counts": dict(sorted(interp.items())),
        "bit_exact_vs_unfused": bit_exact,
        "ok": (fs["fused_boundary_segments"] >= 1 and
               fs["integer_boundaries"] >= 1 and
               fs["boundary_bytes_saved"] > 0 and
               interp.get("MaxPool", 0) == 0 and
               interp.get("Add", 0) == 0 and
               bit_exact),
    }


def check_tune(name: str, cache_dir=None, repeats: int = 5) -> dict:
    """Regression gate for the kernel autotuner + tune cache (repro.tune).

    Three invariants, measured on ``name``:

      * **tuned is never slower**: the plan compiled with ``tune="search"``
        must reach ≥ 90% of the default-blocks plan's throughput
        (interleaved best-of timing; the search always times the default
        tiling too, so a real regression means the selection logic broke —
        the 10% headroom only absorbs timing noise);
      * **warm cache re-tunes nothing**: a second ``compile_graph`` with
        ``tune="cached"`` against the same cache dir must answer every
        kernel segment from the graph manifest — 0 searches, 0 misses,
        1 graph-manifest hit, every kernel segment tuned;
      * **warm plan re-traces nothing**: two same-shape calls of the warm
        plan must leave ``trace_count`` at 1 (one trace for the new shape,
        zero retraces — the persistent-compilation-cache story only holds
        if the plan itself is shape-stable).

    Returns a record; record["ok"] is the verdict.
    """
    g = zoo.ZOO[name]()
    shape = tuple(1 if d is None else int(d) for d in g.inputs[0].shape)
    x = np.random.RandomState(0).randn(*shape).astype(np.float32)

    plan_def = compile_graph(g)
    plan_tuned = compile_graph(g, tune="search", tune_cache_dir=cache_dir)
    out = plan_def.graph.output_names[0]
    s_def, s_tuned = time_fns(
        [lambda: jax.block_until_ready(plan_def({"x": x})[out]),
         lambda: jax.block_until_ready(plan_tuned({"x": x})[out])],
        repeats)
    speedup = s_def / s_tuned if s_tuned else float("inf")
    search_stats = plan_tuned.tuning_stats()

    # warm-cache recompile: everything answered from the manifest
    plan_warm = compile_graph(g, tune="cached", tune_cache_dir=cache_dir)
    warm = plan_warm.tuning_stats()
    warm_ok = (warm.get("searched", 0) == 0 and warm.get("misses", 0) == 0
               and warm.get("graph_hit", 0) == 1 and
               warm["kernel_segments"] >= 1 and
               warm["tuned_segments"] == warm["kernel_segments"])
    jax.block_until_ready(plan_warm({"x": x})[out])
    jax.block_until_ready(plan_warm({"x": x})[out])
    trace_ok = plan_warm.trace_count == 1

    return {
        "model": name,
        "default_us": round(s_def * 1e6, 1),
        "tuned_us": round(s_tuned * 1e6, 1),
        "tuned_speedup": round(speedup, 3),
        "search_stats": search_stats,
        "warm_stats": warm,
        "warm_trace_count": plan_warm.trace_count,
        "ok": bool(speedup >= 0.90 and warm_ok and trace_ok),
    }


def main(argv=None) -> int:
    """CLI used by the CI smoke job: exit 0 iff every row was produced and
    every ``--check-conv`` / ``--check-grouped`` /
    ``--check-integer-requant`` / ``--check-fusion`` / ``--check-tune``
    gate holds.

        python benchmarks/bench_compile.py [--quick] [--json PATH]
                                           [--check-conv MODEL ...]
                                           [--check-grouped MODEL ...]
                                           [--check-integer-requant MODEL ...]
                                           [--check-fusion MODEL ...]
                                           [--check-tune MODEL ...]
                                           [--tune-cache-dir PATH]
                                           [--metrics-snapshot PATH]
    """
    import argparse
    import json

    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="TFC-only cases (fast enough for CI smoke)")
    ap.add_argument("--json", metavar="PATH",
                    help="write machine-readable results (per-model wall "
                         "time, speedup, fused-segment counts) to PATH")
    ap.add_argument("--check-conv", metavar="MODEL", action="append",
                    default=[],
                    help="assert MODEL compiles with ≥1 conv segment fused "
                         "and 0 interpreted Conv nodes (repeatable)")
    ap.add_argument("--check-grouped", metavar="MODEL", action="append",
                    default=[],
                    help="assert MODEL's grouped convs all lower onto the "
                         "grouped/depthwise kernels (0 block-diagonal "
                         "carriers) and the cost report's MAC count drops "
                         "vs the dense-equivalent number (repeatable)")
    ap.add_argument("--check-integer-requant", metavar="MODEL",
                    action="append", default=[],
                    help="assert MODEL compiles with every kernel segment "
                         "on the int32 dyadic requant epilogue (coverage "
                         "1.0, 0 fp32-requant segments; repeatable)")
    ap.add_argument("--check-fusion", metavar="MODEL", action="append",
                    default=[],
                    help="assert MODEL compiles with ≥1 fused boundary "
                         "segment on an integer inter-segment carrier, "
                         "positive boundary bytes-saved, 0 interpreted "
                         "MaxPool/Add nodes, and bit-identical output vs "
                         "use_fusion=False (repeatable)")
    ap.add_argument("--check-tune", metavar="MODEL", action="append",
                    default=[],
                    help="assert the autotuned plan reaches ≥90%% of the "
                         "default-blocks throughput and a warm-cache "
                         "recompile answers every segment with 0 searches "
                         "and 0 retraces (repeatable)")
    ap.add_argument("--tune-cache-dir", metavar="PATH", default=None,
                    help="tune-cache root for --check-tune (default "
                         "$REPRO_TUNE_CACHE_DIR or <checkout>/.cache/tune); "
                         "CI persists this dir across runs")
    ap.add_argument("--metrics-snapshot", metavar="PATH", default=None,
                    help="dump the process-wide obs metrics registry "
                         "(compile gauges, tune hit/miss counters) to PATH "
                         "as JSON")
    args = ap.parse_args(argv)
    cases = QUICK_CASES if args.quick else CASES
    rows, records = run_detailed(cases)
    for row in rows:
        print(row)

    ok = len(rows) == 4 * len(cases)
    checks, grouped_checks, requant_checks = [], [], []
    fusion_checks, tune_checks = [], []

    def _check_tune(name):
        return check_tune(name, cache_dir=args.tune_cache_dir)

    for name, check, bucket, tag in (
            [(n, check_conv_lowering, checks, "check_conv")
             for n in args.check_conv] +
            [(n, check_grouped_lowering, grouped_checks, "check_grouped")
             for n in args.check_grouped] +
            [(n, check_integer_requant, requant_checks,
              "check_integer_requant")
             for n in args.check_integer_requant] +
            [(n, check_fusion, fusion_checks, "check_fusion")
             for n in args.check_fusion] +
            [(n, _check_tune, tune_checks, "check_tune")
             for n in args.check_tune]):
        # a failing/crashing check must still reach the JSON artifact —
        # that's exactly when CI needs the diagnostics
        try:
            c = check(name)
        except Exception as e:  # noqa: BLE001  (unknown model, compile crash)
            c = {"model": name, "ok": False, "error": f"{type(e).__name__}: {e}"}
        bucket.append(c)
        verdict = "OK" if c["ok"] else "FAIL"
        if c.get("error"):
            detail = c["error"]
        elif tag == "check_integer_requant":
            rs = c["requant_stats"]
            detail = (f"coverage={rs['coverage']:.2f};"
                      f"int32={rs['int32_segments']}/"
                      f"{rs['kernel_segments']};"
                      f"fp32_ops_eliminated={rs['fp32_ops_eliminated']}")
        elif tag == "check_fusion":
            fsn = c["fusion_stats"]
            io = c["interp_op_counts"]
            detail = (f"fused_boundaries={fsn['fused_boundary_segments']};"
                      f"int_carriers={fsn['integer_boundaries']};"
                      f"packed={fsn['packed_boundaries']};"
                      f"bytes_saved={fsn['boundary_bytes_saved']};"
                      f"interp_pool={io.get('MaxPool', 0)};"
                      f"interp_add={io.get('Add', 0)};"
                      f"bit_exact={c['bit_exact_vs_unfused']}")
        elif tag == "check_tune":
            ws = c["warm_stats"]
            detail = (f"speedup={c['tuned_speedup']:.2f}x;"
                      f"warm_tuned={ws['tuned_segments']}/"
                      f"{ws['kernel_segments']};"
                      f"warm_searched={ws.get('searched', 0)};"
                      f"warm_trace_count={c['warm_trace_count']}")
        else:
            detail = f"interp_convs={c['conv_nodes_interpreted']}"
            if tag == "check_grouped":
                gs = c["grouped_stats"]
                detail += (f";block_diag={gs['block_diagonal_grouped']};"
                           f"reclaimed_macs={gs['reclaimed_macs']};"
                           f"macs={c['report_macs']}<"
                           f"dense_equiv={c['dense_equiv_macs']}")
        print(f"{tag}/{name},{c.get('conv_segments_fused', 0)},"
              f"{detail};{verdict}")
        ok = ok and c["ok"]

    if args.json:
        payload = {"models": records}
        if checks:
            payload["conv_checks"] = checks
        if grouped_checks:
            payload["grouped_checks"] = grouped_checks
        if requant_checks:
            payload["integer_requant_checks"] = requant_checks
        if fusion_checks:
            payload["fusion_checks"] = fusion_checks
        if tune_checks:
            payload["tune_checks"] = tune_checks
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"# wrote {args.json}")

    if args.metrics_snapshot:
        from repro.obs import default_registry
        with open(args.metrics_snapshot, "w") as f:
            f.write(default_registry().to_json(indent=2, sort_keys=True))
        print(f"# wrote {args.metrics_snapshot}")
    return 0 if ok else 1


if __name__ == "__main__":        # PYTHONPATH=src python benchmarks/bench_compile.py
    raise SystemExit(main())
