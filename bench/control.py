"""Readings that the correctness limits are set from (run on the chip).

    python3 bench/control.py --workload <cell> --seeds 1,2,... \
        --control-seeds 7,8,9 [--controls int4,bf16] [--seconds 2] \
        [--out <file.json>]

For each of ``--seeds``: the cell's program, built from that seed, serves
a short window of the cell's own traffic at its own sizes; every answer is
compared with the plain reference (``check.logit_gap``).  The largest of
these gaps is the *lower reading*.  For each of ``--control-seeds`` and
each of ``--controls`` (by default the configuration's ``check.control``):
that lower-precision copy of the reference (``bench/reference/forward.py``),
put in the program's place, answers a call of the cell's inputs; the
smallest of its gaps is that control's *upper reading*.  The limit in
the configuration lies between the two (``PERF.md`` gives the readings).
One process reads every seed, so the program compiles once.  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _seeds(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]


def read_program(bench, cell, seed: int, seconds: float) -> dict:
    from bench import check, layers as L
    from bench.graph import build_graph
    from bench.reference.forward import Reference
    cfg = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    layers = bench.layers(cfg)
    codes = L.draw_weights(layers, seed)
    graph = build_graph(cfg["name"], layers, codes, cfg["input_shape"])
    drv = bench.driver(traffic).Driver(graph, traffic, seed,
                                       cfg["input_shape"])
    drv.warm()
    rec = drv.run(seconds, lambda name: contextlib.nullcontext())
    xs, index, outs, missing = drv.answers()
    drv.release()
    del drv
    gc.collect()
    ref = Reference(layers, codes)
    want = ref(xs)
    gap = check.logit_gap(outs, want[index])
    return {"seed": seed, "program_gap": gap, "missing": missing,
            "answers": int(len(index)), "attempted": rec["attempted"],
            "failed": rec["failed"]}


def read_control(bench, cell, seed: int, control: str) -> dict:
    from bench import check, inputs, layers as L
    from bench.reference.forward import Reference
    cfg = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    layers = bench.layers(cfg)
    codes = L.draw_weights(layers, seed)
    n = int(traffic["call_batch"])
    xs = inputs.images(seed, 1, n, cfg["input_shape"])
    want = Reference(layers, codes)(xs)
    got = Reference(layers, codes, control=control)(xs)
    return {"seed": seed, "control": control,
            "control_gap": check.logit_gap(got, want),
            "answers": int(len(xs))}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--controls", default=None,
                    help="comma-separated controls (default: the "
                    "configuration's check.control)")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--out", default=None, help="also write the readings")
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".cache",
                                                           "jax")
    sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]
    from bench import spec
    from bench.harness import check_devices
    bench = spec.Bench()
    cell = bench.workload(args.workload)
    device = check_devices(int(cell["chips"]), require_tpu=True)
    check_cfg = bench.config(cell["config"])["check"]
    controls = (args.controls or check_cfg["control"]).split(",")
    rows = []
    for seed in args.seeds:
        t0 = time.monotonic()
        rows.append(read_program(bench, cell, seed, args.seconds))
        print(json.dumps(dict(rows[-1], s=time.monotonic() - t0)),
              flush=True)
    for control in controls:
        for seed in args.control_seeds:
            rows.append(read_control(bench, cell, seed, control))
            print(json.dumps(rows[-1]), flush=True)
    prog = [r["program_gap"] for r in rows if "program_gap" in r]
    upper = {c: min(r["control_gap"] for r in rows
                    if r.get("control") == c)
             for c in controls if args.control_seeds}
    summary = {"workload": args.workload, "device": device,
               "lower_reading": max(prog) if prog else None,
               "upper_readings": upper,
               "limit": check_cfg["logit_gap_limit"], "rows": rows}
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=1)
    print(json.dumps({k: v for k, v in summary.items() if k != "rows"}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
