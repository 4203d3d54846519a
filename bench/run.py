"""Chip benchmark of the compiled QONNX serving path.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, configurations and metrics are listed in ``BENCHMARK.json``; each
is defined by files under ``bench/`` (see ``bench/spec.py``).  Runs only
on a TPU with at least the cell's chips, and exits non-zero with no
result otherwise.  JAX's persistent compilation cache is kept at
``<checkout>/.cache/jax``, so only the first run in a checkout compiles.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True,
                    help="seed of the weights, inputs and arrivals")
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: profile the window, report per-layer metrics")
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".cache",
                                                           "jax")
    sys.path[0:1] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    harness.run(args, t_start=T_START)
    return 0


if __name__ == "__main__":
    sys.exit(main())
