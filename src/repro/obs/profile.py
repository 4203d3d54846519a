"""The shared best-of-N timing harness.

``time_fn`` / ``time_fns`` time host calls that end in
``block_until_ready``: the tune tier's candidate search and the
``benchmarks/`` scripts use them.  Where device time goes inside a plan
is read from a profiler trace instead (``bench/trace_scopes.py``: each
plan segment runs under its own named scope).
"""
from __future__ import annotations

import math
import time

__all__ = ["time_fn", "time_fns"]


def time_fn(fn, repeats: int = 5, *, warmup: int = 1) -> float:
    """Best-of-``repeats`` wall-clock seconds of ``fn()``.

    The one best-of-N timing harness every consumer shares
    (benchmarks/bench_compile, benchmarks/bench_serve, and the tune-tier
    candidate search).  Each call is forced with an explicit
    ``jax.block_until_ready`` so async dispatch can't leak compute out of
    the measurement; ``warmup`` unmeasured calls absorb trace +
    compile.  Best-of (not mean) because scheduling noise is strictly
    additive — the minimum is the least-contaminated estimate.
    """
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn())
    best = math.inf
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def time_fns(fns, repeats: int = 5, *, warmup: int = 1) -> list[float]:
    """Best-of-``repeats`` seconds for each fn, measured in *alternating*
    rounds so load/frequency drift during the run cannot bias one
    contestant — the fair way to compare candidates (bench_serve's
    pipelined-vs-sync gate, the autotuner's tiling search)."""
    import jax
    for fn in fns:
        for _ in range(warmup):
            jax.block_until_ready(fn())
    best = [math.inf] * len(fns)
    for _ in range(repeats):
        for i, fn in enumerate(fns):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            best[i] = min(best[i], time.perf_counter() - t0)
    return best
