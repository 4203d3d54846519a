"""Closed-loop offline scoring: one caller, ``call_batch`` images per call.

Traffic keys: ``max_batch`` (the engine's slot), ``call_batch`` (images per
``CompiledGraphEngine.__call__``), ``pool_calls`` (distinct input batches
drawn from the seed, sent in turn) and, optionally, ``mesh``
(``CompiledGraphEngine(mesh=...)``: ``"auto"`` runs one data-parallel plan
that splits each slot over every local chip, the weights replicated;
without it the plan runs on one device).

The caller hands the engine host numpy batches and waits for each result,
as a scoring job does.  ``images_per_s`` is every image returned inside
the window over the window: from the first call's start to the last
call's return.
"""
from __future__ import annotations

import time

import numpy as np

from bench import inputs
from repro.serve import CompiledGraphEngine


class Driver:
    def __init__(self, graph, traffic: dict, seed: int, input_shape,
                 tracer=None):
        self.call = int(traffic["call_batch"])
        t0 = time.monotonic()
        self.engine = CompiledGraphEngine(
            graph, max_batch=int(traffic["max_batch"]), report_cost=False,
            tracer=tracer, mesh=traffic.get("mesh"))
        t1 = time.monotonic()
        self.pool = [inputs.images(seed, 1 + i, self.call, input_shape)
                     for i in range(int(traffic["pool_calls"]))]
        self.setup_parts = {"engine_s": t1 - t0,
                            "inputs_s": time.monotonic() - t1}
        self.calls: list = []
        self.outs: list = []

    @property
    def plan(self):
        return self.engine.plan

    def warm(self) -> None:
        """One call compiles every shape a window call uses (the slot
        program, the slices and the pad); the second runs warm."""
        for i, x in enumerate(self.pool[:2]):
            t0 = time.monotonic()
            self.engine(x)
            self.setup_parts[f"warm_call{i}_s"] = time.monotonic() - t0

    def run(self, seconds: float, annotate) -> dict:
        calls, outs = [], []
        t0 = time.monotonic()
        i = 0
        while time.monotonic() - t0 < seconds:
            k = i % len(self.pool)
            t_call = time.monotonic()
            with annotate("engine_call"):
                y = self.engine(self.pool[k])
            calls.append((k, t_call, time.monotonic()))
            outs.append(y)
            i += 1
        window = calls[-1][2] - t0
        images = len(calls) * self.call
        self.calls, self.outs = calls, outs
        return {"window_s": window, "images": images,
                "attempted": len(calls), "failed": 0,
                "end_to_end": {"images_per_s": images / window},
                "host_lines": {"calls": len(calls),
                               "call_ms_median": 1e3 * float(np.median(
                                   [c[2] - c[1] for c in calls])),
                               "call_ms_max": 1e3 * max(
                                   c[2] - c[1] for c in calls)}}

    def answers(self):
        """(distinct inputs, index of each answer's input, answers)."""
        xs = np.concatenate(self.pool)
        index = np.concatenate([np.arange(k * self.call, (k + 1) * self.call)
                                for k, _, _ in self.calls])
        return xs, index, np.concatenate(self.outs), 0

    def release(self) -> None:
        self.engine = None
