"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

Read with ``jax.profiler.ProfileData`` alone.  From the device planes
(``/device:TPU:<n>``), the op line gives each operation's interval on the
chip; from the host plane, the harness's own ``TraceAnnotation`` spans
(``window``, ``engine_call``, ``submit``, ``fetch``) give the traced
window and what the host was doing in each idle gap.

``reduce`` returns, over the ``window`` span:

* ``window_s``: its length; ``busy_s``: the union of op intervals,
  averaged over the cell's chips;
* ``pallas_s``: device time of Pallas (Mosaic) calls, averaged over the
  chips, and ``pallas_calls``: one entry per call with its device
  seconds, operations and bytes (``kernel_cost``), for the roofline;
* ``breakdown``: the ten ops with most device time (by HLO instruction
  and result type) and the ten host activities with most device idle
  time under them, each as [name, seconds] per chip.

A device op event's name is its HLO instruction's text; Pallas calls are
found by joining the instruction name to the ``tpu_custom_call``
instructions of the compiled program (``hlo_text``).
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
HOST_SPANS = ("window", "engine_call", "submit", "fetch")
# an HLO shape such as s8[256,576]{1,0:T(8,128)(4,1)} or f32[]
_SHAPE = re.compile(r"\b(pred|[suf]\d+|bf16)\[([\d,]*)\]")
_BITS = {"pred": 8, "bf16": 16}


def newest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def op_name(event_name: str) -> str:
    """The HLO instruction name of a device op event, whose name is the
    instruction's text (``%quant_matmul.1 = f32[...] custom-call(...)``)."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def short_name(event_name: str) -> str:
    """Instruction name and result type, without layouts: a readable
    breakdown key (``copy.625 s8[256,12,12,64,9]``)."""
    head, _, rest = event_name.partition(" = ")
    shape = _SHAPE.search(rest.split(" ", 1)[0]) if rest else None
    name = head.strip().lstrip("%")
    return f"{name} {shape.group(0)}" if shape else name


def union_length(intervals, lo: float, hi: float) -> tuple:
    """(covered length, gaps) of ``intervals`` clipped to [lo, hi]."""
    covered, gaps, cursor = 0.0, [], lo
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if a > cursor:
            gaps.append((cursor, a))
        if b > cursor:
            covered += b - max(a, cursor)
            cursor = b
    if cursor < hi:
        gaps.append((cursor, hi))
    return covered, gaps


def shape_bytes(text: str) -> list:
    """[(dtype, dims, bytes)] for every array shape written in ``text``."""
    out = []
    for dtype, dims in _SHAPE.findall(text):
        d = tuple(int(x) for x in dims.split(",") if x)
        n = 1
        for x in d:
            n *= x
        bits = _BITS.get(dtype, int(re.sub(r"\D", "", dtype) or 32))
        out.append((dtype, d, n * bits // 8))
    return out


def hlo_custom_calls(hlo_text: str) -> dict:
    """{instruction name: its HLO line} for every Pallas (Mosaic) call."""
    out = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            name = line.split("=", 1)[0].strip().lstrip("%")
            out[name] = line
    return out


def kernel_cost(hlo_line: str) -> tuple:
    """(ops, bytes) of one Pallas custom call from its HLO instruction.

    Bytes: every operand and result array the instruction declares (the
    operand shapes are its ``operand_layout_constraints``).  Ops: 2*M*K*N
    for a matmul-family kernel, read from the shapes: the result is
    (M, N) or (G, M, N); the activation operands share its M and their
    trailing dims add up to K (the int4 kernels take x as two halves, so
    K is counted unpacked).  A depthwise call, (T, M, C) taps against
    (T, C) weights into (M, C), counts 2*T*M*C; an elementwise call 0.
    """
    head, _, tail = hlo_line.partition(" custom-call(")
    results = shape_bytes(head.split("=", 1)[-1])
    constraints = re.search(
        r"operand_layout_constraints=\{((?:[^{}]|\{[^{}]*\})*)\}", tail)
    operands = shape_bytes(constraints.group(1) if constraints else
                           tail.split(")", 1)[0])
    nbytes = sum(b for _, _, b in results + operands)
    if not results:
        return 0, nbytes
    out = results[0][1]
    ops = 0
    if len(out) in (2, 3):
        m, n = out[-2], out[-1]
        groups = out[0] if len(out) == 3 else 1
        xs = [d for _, d, _ in operands
              if len(d) == len(out) and d[-2] == m and d[:-2] == out[:-2]]
        ws = [d for _, d, _ in operands
              if len(d) == len(out) and d[-1] == n and d[-2] != m]
        taps = [d for _, d, _ in operands if len(d) == 3 and d[1:] == out]
        if xs and ws:
            ops = 2 * groups * m * sum(d[-1] for d in xs) * n
        elif len(out) == 2 and taps:
            ops = 2 * taps[0][0] * m * n
    return ops, nbytes


def _call_text(event_name: str, hlo_calls: dict) -> str | None:
    """The HLO line of a Pallas call, from the compiled program's text
    (joined by instruction name) or from the event's own name where that
    carries the target; None for any other op."""
    line = hlo_calls.get(op_name(event_name))
    if line is not None:
        return line
    if 'custom_call_target="tpu_custom_call"' in event_name:
        return event_name
    return None


def host_spans(pd) -> list:
    """(name, start_ns, end_ns) of the harness's annotations."""
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in HOST_SPANS:
                    spans.append((e.name, e.start_ns, e.start_ns +
                                  e.duration_ns))
    return spans


class GapLabels:
    """Labels a device idle gap with the innermost harness annotation
    covering its midpoint.  Spans of one name never overlap (each comes
    from one thread, in sequence), so a bisection per name finds it."""

    def __init__(self, spans: list):
        by_name = defaultdict(list)
        for name, a, b in spans:
            by_name[name].append((a, b))
        self._spans = {name: (sorted(v), [a for a, _ in sorted(v)])
                       for name, v in by_name.items()}

    def __call__(self, gap: tuple) -> str:
        mid = (gap[0] + gap[1]) / 2
        best = None
        for name, (ivs, starts) in self._spans.items():
            i = bisect.bisect_right(starts, mid) - 1
            if i >= 0 and ivs[i][1] >= mid:
                length = ivs[i][1] - ivs[i][0]
                if best is None or length < best[0]:
                    best = (length, name)
        return best[1] if best else "outside"


def reduce(pd, chips: int, hlo_text: str = "") -> dict:
    """The numbers above from a loaded trace; ``hlo_text`` is the compiled
    program whose Pallas calls the device ops are joined to by name."""
    hlo_calls = hlo_custom_calls(hlo_text)
    spans = host_spans(pd)
    windows = [(a, b) for name, a, b in spans if name == "window"]
    planes = sorted((p for p in pd.planes if DEVICE_PLANE.match(p.name)),
                    key=lambda p: int(DEVICE_PLANE.match(p.name).group(1)))
    planes = planes[:chips]
    if not planes:
        raise ValueError("the trace holds no TPU device plane")
    per_dev = []
    for plane in planes:
        lines = {ln.name: ln for ln in plane.lines}
        line = lines.get(OP_LINE)
        if line is None:
            raise ValueError(f"{plane.name} has no {OP_LINE!r} line "
                             f"(lines: {sorted(lines)})")
        per_dev.append([(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events])
    if windows:
        lo, hi = windows[0]
    else:
        lo = min(ev[1] for evs in per_dev for ev in evs)
        hi = max(ev[2] for evs in per_dev for ev in evs)
    label = GapLabels(spans)
    busy, pallas_ns = 0.0, 0.0
    op_time = defaultdict(float)
    idle = defaultdict(float)
    calls = []
    for evs in per_dev:
        inside = [ev for ev in evs if ev[2] > lo and ev[1] < hi]
        covered, gaps = union_length([(a, b) for _, a, b in inside], lo, hi)
        busy += covered
        for gap in gaps:
            idle[label(gap)] += gap[1] - gap[0]
        costs = {}
        for name, a, b in inside:
            dur = min(b, hi) - max(a, lo)
            op_time[name] += dur
            if name not in costs:
                text = _call_text(name, hlo_calls)
                costs[name] = None if text is None else kernel_cost(text)
            if costs[name] is not None:
                pallas_ns += dur
                ops, nbytes = costs[name]
                calls.append({"name": op_name(name), "s": dur * 1e-9,
                              "ops": ops, "bytes": nbytes})
    n = len(per_dev)

    def top(d, key=lambda k: k):
        merged = defaultdict(float)
        for k, v in d.items():
            merged[key(k)] += v
        return [[k, v * 1e-9 / n] for k, v in
                sorted(merged.items(), key=lambda kv: -kv[1])[:10]]
    return {"window_s": (hi - lo) * 1e-9, "busy_s": busy * 1e-9 / n,
            "pallas_s": pallas_ns * 1e-9 / n, "pallas_calls": calls,
            "devices": n,
            "breakdown": {"device_ops": top(op_time, short_name),
                          "idle_gaps": top(idle)}}


def reduce_dir(trace_dir: str, chips: int, hlo_text: str = "") -> dict:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(newest_xplane(trace_dir)), chips,
                  hlo_text)
