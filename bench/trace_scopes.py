"""Put device idle time down to the engine's steps and device busy time
down to the plan's segments, from the program's own spans and scopes in a
profiler trace.

The engine records its steps as live spans (``ENGINE_SPANS``) that its
tracer also holds open as ``jax.profiler.TraceAnnotation``s, so they lie
on the host plane, on the device planes' clock.  The plan wraps each
segment in ``jax.named_scope("segNN_<kind>")`` and the kernels' activation
unfolding in ``im2col``; XLA keeps the scoped name as the instruction's
``op_name``, and the profiler records it as the ``tf_op`` stat of the op's
event metadata, keyed by program.  ``jax.profiler.ProfileData`` does not
expose event metadata, so ``xplane_op_names`` reads it from the file's
protobuf wire format.

``reduce`` returns, over the window and device gaps ``trace_reduce`` uses:

* ``window_s`` and ``busy_s``, as ``trace_reduce`` reads them;
* ``engine_idle_ns``: device idle cut at every boundary of the engine's
  spans and given piece by piece to the innermost span over it, or to
  ``outside``; by span name, in ns per chip.  Empty where the trace holds
  no engine span.
* ``segment_s``: device time per chip by what caused it: the plan
  segment (``segNN_kind``), ``outside_plan:<module>`` for the other
  programs (the engine's slicing and copies), or ``unattributed``.  The
  plan's programs are those with a scoped op.  An op without an
  ``op_name`` (XLA's own fusions and layout copies) takes its first
  operand's, one hop.  Empty where no op carries a segment scope.
* ``im2col_s``: device time per chip of plan ops under ``im2col``; None
  where no op carries a segment scope.
* ``breakdown``: ``segments`` (the ten largest of ``segment_s``) and
  ``idle_by_engine_span``, each as [name, seconds].

``for_record`` reads the traced run's own trace, which the harness leaves
in its trace directory, once per process for the readers of the engine
and plan layers, and adds the two breakdowns to the run's.  On a program
without the spans or scopes it reads nothing, and does not raise.
"""
from __future__ import annotations

import bisect
import functools
import math
import os
import re
from collections import defaultdict

from bench import trace_reduce

ENGINE_SPANS = ("call", "stage", "dispatch", "sync", "fetch", "flush")
MODULE_LINE = "XLA Modules"
_SEGMENT = re.compile(r"(?:^|/)(seg\d+_[A-Za-z0-9_]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_MODULE = re.compile(r"^(.*)\((\d+)\)$")


# -- the protobuf wire format of an XSpace, as far as event metadata ------

def _varint(buf: bytes, i: int) -> tuple:
    value, shift = 0, 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, i


def _fields(buf: bytes, lo: int = 0, hi: int | None = None):
    """(field number, value) of a message in ``buf[lo:hi]``: an int for a
    varint, a (start, end) slice for a length-delimited field."""
    i, hi = lo, len(buf) if hi is None else hi
    while i < hi:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield field, value


def _text(buf: bytes, span: tuple) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_values(buf: bytes, span: tuple):
    for field, value in _fields(buf, *span):
        if field == 2:                   # a map entry's value
            yield value


def _plane_op_names(buf: bytes, plane: tuple) -> tuple:
    """(plane name, {(program id, event name): tf_op})."""
    name, stat_meta, event_meta = "", [], []
    for field, value in _fields(buf, *plane):
        if field == 2:                   # XPlane.name
            name = _text(buf, value)
        elif field == 4:                 # XPlane.event_metadata
            event_meta.append(value)
        elif field == 5:                 # XPlane.stat_metadata
            stat_meta.append(value)
    stat_names = {}
    for entry in stat_meta:
        for value in _map_values(buf, entry):
            sid, sname = None, ""
            for f, v in _fields(buf, *value):
                if f == 1:
                    sid = v
                elif f == 2:
                    sname = _text(buf, v)
            stat_names[sid] = sname
    tf_op = {k for k, v in stat_names.items() if v == "tf_op"}
    program = {k for k, v in stat_names.items() if v == "program_id"}
    out = {}
    if not tf_op:
        return name, out
    for entry in event_meta:
        for meta in _map_values(buf, entry):
            ename, op, pid = None, None, None
            for f, v in _fields(buf, *meta):
                if f == 2:               # XEventMetadata.name
                    ename = _text(buf, v)
                elif f == 5:             # XEventMetadata.stats
                    sid, val = None, None
                    for sf, sv in _fields(buf, *v):
                        if sf == 1:
                            sid = sv
                        elif sf == 5:    # str_value
                            val = _text(buf, sv)
                        elif sf in (3, 4):   # uint64_value, int64_value
                            val = sv
                    if sid in tf_op:
                        op = val
                    elif sid in program:
                        pid = val
            if ename is not None and op:     # "<op_name>:<op type>"
                if ":" in op.rsplit("/", 1)[-1]:
                    op = op.rpartition(":")[0]
                out[(pid, ename)] = op
    return name, out


def xplane_op_names(buf: bytes) -> dict:
    """{device plane name: {(program id, op event name): op_name}} of a
    serialized ``.xplane.pb``."""
    out = {}
    for field, value in _fields(buf):
        if field == 1:                   # XSpace.planes
            name, ops = _plane_op_names(buf, value)
            if trace_reduce.DEVICE_PLANE.match(name):
                out[name] = ops
    return out


# -- engine steps ----------------------------------------------------------

def engine_spans(pd) -> list:
    """(name, start_ns, end_ns) of the engine's live spans."""
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name in ENGINE_SPANS]


def span_pieces(spans: list, lo: float, hi: float) -> list:
    """[(a, b, name)] covering [lo, hi]: the interval cut at every span
    boundary, each piece named for the innermost (shortest) span over it,
    or ``outside``."""
    edges = sorted([(a, 1, i) for i, (_, a, _) in enumerate(spans)] +
                   [(b, 0, i) for i, (_, _, b) in enumerate(spans)])
    active, pieces, cursor = set(), [], lo

    def innermost():
        if not active:
            return "outside"
        i = min(active, key=lambda i: spans[i][2] - spans[i][1])
        return spans[i][0]
    for t, starts, i in edges:
        t = min(max(t, lo), hi)
        if t > cursor:
            pieces.append((cursor, t, innermost()))
            cursor = t
        if starts:
            active.add(i)
        else:
            active.discard(i)
    if cursor < hi:
        pieces.append((cursor, hi, innermost()))
    return pieces


def split_gaps(gaps: list, pieces: list) -> dict:
    """{piece name: length of ``gaps`` under its pieces}; both sorted, and
    the pieces cover every gap."""
    out = defaultdict(float)
    j = 0
    for a, b in gaps:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < b:
            out[pieces[k][2]] += min(b, pieces[k][1]) - max(a, pieces[k][0])
            k += 1
    return out


# -- plan segments ---------------------------------------------------------

def module_intervals(plane) -> tuple:
    """(starts, [(start, end, module name, program id)]) of the programs
    run on a device plane, sorted; empty where it has no modules line."""
    mods = []
    for line in plane.lines:
        if line.name != MODULE_LINE:
            continue
        for e in line.events:
            m = _MODULE.match(e.name)
            name, pid = (m.group(1), int(m.group(2))) if m else (e.name, None)
            mods.append((e.start_ns, e.start_ns + e.duration_ns, name, pid))
    mods.sort()
    return [m[0] for m in mods], mods


def by_instruction(ops: dict) -> dict:
    """``ops`` ({(program id, op event name): op_name}) keyed by (program
    id, instruction name), for the operand hop."""
    return {(pid, trace_reduce.op_name(ename)): op
            for (pid, ename), op in ops.items()}


def op_scope(event_name: str, pid, ops: dict, by_instr: dict) -> str:
    """The op_name of a device op in program ``pid``, else its first
    operand's, else ''."""
    op = ops.get((pid, event_name))
    if op is None:
        _, _, rest = event_name.partition(" = ")
        operand = _OPERAND.search(rest)
        op = by_instr.get((pid, operand.group(1))) if operand else None
    return op or ""


def reduce(pd, chips: int, op_names: dict) -> dict:
    """The numbers above from a loaded trace and its ``xplane_op_names``."""
    planes = sorted((p for p in pd.planes
                     if trace_reduce.DEVICE_PLANE.match(p.name)),
                    key=lambda p: int(trace_reduce.DEVICE_PLANE
                                      .match(p.name).group(1)))[:chips]
    if not planes:
        raise ValueError("the trace holds no TPU device plane")
    per_dev = []
    for plane in planes:
        line = {ln.name: ln for ln in plane.lines}.get(trace_reduce.OP_LINE)
        if line is None:
            raise ValueError(f"{plane.name} has no {trace_reduce.OP_LINE!r} "
                             "line")
        per_dev.append([(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events])
    windows = [(a, b) for name, a, b in trace_reduce.host_spans(pd)
               if name == "window"]
    if windows:
        lo, hi = windows[0]
    else:
        lo = min(ev[1] for evs in per_dev for ev in evs)
        hi = max(ev[2] for evs in per_dev for ev in evs)
    pieces = span_pieces(engine_spans(pd), lo, hi)
    if all(name == "outside" for _, _, name in pieces):
        pieces = None                    # the program recorded no span
    busy = 0.0
    engine_idle = defaultdict(float)
    segment_ns = defaultdict(float)
    im2col_ns = 0.0
    for plane, evs in zip(planes, per_dev):
        inside = [ev for ev in evs if ev[2] > lo and ev[1] < hi]
        covered, gaps = trace_reduce.union_length(
            [(a, b) for _, a, b in inside], lo, hi)
        busy += covered
        if pieces is not None:
            for name, ns in split_gaps(gaps, pieces).items():
                engine_idle[name] += ns
        ops = op_names.get(plane.name, {})
        by_instr = by_instruction(ops)
        scoped = {pid for (pid, _), op in ops.items() if _SEGMENT.search(op)}
        starts, mods = module_intervals(plane)
        for name, a, b in inside:
            dur = min(b, hi) - max(a, lo)
            i = bisect.bisect_right(starts, a) - 1
            mod = mods[i] if i >= 0 and mods[i][1] >= a else None
            if mod is not None and mod[3] not in scoped:
                segment_ns[f"outside_plan:{mod[2]}"] += dur
                continue
            op = op_scope(name, mod[3], ops, by_instr) if mod else ""
            seg = _SEGMENT.search(op)
            segment_ns[seg.group(1) if seg else "unattributed"] += dur
            if "/im2col/" in op:
                im2col_ns += dur
    n = len(per_dev)
    has_scopes = any(k.startswith("seg") for k in segment_ns)
    if not has_scopes:
        segment_ns.clear()

    def top(d):
        return [[k, v * 1e-9 / n] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {"window_s": (hi - lo) * 1e-9, "busy_s": busy * 1e-9 / n,
            "engine_idle_ns": {k: v / n for k, v in engine_idle.items()},
            "segment_s": {k: v * 1e-9 / n for k, v in segment_ns.items()},
            "im2col_s": im2col_ns * 1e-9 / n if has_scopes else None,
            "breakdown": {"segments": top(segment_ns),
                          "idle_by_engine_span": top(engine_idle)}}


@functools.lru_cache(maxsize=2)
def reduce_file(path: str, chips: int, mtime_ns: int) -> dict:
    """``reduce`` of one ``.xplane.pb`` (``mtime_ns`` keys the cache)."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        buf = f.read()
    return reduce(ProfileData.from_serialized_xspace(buf), chips,
                  xplane_op_names(buf))


def run_xplane() -> str | None:
    """The newest ``.xplane.pb`` under the harness's trace directory: the
    one the traced run in this process has just written."""
    from bench import harness, spec
    try:
        return trace_reduce.newest_xplane(os.path.join(spec.ROOT,
                                                       harness.TRACE_DIR))
    except FileNotFoundError:
        return None


def for_record(record: dict) -> dict | None:
    """``reduce`` of the run's trace, or None where there is none or it is
    not the one ``record["trace"]`` was read from (another window).  Adds
    ``segments`` and ``idle_by_engine_span`` to the run's breakdown where
    they hold anything."""
    trace = record.get("trace") or {}
    path = run_xplane() if trace.get("window_s") else None
    if path is None:
        return None
    r = reduce_file(path, int(record.get("chips", 1)),
                    os.stat(path).st_mtime_ns)
    if not (math.isclose(r["window_s"], trace["window_s"], rel_tol=1e-9)
            and math.isclose(r["busy_s"], trace.get("busy_s", math.nan),
                             rel_tol=1e-9)):
        return None
    breakdown = trace.get("breakdown")
    if isinstance(breakdown, dict):
        for key, value in r["breakdown"].items():
            if value:
                breakdown.setdefault(key, value)
    return r
