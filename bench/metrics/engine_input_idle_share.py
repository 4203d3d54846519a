"""Device idle under the engine's input steps, in % of the traced window.

Idle inside the window under the engine's ``stage`` span (the caller's
batch to the device) or ``dispatch`` span (slicing, padding and enqueuing
every slot), each stretch going to the innermost span over it
(``engine_idle_ns`` of ``bench/trace_scopes.py``).  With
``engine_output_idle_share`` and the idle under neither it adds up to
``device_idle_share.offline``.  None where the program recorded no span.
"""
from bench import trace_scopes

STEPS = ("stage", "dispatch")


def read(record: dict):
    r = trace_scopes.for_record(record)
    if r is None or not r["engine_idle_ns"] or not r["window_s"]:
        return None
    return 100.0 * sum(r["engine_idle_ns"].get(s, 0.0) for s in STEPS) \
        * 1e-9 / r["window_s"]
