"""Device idle under the engine's output steps, in % of the traced window.

Idle inside the window under the engine's ``sync`` span (the trailing
``block_until_ready``) or ``fetch`` span (every slot's result to the host),
each stretch going to the innermost span over it (``engine_idle_ns`` of
``bench/trace_scopes.py``).  With ``engine_input_idle_share`` and the idle
under neither it adds up to ``device_idle_share.offline``.  None where the
program recorded no span.
"""
from bench import trace_scopes

STEPS = ("sync", "fetch")


def read(record: dict):
    r = trace_scopes.for_record(record)
    if r is None or not r["engine_idle_ns"] or not r["window_s"]:
        return None
    return 100.0 * sum(r["engine_idle_ns"].get(s, 0.0) for s in STEPS) \
        * 1e-9 / r["window_s"]
