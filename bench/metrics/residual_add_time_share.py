"""The plan's residual joins over device busy time, in % (traced run).

Device time of the plan segments scoped ``segNN_eltwise_add`` (each
residual ``Add [-> Relu] [-> Quant]`` join with its carriers' unpack and
repack; ``segment_s`` of ``bench/trace_scopes.py``) over the union of all
op intervals.  None where no op carries a segment scope, or where the
plan has no join, as for a network without residuals.
"""
import re

from bench import trace_scopes

_JOIN = re.compile(r"^seg\d+_eltwise_add$")


def read(record: dict):
    r = trace_scopes.for_record(record)
    if r is None or not r["busy_s"]:
        return None
    joins = [s for k, s in r["segment_s"].items() if _JOIN.match(k)]
    if not joins:
        return None
    return 100.0 * sum(joins) / r["busy_s"]
